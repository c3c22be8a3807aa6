"""The graph ops a sparse-expert decoder trains through
(``rms_normalization_op``, ``rotary_op``, ``flash_attention_op`` with
fewer key/value heads and a window, ``router_op``, ``held_experts_op``)
and the model built from them (``hetu_tpu/models/sparse_decoder.py``):
every op's value and every gradient through ``ht.Executor`` against
``jax.grad`` of plain ``jax.numpy`` written out here, in float32 at
small widths that keep the shape of the thing (8 query heads on 2
key/value heads, and a group of 7; window 8 at S = 32; 8 experts top-3
with 4 held from the third on); the banded grouped-query kernels
interpreted against a masked dense attention at an S that has tiles
wholly behind the band; the whole graph's loss, scores and every
parameter's gradient against ``benchmark/reference/smallthinker_moe.py``;
two Adam steps; the device counter against a count by hand; and the
share test: the four shares' expert sums add up to the uncut layer's
and the four vocabulary slices' logits concatenate to the whole head's.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.models import SparseDecoderLMHeadModel
from hetu_tpu.ops import attention, moe
from hetu_tpu.ops import pallas_attention as pk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.families.smallthinker_moe import model_config  # noqa: E402
from benchmark.reference import smallthinker_moe as reference  # noqa: E402

S, VOCAB, HIDDEN, WIDTH = 32, 96, 64, 32
EXPERTS, TOP_K = 8, 3


def run_graph(outputs, feeds):
    """The values of ``outputs`` (graph nodes) under ``feeds`` ({node:
    array}) through one float32 ``ht.Executor``."""
    ex = ht.Executor(list(outputs))
    return [np.asarray(o.asnumpy()) for o in ex.run(feed_dict=feeds)]


def op_and_grads(build, arrays, upstream):
    """``build(*nodes) -> node``: the op's value and the gradients of
    ``sum(value * upstream)`` to every float input, through the graph's
    own gradient ops (``ht.gradients`` with ``upstream`` as the seed)."""
    nodes = [ht.Variable(f"in{i}", trainable=False,
                         dtype=np.asarray(a).dtype.type)
             for i, a in enumerate(arrays)]
    seed = ht.Variable("seed", trainable=False)
    out = build(*nodes)
    wrt = [n for n, a in zip(nodes, arrays)
           if np.issubdtype(np.asarray(a).dtype, np.floating)]
    grads = ht.gradients(out, wrt, insert_grad=seed)
    feeds = dict(zip(nodes, arrays))
    feeds[seed] = upstream
    got = run_graph([out] + grads, feeds)
    return got[0], got[1:]


def plain_and_grads(fn, arrays, upstream):
    floats = [i for i, a in enumerate(arrays)
              if np.issubdtype(np.asarray(a).dtype, np.floating)]
    arrays = [jnp.asarray(a) for a in arrays]

    def scalar(*fl):
        full = list(arrays)
        for i, a in zip(floats, fl):
            full[i] = a
        return jnp.sum(fn(*full) * upstream)

    value = fn(*arrays)
    grads = jax.grad(scalar, argnums=tuple(range(len(floats))))(
        *[arrays[i] for i in floats])
    return np.asarray(value), [np.asarray(g) for g in grads]


def close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, \
        (float(np.abs(got - want).max()), scale)


def check(build, plain, arrays, seed=0):
    shape = jax.eval_shape(plain, *arrays).shape
    upstream = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    want, want_grads = plain_and_grads(plain, arrays, upstream)
    got, got_grads = op_and_grads(build, arrays, upstream)
    close(got, want)
    assert len(got_grads) == len(want_grads)
    for g, w in zip(got_grads, want_grads):
        close(g, w)


# -- RMS norm and rotary positions -------------------------------------------

@pytest.mark.parametrize("shape", [(2, S, HIDDEN), (24, 40)])
def test_rms_norm_value_and_gradients(shape):
    rng = np.random.RandomState(1)
    x = rng.randn(*shape).astype(np.float32)
    scale = (1.0 + 0.3 * rng.randn(shape[-1])).astype(np.float32)

    def plain(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * scale

    check(lambda x, s: ht.rms_normalization_op(x, s, eps=1e-6), plain,
          [x, scale])


def _plain_rotary(x, heads, theta):
    b, s, width = x.shape
    d = width // heads
    x = x.reshape(b, s, heads, d)
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           -1).reshape(b, s, width)


@pytest.mark.parametrize("heads", [8, 2])
def test_rotary_value_and_gradient(heads):
    x = np.random.RandomState(2).randn(2, S, heads * 16).astype(np.float32)
    check(lambda x: ht.rotary_op(x, heads, 1.5e6),
          lambda x: _plain_rotary(x, heads, 1.5e6), [x])


def test_rotary_turns_a_pair_by_its_position():
    """Dimension i pairs with i + D/2; position 0 is not turned."""
    x = np.zeros((1, 4, 8), np.float32)
    x[..., 0] = 1.0
    (got,) = run_graph(
        [ht.rotary_op(v := ht.Variable("x", trainable=False), 1, 10000.0)],
        {v: x})
    np.testing.assert_allclose(got[0, 0], x[0, 0], atol=1e-7)
    np.testing.assert_allclose(got[0, 3, 0], np.cos(3.0), atol=1e-6)
    np.testing.assert_allclose(got[0, 3, 4], np.sin(3.0), atol=1e-6)


# -- the router --------------------------------------------------------------

def _plain_router(x, w, k):
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    picked, experts = jax.lax.top_k(logits, k)
    return jax.nn.softmax(picked, -1), experts


def test_router_weights_picks_and_gradients():
    rng = np.random.RandomState(3)
    x = rng.randn(2, S, HIDDEN).astype(np.float32)
    w = (0.3 * rng.randn(HIDDEN, EXPERTS)).astype(np.float32)
    check(lambda x, w: ht.router_op(x, w, TOP_K),
          lambda x, w: _plain_router(x, w, TOP_K)[0], [x, w])
    xn, wn = (ht.Variable(n, trainable=False) for n in ("x", "w"))
    router = ht.router_op(xn, wn, TOP_K)
    weights, picks = run_graph([router, ht.router_picks_op(router)],
                               {xn: x, wn: w})
    want_w, want_p = _plain_router(jnp.asarray(x), jnp.asarray(w), TOP_K)
    assert picks.dtype == np.int32
    np.testing.assert_array_equal(picks, np.asarray(want_p))
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    # the same numbers as a softmax over all 64 renormalised over the k
    full = jax.nn.softmax(jnp.asarray(x) @ jnp.asarray(w), -1)
    chosen = jnp.take_along_axis(full, want_p, -1)
    close(weights, chosen / chosen.sum(-1, keepdims=True))


def test_router_reads_the_float32_master_under_mixed_precision():
    """A bfloat16 executor: the logits come from the float32 master of
    the router's weights, not from their bfloat16 working copy."""
    rng = np.random.RandomState(4)
    x = rng.randn(1, S, HIDDEN).astype(np.float32)
    w = ht.Variable("router_w", value=(0.3 * rng.randn(
        HIDDEN, EXPERTS)).astype(np.float32))
    xn = ht.Variable("x", trainable=False)
    router = ht.router_op(xn, w, TOP_K)
    ex = ht.Executor([router, ht.router_picks_op(router)],
                     dtype=jnp.bfloat16)
    weights, picks = (np.asarray(o.asnumpy())
                      for o in ex.run(feed_dict={xn: x}))
    held = jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
    want_w, want_p = _plain_router(held, jnp.asarray(w.tensor_value), TOP_K)
    assert weights.dtype == np.float32
    np.testing.assert_array_equal(picks, np.asarray(want_p))
    close(weights, want_w, 1e-6)


# -- attention: fewer key/value heads, a band --------------------------------

def _dense_attention(q, k, v, heads, groups, window):
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    q4 = q.reshape(b, s, heads, d)
    k4, v4 = (jnp.repeat(t.reshape(b, s, groups, d), heads // groups, 2)
              for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q4, k4) / np.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i if window is None else (j <= i) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v4).reshape(b, s, -1)


def _qkv(b, s, heads, groups, d, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randn(b, s, n * d).astype(np.float32)
            for n in (heads, groups, groups)]


@pytest.mark.parametrize("heads,groups", [(8, 2), (7, 1), (4, 4)])
@pytest.mark.parametrize("window", [8, None])
def test_grouped_window_attention_value_and_gradients(heads, groups, window):
    arrays = _qkv(2, S, heads, groups, 16)
    check(lambda q, k, v: ht.flash_attention_op(
        q, k, v, sm_scale=0.25, causal=True, num_heads=heads,
        num_kv_heads=groups, window=window),
        lambda q, k, v: _dense_attention(q, k, v, heads, groups, window),
        arrays)


def test_grouped_or_banded_attention_refuses_other_forms():
    q = ht.Variable("q", trainable=False)
    with pytest.raises(ValueError, match="causal"):
        ht.flash_attention_op(q, q, q, num_heads=8, num_kv_heads=2)
    with pytest.raises(ValueError, match="causal"):
        ht.flash_attention_op(q, num_heads=8, causal=True, window=8)
    with pytest.raises(ValueError, match="split"):
        ht.flash_attention_op(q, q, q, num_heads=8, num_kv_heads=3,
                              causal=True)


@pytest.fixture
def kernels(monkeypatch):
    """Both kinds of kernel interpreted, chosen as on a TPU."""
    monkeypatch.setattr(pk, "INTERPRET", True)
    monkeypatch.setattr(moe, "INTERPRET", True)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)


@pytest.fixture
def plain_passes(monkeypatch):
    """Serving's entry to the held-extent passes without its ``jax.jit``:
    the jitted one keeps a trace a shape, which would outlive a test's
    patched tiles and buffers, and hides the loops' trip counts."""
    monkeypatch.setattr(moe, "_held_extent_passes_once",
                        moe._held_extent_passes)


class _Training:
    """What an op's ``compute`` needs of a training step's context."""
    training = True
    config = None
    master_params = None

    def __init__(self):
        self.cache = {}

    def get_state(self, key, default=None):
        return default


# S = 1024 at tiles of 256: with a window of 300 the tiles (2, 0), (3, 0)
# and (3, 1) lie wholly behind the band. ``rows`` bounds a region's side:
# 256 makes four regions a side, so the loop, the edge regions and their
# branches all run (window 512 = two regions is the cell's own ratio).
@pytest.mark.parametrize("heads,groups,window,rows", [
    (4, 2, 300, None), (7, 1, None, None), (4, 2, 300, 256),
    (4, 2, 512, 256), (2, 2, 300, 256)])
def test_banded_grouped_kernels_against_masked_dense_attention(
        kernels, monkeypatch, heads, groups, window, rows):
    if rows:
        monkeypatch.setattr(pk, "_REGION_ROWS", rows)
    s, d = 1024, 128
    q, k, v = (jnp.asarray(a) for a in _qkv(1, s, heads, groups, d, 6))
    dy = jnp.asarray(np.random.RandomState(7).randn(1, s, heads * d),
                     jnp.float32)
    walk = pk.tile_walk_counts(s, 256, 256, True, window)
    if window:
        assert walk["tiles_visited"] < 10       # of the diagonal's ten
    op = ht.flash_attention_op(
        *(ht.Variable(n, trainable=False) for n in "qkv"),
        sm_scale=d ** -0.5, causal=True, num_heads=heads,
        num_kv_heads=groups, window=window)
    grad_ops = op.gradient(ht.Variable("dy", trainable=False))
    ectx = _Training()
    out = op.compute([q, k, v], ectx)
    assert ("flash_res", op.id) in ectx.cache       # the fused backward
    grads = [g.compute([q, k, v, dy], ectx) for g in grad_ops]
    want, vjp = jax.vjp(lambda q, k, v: _dense_attention(
        q, k, v, heads, groups, window), q, k, v)
    close(out, want, 1e-5)
    for got, wanted in zip(grads, vjp(dy)):
        assert got.shape == wanted.shape
        close(got, wanted, 1e-5)


def test_kernel_events_carry_their_own_names(kernels):
    """One name for the grouped-query forward and one for its backward,
    with the band and without; the equal-heads calls keep theirs."""
    def names(heads, groups, window):
        lay = pk.TokenMajor(heads, 128, kv_heads=groups)
        sd = jax.ShapeDtypeStruct
        q = sd((1, 256, heads * 128), jnp.float32)
        k = sd((1, 256, (groups or heads) * 128), jnp.float32)
        lse = sd((1, heads, 1, 256), jnp.float32)
        fwd = str(jax.make_jaxpr(lambda q, k: pk.flash_attention_with_lse(
            q, k, k, None, 0.1, True, True, lay, window=window))(q, k))
        bwd = str(jax.make_jaxpr(lambda q, k, lse: pk.flash_attention_bwd(
            q, k, k, None, q, lse, q, 0.1, True, True, lay,
            window=window))(q, k, lse))
        return fwd, bwd

    for (heads, groups, window), (f, b) in {
            (4, 2, None): ("hetu_flash_gqa_fwd", "hetu_flash_gqa_bwd"),
            (4, 2, 100): ("hetu_flash_gqa_window_fwd",
                          "hetu_flash_gqa_window_bwd"),
            (4, None, 100): ("hetu_flash_window", "hetu_flash_window_bwd"),
            (4, None, None): ("_flash_attention_jit",
                              "_flash_attention_bwd_jit")}.items():
        fwd, bwd = names(heads, groups, window)
        assert f"name={f}\n" in fwd or f"name={f} " in fwd, fwd[:400]
        assert f"name={b}\n" in bwd or f"name={b} " in bwd, bwd[:400]


# -- the held experts --------------------------------------------------------

def _plain_experts(x, weights, picks, w_in, w_out, first, act=jax.nn.relu):
    """A dense loop over the held experts: every token through each."""
    out = jnp.zeros(x.shape, jnp.float32)
    width = w_in.shape[-1] // 2
    for e in range(w_in.shape[0]):
        share = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        h = x @ w_in[e]
        out = out + share[..., None] * (
            (act(h[..., :width]) * h[..., width:]) @ w_out[e])
    return out


def _expert_case(routing, hidden=HIDDEN, width=WIDTH, held=4, first=2,
                 tokens=(2, S), seed=8):
    rng = np.random.RandomState(seed)
    x = rng.randn(*tokens, hidden).astype(np.float32)
    if routing == "random":
        picks = np.stack([rng.permutation(EXPERTS)[:TOP_K]
                          for _ in range(tokens[0] * tokens[1])])
    elif routing == "all_to_one":       # every token's first pick: expert 3
        picks = np.tile(np.array([3, 0, 7]), (tokens[0] * tokens[1], 1))
    elif routing == "all_held":         # every pick one of experts 2 .. 5
        picks = np.tile(np.array([5, 2, 3]), (tokens[0] * tokens[1], 1))
    else:                               # "none_held": experts 0, 1, 6, 7
        picks = np.tile(np.array([0, 7, 1]), (tokens[0] * tokens[1], 1))
    picks = picks.reshape(*tokens, TOP_K).astype(np.int32)
    weights = rng.rand(*tokens, TOP_K).astype(np.float32)
    weights /= weights.sum(-1, keepdims=True)
    w_in = (0.2 * rng.randn(held, hidden, 2 * width)).astype(np.float32)
    w_out = (0.2 * rng.randn(held, width, hidden)).astype(np.float32)
    return [x, weights, picks, w_in, w_out], first


@pytest.mark.parametrize("routing", ["random", "all_to_one", "none_held"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_held_experts_value_and_gradients(routing, activation):
    arrays, first = _expert_case(routing)
    act = moe.ACTIVATIONS[activation]
    check(lambda x, w, p, w_in, w_out: ht.held_experts_op(
        x, w, p, w_in, w_out, first=first, activation=activation),
        lambda x, w, p, w_in, w_out: _plain_experts(
            x, w, p, w_in, w_out, first, act), arrays)
    if routing == "none_held":
        got, grads = op_and_grads(
            lambda x, w, p, w_in, w_out: ht.held_experts_op(
                x, w, p, w_in, w_out, first=first, activation=activation),
            arrays, np.ones(arrays[0].shape, np.float32))
        assert not got.any() and not any(g.any() for g in grads)


@pytest.mark.parametrize("routing", ["random", "all_to_one"])
def test_held_experts_kernels_interpreted(kernels, routing):
    """The three grouped-matmul kernels (whole-lane widths, the rows
    padded to the kernel's tile) give what the ragged products give."""
    arrays, first = _expert_case(routing, hidden=128, width=128,
                                 tokens=(1, 40))
    dy = np.random.RandomState(9).randn(1, 40, 128).astype(np.float32)
    out, grads = _op_and_packed_gradients(arrays, first, dy)
    values = [jnp.asarray(a) for a in arrays]
    want, vjp = jax.vjp(lambda x, w, w_in, w_out: _plain_experts(
        x, w, values[2], w_in, w_out, first),
        values[0], values[1], values[3], values[4])
    close(out, want, 1e-5)
    for got, wanted in zip(grads, vjp(jnp.asarray(dy))):
        close(got, wanted, 1e-5)


def _whole_array_serving(x, picks, weights, valid, w_in, w_out, first,
                         act=jax.nn.silu):
    """Serving's ``held_experts`` as it stood while every pass ran over
    ALL ``T x k`` sorted rows, kept here as the plain reference: a
    padded token's pairs in the last group, both ragged products with
    that group's rows zeroed, the way back a gather of every pair and a
    float32 sum over ``k`` in pick order."""
    t, k = picks.shape
    held_n, width = w_in.shape[0], w_in.shape[-1] // 2
    local = picks - first
    held = (local >= 0) & (local < held_n) & valid[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)
    sizes = jnp.zeros(held_n + 1, jnp.int32).at[group].add(1)
    order = jnp.argsort(group, stable=True)
    here = jnp.arange(t * k)[:, None] < jnp.sum(sizes[:-1])
    xs = x[order // k]
    h = jnp.where(here, jax.lax.ragged_dot(xs, w_in, sizes[:-1]), 0.0)
    a = act(h[:, :width]) * h[:, width:]
    ys = jnp.where(here, jax.lax.ragged_dot(a, w_out, sizes[:-1]), 0.0)
    back = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    out = jnp.einsum("tk,tkh->th", jnp.where(held, weights, 0.0),
                     ys[back].reshape(t, k, -1).astype(jnp.float32))
    return out, sizes[:held_n], held


def _whole_array_experts(x, weights, picks, w_in, w_out, first,
                         act=jax.nn.relu):
    """The graph op as it stood before the held extent, kept here as the
    plain reference (``_whole_array_serving`` with no padded token),
    differentiated by ``jax.vjp``."""
    k = picks.shape[-1]
    out, _, _ = _whole_array_serving(
        x.reshape(-1, x.shape[-1]), picks.reshape(-1, k),
        weights.reshape(-1, k), jnp.ones(picks.size // k, bool), w_in,
        w_out, first, act)
    return out.reshape(x.shape)


def _op_and_packed_gradients(arrays, first, upstream, activation="relu"):
    """The op's value and its packed ``(dx, dweights, dw_in, dw_out)``
    by ``compute`` under a training context, no executor."""
    nodes = [ht.Variable(f"n{i}", trainable=False) for i in range(5)]
    op = ht.held_experts_op(*nodes, first=first, activation=activation)
    packed = op.gradient(ht.Variable("dy", trainable=False))[0].inputs[0]
    values = [jnp.asarray(a) for a in arrays]
    ectx = _Training()
    out = op.compute(values, ectx)
    return out, packed.compute(values + [jnp.asarray(upstream)], ectx)


def _nan_buffers(shapes, after):
    return tuple(jnp.full(s.shape, jnp.nan, s.dtype) for s in shapes)


def _check_op_against_the_whole_array_form(arrays, first,
                                           activation="relu"):
    """The op's value and packed gradients (``compute`` under a training
    context), finite and equal to ``_whole_array_experts``' by
    ``jax.vjp``; returns ``(value, gradients)``."""
    upstream = np.random.RandomState(9).randn(*arrays[0].shape).astype(
        np.float32)
    out, grads = _op_and_packed_gradients(arrays, first, upstream,
                                          activation)
    values = [jnp.asarray(a) for a in arrays]
    want, vjp = jax.vjp(lambda x, w, w_in, w_out: _whole_array_experts(
        x, w, values[2], w_in, w_out, first, moe.ACTIVATIONS[activation]),
        values[0], values[1], values[3], values[4])
    assert np.isfinite(np.asarray(out)).all()
    close(out, want, 1e-5)
    for got, wanted in zip(grads, vjp(jnp.asarray(upstream))):
        assert np.isfinite(np.asarray(got)).all()
        close(got, wanted, 1e-5)
    return out, grads


# (routing, the pass's row tile, interpreted kernels, poisoned buffers);
# 2 x 32 tokens x 3 picks = 192 sorted rows composed, 1 x 40 x 3 = 120
# padded to 128 under the kernels
EXTENT_CASES = {
    "no_pair_held": ("none_held", 16, False, False),
    "every_pair_held": ("all_held", 16, False, False),
    "uneven_routing": ("all_to_one", 16, False, False),
    "extent_no_multiple_of_the_tile": ("random", 16, False, False),
    "rows_no_multiple_of_the_tile": ("random", 80, False, False),
    "rows_under_one_tile": ("random", 2048, False, False),
    "kernels_interpreted": ("random", 48, True, False),
    "kernels_interpreted_every_pair_held": ("all_held", 48, True, False),
    "rows_past_the_extent_poisoned": ("random", 16, False, True),
    "rows_past_the_extent_poisoned_kernels": ("random", 48, True, True),
    "no_pair_held_poisoned_kernels": ("none_held", 48, True, True),
}


@pytest.mark.parametrize("case", sorted(EXTENT_CASES))
def test_held_experts_passes_run_to_the_held_extent(case, monkeypatch):
    """Value and all four gradients of the op, whose composed passes
    run the row tiles below the held extent, against the whole-array
    form. A poisoned case starts every carried buffer from NaN: a row
    past the extent that reached a result would show."""
    routing, tile, interpreted, poisoned = EXTENT_CASES[case]
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    if interpreted:
        monkeypatch.setattr(moe, "INTERPRET", True)
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
        arrays, first = _expert_case(routing, hidden=128, width=128,
                                     tokens=(1, 40))
    else:
        arrays, first = _expert_case(routing)
    if poisoned:
        monkeypatch.setattr(moe, "_fresh", _nan_buffers)
    picks = arrays[2]
    held = int(((picks >= first) & (picks < first + 4)).sum())
    if case == "extent_no_multiple_of_the_tile":
        assert held % tile and held > tile
    _check_op_against_the_whole_array_form(arrays, first)


def _serving_case(routing, interpreted, padded):
    """``held_experts``' arguments from ``_expert_case``: one sequence of
    64 tokens (40 of whole-lane widths under the kernels); ``padded``
    makes every third token and the last five padding."""
    arrays, first = _expert_case(routing, hidden=128, width=128,
                                 tokens=(1, 40)) if interpreted \
        else _expert_case(routing, tokens=(1, 2 * S))
    x, weights, picks = (jnp.asarray(a[0]) for a in arrays[:3])
    tokens = x.shape[0]
    valid = (jnp.arange(tokens) % 3 != 1) & (jnp.arange(tokens) < tokens - 5)\
        if padded else jnp.ones(tokens, bool)
    return (x, picks, weights, valid, jnp.asarray(arrays[3]),
            jnp.asarray(arrays[4]), first)


# (routing, the passes' row tile, interpreted kernels, poisoned buffers,
# padded tokens); 64 tokens x 3 picks = 192 sorted rows composed, 40 x 3
# = 120 padded to 128 under the kernels: over every tile but the last
# case's, which is the border itself
SERVING_EXTENT_CASES = {
    "no_pair_held": ("none_held", 16, False, False, False),
    "every_pair_held": ("all_held", 16, False, False, False),
    "all_to_one_expert": ("all_to_one", 16, False, False, False),
    "extent_no_multiple_of_the_tile": ("random", 16, False, False, False),
    "rows_no_multiple_of_the_tile": ("random", 80, False, False, False),
    "padded_tokens": ("random", 16, False, False, True),
    "every_pair_held_but_the_padded": ("all_held", 16, False, True, True),
    "kernels_interpreted": ("random", 48, True, False, False),
    "kernels_interpreted_every_pair_held": ("all_held", 48, True, False,
                                            False),
    "rows_past_the_extent_poisoned": ("random", 16, False, True, False),
    "rows_past_the_extent_poisoned_kernels": ("random", 48, True, True,
                                              False),
    "no_pair_held_poisoned_kernels": ("none_held", 48, True, True, False),
    "padded_tokens_poisoned_kernels": ("random", 48, True, True, True),
    "one_tile_takes_the_whole_array_form": ("random", 192, False, True,
                                            True),
}


@pytest.mark.parametrize("case", sorted(SERVING_EXTENT_CASES))
def test_serving_held_experts_over_the_border_runs_to_the_held_extent(
        case, monkeypatch, plain_passes):
    """``held_experts`` (serving) with more sorted rows than one row
    tile runs the graph op's passes (``_held_extent_passes``): its
    float32 sums and its rows by expert against the whole-array form
    kept above, a token with no held pick and a padded token exactly 0,
    the two row loops' trip count ``ceil(held rows / tile)``. A poisoned
    case starts every buffer from NaN. At or under one tile no loop
    runs and no buffer is made: the whole-array form."""
    routing, tile, interpreted, poisoned, padded = SERVING_EXTENT_CASES[case]
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "TOKEN_TILE", 8)
    if interpreted:
        monkeypatch.setattr(moe, "INTERPRET", True)
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    made = []

    def fresh(shapes, after, real=moe._fresh):
        made.extend(shapes)
        return _nan_buffers(shapes, after) if poisoned \
            else real(shapes, after)

    monkeypatch.setattr(moe, "_fresh", fresh)
    trips, loop = [], jax.lax.fori_loop

    def counted(lower, upper, body, init):
        trips.append(upper)
        return loop(lower, upper, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", counted)
    args = _serving_case(routing, interpreted, padded)
    x, picks, _, valid = args[:4]
    got, rows = moe.held_experts(*args, activation="silu")
    monkeypatch.setattr(jax.lax, "fori_loop", loop)
    want, want_rows, held = _whole_array_serving(*args)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert np.isfinite(np.asarray(got)).all()
    close(got, want, 1e-5)
    assert np.asarray(rows).tolist() == np.asarray(want_rows).tolist()
    idle = ~np.asarray(held).any(axis=1)
    assert idle[~np.asarray(valid)].all()
    assert not np.asarray(got)[idle].any()
    n, sorted_rows = int(held.sum()), -(-picks.size // 128) * 128 \
        if interpreted else picks.size
    if case == "extent_no_multiple_of_the_tile":
        assert n % tile and n > tile
    if case == "rows_no_multiple_of_the_tile":
        assert sorted_rows % tile and n > tile
    if sorted_rows <= tile:
        assert case == "one_tile_takes_the_whole_array_form"
        assert not trips and not made
        return
    # the gather of the tokens and the activation, then the way back
    assert [int(t) for t in trips[:2]] == [-(-n // tile)] * 2
    assert int(trips[2]) == -(-int((~idle).sum()) // 8)
    # xs, h, act, ys by sorted row and the sums by token: nothing else
    assert [s.shape[0] for s in made] == [sorted_rows] * 4 + [x.shape[0]]


def test_serving_held_experts_at_the_modules_own_tiles():
    """Nothing patched: 1,024 tokens x 3 picks = 3,072 sorted rows, over
    ``ROW_TILE`` = 2,048, through the jitted entry a program's layers
    share; 512 tokens, 1,536 rows, the whole-array form. Both against
    the plain expression, every other token padding."""
    assert moe.ROW_TILE == 2048 and moe.TOKEN_TILE == 512
    for tokens in (1024, 512):
        arrays, first = _expert_case("random", tokens=(1, tokens))
        x, weights, picks = (jnp.asarray(a[0]) for a in arrays[:3])
        args = (x, picks, weights, jnp.arange(tokens) % 2 == 0,
                jnp.asarray(arrays[3]), jnp.asarray(arrays[4]), first)
        got, rows = jax.jit(moe.held_experts, static_argnums=6)(*args)
        want, want_rows, _ = _whole_array_serving(*args)
        close(got, want, 1e-5)
        assert np.asarray(rows).tolist() == np.asarray(want_rows).tolist()
        assert not np.asarray(got)[1::2].any()


@pytest.mark.parametrize("rows", ["over_the_border", "one_tile"])
def test_serving_held_experts_hands_the_products_out_over_the_border(
        rows, monkeypatch, plain_passes):
    """Over one row tile of sorted rows ``held_experts`` (serving) reads
    no row of a product past the held extent, and both products write
    into a buffer that starts with no value (``out``); at or under a
    tile it reads ``ys[back]`` past the extent and keeps the library's
    zeros there: no ``out``."""
    seen = []
    real = moe.grouped_matmul

    def recorded(lhs, rhs, group_sizes, out=None):
        if not isinstance(lhs, jax.core.Tracer):    # (``eval_shape``'s)
            seen.append(out)
        return real(lhs, rhs, group_sizes, out)

    monkeypatch.setattr(moe, "grouped_matmul", recorded)
    monkeypatch.setattr(moe, "ROW_TILE", S * TOP_K - 16
                        if rows == "over_the_border" else S * TOP_K)
    arrays, first = _expert_case("random", tokens=(1, S))
    x, w, p = (jnp.asarray(a[0]) for a in arrays[:3])
    moe.held_experts(x, p, w, jnp.ones(S, bool), jnp.asarray(arrays[3]),
                     jnp.asarray(arrays[4]), first, activation="relu")
    assert len(seen) == 2
    if rows == "one_tile":
        assert seen == [None, None]
    else:
        assert all(o is not None and o.shape[0] == S * TOP_K for o in seen)


# (routing, the passes' row tile, the way back's token tile, the gate's
# activation, interpreted kernels): 2 x 32 tokens x 3 picks = 192 pairs
# composed (1 x 40 tokens = 120 pairs padded to 128 under the kernels), 8
# experts of which 2 .. 5 are held
WAY_BACK_CASES = {
    "no_pair_held": ("none_held", 16, 8, "relu", False),
    "every_pair_held": ("all_held", 16, 8, "silu", False),
    "one_pick_of_every_token_held": ("all_to_one", 16, 8, "relu", False),
    "tokens_with_none_one_and_all_picks_held":
        ("random", 16, 8, "relu", False),
    "a_ranks_tokens_end_inside_a_tile": ("random", 16, 16, "silu", False),
    "one_token_a_tile": ("random", 16, 1, "silu", False),
    "tokens_no_multiple_of_the_tile": ("random", 80, 24, "silu", False),
    "tokens_under_one_tile": ("random", 2048, 512, "relu", False),
    "kernels_interpreted": ("random", 48, 16, "relu", True),
    "kernels_interpreted_every_pair_held": ("all_held", 48, 16, "silu", True),
    "kernels_interpreted_no_pair_held": ("none_held", 48, 8, "silu", True),
}


@pytest.mark.parametrize("case", sorted(WAY_BACK_CASES))
def test_the_way_back_visits_the_held_pairs(case, monkeypatch):
    """``moe._token_sums`` (a token's held rows summed from where they
    lie, over the tokens that hold a pair and no further) against the
    whole-array expression it replaced (a gather of all ``T x k`` rows
    and a weighted sum over ``k``), in both directions' form, and
    through it the op's value and four gradients. Every buffer starts
    from NaN, the four products' outputs among them: a row past the
    extent that the way back read would show in a result."""
    routing, row_tile, token_tile, activation, interpreted = \
        WAY_BACK_CASES[case]
    monkeypatch.setattr(moe, "ROW_TILE", row_tile)
    monkeypatch.setattr(moe, "TOKEN_TILE", token_tile)
    monkeypatch.setattr(moe, "_fresh", _nan_buffers)
    if interpreted:
        monkeypatch.setattr(moe, "INTERPRET", True)
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
        arrays, first = _expert_case(routing, hidden=128, width=128,
                                     tokens=(1, 40))
    else:
        arrays, first = _expert_case(routing)
    x, weights, picks = arrays[:3]
    hidden, k = x.shape[-1], TOP_K
    flat_picks = jnp.asarray(picks.reshape(-1, k))
    order, sizes, held, pairs = moe._sorted_pairs(
        flat_picks, jnp.ones(flat_picks.shape[0], bool), 4, first)
    back = moe._moved(jnp.arange(pairs, dtype=jnp.int32), order[:pairs])
    n = int(sizes[:-1].sum())
    count = np.asarray(held).sum(-1)
    if case == "tokens_with_none_one_and_all_picks_held":
        assert {0, 1, k} <= set(count.tolist())
    if case == "a_ranks_tokens_end_inside_a_tile":
        assert all(int((count > r).sum()) % token_tile for r in range(k))
    if case == "tokens_no_multiple_of_the_tile":
        assert len(count) % token_tile and (count > 0).sum() > token_tile
    # the products' rows: defined to the extent, NaN behind it
    rng = np.random.RandomState(11)
    rows = rng.randn(order.shape[0], hidden).astype(np.float32)
    poisoned = jnp.asarray(np.where(np.arange(len(rows))[:, None] < n,
                                    rows, np.nan))
    zeroed = np.where(np.arange(len(rows))[:, None] < n, rows, 0.0)
    coeff = jnp.where(held, jnp.asarray(weights.reshape(-1, k)), 0.0)
    for c in (coeff, None):
        got = moe._token_sums(poisoned, back, held, c, jnp.float32)
        want = jnp.einsum(
            "tk,tkh->th", held.astype(jnp.float32) if c is None else c,
            zeroed[np.asarray(back)].reshape(-1, k, hidden))
        assert np.isfinite(np.asarray(got)).all()
        close(got, want, 1e-6)
        # a token none of whose picks is held: exactly 0
        assert not np.asarray(got)[count == 0].any()
    # the op through it
    out, grads = _check_op_against_the_whole_array_form(arrays, first,
                                                        activation)
    if n == 0:
        assert not np.asarray(out).any() and not np.asarray(grads[0]).any()


@pytest.mark.parametrize("product", ["forward", "rows_grad"])
@pytest.mark.parametrize("out", ["absent", "given"])
def test_a_product_without_out_still_zeroes_the_rows_behind(kernels,
                                                            product, out):
    """Serving's call of the grouped products (no ``out``): the rows
    behind the held groups come back as zeros whatever ``lhs`` holds
    there, a NaN included. With ``out`` (the graph op's call) they keep
    what ``out`` held, and the held groups' rows are the same."""
    m, wide, held_n, n = 256, 128, 2, 100
    rng = np.random.RandomState(12)
    lhs = rng.randn(m, wide).astype(np.float32)
    lhs[n:] = np.nan
    rhs = jnp.asarray(0.1 * rng.randn(held_n, wide, wide), jnp.float32)
    sizes = jnp.asarray([60, 40, m - n], jnp.int32)
    fn = moe.grouped_matmul if product == "forward" \
        else moe.grouped_matmul_rows_grad
    kept = jnp.full((m, wide), 7.0, jnp.float32)
    got = np.asarray(fn(jnp.asarray(lhs), rhs, sizes) if out == "absent"
                     else fn(jnp.asarray(lhs), rhs, sizes, out=kept))
    assert (got[n:] == (0.0 if out == "absent" else 7.0)).all()
    group = np.repeat(np.arange(held_n), [60, 40])
    mats = np.asarray(rhs)[group]
    want = np.einsum("mk,mkn->mn" if product == "forward" else "mn,mkn->mk",
                     lhs[:n], mats)
    close(got[:n], want, 1e-5)


def test_held_experts_activation_is_an_argument_of_the_serving_function():
    arrays, first = _expert_case("random", tokens=(1, S))
    x, w, p, w_in, w_out = (jnp.asarray(a[0]) if a.ndim == 3 and i < 3
                            else jnp.asarray(a)
                            for i, a in enumerate(arrays))
    valid = jnp.ones(x.shape[0], bool)
    for name, act in moe.ACTIVATIONS.items():
        got, rows = moe.held_experts(x, p, w, valid, w_in, w_out, first,
                                     activation=name)
        close(got, _plain_experts(x, w, p, w_in, w_out, first, act))
    silu, _ = moe.held_experts(x, p, w, valid, w_in, w_out, first)
    close(silu, _plain_experts(x, w, p, w_in, w_out, first, jax.nn.silu))
    assert int(rows.sum()) == int(((p >= first) & (p < first + 4)).sum())


# -- the whole graph against the reference -----------------------------------

def tiny_config(first=2, held=4, vocab=VOCAB):
    """A configuration file's content at the test's widths."""
    return {"num_attention_heads": 8, "num_key_value_heads": 2,
            "head_dim": 16, "hidden_size": HIDDEN,
            "moe_ffn_hidden_size": WIDTH, "num_routed_experts": EXPERTS,
            "moe_num_primary_experts": held, "first_expert": first,
            "moe_num_active_primary_experts": TOP_K,
            "sliding_window_size": 8, "sliding_window_layout": [0, 1, 1, 1],
            "rope_layout": [0, 1, 1, 1], "rope_theta": 1.5e6,
            "rms_norm_eps": 1e-6, "num_hidden_layers": 4,
            "vocab_size": vocab, "assumed": {"initializer_std": 0.3}}


def batch(b=2, seed=10, vocab=VOCAB):
    ids = np.random.RandomState(seed).randint(0, vocab, (b, S)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((b, 1), -1, np.int32)], 1)
    return ids, labels


class Graph:
    def __init__(self, config, extra=(), **executor_kw):
        self.config = config
        self.model = SparseDecoderLMHeadModel(model_config(config))
        self.ids = ht.Variable("input_ids", trainable=False)
        self.labels = ht.Variable("labels", trainable=False)
        self.logits, loss = self.model(self.ids, self.labels, seq_len=S)
        self.loss = ht.reduce_mean_op(loss, [0, 1])
        self.groups = {"validate": [self.loss, self.logits],
                       "picks": list(self.model.picks)}
        self.groups.update(extra(self) if extra else {})
        self.executor = ht.Executor(self.groups, seed=3, **executor_kw)
        self.nodes = {n.name: n for n in
                      self.executor.config.placeholder_to_arr_map}

    def params(self):
        return {node.name: np.asarray(arr) for node, arr in
                self.executor.config.placeholder_to_arr_map.items()}

    def run(self, group, ids, labels=None):
        feeds = {self.ids: ids}
        if labels is not None:
            feeds[self.labels] = labels
        def value(o):       # the embedding's gradient comes as its rows
            held = getattr(o, "jax_array", o)
            return np.asarray(held.to_dense() if hasattr(held, "to_dense")
                              else o.asnumpy())

        return [None if o is None else value(o)
                for o in self.executor.run(group, feed_dict=feeds)]


@pytest.fixture(scope="module")
def graph_and_gradients():
    """The graph with one more group: the loss's gradient to EVERY
    parameter, by the graph's own gradient ops."""
    def extra(g):
        names = sorted(n.name for n in ht.executor.find_topo_sort([g.loss])
                       if getattr(n, "trainable", False))
        by_name = {n.name: n for n in ht.executor.find_topo_sort([g.loss])
                   if getattr(n, "trainable", False)}
        g.grad_names = names
        return {"grads": ht.gradients(g.loss,
                                      [by_name[n] for n in names])}
    return Graph(tiny_config(), extra)


def test_every_parameter_of_the_model_is_named_by_the_shapes_function(
        graph_and_gradients):
    g = graph_and_gradients
    from hetu_tpu.models.sparse_decoder import sparse_decoder_param_shapes
    shapes = sparse_decoder_param_shapes(model_config(g.config))
    assert sorted(shapes) == g.grad_names
    assert {k: v.shape for k, v in g.params().items()} == shapes
    assert shapes["sparse_h0_experts_gate_up"] == (4, HIDDEN, 2 * WIDTH)
    assert shapes["sparse_h0_router"] == (HIDDEN, EXPERTS)


def test_loss_and_scores_agree_with_the_reference(graph_and_gradients):
    g = graph_and_gradients
    ids, labels = batch()
    loss, logits = g.run("validate", ids, labels)
    picks = g.run("picks", ids)
    log = []
    want_loss, (want_logits,) = reference.loss_and_scores(
        g.params(), g.config, ids, labels, forced=picks, log=log.append)
    assert log[0]["rows_differing_by_layer"] == [0, 0, 0, 0]
    assert logits.dtype == np.float32
    assert abs(loss - want_loss) <= 1e-5 * abs(want_loss)
    close(logits, want_logits, 2e-5)
    free_loss, _ = reference.loss_and_scores(g.params(), g.config, ids,
                                             labels)
    assert free_loss == want_loss


def test_every_parameters_gradient_agrees_with_the_reference(
        graph_and_gradients):
    g = graph_and_gradients
    ids, labels = batch()
    grads = dict(zip(g.grad_names, g.run("grads", ids, labels)))
    params = {k: jnp.asarray(v) for k, v in g.params().items()}
    want = jax.grad(reference.loss_fn)(params, g.config, ids, labels)
    assert sorted(want) == sorted(grads)
    for name in g.grad_names:
        got = grads[name]
        assert np.abs(want[name]).max() > 0, name
        close(got, want[name], 1e-4)


def test_a_flipped_pick_beyond_the_margin_fails_the_comparison(
        graph_and_gradients):
    g = graph_and_gradients
    ids, labels = batch()
    picks = g.run("picks", ids)
    log = []
    flipped = [p.copy() for p in picks]
    own = set(flipped[1][0, 5].tolist())
    flipped[1][0, 5, 0] = next(e for e in range(EXPERTS) if e not in own)
    loss, _ = reference.loss_and_scores(g.params(), g.config, ids, labels,
                                        forced=flipped, log=log.append)
    assert log[0]["rows_differing_by_layer"] == [0, 1, 0, 0]
    assert log[0]["worst_margin_by_layer"][1] > reference.PICK_MARGIN
    assert np.isnan(loss)


def test_the_eight_bit_control_fails_the_tolerances(graph_and_gradients):
    """The reference with every matrix in 8 bits, on the same picks, is
    further from the sound one than the scores' tolerance allows (the
    loss hardly moves with precision: its limit is the harness's)."""
    from benchmark.harness import stats
    g = graph_and_gradients
    ids, labels = batch()
    picks = g.run("picks", ids)
    loss, (logits,) = reference.loss_and_scores(
        g.params(), g.config, ids, labels, forced=picks)
    bad_loss, (bad,) = reference.loss_and_scores(
        g.params(), g.config, ids, labels, forced=picks, control="all_8bit")
    assert stats.row_errors(bad, logits).max() > reference.OUTPUT_TOLERANCE
    assert np.median(stats.row_errors(bad, logits)) \
        > reference.OUTPUT_TOLERANCE
    assert np.isfinite(bad_loss) and bad_loss != loss


def test_two_adam_steps_reproduce_the_references_losses():
    config = tiny_config()
    lr = 1e-2
    g = Graph(config, lambda g: {"default": [
        g.loss, ht.optim.AdamOptimizer(learning_rate=lr).minimize(g.loss)]})
    ids, labels = batch()
    params = {k: jnp.asarray(v) for k, v in g.params().items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(p) for k, p in params.items()}
    want = []
    for t in range(1, 4):
        loss, grads = jax.value_and_grad(reference.loss_fn)(
            params, config, ids, labels)
        want.append(float(loss))
        for k in params:
            m[k] = 0.9 * m[k] + 0.1 * grads[k]
            v[k] = 0.999 * v[k] + 0.001 * grads[k] ** 2
            scale = lr * np.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
            params[k] = params[k] - scale * m[k] / (jnp.sqrt(v[k]) + 1e-7)
    got = [float(g.run("default", ids, labels)[0]) for _ in range(3)]
    assert got[2] < got[1] < got[0]
    np.testing.assert_allclose(got, want, rtol=5e-3)


def test_the_step_counts_rows_and_visits_on_the_device(monkeypatch):
    monkeypatch.setattr(moe, "ROW_TILE", 16)    # 192 sorted rows: 12 tiles
    config = tiny_config()
    g = Graph(config, lambda g: {"default": [
        g.loss, ht.optim.SGDOptimizer(0.0).minimize(g.loss)]})
    ids, labels = batch()
    assert g.executor.moe_counters() == []          # no step compiled yet
    picks = g.run("picks", ids)
    for _ in range(3):
        g.run("default", ids, labels)
    g.run("validate", ids, labels)                  # inference counts nothing
    counted = g.executor.moe_counters()
    assert len(counted) == 4
    for layer, c in zip(picks, counted):
        by_hand = [int((layer == 2 + e).sum()) for e in range(4)]
        assert c["moe_rows_by_expert"] == [3 * n for n in by_hand]
        assert c["moe_routed_rows"] == 3 * sum(by_hand)
        assert c["moe_expert_visits"] == 3 * sum(n > 0 for n in by_hand)
        assert c["moe_row_tiles"] == 3 * -(-sum(by_hand) // 16)
        assert c["moe_row_tiles_of"] == 3 * 12
        assert 0 < c["moe_row_tiles"] < c["moe_row_tiles_of"]
        # the ragged product computes the rows that landed and no other
        assert c["moe_kernel_rows"] == c["moe_routed_rows"]
        # and the composed weight gradient visits no row tile
        assert c["moe_dw_tiles"] == c["moe_dw_cut_tiles"] == 0
        # the way back reads the held pairs' rows, once a direction
        assert c["moe_back_rows"] == 2 * 3 * sum(by_hand)
        assert c["moe_back_rows_of"] == 2 * 3 * 192
        assert c["steps"] == 3
    # a state restored from before the way back's counters has none:
    # they read 0 and count from there
    for state in g.executor.state.values():
        if "moe_back_rows" in state:
            del state["moe_back_rows"], state["moe_back_rows_of"], \
                state["moe_kernel_rows"]
    assert all(c["moe_back_rows"] == 0 and c["moe_back_rows_of"] == 0
               and c["moe_kernel_rows"] == 0
               and c["steps"] == 3 for c in g.executor.moe_counters())
    g.run("default", ids, labels)
    for layer, c in zip(picks, g.executor.moe_counters()):
        assert c["moe_kernel_rows"] == int(
            ((layer >= 2) & (layer < 6)).sum())
        assert c["moe_back_rows"] == 2 * int(
            ((layer >= 2) & (layer < 6)).sum())
        assert c["moe_back_rows_of"] == 2 * 192 and c["steps"] == 4


class _Counting(_Training):
    """A training context that keeps ONE op's state, from zeros."""

    def __init__(self, op, input_shapes):
        super().__init__()
        self.new_state = {}
        self.state = {k: jnp.zeros(shape, jnp.int32) for k, shape
                      in op.state_shapes(input_shapes).items()}

    def get_state(self, key, default=None):
        return self.state

    def put_state(self, key, value):
        self.new_state[key] = value


def test_the_op_counts_the_rows_its_row_tiles_compute(kernels):
    """Under the kernels ``moe_kernel_rows`` is visits x ``tm``: every
    ``tm``-row tile that a held group with a row touches, from the op's
    own ``sizes`` on the device."""
    arrays, first = _expert_case("random", hidden=128, width=128,
                                 tokens=(1, 200))
    nodes = [ht.Variable(f"n{i}", trainable=False) for i in range(5)]
    op = ht.held_experts_op(*nodes, first=first)
    ectx = _Counting(op, [a.shape for a in arrays])
    op.compute([jnp.asarray(a) for a in arrays], ectx)
    counted = ectx.new_state[op]
    # 200 x 3 pairs padded to 640 sorted rows: the rule's row tile
    tm = moe._kernel_tiles("forward", 640, 128, 256, 4, True)[0]
    assert 640 // tm > 1
    landed = [int((arrays[2] == first + e).sum()) for e in range(4)]
    assert counted["moe_rows_by_expert"].tolist() == landed
    ends = np.cumsum(landed)
    visits = sum(-(-end // tm) - (end - n) // tm
                 for end, n in zip(ends, landed) if n)
    assert int(counted["moe_kernel_rows"]) == visits * tm > sum(landed)
    # a weight gradient walks the same visits; a group's edge cuts those
    # that do not lie wholly inside their group
    assert int(counted["moe_dw_tiles"]) == visits
    starts = ends - landed
    cut = sum(min(-(-end // tm) - start // tm,
                  int(start % tm != 0) + int(end % tm != 0))
              for start, end, n in zip(starts, ends, landed) if n)
    assert int(counted["moe_dw_cut_tiles"]) == cut > 0


def _hlo_computations(text):
    """``{computation: [(name, result type, opcode, [operand names],
    the line)]}`` of a lowered (not yet optimised) HLO module's text."""
    found, body = {}, None
    for line in text.split("\n"):
        if line.endswith("{") and not line.startswith(" "):
            body = found.setdefault(line.split()[-2], [])
        m = re.match(r"\s+(?:ROOT )?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\((.*?)\)(?:, |$)", line)
        if m and body is not None:
            body.append((m.group(1), m.group(2), m.group(3),
                         re.findall(r"[\w.\-]+", m.group(4)), line))
    return found


def _dims(type_text):
    return tuple(int(d) for d in
                 re.search(r"\[([\d,]*)\]", type_text).group(1).split(",")
                 if d)


def _loops_of(comps):
    """The computations that run inside a ``while`` body of a lowered
    module (``_hlo_computations``), however deep."""
    called = {c: {w for *_, line in instrs for w in re.findall(
        r"(?:body|to_apply|calls)=([\w.\-]+)", line)}
        for c, instrs in comps.items()}
    in_a_loop = {w for instrs in comps.values() for *_, op, _, line in instrs
                 if op == "while"
                 for w in re.findall(r"body=([\w.\-]+)", line)}
    grown = True
    while grown:
        more = {w for c in in_a_loop for w in called.get(c, ())} - in_a_loop
        in_a_loop |= more
        grown = bool(more)
    return in_a_loop


def test_no_gather_of_the_tokens_to_all_sorted_rows_outside_a_loop(
        monkeypatch):
    """In the lowered training step every gather of an expert layer
    that reads or makes ``[T x k, hidden]`` rows is a tile inside a
    ``while`` body, in both directions: ``flat[token]`` and
    ``dy[token]`` (from the ``[T, hidden]`` tokens, a row tile) and the
    way back's reads of the products' outputs (from ``[T x k, hidden]``,
    a token tile: ``_token_sums``). NO gather has a ``[T x k, hidden]``
    result. The one whole-array gather a direction that is left reads
    the ``[T, hidden]`` sums in token order. A refactor that puts a
    whole-array pass back fails here."""
    tile, token_tile = 16, 8
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "TOKEN_TILE", token_tile)
    g = Graph(tiny_config(), lambda g: {"default": [
        g.loss, ht.optim.SGDOptimizer(0.1).minimize(g.loss)]})
    ids, labels = batch()
    sub = g.executor.subexecutors["default"]
    feed = {g.ids: ids, g.labels: labels}
    step = sub.prepare(g.executor, feed)
    text = jax.jit(step).lower(*sub.trace_args(g.executor, feed)) \
        .compiler_ir(dialect="hlo").as_hlo_text()
    comps = _hlo_computations(text)
    in_a_loop = _loops_of(comps)
    tokens, rows = ids.size, ids.size * TOP_K
    tiled, way_back, token_order = [], [], []
    for comp, instrs in comps.items():
        types = {name: result for name, result, *_ in instrs}
        for name, result, opcode, operands, _ in instrs:
            if opcode != "gather":
                continue
            source, got = _dims(types[operands[0]]), _dims(result)
            assert got != (rows, HIDDEN), (comp, name)
            if source == (rows, HIDDEN):
                assert comp in in_a_loop and got == (token_tile, HIDDEN), \
                    (comp, name)
                way_back.append(name)
            if source == (tokens, HIDDEN) and got[1:] == (HIDDEN,):
                if comp in in_a_loop:
                    assert got[0] == tile, (comp, name)
                    tiled.append(name)
                else:
                    assert got[0] == tokens, (comp, name)
                    token_order.append(name)
    # a layer: flat[token] forward, flat[token] and dy[token] backward;
    # the way back and its token order, a direction each
    assert len(tiled) == 3 * 4 and len(way_back) == 2 * 4
    assert len(token_order) == 2 * 4
    # and no float32 [T, k, hidden] array anywhere
    assert not re.search(rf"f32\[{tokens},{TOP_K},{HIDDEN}\]", text)


@pytest.mark.parametrize("form", ["held_extent", "one_tile"])
@pytest.mark.parametrize("model", ["latent", "window"])
def test_a_prefill_pass_gathers_no_tokens_to_all_sorted_rows_outside_a_loop(
        model, form, monkeypatch, plain_passes):
    """The lowered 1 x 4,096-token prefill of the tiny latent-attention
    and window configurations (whole-lane widths, the grouped kernels
    interpreted; 2 picks a token: 8,192 sorted rows a pass) at a row
    tile of 1,024: every gather of an expert layer that makes sorted
    rows from the ``[T, hidden]`` tokens is a row tile inside a
    ``while`` body, the way back reads the products' outputs a token
    tile at a time inside its loops, NO gather has a ``[T x k, hidden]``
    result, no whole-array ``select`` stands behind a grouped kernel
    (``hetu_moe_experts``: the library's zero fill), and no float32 ``[T,
    k, hidden]`` array is anywhere. An expert layer has two loops over
    row tiles (the tokens' gather, the activation) and one over token
    tiles; their trip counts are
    ``test_serving_held_experts_over_the_border_runs_to_the_held_extent``'s.
    ``one_tile`` (the row tile the pass's 8,192 rows): the whole-array
    form, in which every one of those is there, so each check above can
    fail."""
    import test_latent_moe_serving as latent
    import test_window_moe_serving as window
    made = latent if model == "latent" else window
    tokens, hidden, k = 4096, 128, 2
    rows, tile, token_tile = tokens * k, 1024, 512
    monkeypatch.setattr(moe, "ROW_TILE", tile if form == "held_extent"
                        else rows)
    monkeypatch.setattr(moe, "TOKEN_TILE", token_tile)
    monkeypatch.setattr(moe, "INTERPRET", True)
    monkeypatch.setattr(pk, "INTERPRET", True)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    config = made.tiny()
    config.update(hidden_size=hidden, moe_intermediate_size=128,
                  max_position_embeddings=2 * tokens)
    expert_layers = 2 if model == "latent" else 3
    engine = made.engine_for(
        config, made.family.seeded_weights(config, 7), max_batch_size=1,
        max_len=tokens + 64, num_blocks=80, block_size=64)
    found = []

    def record(key, fn, *args):
        if key[0] == "prefill":
            assert key == ("prefill", 1, tokens)
            found.append(fn.lower(*args).compiler_ir(dialect="hlo")
                         .as_hlo_text())
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(fn, *args))

    engine._dispatch = record
    engine.warm_up((tokens, tokens), 1)
    engine.close()
    (text,) = found
    comps = _hlo_computations(text)
    in_a_loop = _loops_of(comps)
    whole, tiled, way_back, fills, row_loops = [], [], [], [], 0
    for comp, instrs in comps.items():
        types = {name: result for name, result, *_ in instrs}
        for name, result, opcode, operands, line in instrs:
            if result.startswith("("):
                continue
            got = _dims(result)
            # (``jnp.where`` lowers to a call of a jitted ``_where``)
            if comp.startswith(moe.KERNEL_NAME) and got[:1] == (rows,) \
                    and (opcode == "select" or "to_apply=_where" in line):
                fills.append(name)
            if opcode == "dynamic-update-slice" and comp in in_a_loop \
                    and got[:1] == (rows,) \
                    and _dims(types[operands[1]])[:1] == (tile,):
                row_loops += 1
            if opcode != "gather":
                continue
            source = _dims(types[operands[0]])
            if got == (rows, hidden):
                whole.append(name)
            if source == (rows, hidden) and comp in in_a_loop:
                assert got == (token_tile, hidden), (comp, name)
                way_back.append(name)
            if source == (tokens, hidden) and got == (tile, hidden) \
                    and comp in in_a_loop:
                tiled.append(name)
    wide = re.findall(rf"f32\[{tokens},{k},{hidden}\]", text)
    if form == "one_tile":
        # xs and ys[back] a layer, both products' fills, the float32 pairs
        assert len(whole) == 2 * expert_layers
        assert fills and wide and not tiled and not way_back
        return
    assert not whole and not fills and not wide
    assert len(tiled) == expert_layers and len(way_back) == expert_layers
    # the gather's tile and the activation's, written in place
    assert row_loops == 2 * expert_layers


# -- the share test ----------------------------------------------------------

def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """first = 0, 2, 4, 6 of 8, two held each, through the graph op:
    their sums add up to the reference's whole expert layer."""
    arrays, _ = _expert_case("random", held=EXPERTS, first=0)
    x, weights, picks, w_in, w_out = arrays
    whole = reference.held_experts(
        jnp.asarray(x.reshape(-1, HIDDEN)),
        jnp.asarray(picks.reshape(-1, TOP_K)),
        jnp.asarray(weights.reshape(-1, TOP_K)),
        jnp.asarray(w_in), jnp.asarray(w_out), 0).reshape(x.shape)
    nodes = [ht.Variable(f"n{i}", trainable=False) for i in range(3)]
    total = 0.0
    for first in (0, 2, 4, 6):
        w1 = ht.Variable(f"w_in{first}", trainable=False)
        w2 = ht.Variable(f"w_out{first}", trainable=False)
        (part,) = run_graph(
            [ht.held_experts_op(*nodes, w1, w2, first=first,
                                activation="relu")],
            {**dict(zip(nodes, (x, weights, picks))),
             w1: w_in[first:first + 2], w2: w_out[first:first + 2]})
        assert np.abs(part).max() > 0
        total = total + part
    close(total, whole, 2e-5)


def test_four_slices_of_the_vocabulary_concatenate_to_the_whole_head():
    """A sliced vocabulary is a smaller vocabulary: the head's columns
    cut in four give the four quarters of the whole head's logits."""
    g = Graph(tiny_config())
    ids, labels = batch()
    _, whole = g.run("validate", ids, labels)
    params = g.params()
    parts = []
    for n in range(4):
        cut = dict(params)
        cut["sparse_lm_head"] = params["sparse_lm_head"][
            :, n * VOCAB // 4:(n + 1) * VOCAB // 4]
        quarter = dict(tiny_config(), vocab_size=VOCAB // 4)
        _, (logits,) = reference.loss_and_scores(
            cut, quarter, ids, np.full_like(labels, -1))
        parts.append(logits)
    close(np.concatenate(parts, -1), whole, 2e-5)
