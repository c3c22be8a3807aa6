"""The flash kernels' token-major operand form (PR 38): q, k and v read
out of a qkv projection's own ``[B, S, 3H]`` rows (or three
``[B, S, H]`` arrays), whole lane blocks of heads a program (one at
S = 1024, all of a BERT-base row at S = 128: PR 45), the context and
the gradients written as rows. On the CPU through interpret mode:

* forward, forward + logsumexp and the one-pass backward against
  ``attention_reference`` and its ``jax.vjp``, in float32 and bfloat16,
  causal and padding-masked, at D = 64 (two heads a lane tile: both lane
  windows, and a pair whose two heads differ in scale) and D = 128;
* token-major against head-major BIT FOR BIT (the same arithmetic,
  fetched from where it lies);
* the GPT graph's loss and every parameter gradient with
  ``use_flash_attention=True`` against the composed graph at S = 512;
* the rule that picks the form (``ops/attention.py:flash_layout``) and
  the ``flash_layout`` instant a traced call records;
* the rule that sizes a program (``heads_per_program``), and the
  three-array form at BERT's shape through the op and through the BERT
  graph (PR 45).
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry as tmod
from hetu_tpu.graph.node import ExecContext
from hetu_tpu.ops import attention as attn_mod
from hetu_tpu.ops import pallas_attention as pk
from hetu_tpu.ops import pallas_dropout, pallas_norm, pallas_sparse_update
from hetu_tpu.ops.attention import (FlashAttentionOp, attention_reference,
                                    flash_layout)
from hetu_tpu.telemetry.check import check_args

SCALE = 0.125


def _rows(b, s, heads, d, dtype, seed=0, head_scales=None):
    """Packed qkv rows ``[B, S, 3H]``; ``head_scales`` multiplies each
    head's q, k and v (two heads of one lane tile then differ)."""
    x = np.random.RandomState(seed).randn(b, s, 3, heads, d) * 0.5
    if head_scales is not None:
        x = x * np.asarray(head_scales, np.float64)[None, None, None, :,
                                                    None]
    return jnp.asarray(x.reshape(b, s, 3 * heads * d), dtype)


def _heads(rows, heads, d):
    """rows -> q, k, v ``[B, H, S, D]``."""
    b, s, _ = rows.shape
    return rows.reshape(b, s, 3, heads, d).transpose(2, 0, 3, 1, 4)


def _as_rows(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _padding(b, s):
    m = np.zeros((b, 1, 1, s), np.float32)
    m[0, ..., s - 37:] = -1e9
    m[-1, ..., 130:141] = -1e9
    return jnp.asarray(m)


def _full_mask(mask, s, causal):
    if causal:
        cm = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                       -1e30)[None, None]
        mask = cm if mask is None else mask + cm
    return mask


CASES = [  # heads, head_dim, dtype, causal, padding mask, tiles
    (2, 64, jnp.float32, True, False, (128, 128)),
    (2, 64, jnp.float32, False, True, (128, 256)),
    (4, 64, jnp.float32, True, True, (256, 128)),
    (4, 64, jnp.bfloat16, True, False, (128, 128)),
    (2, 64, jnp.bfloat16, False, True, (256, 256)),
    (2, 128, jnp.float32, True, False, (128, 128)),
    (1, 128, jnp.float32, False, True, (128, 256)),
    (2, 128, jnp.bfloat16, True, True, (256, 128)),
]
IDS = [f"h{h}-d{d}-{jnp.dtype(t).name}-{'causal' if c else 'full'}-"
       f"{'mask' if m else 'nomask'}-{bq}x{bk}"
       for h, d, t, c, m, (bq, bk) in CASES]


def _case(heads, d, dtype, causal, use_mask, s=512, b=2):
    # the two heads of a D = 64 lane tile differ eightfold in scale
    scales = [1.0, 0.125, 0.5, 2.0][:heads]
    rows = _rows(b, s, heads, d, dtype, seed=heads + d, head_scales=scales)
    mask = _padding(b, s) if use_mask else None
    dy = jnp.asarray(np.random.RandomState(5).randn(b, s, heads * d),
                     dtype)
    return rows, mask, dy, pk.TokenMajor.packed(heads, d)


def _tolerance(dtype):
    return dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 \
        else dict(rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("heads,d,dtype,causal,use_mask,tiles", CASES,
                         ids=IDS)
def test_token_major_forward_matches_the_reference(heads, d, dtype, causal,
                                                   use_mask, tiles):
    """Plain forward and forward + logsumexp over the packed rows: the
    context as rows ``[B, S, H]``, the residual as the rows the
    backward reads, ``[B, H, 1, S]``."""
    rows, mask, _, layout = _case(heads, d, dtype, causal, use_mask)
    b, s, _ = rows.shape
    q, k, v = (x.astype(jnp.float32) for x in _heads(rows, heads, d))
    m = _full_mask(mask, s, causal)
    want = _as_rows(attention_reference(q, k, v, m, SCALE))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * SCALE
    want_lse = jax.nn.logsumexp(scores if m is None else scores + m,
                                axis=-1)
    plain = pk._flash_attention_jit(rows, rows, rows, mask, SCALE, causal,
                                    True, *tiles, False, layout)
    o, lse = pk._flash_attention_jit(rows, rows, rows, mask, SCALE, causal,
                                     True, *tiles, True, layout)
    assert plain.shape == o.shape == (b, s, heads * d)
    assert lse.shape == (b, heads, 1, s) and lse.dtype == jnp.float32
    assert np.array_equal(np.asarray(plain), np.asarray(o))
    tol = _tolerance(dtype)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(want_lse), **tol)


@pytest.mark.parametrize("heads,d,dtype,causal,use_mask,tiles", CASES,
                         ids=IDS)
def test_token_major_backward_matches_the_composed_vjp(heads, d, dtype,
                                                       causal, use_mask,
                                                       tiles):
    """dq, dk and dv as rows ``[B, S, H]`` against ``jax.vjp`` of the
    float32 reference over the same numbers."""
    rows, mask, dy, layout = _case(heads, d, dtype, causal, use_mask)
    s = rows.shape[1]
    m = _full_mask(mask, s, causal)
    q, k, v = (x.astype(jnp.float32) for x in _heads(rows, heads, d))
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, m, SCALE),
        q, k, v)
    b = rows.shape[0]
    want = vjp(dy.astype(jnp.float32).reshape(b, s, heads, d)
               .transpose(0, 2, 1, 3))
    o, lse = pk._flash_attention_jit(rows, rows, rows, mask, SCALE, causal,
                                     True, 128, 128, True, layout)
    got = pk._flash_attention_bwd_jit(rows, rows, rows, mask, o, lse, dy,
                                      SCALE, causal, True, *tiles, layout)
    tol = _tolerance(dtype)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == dy.shape and g.dtype == dtype
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g, np.float32) / scale,
            np.asarray(_as_rows(w)) / scale, err_msg=f"d{name}", **tol)


@pytest.mark.parametrize("heads,d,dtype,causal,use_mask,tiles", CASES,
                         ids=IDS)
def test_token_major_is_head_major_bit_for_bit(heads, d, dtype, causal,
                                               use_mask, tiles):
    """The same arithmetic fetched from where it lies: context,
    logsumexp, dq, dk and dv equal the head-major kernels' to the last
    bit at the same tiles, from one packed array read three times AND
    from three separate ``[B, S, H]`` arrays."""
    rows, mask, dy, layout = _case(heads, d, dtype, causal, use_mask)
    b, s, _ = rows.shape
    q, k, v = _heads(rows, heads, d)
    dyh = dy.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
    o_h, lse_h = pk._flash_attention_jit(q, k, v, mask, SCALE, causal,
                                         True, *tiles, True)
    g_h = pk._flash_attention_bwd_jit(q, k, v, mask, o_h, lse_h, dyh,
                                      SCALE, causal, True, *tiles)
    apart = pk.TokenMajor(heads, d)
    thirds = [_as_rows(x) for x in (q, k, v)]
    for operands, form in (((rows, rows, rows), layout),
                           (thirds, apart)):
        o, lse = pk._flash_attention_jit(*operands, mask, SCALE, causal,
                                         True, *tiles, True, form)
        g = pk._flash_attention_bwd_jit(*operands, mask, o, lse, dy, SCALE,
                                        causal, True, *tiles, form)
        assert np.array_equal(np.asarray(o), np.asarray(_as_rows(o_h)))
        assert np.array_equal(np.asarray(lse[:, :, 0]), np.asarray(lse_h))
        for a, c in zip(g, g_h):
            assert np.array_equal(np.asarray(a), np.asarray(_as_rows(c)))


def test_region_walk_with_scratch_is_bit_equal_too():
    """S = 1024 at 128 x 128 tiles walks 2 x 2 regions of 16 pairs: the
    float32 scratch sums (one set a head of the lane tile) round to the
    head-major kernel's bits."""
    heads, d, s = 2, 64, 1024
    assert pk._region_span(s, 128, 128) == 512
    rows = _rows(1, s, heads, d, jnp.float32, seed=3)
    dy = jnp.asarray(np.random.RandomState(4).randn(1, s, heads * d),
                     jnp.float32)
    layout = pk.TokenMajor.packed(heads, d)
    q, k, v = _heads(rows, heads, d)
    for causal in (True, False):
        o, lse = pk._flash_attention_jit(rows, rows, rows, None, SCALE,
                                         causal, True, 256, 512, True,
                                         layout)
        got = pk._flash_attention_bwd_jit(rows, rows, rows, None, o, lse,
                                          dy, SCALE, causal, True, 128,
                                          128, layout)
        o_h, lse_h = pk._flash_attention_jit(q, k, v, None, SCALE, causal,
                                             True, 256, 512, True)
        want = pk._flash_attention_bwd_jit(
            q, k, v, None, o_h, lse_h,
            dy.reshape(1, s, heads, d).transpose(0, 2, 1, 3), SCALE,
            causal, True, 128, 128)
        for a, c in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(_as_rows(c)))


# ---------------------------------------------------------------------------
# the rule, the instant, the op
# ---------------------------------------------------------------------------

def _mesh(size):
    return types.SimpleNamespace(
        config=types.SimpleNamespace(mesh=types.SimpleNamespace(size=size)))


@pytest.mark.parametrize("s,d,heads,token_major,ectx,want", [
    (1024, 64, 12, True, None, ("token_major", None)),
    (512, 128, 8, True, None, ("token_major", None)),
    (1024, 64, 12, True, _mesh(1), ("token_major", None)),
    (128, 64, 12, True, None, ("token_major", None)),
    (128, 192, 64, False, _mesh(4), ("head_major", "lanes")),
    (1024, 192, 64, True, None, ("head_major", "lanes")),
    (1024, 64, 3, True, None, ("head_major", "lanes")),
    (576, 64, 12, True, None, ("head_major", "lanes")),
    (1024, 64, 12, True, _mesh(4), ("head_major", "mesh")),
    (1024, 64, 12, False, None, ("head_major", "caller")),
], ids=["gpt2", "d128", "mesh-of-one", "bert", "first-reason-wins",
        "d192", "odd-heads", "ragged-rows", "mesh", "caller"])
def test_the_rule_on_what_the_code_can_see(s, d, heads, token_major, ectx,
                                           want):
    assert flash_layout(s, d, heads, token_major, ectx) == want


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The op's kernel paths through interpret mode, static tiles, and
    an enabled telemetry that sees the instants."""
    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    monkeypatch.setattr(pk, "INTERPRET", True)
    # a whole graph traced "on the chip" meets the other kernels too
    monkeypatch.setattr(pallas_norm, "INTERPRET", True)
    monkeypatch.setattr(pallas_dropout, "INTERPRET", True)
    monkeypatch.setattr(pallas_sparse_update, "INTERPRET", True)
    old = tmod._default
    tel = tmod.configure(enabled=True, service="test-flash-layout")
    yield tel
    tmod._default = old


def _layout_events(tel):
    events = [e["args"] for e in tel.tracer.drain(clear=True)
              if e.get("name") == "flash_layout"]
    for args in events:
        assert check_args("flash_layout", args) == []
    return events


def _run_packed_op(rows, heads, ectx, dy=None, causal=True):
    node = ht.Variable("qkv_rows", trainable=False)
    fwd = FlashAttentionOp(node, num_heads=heads, sm_scale=SCALE,
                           causal=causal)
    out = fwd.compute([rows], ectx)
    if dy is None:
        return out, None
    grad = fwd.gradient(ht.Variable("dy", trainable=False))
    assert len(grad) == 1
    return out, grad[0].compute([rows, dy], ectx)


def _packed_reference(rows, heads, d, dy, causal=True):
    b, s, _ = rows.shape

    def f(x):
        q, k, v = _heads(x, heads, d)
        return _as_rows(attention_reference(
            q, k, v, _full_mask(None, s, causal), SCALE))

    out, vjp = jax.vjp(f, rows)
    return out, vjp(dy)[0]


def test_packed_op_runs_token_major_and_says_so(kernels_on_cpu):
    """S = 512, two heads of 64: forward with logsumexp and the fused
    backward run token-major, two heads a block, and the one gradient is
    the ``[B, S, 3H]`` rows; the walk instants are the head-major ones',
    the forward's with the lane block's two heads a program."""
    heads, d, s = 2, 64, 512
    rows = _rows(2, s, heads, d, jnp.float32, seed=9)
    dy = jnp.asarray(np.random.RandomState(1).randn(2, s, heads * d),
                     jnp.float32)
    out, dqkv = _run_packed_op(rows, heads, ExecContext(training=True), dy)
    events = kernels_on_cpu.tracer.drain(clear=True)
    layouts = [e["args"] for e in events if e.get("name") == "flash_layout"]
    assert [(a["kernel"], a["layout"], a["heads_per_block"])
            for a in layouts] == [("fwd_lse", "token_major", 2),
                                  ("bwd", "token_major", 2)]
    assert all("reason" not in a and check_args("flash_layout", a) == []
               for a in layouts)
    walk = [e["args"] for e in events if e.get("name") == "flash_bwd_walk"]
    assert len(walk) == 1
    bq, bk = walk[0]["block_q"], walk[0]["block_k"]
    assert {k: walk[0][k] for k in pk.tile_walk_counts(s, bq, bk, True)} \
        == pk.tile_walk_counts(s, bq, bk, True)
    # the forward's walk at ITS tiles: both heads of the lane block in
    # one program, a chain a q-tile each, the diagonal's pairs masked
    fwd = [e["args"] for e in events if e.get("name") == "flash_fwd_walk"]
    assert len(fwd) == 1 and check_args("flash_fwd_walk", fwd[0]) == []
    bq, bk = fwd[0]["block_q"], fwd[0]["block_k"]
    assert fwd[0] == {
        "seq": s, "head_dim": d, "block_q": bq, "block_k": bk,
        "causal": True, "heads_per_program": 2, "chains": 2 * (s // bq),
        **pk.tile_walk_counts(s, bq, bk, True)}
    # the rule's tiles: one q-tile of 512, the diagonal cuts both its k-tiles
    assert (bq, bk, fwd[0]["tiles_masked"]) == (512, 256, 2)
    want_out, want_grad = _packed_reference(rows, heads, d, dy)
    assert dqkv.shape == rows.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(want_grad),
                               rtol=2e-4, atol=2e-4)
    # not training: the plain forward, token-major too
    _run_packed_op(rows, heads, ExecContext(training=False))
    assert [(a["kernel"], a["layout"])
            for a in _layout_events(kernels_on_cpu)] \
        == [("fwd", "token_major")]


@pytest.mark.parametrize("s,heads,d,ectx,reason", [
    (192, 2, 64, ExecContext(training=True), "lanes"),
    (512, 2, 192, ExecContext(training=True), "lanes"),
    (512, 2, 64, types.SimpleNamespace(
        training=True, cache={}, config=types.SimpleNamespace(
            mesh=types.SimpleNamespace(size=4))), "mesh"),
], ids=["s192", "d192", "mesh"])
def test_packed_op_makes_the_trip_itself_where_the_rule_says(
        kernels_on_cpu, s, heads, d, ectx, reason):
    """Packed rows in, rows out, through the head-major kernels (and the
    composed vjp below ``FUSED_BWD_MIN_SEQ``, which holds for
    head-major calls alone: S = 192 is no whole lane tile of rows): the
    instant names the condition that failed, and the numbers are the
    reference's."""
    rows = _rows(1, s, heads, d, jnp.float32, seed=s + d)
    dy = jnp.asarray(np.random.RandomState(2).randn(1, s, heads * d),
                     jnp.float32)
    out, dqkv = _run_packed_op(rows, heads, ectx, dy)
    events = _layout_events(kernels_on_cpu)
    assert events and all(
        a["layout"] == "head_major" and a["heads_per_block"] == 1
        and a["reason"] == reason for a in events)
    want_out, want_grad = _packed_reference(rows, heads, d, dy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(want_grad),
                               rtol=2e-4, atol=2e-4)


def test_head_major_callers_keep_the_head_major_entry(kernels_on_cpu):
    """``[B, H, S, D]`` operands — the encoder's op, serving's prefill —
    reach the kernels as before and are recorded with their reason."""
    q, k, v = _heads(_rows(1, 512, 2, 64, jnp.float32, seed=6), 2, 64)
    nodes = [ht.Variable(n, trainable=False) for n in "qkv"]
    FlashAttentionOp(*nodes, sm_scale=SCALE, causal=True).compute(
        [q, k, v], ExecContext(training=True))
    attn_mod.prefill_attention(q[:, :, :128], k[:, :, :128],
                               v[:, :, :128], SCALE)
    pk.flash_attention(q, k, v, None, SCALE, True)
    assert [(a["kernel"], a["layout"], a["reason"])
            for a in _layout_events(kernels_on_cpu)] == [
        ("fwd_lse", "head_major", "caller"),
        ("fwd", "head_major", "caller"),
        ("fwd", "head_major", "caller")]
    with pytest.raises(ValueError, match="packed qkv rows"):
        FlashAttentionOp(nodes[0], num_heads=None)
    with pytest.raises(ValueError, match="packed qkv rows"):
        FlashAttentionOp(*nodes[:2], num_heads=2)


def test_the_two_operand_forms_are_resolved_and_recorded_apart(
        monkeypatch):
    """The train driver's ``flash_tiles`` line: what this process's calls
    resolved, under the key strings the swept store had."""
    from hetu_tpu.tune.autotune import get_table
    monkeypatch.setattr(pk, "RESOLVED_TILES", {})
    q = jnp.zeros((1, 256, 128), jnp.float32)
    rows = pk.TokenMajor(2, 64)
    pk.flash_attention_with_lse(q, q, q, None, 0.125, True,
                                interpret=True, layout=rows)
    heads = jnp.zeros((1, 2, 256, 64), jnp.float32)
    pk.flash_attention_with_lse(heads, heads, heads, None, 0.125, True,
                                interpret=True)
    chosen = get_table().chosen("flash")
    tiles = pk._block_sizes(256, 64, "fwd_lse", True, False)
    assert chosen == {
        "cpu|flash_fwd_lse_regions|S256|D64|float32|causal|nomask": tiles,
        "cpu|flash_fwd_lse_regions_token_major|S256|D64|float32|causal"
        "|nomask": tiles}
    assert get_table().chosen("flash_bwd") == {}


# ---------------------------------------------------------------------------
# the GPT graph
# ---------------------------------------------------------------------------

def _gpt_loss_and_grads(flash, seq, x, y, train=True):
    import hetu_tpu.models as M
    from hetu_tpu.executor import Executor
    cfg = M.GPTConfig(vocab_size=64, hidden_size=128, num_hidden_layers=2,
                      num_attention_heads=2, max_position_embeddings=seq,
                      hidden_dropout_prob=0.0, use_flash_attention=flash)
    ids = ht.Variable("input_ids", trainable=False)
    labels = ht.Variable("labels", trainable=False)
    _, loss = M.GPTLMHeadModel(cfg)(ids, labels)
    lm = ht.reduce_mean_op(loss, [0, 1])
    params = ht.optim.SGDOptimizer(0.1).get_var_list(lm)
    grads = ht.gradients(lm, params)
    nodes = [lm] + grads
    if train:   # a training step: the forward keeps its residual
        nodes.append(ht.optim.SGDOptimizer(0.1).minimize(lm))
    vals = Executor(nodes).run(
        feed_dict={ids: x, labels: y}, convert_to_numpy_ret_vals=True)
    # an embedding's gradient arrives as the rows it touched
    # (IndexedSlices, boxed in a 0-d object array)
    return float(vals[0]), {
        p.name: np.asarray(g.item().to_dense() if g.dtype == object else g)
        for p, g in zip(params, vals[1:1 + len(params)])}


def test_gpt_graph_matches_the_composed_one_at_s512(kernels_on_cpu):
    """Two layers, S = 512, two heads of 64: the flash graph (packed
    rows into the op, token-major kernels both ways, no split or merge
    node) gives the composed graph's loss and every parameter's
    gradient."""
    seq = 512
    x = np.random.RandomState(3).randint(0, 64, (2, seq))
    y = np.concatenate([x[:, 1:], np.full((2, 1), -1, np.int64)], axis=1)
    want_loss, want = _gpt_loss_and_grads(False, seq, x, y)
    assert not _layout_events(kernels_on_cpu)      # no kernel ran
    got_loss, got = _gpt_loss_and_grads(True, seq, x, y)
    events = _layout_events(kernels_on_cpu)
    assert sorted((a["kernel"], a["layout"], a["heads_per_block"])
                  for a in events) == 2 * [("bwd", "token_major", 2)] \
        + 2 * [("fwd_lse", "token_major", 2)]
    assert got_loss == pytest.approx(want_loss, rel=1e-5)
    assert got.keys() == want.keys() and len(got) == 2 * 12 + 5
    # gradients asked of a step that does not train: no residual was
    # kept, so the plain forward token-major and the composed vjp
    _, untrained = _gpt_loss_and_grads(True, seq, x, y, train=False)
    assert sorted((a["kernel"], a["layout"])
                  for a in _layout_events(kernels_on_cpu)) \
        == 2 * [("fwd", "token_major")]
    for name, w in want.items():
        scale = np.abs(w).max()
        assert scale > 0, name
        for grads in (got, untrained):
            np.testing.assert_allclose(grads[name] / scale, w / scale,
                                       atol=2e-4, err_msg=name)


# ---------------------------------------------------------------------------
# programs several lane blocks wide, and three projections' rows (PR 45)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,heads,d,packed,tiles,batch,want,programs", [
    # GPT-2's cell, forward and backward tiles: a lane block of two
    # heads a program, the grid it always had
    (1024, 12, 64, True, (512, 512), 16, 2, (16, 6, 1)),
    (1024, 12, 64, True, (256, 256), 16, 2, (16, 6, 1)),
    (1024, 12, 64, True, (128, 128), 16, 2, (16, 6, 2)),
    # BERT-base's cell: a batch row's twelve heads, 256 programs a layer
    (128, 12, 64, False, (128, 128), 256, 12, (256, 1, 1)),
    (128, 12, 64, True, (128, 128), 256, 12, (256, 1, 1)),
    # between them the two bounds decide: tile pairs, rows
    (256, 12, 64, False, (128, 128), 8, 4, (8, 3, 1)),
    (256, 12, 64, False, (256, 256), 8, 6, (8, 2, 1)),
    (512, 12, 64, True, (256, 512), 8, 4, (8, 3, 1)),
    # whole blocks only, and a count that divides the row's blocks
    (128, 8, 128, False, (128, 128), 4, 8, (4, 1, 1)),
    (128, 20, 64, False, (128, 128), 4, 10, (4, 2, 1)),
    (128, 32, 64, False, (128, 128), 4, 16, (4, 2, 1)),
    # regions walked by a loop: one lane block
    (4096, 4, 64, False, (512, 512), 1, 2, (1, 2, 2)),
], ids=lambda v: str(v).replace(" ", ""))
def test_program_width_rule(s, heads, d, packed, tiles, batch, want,
                            programs):
    """ONE rule for both kernels (``heads_per_program``): (S, heads, D,
    packed or three arrays, tiles) -> heads a program, and the grid
    both jits trace to at those tiles."""
    layout = pk.TokenMajor.packed(heads, d) if packed \
        else pk.TokenMajor(heads, d)
    assert layout.fits(s)
    assert pk.heads_per_program(heads, s, *tiles, layout) == want
    assert pk.fwd_walk_counts(heads, s, *tiles, False, layout)[
        "heads_per_program"] == want
    width = heads * d * (3 if packed else 1)
    rows = jax.ShapeDtypeStruct((batch, s, width), jnp.bfloat16)
    ctx = jax.ShapeDtypeStruct((batch, s, heads * d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((batch, heads, 1, s), jnp.float32)

    def grid(fn, *operands, static):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn.params["grid_mapping"].grid)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(lambda *a: fn(*a[:3], None, *a[3:], *static))(
            *operands).jaxpr)
        assert len(found) == 1
        return tuple(int(g) for g in found[0])

    assert grid(pk._flash_attention_jit, rows, rows, rows,
                static=(SCALE, False, True, *tiles, True, layout)) \
        == programs
    assert grid(pk._flash_attention_bwd_jit, rows, rows, rows, ctx, lse,
                ctx, static=(SCALE, False, True, *tiles, layout)) \
        == programs


def _three(b, s, heads, d, dtype, seed):
    """q, k, v rows ``[B, S, H]`` of three projections, the heads of a
    lane block at different scales, a padding mask, and dy."""
    rng = np.random.RandomState(seed)
    scales = np.asarray([1.0, 0.125, 0.5, 2.0] * heads)[:heads]
    rows = [jnp.asarray((rng.randn(b, s, heads, d) * 0.5
                         * scales[None, None, :, None]).reshape(
                             b, s, heads * d), dtype) for _ in range(3)]
    m = np.zeros((b, 1, 1, s), np.float32)
    m[0, ..., s - 37:] = -1e9
    m[-1, ..., 30:41] = -1e9
    dy = jnp.asarray(rng.randn(b, s, heads * d), dtype)
    return rows, jnp.asarray(m), dy


def _to_heads(x, heads):
    b, s, width = x.shape
    return x.reshape(b, s, heads, width // heads).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("s,tiles,dtype", [
    (128, (128, 128), jnp.bfloat16), (128, (128, 128), jnp.float32),
    (256, (128, 256), jnp.bfloat16), (256, (256, 128), jnp.float32)],
    ids=["bert-bf16", "bert-f32", "s256-bf16", "s256-f32"])
def test_three_arrays_match_the_composed_vjp_at_berts_shape(s, tiles,
                                                            dtype):
    """Twelve heads of 64 from three ``[B, S, H]`` arrays under a
    padding mask — at S = 128 a batch row's twelve heads one program in
    both directions, at S = 256 two tile pairs a head and six heads a
    program — against
    ``jax.vjp`` of the float32 reference, and against the head-major
    kernels bit for bit."""
    heads, d, b = 12, 64, 2
    layout = pk.TokenMajor(heads, d)
    group = pk.heads_per_program(heads, s, *tiles, layout)
    assert group == (12 if s == 128 else 6)
    (q, k, v), mask, dy = _three(b, s, heads, d, dtype, seed=s)
    o, lse = pk._flash_attention_jit(q, k, v, mask, SCALE, False, True,
                                     *tiles, True, layout)
    got = pk._flash_attention_bwd_jit(q, k, v, mask, o, lse, dy, SCALE,
                                      False, True, *tiles, layout)
    qh, kh, vh, dyh = (_to_heads(x, heads) for x in (q, k, v, dy))
    want_o, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, mask, SCALE),
        *(x.astype(jnp.float32) for x in (qh, kh, vh)))
    want = vjp(dyh.astype(jnp.float32))
    tol = _tolerance(dtype)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(_as_rows(want_o)), **tol)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == dy.shape and g.dtype == dtype
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g, np.float32) / scale,
            np.asarray(_as_rows(w)) / scale, err_msg=f"d{name}", **tol)
    o_h, lse_h = pk._flash_attention_jit(qh, kh, vh, mask, SCALE, False,
                                         True, *tiles, True)
    g_h = pk._flash_attention_bwd_jit(qh, kh, vh, mask, o_h, lse_h, dyh,
                                      SCALE, False, True, *tiles)
    assert np.array_equal(np.asarray(o), np.asarray(_as_rows(o_h)))
    assert np.array_equal(np.asarray(lse[:, :, 0]), np.asarray(lse_h))
    for a, c in zip(got, g_h):
        assert np.array_equal(np.asarray(a), np.asarray(_as_rows(c)))


def _run_rows_op(rows, mask, heads, ectx, dy):
    nodes = [ht.Variable(n, trainable=False) for n in "qkvm"]
    fwd = FlashAttentionOp(*nodes, sm_scale=SCALE, num_heads=heads)
    out = fwd.compute([*rows, mask], ectx)
    assert fwd.infer_shape([x.shape for x in rows]) == out.shape
    grads = fwd.gradient(ht.Variable("dy", trainable=False))
    assert len(grads) == 4 and grads[3] is None
    return out, [g.compute([*rows, mask, dy], ectx) for g in grads[:3]]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_rows_op_runs_token_major_at_s128_and_says_so(kernels_on_cpu,
                                                      dtype):
    """BERT's call: three projections' rows in, the context and three
    gradients out as rows, forward with logsumexp and the FUSED backward
    at S = 128 (token-major the threshold does not apply), a batch
    row's twelve heads a program in both — and the numbers are the
    composed vjp's over ``[B, H, S, D]``."""
    heads, d, s = 12, 64, 128
    rows, mask, dy = _three(2, s, heads, d, dtype, seed=11)
    out, grads = _run_rows_op(rows, mask, heads,
                              ExecContext(training=True), dy)
    events = kernels_on_cpu.tracer.drain(clear=True)
    assert [(e["args"]["kernel"], e["args"]["layout"],
             e["args"]["heads_per_block"])
            for e in events if e.get("name") == "flash_layout"] \
        == [("fwd_lse", "token_major", 2), ("bwd", "token_major", 2)]
    for name in ("flash_fwd_walk", "flash_bwd_walk"):
        (walk,) = [e["args"] for e in events if e.get("name") == name]
        assert check_args(name, walk) == []
        assert walk["heads_per_program"] == 12 and walk["seq"] == s
    qh, kh, vh, dyh = (_to_heads(x.astype(jnp.float32), heads)
                       for x in (*rows, dy))
    want_o, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, mask, SCALE),
        qh, kh, vh)
    tol = _tolerance(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_as_rows(want_o)), **tol)
    for g, w, x in zip(grads, vjp(dyh), rows):
        assert g.shape == x.shape and g.dtype == x.dtype
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g, np.float32) / scale,
                                   np.asarray(_as_rows(w)) / scale, **tol)
    # not training: the plain forward, token-major too, no residual
    ectx = ExecContext(training=False)
    _run_rows_op(rows, mask, heads, ectx, dy)
    assert not [key for key in ectx.cache if key[0] == "flash_res"]
    assert [(a["kernel"], a["layout"])
            for a in _layout_events(kernels_on_cpu)] \
        == [("fwd", "token_major")]


@pytest.mark.parametrize("s,heads,d,patched,ectx,kinds", [
    (128, 2, 64, False, ExecContext(training=True), []),
    (192, 2, 64, True, ExecContext(training=True),
     [("fwd", "lanes")]),
    (128, 2, 64, True, types.SimpleNamespace(
        training=True, cache={}, config=types.SimpleNamespace(
            mesh=types.SimpleNamespace(size=4))), [("fwd", "mesh")]),
], ids=["off-the-chip", "s192", "mesh"])
def test_rows_op_makes_the_trip_itself_where_the_rule_says(
        kernels_on_cpu, monkeypatch, s, heads, d, patched, ectx, kinds):
    """Off a TPU, at rows that are no whole lane tiles and under a mesh
    the op splits the three arrays into ``[B, H, S, D]`` itself, runs
    the reference or the head-major forward with the composed vjp, and
    hands back rows: three gradients, each its projection's."""
    if not patched:
        monkeypatch.setattr(attn_mod, "_use_pallas", lambda: False)
    rows, mask, dy = _three(2, s, heads, d, jnp.float32, seed=s + d)
    out, grads = _run_rows_op(rows, mask, heads, ectx, dy)
    assert [(a["kernel"], a["reason"])
            for a in _layout_events(kernels_on_cpu)] == kinds
    qh, kh, vh, dyh = (_to_heads(x, heads) for x in (*rows, dy))
    want_o, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, mask, SCALE),
        qh, kh, vh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_as_rows(want_o)),
                               rtol=2e-4, atol=2e-4)
    for g, w in zip(grads, vjp(dyh)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(_as_rows(w)),
                                   rtol=2e-4, atol=2e-4)


def _bert_loss_and_grads(flash, seq, feed):
    import hetu_tpu.models as M
    from hetu_tpu.executor import Executor
    cfg = M.BertConfig(vocab_size=64, hidden_size=256,
                       num_hidden_layers=2, num_attention_heads=4,
                       intermediate_size=512, hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0,
                       max_position_embeddings=seq,
                       use_flash_attention=flash)
    nodes = [ht.Variable(n, trainable=False) for n in (
        "input_ids", "token_type_ids", "attention_mask",
        "masked_lm_labels", "next_sentence_label")]
    _, _, mlm_loss, nsp_loss = M.BertForPreTraining(cfg)(*nodes)
    loss = ht.reduce_mean_op(mlm_loss, [0, 1]) \
        + ht.reduce_mean_op(nsp_loss, [0])
    params = ht.optim.SGDOptimizer(0.1).get_var_list(loss)
    grads = ht.gradients(loss, params)
    vals = Executor([loss] + grads
                    + [ht.optim.SGDOptimizer(0.1).minimize(loss)]).run(
        feed_dict=dict(zip(nodes, feed)), convert_to_numpy_ret_vals=True)
    return float(vals[0]), {
        p.name: np.asarray(g.item().to_dense() if g.dtype == object else g)
        for p, g in zip(params, vals[1:1 + len(params)])}


def test_bert_graph_matches_the_composed_one_at_s128(kernels_on_cpu):
    """Two layers, S = 128, four heads of 64 behind three projections
    and a padding mask: the flash graph hands the op the projections'
    rows (no reshape or transpose node around it), both kernels run
    token-major with all four heads a program, and the loss and every
    parameter's gradient are the composed graph's. Parameter names do
    not change with the path."""
    seq, batch = 128, 2
    rng = np.random.RandomState(8)
    ids = rng.randint(0, 64, (batch, seq)).astype(np.int32)
    types_ = np.zeros((batch, seq), np.int32)
    types_[:, seq // 2:] = 1
    mask = np.ones((batch, seq), np.float32)
    mask[0, seq - 29:] = 0
    labels = np.where(rng.rand(batch, seq) < 0.3, ids, -1).astype(np.int32)
    feed = (ids, types_, mask, labels, np.asarray([0, 1], np.int32))
    want_loss, want = _bert_loss_and_grads(False, seq, feed)
    assert not _layout_events(kernels_on_cpu)      # no kernel ran
    got_loss, got = _bert_loss_and_grads(True, seq, feed)
    events = kernels_on_cpu.tracer.drain(clear=True)
    assert sorted((e["args"]["kernel"], e["args"]["layout"])
                  for e in events if e.get("name") == "flash_layout") \
        == 2 * [("bwd", "token_major")] + 2 * [("fwd_lse", "token_major")]
    assert {e["args"]["heads_per_program"] for e in events
            if e.get("name") in ("flash_fwd_walk", "flash_bwd_walk")} \
        == {4}
    assert got_loss == pytest.approx(want_loss, rel=1e-5)
    assert got.keys() == want.keys()
    assert {"layer0_attn_query_weights", "layer0_attn_key_bias",
            "layer1_attn_value_weights"} <= got.keys()
    for name, w in want.items():
        # a key's bias moves every score of a row alike: its gradient
        # is rounding around zero on both sides, held to the weights'
        scale = np.abs(want[name.replace("_bias", "_weights")]
                       if name.endswith("_key_bias") else w).max()
        assert scale > 0, name
        np.testing.assert_allclose(got[name] / scale, w / scale,
                                   atol=2e-4, err_msg=name)
