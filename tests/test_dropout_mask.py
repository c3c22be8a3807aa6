"""A dropout mask from the seed-only kernel
(``hetu_tpu/ops/pallas_dropout.py``), in interpret mode on the CPU.

No interpreter has the core's generator (the generic one has no rule
for ``prng_seed``; the TPU one returns zeros), so under interpret mode
the kernel draws from a stand-in hash of the same per-block key. What
is tested here is everything around the bits: the per-block key, the
unsigned compare against ``keep_prob * 2**32``, the bytes, the shapes
the rule takes, that the gradient op gets the forward's mask, and where
the composed ``jax.random.bernoulli`` stays. The generator's own bits
are checked on the chip (``chip_smoke.py``), and that the kernel
compiles for it in ``tests/test_chip_compile.py``.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.ops import activations, attention, pallas_dropout

BASE = jax.random.PRNGKey(11)


def _mask(key, shape, keep_prob):
    return np.asarray(pallas_dropout.hetu_dropout_mask(
        pallas_dropout.seed_words(key), shape, keep_prob, interpret=True))


@pytest.fixture
def on_tpu(monkeypatch):
    """The TPU's rule in force, the kernel interpreted."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(pallas_dropout, "INTERPRET", True)


# rows 64 (one block), 3-D, 4-D, and two row blocks with a ragged tail
@pytest.mark.parametrize("keep_prob", [0.9, 0.5, 0.1])
@pytest.mark.parametrize("shape", [(64, 128), (4, 32, 256), (2, 2, 32, 128),
                                   (8192 + 32, 128)], ids=str)
def test_the_bytes_are_zero_or_one_and_a_seed_repeats_them(shape, keep_prob):
    mask = _mask(BASE, shape, keep_prob)
    assert mask.shape == shape and mask.dtype == np.int8
    assert set(np.unique(mask)) == {0, 1}
    np.testing.assert_array_equal(mask, _mask(BASE, shape, keep_prob))


@pytest.mark.parametrize("what", ["op id", "step", "row block"])
def test_another_key_or_block_gives_another_mask(what):
    """Two masks that should be independent agree on about
    ``p**2 + q**2`` of their decisions, within four standard deviations;
    equal masks would agree on all."""
    keep_prob, shape = 0.5, (4096, 128)
    step = jax.random.fold_in(BASE, 3)          # the executor's two folds
    a = _mask(jax.random.fold_in(step, 17), shape, keep_prob)
    if what == "op id":
        b = _mask(jax.random.fold_in(step, 18), shape, keep_prob)
    elif what == "step":
        b = _mask(jax.random.fold_in(jax.random.fold_in(BASE, 4), 17),
                  shape, keep_prob)
    else:
        # the rule cuts (16384, 128) into blocks of 6528 rows
        assert pallas_dropout.block_rows(16384, 128) == 6528
        whole = _mask(jax.random.fold_in(step, 17), (16384, 128), keep_prob)
        a, b = whole[:4096], whole[6528:6528 + 4096]
        # and the ragged third block is filled too
        assert 0.45 < whole[2 * 6528:].mean() < 0.55
    agree = float((a == b).mean())
    sd = (0.25 / a.size) ** 0.5
    assert abs(agree - 0.5) < 4 * sd, (what, agree)


@pytest.mark.parametrize("keep_prob", [0.9, 0.5])
def test_kept_share_and_independent_neighbours(keep_prob):
    """2**20 decisions: the kept share within four binomial standard
    deviations of ``keep_prob``, and a decision uncorrelated with the
    one a row below and the one a lane beside it, to the same bound.
    1024 rows of 1024 are two blocks (800 + 224)."""
    assert pallas_dropout.block_rows(1024, 1024) == 800
    mask = _mask(jax.random.fold_in(BASE, 5), (1024, 1024),
                 keep_prob).astype(np.float64)
    n, var = mask.size, keep_prob * (1 - keep_prob)
    assert abs(mask.mean() - keep_prob) < 4 * (var / n) ** 0.5
    centred = mask - keep_prob
    for a, b in ((centred[1:], centred[:-1]),
                 (centred[:, 1:], centred[:, :-1]),
                 (centred[800:], centred[:224])):    # across the blocks
        assert abs((a * b).mean() / var) < 4 / a.size ** 0.5


@pytest.mark.parametrize("keep_prob,want", [
    (0.9, round(0.9 * 2 ** 32) - 1), (0.5, 2 ** 31 - 1),
    (1.0, 2 ** 32 - 1), (1 - 2.0 ** -24, 2 ** 32 - 257), (0.0, 0)])
def test_the_threshold_resolves_32_bits(keep_prob, want):
    """A decision keeps where its 32 bits are <= the threshold:
    ``keep_prob`` to 2**-32 (bernoulli's float32 uniform resolves
    2**-23), every element at 1.0."""
    assert pallas_dropout.threshold(keep_prob) == want
    if keep_prob == 1.0:
        assert _mask(BASE, (64, 128), 1.0).all()


@pytest.mark.parametrize("shape,kernel", [
    ((16, 1024, 768), True), ((16384, 768), True),      # GPT-2's cell
    ((256, 128, 768), True), ((32768, 768), True),      # BERT's cell
    ((256, 12, 128, 128), True), ((32, 128), True),
    ((64, 32, 1, 1), False),        # dropout2d's mask
    ((64, 100), False), ((64, 192), False), ((64, 64), False),
    ((24, 128), False), ((3, 10, 128), False),   # rows: no whole int8 tiles
    ((4096,), False), ((), False),
    ((32, 128 * 2048), False)])     # one tile of rows passes the budget
def test_which_shapes_the_kernel_takes(shape, kernel):
    assert pallas_dropout.supported(shape) is kernel
    if kernel:
        d = shape[-1]
        n = int(np.prod(shape[:-1]))
        block = pallas_dropout.block_rows(n, d)
        assert block % pallas_dropout.TILE_ROWS == 0 and 0 < block <= n
        assert block * d * pallas_dropout.DECISION_BYTES \
            <= pallas_dropout.VMEM_BUDGET
        # the same cut for the two cells: a function of the shape alone
        if d == 768:
            assert block == min(1088, n)


def _jaxpr_primitives(fn, *args):
    seen = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            seen.add(eqn.primitive.name)
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def _ectx(devices=None):
    mesh = devices and jax.sharding.Mesh(
        np.asarray(jax.devices()[:devices]), ("dp",))
    return types.SimpleNamespace(
        config=types.SimpleNamespace(mesh=mesh), training=True,
        rng_for=lambda op: jax.random.fold_in(BASE, op.id))


@pytest.mark.parametrize("case,kernel", [
    ("tpu", True), ("tpu, a mesh of one device", True),
    ("tpu, no config on the context", True),
    ("cpu", False), ("tpu, a mesh of four devices", False),
    ("tpu, per_channel", False), ("tpu, a width of 100", False)])
def test_where_the_composed_draw_stays(monkeypatch, case, kernel):
    """The kernel is taken from what the op can see — platform, mesh
    size, ``per_channel``, shape — and everything else keeps
    ``jax.random.bernoulli`` on the op's key: asserted on the jaxpr."""
    monkeypatch.setattr(pallas_dropout, "INTERPRET", True)
    if case != "cpu":
        monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    ectx = _ectx({"tpu, a mesh of one device": 1,
                  "tpu, a mesh of four devices": 4}.get(case))
    if case == "tpu, no config on the context":
        ectx.config = None
    shape = (64, 100) if case.endswith("100") else (2, 32, 128, 128)
    op = types.SimpleNamespace(id=9)
    per_channel = case.endswith("per_channel")

    def mask():
        return activations._dropout_mask(ectx, op, 0.9, shape, jnp.float32,
                                         per_channel=per_channel)
    primitives = _jaxpr_primitives(mask)
    assert ("pallas_call" in primitives) is kernel
    assert ("random_bits" in primitives) is not kernel
    got = np.asarray(mask())
    assert got.shape == (shape[:2] + (1, 1) if per_channel else shape)
    np.testing.assert_allclose(np.unique(got), [0.0, 1 / 0.9], rtol=1e-6)
    if not kernel:
        # exactly the draw it was
        want = jax.random.bernoulli(jax.random.fold_in(BASE, 9), 0.9,
                                    got.shape)
        np.testing.assert_array_equal(got != 0, np.asarray(want))


@pytest.mark.parametrize("op_name,shape", [
    ("dropout_op", (2, 32, 128)), ("dropout_op", (64, 256)),
    ("dropout_op", (6, 100)),                  # composed on a TPU too
    ("dropout2d_op", (32, 4, 8, 128))])        # per channel: composed
def test_the_gradient_multiplies_by_the_forwards_mask(on_tpu, op_name,
                                                      shape):
    """Through the graph: ``d sum(dropout(x)) / dx`` is the forward's
    mask over ``keep_prob``, element for element, in every step; two
    steps and two dropout ops of one step draw different masks."""
    keep_prob = 0.8
    xv = np.random.RandomState(2).uniform(1, 2, shape).astype(np.float32)
    x = ht.Variable("x", trainable=False)
    axes = list(range(len(shape)))
    outs = [getattr(ht, op_name)(x, keep_prob) for _ in range(2)]
    # the gradient of each dropout's sum w.r.t. its input alone
    grads = [ht.gradients(ht.reduce_sum_op(y, axes=axes), [x])[0]
             for y in outs]
    # an optimizer over a parameter makes the step a training step
    w = ht.Variable("w", value=np.ones((1,), np.float32))
    train_op = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(
        ht.reduce_sum_op(ht.add_op(*outs), axes=axes)
        + ht.reduce_sum_op(w, axes=[0]))
    exe = ht.Executor(outs + grads + [train_op], ctx=ht.cpu(0))
    seen = []
    for _ in range(2):
        y0, y1, g0, g1 = [r.asnumpy() for r in
                          exe.run(feed_dict={x: xv})[:4]]
        for y, g in ((y0, g0), (y1, g1)):
            kept = y != 0
            assert 0.6 < kept.mean() < 0.95
            np.testing.assert_allclose(y, xv * kept / keep_prob, rtol=1e-6)
            np.testing.assert_allclose(g, kept / keep_prob, rtol=1e-6)
            seen.append(kept)
    for i in range(len(seen)):
        for j in range(i):
            assert (seen[i] != seen[j]).any()


def test_inference_passes_through(on_tpu):
    x = ht.Variable("x", trainable=False)
    xv = np.ones((32, 128), np.float32)
    exe = ht.Executor([ht.dropout_op(x, 0.5)], ctx=ht.cpu(0))
    np.testing.assert_array_equal(
        exe.run(feed_dict={x: xv})[0].asnumpy(), xv)
