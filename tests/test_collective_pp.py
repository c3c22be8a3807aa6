"""Collective (SPMD) pipeline mode: the whole GPipe schedule as ONE
shard_map program over a ``stage`` mesh axis with ppermute boundary
shifts (parallel/collective_pp.py) — loss-equivalent to the staged
runner (round-4 review #2)."""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor


def _uniform_pipeline(nstages=4, h=32, seed=0, lr=0.01,
                      opt_cls=None):
    rng = np.random.RandomState(seed)
    act = None
    x = None
    for s in range(nstages):
        with ht.context(ht.cpu(s)):
            if s == 0:
                x = ht.Variable("x", trainable=False)
                act = x
            w = ht.Variable(f"w{s}",
                            value=rng.randn(h, h).astype("f") * 0.2)
            act = ht.matmul_op(act, w)
            if s < nstages - 1:
                act = ht.relu_op(act)
            else:
                y_ = ht.Variable("y_", trainable=False)
                loss = ht.reduce_mean_op(
                    ht.softmaxcrossentropy_op(act, y_), [0])
                opt = (opt_cls or ht.optim.AdamOptimizer)(
                    learning_rate=lr)
                train = opt.minimize(loss)
    return x, y_, loss, train


def test_collective_matches_staged_gpipe():
    """pipeline_mode="collective" == staged GPipe losses over several
    Adam steps (same RNG folding, same mean-loss/summed-grad math)."""
    rng = np.random.RandomState(1)
    xv = rng.randn(16, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 16)]

    x, y_, loss, train = _uniform_pipeline()
    exe1 = Executor([loss, train], gpipe=True, num_microbatches=4)
    want = [float(exe1.run(feed_dict={x: xv, y_: yv},
                           convert_to_numpy_ret_vals=True)[0])
            for _ in range(4)]
    assert len(exe1.subexecutors["default"].stages) == 4

    x, y_, loss, train = _uniform_pipeline()
    exe2 = Executor([loss, train], pipeline_mode="collective",
                    num_microbatches=4)
    sub = exe2.subexecutors["default"]
    assert sub.schedule == "collective"
    got = [float(exe2.run(feed_dict={x: xv, y_: yv},
                          convert_to_numpy_ret_vals=True)[0])
           for _ in range(4)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert sub._cpp is not None
    # params written back per stage: training actually moved them
    w0 = np.asarray(exe2.params[str(
        sub.stages[0].param_nodes[0].id)])
    w0_ref = np.asarray(exe1.params[str(
        exe1.subexecutors["default"].stages[0].param_nodes[0].id)])
    np.testing.assert_allclose(w0, w0_ref, rtol=1e-5, atol=1e-6)


def test_collective_rejects_heterogeneous_stages():
    """Stages with mismatched param shapes fail loudly at build time
    (the homogeneity contract), not with an opaque stacking error."""
    rng = np.random.RandomState(2)
    with ht.context(ht.cpu(0)):
        x = ht.Variable("x", trainable=False)
        w0 = ht.Variable("hw0", value=rng.randn(32, 48).astype("f") * .2)
        a = ht.relu_op(ht.matmul_op(x, w0))
    with ht.context(ht.cpu(1)):
        w1 = ht.Variable("hw1", value=rng.randn(48, 10).astype("f") * .2)
        y_ = ht.Variable("y_", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(a, w1), y_), [0])
        train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=2)
    with pytest.raises(ValueError, match="homogeneous"):
        exe.run(feed_dict={
            x: rng.randn(8, 32).astype("f"),
            y_: np.eye(10, dtype="f")[rng.randint(0, 10, 8)]})


def _staged_reference(M=8, steps=3):
    """Staged-GPipe losses for the 4-stage uniform model (computed once
    per session; every collective variant is asserted against it)."""
    rng = np.random.RandomState(11)
    xv = rng.randn(32, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 32)]
    x, y_, loss, train = _uniform_pipeline(seed=5)
    exe = Executor([loss, train], gpipe=True, num_microbatches=M)
    want = [float(exe.run(feed_dict={x: xv, y_: yv},
                          convert_to_numpy_ret_vals=True)[0])
            for _ in range(steps)]
    return xv, yv, want


_STAGED_REF = {}


def _ref(M=8, steps=3):
    if M not in _STAGED_REF:
        _STAGED_REF[M] = _staged_reference(M, steps)
    return _STAGED_REF[M]


@pytest.mark.parametrize("opts", [
    # every tick-loop/feed-transport variant must stay
    # loss-equivalent to the staged runner (ISSUE 1 acceptance)
    {"feed_mode": "replicated", "fuse_ticks": 1,
     "unroll_fill_drain": False},
    {"feed_mode": "sharded", "fuse_ticks": 1, "unroll_fill_drain": False},
    {"feed_mode": "sharded", "fuse_ticks": 2, "unroll_fill_drain": False},
    {"feed_mode": "sharded", "fuse_ticks": 1, "unroll_fill_drain": True},
    {"feed_mode": "sharded", "fuse_ticks": 2, "unroll_fill_drain": True},
], ids=["repl_scan", "shard_scan", "shard_fuse2", "shard_unroll",
        "shard_unroll_fuse2"])
def test_collective_variants_match_staged(opts):
    """Feed sharding, fused double-ticks and unrolled fill/drain change
    the schedule's lowering, never its math: losses match the staged
    GPipe runner over several Adam steps at M=8 > S=4 (so fill, steady
    state and drain all execute)."""
    xv, yv, want = _ref()
    x, y_, loss, train = _uniform_pipeline(seed=5)
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=8, pp_options=opts)
    got = [float(exe.run(feed_dict={x: xv, y_: yv},
                         convert_to_numpy_ret_vals=True)[0])
           for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_collective_bf16_boundary_close_and_learning():
    """bf16 ppermute payloads quantize only the boundary activations
    (compute, loss, grads, optimizer all fp32): losses track the staged
    runner within the DECLARED boundary tolerance
    (collective_pp.BOUNDARY_RTOL = 5e-3 — the same constant the HT805
    interval math is held against, so retuning one retunes both) and
    the model still learns."""
    from hetu_tpu.parallel.collective_pp import BOUNDARY_RTOL
    from hetu_tpu.analysis.numerics import boundary_error_bound
    # the verifier's derivation must cover this test's stage count:
    # a 2-stage pipeline has one bf16 cast hop
    assert boundary_error_bound("bfloat16", hops=1) <= BOUNDARY_RTOL
    xv, yv, want = _ref()
    x, y_, loss, train = _uniform_pipeline(seed=5)
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=8,
                   pp_options={"boundary_dtype": "bf16"})
    got = [float(exe.run(feed_dict={x: xv, y_: yv},
                         convert_to_numpy_ret_vals=True)[0])
           for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=BOUNDARY_RTOL, atol=1e-4)
    assert got[-1] < got[0]


def test_collective_sharded_feeds_reject_shape_change():
    """The sharded feed transport compiles the byte layout into the
    program, so a later run with a different batch size must fail
    loudly — silently packing into the stale layout would train on
    misaligned microbatch rows."""
    rng = np.random.RandomState(12)
    xv = rng.randn(16, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 16)]
    x, y_, loss, train = _uniform_pipeline(seed=6)
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=4)
    exe.run(feed_dict={x: xv, y_: yv})
    with pytest.raises(ValueError, match="changed shape"):
        exe.run(feed_dict={x: xv[:8], y_: yv[:8]})


def test_collective_sgd_and_more_microbatches():
    """SGD path + M > S: schedule fills and drains correctly."""
    rng = np.random.RandomState(3)
    xv = rng.randn(32, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 32)]

    x, y_, loss, train = _uniform_pipeline(
        nstages=2, seed=4, opt_cls=ht.optim.SGDOptimizer, lr=0.05)
    exe1 = Executor([loss, train], gpipe=True, num_microbatches=8)
    want = [float(exe1.run(feed_dict={x: xv, y_: yv},
                           convert_to_numpy_ret_vals=True)[0])
            for _ in range(3)]

    x, y_, loss, train = _uniform_pipeline(
        nstages=2, seed=4, opt_cls=ht.optim.SGDOptimizer, lr=0.05)
    exe2 = Executor([loss, train], pipeline_mode="collective",
                    num_microbatches=8)
    got = [float(exe2.run(feed_dict={x: xv, y_: yv},
                          convert_to_numpy_ret_vals=True)[0])
           for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert want[-1] < want[0]
