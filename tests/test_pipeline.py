"""Pipeline parallelism: GPipe must be loss-equivalent to single-device
full-batch training; PipeDream (1F1B) must match sequential-microbatch
training when depth allows (reference strategy:
examples/runner/parallel/{gpipe,pipedream}.py + validate_results.py)."""
import numpy as np

import hetu_tpu as ht
from hetu_tpu.executor import Executor


def _weights(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w1": rng.randn(20, 32).astype("f") * 0.2,
        "b1": np.zeros(32, "f"),
        "w2": rng.randn(32, 24).astype("f") * 0.2,
        "w3": rng.randn(24, 10).astype("f") * 0.2,
    }


def _data(n=64, seed=1):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 20).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    return x, y


def _build(weights, staged):
    """2-stage MLP: stage0 = fc1 on cpu:0, stage1 = fc2+fc3+loss on cpu:1
    (reference gpipe.py assigns layer blocks with `with ht.context`)."""
    ctx0 = ht.cpu(0) if staged else None
    ctx1 = ht.cpu(1) if staged else None

    def scope(c):
        return ht.context(c) if c is not None else ht.context(ht.cpu(0))

    with scope(ctx0):
        x = ht.Variable("x", trainable=False)
        w1 = ht.Variable("w1", value=weights["w1"])
        b1 = ht.Variable("b1", value=weights["b1"])
        act = ht.matmul_op(x, w1)
        act = ht.relu_op(act + ht.broadcastto_op(b1, act))
    with scope(ctx1):
        w2 = ht.Variable("w2", value=weights["w2"])
        w3 = ht.Variable("w3", value=weights["w3"])
        act2 = ht.relu_op(ht.matmul_op(act, w2))
        logits = ht.matmul_op(act2, w3)
        y_ = ht.Variable("y_", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, y_), [0])
        train_op = ht.optim.SGDOptimizer(learning_rate=0.2).minimize(loss)
    return x, y_, loss, train_op


def _run(exe, x, y_, xs, ys, steps, bs=32):
    out = []
    for i in range(steps):
        s = (i * bs) % len(xs)
        res = exe.run(feed_dict={x: xs[s:s + bs], y_: ys[s:s + bs]})
        out.append(float(np.asarray(res[0].asnumpy()).reshape(()).item()))
    return np.asarray(out)


def test_gpipe_matches_single_device():
    weights = _weights()
    xs, ys = _data()
    x, y_, loss, train_op = _build(weights, staged=False)
    base_exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = _run(base_exe, x, y_, xs, ys, steps=6)

    x, y_, loss, train_op = _build(weights, staged=True)
    pipe_exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
    assert len(pipe_exe.subexecutors["default"].stages) == 2
    pipe = _run(pipe_exe, x, y_, xs, ys, steps=6)
    # gpipe reports mean of per-microbatch losses == full-batch mean loss
    np.testing.assert_allclose(pipe, base, rtol=2e-4, atol=1e-5)


def test_pipedream_runs_and_converges():
    weights = _weights(2)
    xs, ys = _data(64, 3)
    x, y_, loss, train_op = _build(weights, staged=True)
    exe = Executor([loss, train_op], pipedream=True, num_microbatches=4)
    sub = exe.subexecutors["default"]
    assert sub.schedule == "1f1b" and len(sub.stages) == 2
    losses = _run(exe, x, y_, xs, ys, steps=8)
    assert losses[-1] < losses[0], losses


def test_pipedream_weight_stashing_semantics():
    """With 1 microbatch, 1F1B degenerates to sequential training and must
    exactly match the plain executor on the same microbatch size."""
    weights = _weights(4)
    xs, ys = _data(32, 5)
    x, y_, loss, train_op = _build(weights, staged=False)
    base_exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = _run(base_exe, x, y_, xs, ys, steps=5, bs=16)

    x, y_, loss, train_op = _build(weights, staged=True)
    exe = Executor([loss, train_op], pipedream=True, num_microbatches=1)
    pd = _run(exe, x, y_, xs, ys, steps=5, bs=16)
    np.testing.assert_allclose(pd, base, rtol=2e-4, atol=1e-5)


def _build_tp(weights, staged):
    """2 stages x 2 devices each: stage0 col-splits w1 over its pair (TP),
    stage1 batch-splits its activations (DP) — the composed PP+TP/PP+DP
    mode (reference context.py:652-656, test_mlp_mp_pp.py:57-135)."""
    ctx0 = (ht.cpu(0), ht.cpu(1)) if staged else ht.cpu(0)
    ctx1 = (ht.cpu(2), ht.cpu(3)) if staged else ht.cpu(0)

    with ht.context(ctx0):
        x = ht.Variable("x", trainable=False)
        w1 = ht.Variable("w1", value=weights["w1"])
        b1 = ht.Variable("b1", value=weights["b1"])
        w1d = ht.dispatch(w1, (1, 2)) if staged else w1
        act = ht.matmul_op(x, w1d)
        act = ht.relu_op(act + ht.broadcastto_op(b1, act))
        if staged:
            act = ht.dispatch(act, (1, 1))
    with ht.context(ctx1):
        w2 = ht.Variable("w2", value=weights["w2"])
        w3 = ht.Variable("w3", value=weights["w3"])
        act = ht.dispatch(act, (2, 1)) if staged else act
        act2 = ht.relu_op(ht.matmul_op(act, w2))
        logits = ht.matmul_op(act2, w3)
        y_ = ht.Variable("y_", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, y_), [0])
        train_op = ht.optim.SGDOptimizer(learning_rate=0.2).minimize(loss)
    return x, y_, loss, train_op


def test_gpipe_with_tp_and_dp_stages():
    weights = _weights(7)
    xs, ys = _data(64, 8)
    x, y_, loss, train_op = _build_tp(weights, staged=False)
    base_exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = _run(base_exe, x, y_, xs, ys, steps=6)

    x, y_, loss, train_op = _build_tp(weights, staged=True)
    exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
    sub = exe.subexecutors["default"]
    assert len(sub.stages) == 2
    assert sub.stages[0].mesh is not None, "stage0 should have a TP mesh"
    assert sub.stages[1].mesh is not None, "stage1 should have a DP mesh"
    pipe = _run(exe, x, y_, xs, ys, steps=6)
    np.testing.assert_allclose(pipe, base, rtol=2e-4, atol=1e-5)
    # the dispatched w1 must be *stored* sharded over stage0's pair
    w1_node = next(p for p in sub.stages[0].param_nodes if p.name == "w1")
    arr = sub.stages[0].params[str(w1_node.id)]
    assert len(arr.sharding.device_set) == 2


def test_pipedream_with_tp_stage():
    weights = _weights(9)
    xs, ys = _data(64, 10)
    x, y_, loss, train_op = _build_tp(weights, staged=False)
    base_exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = _run(base_exe, x, y_, xs, ys, steps=5, bs=16)

    x, y_, loss, train_op = _build_tp(weights, staged=True)
    exe = Executor([loss, train_op], pipedream=True, num_microbatches=1)
    pd = _run(exe, x, y_, xs, ys, steps=5, bs=16)
    np.testing.assert_allclose(pd, base, rtol=2e-4, atol=1e-5)


def test_explicit_send_recv_markers():
    """Reference-style explicit pipeline_send/receive markers between
    stages: spliced by the planner, same losses as the marker-free
    graph (ops/comm.py PipelineSendOp/PipelineReceiveOp)."""
    rng = np.random.RandomState(11)
    w1v = rng.randn(12, 10).astype("f") * 0.3
    w2v = rng.randn(10, 4).astype("f") * 0.3
    xs = rng.randn(8, 12).astype("f")
    ys = np.eye(4, dtype="f")[rng.randint(0, 4, 8)]

    def build(markers):
        with ht.context(ht.cpu(0)):
            x = ht.Variable("sr_x", trainable=False)
            w1 = ht.Variable("sr_w1", value=w1v)
            a = ht.relu_op(ht.matmul_op(x, w1))
            if markers:
                a = ht.pipeline_send_op(a, destination=1)
        with ht.context(ht.cpu(1)):
            if markers:
                recv = ht.pipeline_receive_op(source=0)
                # reference pairing: the recv stands in for the sent value
                a_in = recv
            else:
                a_in = a
            w2 = ht.Variable("sr_w2", value=w2v)
            y_ = ht.Variable("sr_y", trainable=False)
            logits = ht.matmul_op(a_in, w2)
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_op(logits, y_), [0])
            train = ht.optim.SGDOptimizer(0.1).minimize(loss)
        return x, y_, loss, train

    x, y_, loss, train = build(markers=False)
    exe = Executor([loss, train], gpipe=True, num_microbatches=2)
    want = [float(np.asarray(exe.run(feed_dict={x: xs, y_: ys}
                                     )[0].asnumpy())) for _ in range(3)]

    x2, y2, loss2, train2 = build(markers=True)
    exe2 = Executor([loss2, train2], gpipe=True, num_microbatches=2)
    got = [float(np.asarray(exe2.run(feed_dict={x2: xs, y2: ys}
                                     )[0].asnumpy())) for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_lr_scheduler_advances_per_global_step():
    """Pinned round-4 semantics: the LR scheduler advances once per
    GLOBAL step under both GPipe and PipeDream — a StepScheduler must
    decay identically on the same config regardless of schedule or
    microbatch count (pipeline.py module docstring)."""
    from hetu_tpu.lr_scheduler import StepScheduler

    for mode, M in (("gpipe", 4), ("pipedream", 2)):
        weights = _weights(3)
        xs, ys = _data(64, 4)
        x, y_, loss, train_op = _build(weights, staged=True)
        exe = Executor([loss, train_op], num_microbatches=M,
                       **({"gpipe": True} if mode == "gpipe"
                          else {"pipedream": True}))
        sched = StepScheduler(0.2, step_size=1, gamma=0.5)
        opt = exe.subexecutors["default"].optimizer
        opt.lr_sched = sched
        for i in range(3):
            exe.run(feed_dict={x: xs[:32], y_: ys[:32]})
        assert sched.cnt == 3, (mode, sched.cnt)
        # after 3 steps the rate decayed exactly 3 halvings, not 3*M
        assert abs(sched.get() - 0.2 * 0.5 ** 3) < 1e-12


def test_gpipe_compiled_dispatch_count():
    """The compiled GPipe step is 2S-1 stage-program dispatches (one
    fwd_block per producing stage, one fused bwd_block per stage) —
    the round-4 redesign target (round-3 review weak #1)."""
    weights = _weights(5)
    xs, ys = _data(64, 6)
    x, y_, loss, train_op = _build(weights, staged=True)
    exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
    exe.run(feed_dict={x: xs[:32], y_: ys[:32]})  # builds blocks
    sub = exe.subexecutors["default"]
    calls = []
    for st in sub.stages:
        for attr in ("fwd_block", "bwd_block"):
            fn = getattr(st, attr)
            if fn is None:
                continue

            def counted(*a, _fn=fn, _tag=(st.index, attr), **kw):
                calls.append(_tag)
                return _fn(*a, **kw)

            setattr(st, attr, counted)
    exe.run(feed_dict={x: xs[:32], y_: ys[:32]})
    # stage0 fwd + stage1 fused fwd/bwd + stage0 bwd = 3 programs; the
    # terminal stage never needs a separate forward dispatch
    assert calls == [(0, "fwd_block"), (1, "bwd_block"),
                     (0, "bwd_block")], calls


def test_single_device_stages_fuse_to_one_program():
    """When every stage resolves to the same physical chip (device ids
    congruent mod the device count), the whole GPipe step compiles into
    ONE dispatch — and stays loss-equivalent to the unfused run."""
    import jax

    n = len(jax.devices())
    weights = _weights(12)
    xs, ys = _data(64, 13)

    x, y_, loss, train_op = _build(weights, staged=False)
    base_exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = _run(base_exe, x, y_, xs, ys, steps=4)

    def build_samedev():
        with ht.context(ht.cpu(0)):
            xx = ht.Variable("x", trainable=False)
            w1 = ht.Variable("w1", value=weights["w1"])
            b1 = ht.Variable("b1", value=weights["b1"])
            act = ht.matmul_op(xx, w1)
            act = ht.relu_op(act + ht.broadcastto_op(b1, act))
        with ht.context(ht.cpu(n)):   # distinct stage key, same device
            w2 = ht.Variable("w2", value=weights["w2"])
            w3 = ht.Variable("w3", value=weights["w3"])
            act2 = ht.relu_op(ht.matmul_op(act, w2))
            logits = ht.matmul_op(act2, w3)
            yy = ht.Variable("y_", trainable=False)
            ls = ht.reduce_mean_op(
                ht.softmaxcrossentropy_op(logits, yy), [0])
            tr = ht.optim.SGDOptimizer(learning_rate=0.2).minimize(ls)
        return xx, yy, ls, tr

    xx, yy, ls, tr = build_samedev()
    exe = Executor([ls, tr], gpipe=True, num_microbatches=4)
    sub = exe.subexecutors["default"]
    assert len(sub.stages) == 2
    got = _run(exe, xx, yy, xs, ys, steps=4)
    assert sub._fused_step is not None, \
        "co-resident stages must fuse into a whole-step program"
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)

    # pipedream variant: fused whole-schedule trace, still trains
    xx, yy, ls, tr = build_samedev()
    exe2 = Executor([ls, tr], pipedream=True, num_microbatches=2)
    sub2 = exe2.subexecutors["default"]
    losses = _run(exe2, xx, yy, xs, ys, steps=6)
    assert sub2._fused_step is not None
    assert losses[-1] < losses[0], losses


def test_group_allreduce_subgroup_semantics():
    """GroupAllReduceCommunicateOp pmeans over its named mesh sub-axis
    only (the reference's NCCL group comm)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from hetu_tpu.ops.comm import GroupAllReduceCommunicateOp
    from hetu_tpu.graph.node import ExecContext

    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, axis_names=("a", "b"))
    xn = ht.Variable("ga_x", trainable=False)
    op = GroupAllReduceCommunicateOp(xn, group="b")
    ectx = ExecContext(training=False)

    x = np.arange(8, dtype=np.float32).reshape(4, 2)

    def body(v):
        return op.compute([v], ectx)

    out = shard_map(body, mesh=mesh, in_specs=P("a", "b"),
                    out_specs=P("a", "b"))(x)
    want = np.repeat(x.mean(axis=1, keepdims=True), 2, axis=1)
    np.testing.assert_allclose(np.asarray(out), want)
