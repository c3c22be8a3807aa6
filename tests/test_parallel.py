"""Parallelism correctness: loss-trajectory equivalence between parallel
configs and the single-device ground truth (reference strategy:
examples/runner/parallel/validate_results.py — base run saves base.npy,
each parallel config must match allclose).

Runs on the 8-device virtual CPU platform from conftest.py.
"""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.parallel import factorized_axes, spec_for_status
from hetu_tpu.context import NodeStatus


def _fixed_weights(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w1": rng.randn(32, 64).astype("f") * 0.1,
        "b1": np.zeros(64, "f"),
        "w2": rng.randn(64, 48).astype("f") * 0.1,
        "w3": rng.randn(48, 10).astype("f") * 0.1,
    }


def _data(seed=1, n=64):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 32).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)]
    return x, y


def _mlp_losses(split=None, steps=6, lr=0.1):
    """split: None (base) or a pair (act_parts, w_parts) applied around the
    middle matmul — mirroring test_mlp_mp.py's left/right/middle cases."""
    weights = _fixed_weights()
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    w1 = ht.Variable("w1", value=weights["w1"])
    b1 = ht.Variable("b1", value=weights["b1"])
    w2 = ht.Variable("w2", value=weights["w2"])
    w3 = ht.Variable("w3", value=weights["w3"])

    act = ht.matmul_op(x, w1)
    act = ht.relu_op(act + ht.broadcastto_op(b1, act))
    if split is not None:
        act_parts, w_parts = split
        act = ht.dispatch(act, act_parts)
        w2d = ht.dispatch(w2, w_parts)
    else:
        w2d = w2
    act = ht.matmul_op(act, w2d)
    if split is not None:
        act = ht.dispatch(act, (1, 1))
    act = ht.relu_op(act)
    logits = ht.matmul_op(act, w3)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
    train_op = ht.optim.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = Executor([loss, train_op], ctx=ht.cpu(0))

    xs, ys = _data()
    out = []
    for i in range(steps):
        s = (i * 16) % 64
        res = exe.run(feed_dict={x: xs[s:s + 16], y_: ys[s:s + 16]})
        out.append(res[0].asnumpy().item())
    return np.asarray(out), exe


BASE = None


def _base():
    global BASE
    if BASE is None:
        BASE = _mlp_losses(None)[0]
    return BASE


@pytest.mark.parametrize("name,split", [
    ("left",   ((2, 1), (1, 1))),   # row-split activation
    ("right",  ((1, 1), (1, 2))),   # col-split weight
    ("middle", ((1, 2), (2, 1))),   # k-split (partial-sum contraction)
    ("grid",   ((2, 2), (2, 1))),   # 2D split
    ("wide",   ((1, 1), (1, 4))),   # 4-way col split
    ("row4",   ((4, 1), (1, 1))),   # 4-way row split
])
def test_mlp_tp_loss_equivalence(name, split):
    losses, exe = _mlp_losses(split)
    np.testing.assert_allclose(losses, _base(), rtol=2e-4, atol=1e-5,
                               err_msg=f"TP split {name} diverged")
    assert exe.config.mesh is not None


def test_param_is_sharded():
    """A dispatched weight must be *stored* sharded (the TP memory win)."""
    _, exe = _mlp_losses(((1, 1), (1, 2)))
    w2 = next(v for k, v in exe.params.items()
              if exe._param_nodes[k].name == "w2")
    shardings = {d.device.id for d in w2.addressable_shards}
    assert len(shardings) >= 2
    # each shard holds half the columns
    shard_shape = w2.addressable_shards[0].data.shape
    assert shard_shape == (64, 24), shard_shape


def test_spec_lowering():
    axes = factorized_axes(8)          # {tp0:2, tp1:2, tp2:2}
    st = NodeStatus((2, 2))
    st.get_default()
    spec = spec_for_status(st, axes)
    assert tuple(spec) == ("tp0", "tp1")
    st4 = NodeStatus((4, 1))
    st4.get_default()
    spec4 = spec_for_status(st4, axes)
    assert tuple(spec4) == (("tp0", "tp1"),)
    st8 = NodeStatus((1, 8))
    st8.get_default()
    assert tuple(spec_for_status(st8, axes)) == (None, ("tp0", "tp1", "tp2"))


def test_spec_lowering_warns_on_unmappable(caplog):
    """A distributed status the planner cannot map is left unconstrained
    (numerics safe) but must WARN naming the node and status — silently
    forfeiting the split the user asked for was round-5 review #7."""
    import logging
    axes = factorized_axes(4)          # {tp0:2, tp1:2}
    st = NodeStatus((3, 1))            # 3-way split: no axis of size 3
    st.get_default()
    with caplog.at_level(logging.WARNING,
                         logger="hetu_tpu.parallel.planner"):
        assert spec_for_status(st, axes, node="MatMulOp(w_proj)") is None
    msgs = [r.getMessage() for r in caplog.records]
    assert any("MatMulOp(w_proj)" in m and "unmappable" in m
               for m in msgs), msgs


def test_dp_loss_equivalence():
    """8-way data parallelism over the mesh matches single-device: the
    global batch is sharded on dp; grads reduce implicitly in XLA."""
    from jax.sharding import Mesh
    import jax
    weights = _fixed_weights()
    xs, ys = _data()

    def build():
        x = ht.Variable("x", trainable=False)
        y_ = ht.Variable("y_", trainable=False)
        w1 = ht.Variable("w1", value=weights["w1"])
        b1 = ht.Variable("b1", value=weights["b1"])
        w2 = ht.Variable("w2", value=weights["w2"])
        w3 = ht.Variable("w3", value=weights["w3"])
        act = ht.matmul_op(x, w1)
        act = ht.relu_op(act + ht.broadcastto_op(b1, act))
        act = ht.relu_op(ht.matmul_op(act, w2))
        logits = ht.matmul_op(act, w3)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
        train_op = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        return x, y_, loss, train_op

    x, y_, loss, train_op = build()
    exe = Executor([loss, train_op], ctx=ht.cpu(0))
    base = [exe.run(feed_dict={x: xs[i * 16:(i + 1) * 16],
                               y_: ys[i * 16:(i + 1) * 16]}
                    )[0].asnumpy().item() for i in range(4)]

    x, y_, loss, train_op = build()
    mesh = Mesh(np.asarray(jax.devices()[:8]), axis_names=("dp",))
    from hetu_tpu.executor import HetuConfig
    config = HetuConfig(eval_node_list=[loss, train_op], mesh=mesh)
    config.nrank = 8
    exe8 = Executor({"default": [loss, train_op]}, config=config)
    dp = [exe8.run(feed_dict={x: xs[i * 16:(i + 1) * 16],
                              y_: ys[i * 16:(i + 1) * 16]}
                   )[0].asnumpy().item() for i in range(4)]
    np.testing.assert_allclose(dp, base, rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# deduce_states rules (reference per-op tables, e.g. MatrixMult.py:88-141)
# ---------------------------------------------------------------------------

def _st(state, dup=1):
    st = NodeStatus(state, duplicate=dup)
    st.get_default()
    return st


def _deduce(node, in_states):
    out = NodeStatus()
    node.deduce_states(in_states, out, False)
    return out


def test_deduce_batch_matmul():
    from hetu_tpu.ops.linalg import batch_matmul_op
    a = ht.Variable("a", trainable=False)
    b = ht.Variable("b", trainable=False)
    node = batch_matmul_op(a, b)
    # batch split on A, col split on B
    out = _deduce(node, [_st((2, 1, 1)), _st((1, 1, 2))])
    assert out.state == (2, 1, 2)
    # k-split contraction folds into duplicate
    out = _deduce(node, [_st((1, 1, 2)), _st((1, 2, 1))])
    assert out.state == (1, 1, 1) and out.duplicate == 2


def test_deduce_conv2d():
    from hetu_tpu.ops.conv import conv2d_op
    a = ht.Variable("a", trainable=False)
    f = ht.Variable("f", trainable=False)
    node = conv2d_op(a, f)
    # batch split + out-channel split
    out = _deduce(node, [_st((2, 1, 1, 1)), _st((2, 1, 1, 1))])
    assert out.state == (2, 2, 1, 1)
    # in-channel contraction -> duplicate
    out = _deduce(node, [_st((1, 2, 1, 1)), _st((1, 2, 1, 1))])
    assert out.state == (1, 1, 1, 1) and out.duplicate == 2


def test_deduce_embedding():
    from hetu_tpu.ops.embedding import embedding_lookup_op
    t = ht.Variable("t", trainable=False)
    i = ht.Variable("i", trainable=False)
    node = embedding_lookup_op(t, i)
    # vocab-sharded table -> duplicate; index batch split passes through
    out = _deduce(node, [_st((4, 1)), _st((2,))])
    assert out.state == (2, 1) and out.duplicate == 4
    # feature-dim table split splits the output feature dim
    out = _deduce(node, [_st((1, 2)), _st((2,))])
    assert out.state == (2, 2)


def test_deduce_shape_ops():
    from hetu_tpu.ops.shape import (array_reshape_op, concat_op,
                                    reduce_sum_op, split_op, transpose_op)
    a = ht.Variable("a", trainable=False)
    b = ht.Variable("b", trainable=False)
    # transpose permutes splits
    out = _deduce(transpose_op(a, [1, 0]), [_st((2, 4))])
    assert out.state == (4, 2)
    # concat folds the concat axis into duplicate, keeps the others
    out = _deduce(concat_op(a, b, axis=0), [_st((2, 4)), _st((2, 4))])
    assert out.state == (1, 4) and out.duplicate == 2
    # reduce folds reduced-axis splits into duplicate (partial sums)
    out = _deduce(reduce_sum_op(a, [0]), [_st((2, 4))])
    assert out.state == (4,) and out.duplicate == 2
    # reshape keeps only the leading split
    out = _deduce(array_reshape_op(a, [-1, 8]), [_st((2, 4))])
    assert out.state == (2, 1) and out.duplicate == 4
    # split forces the sliced axis unsplit
    out = _deduce(split_op(a, [1], [0], [2]), [_st((2, 4))])
    assert out.state == (2, 1)


def test_order_algebra_matches_named_sharding():
    """NodeStatus.map_dev_to_index / get_loop_sizes vs jax: a mesh whose
    axes follow ``order`` (major->minor) must place shards on exactly the
    devices the reference device-index algebra predicts
    (reference context.py:254-285)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    R, C = 8, 8
    for state, dup, order in [
        ((2, 2), 2, (-1, 0, 1)),
        ((2, 2), 2, (0, -1, 1)),
        ((4, 1), 2, (1, 0, -1)),
    ]:
        st = NodeStatus(state, duplicate=dup, order=order)
        # loop_sizes[k] = stride of order[k] in the flat device index
        sizes = {(-1 if d < 0 else d): (dup if d < 0 else state[d])
                 for d in order}
        expect_loops = []
        for k in range(len(order)):
            n = 1
            for d in order[k + 1:]:
                n *= sizes[-1 if d < 0 else d]
            expect_loops.append(n)
        assert st.get_loop_sizes() == expect_loops

        axis_names = tuple("dup" if d < 0 else f"a{d}" for d in order)
        axis_sizes = tuple(sizes[-1 if d < 0 else d] for d in order)
        ndev = int(np.prod(axis_sizes))
        devs = np.asarray(jax.devices("cpu")[:ndev]).reshape(axis_sizes)
        mesh = Mesh(devs, axis_names)
        spec = PartitionSpec(*[f"a{i}" if state[i] > 1 else None
                               for i in range(len(state))])
        sharding = NamedSharding(mesh, spec)
        imap = sharding.devices_indices_map((R, C))
        flat = list(devs.reshape(-1))
        for g, dev in enumerate(flat):
            coords = st.map_dev_to_index(g)
            idx = imap[dev]
            for dim, coord in enumerate(coords):
                size = (R, C)[dim] // state[dim]
                sl = idx[dim]
                start = 0 if sl.start is None else sl.start
                assert start == coord * size, (
                    f"state={state} order={order} dev {g} dim {dim}: "
                    f"algebra says shard {coord}, jax says {sl}")


def test_bert_style_layer_tp_equivalence():
    """A mini attention+FFN block with batch_matmul/transpose/reshape under
    a TP dispatch must stay loss-equivalent with the base run (reference
    test_mlp_mp_pp.py strategy applied to the attention ops)."""
    B, S, H, NH = 4, 8, 16, 2
    rng = np.random.RandomState(3)
    wq = rng.randn(H, H).astype("f") * 0.2
    wo = rng.randn(H, H).astype("f") * 0.2
    xs = rng.randn(B * S, H).astype("f")
    ys = np.eye(10, dtype=np.float32)[rng.randint(0, 10, B)]
    wc = rng.randn(H, 10).astype("f") * 0.2

    def build(tp):
        x = ht.Variable("x", trainable=False)
        y_ = ht.Variable("y_", trainable=False)
        vq = ht.Variable("wq", value=wq.copy())
        vo = ht.Variable("wo", value=wo.copy())
        vc = ht.Variable("wc", value=wc.copy())
        q2 = ht.matmul_op(x, ht.dispatch(vq, (1, 2)) if tp else vq)
        q = ht.transpose_op(
            ht.array_reshape_op(q2, [B, S, NH, H // NH]), [0, 2, 1, 3])
        scores = ht.batch_matmul_op(q, q, trans_B=True)
        probs = ht.softmax_op(scores)
        ctxv = ht.batch_matmul_op(probs, q)
        merged = ht.array_reshape_op(
            ht.transpose_op(ctxv, [0, 2, 1, 3]), [B * S, H])
        h = ht.matmul_op(merged, vo)
        if tp:
            h = ht.dispatch(h, (1, 1))
        pooled = ht.reduce_mean_op(
            ht.array_reshape_op(h, [B, S, H]), [1])
        logits = ht.matmul_op(pooled, vc)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(logits, y_), [0])
        train_op = ht.optim.SGDOptimizer(0.05).minimize(loss)
        exe = Executor([loss, train_op], ctx=ht.cpu(0))
        out = []
        for _ in range(4):
            res = exe.run(feed_dict={x: xs, y_: ys})
            out.append(res[0].asnumpy().item())
        return np.asarray(out), exe

    base, _ = build(False)
    tp, exe = build(True)
    np.testing.assert_allclose(tp, base, rtol=2e-4, atol=1e-5)
    assert exe.config.mesh is not None
