"""lax.scan training blocks (Executor.run_batches) must be step-for-step
identical to sequential Executor.run calls — the block is the same step
function threaded through a scan carry instead of a host loop."""
import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.ps import client as ps_client
from hetu_tpu.ps import server as ps_server


def _mlp(lr=0.05):
    x = ht.Variable("rb_x", trainable=False)
    y_ = ht.Variable("rb_y", trainable=False)
    w1 = ht.init.xavier_normal((20, 16), name="rb_w1")
    w2 = ht.init.xavier_normal((16, 4), name="rb_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    out = ht.matmul_op(h, w2)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(out, y_), [0])
    train = ht.optim.SGDOptimizer(lr).minimize(loss)
    return x, y_, loss, train


def _batches(rng, steps, batch=8):
    return [{"x": rng.randn(batch, 20).astype(np.float32),
             "y": np.eye(4, dtype=np.float32)[rng.randint(0, 4, batch)]}
            for _ in range(steps)]


def test_block_matches_sequential():
    rng = np.random.RandomState(0)
    data = _batches(rng, 12)

    x, y_, loss, train = _mlp()
    exe = Executor([loss, train])
    want = [float(exe.run(feed_dict={x: d["x"], y_: d["y"]},
                          convert_to_numpy_ret_vals=True)[0])
            for d in data]

    x2, y2, loss2, train2 = _mlp()
    exe2 = Executor([loss2, train2])
    res = exe2.run_batches([{x2: d["x"], y2: d["y"]} for d in data],
                           convert_to_numpy_ret_vals=True)
    got = [float(r[0]) for r in res]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # params identical afterwards
    for sid in exe.params:
        node = exe._param_nodes[sid]
        twin = [s for s, n in exe2._param_nodes.items()
                if n.name == node.name][0]
        np.testing.assert_allclose(np.asarray(exe.params[sid]),
                                   np.asarray(exe2.params[twin]), rtol=1e-5)


def test_block_advances_lr_schedule():
    """Per-step learning rates inside a block must follow the scheduler
    exactly as sequential run() calls do."""
    from hetu_tpu.lr_scheduler import StepScheduler

    rng = np.random.RandomState(3)
    data = _batches(rng, 8)

    def build():
        x = ht.Variable("lrb_x", trainable=False)
        y_ = ht.Variable("lrb_y", trainable=False)
        w1 = ht.init.xavier_normal((20, 4), name="lrb_w")
        out = ht.matmul_op(x, w1)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(out, y_), [0])
        sched = StepScheduler(0.1, step_size=2, gamma=0.5)
        train = ht.optim.SGDOptimizer(sched).minimize(loss)
        return x, y_, loss, train

    x, y_, loss, train = build()
    exe = Executor([loss, train])
    want = [float(exe.run(feed_dict={x: d["x"], y_: d["y"]},
                          convert_to_numpy_ret_vals=True)[0])
            for d in data]

    x2, y2, loss2, train2 = build()
    exe2 = Executor([loss2, train2])
    res = exe2.run_batches([{x2: d["x"], y2: d["y"]} for d in data],
                           convert_to_numpy_ret_vals=True)
    got = [float(r[0]) for r in res]
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.fixture()
def ps_env():
    port = ps_server.pick_free_port()
    os.environ["HETU_PS_PORTS"] = str(port)
    os.environ["HETU_PS_HOSTS"] = "127.0.0.1"
    ps_server.ensure_server(port=port, nworkers=1)
    client = ps_client.PSClient(rank=0, nworkers=1)
    ps_client.set_default_client(client)
    yield client
    client.shutdown_servers()
    ps_client.close_default_client()
    ps_server.shutdown_server()


def _embed_model(table_value, lr=0.1):
    ids = ht.Variable("rb_ids", trainable=False)
    y_ = ht.Variable("rb_ey", trainable=False)
    table = ht.Variable("rb_table", value=table_value)
    w = ht.Variable("rb_ew", value=np.full((4, 2), 0.3, np.float32))
    rows = ht.embedding_lookup_op(table, ids)
    pred = ht.matmul_op(ht.reduce_sum_op(rows, [1]), w)
    diff = pred + (-1) * y_
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    train = ht.optim.SGDOptimizer(lr).minimize(loss)
    return ids, y_, loss, train


def test_ps_device_cache_block_matches_sequential(ps_env):
    rng = np.random.RandomState(1)
    table = rng.randn(60, 4).astype(np.float32)
    data = [(rng.randint(0, 60, (8, 3)),
             rng.randn(8, 2).astype(np.float32)) for _ in range(12)]

    ids, y_, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", cstable_policy="Device",
                   cache_bound=5)
    want = [float(exe.run(feed_dict={ids: i, y_: y},
                          convert_to_numpy_ret_vals=True)[0])
            for i, y in data]
    exe.close()

    ids2, y2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="PS",
                    cstable_policy="Device", cache_bound=5)
    got = []
    for chunk in (data[:4], data[4:8], data[8:]):
        res = exe2.run_batches([{ids2: i, y2: y} for i, y in chunk],
                               convert_to_numpy_ret_vals=True)
        got.extend(float(r[0]) for r in res)
    rt = next(iter(exe2.ps_runtime.device_tables.values()))
    exe2.ps_runtime.drain()
    # server agrees with the device cache after drain
    cache = np.asarray(exe2.params[rt.cache_sid])
    touched = np.nonzero(rt.id_of >= 0)[0]
    server_rows = ps_env.sparse_pull(rt.tid, rt.id_of[touched], rt.width)
    np.testing.assert_allclose(server_rows, cache[touched], rtol=1e-4)
    exe2.close()
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_ps_block_eviction_matches_sequential(ps_env):
    """Blocks under cache pressure: pins hold every in-block row, misses
    across the block fill before dispatch."""
    rng = np.random.RandomState(2)
    table = rng.randn(64, 4).astype(np.float32)
    data = [(rng.randint(0, 64, (8, 3)),
             rng.randn(8, 2).astype(np.float32)) for _ in range(16)]

    ids, y_, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", cstable_policy="Device",
                   cache_bound=4, cache_capacity=56)
    want = [float(exe.run(feed_dict={ids: i, y_: y},
                          convert_to_numpy_ret_vals=True)[0])
            for i, y in data]
    exe.close()

    ids2, y2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="PS",
                    cstable_policy="Device", cache_bound=4,
                    cache_capacity=56)
    got = []
    for k in range(0, 16, 2):
        res = exe2.run_batches(
            [{ids2: i, y2: y} for i, y in data[k:k + 2]],
            convert_to_numpy_ret_vals=True)
        got.extend(float(r[0]) for r in res)
    rt = next(iter(exe2.ps_runtime.device_tables.values()))
    assert rt.evicts > 0
    exe2.close()
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_ps_stream_matches_run_batches(ps_env):
    """run_batches_stream (double-buffered feed ingest on a lookahead
    thread) trains identically to sequential run_batches on the
    device-cache path — the overlap must not reorder stateful work."""
    rng = np.random.RandomState(3)
    table = rng.randn(60, 4).astype(np.float32)
    data = [(rng.randint(0, 60, (8, 3)),
             rng.randn(8, 2).astype(np.float32)) for _ in range(12)]
    blocks = [data[:4], data[4:8], data[8:]]

    ids, y_, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", cstable_policy="Device",
                   cache_bound=5)
    for chunk in blocks:
        out = exe.run_batches([{ids: i, y_: y} for i, y in chunk],
                              convert_to_numpy_ret_vals=True)
    want_last = float(out[-1][0])
    rt = next(iter(exe.ps_runtime.device_tables.values()))
    exe.ps_runtime.drain()
    want_cache = np.asarray(exe.params[rt.cache_sid]).copy()
    want_ids = rt.id_of.copy()
    exe.close()

    ids2, y2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="PS",
                    cstable_policy="Device", cache_bound=5)
    out2 = exe2.run_batches_stream(
        ([{ids2: i, y2: y} for i, y in chunk] for chunk in blocks),
        convert_to_numpy_ret_vals=True)
    got_last = float(out2[-1][0])
    rt2 = next(iter(exe2.ps_runtime.device_tables.values()))
    exe2.ps_runtime.drain()
    got_cache = np.asarray(exe2.params[rt2.cache_sid])
    np.testing.assert_allclose(got_last, want_last, rtol=1e-5)
    np.testing.assert_array_equal(rt2.id_of, want_ids)
    np.testing.assert_allclose(got_cache, want_cache, rtol=1e-5)
    assert exe2.ps_runtime.times["feed_ingest"] >= 0.0
    exe2.close()


def test_ps_stream_lookahead_depths_match(ps_env):
    """The configurable ingest lookahead (default 2; 1 = the classic
    double-buffer, kept reachable for the overhead guard) must train
    identically at any depth — deeper lookahead changes WHEN feeds
    transfer, never what the steps compute."""
    rng = np.random.RandomState(7)
    table = rng.randn(60, 4).astype(np.float32)
    data = [(rng.randint(0, 60, (8, 3)),
             rng.randn(8, 2).astype(np.float32)) for _ in range(16)]
    blocks = [data[:4], data[4:8], data[8:12], data[12:]]

    ids, y_, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", cstable_policy="Device",
                   cache_bound=5)
    for chunk in blocks:
        out = exe.run_batches([{ids: i, y_: y} for i, y in chunk],
                              convert_to_numpy_ret_vals=True)
    want_last = float(out[-1][0])
    rt = next(iter(exe.ps_runtime.device_tables.values()))
    exe.ps_runtime.drain()
    want_cache = np.asarray(exe.params[rt.cache_sid]).copy()
    exe.close()

    for lookahead in (1, 3):
        ids2, y2, loss2, train2 = _embed_model(table)
        exe2 = Executor([loss2, train2], comm_mode="PS",
                        cstable_policy="Device", cache_bound=5)
        out2 = exe2.run_batches_stream(
            ([{ids2: i, y2: y} for i, y in chunk] for chunk in blocks),
            convert_to_numpy_ret_vals=True, lookahead=lookahead)
        got_last = float(out2[-1][0])
        rt2 = next(iter(exe2.ps_runtime.device_tables.values()))
        exe2.ps_runtime.drain()
        got_cache = np.asarray(exe2.params[rt2.cache_sid])
        np.testing.assert_allclose(got_last, want_last, rtol=1e-5,
                                   err_msg=f"lookahead={lookahead}")
        np.testing.assert_allclose(got_cache, want_cache, rtol=1e-5,
                                   err_msg=f"lookahead={lookahead}")
        exe2.close()

    with pytest.raises(ValueError, match="lookahead"):
        exe2.run_batches_stream(iter([]), lookahead=0)


def _softmax_model(prefix):
    """Same 1-layer softmax model under a name prefix (two fresh graphs
    with identical init values, the file's _embed_model convention)."""
    rng = np.random.RandomState(5)
    x = ht.Variable(prefix + "_x", trainable=False)
    y_ = ht.Variable(prefix + "_y", trainable=False)
    w = ht.Variable(prefix + "_w", value=rng.randn(8, 4).astype("f") * 0.3)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return x, y_, w, loss, train


def test_stream_non_ps_matches_run_batches():
    """run_batches_stream on a plain (non-PS) executor falls back to the
    scan-block path with identical results."""
    rng = np.random.RandomState(6)
    raw = [(rng.randn(16, 8).astype("f"),
            np.eye(4, dtype="f")[rng.randint(0, 4, 16)])
           for _ in range(6)]

    x, y_, w, loss, train = _softmax_model("s")
    data = [{x: d, y_: y} for d, y in raw]
    exe = Executor([loss, train])
    for chunk in (data[:3], data[3:]):
        out = exe.run_batches(chunk, convert_to_numpy_ret_vals=True)
    want = float(out[-1][0])
    want_w = np.asarray(exe.params[str(w.id)])

    x2, y2, w2, loss2, train2 = _softmax_model("s2")
    data2 = [{x2: d, y2: y} for d, y in raw]
    exe2 = Executor([loss2, train2])
    out2 = exe2.run_batches_stream(
        (c for c in (data2[:3], data2[3:])), convert_to_numpy_ret_vals=True)
    got = float(out2[-1][0])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(exe2.params[str(w2.id)]),
                               want_w, rtol=1e-5)


@pytest.mark.parametrize("dtype", [None, "bfloat16"], ids=["f32", "bf16"])
def test_evaluation_block_matches_sequential(dtype):
    """A block of an INFERENCE subgraph: the step hands no tree back, so
    the scan carries none; the losses are the sequential ones and the
    parameters are left as they were."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    data = _batches(rng, 5)
    x, y_, loss, train = _mlp()
    kw = {} if dtype is None else {"dtype": getattr(jnp, dtype)}
    exe = Executor({"train": [loss, train], "validate": [loss]}, **kw)
    feeds = [{x: d["x"], y_: d["y"]} for d in data]
    exe.run("train", feed_dict=feeds[0])
    before = {sid: np.asarray(v) for sid, v in exe.params.items()}
    want = [float(exe.run("validate", feed_dict=fd,
                          convert_to_numpy_ret_vals=True)[0])
            for fd in feeds]
    got = [float(r[0]) for r in exe.run_batches(
        feeds, "validate", convert_to_numpy_ret_vals=True)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for sid, v in exe.params.items():
        np.testing.assert_array_equal(np.asarray(v), before[sid])
