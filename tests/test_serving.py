"""Online inference subsystem (hetu_tpu/serving/): frozen-graph
sessions with bounded-compile shape bucketing, dynamic micro-batching,
the plain GPT serving forward pinned against the graph's, PS-backed
read-only embedding serving, and the checkpoint-layout satellites
(save-collision / load-missing / sharding-preserving state restore)."""
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.executor import Executor
from hetu_tpu.models.gpt import gpt_forward, gpt_serving_params
from hetu_tpu.serving import (InferenceSession, MicroBatcher,
                              ServingHTTPServer, next_bucket,
                              serve_embeddings_from_ps)

from gpt_reference import VOCAB, full_forward, gpt_session


def _tel():
    return telemetry.Telemetry(enabled=True)


# ---------------------------------------------------------------------------
# session: bucketing + frozen contract
# ---------------------------------------------------------------------------

def _linear_graph(seed=0):
    rng = np.random.RandomState(seed)
    w = ht.Variable("w", value=rng.randn(20, 4).astype("f"))
    x = ht.Variable("x", trainable=False)
    return x, ht.matmul_op(x, w), np.asarray(rng.randn(20, 4), "f")


def test_session_bucketing_bounds_jit_compiles():
    """50 ragged requests (batch 1..8) compile at most once per bucket:
    jit_compiles stops growing once every bucket is warm — the retrace-
    storm guarantee the PR-2 metric made visible."""
    tel = _tel()
    x, out, _ = _linear_graph()
    sess = InferenceSession([out], telemetry=tel)
    rng = np.random.RandomState(1)
    compiles = []
    for _ in range(50):
        n = int(rng.randint(1, 9))
        r = sess.predict({x: rng.randn(n, 20).astype("f")})
        assert r[0].shape == (n, 4)
        compiles.append(tel.counter_value("jit_compiles"))
    # buckets hit: {1, 2, 4, 8} -> at most 4 programs, all compiled
    # within the first requests; the tail adds ZERO
    assert compiles[-1] <= 4, compiles
    assert compiles[-1] == compiles[20], \
        f"jit_compiles still growing in steady state: {compiles}"


def test_session_predict_unpads_batch_and_matches():
    x, out, _ = _linear_graph(seed=2)
    sess = InferenceSession([out])
    v = np.random.RandomState(3).randn(5, 20).astype("f")
    got = sess.predict({"x": v})[0]
    w = np.asarray(sess.params_by_name()["w"])
    assert got.shape == (5, 4)
    np.testing.assert_allclose(got, v @ w, rtol=1e-5)


def test_session_rejects_training_graph():
    x, out, _ = _linear_graph(seed=4)
    y_ = ht.Variable("y", trainable=False)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(out, y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    with pytest.raises(ValueError, match="OptimizerOp"):
        InferenceSession([loss, train])


def test_next_bucket():
    assert [next_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]
    assert next_bucket(3, (4, 16)) == 4
    with pytest.raises(ValueError):
        next_bucket(17, (4, 16))


# ---------------------------------------------------------------------------
# satellite: save/load hygiene + round-trip into a session
# ---------------------------------------------------------------------------

def test_save_detects_param_name_collision(tmp_path):
    rng = np.random.RandomState(0)
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("dup_w", value=rng.randn(20, 8).astype("f"))
    w2 = ht.Variable("dup_w", value=rng.randn(8, 4).astype("f"))
    out = ht.matmul_op(ht.matmul_op(x, w1), w2)
    exe = Executor([out], ctx=ht.cpu(0))
    with pytest.raises(ValueError, match="dup_w"):
        exe.save(str(tmp_path))


def test_load_warns_on_missing_param_file(tmp_path):
    x, out, _ = _linear_graph(seed=5)
    exe = Executor([out], ctx=ht.cpu(0))
    exe.save(str(tmp_path))
    os.remove(str(tmp_path / "w.npy"))
    with pytest.warns(UserWarning, match="'w'"):
        exe.load(str(tmp_path))


def test_load_restores_state_with_shardings(tmp_path):
    """opt_state / batchnorm state come back device_put with the
    pre-load shardings (not bare committed jnp.asarray)."""
    rng = np.random.RandomState(6)
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y", trainable=False)
    w = ht.Variable("w", value=rng.randn(20, 4).astype("f"))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    train = ht.optim.AdamOptimizer(0.01).minimize(loss)
    exe = Executor([loss, train], ctx=ht.cpu(0))
    xs = rng.randn(8, 20).astype("f")
    ys = np.eye(4, dtype="f")[rng.randint(0, 4, 8)]
    exe.run(feed_dict={x: xs, y_: ys})
    exe.save(str(tmp_path))
    import jax
    before = [(np.asarray(v), v.sharding)
              for v in jax.tree_util.tree_leaves(exe.opt_state)]
    exe.run(feed_dict={x: xs, y_: ys})
    exe.load(str(tmp_path))
    after = jax.tree_util.tree_leaves(exe.opt_state)
    assert len(after) == len(before) > 0
    for leaf, (val, shd) in zip(after, before):
        np.testing.assert_allclose(np.asarray(leaf), val, rtol=1e-6)
        assert leaf.sharding == shd


def test_dense_roundtrip_save_session_predict(tmp_path):
    """save -> InferenceSession(checkpoint) -> predict equals the
    training executor's own eval output (dense CNN model)."""
    from hetu_tpu.models.cnn import cnn_3_layers
    rng = np.random.RandomState(7)
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    loss, y = cnn_3_layers(x, y_)
    train = ht.optim.SGDOptimizer(0.05).minimize(loss)
    exe = Executor({"train": [loss, train], "eval": [y]}, ctx=ht.cpu(0))
    xs = rng.randn(8, 784).astype("f")
    ys = np.eye(10, dtype="f")[rng.randint(0, 10, 8)]
    for _ in range(3):
        exe.run("train", feed_dict={x: xs, y_: ys})
    want = np.asarray(exe.run("eval", feed_dict={x: xs},
                              convert_to_numpy_ret_vals=True)[0])
    exe.save(str(tmp_path))

    sess = InferenceSession([y], checkpoint=str(tmp_path), ctx=ht.cpu(0))
    got = sess.predict({x: xs})[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the serving block, no cache
# ---------------------------------------------------------------------------

def test_gpt_forward_matches_graph_forward_every_position():
    """``gpt_forward`` (the one serving block with an attend that writes
    nothing; chip_smoke.py's first-token reference) equals the GRAPH's
    full forward at every position, rtol<=1e-5 fp32."""
    cfg, sess = gpt_session()
    params = gpt_serving_params(cfg, sess.params_by_name().__getitem__)
    x = np.random.RandomState(0).randint(0, VOCAB, (2, 16))
    got = gpt_forward(params, x, num_heads=cfg.num_attention_heads)
    assert got.shape == (2, 16, VOCAB)
    np.testing.assert_allclose(np.asarray(got), full_forward(sess, x),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

def test_batcher_coalesces_and_splits():
    tel = _tel()
    x, out, _ = _linear_graph(seed=8)
    sess = InferenceSession([out], telemetry=tel)
    w = np.asarray(sess.params_by_name()["w"])
    calls = []

    def serve(feeds):
        calls.append(feeds["x"].shape[0])
        return sess.predict(feeds)

    rng = np.random.RandomState(8)
    rows = rng.randn(24, 20).astype("f")
    with MicroBatcher(serve, max_batch_size=16, max_wait_ms=25,
                      telemetry=tel) as mb:
        futs = [mb.submit({"x": rows[i:i + 1]}) for i in range(24)]
        outs = [f.result(30) for f in futs]
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o[0], rows[i:i + 1] @ w, rtol=1e-5)
    assert len(calls) < 24, f"no coalescing happened: {calls}"
    assert sum(calls) == 24
    # metrics exported through the registry
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    assert snap["serve_requests"]["value"] == 24
    assert snap["serve_latency_ms"]["count"] == 24
    assert "p99" in snap["serve_latency_ms"]
    assert 0 < snap["serve_batch_occupancy"]["max"] <= 1.0
    assert "serve_queue_depth" in snap


def test_batcher_survives_malformed_tick():
    """A tick whose requests can't concatenate (ragged trailing dims)
    fails THOSE futures — the batcher thread survives and later
    requests still serve."""
    def serve(feeds):
        return feeds["x"] * 2.0

    with MicroBatcher(serve, max_batch_size=8, max_wait_ms=30) as mb:
        f1 = mb.submit({"x": np.zeros((1, 4))})
        f2 = mb.submit({"x": np.zeros((1, 5))})   # ragged: concat fails
        excs = 0
        for f in (f1, f2):
            try:
                f.result(30)
            except ValueError:
                excs += 1
        assert excs >= 1      # at least the coalesced tick failed
        # the thread must still be alive and serving
        ok = mb.submit({"x": np.ones((2, 3))}).result(30)
        np.testing.assert_allclose(ok, 2.0)


def test_batcher_propagates_errors_and_rejects_after_close():
    def boom(feeds):
        raise RuntimeError("kaboom")

    mb = MicroBatcher(boom, max_wait_ms=1)
    fut = mb.submit({"x": np.zeros((1, 2))})
    with pytest.raises(RuntimeError, match="kaboom"):
        fut.result(10)
    mb.close()
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit({"x": np.zeros((1, 2))})


def test_batcher_splits_oversized_request_into_one_future():
    """A request wider than max_batch_size is split server-side into
    adjacent chunks; the caller still holds ONE future whose result is
    the row-ordered stitch of every chunk."""
    tel = _tel()
    x, out, _ = _linear_graph(seed=9)
    sess = InferenceSession([out], telemetry=tel)
    w = np.asarray(sess.params_by_name()["w"])
    calls = []

    def serve(feeds):
        calls.append(feeds["x"].shape[0])
        return sess.predict(feeds)

    rng = np.random.RandomState(9)
    rows = rng.randn(10, 20).astype("f")
    with MicroBatcher(serve, max_batch_size=4, max_wait_ms=5,
                      telemetry=tel) as mb:
        got = mb.submit({"x": rows}).result(30)[0]
    np.testing.assert_allclose(got, rows @ w, rtol=1e-5, atol=1e-5)
    assert max(calls) <= 4, f"a chunk exceeded max_batch_size: {calls}"
    assert sum(calls) == 10
    assert tel.counter_value("serve_split_requests") == 1
    # a chunk failure fails the ONE future, with the chunk's error
    attempts = []

    def flaky(feeds):
        attempts.append(feeds["x"].shape[0])
        if len(attempts) >= 2:
            raise RuntimeError("chunk 2 kaboom")
        return feeds["x"] * 2.0

    with MicroBatcher(flaky, max_batch_size=4, max_wait_ms=5) as mb:
        with pytest.raises(RuntimeError, match="kaboom"):
            mb.submit({"x": rows}).result(30)


# ---------------------------------------------------------------------------
# HTTP frontend + load driver
# ---------------------------------------------------------------------------

def _post(port, payload, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        json.dumps(payload).encode(),
        {"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def test_http_predict_health_metrics():
    tel = _tel()
    x, out, _ = _linear_graph(seed=9)
    sess = InferenceSession([out], telemetry=tel)
    w = np.asarray(sess.params_by_name()["w"])
    v = np.random.RandomState(9).randn(3, 20).astype("f")
    with ServingHTTPServer(sess, telemetry=tel) as srv:
        resp = _post(srv.port, {"inputs": {"x": v.tolist()}})
        np.testing.assert_allclose(np.asarray(resp["outputs"][0]), v @ w,
                                   rtol=1e-4)
        ok = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10).read())
        assert ok == {"ok": True}
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read()
        assert b"http_request_ms" in metrics


@pytest.mark.slow
def test_http_closed_loop_load():
    """Serving load test: a multi-threaded closed-loop client over the
    session+batcher+HTTP stack; compiles stay bounded by the buckets."""
    tel = _tel()
    x, out, _ = _linear_graph(seed=10)
    sess = InferenceSession([out], telemetry=tel)
    serve = sess.predict
    rng = np.random.RandomState(10)
    rows = rng.randn(64, 20).astype("f")
    with MicroBatcher(serve, max_batch_size=16, max_wait_ms=3,
                      telemetry=tel) as mb, \
            ServingHTTPServer(mb, telemetry=tel) as srv:
        errors = []

        def client(k):
            try:
                for i in range(10):
                    n = 1 + (k + i) % 3
                    v = rows[(k * 10 + i) % 60:][:n]
                    resp = _post(srv.port, {"inputs": {"x": v.tolist()}})
                    assert len(resp["outputs"][0]) == n
            except Exception as e:              # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    assert snap["serve_requests"]["value"] == 40
    assert tel.counter_value("jit_compiles") <= 5


# ---------------------------------------------------------------------------
# PS-backed sparse serving
# ---------------------------------------------------------------------------

@pytest.fixture()
def ps_env():
    from hetu_tpu.ps import client as ps_client
    from hetu_tpu.ps import server as ps_server
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


def test_ctr_ps_roundtrip_and_readonly_guard(ps_env, tmp_path):
    """Sparse round-trip: train WDL (PS mode), save, rewrite the eval
    graph to read-only PS pulls, serve — predictions equal the training
    executor's eval output; a push from the serving client raises; the
    row cache exports its hit rate."""
    from hetu_tpu.models.ctr import wdl_adult
    rng = np.random.RandomState(11)
    dense = ht.Variable("dense_input", trainable=False)
    sparse = ht.Variable("sparse_input", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    loss, y, y_, train_op = wdl_adult(dense, sparse, y_)
    exe = Executor({"train": [loss, train_op], "eval": [y]},
                   comm_mode="PS")
    dn = rng.randn(16, 6).astype("f")
    sp = rng.randint(0, 50000, (16, 8))
    lb = np.eye(2, dtype="f")[rng.randint(0, 2, 16)]
    for _ in range(4):
        exe.run("train", feed_dict={dense: dn, sparse: sp, y_: lb})
    want = np.asarray(exe.run("eval",
                              feed_dict={dense: dn, sparse: sp},
                              convert_to_numpy_ret_vals=True)[0])
    exe.save(str(tmp_path))
    exe.close()

    tel = _tel()
    eval_nodes = [y]
    pulls = serve_embeddings_from_ps(eval_nodes)
    assert len(pulls) == 1
    sess = InferenceSession(eval_nodes, checkpoint=str(tmp_path),
                            comm_mode="PS", embed_cache_rows=4096,
                            telemetry=tel)
    got = sess.predict({dense: dn, sparse: sp})[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # second hit: rows come from the host cache, hit rate > 0
    sess.predict({dense: dn, sparse: sp})
    assert sess.ps_client.hit_rate > 0.4
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    assert snap["serve_embed_cache_hit_rate"]["value"] > 0.4

    with pytest.raises(RuntimeError, match="read-only"):
        sess.ps_client.push(123, np.zeros(4, np.float32))
    with pytest.raises(RuntimeError, match="read-only"):
        sess.ps_client.sparse_push(123, np.zeros(1), np.zeros((1, 4)), 4)
    sess.close()


def test_ctr_ps_session_behind_batcher_and_http(ps_env):
    """The PS-backed serving session under load: four closed-loop HTTP
    clients send 1-4 rows a request through the micro-batcher; every
    response has its request's rows, equal to what the session predicts
    for those rows alone, and the frontend counted every request."""
    from hetu_tpu.models.ctr import wdl_adult
    rng = np.random.RandomState(12)
    dense = ht.Variable("dense_input", trainable=False)
    sparse = ht.Variable("sparse_input", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    loss, y, y_, train_op = wdl_adult(dense, sparse, y_)
    exe = Executor([loss, train_op], comm_mode="PS")
    for _ in range(2):          # registers + trains the table on the PS
        exe.run(feed_dict={
            dense: rng.randn(32, 6).astype("f"),
            sparse: rng.randint(0, 50000, (32, 8)),
            y_: np.eye(2, dtype="f")[rng.randint(0, 2, 32)]})
    exe.close()

    tel = _tel()
    eval_nodes = [y]
    serve_embeddings_from_ps(eval_nodes)
    sess = InferenceSession(eval_nodes, comm_mode="PS",
                            embed_cache_rows=1 << 12, telemetry=tel)
    dn = rng.randn(64, 6).astype("f")
    sp = rng.randint(0, 50000, (64, 8))
    want = np.asarray(sess.predict({"dense_input": dn,
                                    "sparse_input": sp})[0])
    errors = []
    with MicroBatcher(sess.predict, max_batch_size=16, max_wait_ms=2,
                      telemetry=tel) as mb, \
            ServingHTTPServer(mb, telemetry=tel) as srv:
        def client(k):
            try:
                for i in range(6):
                    n, at = 1 + (k + i) % 4, (k * 6 + i) * 2
                    resp = _post(srv.port, {"inputs": {
                        "dense_input": dn[at:at + n].tolist(),
                        "sparse_input": sp[at:at + n].tolist()}})
                    np.testing.assert_allclose(
                        np.asarray(resp["outputs"][0]), want[at:at + n],
                        rtol=1e-5, atol=1e-6)
            except Exception as e:              # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads), "a client hung"
        assert not errors, errors
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    assert snap["serve_requests"]["value"] == 24
    assert sess.ps_client.hit_rate > 0
    sess.close()
