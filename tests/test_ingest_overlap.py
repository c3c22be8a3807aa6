"""Hide the host (PR 7): the async ingest engine (hetu_tpu/ingest.py),
the PS runtime's pipelined per-step stream (overlapped SparsePull +
feed transfer), and bucketed gradient allreduce must change WHEN host
work happens, never WHAT the steps compute — pinned here as streamed
vs synchronous numeric equivalence across every PS mode, the BSP
version-semantics pin, the throttled-feed ingest_wait_ms ≈ 0 pin, and
the round-6 stream-error contract (cancel + block index)."""
import os
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import ingest
from hetu_tpu.executor import Executor
from hetu_tpu.ps import client as ps_client
from hetu_tpu.ps import server as ps_server


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    """Executor(telemetry=<enabled>) installs the instance as the
    process-global default; reset it so later test modules run with
    telemetry off again (the test_telemetry.py convention)."""
    import hetu_tpu.telemetry as tmod
    yield
    tmod._default = None


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
    ids = ht.Variable("io_ids", trainable=False)
    y_ = ht.Variable("io_y", trainable=False)
    table = ht.Variable("io_table", value=table_value)
    w = ht.Variable("io_w", value=np.full((4, 2), 0.3, np.float32))
    rows = ht.embedding_lookup_op(table, ids)
    pred = ht.matmul_op(ht.reduce_sum_op(rows, [1]), w)
    diff = pred + (-1) * y_
    loss = ht.reduce_mean_op(ht.reduce_sum_op(diff * diff, [1]), [0])
    train = ht.optim.SGDOptimizer(lr).minimize(loss)
    return ids, y_, w, loss, train


def _data(rng, steps, nrows=40, batch=8):
    return [(rng.randint(0, nrows, (batch, 3)),
             rng.randn(batch, 2).astype(np.float32))
            for _ in range(steps)]


# ---------------------------------------------------------------------------
# OverlapOptions knob set
# ---------------------------------------------------------------------------

def test_overlap_options_resolve_and_validate():
    opts = ingest.OverlapOptions.resolve(None)
    assert (opts.ingest, opts.lookahead, opts.bucket_bytes) == (True, 2,
                                                                None)
    opts = ingest.OverlapOptions.resolve(
        {"ingest": False, "lookahead": 4, "bucket_bytes": 1 << 20})
    assert (opts.ingest, opts.lookahead, opts.bucket_bytes) == (
        False, 4, 1 << 20)
    assert ingest.OverlapOptions.resolve(opts) is opts
    with pytest.raises(ValueError, match="unknown overlap_options"):
        ingest.OverlapOptions.resolve({"lookhaed": 3})
    with pytest.raises(ValueError, match="lookahead"):
        ingest.OverlapOptions(lookahead=0)
    with pytest.raises(ValueError, match="bucket_bytes"):
        ingest.OverlapOptions(bucket_bytes=0)
    with pytest.raises(TypeError):
        ingest.OverlapOptions.resolve(3)


# ---------------------------------------------------------------------------
# engine semantics: hide a throttled feed; error contract
# ---------------------------------------------------------------------------

def test_engine_hides_throttled_feed():
    """The acceptance pin: with ingest jobs slower than nothing but
    faster than compute (a throttled 21.5 MB/s-link stand-in), the
    lookahead worker keeps the queue ahead of the consumer and
    ingest_wait_ms p50 ≈ 0 — the device never waits for the host."""
    sink = ingest.new_stats()
    eng = ingest.IngestEngine(None, lookahead=2, sink=sink)

    def job(i):
        time.sleep(0.03)        # throttled feed: 30 ms of host work
        return i * 10

    with eng:
        eng.submit(job, 0, tag=0)
        eng.submit(job, 1, tag=1)
        _, first = eng.pop(record_wait=False)   # pipeline fill
        assert first == 0
        for i in range(2, 8):
            eng.submit(job, i, tag=i)
            time.sleep(0.06)    # "compute": twice the ingest cost
            tag, val = eng.pop()
            assert val == tag * 10
    fields = ingest.stats_fields(sink)
    assert fields["ingest_wait_ms"] < 10.0, fields
    assert fields["overlap_fraction"] > 0.5, fields
    assert sink["pops"] == 6


def test_engine_error_tags_block_and_cancels():
    """Round-6 leak fix: a failing ingest job re-raises as IngestError
    naming its block, and teardown on error CANCELS queued jobs
    instead of waiting them out."""
    ran = []

    def job(i):
        if i == 1:
            raise RuntimeError("boom")
        time.sleep(0.15)
        ran.append(i)
        return i

    eng = ingest.IngestEngine(None, lookahead=4)
    for i in range(4):
        eng.submit(job, i, tag=i)
    tag, val = eng.pop()
    assert (tag, val) == (0, 0)
    with pytest.raises(ingest.IngestError, match="block 1") as ei:
        eng.pop()
    assert isinstance(ei.value.__cause__, RuntimeError)
    t0 = time.perf_counter()
    eng.close(cancel=True)      # job 2 may be mid-run; job 3 must not
    assert time.perf_counter() - t0 < 0.1, "cancel must not wait out " \
        "the queue"
    time.sleep(0.4)
    assert 3 not in ran, "queued job survived the cancel"


def test_stream_error_names_block_index():
    """An ingest failure mid-stream surfaces as IngestError carrying
    the offending block index (the old stream re-raised a bare
    fut.result() error with nothing to debug from)."""
    rng = np.random.RandomState(0)
    x = ht.Variable("se_x", trainable=False)
    y_ = ht.Variable("se_y", trainable=False)
    w = ht.Variable("se_w", value=rng.randn(8, 4).astype("f") * 0.3)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train])

    def batch(n=8):
        return {x: rng.randn(n, 8).astype("f"),
                y_: np.eye(4, dtype="f")[rng.randint(0, 4, n)]}

    good = [batch() for _ in range(3)]
    ragged = [batch(), batch(7)]        # np.stack on the worker raises
    with pytest.raises(ingest.IngestError, match="block 2"):
        exe.run_batches_stream(iter([good, good, ragged, good]))


# ---------------------------------------------------------------------------
# streamed vs synchronous equivalence, all four PS modes
# ---------------------------------------------------------------------------

def _sync_reference(table, data, **exe_kwargs):
    """Per-step run() losses + final dense weight + final server rows.

    Under ASP the reference loop is inherently racy (the async push
    pool vs the next step's pull); flush pushes after every step so
    the reference is the deterministic all-pushes-visible sequence —
    exactly what the pipelined stream's revalidation guarantees."""
    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], **exe_kwargs)
    tid = next(op.parameter.id
               for op in exe.subexecutors["default"].ps_ops)
    losses = []
    for i, y in data:
        losses.append(float(exe.run(feed_dict={ids: i, y_: y},
                                    convert_to_numpy_ret_vals=True)[0]))
        exe.ps_runtime._flush_pushes(tid)
    dense = np.asarray(exe.params[str(w.id)]).copy()
    exe.close()
    return losses, dense, tid


@pytest.mark.parametrize("mode_kwargs", [
    {"comm_mode": "PS"},                     # host path, ASP
    {"comm_mode": "PS", "bsp": True},        # host path, BSP
    {"comm_mode": "Hybrid", "bsp": True},    # Hybrid dense half in-graph
], ids=["ps_host_asp", "ps_host_bsp", "hybrid_host_bsp"])
def test_pipelined_stream_matches_per_step(ps_env, mode_kwargs):
    """Host-path PS configs used to fall back to a fully synchronous
    run_step loop; the pipelined stream overlaps step i+1's SparsePull
    and feed transfer with step i's compute and must stay numerically
    identical — same per-step losses, same final dense params, same
    final server rows."""
    rng = np.random.RandomState(11)
    table = rng.randn(40, 4).astype(np.float32)
    data = _data(rng, 10)

    want, want_dense, tid = _sync_reference(table, data, **mode_kwargs)
    want_rows = ps_env.sparse_pull(tid, np.arange(40), 4).copy()
    ps_env.clear(tid)

    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], **mode_kwargs)
    out = exe.run_batches_stream(
        [[{ids: i, y_: y} for i, y in data]],    # one 10-step block
        convert_to_numpy_ret_vals=True)
    got = [float(r[0]) for r in out]
    got_dense = np.asarray(exe.params[str(w.id)])
    tid2 = next(op.parameter.id
                for op in exe.subexecutors["default"].ps_ops)
    # under ASP the last step's push may still be in the pool when the
    # stream returns (a loaded host, six xdist workers): the rows are
    # compared once every push is visible, as the reference's are
    exe.ps_runtime._flush_pushes(tid2)
    got_rows = ps_env.sparse_pull(tid2, np.arange(40), 4)
    exe.close()

    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_dense, want_dense, rtol=1e-5)
    np.testing.assert_allclose(got_rows, want_rows, rtol=1e-5)


def test_bsp_overlapped_pull_reads_post_barrier_values(ps_env):
    """BSP version-semantics pin: every step reads the SAME rows the
    previous step pushed, so every speculative pull is stale by
    construction — the dirty re-pull must hand the step exactly the
    post-barrier (post-push) server state the synchronous loop reads,
    and the repull phase must actually engage (not vacuously pass)."""
    rng = np.random.RandomState(13)
    table = rng.randn(8, 4).astype(np.float32)
    # same ids every step: maximal read-after-write pressure
    data = [(np.broadcast_to(np.arange(3), (8, 3)).copy(),
             rng.randn(8, 2).astype(np.float32)) for _ in range(8)]

    want, want_dense, tid = _sync_reference(table, data,
                                            comm_mode="PS", bsp=True)
    want_rows = ps_env.sparse_pull(tid, np.arange(8), 4).copy()
    ps_env.clear(tid)

    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", bsp=True)
    out = exe.run_batches_stream(
        [[{ids: i, y_: y} for i, y in data]],
        convert_to_numpy_ret_vals=True, lookahead=3)
    got = [float(r[0]) for r in out]
    got_rows = ps_env.sparse_pull(
        next(op.parameter.id
             for op in exe.subexecutors["default"].ps_ops),
        np.arange(8), 4)
    assert exe.ps_runtime.times["repull"] > 0.0, \
        "speculative pulls were never revalidated — the pin is vacuous"
    exe.close()
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_rows, want_rows, rtol=1e-5)


def test_hybrid_device_cache_stream_matches_run_batches(ps_env):
    """Fourth mode: Hybrid with the HBM device cache rides the
    scan-block stream — same losses, same final cache rows and slot
    map (dirty-state) as a synchronous run_batches loop."""
    rng = np.random.RandomState(17)
    table = rng.randn(60, 4).astype(np.float32)
    data = _data(rng, 12, nrows=60)
    blocks = [data[:4], data[4:8], data[8:]]

    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="Hybrid",
                   cstable_policy="Device", cache_bound=5)
    for chunk in blocks:
        out = exe.run_batches([{ids: i, y_: y} for i, y in chunk],
                              convert_to_numpy_ret_vals=True)
    want_last = float(out[-1][0])
    rt = next(iter(exe.ps_runtime.device_tables.values()))
    exe.ps_runtime.drain()
    want_cache = np.asarray(exe.params[rt.cache_sid]).copy()
    want_ids = rt.id_of.copy()
    exe.close()

    ids2, y2, w2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="Hybrid",
                    cstable_policy="Device", cache_bound=5)
    out2 = exe2.run_batches_stream(
        ([{ids2: i, y2: y} for i, y in chunk] for chunk in blocks),
        convert_to_numpy_ret_vals=True)
    got_last = float(out2[-1][0])
    rt2 = next(iter(exe2.ps_runtime.device_tables.values()))
    exe2.ps_runtime.drain()
    got_cache = np.asarray(exe2.params[rt2.cache_sid])
    got_ids = rt2.id_of.copy()
    stats = exe2.ingest_stats()
    assert stats["ingest_busy_ms_sum"] > 0.0, \
        "the engine never ran — the stream silently fell back"
    exe2.close()
    np.testing.assert_allclose(got_last, want_last, rtol=1e-5)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_allclose(got_cache, want_cache, rtol=1e-5)


def test_ingest_off_is_fully_synchronous(ps_env):
    """overlap_options={"ingest": False} restores the pre-engine
    behavior on every path: a plain run_batches loop, no worker, no
    stats — and identical numbers."""
    rng = np.random.RandomState(19)
    table = rng.randn(40, 4).astype(np.float32)
    data = _data(rng, 8)

    # BSP: pushes are synchronous, so both loops are deterministic
    want, want_dense, tid = _sync_reference(table, data, comm_mode="PS",
                                            bsp=True)
    ps_env.clear(tid)

    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", bsp=True,
                   overlap_options={"ingest": False})
    out = exe.run_batches_stream(
        [[{ids: i, y_: y} for i, y in data]],
        convert_to_numpy_ret_vals=True)
    got = [float(r[0]) for r in out]
    stats = exe.ingest_stats()
    assert stats["ingest_busy_ms_sum"] == 0.0
    assert stats["overlap_fraction"] == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(exe.params[str(w.id)]),
                               want_dense, rtol=1e-5)
    exe.close()


# ---------------------------------------------------------------------------
# bucketed gradient allreduce
# ---------------------------------------------------------------------------

class _OverlapCfg:
    """Minimal config stub for the op-level bucketing contract."""
    spmd_axis = None

    def __init__(self, bucket_bytes):
        self.overlap = ingest.OverlapOptions(bucket_bytes=bucket_bytes)


def _bucketing_case(bucket_bytes):
    """settle_deferred_allreduce inside a real shard_map vs per-grad
    lax.pmean; returns (got list, want list, pmean call count)."""
    import jax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from hetu_tpu.graph.node import ExecContext
    from hetu_tpu.ops import comm

    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("a",))
    nodes = [ht.Variable(f"bk_g{i}", trainable=False) for i in range(3)]
    ops = [comm.AllReduceCommunicateOp(n) for n in nodes]
    ectx = ExecContext(training=False,
                       config=_OverlapCfg(bucket_bytes))
    ectx.spmd_axis = "a"
    ectx.allreduce_defer = frozenset(ops)

    rng = np.random.RandomState(23)
    gs = [rng.randn(4, 8).astype(np.float32),
          rng.randn(4, 3, 5).astype(np.float32),
          rng.randn(4, 2).astype(np.float32)]

    calls = []
    real_pmean = comm.lax.pmean

    class _Lax:
        def __getattr__(self, name):
            if name == "pmean":
                def counting(val, axis):
                    calls.append(val.shape)
                    return real_pmean(val, axis)
                return counting
            return getattr(lax, name)

    orig = comm.lax
    comm.lax = _Lax()
    try:
        def body(*vals):
            deferred = [op.compute([v], ectx)
                        for op, v in zip(ops, vals)]
            for d, v in zip(deferred, vals):
                assert d is v, "deferred op must be a pass-through"
            out = comm.settle_deferred_allreduce(ops, list(deferred),
                                                 ectx)
            ref = [real_pmean(v, "a") for v in vals]
            return tuple(out) + tuple(ref)

        res = shard_map(body, mesh=mesh,
                        in_specs=tuple(P("a") for _ in gs),
                        out_specs=tuple(P("a") for _ in gs) * 2)(*gs)
    finally:
        comm.lax = orig
    return res[:3], res[3:], len(calls)


def test_bucketed_allreduce_one_collective_matches_pergrad():
    """One big bucket: all three grads ride ONE pmean over a flattened
    concat, numerically identical to per-grad collectives."""
    got, want, ncalls = _bucketing_case(bucket_bytes=1 << 30)
    assert ncalls == 1, f"expected one bucket collective, saw {ncalls}"
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6)


def test_bucketed_allreduce_small_buckets_match_pergrad():
    """bucket_bytes below any grad: every grad becomes its own bucket
    (the degenerate case must not corrupt shapes or order)."""
    got, want, ncalls = _bucketing_case(bucket_bytes=1)
    assert ncalls == 3
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6)


def test_executor_bucket_bytes_is_numeric_noop(ps_env):
    """End-to-end: Hybrid training with bucket_bytes set must equal the
    default per-grad path (on one worker the dp axis is unbound — both
    reduce to markers — and the defer plumbing must not disturb the
    optimizer's inputs)."""
    rng = np.random.RandomState(29)
    table = rng.randn(40, 4).astype(np.float32)
    data = _data(rng, 8)

    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="Hybrid", bsp=True)
    want = [float(exe.run(feed_dict={ids: i, y_: y},
                          convert_to_numpy_ret_vals=True)[0])
            for i, y in data]
    want_dense = np.asarray(exe.params[str(w.id)]).copy()
    tid = next(op.parameter.id
               for op in exe.subexecutors["default"].ps_ops)
    exe.close()
    ps_env.clear(tid)

    ids2, y2, w2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="Hybrid", bsp=True,
                    overlap_options={"bucket_bytes": 1 << 20})
    got = [float(exe2.run(feed_dict={ids2: i, y2: y},
                          convert_to_numpy_ret_vals=True)[0])
           for i, y in data]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(exe2.params[str(w2.id)]),
                               want_dense, rtol=1e-6)
    exe2.close()


# ---------------------------------------------------------------------------
# HT5xx advisory
# ---------------------------------------------------------------------------

def test_overlapped_spans_marked_in_trace(ps_env):
    """The merged trace must show WHICH pulls/transfers rode under
    compute: ps:pull and h2d_transfer spans issued from the ingest
    worker carry overlapped=True; the synchronous ones say False."""
    from hetu_tpu.telemetry import Telemetry

    rng = np.random.RandomState(41)
    table = rng.randn(40, 4).astype(np.float32)
    data = _data(rng, 6)
    ids, y_, w, loss, train = _embed_model(table)
    tel = Telemetry(enabled=True, rank=0)
    exe = Executor([loss, train], comm_mode="PS", telemetry=tel)
    exe.run_batches_stream([[{ids: i, y_: y} for i, y in data]],
                           convert_to_numpy_ret_vals=True)
    events = [e for e in tel.tracer.drain() if e["ph"] == "X"]
    pulls = [e for e in events if e["name"] == "ps:pull"]
    assert any((e.get("args") or {}).get("overlapped") for e in pulls), \
        "no speculative pull ever rode the ingest worker"
    h2d = [e for e in events if e["name"] == "h2d_transfer"]
    assert any((e.get("args") or {}).get("overlapped") for e in h2d), \
        "no feed transfer ever rode the ingest worker"
    assert all("overlapped" in (e.get("args") or {}) for e in pulls)
    exe.close()


def test_ht501_ingest_disabled_on_ps_graph(ps_env):
    rng = np.random.RandomState(31)
    table = rng.randn(40, 4).astype(np.float32)
    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", validate="warn",
                   overlap_options={"ingest": False})
    codes = [f.code for f in exe.config.analysis_report.findings]
    assert "HT501" in codes
    exe.close()

    ids2, y2, w2, loss2, train2 = _embed_model(table)
    exe2 = Executor([loss2, train2], comm_mode="PS", validate="warn")
    codes = [f.code for f in exe2.config.analysis_report.findings]
    assert "HT501" not in codes, "advisory must not fire with ingest on"
    exe2.close()


def test_ht502_plain_run_loop_advisory(ps_env, monkeypatch):
    from hetu_tpu.analysis import overlap as overlap_mod
    monkeypatch.setattr(overlap_mod, "RUN_LOOP_ADVISORY_STEPS", 5)

    rng = np.random.RandomState(37)
    table = rng.randn(40, 4).astype(np.float32)
    data = _data(rng, 12)
    ids, y_, w, loss, train = _embed_model(table)
    exe = Executor([loss, train], comm_mode="PS", validate="warn")
    for i, y in data[:4]:
        exe.run(feed_dict={ids: i, y_: y})
    # a block call resets the counter — no advisory yet
    exe.run_batches([{ids: i, y_: y} for i, y in data[4:6]])
    codes = [f.code for f in exe.config.analysis_report.findings]
    assert "HT502" not in codes
    for i, y in data[6:12]:
        exe.run(feed_dict={ids: i, y_: y})
    codes = [f.code for f in exe.config.analysis_report.findings]
    assert codes.count("HT502") == 1
    f = next(f for f in exe.config.analysis_report.findings
             if f.code == "HT502")
    assert "run_batches_stream" in f.message
    assert f.severity == "info", "advisory must never fail preflight"
    # fires once, not per step
    for i, y in data[:6]:
        exe.run(feed_dict={ids: i, y_: y})
    codes = [f.code for f in exe.config.analysis_report.findings]
    assert codes.count("HT502") == 1
    exe.close()
