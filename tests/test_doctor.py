"""Perf doctor (hetu_tpu/telemetry/{doctor,costdb}): bucket attribution
with conservation, hidden/exposed transfer split, the doctor CLI, the
measured cost database (persistence across reload, comm curves,
span/profile producers) and the span-attr schema fixtures."""
import json
import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.telemetry import Telemetry, Tracer, check, doctor
from hetu_tpu.telemetry.costdb import (CostDB, comm_microbench,
                                       record_spans)


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    import hetu_tpu.telemetry as tmod
    yield
    tmod._default = None


# ---------------------------------------------------------------------------
# synthetic-trace attribution: exact bucket math
# ---------------------------------------------------------------------------

def _ev(name, ts, dur, pid=0, tid=0, **args):
    ev = {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
          "pid": pid, "tid": tid, "cat": "hetu"}
    if args:
        ev["args"] = args
    return ev


def test_attribution_buckets_and_priority():
    """Nested spans must not double-count: a ps:pull inside ps:host_pull
    is one ps_pull interval; a pp_stage_idle inside a fwd block is
    bubble, not compute; the residual is unaccounted — and everything
    sums exactly to the window wall."""
    events = [
        _ev("step", 0, 1000, subgraph="default"),
        _ev("ps:host_pull", 0, 300),
        _ev("ps:pull", 50, 200, bytes=1024, overlapped=False),  # nested
        _ev("pp_fwd_block", 300, 400, stage=0),
        _ev("pp_stage_idle", 350, 100, stage=0, tag="t", bytes=64),
        _ev("device_dispatch", 700, 200, subgraph="default"),
    ]
    attr = doctor.attribute_events(events)
    b = attr["buckets"]
    assert attr["steps"] == 1 and attr["windows"] == 1
    assert b["ps_pull"] == pytest.approx(0.3)       # 300 µs, not 500
    assert b["bubble"] == pytest.approx(0.1)        # claimed over compute
    assert b["compute"] == pytest.approx(0.5)       # 400-100 + 200
    assert b["unaccounted"] == pytest.approx(0.1)   # 1000-900
    total = sum(b.values())
    assert total == pytest.approx(attr["wall_ms"])
    assert attr["conserved"]


def test_attribution_straddling_claim_no_double_count():
    """A higher-priority span straddling TWO same-bucket intervals
    subtracts from both (regression: the interval-subtract cursor used
    to strand the straddler after the first interval, double-counting
    its tail and breaking conservation)."""
    events = [
        _ev("step", 0, 20),
        _ev("pp_stage_idle", 5, 10, stage=0, tag="t", bytes=1),
        _ev("h2d_transfer", 0, 10, bytes=1, overlapped=False),
        _ev("h2d_transfer", 12, 8, bytes=1, overlapped=False),
    ]
    attr = doctor.attribute_events(events)
    b = attr["buckets"]
    assert b["bubble"] == pytest.approx(0.01)       # [5, 15]
    assert b["h2d_ingest"] == pytest.approx(0.01)   # [0,5] + [15,20]
    assert sum(b.values()) == pytest.approx(attr["wall_ms"])
    assert attr["conserved"]


def test_attribution_hidden_vs_exposed_transfer():
    """overlapped=True spans (and spans riding another thread) are
    hidden: reported, never charged against the step wall."""
    events = [
        _ev("step", 0, 1000),
        _ev("h2d_transfer", 100, 300, bytes=4096, overlapped=True),
        _ev("ps:pull", 200, 400, tid=7, bytes=2048, overlapped=True),
        _ev("h2d_transfer", 600, 100, bytes=512, overlapped=False),
    ]
    attr = doctor.attribute_events(events)
    assert attr["buckets"]["h2d_ingest"] == pytest.approx(0.1)
    assert attr["hidden_ms"]["h2d_ingest"] == pytest.approx(0.3)
    assert attr["hidden_ms"]["ps_pull"] == pytest.approx(0.4)
    assert attr["conserved"]
    diag = doctor.diagnose({"rank0": attr})
    # hidden 700 µs vs exposed 100 µs of transfer
    assert diag["transfer_hidden_fraction"] == pytest.approx(0.875)


def test_attribution_step_block_weighting():
    """A step_block window with steps=50 divides into per-step numbers;
    windows nested inside it are ignored (no double billing)."""
    events = [
        _ev("step_block", 0, 5000, steps=50, subgraph="default"),
        _ev("block_dispatch", 500, 4000, steps=50, subgraph="default"),
        _ev("step", 600, 100),       # stray nested window: dropped
    ]
    attr = doctor.attribute_events(events)
    assert attr["steps"] == 50 and attr["windows"] == 1
    assert attr["step_wall_ms"] == pytest.approx(0.1)
    assert attr["per_step_ms"]["compute"] == pytest.approx(0.08)
    assert attr["conserved"]


def test_attribution_none_without_windows():
    assert doctor.attribute_events([_ev("h2d_transfer", 0, 10,
                                        bytes=1, overlapped=False)]) \
        is None


def test_diagnose_ranks_and_remedy():
    events = [
        _ev("step", 0, 1000),
        _ev("ps:host_pull", 0, 600),
        _ev("device_dispatch", 600, 300),
    ]
    diag = doctor.diagnose({"rank0": doctor.attribute_events(events)})
    assert diag["top_exposed_bucket"]["bucket"] == "ps_pull"
    assert "lookahead" in diag["top_exposed_bucket"]["remedy"]
    assert diag["comm_compute_ratio"] == pytest.approx(0.6 / 0.3,
                                                       rel=1e-3)
    assert diag["conserved"]


# ---------------------------------------------------------------------------
# end-to-end: executor telemetry dir -> doctor CLI (acceptance)
# ---------------------------------------------------------------------------

def _mlp():
    x = ht.Variable("dr_x", trainable=False)
    y_ = ht.Variable("dr_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name="dr_w1")
    w2 = ht.init.xavier_normal((12, 4), name="dr_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return x, y_, loss, train


@pytest.fixture(scope="module")
def driven_dir(tmp_path_factory):
    """One real telemetry-enabled run shared by the doctor tests: 4
    run() steps + one 4-step run_batches block + 3 streamed 4-step
    blocks = 20 steps."""
    import hetu_tpu.telemetry as tmod
    tdir = str(tmp_path_factory.mktemp("doctor") / "tel")
    tel = Telemetry(enabled=True, out_dir=tdir, rank=0)
    x, y_, loss, train = _mlp()
    exe = Executor([loss, train], telemetry=tel)
    rng = np.random.RandomState(0)

    def feeds():
        return {x: rng.randn(8, 16).astype("f"),
                y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]}
    for _ in range(4):
        exe.run(feed_dict=feeds())
    exe.run_batches([feeds() for _ in range(4)])
    exe.run_batches_stream([[feeds() for _ in range(4)]
                            for _ in range(3)])
    exe.close()
    tel.flush()
    tmod._default = None
    return tdir


def test_doctor_on_real_telemetry_dir(driven_dir):
    """Acceptance core: a real run's trace attributes with buckets
    summing to within 10% of measured step wall, the trace passes the
    extended schema validator, and step counting matches the run
    (4 run + 4 batch + 12 streamed = 20 steps)."""
    tdir = driven_dir
    n, errors = check.validate(os.path.join(tdir, "trace_rank0.json"))
    assert not errors, errors
    per = doctor.attribute_trace(tdir)
    assert "rank0" in per
    a = per["rank0"]
    assert a["steps"] == 20
    total = sum(a["buckets"].values())
    assert abs(total - a["wall_ms"]) <= 0.10 * a["wall_ms"]
    assert a["conserved"]
    # the real trace exercises jit/compute/h2d buckets
    assert a["buckets"]["compute"] > 0
    assert a["buckets"]["jit"] > 0


def test_doctor_cli_json_exit0(driven_dir, capsys):
    """The CI invocation shape (doctor.main is exactly what `python -m
    hetu_tpu.telemetry.doctor` dispatches to): --json exits 0, the
    diagnosis parses, conservation holds."""
    tdir = driven_dir
    assert doctor.main([tdir, "--json"]) == 0
    diag = json.loads(capsys.readouterr().out)
    assert diag["conserved"] is True
    assert diag["top_exposed_bucket"]["bucket"]
    assert diag["ranks"]["rank0"]["steps"] == 20
    # human form exits 0 too and names the top bucket
    assert doctor.main([tdir]) == 0
    out = capsys.readouterr().out
    assert "top exposed bucket" in out
    assert "conservation" in out


def test_doctor_cli_empty_dir_exits_nonzero(tmp_path, capsys):
    assert doctor.main([str(tmp_path)]) == 1        # no windows
    assert doctor.main([str(tmp_path / "nope")]) == 2   # no such dir


# ---------------------------------------------------------------------------
# cost database
# ---------------------------------------------------------------------------

def test_costdb_12_kinds_survive_restart(tmp_path):
    """Acceptance: profile_op_records + the comm microbench persist
    >= 12 distinct op/collective kinds, and a FRESH CostDB instance
    (new process state, same file) serves every one of them from disk
    — reload hits, no remeasure."""
    db_path = str(tmp_path / "costdb.json")
    db = CostDB(db_path)
    x, y_, loss, train = _mlp()
    exe = Executor([loss, train])
    rng = np.random.RandomState(0)
    fd = {x: rng.randn(8, 16).astype("f"),
          y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]}
    exe.run(feed_dict=fd)
    from hetu_tpu.profiler import profile_op_records
    records = profile_op_records(exe, fd, costdb=db)
    assert all({"name", "kind", "shape", "dtype", "ms"} <= set(r)
               for r in records)
    comm_microbench(db, sizes=(1 << 14, 1 << 16), reps=1)

    reloaded = CostDB(db_path)          # fresh instance: disk only
    kinds = reloaded.kinds()
    assert len(kinds) >= 12, kinds
    # comm kinds landed beside the op kinds (8 virtual devices ->
    # allreduce/p2p sweeps run too)
    assert {"h2d", "d2h", "allreduce", "p2p"} <= set(kinds)
    # reload-hit pin: every profiled record resolves from the fresh
    # instance without any new measurement
    hits = sum(1 for r in records
               if reloaded.get(r["kind"], r["shape"], r["dtype"]))
    assert hits == len(records)
    # and a curve + estimate come straight off the reloaded file
    assert reloaded.curve("h2d")["points"] >= 2
    assert reloaded.estimate_ms("h2d", 1 << 15) is not None


def test_costdb_running_mean_and_min(tmp_path):
    db = CostDB(str(tmp_path / "c.json"))
    db.record("MatMulOp", (8, 8), "float32", 2.0)
    db.record("MatMulOp", (8, 8), "float32", 4.0)
    ent = db.get("MatMulOp", (8, 8))
    assert ent["n"] == 2
    assert ent["ms"] == pytest.approx(3.0)
    assert ent["min_ms"] == pytest.approx(2.0)


def test_costdb_record_spans_from_trace(tmp_path):
    """Span aggregates populate comm cost points: h2d_transfer /
    ps:pull spans with byte counts become pow2-bucketed entries."""
    db = CostDB(str(tmp_path / "c.json"))
    events = [
        _ev("h2d_transfer", 0, 500, bytes=3000, overlapped=False),
        _ev("ps:pull", 600, 1500, bytes=8192, overlapped=True),
        _ev("p2p_send", 2200, 700, tag="t", dst=1, bytes=4096),
        _ev("step", 0, 10),           # not a comm span: ignored
    ]
    n = record_spans(db, events)
    assert n == 3
    assert db.get("h2d", 4096, "bytes")["ms"] == pytest.approx(0.5)
    assert db.get("ps_pull", 8192, "bytes")["ms"] == pytest.approx(1.5)
    assert db.get("p2p", 4096, "bytes")["ms"] == pytest.approx(0.7)
    present, missing = db.coverage()
    assert "h2d" in present and "ps_sparse_pull" in missing


def test_costdb_ps_microbench_live_server(tmp_path):
    """The PS sweep measures SparsePull/SparsePush + dense Pull/Push
    against a real local server and persists bandwidth points for all
    four PS comm kinds."""
    from hetu_tpu.ps import server as ps_server
    from hetu_tpu.ps import client as ps_client
    from hetu_tpu.telemetry.costdb import ps_microbench

    port = ps_server.pick_free_port()
    os.environ["HETU_PS_PORTS"] = str(port)
    os.environ["HETU_PS_HOSTS"] = "127.0.0.1"
    ps_server.ensure_server(port=port, nworkers=1)
    client = ps_client.PSClient(rank=0, nworkers=1)
    try:
        db = CostDB(str(tmp_path / "c.json"))
        swept = ps_microbench(db, client, sizes=(16, 128), reps=1)
        assert swept == {k: 2 for k in
                         ("ps_sparse_pull", "ps_sparse_push",
                          "ps_pull", "ps_push")}
        reloaded = CostDB(str(tmp_path / "c.json"))
        present, missing = reloaded.coverage()
        assert {"ps_sparse_pull", "ps_sparse_push", "ps_pull",
                "ps_push"} <= set(present)
        assert reloaded.curve("ps_sparse_pull")["points"] == 2
    finally:
        client.shutdown_servers()
        client.close()
        ps_server.shutdown_server()


def test_costdb_corrupt_file_cold_start(tmp_path):
    p = tmp_path / "c.json"
    p.write_text("{not json")
    db = CostDB(str(p))
    assert len(db) == 0
    db.record("k", (1,), "float32", 1.0)
    db.save()
    assert CostDB(str(p)).get("k", (1,)) is not None


# ---------------------------------------------------------------------------
# span-attr schema (check.py satellite): one fixture per producer
# ---------------------------------------------------------------------------

def _producer_fixture_tracer():
    """A trace carrying every schema'd span kind with its real attrs —
    the drift gate's fixture: a producer changing its attrs must update
    SPAN_SCHEMA and this fixture together."""
    tr = Tracer(pid=0)
    t = tr.clock()

    def span(name, **args):
        nonlocal t
        tr.complete(name, t, t + 1000, args or None)
        t += 2000
    span("step", subgraph="default")
    span("step", subgraph="default", pipelined=True)
    span("step_block", steps=4, subgraph="default")
    span("jit_compile", subgraph="default", shape_key="k",
         allreduce_defer=2, arg_bytes=10)
    span("device_dispatch", subgraph="default")
    span("block_dispatch", steps=4, subgraph="default")
    span("h2d_transfer", bytes=1024, overlapped=True)
    span("ingest_wait", tag=3)
    span("ps:pull", bytes=2048, overlapped=False)
    span("ps:drain_push", rows=7)
    for phase in ("slot_assign", "miss_fill", "refresh", "dispatch",
                  "drain_submit", "dense", "host_pull", "sync_push",
                  "feed_ingest", "prefetch", "repull"):
        span(f"ps:{phase}")
    span("pp_stage_idle", stage=1, tag="b0:1", bytes=64)
    span("pp_fwd_block", stage=0)
    span("pp_bwd_block", stage=0)
    span("p2p_send", tag="t", dst=1, bytes=128)
    span("p2p_recv", tag="t", bytes=128)
    span("cpp_dispatch", ticks=5, fill=1, drain=1, fuse_ticks=2,
         stages=2, microbatches=4, bytes=4096)
    span("cpp_pack_feeds", bytes=512)
    span("fleet_watch", step=12, straggler=1, skew_ms=15.5, victims=2,
         aligned=True, ranks=3)
    span("fleet_watch", step=-1, straggler=None, skew_ms=0.0, victims=0)
    span("health", step=10, layers=3, trips=1)
    span("attn_probe", kernel="fwd", ms=0.5, blocks="(128, 128)",
         seq=2048, head_dim=64, dtype="bfloat16")
    tr.instant("flash_bwd_walk", seq=1024, head_dim=64, block_q=256,
               block_k=256, causal=True, tiles_visited=10,
               tiles_square=16, tiles_masked=4, visited_share=0.625,
               masked_share=0.4)
    tr.instant("h2d_stacked", bytes=4096, overlapped=False)
    tr.instant("memory_analysis", label="default", arg_bytes=1)
    tr.instant("step_logged", step=1, wall_ms=2.5)
    tr.instant("health_trip", step=10, kind="nonfinite", layer="w1",
               value=3.0, limit=0)
    tr.instant("health_trip", step=20, kind="staleness", table="7",
               value=9.0, limit=4.0)
    tr.instant("drift", rank=1, kind="p2p", bytes=1 << 20,
               measured_ms=10.0, predicted_ms=0.4, windows=3,
               tripped=True, source="measured")
    return tr


def test_schema_accepts_every_producer_fixture(tmp_path):
    tr = _producer_fixture_tracer()
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    n, errors = check.validate(path)
    assert not errors, errors
    assert n > 20


@pytest.mark.parametrize("name,args,match", [
    # wrong attr type: overlapped must be bool, not int
    ("h2d_transfer", {"bytes": 10, "overlapped": 1}, "overlapped"),
    # required attr dropped
    ("h2d_transfer", {"overlapped": True}, "missing"),
    ("ps:pull", {"bytes": 10}, "overlapped"),
    # unknown attr on a known span = schema drift
    ("step_block", {"steps": 2, "novel_attr": 1}, "unknown attr"),
    ("attn_probe", {"kernel": "fwd", "ms": "fast"}, "ms"),
    ("cpp_dispatch", {"fill": 1}, "ticks"),
    # the flash backward's walk: the shares are numbers, causal a bool
    ("flash_bwd_walk", {"seq": 1024, "block_q": 256, "block_k": 256,
                        "causal": 1, "tiles_visited": 10,
                        "tiles_square": 16, "tiles_masked": 4,
                        "visited_share": 0.625, "masked_share": 0.4},
     "causal"),
    # fleet watch / drift (telemetry/fleet.py)
    ("fleet_watch", {"skew_ms": 0.0}, "missing"),
    ("fleet_watch", {"step": 1, "skew_ms": "big"}, "skew_ms"),
    ("drift", {"rank": 0, "kind": "p2p", "measured_ms": 1.0,
               "predicted_ms": 0.5, "windows": 1, "tripped": 1},
     "tripped"),
])
def test_schema_rejects_drifted_attrs(tmp_path, name, args, match):
    tr = Tracer(pid=0)
    t = tr.clock()
    tr.complete(name, t, t + 1000, args)
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    _, errors = check.validate(path)
    assert errors and any(match in e for e in errors), (errors, match)


def test_schema_ignores_user_spans(tmp_path):
    tr = Tracer(pid=0)
    t = tr.clock()
    tr.complete("my_custom_phase", t, t + 10, {"whatever": object,
                                               "n": 3.5})
    # non-JSON arg would fail export; use JSON-able values
    tr = Tracer(pid=0)
    t = tr.clock()
    tr.complete("my_custom_phase", t, t + 10, {"anything": [1, 2]})
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    _, errors = check.validate(path)
    assert not errors, errors


def test_check_cli_no_attrs_flag(tmp_path, capsys):
    tr = Tracer(pid=0)
    t = tr.clock()
    tr.complete("h2d_transfer", t, t + 10, {"overlapped": True})
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    assert check.main([path]) == 1              # bytes attr missing
    assert "INVALID" in capsys.readouterr().out
    assert check.main(["--no-attrs", path]) == 0
    assert "OK" in capsys.readouterr().out
