"""Unified runtime telemetry (hetu_tpu/telemetry): span tracer, metrics
registry, Chrome-trace export/merge/validation, executor integration,
and the overhead contract."""
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.telemetry import (Telemetry, Tracer, MetricsRegistry, NULL,
                                merge_traces, validate)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    """Executor(telemetry=<enabled>) installs the instance as the
    process-global default (so the p2p channel traces into it); reset
    it so later test modules run with telemetry off again."""
    import hetu_tpu.telemetry as tmod
    yield
    tmod._default = None


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_events_between_finds_events_recorded_late():
    """The ring is in completion order but for events a caller records
    afterwards with old times (a request's lifecycle episodes, at its
    retirement): a newest-window read must not stop at one of those."""
    tr = Tracer()
    tr.complete("a", 100, 200)
    tr.complete("b", 300, 400)
    tr.complete("late", 50, 120)        # ended long before "b"
    tr.complete("c", 410, 500)
    names = lambda t0, t1: [e[0] for e in tr.events_between(t0, t1)]  # noqa: E731
    assert names(350, 600) == ["b", "c"]
    assert names(150, 450) == ["a", "b"]
    assert names(0, 600) == ["a", "b", "late", "c"]
    # an in-order ring still stops early: nothing is late there
    ordered = Tracer()
    for i in range(5):
        ordered.complete(str(i), 10 * i, 10 * i + 5)
    assert ordered._late_ns == 0
    assert [e[0] for e in ordered.events_between(32, 50)] == ["3", "4"]


def test_spans_nest_across_threads(tmp_path):
    """Each thread records under its own tid; nested spans stay properly
    contained within their parent on that tid."""
    tr = Tracer(pid=0)
    # Both threads are alive from before the first span to after the
    # last: each waits for the other at both ends. A thread's ident is
    # free for reuse the moment it exits, so on a loaded machine a
    # second thread that starts after the first has finished can get
    # its ident, and both would record under one tid.
    both = threading.Barrier(2, timeout=30)

    def work():
        both.wait()
        with tr.span("outer"):
            with tr.span("inner"):
                time.sleep(0.002)
            time.sleep(0.001)
        both.wait()

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    events = json.load(open(path))["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], {})[e["name"]] = e
    assert len(by_tid) == 2, "two threads must get two distinct tids"
    for tid, named in by_tid.items():
        outer, inner = named["outer"], named["inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= \
            outer["ts"] + outer["dur"] + 0.01


def test_export_is_valid_chrome_trace(tmp_path):
    tr = Tracer(pid=3)
    with tr.span("a", bytes=128):
        pass
    tr.instant("mark", step=1)
    with tr.span("b"):
        pass
    path = tr.export(str(tmp_path / "trace_rank3.json"))
    n, errors = validate(path)
    assert not errors, errors
    events = json.load(open(path))["traceEvents"]
    assert n == len(events) >= 5          # 2 meta + 3 recorded
    for e in events:
        for k in ("ph", "ts", "pid", "tid"):
            assert k in e, (k, e)
    # monotonic ts over the non-metadata events, in file order
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert all(e["pid"] == 3 for e in events)


def test_check_cli_gate(tmp_path):
    tr = Tracer(pid=0)
    with tr.span("x"):
        pass
    good = tr.export(str(tmp_path / "trace_rank0.json"))
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [{"name": "x", "ph": "X"}]}, f)
    ok = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.check", good],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "OK" in ok.stdout
    nok = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.check", bad],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert nok.returncode == 1
    assert "INVALID" in nok.stdout


def test_ring_is_bounded():
    tr = Tracer(pid=0, capacity=16)
    for i in range(100):
        tr.instant(f"e{i}")
    events = [e for e in tr.drain() if e["ph"] != "M"]
    assert len(events) == 16
    assert events[-1]["name"] == "e99"    # newest survive


def test_merge_assigns_distinct_pids(tmp_path):
    """The 2-process merge: per-rank files stitch into ONE trace with
    one pid per rank."""
    for rank in range(2):
        tr = Tracer(pid=rank)
        with tr.span(f"work_r{rank}"):
            pass
        tr.export(str(tmp_path / f"trace_rank{rank}.json"))
    merged = merge_traces(str(tmp_path))
    assert merged.endswith("trace_merged.json")
    n, errors = validate(merged)
    assert not errors, errors
    events = json.load(open(merged))["traceEvents"]
    pids = {e["pid"] for e in events}
    assert pids == {0, 1}
    names = {e["name"] for e in events}
    assert {"work_r0", "work_r1"} <= names


def test_merge_three_ranks_skewed_anchors_one_truncated(tmp_path,
                                                        capsys):
    """3-rank merge with deliberately skewed wall-clock anchors and one
    rank's file torn mid-export: every rank keeps a distinct pid, each
    rank's own events stay ts-monotonic after the merge, and the
    truncated rank salvages its valid prefix with a warning instead of
    failing the merge."""
    for rank in range(3):
        tr = Tracer(pid=rank)
        # skew this rank's wall anchor: ranks' clocks disagree by
        # seconds in real fleets; exported ts must still merge
        tr._anchor_wall_ns += rank * 3_000_000_000
        for i in range(4):
            with tr.span(f"r{rank}_e{i}", idx=i):
                time.sleep(0.001)
        tr.export(str(tmp_path / f"trace_rank{rank}.json"))
    # tear rank 2's file mid-events (killed during export)
    p2 = tmp_path / "trace_rank2.json"
    text = p2.read_text()
    p2.write_text(text[: int(len(text) * 0.6)])

    merged = merge_traces(str(tmp_path))
    out = capsys.readouterr().out
    assert "salvaged" in out       # the warning names the torn rank
    events = json.load(open(merged))["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    # pid remap: three distinct process rows survive
    assert {e["pid"] for e in spans} == {0, 1, 2}
    # rank 2's prefix survived, tail lost
    r2 = [e for e in spans if e["pid"] == 2]
    assert 0 < len(r2) < 4
    # per-rank ts monotonic after the global merge sort
    for pid in (0, 1, 2):
        ts = [e["ts"] for e in events
              if e.get("pid") == pid and e.get("ph") != "M"]
        assert ts == sorted(ts), f"rank {pid} ts not monotonic"
    # skew is visible in the merged timeline (anchors ~3 s apart), and
    # the merged file still validates structurally
    t0 = min(e["ts"] for e in spans if e["pid"] == 0)
    t1 = min(e["ts"] for e in spans if e["pid"] == 1)
    assert t1 - t0 > 1_000_000     # > 1 s in trace µs
    n, errors = validate(merged)
    assert not errors, errors


def test_merge_remaps_colliding_pids(tmp_path):
    """Two files that both claim pid 0 (e.g. two single-rank runs) must
    not overlay onto one process row."""
    for i in range(2):
        tr = Tracer(pid=0)
        with tr.span(f"f{i}"):
            pass
        tr.export(str(tmp_path / f"trace_{i}.json"))
    merged = merge_traces([str(tmp_path / "trace_0.json"),
                           str(tmp_path / "trace_1.json")],
                          str(tmp_path / "m.json"))
    events = json.load(open(merged))["traceEvents"]
    assert len({e["pid"] for e in events}) == 2


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms")
    rng = np.random.RandomState(7)
    sample = rng.gamma(2.0, 3.0, size=1000)
    for v in sample:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(sample, q)), rel=1e-12)
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["p50"] == pytest.approx(float(np.percentile(sample, 50)))


def test_registry_exports_jsonl_and_prometheus(tmp_path):
    reg = MetricsRegistry()
    reg.counter("h2d_bytes").inc(4096)
    reg.gauge("bubble_fraction").set(0.25)
    h = reg.histogram("step wall ms")      # name needs sanitizing
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    lines = [json.loads(l) for l in reg.to_jsonl().splitlines()]
    by_name = {l["name"]: l for l in lines}
    assert by_name["h2d_bytes"]["value"] == 4096
    assert by_name["step wall ms"]["p50"] == 2.0
    prom = reg.to_prometheus()
    assert "# TYPE h2d_bytes counter" in prom
    assert "# TYPE bubble_fraction gauge" in prom
    assert 'step_wall_ms{quantile="0.5"} 2.0' in prom
    assert "step_wall_ms_count 3" in prom
    path = reg.dump_jsonl(str(tmp_path / "m.jsonl"))
    assert len(open(path).read().splitlines()) == 3


def test_prometheus_http_scrape():
    import urllib.request
    reg = MetricsRegistry()
    reg.counter("scrapes").inc(5)
    port = reg.serve(0)                   # ephemeral port
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "scrapes 5" in body
    finally:
        reg.close()


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------

def test_disabled_span_zero_allocations():
    """Telemetry off: span() is the bare profiler annotation (a shared
    no-op where jax was never imported) and nothing is kept once its
    ``with`` ends — zero NET per-step allocations on the hot path, with
    and without attrs. Interpreter freelists that ``gc.collect()``
    emptied refill once (a constant, some 80 blocks with keyword
    attrs), so the claim is held as: five times the steps allocate no
    more."""
    assert not NULL.enabled and NULL.tracer is None
    assert not isinstance(NULL.span("a"), type(Tracer().span("a")))

    def net_blocks(steps):
        for _ in range(200):              # warm caches
            with NULL.span("step"):
                pass
            with NULL.span("serve.decode.device", width=3, batch_bucket=4):
                pass
            NULL.inc("x")
            NULL.observe("y", 1.0)
        gc.collect()
        gc.disable()
        try:
            before = sys.getallocatedblocks()
            for _ in range(steps):
                with NULL.span("step"):
                    pass
                with NULL.span("serve.decode.device", width=3,
                               batch_bucket=4):
                    pass
            return sys.getallocatedblocks() - before
        finally:
            gc.enable()

    short, long = net_blocks(1000), net_blocks(5000)
    assert long - short <= 8 and long <= 128, \
        f"disabled span kept {long - short} blocks over 4000 more steps"


def test_overhead_guard_traced_step_under_1pct(counted):
    """The traced step path with telemetry DISABLED adds <1% wall time
    vs a no-telemetry build of the same step. The only delta between
    the two builds is the disabled instrumentation calls themselves, so
    bound (sites per step, COUNTED with a stand-in for the annotation)
    x (per-site cost) against the step. Both timings are the best of
    several repeats: under parallel test workers a median of either is
    mostly the other workers' load."""
    rng = np.random.RandomState(0)
    x = ht.Variable("ov_x", trainable=False)
    y_ = ht.Variable("ov_y", trainable=False)
    w1 = ht.init.xavier_normal((3072, 1024), name="ov_w1")
    w2 = ht.init.xavier_normal((1024, 10), name="ov_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train])
    assert not exe.config.telemetry.enabled
    feeds = {x: rng.randn(128, 3072).astype("f"),
             y_: np.eye(10, dtype="f")[rng.randint(0, 10, 128)]}
    for _ in range(3):
        exe.run(feed_dict=feeds)
    times = []
    for _ in range(15):
        t0 = time.perf_counter()
        out = exe.run(feed_dict=feeds)
        out[0].asnumpy()
        times.append(time.perf_counter() - t0)
    step_ms = min(times) * 1000

    def per_site_ms(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with NULL.span("site", width=3):
                pass
        return (time.perf_counter() - t0) / n * 1000

    site_ms = min(per_site_ms() for _ in range(10))

    del counted[:]
    exe.run(feed_dict=feeds)
    sites = len(counted)
    assert 0 < sites <= 4, counted
    assert sites * site_ms < 0.01 * step_ms, (sites, site_ms, step_ms)


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------

def _mlp():
    x = ht.Variable("tel_x", trainable=False)
    y_ = ht.Variable("tel_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name="tel_w1")
    w2 = ht.init.xavier_normal((12, 4), name="tel_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return x, y_, loss, train


def test_executor_telemetry_end_to_end(tmp_path):
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"), rank=0)
    x, y_, loss, train = _mlp()
    exe = Executor([loss, train], telemetry=tel)
    rng = np.random.RandomState(0)
    for _ in range(3):
        exe.run(feed_dict={
            x: rng.randn(8, 16).astype("f"),
            y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]})
    exe.close()                            # flushes trace + metrics
    assert tel.counter_value("jit_compiles") == 1
    assert tel.counter_value("h2d_bytes") > 0
    assert tel.metrics.histogram("step_wall_ms").count == 3
    trace = os.path.join(tel.out_dir, "trace_rank0.json")
    n, errors = validate(trace)
    assert not errors, errors
    names = {e["name"] for e in json.load(open(trace))["traceEvents"]}
    assert {"step", "jit_compile", "device_dispatch",
            "h2d_transfer"} <= names
    metrics = [json.loads(l) for l in
               open(os.path.join(tel.out_dir, "metrics_rank0.jsonl"))]
    assert any(m["name"] == "step_wall_ms" and "p50" in m
               for m in metrics)


def test_executor_pipeline_bubble_metric():
    tel = Telemetry(enabled=True, rank=0)
    rng = np.random.RandomState(0)
    with ht.context(ht.cpu(0)):
        x = ht.Variable("tb_x", trainable=False)
        w1 = ht.Variable("tb_w1", value=rng.randn(8, 6).astype("f"))
        a = ht.relu_op(ht.matmul_op(x, w1))
    with ht.context(ht.cpu(1)):
        w2 = ht.Variable("tb_w2", value=rng.randn(6, 3).astype("f"))
        y_ = ht.Variable("tb_y", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(a, w2), y_), [0])
        train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train], gpipe=True, num_microbatches=4,
                   telemetry=tel)
    feeds = {x: rng.randn(8, 8).astype("f"),
             y_: np.eye(3, dtype="f")[rng.randint(0, 3, 8)]}
    for _ in range(2):
        exe.run(feed_dict=feeds)
    h = tel.metrics.histogram("pp_bubble_fraction")
    assert h.count == 2
    # S=2, M=4 -> (S-1)/(M+S-1) = 0.2
    assert h.percentile(50) == pytest.approx(0.2)


def test_steplogger_compat_wrapper(tmp_path):
    """StepLogger rides the telemetry sink: the JSONL line and the
    step histogram both record."""
    tel = Telemetry(enabled=True, rank=0)
    log = str(tmp_path / "steps.jsonl")
    x, y_, loss, train = _mlp()
    exe = Executor([loss, train], log_path=log, telemetry=tel)
    rng = np.random.RandomState(0)
    for _ in range(2):
        exe.run(feed_dict={
            x: rng.randn(8, 16).astype("f"),
            y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]})
    exe.close()
    lines = [json.loads(l) for l in open(log)]
    assert len(lines) == 2
    assert tel.metrics.histogram("steplogger_wall_ms").count == 2


# ---------------------------------------------------------------------------
# 2-process GPipe dryrun with --telemetry (the acceptance scenario)
# ---------------------------------------------------------------------------

TELEMETRY_CONFIG = """
spmd: true
nodes:
  - host: localhost
    servers: 1
    workers: 2
    chief: true
"""

TELEMETRY_PP_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, maybe_init_distributed
maybe_init_distributed()
import jax
import hetu_tpu as ht

rank = int(os.environ["HETU_PROC_ID"])
rng = np.random.RandomState(0)
with ht.context(ht.rcpu("worker0", 0)):
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("w1", value=rng.randn(12, 16).astype("f") * 0.3)
    a = ht.relu_op(ht.matmul_op(x, w1))
with ht.context(ht.rcpu("worker1", 0)):
    w2 = ht.Variable("w2", value=rng.randn(16, 4).astype("f") * 0.3)
    y_ = ht.Variable("y_", trainable=False)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(a, w2), y_), [0])
    train_op = ht.optim.SGDOptimizer(0.2).minimize(loss)
exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
assert exe.config.telemetry.enabled, "HETU_TELEMETRY must enable it"
assert exe.subexecutors["default"].multiproc
frng = np.random.RandomState(3)
xs = frng.randn(32, 12).astype("f")
ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
for _ in range(4):
    exe.run(feed_dict={x: xs, y_: ys})
exe.close()

if rank == 0:
    # a small PS-mode session on the same fleet: its host-pull/push
    # phases land in THIS rank's trace as ps:* spans
    emb = ht.Variable("tel_emb", value=rng.randn(20, 4).astype("f"))
    ids = ht.Variable("ids", trainable=False)
    yp = ht.Variable("yp", trainable=False)
    look = ht.embedding_lookup_op(emb, ids)
    flat = ht.array_reshape_op(look, (-1, 4 * 3))
    wp = ht.Variable("wp", value=rng.randn(12, 1).astype("f") * 0.1)
    out = ht.sigmoid_op(ht.matmul_op(flat, wp))
    loss2 = ht.reduce_mean_op(ht.binarycrossentropy_op(out, yp), [0])
    train2 = ht.optim.SGDOptimizer(0.1).minimize(loss2)
    exe2 = Executor([loss2, train2], ctx=ht.cpu(0), comm_mode="PS")
    for _ in range(3):
        exe2.run(feed_dict={ids: frng.randint(0, 20, (8, 3)),
                            yp: frng.randint(0, 2, (8, 1)).astype("f")})
    exe2.close()
"""


def test_two_process_gpipe_dryrun_merged_trace(tmp_path):
    """Acceptance: a 2-process GPipe dryrun under ``heturun
    --telemetry`` yields ONE merged trace that validates under
    hetu_tpu.telemetry.check and contains spans from both ranks AND at
    least one PS phase span."""
    cfg_path = tmp_path / "tel.yml"
    cfg_path.write_text(TELEMETRY_CONFIG)
    script = tmp_path / "worker.py"
    script.write_text(TELEMETRY_PP_WORKER)
    tdir = tmp_path / "teldir"
    from launcher_util import clean_launcher_env
    env = clean_launcher_env()
    env.pop("HETU_TELEMETRY", None)
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         "--telemetry", str(tdir), sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    merged = tdir / "trace_merged.json"
    assert merged.exists(), proc.stdout
    # the CLI gate the CI uses
    check = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.check", str(merged)],
        env=env, capture_output=True, text=True)
    assert check.returncode == 0, check.stdout + check.stderr
    events = json.load(open(merged))["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    pids = {e["pid"] for e in spans}
    assert len(pids) >= 2, f"expected spans from both ranks, got {pids}"
    names = {e["name"] for e in spans}
    assert any(n.startswith("ps:") for n in names), sorted(names)
    # pipeline structure made it into the trace too
    assert any(n.startswith("pp_") or n.startswith("p2p_")
               for n in names), sorted(names)
    # per-rank metrics files rode along
    assert (tdir / "metrics_rank0.jsonl").exists()
    assert (tdir / "metrics_rank1.jsonl").exists()
