"""Black-box observability (hetu_tpu/telemetry/{flight,watchdog,memory,
blackbox}): flight-recorder ring semantics, seq-divergence
detection, memory accounting, heartbeats + fleet watchdog, truncated-
trace salvage, and the acceptance scenario — one rank
of a 2-process GPipe dryrun SIGKILLed mid-run."""
import gc
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.telemetry import (Telemetry, FlightRecorder, MetricsRegistry,
                                NULL, merge_traces, validate)
from hetu_tpu.telemetry import blackbox, memory
from hetu_tpu.telemetry.watchdog import (EXIT_WATCHDOG, FleetWatchdog,
                                         Heartbeat)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    import hetu_tpu.telemetry as tmod
    yield
    tmod._default = None


def _cli_env():
    return {**os.environ, "PYTHONPATH": REPO + os.pathsep
            + os.environ.get("PYTHONPATH", "")}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_wraparound(tmp_path):
    """Only the newest ``capacity`` events survive; a record completed
    after its slot was recycled must not corrupt the ring."""
    fr = FlightRecorder(rank=0, capacity=8)
    early = fr.start("p2p", "p2p_recv", peer=1, tag="early")
    for i in range(30):
        fr.record("collective", "cpp_dispatch", tag=f"step{i}")
    fr.complete(early)              # slot long recycled: must not raise
    fr.step(29)
    path = fr.dump(str(tmp_path), reason="test")
    doc = json.load(open(path))
    assert len(doc["events"]) == 8
    seqs = [e["seq"] for e in doc["events"]]
    assert seqs == list(range(22, 30)), seqs        # newest survive
    assert all(e["t1"] is not None for e in doc["events"])
    assert doc["last_step"] == 29 and doc["reason"] == "test"


def test_flight_step_ring_survives_event_volume():
    """Step boundaries live in their own small ring — a flood of comm
    events can't evict them."""
    fr = FlightRecorder(rank=0, capacity=4, step_capacity=16)
    for s in range(3):
        fr.step(s)
        for i in range(50):
            fr.record("ps", "ps_pull", nbytes=4)
    snap = fr.snapshot()
    assert [s for s, _ in snap["steps"]] == [0, 1, 2]
    assert all(e["step"] == 2 for e in snap["events"])  # newest step tag


def test_flight_crash_reason_survives_flush(tmp_path):
    fr = FlightRecorder(rank=3)
    fr.dump(str(tmp_path), reason="signal 15")
    fr.dump(str(tmp_path), reason="flush")      # atexit re-dump
    doc = json.load(open(tmp_path / "flight_rank3.json"))
    assert doc["reason"] == "signal 15"


# ---------------------------------------------------------------------------
# blackbox analyzer
# ---------------------------------------------------------------------------

def _write_dump(tmp_path, rank, events, last_step=0, nprocs=2):
    doc = {"rank": rank, "pid": 1000 + rank, "nprocs": nprocs,
           "wall": time.time(), "last_step": last_step,
           "steps": [[last_step, time.time()]], "events": events,
           "reason": "flush"}
    with open(tmp_path / f"flight_rank{rank}.json", "w") as f:
        json.dump(doc, f)


def _coll(seq, kind="cpp_dispatch", t1=1.0):
    return {"seq": seq, "group": "collective", "kind": kind,
            "peer": None, "tag": f"s{seq}", "bytes": 0, "step": seq,
            "t0": 1.0, "t1": t1}


def test_blackbox_seq_divergence(tmp_path):
    """Rank 0 entered collective seq 4 that rank 1 never did -> rank 1
    is the laggard/suspect and the divergence names the op."""
    _write_dump(tmp_path, 0, [_coll(s) for s in range(5)], last_step=4)
    _write_dump(tmp_path, 1, [_coll(s) for s in range(4)], last_step=3)
    rep = blackbox.analyze(str(tmp_path))
    d = rep["divergence"]
    assert d is not None
    assert d["seq"] == 4 and d["ahead"] == [0] and d["behind"] == [1]
    assert d["event"]["kind"] == "cpp_dispatch"
    assert rep["suspect_ranks"] == [1]
    text = blackbox.format_report(rep)
    assert "DIVERGENCE at collective seq 4" in text


def test_blackbox_dead_rank_and_pending(tmp_path):
    """A rank with a heartbeat but no flight dump is dead; a surviving
    rank's pending recv corroborates by naming the peer."""
    pending = {"seq": 0, "group": "p2p", "kind": "p2p_recv", "peer": 1,
               "tag": "f3:77:1", "bytes": 0, "step": 3, "t0": 5.0,
               "t1": None}
    _write_dump(tmp_path, 0, [pending], last_step=3)
    for rank, step in ((0, 3), (1, 2)):
        with open(tmp_path / f"hb_rank{rank}.json", "w") as f:
            json.dump({"rank": rank, "pid": 1000 + rank, "step": step,
                       "time": time.time() - 60, "done": False}, f)
    rep = blackbox.analyze(str(tmp_path))
    assert rep["dead_ranks"] == [1]
    assert rep["suspect_ranks"] == [1]
    assert rep["ranks"]["0"]["pending"][0]["kind"] == "p2p_recv"
    text = blackbox.format_report(rep)
    assert "NO flight dump" in text and "PENDING p2p_recv" in text


def test_blackbox_cli(tmp_path):
    _write_dump(tmp_path, 0, [_coll(0)], last_step=1)
    out = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.blackbox",
         str(tmp_path), "--json"],
        capture_output=True, text=True, env=_cli_env())
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert "0" in rep["ranks"]
    empty = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.blackbox",
         str(tmp_path / "nope")],
        capture_output=True, text=True, env=_cli_env())
    assert empty.returncode == 2


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------

def _mlp():
    x = ht.Variable("bb_x", trainable=False)
    y_ = ht.Variable("bb_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name="bb_w1")
    w2 = ht.init.xavier_normal((12, 4), name="bb_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return x, y_, loss, train


def test_memory_analysis_captured_at_compile(tmp_path):
    """memory_analysis lands on the jit_compile span AND the memory_*
    gauge family; compiled outputs stay correct through the AOT path."""
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"), rank=0)
    x, y_, loss, train = _mlp()
    exe = Executor([loss, train], telemetry=tel)
    rng = np.random.RandomState(0)
    feeds = {x: rng.randn(8, 16).astype("f"),
             y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]}
    l0 = float(np.asarray(exe.run(feed_dict=feeds)[0].asnumpy()))
    l1 = float(np.asarray(exe.run(feed_dict=feeds)[0].asnumpy()))
    assert l1 < l0                          # training still trains
    exe.close()
    gauges = {m["name"]: m["value"] for m in tel.metrics.snapshot()
              if m["name"].startswith("memory_")}
    assert gauges.get("memory_arg_bytes", 0) > 0
    assert "memory_temp_bytes" in gauges
    trace = json.load(open(tmp_path / "tel" / "trace_rank0.json"))
    jc = [e for e in trace["traceEvents"] if e["name"] == "jit_compile"]
    assert jc and jc[0]["args"]["arg_bytes"] > 0
    assert "temp_bytes" in jc[0]["args"]
    assert tel.counter_value("jit_compiles") == 1


def test_device_memory_stats_graceful_on_cpu():
    """CPU devices report no memory_stats: the probe returns {} and the
    per-step observer is a no-op instead of raising."""
    assert memory.device_memory_stats() == {}
    tel = Telemetry(enabled=True, rank=0)
    memory.observe_device_memory(tel)       # must not raise
    memory.observe_device_memory(NULL)


def test_oom_report_names_parameters():
    import jax.numpy as jnp
    # large enough to be among the five largest whatever arrays the
    # worker's earlier test files left alive (a run of the suite found
    # 4.2 MiB of them above a 16 KiB table)
    big = jnp.zeros((2048, 1024), jnp.float32)
    text = memory.oom_report(named_params={"my_table": big}, limit=5)
    assert "my_table" in text and "live buffers" in text
    assert memory.is_oom(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not memory.is_oom(ValueError("shapes disagree"))


# ---------------------------------------------------------------------------
# overhead contract (flight recorder + heartbeat disabled path)
# ---------------------------------------------------------------------------

def test_disabled_flight_zero_allocations():
    """Telemetry off: flight_start returns the shared None and the
    start/complete pair allocates nothing."""
    assert NULL.flight_start("p2p", "p2p_recv") is None
    for _ in range(200):
        NULL.flight_complete(NULL.flight_start("p2p", "x"))
        NULL.flight_step(1)
    gc.collect()
    gc.disable()
    try:
        before = sys.getallocatedblocks()
        for _ in range(5000):
            NULL.flight_complete(NULL.flight_start("p2p", "x"))
            NULL.flight_step(1)
        after = sys.getallocatedblocks()
    finally:
        gc.enable()
    assert after - before <= 8, \
        f"disabled flight path leaked {after - before} blocks"


def test_enabled_flight_overhead_under_1pct():
    """Enabled flight recording: bound (sites-per-step x per-record
    cost) against a measured step, the same method as PR 2's span
    guard — a real step crosses far fewer than 32 flight sites."""
    rng = np.random.RandomState(0)
    x = ht.Variable("fo_x", trainable=False)
    y_ = ht.Variable("fo_y", trainable=False)
    w1 = ht.init.xavier_normal((3072, 1024), name="fo_w1")
    w2 = ht.init.xavier_normal((1024, 10), name="fo_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train])
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
    step_ms = float(np.median(times)) * 1000

    tel = Telemetry(enabled=True, rank=0)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        tel.flight_complete(tel.flight_start("ps", "ps_pull", nbytes=64))
    per_record_ms = (time.perf_counter() - t0) / n * 1000
    assert 32 * per_record_ms < 0.01 * step_ms, (per_record_ms, step_ms)


# ---------------------------------------------------------------------------
# heartbeat + watchdog units
# ---------------------------------------------------------------------------

def test_heartbeat_throttles_and_marks_done(tmp_path):
    hb = Heartbeat(str(tmp_path), rank=2, interval=30.0)
    first = json.load(open(tmp_path / "hb_rank2.json"))
    assert first["pid"] == os.getpid() and not first["done"]
    hb.beat(5)                     # inside the interval: no write
    assert json.load(open(tmp_path / "hb_rank2.json"))["step"] == 0
    hb.done()
    doc = json.load(open(tmp_path / "hb_rank2.json"))
    assert doc["done"] and doc["step"] == 5


def test_watchdog_check_stall_semantics(tmp_path):
    wd = FleetWatchdog(str(tmp_path), num_workers=2, timeout=5.0)
    wd.started = time.time() - 120          # fleet launched 2 min ago
    now = time.time()
    for rank, (age, done) in enumerate(((1.0, False), (60.0, False))):
        with open(tmp_path / f"hb_rank{rank}.json", "w") as f:
            json.dump({"rank": rank, "pid": 1, "step": 3,
                       "time": now - age, "done": done}, f)
    stalled = wd.check()
    assert [r for r, _, _ in stalled] == [1]
    # a done rank is never stalled, however old its beat
    with open(tmp_path / "hb_rank1.json", "w") as f:
        json.dump({"rank": 1, "pid": 1, "step": 9,
                   "time": now - 60.0, "done": True}, f)
    assert wd.check() == []
    # a missing heartbeat only counts after the boot grace: with a
    # fresh fleet it is ignored, 120s into the fleet it is a stall
    os.remove(tmp_path / "hb_rank0.json")
    wd.started = time.time()
    assert wd.check() == []
    wd.started = time.time() - 120
    assert [r for r, _, _ in wd.check()] == [0]


def test_watchdog_ignores_prestart_heartbeats(tmp_path):
    """A leftover heartbeat from a previous fleet in a reused telemetry
    dir must not false-fire the watchdog on the new healthy fleet."""
    with open(tmp_path / "hb_rank0.json", "w") as f:
        json.dump({"rank": 0, "pid": 1, "step": 7,
                   "time": time.time() - 600, "done": False}, f)
    wd = FleetWatchdog(str(tmp_path), num_workers=1, timeout=5.0)
    assert wd.check() == []        # stale beat -> boot grace, not stall


# ---------------------------------------------------------------------------
# truncated-trace salvage (satellite: crashed-rank merge tolerance)
# ---------------------------------------------------------------------------

def test_merge_salvages_truncated_trace(tmp_path, capsys):
    from hetu_tpu.telemetry import Tracer
    for rank in range(2):
        tr = Tracer(pid=rank)
        for i in range(20):
            with tr.span(f"w{rank}_{i}"):
                pass
        tr.export(str(tmp_path / f"trace_rank{rank}.json"))
    # rank 1 "crashed mid-export": chop the file mid-object
    p1 = tmp_path / "trace_rank1.json"
    text = p1.read_text()
    p1.write_text(text[:int(len(text) * 0.6)])
    merged = merge_traces(str(tmp_path))
    out = capsys.readouterr().out
    assert "salvaged" in out
    n, errors = validate(merged)
    assert not errors, errors
    events = json.load(open(merged))["traceEvents"]
    pids = {e["pid"] for e in events}
    assert pids == {0, 1}          # the crashed rank still contributes
    r1 = [e for e in events if e["pid"] == 1 and e["ph"] == "X"]
    assert 0 < len(r1) < 20        # a prefix, not everything


# ---------------------------------------------------------------------------
# metrics /healthz + serving SLO healthz (satellite)
# ---------------------------------------------------------------------------

def test_metrics_server_healthz_and_shutdown():
    import urllib.request
    import urllib.error
    reg = MetricsRegistry()
    reg.counter("x").inc()
    port = reg.serve(0)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=5).read()
    assert json.loads(body)["ok"] is True
    reg.shutdown()
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=1)


def test_serving_healthz_slo_503():
    import urllib.request
    import urllib.error
    from hetu_tpu.serving.http import ServingHTTPServer

    class SlowBackend:
        def predict(self, feeds):
            time.sleep(0.05)
            return [np.zeros(1)]

    srv = ServingHTTPServer(SlowBackend(), slo_p99_ms=10.0,
                            slo_window=16)
    port = srv.start()
    try:
        url = f"http://127.0.0.1:{port}"
        body = urllib.request.urlopen(f"{url}/healthz", timeout=5).read()
        assert json.loads(body)["ok"] is True      # no traffic yet
        req = urllib.request.Request(
            f"{url}/v1/predict",
            data=json.dumps({"inputs": {"x": [[1.0]]}}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=5).read()
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{url}/healthz", timeout=5)
        assert exc.value.code == 503
        assert "p99" in json.loads(exc.value.read())["reason"]
    finally:
        srv.stop()


def test_serving_healthz_error_rate_503():
    import urllib.request
    import urllib.error
    from hetu_tpu.serving.http import ServingHTTPServer

    class FailingBackend:
        def predict(self, feeds):
            raise RuntimeError("backend down")

    srv = ServingHTTPServer(FailingBackend(), slo_error_rate=0.5,
                            slo_window=16)
    port = srv.start()
    try:
        url = f"http://127.0.0.1:{port}"
        req = urllib.request.Request(
            f"{url}/v1/predict",
            data=json.dumps({"inputs": {"x": [[1.0]]}}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=5)
        assert exc.value.code == 500
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{url}/healthz", timeout=5)
        assert exc.value.code == 503
        assert "error rate" in json.loads(exc.value.read())["reason"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# acceptance: 2-process GPipe dryrun, one rank SIGKILLed mid-run
# ---------------------------------------------------------------------------

WATCHDOG_CONFIG = """
spmd: true
nodes:
  - host: localhost
    workers: 2
    chief: true
"""

WATCHDOG_WORKER = """
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, maybe_init_distributed
maybe_init_distributed()
import hetu_tpu as ht

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
assert exe._heartbeat is not None, "HETU_WATCHDOG_DIR must arm it"
frng = np.random.RandomState(3)
xs = frng.randn(32, 12).astype("f")
ys = np.eye(4, dtype="f")[frng.randint(0, 4, 32)]
for _ in range(600):
    exe.run(feed_dict={x: xs, y_: ys})
    time.sleep(0.05)
exe.close()
"""


def test_watchdog_names_sigkilled_rank(tmp_path):
    """Acceptance: SIGKILL one rank of a 2-process GPipe dryrun ->
    the watchdog fires within the timeout, the fleet exits with the
    distinct watchdog code, flight dumps exist for the surviving rank,
    and the blackbox CLI names the dead rank."""
    from launcher_util import clean_launcher_env
    cfg = tmp_path / "wd.yml"
    cfg.write_text(WATCHDOG_CONFIG)
    script = tmp_path / "worker.py"
    script.write_text(WATCHDOG_WORKER)
    tdir = tmp_path / "teldir"
    env = clean_launcher_env()
    env.pop("HETU_TELEMETRY", None)
    hang_timeout = 8.0
    proc = subprocess.Popen(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg),
         "--telemetry", str(tdir), "--hang-timeout", str(hang_timeout),
         sys.executable, str(script)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    victim_pid = None
    try:
        # wait for rank 1 to boot and make progress, then SIGKILL it
        hb1 = tdir / "hb_rank1.json"
        deadline = time.time() + 180
        while time.time() < deadline:
            try:
                doc = json.loads(hb1.read_text())
                if doc.get("step", 0) >= 2:
                    victim_pid = doc["pid"]
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.2)
        assert victim_pid is not None, \
            (proc.poll(), tdir.exists() and sorted(os.listdir(tdir)))
        t_kill = time.time()
        os.kill(victim_pid, signal.SIGKILL)
        out, _ = proc.communicate(timeout=120)
        fired_after = time.time() - t_kill
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    # distinct exit code, within timeout (+ grace for dump/kill/merge)
    assert proc.returncode == EXIT_WATCHDOG, (proc.returncode, out)
    assert fired_after < hang_timeout + 30, fired_after
    assert "watchdog: rank" in out and "stalled" in out, out
    # the surviving rank's black box made it out
    assert (tdir / "flight_rank0.json").exists(), sorted(os.listdir(tdir))
    assert not (tdir / "flight_rank1.json").exists()
    # faulthandler stacks were collected from the survivor (SIGUSR1)
    stacks = (tdir / "stacks_rank0.log")
    assert stacks.exists() and "Thread" in stacks.read_text()
    # blackbox names the dead rank
    bb = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.blackbox",
         str(tdir), "--json"],
        capture_output=True, text=True, env=_cli_env())
    assert bb.returncode == 0, bb.stdout + bb.stderr
    rep = json.loads(bb.stdout)
    assert 1 in rep["dead_ranks"], rep
    assert rep["suspect_ranks"] == [1], rep
    # the survivor's dump explains where it was: most kills land with
    # rank 0 blocked in a p2p recv/send on the dead peer (a pending
    # flight entry); a kill mid-transfer can instead crash rank 0 on
    # the broken socket, in which case the excepthook dumped with an
    # "uncaught" reason — either way the black box names the site
    dump0 = json.loads((tdir / "flight_rank0.json").read_text())
    pend = [e for e in dump0["events"] if e["t1"] is None]
    assert pend or dump0["reason"].startswith("uncaught"), dump0["reason"]
    if pend:
        assert pend[-1]["group"] in ("p2p", "sched"), pend
    # p2p traffic to the dead peer is in the ring regardless
    assert any(e["kind"].startswith("p2p_") for e in dump0["events"])
