"""HT6xx concurrency verifier + racecheck harness (ISSUE 12).

Acceptance pins:

* each HT601-HT606 injected-bug fixture is detected with the correct
  code and user-line provenance, and a ``# lock-ok: HT6xx`` annotation
  suppresses exactly that finding;
* the repo itself lints clean (``python -m
  hetu_tpu.analysis.concurrency`` exits 0) — every real finding the
  pass surfaced was fixed or justified in this PR;
* the racecheck stress suite certifies the batcher, ingest engine
  and PS-client paths with acyclic measured lock graphs under
  >=8-thread load, and pins the submit/close contract
  the MicroBatcher fix introduced (complete or raise, never hang).
"""
import os
import queue
import threading
import time

import numpy as np
import pytest

from hetu_tpu.analysis import concurrency
from hetu_tpu.analysis.racecheck import LockCycleError, racecheck as rc_cm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "hetu_tpu")


# ---------------------------------------------------------------------------
# static pass: one injected-bug fixture per code
# ---------------------------------------------------------------------------

def _codes(report):
    return sorted(f.code for f in report.findings)


def _line_of(src, needle):
    return src.splitlines().index(
        next(l for l in src.splitlines() if needle in l)) + 1


HT601_SRC = '''\
import threading

class Worker:
    def __init__(self):
        self.items = []
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        self.items.append(1)          # thread-context write, no lock

    def add(self, x):
        self.items.append(x)          # main-context write, no lock
'''


def test_ht601_unsynchronized_shared_write():
    report = concurrency.check_source(HT601_SRC, path="bug601.py")
    hits = [f for f in report.findings if f.code == "HT601"]
    assert len(hits) == 1 and hits[0].severity == "error"
    f = hits[0]
    assert "Worker.items" in f.message
    # anchored at one of the two write sites, with both named
    assert f.where in (f"bug601.py:{_line_of(HT601_SRC, 'thread-context')}",
                       f"bug601.py:{_line_of(HT601_SRC, 'main-context')}")
    assert "_loop()" in f.message and "add()" in f.message
    # a guarded twin is clean
    fixed = HT601_SRC.replace("self.items.append(1)",
                              "with self._lock: self.items.append(1)") \
                     .replace("self.items.append(x)",
                              "with self._lock: self.items.append(x)")
    assert not concurrency.check_source(fixed).findings
    # lock-ok on either site suppresses
    ok = HT601_SRC.replace(
        "# thread-context write, no lock",
        "# lock-ok: HT601 injected-bug fixture")
    assert not [f for f in concurrency.check_source(ok).findings
                if f.code == "HT601"]


HT602_SRC = '''\
import threading

class Pair:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def fwd(self):
        with self.a:
            with self.b:              # a -> b
                pass

    def rev(self):
        with self.b:
            with self.a:              # b -> a
                pass
'''


def test_ht602_lock_order_inversion():
    report = concurrency.check_source(HT602_SRC, path="bug602.py")
    hits = [f for f in report.findings if f.code == "HT602"]
    assert len(hits) == 1 and hits[0].severity == "error"
    f = hits[0]
    # names both locks AND their defined_at user lines
    assert set(f.data["locks"]) == {"Pair.a", "Pair.b"}
    assert set(f.data["defined_at"]) == {
        f"bug602.py:{_line_of(HT602_SRC, 'self.a = threading.Lock()')}",
        f"bug602.py:{_line_of(HT602_SRC, 'self.b = threading.Lock()')}"}
    ok = HT602_SRC.replace("# b -> a", "# lock-ok: HT602 fixture")
    assert not [f for f in concurrency.check_source(ok).findings
                if f.code == "HT602"]


HT603_SRC = '''\
import queue
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._queue = queue.Queue()

    def take(self):
        with self._lock:
            return self._queue.get()  # blocks holding _lock
'''


def test_ht603_blocking_under_lock():
    report = concurrency.check_source(HT603_SRC, path="bug603.py")
    hits = [f for f in report.findings if f.code == "HT603"]
    assert len(hits) == 1
    f = hits[0]
    assert f.where == f"bug603.py:{_line_of(HT603_SRC, 'blocks holding')}"
    assert "Pump._lock" in f.message and "_queue.get" in f.message
    ok = HT603_SRC.replace("# blocks holding _lock",
                           "# lock-ok: HT603 fixture")
    assert not concurrency.check_source(ok).findings
    # cond.wait() on the lock being waited on is the normal pattern,
    # NOT a finding (wait releases its own lock)
    normal = ("import threading\n"
              "class C:\n"
              "    def __init__(self):\n"
              "        self._cond = threading.Condition()\n"
              "    def take(self):\n"
              "        with self._cond:\n"
              "            self._cond.wait()\n")
    assert not concurrency.check_source(normal).findings


HT604_SRC = '''\
import threading
from concurrent.futures import ThreadPoolExecutor

def spawn():
    t = threading.Thread(target=_loop)
    t.start()
    pool = ThreadPoolExecutor(max_workers=2)
    return t, pool

def _loop():
    pass
'''


def test_ht604_lifecycle_leaks():
    report = concurrency.check_source(HT604_SRC, path="bug604.py")
    hits = [f for f in report.findings if f.code == "HT604"]
    assert len(hits) == 2
    wheres = {f.where for f in hits}
    assert f"bug604.py:{_line_of(HT604_SRC, 'threading.Thread')}" in wheres
    assert f"bug604.py:{_line_of(HT604_SRC, 'ThreadPoolExecutor(max')}" \
        in wheres
    # a join + shutdown path clears both
    fixed = HT604_SRC.replace(
        "    return t, pool",
        "    t.join()\n    pool.shutdown()\n    return t, pool")
    assert not [f for f in concurrency.check_source(fixed).findings
                if f.code == "HT604"]
    # daemon threads are exempt by definition
    daemon = HT604_SRC.replace("target=_loop", "target=_loop, daemon=True")
    assert not [f for f in concurrency.check_source(daemon).findings
                if f.code == "HT604"
                and "worker pool" not in f.message]


HT605_SRC = '''\
import threading

_lock = threading.Lock()
_client = None

def get_client():
    global _client
    if _client is None:
        _client = object()            # check-then-create, no lock
    return _client
'''


def test_ht605_unguarded_lazy_init():
    report = concurrency.check_source(HT605_SRC, path="bug605.py")
    hits = [f for f in report.findings if f.code == "HT605"]
    assert len(hits) == 1
    assert hits[0].where == \
        f"bug605.py:{_line_of(HT605_SRC, 'check-then-create')}"
    # double-checked locking is the fix, and is clean
    fixed = HT605_SRC.replace(
        "        _client = object()            # check-then-create, no lock",
        "        with _lock:\n"
        "            if _client is None:\n"
        "                _client = object()")
    assert not concurrency.check_source(fixed).findings
    ok = HT605_SRC.replace("# check-then-create, no lock",
                           "# lock-ok: HT605 fixture")
    assert not concurrency.check_source(ok).findings


HT606_SRC = '''\
import signal
import threading

_lock = threading.Lock()

def _handler(signum, frame):
    with _lock:                       # lock inside a signal handler
        pass

def install():
    signal.signal(signal.SIGTERM, _handler)
'''


def test_ht606_signal_handler_unsafe_work():
    report = concurrency.check_source(HT606_SRC, path="bug606.py")
    hits = [f for f in report.findings if f.code == "HT606"]
    assert len(hits) == 1
    f = hits[0]
    assert f.where == \
        f"bug606.py:{_line_of(HT606_SRC, 'lock inside a signal')}"
    assert "_handler" in f.message
    ok = HT606_SRC.replace("# lock inside a signal handler",
                           "# lock-ok: HT606 fixture")
    assert not concurrency.check_source(ok).findings


def test_lock_ok_code_must_match():
    """An annotation naming a DIFFERENT code does not suppress."""
    src = HT603_SRC.replace("# blocks holding _lock",
                            "# lock-ok: HT601 wrong code")
    assert [f for f in concurrency.check_source(src).findings
            if f.code == "HT603"]


# ---------------------------------------------------------------------------
# the repo-wide gate: the package itself lints clean
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    report = concurrency.check_paths([PKG])
    assert not report.findings, "\n" + report.to_text()


def test_cli_exit_codes(tmp_path):
    import subprocess
    import sys
    env = {**os.environ, "PYTHONPATH": REPO}
    out = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.analysis.concurrency", PKG],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    bug = tmp_path / "bug.py"
    bug.write_text(HT601_SRC)
    out = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.analysis.concurrency", "--json",
         str(bug)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 1
    import json
    doc = json.loads(out.stdout)
    assert doc["errors"] == 1 and doc["findings"][0]["code"] == "HT601"


# ---------------------------------------------------------------------------
# racecheck harness unit behavior
# ---------------------------------------------------------------------------

def test_racecheck_catches_lock_order_cycle():
    with rc_cm("cycle", assert_acyclic=False) as rc:
        a = threading.Lock()
        b = threading.Lock()

        def fwd():
            with a:
                with b:
                    pass

        def rev():
            with b:
                with a:
                    pass

        fwd()
        rev()       # same thread, so no deadlock — but the order cycle
    cycle = rc.find_cycle()
    assert cycle is not None
    with pytest.raises(LockCycleError) as ei:
        rc.assert_acyclic()
    assert "test_concurrency.py" in str(ei.value)   # creation sites


def test_racecheck_clean_graph_and_contention_stats():
    with rc_cm("clean") as rc:
        lk = threading.Lock()
        hits = []

        def work():
            for i in range(200):
                with lk:
                    hits.append(1)
                    if i % 50 == 0:
                        # hold across a real sleep so the 8 threads
                        # measurably contend (a bare append under the
                        # GIL can win the fast path every time)
                        time.sleep(0.001)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(hits) == 8 * 200
    res = rc.result()
    (stats,) = [s for s in res["locks"].values() if s["acquires"] >= 1600]
    assert stats["acquires"] == 1600
    # 8 threads on one lock MUST have contended at least once
    assert stats["contended"] > 0 and stats["wait_ms_max"] >= 0.0
    rc.assert_acyclic()                 # single lock: trivially acyclic


def test_racecheck_condition_wait_works_when_traced():
    """Condition machinery (wait/notify) must run correctly over traced
    locks — the _is_owned delegation the wrapper provides."""
    with rc_cm("cond"):
        cond = threading.Condition()
        got = []

        def consumer():
            with cond:
                while not got:
                    cond.wait(timeout=5.0)

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.05)
        with cond:
            got.append(1)
            cond.notify_all()
        t.join(timeout=5.0)
        assert not t.is_alive()


def test_racecheck_condition_wait_releases_reentrant_rlock():
    """cond.wait() under a REENTRANT hold must release every recursion
    level (the _release_save passthrough) — the stdlib fallback would
    release one level and deadlock the notifier."""
    with rc_cm("cond-rlock"):
        cond = threading.Condition()    # traced RLock underneath
        done = []

        def consumer():
            with cond:
                with cond:              # depth 2
                    while not done:
                        cond.wait(timeout=5.0)

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.05)
        with cond:                      # hangs without the passthrough
            done.append(1)
            cond.notify_all()
        t.join(timeout=5.0)
        assert not t.is_alive()


# ---------------------------------------------------------------------------
# stress: MicroBatcher submit/close race (the ISSUE 12 satellite)
# ---------------------------------------------------------------------------

def test_batcher_submit_close_race_under_racecheck(racecheck):
    """>=8 threads hammer submit() while close() lands mid-flight:
    every future must resolve or raise RuntimeError('batcher closed') —
    never hang, never drop — and the measured lock graph is acyclic."""
    from hetu_tpu.serving.batcher import MicroBatcher

    batcher = MicroBatcher(lambda feeds: feeds["x"] * 2,
                           max_batch_size=16, max_wait_ms=0.5)
    futures = []
    errors = []
    fut_mu = threading.Lock()
    start = threading.Barrier(9)

    def hammer(i):
        start.wait()
        for j in range(50):
            x = np.full((2, 3), i * 100 + j, np.float32)
            try:
                f = batcher.submit({"x": x})
            except RuntimeError as e:
                if "batcher closed" not in str(e):
                    with fut_mu:
                        errors.append(e)
                return
            except BaseException as e:  # noqa: BLE001 — surfaced below
                with fut_mu:
                    errors.append(e)
                return
            with fut_mu:
                futures.append((x, f))

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    start.wait()
    time.sleep(0.01)
    batcher.close()                     # races the in-flight submits
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()
    assert not errors, errors           # thread-side failures surface
    served = failed = 0
    for x, f in futures:
        try:
            out = f.result(timeout=10.0)    # the no-hang pin
            np.testing.assert_allclose(out, x * 2)
            served += 1
        except RuntimeError as e:
            assert "batcher closed" in str(e)
            failed += 1
    assert served + failed == len(futures) and served > 0


def test_batcher_drains_queue_on_close(racecheck):
    """Requests accepted before close() are served, not dropped."""
    from hetu_tpu.serving.batcher import MicroBatcher

    release = threading.Event()

    def slow(feeds):
        release.wait(timeout=5.0)
        return feeds["x"] + 1

    b = MicroBatcher(slow, max_batch_size=4, max_wait_ms=0.1)
    futs = [b.submit({"x": np.full((1,), i, np.float32)})
            for i in range(8)]
    release.set()
    b.close()
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(timeout=5.0), [i + 1])


def test_batcher_submit_after_close_raises():
    from hetu_tpu.serving.batcher import MicroBatcher
    b = MicroBatcher(lambda feeds: feeds["x"], max_batch_size=4)
    b.close()
    with pytest.raises(RuntimeError, match="batcher closed"):
        b.submit({"x": np.zeros((1,), np.float32)})


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_batcher_crash_mid_coalesce_fails_claimed_requests():
    """A crash landing in the straggler wait — AFTER requests were
    popped off the queue into the forming batch — must fail those
    futures too, never strand them (the 'never hangs' contract). The
    injected crash re-raises on the batcher thread BY DESIGN (a dying
    batcher should be loud on stderr) — hence the warning filter."""
    from hetu_tpu.serving.batcher import MicroBatcher

    b = MicroBatcher(lambda feeds: feeds["x"], max_batch_size=64,
                     max_wait_ms=200.0)
    orig_wait = b._cond.wait

    def boom(timeout=None):
        if timeout is not None:         # only the timed coalesce wait
            raise RuntimeError("injected mid-coalesce crash")
        return orig_wait(timeout)

    b._cond.wait = boom
    fut = b.submit({"x": np.ones((1,), np.float32)})
    with pytest.raises(RuntimeError, match="batcher thread died"):
        fut.result(timeout=5.0)
    with pytest.raises(RuntimeError, match="batcher closed"):
        b.submit({"x": np.ones((1,), np.float32)})
    b._cond.wait = orig_wait
    b.close()


def test_batcher_serve_error_fails_tick_not_batcher():
    from hetu_tpu.serving.batcher import MicroBatcher
    b = MicroBatcher(lambda feeds: 1 / 0, max_batch_size=4)
    with pytest.raises(ZeroDivisionError):
        b.submit({"x": np.zeros((1,), np.float32)}).result(timeout=5.0)
    b.serve_fn = lambda feeds: feeds["x"]
    out = b.submit({"x": np.ones((1,), np.float32)}).result(timeout=5.0)
    np.testing.assert_allclose(out, [1.0])
    b.close()


# ---------------------------------------------------------------------------
# stress + regression: IngestEngine / DaemonPool teardown
# ---------------------------------------------------------------------------

def test_ingest_close_cancel_never_deadlocks_on_blocked_worker():
    """The HT603 regression the ISSUE names: a worker wedged in
    queue.get must not deadlock close(cancel=True) (mid-error
    teardown) — and must not hang interpreter exit (daemon worker)."""
    from hetu_tpu.ingest import IngestEngine

    q = queue.Queue()
    eng = IngestEngine(None, lookahead=4)
    eng.submit(q.get, tag=0)            # wedges the worker
    eng.submit(lambda: 1, tag=1)        # queued behind it
    time.sleep(0.05)
    t0 = time.monotonic()
    eng.close(cancel=True)
    assert time.monotonic() - t0 < 2.0, "close(cancel=True) deadlocked"
    q.put(None)                         # let the wedged worker finish


def test_ingest_engine_stress_under_racecheck(racecheck):
    from hetu_tpu.ingest import IngestEngine

    def run_engine(seed):
        eng = IngestEngine(None, lookahead=3, name=f"stress{seed}")
        total = 0
        with eng:
            inflight = 0
            for i in range(60):
                eng.submit(lambda v: v * 2, i, tag=i)
                inflight += 1
                if inflight >= 3:
                    tag, out = eng.pop()
                    assert out == tag * 2
                    total += 1
                    inflight -= 1
            while inflight:
                tag, out = eng.pop()
                assert out == tag * 2
                total += 1
                inflight -= 1
        return total

    results = []
    res_mu = threading.Lock()

    def worker(seed):
        n = run_engine(seed)
        with res_mu:
            results.append(n)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()
    assert results == [60] * 8


def test_daemon_pool_semantics():
    from concurrent.futures import CancelledError
    from hetu_tpu.ingest import DaemonPool

    pool = DaemonPool(max_workers=1, thread_name_prefix="t")
    order = []
    futs = [pool.submit(order.append, i) for i in range(10)]
    for f in futs:
        f.result(timeout=5.0)
    assert order == list(range(10))     # one worker: submission order

    err = pool.submit(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        err.result(timeout=5.0)

    q = queue.Queue()
    wedged = pool.submit(q.get)         # blocks the worker
    queued = pool.submit(lambda: 2)
    time.sleep(0.05)
    t0 = time.monotonic()
    ok = pool.shutdown(cancel_futures=True, timeout=0.5)
    assert time.monotonic() - t0 < 2.0
    assert not ok                       # the wedged worker did not exit
    with pytest.raises(CancelledError):
        queued.result(timeout=1.0)
    q.put("x")                          # unwedge; daemon worker exits
    assert wedged.result(timeout=5.0) == "x"
    with pytest.raises(RuntimeError):
        pool.submit(lambda: 3)


def _bare_ps_runtime(push_pool):
    """A PSRuntime skeleton with just the teardown-path state — close()
    and drain() exercise the real shutdown ordering without a server
    fleet."""
    from hetu_tpu.ps.runtime import PSRuntime

    rt = object.__new__(PSRuntime)
    rt._closed = False
    rt._push_pool = push_pool
    rt._pending_push = []
    rt._dense_future = None
    rt.device_tables = {}
    rt.caches = {}
    rt.updates_dropped = False

    class _Tel:
        enabled = False

    class _Cfg:
        ps_dense_cached = ()
        telemetry = _Tel()

    class _Client:
        servers_down = False
        nworkers = 1

        def wait_all(self):
            pass

    rt.config = _Cfg()
    rt.client = _Client()
    return rt


def test_ps_runtime_close_shuts_push_pool_after_drain():
    """The HT604 regression: PSRuntime's ASP push pool used to have NO
    shutdown path at all — close() must drain, then stop the workers."""
    from hetu_tpu.ingest import DaemonPool

    pool = DaemonPool(max_workers=2, thread_name_prefix="ps-push-t")
    rt = _bare_ps_runtime(pool)
    fut = pool.submit(lambda: 42)
    rt._pending_push.append(fut)
    rt.close()
    assert fut.result(timeout=1.0) == 42    # drained BEFORE shutdown
    assert all(not t.is_alive() for t in pool._threads)
    with pytest.raises(RuntimeError):
        pool.submit(lambda: 1)
    rt.close()                              # idempotent


def test_ps_runtime_close_never_deadlocks_on_wedged_rpc():
    """Shutdown ordering under a dead fleet: a push wedged in an RPC
    retry must not hang close() (drain is skipped post-shutdown, the
    queue is cancelled, the daemon worker is abandoned)."""
    from hetu_tpu.ingest import DaemonPool

    pool = DaemonPool(max_workers=1, thread_name_prefix="ps-push-w")
    rt = _bare_ps_runtime(pool)
    rt.client.servers_down = True           # fleet already stopped
    q = queue.Queue()
    pool.submit(q.get)                      # the wedged "RPC"
    time.sleep(0.05)
    t0 = time.monotonic()
    rt.close()
    assert time.monotonic() - t0 < 2.0, "close() deadlocked on the RPC"
    assert rt.updates_dropped               # drain was skipped, flagged
    q.put(None)                             # unwedge the daemon worker


# ---------------------------------------------------------------------------
# stress: PS client from many threads
# ---------------------------------------------------------------------------

@pytest.fixture
def ps_client(monkeypatch):
    from hetu_tpu.ps import client as ps_client_mod
    from hetu_tpu.ps import server as ps_server

    port = ps_server.pick_free_port()
    monkeypatch.setenv("HETU_PS_PORTS", str(port))
    monkeypatch.setenv("HETU_PS_HOSTS", "127.0.0.1")
    ps_server.ensure_server(port=port, nworkers=1)
    client = ps_client_mod.PSClient(rank=0, nworkers=1)
    yield client
    client.shutdown_servers()
    client.close()
    ps_server.shutdown_server()


def test_ps_client_many_thread_stress_under_racecheck(racecheck,
                                                      ps_client):
    """8 threads push/pull one sparse table concurrently: no deadlock,
    no lost update (the server's row accumulation is exact), acyclic
    measured lock graph on the worker side."""
    tid, rows, width, nthreads, reps = 7101, 64, 4, 8, 25
    ps_client.init_tensor(tid, (rows, width), kind=1, opt="None")
    ps_client.set_param(tid, np.zeros((rows, width), np.float32))
    start = threading.Barrier(nthreads)

    def hammer(t):
        start.wait()
        idx = np.array([t, (t + 1) % rows], dtype=np.int64)
        vals = np.ones((2, width), np.float32)
        for _ in range(reps):
            ps_client.sparse_push(tid, idx, vals, width)
            ps_client.wait(tid)
            got = ps_client.sparse_pull(tid, idx, width)
            assert got.shape == (2, width)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive()
    final = ps_client.sparse_pull(tid, np.arange(rows), width)
    # row r was hit by thread r and thread r-1 -> 2*reps increments
    expect = np.zeros((rows, width), np.float32)
    for t in range(nthreads):
        expect[t] += reps
        expect[(t + 1) % rows] += reps
    np.testing.assert_allclose(final, expect)


# ---------------------------------------------------------------------------
# server lifecycle: metrics scrape + graphboard handles
# ---------------------------------------------------------------------------

def test_metrics_shutdown_joins_thread_and_frees_port():
    import socket
    from hetu_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("x").inc(3)
    port = reg.serve(0)
    import urllib.request
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert "x 3" in body
    thread = reg._server_thread
    reg.shutdown()
    assert thread is not None and not thread.is_alive()
    # the socket is actually released: an immediate rebind succeeds
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", port))
    s.close()
    reg.shutdown()                      # idempotent


def test_graphboard_show_returns_shutdown_handle(tmp_path):
    import urllib.request
    import hetu_tpu as ht
    from hetu_tpu import graphboard
    from hetu_tpu.executor import Executor

    x = ht.Variable("cc_x", trainable=False)
    w = ht.init.xavier_normal((6, 3), name="cc_w")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0])
    exe = Executor([loss])
    url = graphboard.show(exe, str(tmp_path / "g.html"), port=0)
    # port=0 is not meaningful for SimpleHTTPRequestHandler URLs built
    # from the requested port — use the handle's bound address instead
    port = url._httpd.server_address[1]
    page = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/g.html", timeout=5).read().decode()
    assert "<svg" in page
    thread = url._thread
    url.shutdown()                      # joins serve_forever + socket
    assert not thread.is_alive()
    url.shutdown()                      # idempotent
    graphboard.close()                  # module-level close: no-op now
