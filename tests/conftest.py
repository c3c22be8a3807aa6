"""Test harness: force an 8-device virtual CPU platform so sharding /
multi-chip code paths run hermetically without TPUs (the fake-device
strategy the reference lacks — SURVEY.md §4).

The platform is assigned, not setdefault: on a machine with a chip the
suite must still run on the CPU (a chip belongs to one process, and the
numeric references assume float32 matmuls).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# jax may have been imported (and have read JAX_PLATFORMS) before this
# file ran; pin the choice through the config as well
jax.config.update("jax_platforms", "cpu")

# numeric tests compare against float64 numpy references; keep matmuls in
# real float32 on the CPU backend (TPU bench runs use the default bf16 path)
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)


@pytest.fixture(autouse=True)
def _no_telemetry_default_leak():
    """telemetry.resolve() promotes any ENABLED Telemetry instance to
    the process-global default (deliberate in production: config-less
    components attribute into the same trace). Between tests it is
    leakage — a test passing telemetry=Telemetry(enabled=True) into any
    component would silently flip every LATER test's executors onto the
    telemetry-on code paths (AOT compile, spans, atexit flushes), making
    the suite order-dependent. Restore the default around every test."""
    from hetu_tpu import telemetry as _tmod
    before = _tmod._default
    yield
    _tmod._default = before


@pytest.fixture
def counted(monkeypatch):
    """The names of the profiler annotations ``Telemetry.span`` opens
    while the test runs, in order: a counting stand-in takes the place
    of ``jax.profiler.TraceAnnotation``, so what the disabled path costs
    is held by COUNT, never by a timing."""
    from hetu_tpu.telemetry import tracer
    entered = []

    class CountingAnnotation:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer, "_trace_annotation", CountingAnnotation)
    return entered


# ---------------------------------------------------------------------------
# thread hygiene (ISSUE 12): a test that leaks a live non-daemon thread
# fails — leaked threads outlive the test, hang interpreter exit, and
# poison later tests' thread-leak baselines one test too late.
# ---------------------------------------------------------------------------

# names (prefix match) of non-daemon threads that are allowed to
# outlive a test; extend deliberately, with a reason
THREAD_LEAK_ALLOWLIST = (
    "pytest",           # pytest-timeout & friends
    "pydevd",           # debugger attach
)


def _leaked_nondaemon(before):
    import threading
    out = []
    for t in threading.enumerate():
        if t in before or t.daemon or t is threading.current_thread():
            continue
        if any(t.name.startswith(p) for p in THREAD_LEAK_ALLOWLIST):
            continue
        # teardown that is mid-exit gets a short grace join before
        # being called a leak
        t.join(timeout=2.0)
        if t.is_alive():
            out.append(t)
    return out


@pytest.fixture(autouse=True)
def _no_thread_leaks(request):
    """Fail any test that leaves a live non-daemon thread behind
    (explicit allowlist above; opt out per-test with
    ``@pytest.mark.thread_leak_ok`` and a comment saying why)."""
    import threading
    before = set(threading.enumerate())
    yield
    if request.node.get_closest_marker("thread_leak_ok"):
        return
    leaked = _leaked_nondaemon(before)
    if leaked:
        names = ", ".join(f"{t.name!r}" for t in leaked)
        pytest.fail(
            f"test leaked live non-daemon thread(s): {names} — join "
            f"them (or shutdown their pool/server) before returning; "
            f"see THREAD_LEAK_ALLOWLIST in conftest.py",
            pytrace=False)


@pytest.fixture
def racecheck(tmp_path, request):
    """Instrumented-lock harness (hetu_tpu/analysis/racecheck.py):
    locks created inside the test are traced; on teardown the measured
    acquisition-order graph is dumped to ``lockgraph_<test>.json`` (a
    CI failure artifact) and asserted acyclic."""
    from hetu_tpu.analysis.racecheck import racecheck as _rc
    with _rc(name=request.node.name, assert_acyclic=False) as rc:
        yield rc
    path = tmp_path / f"lockgraph_{request.node.name}.json"
    path.write_text(rc.to_json())
    rc.assert_acyclic()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On test failure, copy any telemetry / black-box files the test's
    tmp_path left behind (merged traces, flight dumps, heartbeats,
    stack logs) into $HETU_TEST_ARTIFACTS/<testname>/ — CI uploads that
    directory as an artifact when the job fails, so a red distributed
    test ships its own post-mortem instead of just a log tail."""
    outcome = yield
    rep = outcome.get_result()
    if rep.failed:
        item._hetu_failed = True
    # collect at TEARDOWN of a failed test (any phase): fixture-written
    # artifacts — e.g. the racecheck lockgraph JSON, written (and its
    # acyclicity asserted) in fixture finalization — exist only then
    if rep.when != "teardown" or not getattr(item, "_hetu_failed", False):
        return
    dest_root = os.environ.get("HETU_TEST_ARTIFACTS")
    tmp = getattr(item, "funcargs", {}).get("tmp_path")
    if not dest_root or tmp is None:
        return
    import glob
    import shutil
    patterns = ("trace_*.json", "flight_rank*.json", "hb_rank*.json",
                "stacks_*.log", "metrics_rank*.jsonl", "oom_rank*.txt",
                "health_rank*.jsonl", "health_lastgood_rank*.json",
                "lockgraph_*.json", "rangedb_*.json",
                "timeline_rank*.jsonl", "fleet_report.json")
    found = []
    for pat in patterns:
        found += glob.glob(os.path.join(str(tmp), "**", pat),
                           recursive=True)
    for src in found:
        dst = os.path.join(dest_root, item.name,
                           os.path.relpath(src, str(tmp)))
        try:
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy2(src, dst)
        except OSError:
            pass                    # artifact salvage is best effort
