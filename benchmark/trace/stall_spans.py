"""The spans of the serving engine's phase clock in a profile (read by
``layer_metrics/engine.decode_stalled_pct``, ``engine.stall_ms`` and
``engine.loop_host_pct``). The names are data:
``layer_metrics/stall_names.json`` and, for the wait span,
``layer_metrics/program_names.json``. ``xplane.load`` keeps a span's
name, start and end and no argument, so everything here is arithmetic
on names and intervals through ``program_spans.spans``. Everything
returns ``None`` for a program from before the clock, never zero.
"""
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import program_spans, xplane


def names():
    return read_json(BENCH_DIR + "/layer_metrics/stall_names.json")


def has_phase_clock(trace):
    """Does the profile, anywhere, hold a span only a program with the
    phase clock opens?"""
    if trace is None:
        return False
    n = names()
    new = {n["stall_span"], n["prefill_sync_span"]}
    return any(name in new for name, _, _ in xplane.host_spans(
        trace, prefix=program_spans.PREFIX))


def stalls(trace, whole):
    """``[(start, end)]`` of the stall spans inside the window (wholly,
    or every one's part inside it); ``None`` without the phase
    clock."""
    if not has_phase_clock(trace):
        return None
    return program_spans.spans(trace, name=names()["stall_span"],
                               whole=whole)


def loop_split(trace):
    """``(ns the scheduler thread is blocked on the device, ns it is
    not waiting for work)`` inside the window; ``None`` without the
    phase clock or where the thread only waited."""
    if not has_phase_clock(trace):
        return None
    n = names()

    def clipped(name):
        return program_spans.spans(trace, name=name, whole=False)

    lo, hi = xplane.window(trace)
    waits = xplane.union(clipped(program_spans.names()["wait_span"]))
    serving = (hi - lo) - xplane.total(waits)
    if serving <= 0:
        return None
    dispatches = xplane.union(
        [i for name in n["dispatch_spans"] for i in clipped(name)])
    decode_syncs = xplane.subtract(
        xplane.union(clipped(n["decode_sync_span"])), dispatches)
    sync = xplane.total(decode_syncs) + xplane.total(
        xplane.union(clipped(n["prefill_sync_span"])))
    return sync, serving
