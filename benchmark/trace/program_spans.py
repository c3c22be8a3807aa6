"""The program's own spans and programs in a profile (read by the
per-layer metrics that the ``hetu.*`` spans serve).

``Telemetry.span`` puts every program span into the profiler's host
plane as ``hetu.<name>``, on the clock the device planes share, and the
engine's and the executor's jitted programs have stable names. Which
names, and which patterns, is data:
``layer_metrics/program_names.json``. Everything here returns ``None``
where the trace holds no such span or program (a program from before
the spans), never zero.
"""
import re

from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane

PREFIX = "hetu."


def names():
    return read_json(BENCH_DIR + "/layer_metrics/program_names.json")


def spans(trace, name=None, prefix=None, whole=True):
    """Sorted ``[(start, end)]`` of the program spans called ``name``
    (or whose name starts with ``prefix``) inside the traced window:
    those wholly inside it, or with ``whole=False`` every one's part
    inside it. ``None`` where the trace holds no program span at
    all."""
    if trace is None:
        return None
    every = xplane.host_spans(trace, prefix=PREFIX)
    if not every:
        return None
    lo, hi = xplane.window(trace)
    picked = sorted((s, e) for n, s, e in every
                    if (n == name if name is not None
                        else n.startswith(prefix)))
    if whole:
        return [(s, e) for s, e in picked if s >= lo and e <= hi]
    return xplane.clip(picked, lo, hi)


def modules(trace, pattern):
    """Sorted ``[(start, end)]`` of the programs on the first device's
    ``XLA Modules`` line whose name matches ``pattern`` and that ran
    wholly inside the window."""
    if trace is None or not xplane.device_planes(trace):
        return []
    lo, hi = xplane.window(trace)
    pat = re.compile(pattern)
    return sorted((s, e) for n, s, e in xplane.line_events(
        xplane.device_planes(trace)[0], xplane.MODULES_LINE)
        if s >= lo and e <= hi and pat.search(n))


def milliseconds(intervals):
    return [(e - s) / 1e6 for s, e in intervals]


def idle_split(trace):
    """The device's idle time in the window, split at the edges of the
    scheduler's ``wait`` spans: ``(idle ns outside them, idle ns inside
    them, window ns)``, idle averaged over the device planes as
    ``xplane.idle_percent`` does. ``None`` without a device plane or
    without the scheduler's spans."""
    n = names()
    serve = spans(trace, prefix=n["serve_span_prefix"], whole=False)
    if not serve or not xplane.device_planes(trace):
        return None
    lo, hi = xplane.window(trace)
    waits = xplane.union(spans(trace, name=n["wait_span"], whole=False))
    _, _, unions = xplane.busy(trace)
    outside = inside = 0.0
    for busy in unions:
        idle = xplane.subtract([(lo, hi)], busy)
        engine = xplane.subtract(idle, waits)
        outside += xplane.total(engine) / len(unions)
        inside += (xplane.total(idle) - xplane.total(engine)) / len(unions)
    return outside, inside, hi - lo


def leaf_coverage(trace):
    """Share of the window, in percent, that the scheduler's leaf spans
    cover (they tile its thread, so this should read near 100)."""
    serve = spans(trace, prefix=names()["serve_span_prefix"], whole=False)
    if not serve:
        return None
    lo, hi = xplane.window(trace)
    return 100.0 * xplane.total(xplane.union(serve)) / (hi - lo)
