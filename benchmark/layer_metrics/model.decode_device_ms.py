"""Device milliseconds of one decode program (``gpt_paged_step``: all
running sequences, one token each): for every ``bench.engine.decode``
host span of the traced window, the longest program on the device's
``XLA Modules`` line that ran inside it; the median over the spans.
The program itself cannot be picked out by name: the engine jits a
``functools.partial``, so every one of its programs is
``jit__unknown(<fingerprint>)`` in the trace (PERF.md, for the tracing
issue); the small eager gathers and slices that share the span are
shorter than the step.

layer: model step (hetu_tpu/models/gpt.py paged forwards) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane


def reduce(trace, facts):
    if trace is None or not xplane.device_planes(trace):
        return None
    span_name = read_json(BENCH_DIR + "/trace/names.json")["decode_span"]
    lo, hi = xplane.window(trace)
    modules = sorted(
        (s, e) for _, s, e in xplane.line_events(
            xplane.device_planes(trace)[0], xplane.MODULES_LINE))
    longest = []
    for name, s0, s1 in xplane.host_spans(trace):
        if name != span_name or s0 < lo or s1 > hi:
            continue
        inside = [e - s for s, e in modules if s >= s0 and e <= s1]
        if inside:
            longest.append(max(inside) / 1e6)
    return stats.median(longest) if longest else None
