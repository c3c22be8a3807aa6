"""Share of the traced window, in percent, in which no operation ran
on the device AND the engine had something to serve: the idle time
``device_idle_pct.serve`` counts, less the part of it that lies inside
the scheduler's ``hetu.serve.wait`` spans (nothing waiting, nothing
running). Each idle gap is split at the span's edges, not given whole
to one span. What is left is the engine's own host work between two
programs. A wait that was already open when the profiler started has
no span and counts for the engine; the log line gives the share of the
window the scheduler's leaf spans cover, which shows it.

layer: device — source: device_trace — moves: serve_request_p95_ms.
"""
import json

from benchmark.trace import program_spans


def reduce(trace, facts):
    split = program_spans.idle_split(trace)
    if split is None:
        return None
    outside, inside, window = split
    print(json.dumps({
        "idle_inside_serve_wait_pct": 100.0 * inside / window,
        "serve_leaf_coverage_pct": program_spans.leaf_coverage(trace)}),
        flush=True)
    return 100.0 * outside / window
