"""Milliseconds a ``hetu.serve.stall`` span lasts, the median over
those wholly inside the traced window: the gap between two tokens that
a streaming caller sees when another request's prompt is prefilled
(its build, its program and the host's wait for it, its sample, the
finish). ``engine.decode_stalled_pct`` is how much of the window they
add up to; this is how long one is, which chunked prefill shortens and
a faster prefill program shortens.

``None`` without a stall wholly inside the window, and for a program
from before the span.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import program_spans, stall_spans


def reduce(trace, facts):
    stalls = stall_spans.stalls(trace, whole=True)
    if not stalls:
        return None
    return stats.median(program_spans.milliseconds(stalls))
