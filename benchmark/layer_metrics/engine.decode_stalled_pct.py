"""Share of the traced window, in percent, in which decode-ready rows
stood still behind another request's prefill: the union of the
``hetu.serve.stall`` spans (``scheduler.py:step``: from after the read
of the program in flight to the end of the finish that follows the
prefill, opened only when at least one other running row could have
decoded), each clipped to the window, over the window. Under
``prefill_chunk`` every chunk's step is its own span: the same share in
shorter pieces. 0 in a window whose prefills all met an empty engine.

``None`` where the profile holds neither that span nor the prefill's
sync leaf anywhere, or no ``hetu.*`` span at all: a program from before
the phase clock, and also a profile of the new program in which no
prompt was prefilled at all, which nothing in it tells from the old one
(names and intervals are all a reader has). No serve cell's window is
without a prefill.

layer: serving engine (hetu_tpu/serving/scheduler.py) — source:
program_span — moves: serve_request_p95_ms.
"""
from benchmark.trace import stall_spans, xplane


def reduce(trace, facts):
    stalls = stall_spans.stalls(trace, whole=False)
    if stalls is None:
        return None
    lo, hi = xplane.window(trace)
    return 100.0 * xplane.total(xplane.union(stalls)) / (hi - lo)
