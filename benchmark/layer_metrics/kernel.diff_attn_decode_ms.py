"""Device milliseconds of the differential attentions over the ONE
shared cache inside a decode program (events ``hetu_diff_attn_decode_in``
.. ``_out``: the full layer's and each cross layer's composed
``diff_rows_attention``, the one gather of the shared rows inside the
first): the median over the decode programs of the traced window. The
brackets are in a profiled engine's programs alone, and are fusion
barriers: this is the bracketed program's time (``PERF.md`` section 7).
``None`` for a program without them.

layer: kernels (hetu_tpu/ops/attention.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import diff_events


def reduce(trace, facts):
    programs = diff_events.per_program(trace, "decode")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
