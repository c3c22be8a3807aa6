"""Device milliseconds of the two composed decode attentions (events
``hetu_gqa_decode_window_in`` .. ``_out``: the sliding layers' ring
behind its mask; ``hetu_gqa_decode_full_in`` .. ``_out``: the full
layers' block table) inside ONE decode program: the median over the
decode programs of the traced window. The brackets are in a profiled
engine's programs alone, and are fusion barriers: this is the bracketed
program's time (``PERF.md`` section 7). ``None`` for a program without
them.

layer: kernels (hetu_tpu/ops/attention.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import window_events


def reduce(trace, facts):
    programs = window_events.per_program(trace, "decode")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
