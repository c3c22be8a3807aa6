"""Device milliseconds of the one-token delta-rule update
(``hetu_kda_step``, every delta-rule layer) inside ONE decode program:
the median over the decode programs of the traced window. ``None`` for
a program without the kernel.

layer: kernels (hetu_tpu/ops/kda.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import kda_events


def reduce(trace, facts):
    programs = kda_events.per_program(trace, "decode")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
