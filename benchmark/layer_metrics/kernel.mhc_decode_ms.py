"""Device milliseconds of the hyper-connections' residual path
(``hetu_mhc_pre`` + ``hetu_mhc_post``, every sublayer) inside ONE
decode program: the median over the decode programs of the traced
window. ``None`` for a program without the kernels.

layer: kernels (hetu_tpu/ops/mhc.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import mhc_events


def reduce(trace, facts):
    programs = mhc_events.per_program(trace, "decode")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
