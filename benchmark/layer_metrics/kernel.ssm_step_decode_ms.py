"""Device milliseconds of the one-token state update (``hetu_ssm_step``,
every Mamba layer) inside ONE decode program: the median over the
decode programs of the traced window. ``None`` for a program without
the kernel.

layer: kernels (hetu_tpu/ops/ssm.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import ssm_events


def reduce(trace, facts):
    programs = ssm_events.per_program(trace, "decode")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
