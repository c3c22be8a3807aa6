"""Device milliseconds of the routed experts' grouped matmuls
(``hetu_moe_experts``: gate|up and down over the held experts, every
expert layer) inside ONE decode program: the median over the decode
programs of the traced window.

layer: kernels (hetu_tpu/ops/moe.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import latent_moe_events as events


def reduce(trace, facts):
    programs = events.per_program(trace, "decode", "moe_experts_kernel")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
