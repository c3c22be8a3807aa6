"""Device milliseconds of the absorbed latent attention
(``hetu_mla_decode``, every layer) inside ONE decode program: the
median over the decode programs of the traced window.

layer: kernels (hetu_tpu/ops/pallas_mla.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
from benchmark.harness import stats
from benchmark.trace import latent_moe_events as events


def reduce(trace, facts):
    programs = events.per_program(trace, "decode", "mla_decode_kernel")
    if not programs:
        return None
    return stats.median([ns / 1e6 for _, _, ns in programs])
