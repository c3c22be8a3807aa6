"""The chunked delta-rule kernel in PREFILL programs as a share of the
bound that binds it, in percent: COUNTED (real token, delta-rule layer)
pairs (the programs' own ``prefill_kda_rows``) x the least time one
takes at the chip's peaks (``benchmark/flops/kda.py``: the larger of
the chunked form's necessary matmul FLOPs over ``bf16_flops_per_s`` and
the least bytes over ``hbm_bytes_per_s``; the memory bound binds at the
published widths) over the ``hetu_kda_chunk`` events' time in the same
programs. It reads LOW, and that is its message: the recurrence is
float32 (six bfloat16 passes a matmul, no published peak) and the
kernel inverts a triangular matrix by products. Padded tokens are the
kernel's own cost. No fusing can push it past 100. ``None`` where
counts and time cannot be matched program by program
(``trace/kda_events.py``: by order where their numbers agree).

layer: kernels (hetu_tpu/ops/kda.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import kda_events


def reduce(trace, facts):
    return kda_events.roofline(trace, facts, "prefill")
