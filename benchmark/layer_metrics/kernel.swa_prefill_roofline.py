"""The flash forward WITH THE BAND (``hetu_flash_window``, the sliding
layers) in PREFILL programs as a share of the chip's compute peak, in
percent: COUNTED (query, key) pairs inside the band of the programs'
real tokens (their own ``prefill_attn_window_rows``, already times the
sliding layers) x ``4 x head_dim x heads`` operations
(``benchmark/flops/gqa_window.py``) over the events' time in the same
programs, over ``bf16_flops_per_s`` of ``benchmark/peaks.json``. What a
tile computes beyond the band (the half an edge cuts, a padded prompt's
tail) is the kernel's own cost, so it cannot pass 100. ``None`` where
counts and time cannot be matched program by program
(``trace/window_events.py``).

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.trace import window_events


def reduce(trace, facts):
    return window_events.roofline(trace, facts, "prefill")
