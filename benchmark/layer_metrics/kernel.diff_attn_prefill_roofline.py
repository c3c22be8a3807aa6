"""The banded two-map flash forward (the window layers' differential
attention: the window kernel's own events, ``hetu_flash_window``, whose
heads here are the two maps of each pair) in PREFILL programs as a share
of the chip's compute peak, in percent: COUNTED (query, key) pairs
inside the band of the programs' real tokens (their own
``prefill_attn_window_rows``, already times the window layers) x ``6 x
head_dim x heads`` operations (two score maps over ``head_dim``, two
products with the ``2 x head_dim`` value: ``benchmark/flops/diff_attn.py``)
over the events' time in the same programs, over ``bf16_flops_per_s``
of ``benchmark/peaks.json``. The zeros the kernel multiplies to bring
query and key to the value's width, and what a tile computes beyond the
band, are the kernel's own cost, so it cannot pass 100. ``None`` where
counts and time cannot be matched program by program
(``trace/diff_events.py``).

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: serve_request_p95_ms.
"""
from benchmark.trace import diff_events


def reduce(trace, facts):
    return diff_events.roofline(trace, facts, "prefill")
