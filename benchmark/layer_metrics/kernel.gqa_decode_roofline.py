"""The two composed decode attentions in DECODE programs as a share of
the chip's memory bandwidth, in percent: COUNTED cached rows the
programs' real tokens read (their own ``decode_attn_window_rows`` +
``decode_attn_full_rows``: ``min(context, window)`` a sliding layer,
``context`` a full one) x one ``k`` and one ``v`` row of the key/value
heads (``benchmark/flops/gqa_window.py``: 4,096 bytes at the published
widths) over the ``hetu_gqa_decode_*`` events' time in the same
programs, over ``hbm_bytes_per_s``. The gather's second pass, the
ring's rows outside the window and the context bucket's padding are the
implementation's cost: the share says how far the composed form is from
a paged kernel that reads each row once. ``None`` where counts and time
cannot be matched program by program (``trace/window_events.py``).

layer: kernels (hetu_tpu/ops/attention.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
from benchmark.trace import window_events


def reduce(trace, facts):
    return window_events.roofline(trace, facts, "decode")
