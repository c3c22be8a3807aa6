"""The differential attentions over the ONE shared cache in DECODE
programs as a share of the chip's memory bandwidth, in percent: COUNTED
shared rows the programs' real tokens read (their own
``decode_attn_full_rows``: a row's context, once for each of the layers
that read it) x one ``k`` and one ``v`` row of the key/value heads
(``benchmark/flops/diff_attn.py``: 5,120 bytes at the published widths)
over the ``hetu_diff_attn_decode_*`` brackets' time in the same
programs (the one gather included), over ``hbm_bytes_per_s``. It is the
LEAST any implementation moves: a layer's query depends on the layer
before, so the readers' reads cannot be one. The gather's pass and the
context bucket's padding are the implementation's cost, so it cannot
pass 100. ``None`` where counts and time cannot be matched program by
program (``trace/diff_events.py``).

layer: kernels (hetu_tpu/ops/attention.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
from benchmark.trace import diff_events


def reduce(trace, facts):
    return diff_events.roofline(trace, facts, "decode")
