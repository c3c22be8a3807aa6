"""The one-token delta-rule update in DECODE programs as a share of the
chip's memory bandwidth, in percent: COUNTED (real row, delta-rule
layer) pairs (the programs' own ``decode_kda_rows``) x the state read
once and written once (``2 x 4 x heads x 128 x 128`` bytes:
``benchmark/flops/kda.py``) over the ``hetu_kda_step`` events' time in
the same programs, over ``hbm_bytes_per_s``. Padded lanes (the batch
bucket's, on the scratch slot) are the kernel's own cost. ``None``
where counts and time cannot be matched program by program
(``trace/kda_events.py``).

layer: kernels (hetu_tpu/ops/kda.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import kda_events


def reduce(trace, facts):
    return kda_events.roofline(trace, facts, "decode")
