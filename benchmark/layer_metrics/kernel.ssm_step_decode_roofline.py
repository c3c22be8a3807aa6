"""The one-token state update in DECODE programs as a share of the
chip's memory bandwidth, in percent: COUNTED (real row, Mamba layer)
pairs (the programs' own ``decode_ssm_rows``) x the state read once and
written once (``2 x 4 x d_inner x d_state`` bytes:
``benchmark/flops/ssm.py``) over the ``hetu_ssm_step`` events' time in
the same programs, over ``hbm_bytes_per_s``. Padded lanes (the batch
bucket's, on the scratch slot) are the kernel's own cost. ``None``
where counts and time cannot be matched program by program
(``trace/ssm_events.py``).

layer: kernels (hetu_tpu/ops/ssm.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import ssm_events


def reduce(trace, facts):
    return ssm_events.roofline(trace, facts, "decode")
