"""The hyper-connections' residual path in DECODE programs as a share
of the chip's memory bandwidth, in percent: COUNTED (real token,
sublayer) pairs (the programs' own ``mhc_rows``) x the bytes one must
move (``benchmark/flops/mhc.py``) over the ``hetu_mhc_*`` events' time
in the same programs, over ``hbm_bytes_per_s``. It reads LOW, and that
is its message: a decode call moves at most 32 rows x 72 KB = 2.3 MB
of stream, less than the prepared maps it reads beside them, and its
time is launches and latency, not bandwidth. ``None`` where counts and
time cannot be matched program by program (``trace/mhc_events.py``).

layer: kernels (hetu_tpu/ops/mhc.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import mhc_events


def reduce(trace, facts):
    return mhc_events.roofline(trace, facts, "decode")
