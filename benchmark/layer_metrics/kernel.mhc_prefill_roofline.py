"""The hyper-connections' residual path in PREFILL programs as a share
of the chip's memory bandwidth, in percent: COUNTED (real token,
sublayer) pairs (the programs' own ``mhc_rows``) x the bytes one must
move whatever implements it (the stream read once and written once,
``y`` read, ``u`` written: ``benchmark/flops/mhc.py``) over the
``hetu_mhc_*`` events' time in the same programs, over
``hbm_bytes_per_s`` of ``benchmark/peaks.json``. The program's two
kernels read the stream twice, so where it lives in HBM (prompt buckets
from 2,048 tokens) they cannot pass 10 / 14 = 71% at four streams by
this count; a shorter prompt's stream XLA keeps on chip between them,
and there the kernels' vector work bounds the reading, at about 77%
(``benchmark/flops/mhc.py``). Padded tokens are the kernels' own cost.
``None`` where counts and time cannot be matched program by program
(``trace/mhc_events.py``).

layer: kernels (hetu_tpu/ops/mhc.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import mhc_events


def reduce(trace, facts):
    return mhc_events.roofline(trace, facts, "prefill")
