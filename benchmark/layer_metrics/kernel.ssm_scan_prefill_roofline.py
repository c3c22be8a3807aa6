"""The chunked scan kernel in PREFILL programs as a share of the chip's
memory bandwidth, in percent: COUNTED (real token, Mamba layer) pairs
(the programs' own ``prefill_ssm_rows``) x the LEAST bytes any
implementation must move for one (``x`` in, ``y`` out, ``dt_rank + 2
d_state`` float32 in: ``benchmark/flops/ssm.py``) over the
``hetu_ssm_scan`` events' time in the same programs, over
``hbm_bytes_per_s`` of ``benchmark/peaks.json``. It reads LOW, and that
is its message: the recurrence is bound by the vector unit (27
elementwise operations a byte of this count), and ``peaks.json``
publishes no vector peak to hold it against, so the metric says how far
the kernel is from the one bound the benchmark has. Padded tokens are
the kernel's own cost. No fusing can push it past 100. ``None`` where
counts and time cannot be matched program by program
(``trace/ssm_events.py``).

layer: kernels (hetu_tpu/ops/ssm.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import ssm_events


def reduce(trace, facts):
    return ssm_events.roofline(trace, facts, "prefill")
