"""The absorbed latent attention of DECODE programs as a share of its
roofline, in percent: the least time the chip could take for the
COUNTED context rows (the programs' own ``mla_context_rows``: the
larger of their bytes over ``hbm_bytes_per_s`` and their operations
over ``bf16_flops_per_s``, ``benchmark/flops/mla.py`` and
``benchmark/peaks.json``) over the ``hetu_mla_decode`` events' time in
the same programs. ``None`` where counts and time cannot be matched
program by program (``trace/latent_moe_events.py``).

layer: kernels (hetu_tpu/ops/pallas_mla.py) — source: device_trace —
moves: serve_request_p95_ms.
"""
import json

from benchmark.flops import mla
from benchmark.harness import device
from benchmark.trace import latent_moe_events as events


def reduce(trace, facts):
    found = events.counted(trace, facts, "decode", "mla_decode_kernel")
    if found is None:
        return None
    totals, seconds = found
    w = events.model_widths(facts)
    peaks = device.peaks(facts["device_kind"])
    rows = totals["decode_mla_context_rows"]
    t_memory = mla.absorbed_bytes(rows, w["latent"], w["rope"],
                                  w["itemsize"]) / peaks["hbm_bytes_per_s"]
    t_compute = mla.absorbed_flops(rows, w["heads"], w["latent"],
                                   w["rope"]) / peaks["bf16_flops_per_s"]
    print(json.dumps({"mla_decode": {
        "context_rows": rows, "kernel_s": seconds,
        "bound": "compute" if t_compute >= t_memory else "memory"}}),
        flush=True)
    return 100.0 * max(t_memory, t_compute) / seconds
