"""The routed experts' grouped matmuls in DECODE programs, as a share
of the chip's memory bandwidth, in percent: COUNTED bytes (the
programs' own ``moe_expert_visits`` x 3 x hidden x width x itemsize: an
expert that got a row has its weights read, one that got none is not,
``benchmark/flops/moe.py``) over the ``hetu_moe_experts`` events' time
in the same programs, over ``hbm_bytes_per_s`` of
``benchmark/peaks.json``. ``None`` where counts and time cannot be
matched program by program (``trace/latent_moe_events.py``).

layer: kernels (hetu_tpu/ops/moe.py) — source: device_trace — moves:
serve_request_p95_ms.
"""
import json

from benchmark.flops import moe
from benchmark.harness import device
from benchmark.trace import latent_moe_events as events


def reduce(trace, facts):
    found = events.counted(trace, facts, "decode", "moe_experts_kernel")
    if found is None:
        return None
    totals, seconds = found
    w = events.model_widths(facts)
    nbytes = moe.weight_bytes(totals["decode_moe_expert_visits"],
                              w["hidden"], w["width"], w["itemsize"])
    print(json.dumps({"moe_experts_decode": {
        "expert_visits": totals["decode_moe_expert_visits"],
        "kernel_s": seconds, "gbytes_per_s": nbytes / seconds / 1e9}}),
        flush=True)
    return 100.0 * nbytes / seconds \
        / device.peaks(facts["device_kind"])["hbm_bytes_per_s"]
