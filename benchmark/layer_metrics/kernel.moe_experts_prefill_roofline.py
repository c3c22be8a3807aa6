"""The routed experts' grouped matmuls in PREFILL programs, as a share
of the chip's compute peak, in percent: COUNTED work (the programs'
own ``moe_routed_rows`` x 6 x hidden x width operations,
``benchmark/flops/moe.py``) over the ``hetu_moe_experts`` events' time
in the same programs, over ``bf16_flops_per_s`` of
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
    found = events.counted(trace, facts, "prefill", "moe_experts_kernel")
    if found is None:
        return None
    totals, seconds = found
    w = events.model_widths(facts)
    flops = moe.flops(totals["prefill_moe_routed_rows"], w["hidden"],
                      w["width"])
    print(json.dumps({"moe_experts_prefill": {
        "routed_rows": totals["prefill_moe_routed_rows"],
        "kernel_s": seconds, "tflops_per_s": flops / seconds / 1e12}}),
        flush=True)
    return 100.0 * flops / seconds \
        / device.peaks(facts["device_kind"])["bf16_flops_per_s"]
