"""The expanded latent attention of PREFILL programs (the flash forward
kernel at query/key heads of nope + rope and value heads of v) as a
share of the chip's compute peak, in percent: COUNTED work by the REAL
prompt lengths (the programs' own ``mla_score_pairs`` x 2 x heads x
(nope + rope + v) operations, ``benchmark/flops/mla.py``) over the
kernel's time in the same programs, over ``bf16_flops_per_s`` of
``benchmark/peaks.json``. The bucket's padding and the values padded
to the keys' width are the kernel's own cost. ``None`` where counts and
time cannot be matched program by program
(``trace/latent_moe_events.py``).

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: serve_request_p95_ms.
"""
import json

from benchmark.flops import mla
from benchmark.harness import device
from benchmark.trace import latent_moe_events as events


def reduce(trace, facts):
    found = events.counted(trace, facts, "prefill", "flash_forward_kernel")
    if found is None:
        return None
    totals, seconds = found
    w = events.model_widths(facts)
    flops = mla.expanded_flops(totals["prefill_mla_score_pairs"],
                               w["heads"], w["nope"], w["rope"], w["v"])
    print(json.dumps({"mla_prefill": {
        "score_pairs": totals["prefill_mla_score_pairs"],
        "kernel_s": seconds, "tflops_per_s": flops / seconds / 1e12}}),
        flush=True)
    return 100.0 * flops / seconds \
        / device.peaks(facts["device_kind"])["bf16_flops_per_s"]
