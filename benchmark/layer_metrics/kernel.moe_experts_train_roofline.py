"""The held experts' grouped products' share of their roofline in a
training step, in percent: 18 x rows x hidden x width operations from
the rows the step COUNTED on the device (forward 6, backward 12) and
the visited experts' weights read in both directions plus their
float32 gradients written (``benchmark/flops/moe_train.py``), the
larger of the two bounds of ``benchmark/peaks.json``, over the kernels'
time in the trace. What a row tile's padding computes is the kernel's
own cost, so the share cannot pass 100.

layer: kernels (hetu_tpu/ops/moe.py) — source: device_trace — moves:
train_tokens_per_s_per_chip.
"""
import json

from benchmark.trace import gqa_train_events as events


def reduce(trace, facts):
    found = events.roofline(trace, facts, "moe_experts_train_kernels",
                            events.experts_least_seconds_per_step)
    if found is None:
        return None
    value, least, bound = found
    print(json.dumps({
        "moe_experts_train_bound": bound,
        "moe_experts_train_least_ms_per_step": least * 1e3,
        "moe_experts_backward_ms_per_step": 1e3 * (events.seconds_per_step(
            trace, facts, "moe_experts_backward_kernels") or 0.0)}),
        flush=True)
    return value
