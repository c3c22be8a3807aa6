"""The grouped-query flash kernels' share of their roofline in a
training step, in percent: the least time the chip could take for the
step's calls — forward 4 and backward 10 operations x ``head_dim`` x 28
heads a scored pair, pairs counted INSIDE the band or under the
diagonal only, K / V bytes once a group; per call the larger of the two
bounds of ``benchmark/peaks.json`` (``benchmark/flops/gqa_train.py``) —
over the kernels' time in the trace. What a tile computes beyond the
counted pairs is the kernel's own cost, so the share cannot pass 100.

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: train_tokens_per_s_per_chip.
"""
import json

from benchmark.trace import gqa_train_events as events


def reduce(trace, facts):
    found = events.roofline(trace, facts, "flash_gqa_kernels",
                            events.attention_least_seconds_per_step)
    if found is None:
        return None
    value, least, bound = found
    print(json.dumps({
        "flash_gqa_bound": bound,
        "flash_gqa_least_ms_per_step": least * 1e3,
        "flash_gqa_window_ms_per_step": 1e3 * (events.seconds_per_step(
            trace, facts, "flash_gqa_window_kernels") or 0.0),
        "flash_gqa_backward_ms_per_step": 1e3 * (events.seconds_per_step(
            trace, facts, "flash_gqa_backward_kernels") or 0.0)}),
        flush=True)
    return value
