"""Device milliseconds per training step inside the Pallas flash
attention kernels (forward, and from S=512 up the two backward
kernels): the sum of their events' durations in the traced window over
its steps.

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import flash_calls as _flash


def reduce(trace, facts):
    seconds = _flash.seconds_per_step(trace, facts)
    return None if seconds is None else seconds * 1e3
