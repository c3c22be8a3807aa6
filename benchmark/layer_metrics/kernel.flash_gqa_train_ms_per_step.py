"""Device milliseconds one training step spends in the grouped-query
flash kernels, forward and backward, with the band and without
(``hetu_flash_gqa[_window]_fwd`` / ``_bwd``:
``layer_metrics/gqa_train_names.json``). ``None`` where the profile
holds no such event.

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import gqa_train_events as events


def reduce(trace, facts):
    seconds = events.seconds_per_step(trace, facts, "flash_gqa_kernels")
    return None if seconds is None else seconds * 1e3
