"""Device milliseconds one training step spends in the held experts'
grouped products: the forward's two (``hetu_moe_experts``), the
backward's two for the rows (``hetu_moe_experts_dx``) and two
transposed for the weights (``hetu_moe_experts_dw``), over the expert
layers (``layer_metrics/gqa_train_names.json``). ``None`` where the
profile holds no such event.

layer: kernels (hetu_tpu/ops/moe.py) — source: device_trace — moves:
train_tokens_per_s_per_chip.
"""
from benchmark.trace import gqa_train_events as events


def reduce(trace, facts):
    seconds = events.seconds_per_step(trace, facts,
                                      "moe_experts_train_kernels")
    return None if seconds is None else seconds * 1e3
