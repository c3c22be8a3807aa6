"""Device milliseconds per training step inside LayerNorm's backward
kernel (``hetu_layer_norm_bwd``: dx and the scale / bias gradients from
one read of x and dy): the sum of its events' durations in the traced
window over its steps.

Logged beside it, for whoever reads a run's output: the calls per step
(one per LayerNorm of the model: 25 in GPT-2 small, 26 in BERT-base)
and, per shape, the least and the median time of a call.

layer: kernels (hetu_tpu/ops/pallas_norm.py) — source: device_trace —
moves: train_tokens_per_s_per_chip.
"""
import json
import statistics

from benchmark.trace import layernorm_calls, xplane


def reduce(trace, facts):
    found = layernorm_calls.calls(trace)
    if not found or not facts.get("steps"):
        return None
    # per training step and chip
    share = facts["steps"] * len(xplane.device_planes(trace))
    every = [ns for durations in found.values() for ns in durations]
    ms = sum(every) / 1e6 / share
    print(json.dumps({
        "layernorm_bwd_calls_per_step": len(every) / share,
        "layernorm_bwd_ms_per_step": ms,
        "layernorm_bwd_us_per_call": {
            name: {"least": min(ns) / 1e3,
                   "median": statistics.median(ns) / 1e3}
            for name, ns in found.items()}}), flush=True)
    return ms
