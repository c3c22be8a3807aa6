"""Device milliseconds per training step inside the dropout-mask
kernel (``hetu_dropout_mask``: one mask's keep decisions from the
core's generator, as bytes, from a seed alone): the sum of its events'
durations in the traced window over its steps.

Logged beside it, for whoever reads a run's output: the calls per step
and, per shape, the least and the median time of a call. A model with
``n`` dropout ops makes ``2 n`` calls a step where the backward draws
its mask again (50 in GPT-2 small and in BERT-base: the embedding's
dropout and two a layer), ``n`` where the compiler merged each pair
into one call and kept the mask; any other count says the kernel is not
taken where it should be.

layer: kernels (hetu_tpu/ops/pallas_dropout.py) — source: device_trace —
moves: train_tokens_per_s_per_chip.
"""
import json
import statistics

from benchmark.trace import dropout_calls, xplane


def reduce(trace, facts):
    found = dropout_calls.calls(trace)
    if not found or not facts.get("steps"):
        return None
    # per training step and chip
    share = facts["steps"] * len(xplane.device_planes(trace))
    every = [ns for durations in found.values() for ns in durations]
    ms = sum(every) / 1e6 / share
    print(json.dumps({
        "dropout_mask_calls_per_step": len(every) / share,
        "dropout_mask_ms_per_step": ms,
        "dropout_mask_us_per_call": {
            name: {"least": min(ns) / 1e3,
                   "median": statistics.median(ns) / 1e3}
            for name, ns in found.items()}}), flush=True)
    return ms
