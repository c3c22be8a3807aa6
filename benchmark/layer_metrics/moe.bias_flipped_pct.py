"""The share of a training session's picks that the router's selection
bias changed, in percent: the picks that are not among the top-k of the
sigmoid scores alone, over ALL the picks of the expert layers (wherever
their expert is held), from the counter the compiled step accumulates
on the device (``Executor.moe_counters()``: ``moe_bias_flipped_picks``;
every training step since the session began). The bias here is a seeded
buffer that nothing moves, so the share says how far the selection
stands from the scores' own; under a balancing rule it would say how
hard the rule pushes.

layer: model step (hetu_tpu/models/hybrid_decoder.py) — source:
program_counter — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import short_conv_events as events


def reduce(trace, facts):
    counted = events.counters(facts)
    if counted is None:
        return None
    return 100.0 * counted["moe_bias_flipped_picks"] / counted["moe_picks"]
