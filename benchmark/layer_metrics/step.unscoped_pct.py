"""Share, in percent, of a training step's device self time that the
account cannot put down to a graph op: instructions with no ``hetu.``
scope in them (XLA's own copies, bitcasts and what it made between two
scopes) and events whose instruction the profile's module does not hold.
The yardstick's own blind share (``trace/step_account.py``). ``None``
where the profile carries no such program or the join does not hold.

layer: device —
source: device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import step_account


def reduce(trace, facts):
    return step_account.metric(trace, facts, "unscoped_pct")
