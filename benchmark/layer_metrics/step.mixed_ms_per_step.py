"""Device milliseconds one training step spends in instructions that hold
MORE than one role: XLA fused operations of two passes into one
(``bwd+opt`` is a weight's gradient matmul with its Adam update inside;
``fwd+bwd`` a forward value recomputed beside its gradient). The reader
logs the time by pair (``trace/step_account.py``). ``None`` where the
profile carries no such program or the join does not hold.

layer: model step (hetu_tpu/executor.py:_build_step, Op.scope) —
source: device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import step_account


def reduce(trace, facts):
    return step_account.metric(trace, facts, "mixed")
