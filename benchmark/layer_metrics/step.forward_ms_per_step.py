"""Device milliseconds one training step spends in the instructions whose
only role is the FORWARD pass's: self time on the ``XLA Ops`` line of
the events whose instruction, and everything fused into it, was traced
under ``hetu.fwd/<op_type>/<node>`` scopes, over the whole executions of
the step inside the window (``trace/step_account.py``). ``None`` where
the profile carries no such program or the join does not hold.

layer: model step (hetu_tpu/executor.py:_build_step, Op.scope) —
source: device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import step_account


def reduce(trace, facts):
    return step_account.metric(trace, facts, "forward")
