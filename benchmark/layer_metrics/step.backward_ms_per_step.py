"""Device milliseconds one training step spends in the instructions whose
only role is the BACKWARD pass's (``hetu.bwd/<op_type>/<node>``: the
nodes ``graph/autodiff.py:gradients`` minted), read as
``step.forward_ms_per_step`` is (``trace/step_account.py``). ``None``
where the profile carries no such program or the join does not hold.

layer: model step (hetu_tpu/executor.py:_build_step, Op.scope) —
source: device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import step_account


def reduce(trace, facts):
    return step_account.metric(trace, facts, "backward")
