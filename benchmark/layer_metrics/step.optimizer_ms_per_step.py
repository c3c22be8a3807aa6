"""Device milliseconds one training step spends in the instructions whose
only role is the OPTIMIZER's (``hetu.opt/OptimizerOp/<node>/<parameter>``:
each parameter's update, its working copy included), read as
``step.forward_ms_per_step`` is (``trace/step_account.py``, which also
logs the time by parameter). A weight's update that XLA fused into its
gradient's matmul is not here but in ``step.mixed_ms_per_step``. ``None``
where the profile carries no such program or the join does not hold.

layer: optimizer (hetu_tpu/optimizer.py:OptimizerOp.compute) —
source: device_trace — moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import step_account


def reduce(trace, facts):
    return step_account.metric(trace, facts, "optimizer")
