"""Device milliseconds one training step spends in the gated short
convolutions, forward and backward, over the convolution layers: the
step's events booked to the graph ops ``ShortConvOp`` and
``_ShortConvGradientOp`` by their scopes (``trace/step_account.py``;
``layer_metrics/short_conv_names.json`` says why by scope). ``None``
where the profile holds no such op or no account of the step.

layer: kernels (hetu_tpu/ops/short_conv.py) — source: device_trace —
moves: train_tokens_per_s_per_chip.
"""
from benchmark.trace import short_conv_events as events


def reduce(trace, facts):
    seconds = events.seconds_per_step(trace, facts)
    return None if seconds is None else seconds * 1e3
