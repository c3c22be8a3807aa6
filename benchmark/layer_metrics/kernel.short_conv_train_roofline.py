"""The gated short convolutions' share of their roofline in a training
step, in percent: the bytes that must cross HBM once — forward reads
the projection's ``[T, 3C]`` rows and writes ``[T, C]``, backward reads
both and ``dy`` and writes ``[T, 3C]``, bfloat16, the taps left out
(``benchmark/flops/short_conv.py``) — over the bandwidth of
``benchmark/peaks.json``, over the op's time in the trace. What a
composed form writes and reads again in between is the program's own
cost, so the share cannot pass 100.

layer: kernels (hetu_tpu/ops/short_conv.py) — source: device_trace —
moves: train_tokens_per_s_per_chip.
"""
import json

from benchmark.trace import short_conv_events as events


def reduce(trace, facts):
    seconds = events.seconds_per_step(trace, facts)
    if seconds is None:
        return None
    found = events.least_seconds_per_step(facts)
    if found is None:
        return None
    least, bound = found
    print(json.dumps({"short_conv_train_bound": bound,
                      "short_conv_train_least_ms_per_step": least * 1e3}),
          flush=True)
    return 100.0 * least / seconds
