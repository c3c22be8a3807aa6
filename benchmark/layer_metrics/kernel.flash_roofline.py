"""The flash kernels' share of their roofline, in percent: the least
time the chip could take for one step's flash calls (per call the
larger of operations / peak FLOP/s and bytes / peak bytes/s, from
``benchmark/flops/flash.py`` and ``benchmark/peaks.json``) over the
time the kernels took in the trace. Which bound applies is logged by
the driver line ``flash_bound``.

layer: kernels (hetu_tpu/ops/pallas_attention.py) — source:
device_trace — moves: train_tokens_per_s_per_chip.
"""
import json

from benchmark.trace import flash_calls as _flash


def reduce(trace, facts):
    seconds = _flash.seconds_per_step(trace, facts)
    if seconds is None:
        return None
    least, bound = _flash.least_seconds_per_step(trace, facts)
    print(json.dumps({"flash_bound": bound,
                      "flash_least_ms_per_step": least * 1e3,
                      "flash_ms_per_step": seconds * 1e3}), flush=True)
    return 100.0 * least / seconds
