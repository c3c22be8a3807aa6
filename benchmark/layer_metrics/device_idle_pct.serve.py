"""Share of the traced window, in percent, in which no operation ran
on the device (mean over the chips used): 1 - union of the device-op
intervals / window.

layer: device — source: device_trace — moves:
serve_request_p95_ms.
"""
from benchmark.trace import xplane


def reduce(trace, facts):
    return xplane.idle_percent(trace)
