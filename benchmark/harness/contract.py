"""The one line a run ends with."""
import json
import math

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def result_line(correct, attempted, failed, metrics, device,
                breakdown=None):
    """``metrics``: ``{name: (value, unit)}``. Values go out as
    measured, with all their digits; one that is not a finite number is
    an error here rather than a line the driver cannot read."""
    out_metrics = {}
    for name, (value, unit) in metrics.items():
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {name} is {value}")
        out_metrics[name] = {"value": value, "unit": unit}
    for key in DEVICE_KEYS:
        if key not in device:
            raise KeyError(f"device stamp lacks {key!r}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": out_metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = {
            k: [[str(n), float(s)] for n, s in breakdown[k][:10]]
            for k in ("device_ops", "idle_gaps")}
    return json.dumps(line)
