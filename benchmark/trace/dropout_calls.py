"""Which trace events are the dropout-mask kernel
(``hetu_tpu/ops/pallas_dropout.py``), read by
``kernel.dropout_mask_ms_per_step``. The pattern is data
(``layer_metrics/dropout_kernel_names.json``)."""
import re

from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane


def calls(trace):
    """``{short name: [duration ns, ...]}`` of the kernel's events that
    ran wholly inside the traced window, over every device plane; or
    ``None`` where the trace holds no such event (a program from before
    the kernel, a step under a mesh, a cell that drops nothing, no
    device plane)."""
    if trace is None or not xplane.device_planes(trace):
        return None
    pattern = re.compile(read_json(
        BENCH_DIR + "/layer_metrics/dropout_kernel_names.json")[
            "dropout_mask_kernel"])
    lo, hi = xplane.window(trace)
    found = {}
    for plane in xplane.device_planes(trace):
        for name, start, end in xplane.line_events(plane, xplane.OPS_LINE):
            if start >= lo and end <= hi and pattern.search(name):
                found.setdefault(name, []).append(end - start)
    return found or None
