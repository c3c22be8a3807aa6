"""Which trace events are the Pallas flash-attention kernels, and the
least time one training step's calls of them need (read by the
kernel.flash_* metrics). What a step calls is the family's knowledge
(``families/<family>.py:flash_calls_per_step``, handed on by the
driver as ``facts["flash_calls"]``); which of those calls ran as
kernels is read from the trace."""
import re

from benchmark.flops import flash
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane


def _patterns():
    names = read_json(BENCH_DIR + "/trace/names.json")
    return (re.compile(names["flash_kernels"]),
            re.compile(names["flash_backward_kernels"]))


def _kernel_seconds(trace):
    """(device seconds in all flash kernels, of them in the backward
    ones), mean over the device planes, inside the traced window."""
    every, backward = _patterns()
    ops = xplane.op_seconds(trace)
    return (sum(s for name, s in ops.items() if every.search(name)),
            sum(s for name, s in ops.items() if backward.search(name)))


def seconds_per_step(trace, facts):
    """Device seconds of the flash kernels per training step, or None
    where the trace shows none."""
    if trace is None or not facts.get("steps"):
        return None
    total, _ = _kernel_seconds(trace)
    return total / facts["steps"] if total else None


def least_seconds_per_step(trace, facts):
    """The least time one chip could take for the flash calls of one
    step: (seconds, bound of the largest part). A backward call counts
    only where the trace shows the backward kernels; the forward keeps
    its logsumexp exactly then."""
    peaks = device.peaks(facts["device_kind"])
    fused_backward = _kernel_seconds(trace)[1] > 0
    parts = []
    for call in facts["flash_calls"]:
        shape = (call["b"], call["h"], call["s"], call["d"],
                 call["itemsize"], call["causal"])
        if call["kind"] == "forward":
            work = flash.forward(*shape, with_lse=fused_backward)
        elif fused_backward:
            work = flash.backward(*shape)
        else:
            continue
        seconds, bound = flash.least_seconds(*work, peaks)
        parts.append((seconds * call["calls"], bound))
    return sum(t for t, _ in parts), max(parts)[1]
