"""The grouped-query flash calls and the held experts' grouped products
of a TRAINING step, as a profile shows them and as the step counted
them (read by ``kernel.flash_gqa_train_*``, ``kernel.moe_experts_train_*``
and ``moe.train_load_imbalance``).

The train driver hands readers the ``facts`` it builds; the one entry a
family fills is ``flash_calls`` (asked of the family after the window).
``families/smallthinker_moe.py`` carries there its attention calls
(``kv_heads``, ``window``, ``calls``) and the entry of kind
``moe_counters``: what the compiled step added up on the device over
every training step of the session (``Executor.moe_counters()``).

Everything returns ``None`` where there is nothing to read: no profile,
no device plane, a program without these kernels (the parent), a family
that carries no such calls or counters.
"""
import re

from benchmark.flops import flash, gqa_train, moe_train
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane


def names():
    return read_json(BENCH_DIR + "/layer_metrics/gqa_train_names.json")


def seconds_per_step(trace, facts, pattern):
    """Device seconds a training step spends in the events whose short
    name ``names()[pattern]`` finds, or None."""
    if trace is None or not facts.get("steps") \
            or not xplane.device_planes(trace):
        return None
    found = re.compile(names()[pattern])
    total = sum(s for name, s in xplane.op_seconds(trace).items()
                if found.search(name))
    return total / facts["steps"] if total else None


def attention_calls(facts):
    """The family's grouped-query calls of one step."""
    return [c for c in facts.get("flash_calls") or ()
            if c.get("kind") in ("forward", "backward")
            and "kv_heads" in c]


def counters(facts):
    """The step's device counters (the ``moe_counters`` entry), or
    None."""
    kind = names()["counters_kind"]
    for entry in facts.get("flash_calls") or ():
        if entry.get("kind") == kind and entry.get("steps"):
            return entry
    return None


def attention_least_seconds_per_step(facts):
    """``(seconds, bound)``: the least one chip could take for a step's
    grouped-query calls — per call the larger of its operations over
    the compute peak and its bytes over the bandwidth
    (``flops/gqa_train.py``: pairs inside the band or under the
    diagonal only, K / V bytes once a group)."""
    calls = attention_calls(facts)
    if not calls:
        return None
    peaks = device.peaks(facts["device_kind"])
    parts = []
    for c in calls:
        work = getattr(gqa_train, c["kind"])(
            c["b"], c["h"], c["kv_heads"], c["s"], c["d"], c["itemsize"],
            c["window"])
        seconds, bound = flash.least_seconds(*work, peaks)
        parts.append((seconds * c["calls"], bound))
    return sum(t for t, _ in parts), max(parts)[1]


def experts_least_seconds_per_step(facts):
    """``(seconds, bound)`` for a step's grouped products, from the
    COUNTED rows and visits a step (``flops/moe_train.py``)."""
    counted = counters(facts)
    if counted is None:
        return None
    c = facts["config"]
    hidden, width = c["hidden_size"], c["moe_ffn_hidden_size"]
    steps = counted["steps"]
    work = (moe_train.flops(counted["moe_routed_rows"] / steps, hidden,
                            width),
            moe_train.weight_bytes(counted["moe_expert_visits"] / steps,
                                   hidden, width, 2))
    return flash.least_seconds(*work, device.peaks(facts["device_kind"]))


def roofline(trace, facts, pattern, least):
    """``(percent, least seconds a step, which bound)``: ``least(facts)``
    over the matching events' time a step; None where either is
    missing."""
    seconds = seconds_per_step(trace, facts, pattern)
    if seconds is None:     # (and no peak is asked of an unknown device)
        return None
    found = least(facts)
    if found is None:
        return None
    return 100.0 * found[0] / seconds, found[0], found[1]
