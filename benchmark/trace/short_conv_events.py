"""The gated short convolutions of a TRAINING step, as a profile shows
them and as the family counts them (read by
``kernel.short_conv_train_ms_per_step``,
``kernel.short_conv_train_roofline`` and ``moe.bias_flipped_pct``).

The op's two directions are composed ``jax.numpy`` that XLA inlines into
the step, so no event carries a name of theirs; their time is what
``trace/step_account.py`` books to the op types of
``layer_metrics/short_conv_names.json`` (an event goes to the graph op
most of its instructions were traced under). The shapes come from the
family (``facts["flash_calls"]``, the entry of kind ``short_conv``).

Everything returns ``None`` where there is nothing to read: no profile,
no device plane, no account of the step, a program without the op (the
parent), a family that hands on no such entry.
"""
from benchmark.flops import flash, short_conv
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import step_account, xplane


def names():
    return read_json(BENCH_DIR + "/layer_metrics/short_conv_names.json")


def _entry(facts, kind):
    for entry in facts.get("flash_calls") or ():
        if isinstance(entry, dict) and entry.get("kind") == kind:
            return entry
    return None


def calls(facts):
    """The family's entry for one step's convolution layers, or None."""
    return _entry(facts, names()["calls_kind"])


def counters(facts):
    """The step's device counters where they count the picks the bias
    changed, or None."""
    entry = _entry(facts, names()["counters_kind"])
    if entry is None or not entry.get("moe_picks") \
            or "moe_bias_flipped_picks" not in entry:
        return None
    return entry


def seconds_per_step(trace, facts):
    """Device seconds a training step spends in the events booked to the
    convolution's two op types, or None."""
    if trace is None or not xplane.device_planes(trace):
        return None
    path = step_account.find_profile()
    if path is None:
        return None
    account = step_account.account(path, xplane.window(trace))
    if account is None:
        return None
    wanted = set(names()["op_types"])
    ms = sum(ms for op_type, _, ms in account.grouped(lambda a: a.op_type)
             if op_type in wanted)
    return ms / 1e3 if ms else None


def least_seconds_per_step(facts):
    """``(seconds, bound)``: the least one chip could take for a step's
    convolutions — their bytes over the bandwidth or their operations
    over the peak, whichever is longer (``flops/short_conv.py``)."""
    entry = calls(facts)
    if entry is None or not entry.get("calls"):
        return None
    peaks = device.peaks(facts["device_kind"])
    shape = (entry["rows"], entry["channels"], entry["taps"],
             entry["itemsize"])
    parts = [flash.least_seconds(*getattr(short_conv, kind)(*shape), peaks)
             for kind in ("forward", "backward")]
    return entry["calls"] * sum(t for t, _ in parts), max(parts)[1]
