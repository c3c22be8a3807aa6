"""The window model's attention events, program by program (read by the
``kernel.swa_*`` and ``kernel.gqa_*`` metrics), and the window layers'
blocks the engine's program records show (``kvcache.window_used_pct``).

``trace/latent_moe_events.py`` finds a kernel's events inside each
program of a kind (``per_program(..., literal=True)`` with the pattern
of ``layer_metrics/window_names.json``): the windowed flash call's in a
prefill program. A decode program's two attentions are composed, and
XLA names their fusions for their roots; a profiled program brackets
each with two pass-through kernels (``ops/attention.py:bracketed``),
and what lies between an ``_in`` event's start and the next ``_out``
event's end is read as the attention. ``trace/mhc_events.py`` pairs
a program with the engine's record of it (``_pairs``; its wider slack
for prefill programs that lie far apart), as ``trace/ssm_events.py``
does; a roofline is taken where at least ``MATCHED_SHARE`` (98%) of the
window's programs are paired.

Everything returns ``None`` where there is nothing to read: a program
from before this model (the parent), a cell whose model has no window
layer, no device plane, an engine whose records carry no window blocks.
"""
import json

from benchmark.flops import gqa_window as flops
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import latent_moe_events as events
from benchmark.trace import mhc_events, program_spans, xplane

# which events are read in which kind of program
KERNEL = {"prefill": "flash_window_kernel", "decode": "gqa_decode_in"}


def names():
    return read_json(BENCH_DIR + "/layer_metrics/window_names.json")


def _counters(kind):
    n = names()
    short = [n["swa_pairs_counter"]] if kind == "prefill" \
        else n["decode_rows_counters"]
    return [f"{kind}_{name}" for name in short]


def _brackets(trace):
    """``[(start, end)]``: from each ``_in`` event's start to the end
    of the first ``_out`` event behind it."""
    n = names()
    ins = events._kernel_events(trace, n["gqa_decode_in"])
    outs = events._kernel_events(trace, n["gqa_decode_out"])
    found, j = [], 0
    for start, end in ins:
        while j < len(outs) and outs[j][0] < end:
            j += 1
        if j < len(outs):
            found.append((start, outs[j][1]))
    return found


def per_program(trace, kind):
    """``[(module start, module end, the kind's attention's ns inside
    it)]`` of the window's programs of ``kind``, or ``None``: a prefill
    program's windowed flash events, a decode program's brackets."""
    if kind == "prefill":
        return events.per_program(trace, kind, names()[KERNEL[kind]],
                                  literal=True)
    if trace is None or not xplane.device_planes(trace):
        return None
    modules = program_spans.modules(
        trace, program_spans.names()["decode_module"])
    brackets = _brackets(trace)
    if not modules or not brackets:
        return None
    out = [(s, e, sum(min(b, e) - max(a, s) for a, b in brackets
                      if a < e and b > s)) for s, e in modules]
    return out if any(ns for _, _, ns in out) else None


def counted(trace, facts, kind):
    """``(what the matched programs counted, the events' seconds inside
    them)`` or ``None``."""
    programs = per_program(trace, kind)
    keys = _counters(kind)
    records = sorted((r for r in facts.get("programs") or ()
                      if r.get("kind") == kind
                      and all(k in r for k in keys)),
                     key=lambda r: r["t0_ns"])
    if not programs or not records or "window_perf_ns" not in facts:
        return None
    offset = xplane.window(trace)[0] - facts["window_perf_ns"]
    slack = facts.get("clock_slack_ns", events.SLACK_NS)
    pairs = mhc_events._pairs(programs, records, offset, slack)
    wide = max(slack, mhc_events.ORDER_SLACK_NS)
    apart = all(b["t0_ns"] - a["t1_ns"] > 2 * wide
                for a, b in zip(records, records[1:]))
    if len(pairs) < len(programs) == len(records) and apart:
        pairs = mhc_events._pairs(programs, records, offset, wide)
    seconds = sum(ns for (_, _, ns), _ in pairs) / 1e9
    if len(pairs) < events.MATCHED_SHARE * len(programs) or not seconds:
        print(json.dumps({"unmatched": {
            "kind": kind, "kernel": KERNEL[kind],
            "programs": len(programs), "records": len(records),
            "matched": len(pairs)}}), flush=True)
        return None
    return sum(record[k] for _, record in pairs for k in keys), seconds


def roofline(trace, facts, kind):
    """``prefill``: the windowed flash events' share of the chip's
    compute peak, COUNTED pairs inside the band x the operations of one
    (``flops/gqa_window.py``) over the events' time in the SAME
    programs. ``decode``: both decode attentions' share of the memory
    bandwidth, COUNTED cached rows x a row's bytes over theirs. In
    percent."""
    found = counted(trace, facts, kind)
    if found is None:
        return None
    count, seconds = found
    c = facts["config"]
    peaks = device.peaks(facts["device_kind"])
    if kind == "prefill":
        work = count * flops.score_pair_flops(c["num_attention_heads"],
                                              c["head_dim"])
        peak = peaks["bf16_flops_per_s"]
    else:
        work = count * flops.cached_row_bytes(
            c["num_key_value_heads"], c["head_dim"],
            2 if c["serve_dtype"] == "bfloat16" else 4)
        peak = peaks["hbm_bytes_per_s"]
    print(json.dumps({f"window_attention_{kind}": {
        "counted": count, "events_s": seconds,
        "per_s": work / seconds}}), flush=True)
    return 100.0 * work / seconds / peak


def window_blocks_used_peak(facts):
    """``(most window blocks held when a program of the window ended,
    the blocks there are)`` from the engine's program records, or
    ``None``."""
    n = names()
    held = [(r[n["window_blocks_used"]], r[n["window_blocks"]])
            for r in facts.get("programs") or ()
            if n["window_blocks_used"] in r]
    if not held or not held[0][1]:
        return None
    return max(used for used, _ in held), held[0][1]
