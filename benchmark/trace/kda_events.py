"""The delta-rule kernels' events, program by program (read by the
``kernel.kda_*`` metrics).

``trace/latent_moe_events.py`` finds a kernel's events inside each
program of a kind (``per_program(..., literal=True)`` with the pattern
of ``layer_metrics/kda_names.json``). A program is paired with the
engine's record of it (``facts["programs"]``: the counters it returned)
BY ORDER where the window holds as many programs of the kind as records
with the counter: both are in time order, the engine runs one program
at a time, and no clock has to agree (the profile's host and device
planes lie up to 1.4 ms apart from one session to the next, PERF.md
section 7, and a millisecond of slack once dropped a whole window of
three prefills). Where the counts differ (a program cut by the window's
edge on one clock and not the other) the pairing falls back on the
clocks: ``trace/mhc_events.py:_pairs`` with the usual slack, and a
roofline is taken only where at least ``MATCHED_SHARE`` of the window's
programs are paired.

Everything returns ``None`` where there is nothing to read: a program
from before the kernels (the parent), a cell whose model has no
delta-rule layer, no device plane, records without the counter.
"""
import json

from benchmark.flops import kda as flops
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import latent_moe_events as events
from benchmark.trace import mhc_events, xplane

# which kernel runs in which kind of program
KERNEL = {"prefill": "kda_chunk_kernel", "decode": "kda_step_kernel"}


def names():
    return read_json(BENCH_DIR + "/layer_metrics/kda_names.json")


def per_program(trace, kind):
    """``[(module start, module end, the kind's kernel's ns inside
    it)]`` of the window's programs of ``kind``, or ``None``."""
    return events.per_program(trace, kind, names()[KERNEL[kind]],
                              literal=True)


def counted(trace, facts, kind):
    """``(rows the matched programs counted, the kernel's seconds
    inside them)`` or ``None``."""
    programs = per_program(trace, kind)
    key = f"{kind}_{names()['rows_counter']}"
    records = sorted((r for r in facts.get("programs") or ()
                      if r.get("kind") == kind and key in r),
                     key=lambda r: r["t0_ns"])
    if not programs or not records:
        return None
    if len(programs) == len(records):
        pairs = list(zip(programs, records))
    elif "window_perf_ns" in facts:
        offset = xplane.window(trace)[0] - facts["window_perf_ns"]
        slack = facts.get("clock_slack_ns", events.SLACK_NS)
        pairs = mhc_events._pairs(programs, records, offset, slack)
    else:
        return None
    seconds = sum(ns for (_, _, ns), _ in pairs) / 1e9
    if len(pairs) < events.MATCHED_SHARE * len(programs) or not seconds:
        print(json.dumps({"unmatched": {
            "kind": kind, "kernel": KERNEL[kind],
            "programs": len(programs), "records": len(records),
            "matched": len(pairs)}}), flush=True)
        return None
    return sum(record[key] for _, record in pairs), seconds


def roofline(trace, facts, kind):
    """The kind's kernel's share of the bound that binds it in the
    window's programs of ``kind``, in percent: COUNTED (real token,
    delta-rule layer) pairs x the least time one takes at the chip's
    peaks (``flops/kda.py``) over the events' time in the SAME
    programs."""
    found = counted(trace, facts, kind)
    if found is None:
        return None
    rows, seconds = found
    c = facts["config"]
    heads, d = c["num_attention_heads"], c["head_dim"]
    peaks = device.peaks(facts["device_kind"])
    if kind == "decode":
        least, bound = flops.step_bytes_per_row(heads, d) \
            / peaks["hbm_bytes_per_s"], "memory"
    else:
        # there are chunk events, so the program has the kernel
        from hetu_tpu.ops.kda import CHUNK
        least, bound = flops.chunk_least_seconds_per_row(
            heads, d, CHUNK,
            2 if c["serve_dtype"] == "bfloat16" else 4, peaks)
    print(json.dumps({f"kda_{kind}": {
        "rows": rows, "kernel_s": seconds, "bound": bound,
        "ns_per_row": 1e9 * seconds / rows,
        "least_ns_per_row": 1e9 * least}}), flush=True)
    return 100.0 * rows * least / seconds
