"""The state-space kernels' events, program by program (read by the
``kernel.ssm_*`` metrics), and the state slots the engine's program
records show (``statecache.used_pct``).

``trace/latent_moe_events.py`` finds a kernel's events inside each
program of a kind (``per_program(..., literal=True)`` with the pattern
of ``layer_metrics/ssm_names.json``), ``trace/mhc_events.py`` pairs a
program with the engine's record of it (``_pairs``: the record whose
host interval holds the program's device interval; its wider slack for
prefill programs that lie far apart); a roofline is taken where at
least ``MATCHED_SHARE`` of the window's programs are paired.

Everything returns ``None`` where there is nothing to read: a program
from before the kernels (the parent), a cell whose model has no
state-space layer, no device plane, an engine whose records carry no
slots.
"""
import json

from benchmark.flops import ssm as flops
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import latent_moe_events as events
from benchmark.trace import mhc_events, xplane

# which kernel runs in which kind of program
KERNEL = {"prefill": "ssm_scan_kernel", "decode": "ssm_step_kernel"}


def names():
    return read_json(BENCH_DIR + "/layer_metrics/ssm_names.json")


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
    return sum(record[key] for _, record in pairs), seconds


def roofline(trace, facts, kind):
    """The kind's kernel's share of the chip's memory bandwidth in the
    window's programs of ``kind``, in percent: COUNTED (real token,
    Mamba layer) pairs x the bytes one must move (``flops/ssm.py``)
    over the events' time in the SAME programs, over
    ``hbm_bytes_per_s``."""
    found = counted(trace, facts, kind)
    if found is None:
        return None
    rows, seconds = found
    c = facts["config"]
    d, n = c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"]
    per_row = flops.step_bytes_per_row(d, n) if kind == "decode" \
        else flops.scan_bytes_per_row(
            d, n, c["mamba_dt_rank"],
            2 if c["serve_dtype"] == "bfloat16" else 4)
    print(json.dumps({f"ssm_{kind}": {
        "rows": rows, "kernel_s": seconds,
        "gbytes_per_s": rows * per_row / seconds / 1e9,
        "ns_per_row": 1e9 * seconds / rows}}), flush=True)
    return 100.0 * rows * per_row / seconds \
        / device.peaks(facts["device_kind"])["hbm_bytes_per_s"]


def slots_used_peak(facts):
    """``(most state slots held when a program of the window ended, the
    slots there are)`` from the engine's program records, or ``None``."""
    n = names()
    held = [(r[n["slots_used"]], r[n["slots"]])
            for r in facts.get("programs") or () if n["slots_used"] in r]
    if not held or not held[0][1]:
        return None
    return max(used for used, _ in held), held[0][1]
