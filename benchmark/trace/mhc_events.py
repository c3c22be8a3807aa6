"""The hyper-connections' kernels' events, program by program (read by
the ``kernel.mhc_*`` metrics).

``trace/latent_moe_events.py`` does the matching (a program is one
event of the device's ``XLA Modules`` line; a kernel's events belong to
the program they ran inside; the engine's own record of that program,
``facts["programs"]``, carries the counters it returned), but its
``counted`` takes only keys of ``latent_moe_names.json``. Here the
pattern comes from ``mhc_names.json`` and goes in by
``per_program(..., literal=True)``; the pairing of a program with its
record is the same rule (the record whose host interval holds the
program's device interval, within the same slack; at least
``MATCHED_SHARE`` of the window's programs paired, else ``None``), with
a wider slack where the window holds as many records as programs
(:func:`counted`).

Everything returns ``None`` where the trace holds no such event: a
program from before the kernels (the parent), a cell whose model has
one residual stream, no device plane.
"""
import json

from benchmark.flops import mhc as flops
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import latent_moe_events as events
from benchmark.trace import xplane


# how far apart the two clocks may put a program and its record where
# the window's programs and records agree in number and the records lie
# more than twice this apart (``counted``)
ORDER_SLACK_NS = 5_000_000


def names():
    return read_json(BENCH_DIR + "/layer_metrics/mhc_names.json")


def per_program(trace, kind):
    """``[(module start, module end, mhc kernels' ns inside it)]`` of
    the window's programs of ``kind``, or ``None``."""
    return events.per_program(trace, kind, names()["mhc_kernels"],
                              literal=True)


def _pairs(programs, records, offset, slack):
    """``[(program, record)]``: the record whose host interval
    (dispatch to the end of the host sync, moved onto the profile's
    clock) holds the program's device interval within ``slack``."""
    out, i = [], 0
    for program in programs:        # both in time order
        s, e, _ = program
        while i < len(records) \
                and records[i]["t1_ns"] + offset + slack < e:
            i += 1
        if i == len(records) \
                or records[i]["t0_ns"] + offset - slack > s:
            continue
        out.append((program, records[i]))
        i += 1
    return out


def counted(trace, facts, kind):
    """``(rows the matched programs counted, the kernels' seconds
    inside them)`` or ``None``, paired by ``latent_moe_events``' rule.
    The profile's host and device planes lie up to 1.4 ms apart from
    one session to the next (PERF.md section 7), and that module's
    millisecond of slack then drops a whole run's prefill rooflines for
    one program. So where the strict rule leaves a program unpaired,
    the window holds as many records as programs, and the records lie
    further apart than twice ``ORDER_SLACK_NS`` (prefill programs: no
    program can then be within the slack of two records), the slack is
    ``ORDER_SLACK_NS``. Decode programs run back to back with their
    records overlapping: for them the strict rule stands."""
    programs = per_program(trace, kind)
    key = f"{kind}_{names()['rows_counter']}"
    records = sorted((r for r in facts.get("programs") or ()
                      if r.get("kind") == kind and key in r),
                     key=lambda r: r["t0_ns"])
    if not programs or not records or "window_perf_ns" not in facts:
        return None
    offset = xplane.window(trace)[0] - facts["window_perf_ns"]
    slack = facts.get("clock_slack_ns", events.SLACK_NS)
    pairs = _pairs(programs, records, offset, slack)
    wide = max(slack, ORDER_SLACK_NS)
    apart = all(b["t0_ns"] - a["t1_ns"] > 2 * wide
                for a, b in zip(records, records[1:]))
    if len(pairs) < len(programs) == len(records) and apart:
        pairs = _pairs(programs, records, offset, wide)
    seconds = sum(ns for (_, _, ns), _ in pairs) / 1e9
    if len(pairs) < events.MATCHED_SHARE * len(programs) or not seconds:
        print(json.dumps({"unmatched": {
            "kind": kind, "kernel": "mhc_kernels",
            "programs": len(programs), "records": len(records),
            "matched": len(pairs)}}), flush=True)
        return None
    return sum(record[key] for _, record in pairs), seconds


def roofline(trace, facts, kind):
    """The kernels' share of the chip's memory bandwidth in the
    window's programs of ``kind``, in percent: COUNTED rows x the bytes
    a (token, sublayer) must move (``flops/mhc.py``) over the events'
    time in the SAME programs, over ``hbm_bytes_per_s``."""
    found = counted(trace, facts, kind)
    if found is None:
        return None
    rows, seconds = found
    c = facts["config"]
    nbytes = rows * flops.bytes_per_row(
        c["hc_mult"], c["hidden_size"],
        2 if c["serve_dtype"] == "bfloat16" else 4)
    print(json.dumps({f"mhc_{kind}": {
        "rows": rows, "kernel_s": seconds,
        "gbytes_per_s": nbytes / seconds / 1e9,
        "two_kernel_ceiling_pct":
            100.0 * flops.two_kernel_ceiling(c["hc_mult"])}}), flush=True)
    return 100.0 * nbytes / seconds \
        / device.peaks(facts["device_kind"])["hbm_bytes_per_s"]
