"""The latent-attention / expert kernels' events, program by program
(read by the ``kernel.moe_experts_*`` and ``kernel.mla_*`` metrics).

Two things are matched here, both on the profile's clock:

* a *program* is one event of the device's ``XLA Modules`` line whose
  name is the engine's decode or prefill program
  (``layer_metrics/program_names.json``) and that ran wholly inside the
  traced window; a kernel's events belong to the program they ran
  inside;
* the engine's own record of that program (``facts["programs"]``:
  ``engine.program_log``, host times from dispatch
  to the end of the host sync, with the counters the program returned).
  ``facts["window_perf_ns"]`` is the host clock read where the
  ``bench.window`` annotation starts, so the records move onto the
  profile's clock by one offset; a program's device event lies inside
  its record's host interval.

A roofline divides COUNTED work by measured time, so it is taken over
the programs that have BOTH, and only where those are at least 98% of
the window's programs of the kind (``MATCHED_SHARE``): else the count
cannot be held against the time, and the reader returns ``None``.
Everything returns ``None`` where the trace holds no such event (a
program from before the kernels, a cell that runs none, no device
plane).
"""
import json
import re

from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import program_spans, xplane

MATCHED_SHARE = 0.98
# how far a program's device interval may reach out of its record's host
# interval once both are on one clock: the annotation's start and the
# driver's clock read are microseconds apart, the profile's host and
# device timelines a little more. A decode program lasts some 20 ms and
# two of them never overlap, so a millisecond cannot pair a program
# with its neighbour's record (``facts["clock_slack_ns"]`` overrides).
SLACK_NS = 1_000_000


def names():
    return read_json(BENCH_DIR + "/layer_metrics/latent_moe_names.json")


def _kernel_events(trace, pattern):
    pat = re.compile(pattern)
    return sorted((s, e) for n, s, e in xplane.line_events(
        xplane.device_planes(trace)[0], xplane.OPS_LINE) if pat.search(n))


def per_program(trace, kind, kernel, literal=False):
    """``[(module start, module end, kernel ns inside it)]`` for the
    window's programs of ``kind`` (``"decode"`` / ``"prefill"``), or
    ``None`` where the trace shows no such program or no event of the
    kernel in any of them. ``kernel`` is a key of
    ``latent_moe_names.json``, or with ``literal`` the pattern itself."""
    if trace is None or not xplane.device_planes(trace):
        return None
    modules = program_spans.modules(
        trace, program_spans.names()[kind + "_module"])
    events = _kernel_events(trace, kernel if literal else names()[kernel])
    if not modules or not events:
        return None
    out, i = [], 0
    for s, e in modules:
        while i < len(events) and events[i][1] <= s:
            i += 1
        j, ns = i, 0
        while j < len(events) and events[j][0] < e:
            ns += min(events[j][1], e) - max(events[j][0], s)
            j += 1
        out.append((s, e, ns))
    return out if any(ns for _, _, ns in out) else None


def counted(trace, facts, kind, kernel):
    """``(summed counters of the matched programs, kernel seconds
    inside them)`` or ``None`` (no events, no records, or fewer than
    ``MATCHED_SHARE`` of the programs matched)."""
    programs = per_program(trace, kind, kernel)
    records = sorted((r for r in facts.get("programs") or ()
                      if r.get("kind") == kind), key=lambda r: r["t0_ns"])
    if not programs or not records or "window_perf_ns" not in facts:
        return None
    offset = xplane.window(trace)[0] - facts["window_perf_ns"]
    slack = facts.get("clock_slack_ns", SLACK_NS)
    totals, seconds, matched, i = {}, 0.0, 0, 0
    for s, e, ns in programs:       # both in time order
        # the record whose host interval (dispatch to the end of the
        # host sync) holds this program's device interval
        while i < len(records) \
                and records[i]["t1_ns"] + offset + slack < e:
            i += 1
        if i == len(records) \
                or records[i]["t0_ns"] + offset - slack > s:
            continue
        record = records[i]
        i += 1
        matched += 1
        seconds += ns / 1e9
        for key, value in record.items():
            if isinstance(value, (int, float)) and not key.endswith("_ns"):
                totals[key] = totals.get(key, 0) + value
    if matched < MATCHED_SHARE * len(programs) or not seconds:
        print(json.dumps({"unmatched": {
            "kind": kind, "kernel": kernel, "programs": len(programs),
            "records": len(records), "matched": matched}}), flush=True)
        return None
    return totals, seconds


def model_widths(facts):
    """The widths the operation counts need, from the cell's
    configuration file."""
    c = facts["config"]
    return {"hidden": c["hidden_size"], "width": c["moe_intermediate_size"],
            "heads": c["num_attention_heads"], "latent": c["kv_lora_rank"],
            "rope": c["qk_rope_head_dim"], "nope": c["qk_nope_head_dim"],
            "v": c["v_head_dim"],
            "itemsize": 2 if c["serve_dtype"] == "bfloat16" else 4}
