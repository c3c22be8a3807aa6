"""The differential attention's and the cross-decoder's events of a
model with one shared cache (``hetu_tpu/models/shared_cache_decoder.py``),
program by program: read by the ``kernel.diff_attn_*`` and
``model.cross_decoder_decode_ms`` metrics.

``trace/latent_moe_events.py`` finds a kernel's events inside each
program of a kind (``per_program(..., literal=True)`` with the pattern
of ``layer_metrics/diff_attn_names.json``): the banded two-map flash
call's in a prefill program. A decode program's parts are composed and
lie between two pass-through kernels each
(``trace/window_events.py`` says how such brackets are read);
``trace/mhc_events.py`` pairs a program with the engine's record of it,
and a roofline is taken where at least ``MATCHED_SHARE`` (98%) of the
window's programs are paired.

Everything returns ``None`` where there is nothing to read: a program
from before this model (the parent), a cell whose model has no such
layer, no device plane, an engine whose records carry no such counter.
"""
import json

from benchmark.flops import diff_attn as flops
from benchmark.harness import device
from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import latent_moe_events as events
from benchmark.trace import mhc_events, program_spans, xplane

# what is read in a decode program, by the names' prefix
PARTS = ("decode", "cross_decoder")


def names():
    return read_json(BENCH_DIR + "/layer_metrics/diff_attn_names.json")


def _brackets(trace, part):
    """``[(start, end)]``: from each ``_in`` event's start to the end
    of the first ``_out`` event behind it."""
    n = names()
    ins = events._kernel_events(trace, n[part + "_in"])
    outs = events._kernel_events(trace, n[part + "_out"])
    found, j = [], 0
    for start, end in ins:
        while j < len(outs) and outs[j][0] < end:
            j += 1
        if j < len(outs):
            found.append((start, outs[j][1]))
    return found


def per_program(trace, kind, part="decode"):
    """``[(module start, module end, ns inside it)]`` of the window's
    programs of ``kind``, or ``None``: a prefill program's banded
    two-map flash events, a decode program's brackets of ``part``."""
    if kind == "prefill":
        return events.per_program(trace, kind, names()["prefill_kernel"],
                                  literal=True)
    if trace is None or not xplane.device_planes(trace):
        return None
    modules = program_spans.modules(
        trace, program_spans.names()["decode_module"])
    brackets = _brackets(trace, part)
    if not modules or not brackets:
        return None
    out = [(s, e, sum(min(b, e) - max(a, s) for a, b in brackets
                      if a < e and b > s)) for s, e in modules]
    return out if any(ns for _, _, ns in out) else None


def counted(trace, facts, kind):
    """``(what the matched programs counted, the events' seconds inside
    them)`` or ``None``."""
    programs = per_program(trace, kind)
    n = names()
    key = f"{kind}_" + (n["band_pairs_counter"] if kind == "prefill"
                        else n["shared_rows_counter"])
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
            "kind": kind, "kernel": "diff_attn",
            "programs": len(programs), "records": len(records),
            "matched": len(pairs)}}), flush=True)
        return None
    return sum(record[key] for _, record in pairs), seconds


def roofline(trace, facts, kind):
    """``prefill``: the banded two-map flash events' share of the
    chip's compute peak, COUNTED pairs inside the band x the operations
    of one (``flops/diff_attn.py``) over the events' time in the SAME
    programs. ``decode``: the shared rows' attentions' (and their one
    gather's) share of the memory bandwidth, COUNTED rows x a row's
    bytes over theirs. In percent."""
    found = counted(trace, facts, kind)
    if found is None:
        return None
    count, seconds = found
    c = facts["config"]
    head_dim = c["assumed"]["head_dim"]
    peaks = device.peaks(facts["device_kind"])
    if kind == "prefill":
        work = count * flops.score_pair_flops(c["num_attention_heads"],
                                              head_dim)
        peak = peaks["bf16_flops_per_s"]
    else:
        work = count * flops.shared_row_bytes(
            c["num_key_value_heads"], head_dim,
            2 if c["serve_dtype"] == "bfloat16" else 4)
        peak = peaks["hbm_bytes_per_s"]
    print(json.dumps({f"diff_attention_{kind}": {
        "counted": count, "events_s": seconds,
        "per_s": work / seconds}}), flush=True)
    return 100.0 * work / seconds / peak


def cross_rows_percent(facts):
    """``100 x`` the rows the cross-decoder processed over the real
    tokens through the self-decoder, over the window's prefill
    programs, from the engine's program records; ``None`` without
    them."""
    n = names()
    cross, own = ("prefill_" + n[k] for k in ("cross_rows_counter",
                                              "self_rows_counter"))
    rows = [r for r in facts.get("programs") or ()
            if r.get("kind") == "prefill" and cross in r and own in r]
    tokens = sum(r[own] for r in rows)
    if not tokens:
        return None
    return 100.0 * sum(r[cross] for r in rows) / tokens
