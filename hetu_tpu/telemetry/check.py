"""Chrome trace-event schema validator:

    python -m hetu_tpu.telemetry.check trace.json [more.json ...]

Used by the tests and as the CI gate on every exported/merged trace:
exit 0 with an event count when every file validates, exit 1 with the
first errors otherwise. ``validate()`` is the library form.

Beyond the structural Chrome-trace checks (required keys, known phase,
monotonic ts), known **span kinds carry a typed attr schema**: every
instrumentation site in the codebase registers its span name and attr
types in ``SPAN_SCHEMA`` below, and an exported trace whose known span
carries an attr of the wrong type — or an attr the schema has never
heard of — fails validation. That is the drift gate: PR 7's
``overlapped=`` attr shipped with no schema at all, so a consumer (the doctor's
hidden/exposed split) could silently
misread them. New span kinds/attrs must be added HERE and covered by a
fixture trace in ``tests/test_doctor.py``.
"""
from __future__ import annotations

import json
import sys

__all__ = ["validate", "main", "SPAN_SCHEMA", "check_args"]

_REQUIRED = ("name", "ph", "ts", "pid", "tid")
_KNOWN_PH = {"X", "B", "E", "i", "I", "M", "C", "b", "e", "n", "s", "t",
             "f"}

# attr-type vocabulary
_INT = (int,)
_NUM = (int, float)
_STR = (str,)
_BOOL = (bool,)


def _opt(kinds):
    """Optional attr: absent is fine, wrong type is not."""
    return ("opt", kinds)


def _req(kinds):
    """Required attr: a producer that drops it regressed."""
    return ("req", kinds)


def _any():
    return ("opt", None)            # any JSON type (tags, labels)


# one entry per span/instant kind the codebase emits; key attrs typed,
# memory_* / per-candidate payloads validated loosely where the value
# set is open-ended. ``...`` (Ellipsis) allows arbitrary extra attrs
# for spans whose payload is a measurement dict (memory analysis).
SPAN_SCHEMA = {
    # executor (executor.py)
    "step": {"subgraph": _opt(_STR), "pipelined": _opt(_BOOL)},
    "step_block": {"steps": _req(_INT), "subgraph": _opt(_STR)},
    "jit_compile": {"subgraph": _opt(_STR), "shape_key": _opt(_STR),
                    "allreduce_defer": _opt(_INT), ...: True},
    "device_dispatch": {"subgraph": _opt(_STR)},
    "block_dispatch": {"steps": _opt(_INT), "subgraph": _opt(_STR)},
    "h2d_transfer": {"bytes": _req(_INT), "overlapped": _req(_BOOL)},
    "h2d_stacked": {"bytes": _req(_INT), "overlapped": _req(_BOOL)},
    "memory_analysis": {"label": _opt(_STR), ...: True},
    # one a compiled step of an Executor(dtype=...): how many of the
    # subgraph's parameters the step reads as working copies (the
    # masters in the compute dtype, written by the step before), their
    # bytes, and the names (up to ten) of the floating parameters it
    # converts itself: those the PS runtime writes between steps
    "working_copies": {"subgraph": _req(_STR), "params": _req(_INT),
                       "bytes": _req(_INT), "in_step_casts": _any()},
    # one a table whose gradient reaches the optimizer sparse, a
    # compiled step (optimizer.py:_update_rows): the table's name and
    # shape, the ids the step looks up, the optimizer's slots, and the
    # path its rows take: kernel (hetu_sparse_rows_update) or composed,
    # which names the first condition of optimizer.py:
    # sparse_update_path that failed (caller / platform / mesh / lanes /
    # rows / dtype)
    "sparse_update": {"table": _req(_STR), "rows": _req(_INT),
                      "width": _req(_INT), "ids": _req(_INT),
                      "slots": _req(_INT), "path": _req(_STR),
                      "reason": _opt(_STR)},
    "step_logged": {"step": _opt(_INT), "wall_ms": _opt(_NUM)},
    # SubExecutor.run around device_dispatch: the feed loop and
    # dataloader batches of one step; state swap, health monitor and
    # output wrapping after it
    "executor.ingest": {}, "executor.outputs": {},
    # async ingest (ingest.py)
    "ingest_wait": {"tag": _any()},
    # PS runtime / client (ps/) — PSRuntime._phase emits every phase
    # as an argless ps:<name> span; registering them means a future
    # attr addition must land here (and in the doctor's classifier)
    "ps:pull": {"bytes": _req(_INT), "overlapped": _req(_BOOL)},
    "ps:drain_push": {"rows": _opt(_INT)},
    "ps:slot_assign": {}, "ps:miss_fill": {}, "ps:refresh": {},
    "ps:dispatch": {}, "ps:drain_submit": {}, "ps:dense": {},
    "ps:host_pull": {}, "ps:sync_push": {}, "ps:feed_ingest": {},
    "ps:prefetch": {}, "ps:repull": {},
    # pipeline (parallel/pipeline.py)
    "pp_stage_idle": {"stage": _req(_INT), "tag": _any(),
                      "bytes": _opt(_INT)},
    "pp_fill": {"warmup": _opt(_INT)},
    "pp_steady": {"ticks": _opt(_INT)},
    "pp_drain": {"ticks": _opt(_INT)},
    "pp_fwd_block": {"stage": _req(_INT)},
    "pp_bwd_block": {"stage": _req(_INT)},
    # p2p channel (parallel/p2p.py)
    "p2p_send": {"tag": _any(), "dst": _req(_INT), "bytes": _req(_INT)},
    "p2p_recv": {"tag": _any(), "bytes": _req(_INT)},
    # collective pipeline (parallel/collective_pp.py)
    "cpp_build": {},
    "cpp_pack_feeds": {"bytes": _opt(_INT)},
    "cpp_replicate_feeds": {},
    "cpp_dispatch": {"ticks": _req(_INT), "fill": _opt(_INT),
                     "drain": _opt(_INT), "fuse_ticks": _opt(_INT),
                     "stages": _opt(_INT), "microbatches": _opt(_INT),
                     "virtual_stages": _opt(_INT), "bytes": _opt(_INT)},
    # fleet monitor (telemetry/fleet.py): one fleet_watch span per
    # monitor poll (straggler attribution over the aligned step window),
    # one "drift" instant per CostDB drift verdict that tripped — both
    # strictly typed, no open payload (the post-hoc CLI and CI assert on
    # these fields).
    "fleet_watch": {"step": _req(_INT), "straggler": _opt(_INT),
                    "skew_ms": _req(_NUM), "victims": _opt(_INT),
                    "aligned": _opt(_BOOL), "ranks": _opt(_INT)},
    "drift": {"rank": _req(_INT), "kind": _req(_STR),
              "bytes": _opt(_INT), "measured_ms": _req(_NUM),
              "predicted_ms": _req(_NUM), "windows": _req(_INT),
              "tripped": _opt(_BOOL), "source": _opt(_STR)},
    # training health monitor (telemetry/health.py): one "health" span
    # per sampled check, one "health_trip" instant per ladder firing
    "health": {"step": _req(_INT), "layers": _opt(_INT),
               "trips": _opt(_INT)},
    "health_trip": {"step": _req(_INT), "kind": _req(_STR),
                    "layer": _opt(_STR), "table": _opt(_STR),
                    "value": _opt(_NUM), "limit": _opt(_NUM)},
    # serving request lifecycle (serving/lifecycle.py + scheduler.py):
    # one serve_request span per retired request (submit -> retire) with
    # its account (ms by phase; they sum to the span), one serve_phase
    # span per contiguous episode (queue / prefill / decode / stalled /
    # replay), one serve_preempt instant per preemption. request_id is
    # the end-to-end tracing id minted at ingress; the serving doctor
    # keys its per-request conservation check on these — typed strictly,
    # no open payload.
    "serve_request": {"request_id": _req(_STR), "tokens": _req(_INT),
                      "preempts": _req(_INT), "phase": _opt(_STR),
                      "queue_ms": _opt(_NUM), "prefill_ms": _opt(_NUM),
                      "stalled_ms": _opt(_NUM),
                      "decode_device_ms": _opt(_NUM),
                      "decode_host_ms": _opt(_NUM),
                      "replay_ms": _opt(_NUM)},
    # the first prefill episode splits the prompt's tokens into
    # cache-resolved vs chip-computed (admission charged only the
    # latter) — the doctor's cache-efficacy attribution keys on these; a
    # stalled episode names the requests whose prompts ran (their ids,
    # comma-joined)
    "serve_phase": {"request_id": _req(_STR), "phase": _req(_STR),
                    "tokens": _opt(_INT), "cached_tokens": _opt(_INT),
                    "computed_tokens": _opt(_INT),
                    "blocked_by": _opt(_STR)},
    "serve_preempt": {"request_id": _req(_STR), "tokens": _opt(_INT)},
    # one span per chunked/suffix prefill dispatch (scheduler.py
    # _prefill_suffix_step): seqs in the group, computed (real, unpadded)
    # tokens, the pow2 chunk bucket dispatched, and prefix-cache tokens
    # resolved for sequences on their first chunk
    "serve_prefill_chunk": {"seqs": _req(_INT), "tokens": _req(_INT),
                            "bucket": _opt(_INT), "cached": _opt(_INT)},
    # the leaf spans that tile the scheduler thread (scheduler.py): a
    # .device span runs from the dispatch of its program through the
    # host sync of the rows the scheduler reads
    "serve.wait": {}, "serve.admit": {}, "serve.finish": {},
    "serve.prefill.build": {}, "serve.prefill.sample": {},
    # the host's wait for a prefill's rows, inside serve.prefill.device
    "serve.prefill.sync": {},
    # the parent of a prefill (through the finish after it) that
    # ``rows`` decode-ready rows wait behind; ``admitted`` = the
    # sequences whose prompts run
    "serve.stall": {"rows": _req(_INT), "admitted": _req(_INT)},
    "serve.prefill.device": {"batch_bucket": _req(_INT),
                             "prompt_bucket": _req(_INT),
                             "ctx_bucket": _opt(_INT)},
    "serve.decode.build": {}, "serve.decode.sample": {},
    "serve.decode.device": {"width": _req(_INT),
                            "batch_bucket": _req(_INT),
                            "ctx_bucket": _req(_INT)},
    # the dispatch of a decode step made while the step before is
    # still unread (its tokens stay on the device)
    "serve.decode.ahead": {"width": _req(_INT),
                           "batch_bucket": _req(_INT),
                           "ctx_bucket": _req(_INT)},
    # the probe (tune/)
    "attn_probe": {"kernel": _opt(_STR), "ms": _opt(_NUM),
                   "blocks": _opt(_STR), "seq": _opt(_INT),
                   "head_dim": _opt(_INT), "dtype": _opt(_STR)},
    # the flash backward's tile walk, recorded beside its tiles at
    # trace time (ops/pallas_attention.py:flash_attention_bwd), and
    # the heads a program takes (pallas_attention.heads_per_program)
    "flash_bwd_walk": {"seq": _req(_INT), "head_dim": _opt(_INT),
                       "block_q": _req(_INT), "block_k": _req(_INT),
                       "causal": _req(_BOOL),
                       "heads_per_program": _opt(_INT),
                       "tiles_visited": _req(_INT),
                       "tiles_square": _req(_INT),
                       "tiles_masked": _req(_INT),
                       "visited_share": _req(_NUM),
                       "masked_share": _req(_NUM)},
    # the flash forward's walk at the tiles a traced call runs with
    # (ops/pallas_attention.py:_forward): the same counts, and what a
    # program holds — its heads and its independent chains
    "flash_fwd_walk": {"seq": _req(_INT), "head_dim": _req(_INT),
                       "block_q": _req(_INT), "block_k": _req(_INT),
                       "causal": _req(_BOOL),
                       "tiles_visited": _req(_INT),
                       "tiles_square": _req(_INT),
                       "tiles_masked": _req(_INT),
                       "visited_share": _req(_NUM),
                       "masked_share": _req(_NUM),
                       "heads_per_program": _req(_INT),
                       "chains": _req(_INT),
                       # a windowed call (the band i - window < j <= i;
                       # the counts are the band's)
                       "window": _opt(_INT)},
    # the operand form a flash call runs in, at trace time (ops/
    # pallas_attention.py:_plan): token_major reads q, k, v out of the
    # projection's rows, heads_per_block heads a program; head_major
    # names the first condition of ops/attention.py:flash_layout that
    # kept it there (lanes / mesh / caller)
    "flash_layout": {"kernel": _req(_STR), "layout": _req(_STR),
                     "heads_per_block": _req(_INT), "seq": _req(_INT),
                     "head_dim": _req(_INT), "reason": _opt(_STR)},
    # the form a hyper-connections sublayer's residual path runs in,
    # at trace time (ops/mhc.py:_plan): kernel (the two Pallas kernels
    # hetu_mhc_pre / hetu_mhc_post) or composed, which names the first
    # condition of ops/mhc.py:supported that failed (dtype / lanes /
    # streams), or platform off a TPU
    "mhc_plan": {"streams": _req(_INT), "iters": _req(_INT),
                 "form": _req(_STR), "reason": _opt(_STR)},
    # which form a traced state-space call runs in (ops/ssm.py): op
    # scan (a prompt or a chunk of one) or step (one token a row);
    # form kernel (a TPU: hetu_ssm_scan / hetu_ssm_step) or composed,
    # which says why (a shape ops/ssm.py:supported does not take, or
    # platform off a TPU)
    "ssm_plan": {"op": _req(_STR), "form": _req(_STR),
                 "reason": _opt(_STR)},
    # ops/kda.py: the form a traced call of the delta rule runs in:
    # op (chunk / step), form (kernel / composed), reason (composed
    # only: "platform", or why supported() says the kernels do not take
    # the head's widths)
    "kda_plan": {"op": _req(_STR), "form": _req(_STR),
                 "reason": _opt(_STR)},
    # which form a traced program's window layers attend in (models/
    # window_moe.py): op prefill (the flash forward with the band:
    # kernel on a TPU, hetu_flash_window; composed off one, reason
    # platform) or decode (the ring gathered behind its mask, composed:
    # grouped_ring_decode_attention; reason no_paged_grouped_kernel)
    "attn_window_plan": {"op": _req(_STR), "window": _req(_INT),
                         "form": _req(_STR), "reason": _opt(_STR)},
    # ops/short_conv.py: the form a traced gated short convolution runs
    # in: composed (one jitted function a direction, hetu_short_conv_fwd
    # / hetu_short_conv_bwd, which XLA inlines: a profile reads them by
    # the scopes hetu.fwd/ShortConvOp/ and
    # hetu.bwd/_ShortConvGradientOp/); reason no_kernel (there is none
    # to choose: the composed form ran first, PERF.md section 6 PR 56
    # says what its trace showed)
    "short_conv_plan": {"form": _req(_STR), "reason": _opt(_STR),
                        "rows": _req(_INT), "channels": _req(_INT),
                        "taps": _req(_INT)},
}


def check_args(name, args):
    """Validate one event's ``args`` against SPAN_SCHEMA. Returns a
    list of error strings (empty = clean). Spans not in the schema are
    user spans — unchecked."""
    schema = SPAN_SCHEMA.get(name)
    if schema is None:
        return []
    if args is not None and not isinstance(args, dict):
        # a malformed trace must report INVALID, not traceback the gate
        return [f"span {name!r}: args must be an object, got "
                f"{type(args).__name__}"]
    errors = []
    open_ended = schema.get(..., False)
    args = args or {}
    for key, value in args.items():
        spec = schema.get(key)
        if spec is None:
            if open_ended:
                continue
            errors.append(
                f"span {name!r}: unknown attr {key!r} — register it in "
                f"telemetry.check.SPAN_SCHEMA (drift gate)")
            continue
        _, kinds = spec
        if kinds is None or value is None:
            continue
        # bool is an int subclass: an int-typed attr must not accept a
        # bool, and a bool-typed attr must be exactly bool
        if kinds == _BOOL:
            ok = isinstance(value, bool)
        elif isinstance(value, bool):
            ok = False
        else:
            ok = isinstance(value, kinds)
        if not ok:
            errors.append(
                f"span {name!r}: attr {key!r} has type "
                f"{type(value).__name__}, expected "
                f"{'/'.join(k.__name__ for k in kinds)}")
    for key, spec in schema.items():
        if key is ... or spec[0] != "req":
            continue
        if key not in args:
            errors.append(
                f"span {name!r}: required attr {key!r} missing")
    return errors


def validate(path, check_attrs=True):
    """Validate one trace file; returns (n_events, errors).
    ``check_attrs=False`` skips the span-attr schema (structural checks
    only — foreign traces)."""
    errors = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return 0, [f"{path}: unreadable JSON: {e}"]
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return 0, [f"{path}: no 'traceEvents' list"]
    elif isinstance(doc, list):
        events = doc
    else:
        return 0, [f"{path}: top level must be an object or array"]

    last_ts = None
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        missing = [k for k in _REQUIRED if k not in ev]
        if missing:
            errors.append(f"event {i} ({ev.get('name')!r}): missing "
                          f"keys {missing}")
            continue
        ph = ev["ph"]
        if ph not in _KNOWN_PH:
            errors.append(f"event {i}: unknown ph {ph!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            errors.append(f"event {i}: bad ts {ev['ts']!r}")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i}: 'X' event needs dur >= 0 "
                              f"(got {dur!r})")
        if check_attrs and ph in ("X", "i", "I"):
            for e in check_args(ev["name"], ev.get("args")):
                errors.append(f"event {i}: {e}")
        if ph != "M":
            # exporters sort non-metadata events: ts must be monotonic
            # non-decreasing so Perfetto's sequential parsers stay happy
            if last_ts is not None and ev["ts"] < last_ts:
                errors.append(
                    f"event {i}: ts {ev['ts']} < previous {last_ts} "
                    f"(non-monotonic)")
            last_ts = ev["ts"]
        if len(errors) >= 20:
            errors.append("... (truncated)")
            break
    return len(events), errors


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    check_attrs = True
    if "--no-attrs" in argv:
        argv = [a for a in argv if a != "--no-attrs"]
        check_attrs = False
    if not argv:
        print("usage: python -m hetu_tpu.telemetry.check [--no-attrs] "
              "<trace.json>...", file=sys.stderr)
        return 2
    rc = 0
    for path in argv:
        n, errors = validate(path, check_attrs=check_attrs)
        if errors:
            rc = 1
            print(f"{path}: INVALID ({len(errors)} errors)")
            for e in errors:
                print(f"  {e}")
        else:
            print(f"{path}: OK ({n} events)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
