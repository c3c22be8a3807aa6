"""The serving doctor (telemetry/doctor.py --serving), the serve_*
span schema (telemetry/check.py), the black-box requests ingest
(telemetry/blackbox.py), the crash-time in-flight dump
(Telemetry.flush -> lifecycle.dump_inflight), and the regress-gate
directions for the stamped serving percentiles — synthetic-span math
first, then a real-producer round trip through the exported files."""
import json

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
import hetu_tpu.models as M
from hetu_tpu.serving import ContinuousBatchingEngine, InferenceSession
from hetu_tpu.telemetry import blackbox
from hetu_tpu.telemetry.check import check_args, validate
from hetu_tpu.telemetry.doctor import (SERVE_BUCKETS,
                                       attribute_request_events,
                                       parse_request_events,
                                       render_serving_text,
                                       summarize_requests)
from hetu_tpu.telemetry.doctor import main as doctor_main

VOCAB, SEQ = 64, 32


def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "pid": 0, "tid": 1, "args": args}


def _request_spans(rid, t0, episodes, tokens=5, preempts=0):
    """serve_phase spans for (phase, start, end) triples (µs): one a
    contiguous episode, as the engine exports them (a run of decode
    steps is ONE episode, cut only by a stall or a replay)."""
    evs = [_span("serve_phase", s, t - s, request_id=rid, phase=ph)
           for ph, s, t in episodes]
    return evs


# ---------------------------------------------------------------------------
# attribution math on synthetic spans
# ---------------------------------------------------------------------------

def test_attribution_math_exact():
    """Known episode durations -> exact buckets; the episodes tile the
    request, so overhead (what they leave of e2e) is zero; a stall
    between two decode runs is its own bucket; TTFT is the last prefill
    end before decode starts (the only prefill end here); the engine's
    split of decode rides on the serve_request span."""
    rid = "synth-1"
    evs = _request_spans(rid, 1000, [
        ("queue", 1000, 3000),          # 2 ms
        ("prefill", 3000, 5000),        # 2 ms (TTFT point: 5000)
        ("decode", 5000, 6500),
        ("stalled", 6500, 8000),        # another request's prompt ran
        ("decode", 8000, 11000),        # 4.5 ms total decode
    ])
    evs.append(_span("serve_request", 1000, 10000, request_id=rid,
                     phase="retired", tokens=5, preempts=0,
                     decode_device_ms=3.5, decode_host_ms=1.0))
    (r,) = parse_request_events(evs)
    assert r["conserved"] and r["complete"]
    assert r["e2e_ms"] == 10.0
    assert r["buckets_ms"] == {"queue": 2.0, "prefill": 2.0,
                               "decode": 4.5, "stalled": 1.5,
                               "replay": 0.0, "overhead": 0.0}
    assert sum(r["buckets_ms"].values()) == r["e2e_ms"]
    assert r["decode_split_ms"] == {"decode_device": 3.5,
                                    "decode_host": 1.0}
    assert r["ttft_ms"] == 4.0          # 5000 - 1000
    # TPOT: (retire - first token) / (tokens - 1) = 6ms / 4
    assert r["tpot_ms"] == 1.5
    diag = summarize_requests([r])
    assert diag["requests"] == 1 and diag["conserved"]
    assert diag["decode_split_ms"]["decode_device"] == 3.5
    assert diag["top_bucket"]["bucket"] in SERVE_BUCKETS
    assert diag["top_bucket"]["remedy"]
    text = render_serving_text(diag)
    assert "conservation" in text and "[OK]" in text
    assert "top bucket" in text


def test_replay_bucket_and_preempt_stats():
    rid = "synth-p"
    evs = _request_spans(rid, 0, [
        ("queue", 0, 1000),
        ("prefill", 1000, 2000),
        ("decode", 2000, 3000),
        ("replay", 3000, 7000),         # preempted: wait + re-earn
        ("decode", 7000, 9000),
    ])
    evs.append(_span("serve_request", 0, 9000, request_id=rid,
                     phase="retired", tokens=4, preempts=1))
    diag = attribute_request_events(evs)
    assert diag["conserved"] and diag["complete"]
    assert diag["preempted_requests"] == 1 and diag["preempt_rate"] == 1.0
    assert diag["buckets_ms"]["replay"] == 4.0
    assert diag["replay_fraction"] == pytest.approx(4.0 / 9.0, abs=1e-3)


def test_chunked_prefill_ttft_is_final_chunk_end():
    """Under chunked prefill the engine exports ONE prefill episode,
    admission to the final chunk's host sync (the others' decode steps
    between the chunks are inside it); a trace cut into several prefill
    episodes (a request preempted before its first token) reads the
    same: the first token only exists once the final chunk lands, so
    TTFT is the LAST prefill end preceding the first decode start —
    the first-episode end would fake a 3x-better TTFT here."""
    rid = "synth-chunk"
    evs = _request_spans(rid, 0, [
        ("queue", 0, 1000),
        ("prefill", 1000, 2000),        # preempted before token 0
        ("replay", 2000, 3000),         # back in the queue
        ("prefill", 3000, 6000),        # every chunk of the second try
        ("decode", 6000, 9000),
    ])
    evs.append(_span("serve_request", 0, 9000, request_id=rid,
                     phase="retired", tokens=3, preempts=0))
    (r,) = parse_request_events(evs)
    assert r["conserved"] and r["complete"]
    assert r["ttft_ms"] == 6.0, \
        "TTFT must be the FINAL chunk's end, not the first's"
    assert r["buckets_ms"]["prefill"] == 4.0
    assert r["buckets_ms"]["overhead"] == 0.0
    # TPOT spans first token -> retire over tokens-1
    assert r["tpot_ms"] == pytest.approx(3.0 / 2)


def test_prefill_cached_vs_computed_attribution():
    """serve_phase prefill episodes carry the cached/computed token
    split; the doctor rolls both up per request and fleet-wide so cache
    efficacy is auditable from the trace alone."""
    evs = []
    for rid, cached, computed in (("r-cold", 0, 20), ("r-hot", 16, 4)):
        t0 = 0
        evs += _request_spans(rid, t0, [("queue", 0, 500)])
        evs.append(_span("serve_phase", 500, 1000, request_id=rid,
                         phase="prefill", cached_tokens=cached,
                         computed_tokens=computed))
        evs.append(_span("serve_phase", 1500, 1000, request_id=rid,
                         phase="decode"))
        evs.append(_span("serve_request", 0, 2500, request_id=rid,
                         phase="retired", tokens=2, preempts=0))
    reqs = {r["request_id"]: r for r in parse_request_events(evs)}
    assert reqs["r-cold"]["cached_tokens"] == 0
    assert reqs["r-cold"]["computed_tokens"] == 20
    assert reqs["r-hot"]["cached_tokens"] == 16
    assert reqs["r-hot"]["computed_tokens"] == 4
    diag = summarize_requests(list(reqs.values()))
    assert diag["prefill_cached_tokens"] == 16
    assert diag["prefill_computed_tokens"] == 24
    # the prefill remedy names the knobs that fix a prefill-bound fleet
    from hetu_tpu.telemetry.doctor import _SERVE_REMEDY
    assert "prefix_cache" in _SERVE_REMEDY["prefill"]
    assert "prefill_chunk" in _SERVE_REMEDY["prefill"]


def test_overclaim_fails_conservation():
    """Episodes claiming more than the measured e2e — the producer bug
    conservation exists to catch — fail the verdict, and the CLI-level
    verdict would be exit 1."""
    rid = "synth-bad"
    evs = _request_spans(rid, 0, [
        ("queue", 0, 4000),
        ("prefill", 4000, 9000),
        ("decode", 9000, 15000),        # claims 15ms against a 10ms e2e
    ])
    evs.append(_span("serve_request", 0, 10000, request_id=rid,
                     phase="retired", tokens=3, preempts=0))
    diag = attribute_request_events(evs)
    assert not diag["conserved"]
    assert diag["violations"] == [rid]
    assert "FAILED" in render_serving_text(diag)
    # and so does a hole: the engine's episodes tile the request, so
    # 3 of 10 ms that no episode covers is a skipped recording site
    evs = _request_spans(rid, 0, [
        ("queue", 0, 2000), ("prefill", 2000, 4000),
        ("decode", 4000, 5000), ("decode", 8000, 10000)])
    evs.append(_span("serve_request", 0, 10000, request_id=rid,
                     phase="retired", tokens=3, preempts=0))
    (r,) = parse_request_events(evs)
    assert not r["conserved"] and r["buckets_ms"]["overhead"] == 3.0


def test_out_of_window_episode_fails_conservation():
    rid = "synth-oow"
    evs = _request_spans(rid, 5000, [
        ("queue", 5000, 6000),
        ("prefill", 6000, 7000),
        ("decode", 1000, 2000),         # before the request existed
    ])
    evs.append(_span("serve_request", 5000, 5000, request_id=rid,
                     phase="retired", tokens=2, preempts=0))
    diag = attribute_request_events(evs)
    assert not diag["conserved"]


def test_incomplete_timeline_detected():
    """A request that never recorded its queue episode (a skipped
    recording site) is flagged incomplete, not silently attributed."""
    rid = "synth-inc"
    evs = _request_spans(rid, 0, [
        ("prefill", 0, 2000),
        ("decode", 2000, 4000),
    ])
    evs.append(_span("serve_request", 0, 4000, request_id=rid,
                     phase="retired", tokens=2, preempts=0))
    diag = attribute_request_events(evs)
    assert diag["conserved"]            # arithmetic is fine...
    assert not diag["complete"]         # ...but the timeline is not
    assert diag["incomplete"] == [rid]


def test_inflight_requests_are_not_attributed():
    """serve_phase spans without a retiring serve_request span (the
    request was still running at export) attribute to nothing."""
    evs = _request_spans("still-going", 0, [("queue", 0, 1000)])
    diag = attribute_request_events(evs)
    assert diag["requests"] == 0
    assert not diag["conserved"]
    assert "error" in diag


# ---------------------------------------------------------------------------
# span schema: producer fixtures validate, drift is rejected
# ---------------------------------------------------------------------------

def test_serve_span_fixtures_validate(tmp_path):
    evs = [
        _span("serve_phase", 0, 100, request_id="r1", phase="queue"),
        _span("serve_request", 0, 200, request_id="r1", phase="retired",
              tokens=4, preempts=1, queue_ms=0.1, prefill_ms=0.04,
              stalled_ms=0.01, decode_device_ms=0.03, decode_host_ms=0.02,
              replay_ms=0),
        _span("serve_preempt", 50, 0, request_id="r1", tokens=3),
        # chunked-prefill dispatch span + prefill episode carrying the
        # cached/computed token split
        _span("serve_prefill_chunk", 100, 400, seqs=2, tokens=14,
              bucket=8, cached=9),
        _span("serve_phase", 100, 400, request_id="r1", phase="prefill",
              cached_tokens=9, computed_tokens=5),
        # a stall behind two other requests' prompts: the episode, the
        # engine's parent span, the host's wait inside the prefill
        _span("serve_phase", 140, 10, request_id="r1", phase="stalled",
              blocked_by="r2,r3"),
        _span("serve.stall", 140, 10, rows=1, admitted=2),
        _span("serve.prefill.sync", 141, 5),
    ]
    p = tmp_path / "trace_rank0.json"
    p.write_text(json.dumps({"traceEvents": evs}))
    n, errors = validate(str(p))
    assert n == 8 and errors == [], errors


def test_serve_span_schema_rejects_drift():
    # unknown attr: the drift gate's whole point
    errs = check_args("serve_phase", {"request_id": "r", "phase": "queue",
                                      "speed": 9})
    assert errs and "unknown attr" in errs[0]
    # a producer that drops a required attr regressed
    errs = check_args("serve_request", {"request_id": "r", "tokens": 1})
    assert any("preempts" in e and "missing" in e for e in errs)
    # wrong type: request ids are strings, not ints
    errs = check_args("serve_request", {"request_id": 7, "tokens": 1,
                                        "preempts": 0})
    assert any("request_id" in e and "type" in e for e in errs)
    # bool is not an int (the schema's strictness contract)
    errs = check_args("serve_preempt", {"request_id": "r",
                                        "tokens": True})
    assert any("tokens" in e for e in errs)
    # chunked-prefill spans: unknown attr / dropped required / bool-int
    errs = check_args("serve_prefill_chunk", {"seqs": 1, "tokens": 8,
                                              "hit_rate": 0.5})
    assert errs and "unknown attr" in errs[0]
    errs = check_args("serve_prefill_chunk", {"seqs": 1})
    assert any("tokens" in e and "missing" in e for e in errs)
    errs = check_args("serve_prefill_chunk", {"seqs": 1, "tokens": 8,
                                              "cached": True})
    assert any("cached" in e for e in errs)
    # prefill attribution attrs validate clean and reject drift
    assert check_args("serve_phase", {"request_id": "r",
                                      "phase": "prefill",
                                      "cached_tokens": 9,
                                      "computed_tokens": 5}) == []
    errs = check_args("serve_phase", {"request_id": "r",
                                      "phase": "prefill",
                                      "cached_tokens": "lots"})
    assert any("cached_tokens" in e and "type" in e for e in errs)
    # the account's args are numbers; a stall names its rows as ints
    errs = check_args("serve_request", {"request_id": "r", "tokens": 1,
                                        "preempts": 0,
                                        "stalled_ms": "long"})
    assert any("stalled_ms" in e and "type" in e for e in errs)
    errs = check_args("serve.stall", {"rows": 1})
    assert any("admitted" in e and "missing" in e for e in errs)


# ---------------------------------------------------------------------------
# real producer -> exported files -> CLI round trip
# ---------------------------------------------------------------------------

def _run_engine(out_dir, num_blocks=30, reserve="full", n=4):
    cfg = M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=SEQ,
                      hidden_dropout_prob=0.0)
    model = M.GPTLMHeadModel(cfg)
    ids = ht.Variable("input_ids", trainable=False)
    sess = InferenceSession([model(ids)], seq_buckets=(SEQ,), seed=0)
    tel = telemetry.Telemetry(enabled=True, out_dir=str(out_dir), rank=0)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=num_blocks, block_size=4, max_batch_size=4,
        reserve=reserve, telemetry=tel, start=False)
    rng = np.random.RandomState(7)
    futs = [eng.submit(rng.randint(0, VOCAB, (5,)), 6, temperature=0.8,
                       seed=40 + i) for i in range(n)]
    steps = 0
    while any(not f.done() for f in futs):
        eng.step()
        steps += 1
        assert steps < 500
    return tel, eng


def test_doctor_serving_cli_roundtrip(tmp_path, capsys):
    """The acceptance path: a real engine's exported trace validates
    against the span schema, and ``doctor --serving`` exits 0 naming a
    top bucket with a knob remediation."""
    tel, eng = _run_engine(tmp_path, num_blocks=7, reserve="lazy")
    tel.flush()
    eng.close()
    n, errors = validate(str(tmp_path / "trace_rank0.json"))
    assert errors == [], errors

    rc = doctor_main(["--serving", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    diag = json.loads(out)
    assert diag["requests"] == 4
    assert diag["conserved"] and diag["complete"]
    assert diag["top_bucket"]["bucket"] in SERVE_BUCKETS
    assert diag["top_bucket"]["remedy"]
    # the percentile fields the serve cells report exist here too
    for field in ("serve_ttft_p99_ms", "serve_tpot_p50_ms",
                  "serve_queue_wait_p99_ms"):
        assert diag[field] > 0, field


def test_doctor_serving_exit1_on_violation(tmp_path, capsys):
    rid = "bad-1"
    evs = [_span("serve_phase", 0, 20000, request_id=rid, phase="decode"),
           _span("serve_request", 0, 10000, request_id=rid,
                 phase="retired", tokens=2, preempts=0)]
    (tmp_path / "trace_rank0.json").write_text(
        json.dumps({"traceEvents": evs}))
    assert doctor_main(["--serving", str(tmp_path)]) == 1
    assert "FAILED" in capsys.readouterr().out


def test_doctor_serving_exit1_when_no_requests(tmp_path, capsys):
    (tmp_path / "trace_rank0.json").write_text(
        json.dumps({"traceEvents": []}))
    assert doctor_main(["--serving", str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# crash-time dump + black-box ingest
# ---------------------------------------------------------------------------

def test_flush_dumps_inflight_requests(tmp_path):
    """Telemetry.flush() (what the crash handlers call) writes
    requests_rank<r>.json naming the requests still in flight."""
    tel, eng = _run_engine(tmp_path)
    eng.submit(np.arange(5), 6, request_id="stuck-1")   # never stepped
    paths = tel.flush()
    rpath = tmp_path / "requests_rank0.json"
    assert str(rpath) in paths
    doc = json.loads(rpath.read_text())
    # other live engines may be registered too; find ours by request
    rows = [r for c in doc["components"] for r in c["requests"]]
    (row,) = [r for r in rows if r["request_id"] == "stuck-1"]
    assert row["phase"] == "waiting"
    comp = next(c for c in doc["components"]
                if any(r["request_id"] == "stuck-1"
                       for r in c["requests"]))
    assert comp["kind"] == "ContinuousBatchingEngine"
    assert comp["stats"]["waiting"] == 1
    eng.close()


def test_blackbox_names_stuck_requests(tmp_path):
    """A watchdogged/crashed engine's black-box report names the stuck
    requests, not just the guilty rank."""
    tel, eng = _run_engine(tmp_path)
    eng.submit(np.arange(5), 6, request_id="stuck-bb")
    tel.flush()
    eng.close()
    rep = blackbox.analyze(str(tmp_path))
    assert rep is not None
    rows = rep["serving"]["0"]["stuck_requests"]
    assert "stuck-bb" in [r["request_id"] for r in rows]
    text = blackbox.format_report(rep)
    assert "SERVING rank 0" in text
    assert "STUCK 'stuck-bb'" in text


def test_blackbox_ingests_requests_without_flight_dump(tmp_path):
    """A requests dump alone (flight ring never flushed) is still a
    report, not 'nothing to analyze'."""
    (tmp_path / "requests_rank0.json").write_text(json.dumps({
        "rank": 0, "pid": 1, "wall": 0.0,
        "components": [{"name": "engine",
                        "kind": "ContinuousBatchingEngine",
                        "requests": [{"request_id": "lone-1",
                                      "phase": "running",
                                      "tokens_done": 2,
                                      "tokens_budget": 8,
                                      "kv_blocks": 3, "preempts": 1,
                                      "age_ms": 1234.5}]}]}))
    rep = blackbox.analyze(str(tmp_path))
    assert rep is not None
    text = blackbox.format_report(rep)
    assert "lone-1" in text and "3 KV blocks held" in text
