"""Request-level serving observability (hetu_tpu/serving/lifecycle.py
+ the instrumented scheduler/batcher/router/http planes): end-to-end
request ids minted at ingress and honored through every hop, per-request
phase timelines whose doctor-attributed buckets sum to measured e2e,
preemption/replay episodes, live in-flight introspection
(``inflight_requests()`` / ``stats()`` / ``GET /v1/requests`` /
``GET /stats``), structured 429/503 overload mapping, the TTFT-aware
SLO window, and the PR 2 zero-alloc disabled path."""
import gc
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.serving import (ContinuousBatchingEngine, EngineOverloaded,
                              KVCacheExhausted, MicroBatcher,
                              ReplicaRouter, RouterOverloaded,
                              ServingHTTPServer, SLOWindow)
from hetu_tpu.serving.lifecycle import PHASES
from hetu_tpu.telemetry.doctor import attribute_request_events

from gpt_reference import VOCAB, gpt_session


def _tel():
    return telemetry.Telemetry(enabled=True)




def _drive(engine, futures, limit=500):
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"
    return steps


# ---------------------------------------------------------------------------
# timelines: completeness + conservation on a live engine
# ---------------------------------------------------------------------------

def _closed_loop(eng, work):
    """One client thread a row of ``work``, each sending its requests
    one after another: more clients than batch slots, so requests
    wait, join and leave a running batch. Returns every request's
    output in submission order a client."""
    outs, errors = [None] * len(work), []

    def client(k):
        try:
            outs[k] = [eng.submit(p, g, request_id=f"obs-{k}-{i}")
                       .result(120) for i, (p, g) in enumerate(work[k])]
        except Exception as e:                      # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(len(work))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not any(t.is_alive() for t in threads), "a client hung"
    assert not errors, errors
    return outs


@pytest.mark.parametrize("closed_loop", [False, True],
                         ids=["hand_driven", "closed_loop"])
def test_request_timelines_conserve_end_to_end(closed_loop):
    """Every retired request carries a complete timeline whose
    queue/prefill/decode/replay/overhead buckets sum to its measured
    e2e — the tentpole acceptance check, in-process: on an engine
    stepped by hand, and on a running engine behind a closed loop of
    twice as many client threads as batch slots, where every request
    also gets the tokens it asked for and the engine's token counter
    counts exactly those."""
    tel = _tel()
    cfg, sess = gpt_session(seed=0)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        telemetry=tel, start=closed_loop)
    rng = np.random.RandomState(1)
    if closed_loop:
        work = [[(rng.randint(0, VOCAB, (int(rng.randint(2, 10)),)),
                  int(rng.randint(1, 7))) for _ in range(3)]
                for _ in range(8)]
        outs = _closed_loop(eng, work)
        asked = [g for reqs in work for _, g in reqs]
        assert [len(o) for client in outs for o in client] == asked
        assert tel.counter_value("engine_tokens") == sum(asked)
        ids = {f"obs-{k}-{i}" for k in range(8) for i in range(3)}
    else:
        futs = [eng.submit(rng.randint(0, VOCAB,
                                       (int(rng.randint(2, 10)),)),
                           int(g), request_id=f"obs-{i}")
                for i, g in enumerate(rng.randint(1, 7, 6))]
        _drive(eng, futs)
        ids = {f"obs-{i}" for i in range(6)}
    eng.close()

    diag = attribute_request_events(tel.tracer.drain())
    assert diag["requests"] == len(ids)
    assert diag["conserved"], f"violations: {diag['violations']}"
    assert diag["complete"], f"incomplete: {diag['incomplete']}"
    # the ingress-supplied ids survived to the attribution
    seen = {r["request_id"] for r in diag["slowest_requests"]}
    assert seen <= ids
    # per-request invariants: TTFT exists, buckets non-negative
    for r in diag["slowest_requests"]:
        assert r["ttft_ms"] is not None and r["ttft_ms"] >= 0
        assert all(v >= 0 for v in r["buckets_ms"].values())
        total = sum(r["buckets_ms"].values())
        assert total == pytest.approx(r["e2e_ms"], rel=0.06, abs=0.5)
    # fleet percentiles exist and the top bucket names a real knob
    assert diag["serve_ttft_p99_ms"] > 0
    assert diag["top_bucket"]["bucket"] in diag["buckets_ms"]
    assert diag["top_bucket"]["remedy"]


def test_minted_ids_and_histograms():
    """submit() without request_id mints one; the TTFT/TPOT/queue-wait
    histograms land with one observation per retired request."""
    tel = _tel()
    cfg, sess = gpt_session(seed=1)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        telemetry=tel, start=False)
    futs = [eng.submit(np.arange(3) + i, 3) for i in range(3)]
    _drive(eng, futs)
    eng.close()
    spans = [e for e in tel.tracer.drain() if e["name"] == "serve_request"]
    assert len(spans) == 3
    for e in spans:
        assert e["args"]["request_id"].startswith("req-")
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    for hist in ("serve_ttft_ms", "serve_tpot_ms", "serve_queue_wait_ms",
                 "serve_preempts"):
        assert snap[hist]["count"] == 3, hist


def test_preemption_becomes_replay_episodes():
    """A lazy-reserve pool too small for everyone: the preempted
    request's timeline carries replay episodes, the serve_preempt
    instant fires, and conservation still holds."""
    tel = _tel()
    cfg, sess = gpt_session(seed=6)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=7, block_size=4, max_batch_size=4,
        reserve="lazy", telemetry=tel, start=False)
    rng = np.random.RandomState(7)
    futs = [eng.submit(rng.randint(0, VOCAB, (5,)), 6, temperature=0.8,
                       seed=40 + i) for i in range(4)]
    _drive(eng, futs)
    eng.close()
    assert tel.counter_value("engine_preemptions") > 0, \
        "7-block lazy pool never preempted — the test lost its point"
    events = tel.tracer.drain()
    assert any(e["name"] == "serve_preempt" for e in events)
    diag = attribute_request_events(events)
    assert diag["requests"] == 4
    assert diag["conserved"] and diag["complete"]
    assert diag["preempted_requests"] >= 1
    assert diag["buckets_ms"]["replay"] > 0
    victim = next(r for r in diag["slowest_requests"]
                  if r["preempts"] > 0)
    assert victim["buckets_ms"]["replay"] > 0
    # ONE replay episode a preemption (its wait, its prefill and the
    # steps that earn its tokens back merged), and the Future's account
    # says the same: what was lost and re-done is replay, not decode
    replays = [e for e in events if e["name"] == "serve_phase"
               and e["args"]["phase"] == "replay"
               and e["args"]["request_id"] == victim["request_id"]]
    assert len(replays) == victim["preempts"]
    lost = [f.account for f in futs if f.account["replay"] > 0]
    assert len(lost) == diag["preempted_requests"]
    assert sum(a["replay"] for a in lost) == pytest.approx(
        diag["buckets_ms"]["replay"], abs=0.01)


# ---------------------------------------------------------------------------
# live introspection: inflight_requests() / stats()
# ---------------------------------------------------------------------------

def test_engine_inflight_table_and_stats():
    cfg, sess = gpt_session(seed=2)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        start=False)
    fut = eng.submit(np.arange(4), 3, request_id="intro-1")
    rows = eng.inflight_requests()
    assert len(rows) == 1
    (row,) = rows
    assert row["request_id"] == "intro-1"
    assert row["phase"] == "waiting"
    assert row["tokens_done"] == 0 and row["tokens_budget"] == 3
    assert row["kv_blocks"] == 0 and row["preempts"] == 0
    assert row["age_ms"] >= 0
    eng.step()                          # admit + prefill
    (row,) = eng.inflight_requests()
    assert row["phase"] == "running"
    assert row["kv_blocks"] > 0
    _drive(eng, [fut])
    assert eng.inflight_requests() == []
    st = eng.stats()
    assert st["kind"] == "ContinuousBatchingEngine"
    assert st["running"] == 0 and st["waiting"] == 0
    assert st["kv_blocks"] == 30 and st["kv_blocks_used"] == 0
    assert st["jit_compiles"] <= st["compile_bound"]
    assert st["healthy"] is True
    eng.close()


def test_router_unions_replica_tables():
    class _Replica:
        def __init__(self, i):
            self.i = i

        def inflight_requests(self):
            return [{"request_id": f"r{self.i}", "phase": "waiting"}]

        def stats(self):
            return {"kind": "stub", "i": self.i}

    router = ReplicaRouter([_Replica(0), _Replica(1)])
    rows = router.inflight_requests()
    assert {(r["request_id"], r["replica"]) for r in rows} == \
        {("r0", 0), ("r1", 1)}
    st = router.stats()
    assert st["kind"] == "ReplicaRouter" and len(st["replicas"]) == 2
    assert st["replicas"][1]["replica"] == {"kind": "stub", "i": 1}
    assert all(e["healthy"] for e in st["replicas"])


def test_batcher_inflight_and_queue_wait_histogram():
    tel = _tel()
    release = threading.Event()

    def serve(feeds):
        release.wait(5)
        return [feeds["x"] * 2]

    with MicroBatcher(serve, max_batch_size=4, max_wait_ms=1,
                      telemetry=tel) as mb:
        fut = mb.submit({"x": np.ones((1, 2), "f")},
                        request_id="batch-1")
        deadline = time.time() + 5
        while not mb.inflight_requests() and time.time() < deadline:
            time.sleep(0.005)
        rows = mb.inflight_requests()
        if rows:            # the tick may have claimed it already
            assert rows[0]["request_id"] == "batch-1"
            assert rows[0]["phase"] == "waiting"
        st = mb.stats()
        assert st["kind"] == "MicroBatcher"
        assert st["max_batch_size"] == 4
        release.set()
        fut.result(5)
    snap = {s["name"]: s for s in tel.metrics.snapshot()}
    assert snap["serve_queue_wait_ms"]["count"] >= 1


# ---------------------------------------------------------------------------
# TTFT-aware SLO window
# ---------------------------------------------------------------------------

def test_slo_window_ttft_breach():
    """A request fleet can meet its e2e SLO while first tokens arrive
    unacceptably late — the TTFT SLO catches exactly that."""
    slo = SLOWindow(p99_ms=1000.0, ttft_p99_ms=50.0)
    for _ in range(40):
        slo.note(True, 200.0, ttft_ms=180.0)    # e2e fine, TTFT awful
    healthy, reason = slo.health()
    assert not healthy
    assert "serve_ttft_ms" in reason
    # without TTFT samples the verdict falls back to e2e-only
    slo2 = SLOWindow(p99_ms=1000.0, ttft_p99_ms=50.0)
    for _ in range(40):
        slo2.note(True, 200.0)
    assert slo2.health()[0]


def test_engine_accepts_ttft_slo():
    """An engine whose requests ALL meet the e2e SLO still flips
    /healthz when TTFT breaches (timelines feed the window tel-on)."""
    cfg, sess = gpt_session(seed=3)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        slo_p99_ms=1e9, slo_ttft_p99_ms=0.0001, telemetry=_tel(),
        start=False)
    futs = [eng.submit(np.arange(4) + i, 2) for i in range(3)]
    _drive(eng, futs)
    healthy, reason = eng.health()
    assert not healthy and "serve_ttft_ms" in reason
    eng.close()


# ---------------------------------------------------------------------------
# HTTP ingress: request ids + structured overload mapping
# ---------------------------------------------------------------------------

def _post(port, body=b'{"inputs": {"x": [[1.0]]}}', headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict", data=body,
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=5)


class _OkBackend:
    """submit(feeds, request_id=...) backend that records the rid."""

    def __init__(self):
        self.rids = []

    def submit(self, feeds, request_id=None):
        self.rids.append(request_id)
        fut = Future()
        fut.set_result([np.asarray([[42.0]])])
        return fut


class _RaisingBackend:
    def __init__(self, exc):
        self.exc = exc

    def submit(self, feeds, request_id=None):
        raise self.exc


def test_http_request_id_honored_and_echoed():
    backend = _OkBackend()
    with ServingHTTPServer(backend) as srv:
        resp = _post(srv.port, headers={"x-request-id": "client-7"})
        body = json.loads(resp.read())
        assert resp.headers["X-Request-Id"] == "client-7"
        assert body["request_id"] == "client-7"
        assert backend.rids == ["client-7"]
        # no header -> the server mints one and still echoes it
        resp = _post(srv.port)
        body = json.loads(resp.read())
        rid = body["request_id"]
        assert rid.startswith("req-")
        assert resp.headers["X-Request-Id"] == rid
        assert backend.rids[-1] == rid


@pytest.mark.parametrize("exc,code,retry_s", [
    (EngineOverloaded("queue full"), 429, 1),
    (RouterOverloaded("fleet breached"), 503, 2),
    (KVCacheExhausted("pool dry"), 503, 2),
])
def test_http_overload_maps_to_structured_backpressure(exc, code, retry_s):
    tel = _tel()
    with ServingHTTPServer(_RaisingBackend(exc), telemetry=tel) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, headers={"x-request-id": "shed-1"})
        err = ei.value
        assert err.code == code
        assert err.headers["Retry-After"] == str(retry_s)
        assert err.headers["X-Request-Id"] == "shed-1"
        body = json.loads(err.read())
        assert body["request_id"] == "shed-1"
        assert body["retry_after_ms"] == retry_s * 1000
        assert type(exc).__name__ in body["error"]
    assert tel.counter_value("http_shed_requests") == 1


def test_http_model_bugs_still_500_with_rid():
    with ServingHTTPServer(_RaisingBackend(RuntimeError("boom"))) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port)
        assert ei.value.code == 500
        body = json.loads(ei.value.read())
        assert "boom" in body["error"]
        assert body["request_id"].startswith("req-")


def test_http_requests_and_stats_routes():
    class _Introspectable(_OkBackend):
        def inflight_requests(self):
            return [{"request_id": "live-1", "phase": "running"}]

        def stats(self):
            return {"kind": "stub", "running": 1}

    with ServingHTTPServer(_Introspectable(), slo_p99_ms=500.0) as srv:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/requests",
            timeout=5).read())
        assert doc["count"] == 1
        assert doc["requests"][0]["request_id"] == "live-1"
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/stats", timeout=5).read())
        assert doc["healthy"] is True
        assert doc["slo_p99_ms"] == 500.0
        assert doc["backend"] == {"kind": "stub", "running": 1}
    # a backend without introspection 404s instead of crashing
    with ServingHTTPServer(_OkBackend()) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/requests", timeout=5)
        assert ei.value.code == 404


# ---------------------------------------------------------------------------
# the PR 2 contract: disabled telemetry stays zero-alloc per step
# ---------------------------------------------------------------------------

def test_disabled_engine_allocates_no_timelines():
    """The always-on account is a few marks a request, however many
    tokens it decodes, and nothing a step."""
    cfg, sess = gpt_session(seed=4)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        start=False)
    assert not eng.telemetry.enabled
    fut = eng.submit(np.arange(4), 20)
    seq = eng._waiting[0]
    assert seq.rid                          # the id exists
    assert len(seq.tl.marks) == 1           # submitted: in the queue
    _drive(eng, [fut])
    # queue -> prefill -> run: three marks for twenty tokens
    assert [m[3] for m in seq.tl.marks] == ["queue", "prefill", "run"]
    assert set(fut.account) == set(PHASES)

    # idle step() (the hot steady-state poll) is allocation-free; the
    # first few thousand iterations grow interpreter freelists once, so
    # warm PAST that before pinning the steady state
    for _ in range(5200):
        eng.step()
    gc.collect()
    gc.disable()
    try:
        grown = []
        # two rounds: the first also pays what the measurement itself
        # allocates once (11 blocks with the phase clock's ints, 8
        # before it); a leak a step would show in both
        for _ in range(2):
            before = sys.getallocatedblocks()
            for _ in range(5000):
                eng.step()
            grown.append(sys.getallocatedblocks() - before)
    finally:
        gc.enable()
    assert grown[0] <= 16 and grown[1] <= 8, \
        f"disabled idle step leaked {grown} blocks over 2 x 5000"
    eng.close()
