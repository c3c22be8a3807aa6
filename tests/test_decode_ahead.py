"""The decode loop keeps one program in flight (ISSUE 33): step k+1 is
dispatched before step k's ids are read, its ``tokens`` the device
array step k returns. Held here, on the CPU, by comparison and by
count: the engine answers token for token what a run that reads every
program before the next step begins answers, it runs the same number of
decode programs, it says how often it dispatched ahead, and it leaves
nothing unread behind. Nothing is timed.
"""
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.serving import ContinuousBatchingEngine
from hetu_tpu.telemetry.doctor import attribute_request_events

from gpt_reference import gpt_session
from test_decode_device_pick import _prompts
from test_latent_moe_serving import engine_for, family, tiny

# name -> (engine keywords, submit arguments by request)
GREEDY = [(6,), (9,), (4,), (7,)]
CASES = {
    "staggered_greedy": (dict(num_blocks=40), GREEDY),
    "sampled_row_among_greedy": (
        dict(num_blocks=40), [(6,), (9,), (4, 0.8, 41), (7,)]),
    "lazy_preempting": (dict(num_blocks=7, reserve="lazy"), GREEDY),
    "prefix_chunked": (
        dict(num_blocks=40, prefix_cache=True, prefill_chunk=8), GREEDY),
}


@pytest.fixture(scope="module")
def gpt():
    return gpt_session(seed=11)


@pytest.fixture(scope="module")
def latent():
    config = tiny()
    return config, family.seeded_weights(config, 7)


def _gpt_engine(gpt, **kw):
    cfg, sess = gpt
    kw.setdefault("telemetry", False)
    kw.setdefault("start", False)
    return ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, **kw)


def _serve(engine, requests, every_step_read=False, limit=500):
    """One request submitted a step, so later ones are admitted beside
    running ones; then driven to the end. With ``every_step_read`` the
    program a step leaves in flight is read before the next step
    begins: the synchronous loop."""
    futures = []

    def step():
        engine.step()
        if every_step_read:
            engine._read_flight()

    for request in requests:
        futures.append(engine.submit(*request))
        step()
    steps = 0
    while any(not f.done() for f in futures):
        step()
        steps += 1
        assert steps < limit, "engine failed to converge"
    assert engine._flight is None
    return futures


def _both(make, requests):
    """(engine, futures) of the engine as it is and of the run that
    reads every step."""
    runs = []
    for every_step_read in (False, True):
        engine = make()
        runs.append((engine, _serve(engine, requests, every_step_read)))
    return runs


# ---------------------------------------------------------------------------
# the same tokens, the same programs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_equal_the_run_that_reads_every_step(gpt, case):
    kw, tails = CASES[case]
    requests = [(p,) + tail for p, tail in zip(_prompts(), tails)]
    tel = telemetry.Telemetry(enabled=True)
    (ahead, got), (sync, want) = _both(
        lambda: _gpt_engine(gpt, telemetry=tel, **kw), requests)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.result(0), w.result(0))
    assert sync.decode_ahead_steps == 0
    assert ahead.decode_ahead_steps > 0
    assert ahead.decode_ahead_steps <= ahead.decode_steps
    # no program more, none less: a row's last step is known by count
    assert ahead.decode_steps == sync.decode_steps
    assert ahead.jit_compiles == sync.jit_compiles
    if case == "lazy_preempting":
        assert tel.counter_value("engine_preemptions") > 0, \
            "the 7-block pool never preempted: the case lost its point"
    if case == "sampled_row_among_greedy":
        assert 0 < ahead.decode_device_pick_steps < ahead.decode_steps
    for engine in (ahead, sync):
        assert engine.cache.referenced_blocks == 0
        engine.close()


def test_one_request_runs_max_new_less_one_programs_all_but_one_ahead(gpt):
    engine = _gpt_engine(gpt, num_blocks=40)
    (future,) = _serve(engine, [(_prompts()[0], 8)])
    assert future.result(0).shape == (8,)
    assert engine.decode_steps == 7
    # the first has nothing in flight before it
    assert engine.decode_ahead_steps == 6
    assert engine.stats()["decode_ahead_steps"] == 6
    engine.close()


def test_decode_steps_are_the_sum_of_max_new_less_one(gpt):
    """Requests that never share a step."""
    engine = _gpt_engine(gpt, num_blocks=40)
    lengths = [5, 3, 7]
    for p, n in zip(_prompts(), lengths):
        _serve(engine, [(p, n)])
    assert engine.decode_steps == sum(n - 1 for n in lengths)
    assert 0 < engine.decode_ahead_steps <= engine.decode_steps
    engine.close()


def test_a_lone_sampled_request_is_never_ahead(gpt):
    engine = _gpt_engine(gpt, num_blocks=40)
    (future,) = _serve(engine, [(_prompts()[0], 6, 0.7, 3)])
    assert future.result(0).shape == (6,)
    assert engine.decode_steps == 5
    assert engine.decode_ahead_steps == 0
    assert engine.decode_device_pick_steps == 0
    engine.close()


def test_latent_model_tokens_and_records_row_for_row(latent):
    config, weights = latent
    tel = telemetry.Telemetry(enabled=True)
    requests = [(p, n) for p, n in zip(_prompts(), (6, 9, 4, 7))]
    (ahead, got), (sync, want) = _both(
        lambda: engine_for(config, weights, telemetry=tel), requests)
    for g, w, (_, n) in zip(got, want, requests):
        np.testing.assert_array_equal(g.result(0), w.result(0))
        assert g.token_records.shape == (n, ahead.model.row_record_width)
        np.testing.assert_array_equal(g.token_records, w.token_records)
    assert 0 < ahead.decode_ahead_steps <= ahead.decode_steps
    assert ahead.decode_steps == sync.decode_steps
    assert ahead.stats()["decode_moe_tokens"] == \
        sync.stats()["decode_moe_tokens"]
    # the ids reach the next step through a slice on the device, a
    # program a batch bucket, inside the bound
    assert any(k[0] == "decode_ids" for k in ahead._signatures)
    assert not any(k[0] == "decode_ids" for k in sync._signatures)
    assert ahead.jit_compiles <= ahead.compile_bound
    # a row of the log keeps its own program's dispatch and the end of
    # its own host read; rows dispatched ahead overlap the one before
    rows = [r for r in ahead.program_log if r["kind"] == "decode"]
    assert len(rows) == ahead.decode_steps
    assert all(r["t0_ns"] < r["t1_ns"] for r in rows)
    ends = [r["t1_ns"] for r in rows]
    assert ends == sorted(ends)
    overlapping = sum(b["t0_ns"] < a["t1_ns"]
                      for a, b in zip(rows, rows[1:]))
    assert overlapping == ahead.decode_ahead_steps
    for engine in (ahead, sync):
        engine.close()


# ---------------------------------------------------------------------------
# what step() may leave, and who reads it
# ---------------------------------------------------------------------------

def _with_one_in_flight(engine, prompt, new=8):
    future = engine.submit(prompt, new)
    engine.step()               # prefill, first decode: left in flight
    engine.step()               # the second dispatched ahead of its read
    assert engine._flight is not None and engine.decode_ahead_steps == 1
    return future


def test_step_returns_with_one_program_in_flight(gpt):
    engine = _gpt_engine(gpt, num_blocks=40)
    future = _with_one_in_flight(engine, _prompts()[0])
    (row,) = engine.inflight_requests()
    # prefill's token and the first step's are read, the second step's
    # is on the device: the table trails it by one
    assert row["tokens_done"] == 2 and engine.decode_steps == 2
    assert not future.done()
    engine._read_flight()
    assert engine.inflight_requests()[0]["tokens_done"] == 3
    assert engine._flight is None
    engine.close()


@pytest.mark.parametrize("how", ["close", "fail_outstanding", "thread"])
def test_closing_with_a_program_in_flight_fails_the_futures(gpt, how):
    if how == "thread":
        engine = _gpt_engine(gpt, num_blocks=40, start=True, name="ahead")
        future = engine.submit(_prompts()[0], 19)
        while engine.decode_ahead_steps == 0:
            time.sleep(0.001)
        engine.close()
        assert engine._thread is None
    else:
        engine = _gpt_engine(gpt, num_blocks=40)
        future = _with_one_in_flight(engine, _prompts()[0])
        if how == "close":
            engine.close()
        else:
            engine._fail_outstanding(RuntimeError("engine closed"))
    assert engine._flight is None
    if not future.done() or future.exception(0) is not None:
        with pytest.raises(RuntimeError, match="engine closed"):
            future.result(0)
    assert engine.cache.referenced_blocks == 0
    assert engine.stats()["running"] == 0
    assert not any(t.name == "ahead-scheduler"
                   for t in threading.enumerate())
    with pytest.raises(RuntimeError, match="engine closed"):
        engine.submit(_prompts()[0], 2)


def test_after_warm_up_traffic_compiles_nothing(gpt):
    import jax.monitoring as monitoring
    backend = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: backend.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    engine = _gpt_engine(gpt, num_blocks=40)
    ran = engine.warm_up((10, 13), 9)
    assert ran["decode"] and ran["prefill"] and not ran["decode_ids"]
    warmed, compiled = engine.jit_compiles, len(backend)
    requests = [(p, n) for p, n in zip(_prompts(), (6, 9, 4, 7))]
    _serve(engine, requests)
    assert engine.decode_ahead_steps > 0
    # a result array fed back as ``tokens`` meets the program the numpy
    # arrays of the warm-up compiled
    assert engine.jit_compiles == warmed
    assert len(backend) == compiled
    engine.close()


# ---------------------------------------------------------------------------
# the counter, the span, the timelines
# ---------------------------------------------------------------------------

def test_counter_span_and_episodes(gpt, counted):
    tel = telemetry.Telemetry(enabled=True)
    engine = _gpt_engine(gpt, num_blocks=40, telemetry=tel, name="eng")
    requests = [(p, n) for p, n in zip(_prompts(), (6, 9, 4, 7))]
    futures = _serve(engine, requests)
    ahead = engine.decode_ahead_steps
    assert 0 < ahead < engine.decode_steps
    assert tel.counter_value("eng_decode_ahead_steps") == ahead
    assert tel.counter_value("eng_decode_steps") == engine.decode_steps
    assert tel.counter_value("eng_tokens") == sum(n for _, n in requests)
    # one leaf span a dispatch made ahead; every program is read once
    assert counted.count("hetu.serve.decode.ahead") == ahead
    assert counted.count("hetu.serve.decode.sample") == engine.decode_steps
    assert counted.count("hetu.serve.decode.build") == engine.decode_steps
    # a request's episodes tile its life though its programs overlap:
    # each starts where the one before ended, the first at its submit
    # and the last at its retirement, and a run of decode steps is ONE
    # episode, cut only where another request's prompt stalled it
    events = tel.tracer.drain()
    diag = attribute_request_events(events)
    assert diag["requests"] == len(requests)
    assert diag["conserved"] and diag["complete"]
    assert diag["buckets_ms"]["overhead"] < 0.01 * diag["e2e_total_ms"]
    episodes, whole = {}, {}
    for e in events:
        if e["ph"] == "X" and e["name"] == "serve_phase":
            episodes.setdefault(e["args"]["request_id"], []).append(
                (e["ts"], e["ts"] + e["dur"], e["args"]["phase"]))
        elif e["ph"] == "X" and e["name"] == "serve_request":
            whole[e["args"]["request_id"]] = (e["ts"], e["ts"] + e["dur"])
    assert len(episodes) == len(requests)
    stalls = counted.count("hetu.serve.stall")
    assert stalls == len(requests) - 1      # each but the first met rows
    for rid, spans in episodes.items():
        spans.sort()
        phases = [ph for _, _, ph in spans]
        assert phases[:2] == ["queue", "prefill"]
        assert set(phases[2:]) <= {"decode", "stalled"}
        # at most one decode run more than stalls, never one a step
        assert phases.count("decode") <= phases.count("stalled") + 1
        assert phases.count("stalled") <= stalls
        edges = [whole[rid][0]] + [t for s, e, _ in spans
                                   for t in (s, e)] + [whole[rid][1]]
        for end, start in zip(edges[::2], edges[1::2]):
            # microseconds near 2e15: a float holds them to 0.25
            assert abs(start - end) <= 1.0, spans
    # steps dispatched ahead are counted once: decode_device,
    # decode_host and stalled tile what follows a request's first token
    # (the span's end is the retirement, to a float's 0.25 us)
    for f, rid in zip(futures, episodes):
        a = f.account
        after = a["decode_device"] + a["decode_host"] + a["stalled"]
        retire_us = whole[rid][1] - whole[rid][0] + f.t_submit_ns / 1e3
        assert after * 1e3 == pytest.approx(
            retire_us - f.t_first_token_ns / 1e3, abs=2.0)
        assert a["decode_device"] > 0 and a["replay"] == 0
    engine.close()
