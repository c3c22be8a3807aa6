"""The serving engine's phase clock (ISSUE 37): one clock whose laps
tile the scheduler thread (``stats()["phase_ms"]``), the stall a
running row waits behind as a span (``serve.stall``), and every
request's latency by phase as differences of readings of that clock
(``Future.account``, ``stats()["request_account"]``), telemetry on or
off. Held on the CPU with the tiny GPT by conservation and by count:
what must sum sums, what must be absent is absent. Nothing is timed
against a budget."""
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.serving import ContinuousBatchingEngine
from hetu_tpu.serving.lifecycle import PHASES
from hetu_tpu.serving.scheduler import ENGINE_PHASES
from hetu_tpu.telemetry.doctor import attribute_request_events

from gpt_reference import VOCAB, gpt_session


@pytest.fixture(scope="module")
def gpt():
    return gpt_session(seed=5)


def _engine(gpt, **kw):
    cfg, sess = gpt
    kw.setdefault("telemetry", False)
    kw.setdefault("start", False)
    kw.setdefault("num_blocks", 40)
    return ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,))


def _drive(engine, futures, limit=500):
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"


def _staggered(engine, requests, gap=3):
    """Submit one request every ``gap`` steps, so a later one is
    admitted beside running ones; drive to the end."""
    futures = []
    for prompt, new in requests:
        futures.append(engine.submit(prompt, new))
        for _ in range(gap):
            engine.step()
    _drive(engine, futures)
    return futures


def _ring(tel, name):
    """``[(start_us, end_us, args)]`` of the ring's spans ``name``."""
    return sorted(((e["ts"], e["ts"] + e["dur"], e.get("args") or {})
                   for e in tel.tracer.drain()
                   if e["ph"] == "X" and e["name"] == name),
                  key=lambda span: span[:2])


REQUESTS = [(_prompt(9, 1), 12), (_prompt(13, 2), 5), (_prompt(6, 3), 9),
            (_prompt(11, 4), 7)]


# ---------------------------------------------------------------------------
# a request's account
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("on", [False, True], ids=["telemetry_off",
                                                   "telemetry_on"])
def test_account_sums_to_retire_less_submit(gpt, on):
    tel = telemetry.Telemetry(enabled=True) if on else False
    engine = _engine(gpt, telemetry=tel)
    futures = _staggered(engine, REQUESTS)
    for f in futures:
        assert set(f.account) == set(PHASES)
        assert all(v >= 0 for v in f.account.values()), f.account
        latency_us = (f.t_retire_ns - f.t_submit_ns) / 1e3
        assert sum(f.account.values()) * 1e3 == pytest.approx(
            latency_us, abs=50.0)
        # TTFT is the queue and the request's own prefill, nothing else
        assert (f.account["queue"] + f.account["prefill"]) * 1e3 == \
            pytest.approx((f.t_first_token_ns - f.t_submit_ns) / 1e3,
                          abs=50.0)
    if on:
        # the operator's tool reads the same thing off the ring: the
        # exported episodes leave under 1% of any request uncovered
        diag = attribute_request_events(tel.tracer.drain())
        assert diag["requests"] == len(REQUESTS)
        assert diag["conserved"] and diag["complete"]
        for r in diag["slowest_requests"]:
            assert r["buckets_ms"]["overhead"] <= 0.01 * r["e2e_ms"]
    engine.close()


def test_a_request_alone_is_never_stalled(gpt, counted):
    engine = _engine(gpt)
    (future,) = _staggered(engine, [(_prompt(9), 20)])
    assert future.account["stalled"] == 0
    assert future.account["replay"] == 0
    assert "hetu.serve.stall" not in counted
    assert "hetu.serve.prefill.sync" in counted
    assert engine.stats()["phase_ms"]["stalled"] == 0
    engine.close()


def test_an_admission_stalls_the_running_row(gpt):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, telemetry=tel)
    first, second = _staggered(engine, [(_prompt(9, 1), 12),
                                        (_prompt(13, 2), 5)])
    (stall,) = _ring(tel, "serve.stall")
    assert stall[2] == {"rows": 1, "admitted": 1}
    device = [d for d in _ring(tel, "serve.prefill.device")
              if stall[0] <= d[0] and d[1] <= stall[1] + 1.0]
    assert len(device) == 1             # the second request's prefill
    (d0, d1, _), = device
    # the first request stood still at least that long ...
    assert first.account["stalled"] * 1e3 >= (d1 - d0) - 1.0
    assert first.account["stalled"] * 1e3 == pytest.approx(
        stall[1] - stall[0], abs=50.0)
    # ... and the same interval is the second one's own prefill
    (_, (p0, p1)) = sorted((s, e) for s, e, a in _ring(tel, "serve_phase")
                           if a["phase"] == "prefill")
    assert p0 <= d0 + 1.0 and p1 >= d1 - 50.0
    assert second.account["prefill"] * 1e3 >= (d1 - d0) - 1.0
    # the first one's episode names who blocked it
    stalled = [a for _, _, a in _ring(tel, "serve_phase")
               if a["phase"] == "stalled"]
    assert stalled and all("blocked_by" in a for a in stalled)
    engine.close()


def test_the_stall_its_own_prompt_ran_in_is_not_a_requests(gpt):
    """The second request takes its first token INSIDE the stall it
    causes; what is left of that stall (its sample, the finish) is its
    own host time, and no other prompt comes while it runs."""
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, telemetry=tel)
    first, second, third = _staggered(
        engine, [(_prompt(9, 1), 12), (_prompt(13, 2), 5),
                 (_prompt(7, 3), 1)])
    assert first.account["stalled"] > 0
    assert second.account["stalled"] > 0        # behind the third
    stalls = _ring(tel, "serve.stall")
    assert len(stalls) == 2
    # ... and behind nothing else: not more than the third's stall
    assert second.account["stalled"] * 1e3 == pytest.approx(
        stalls[1][1] - stalls[1][0], abs=50.0)
    # the third retires inside the stall it caused, its one token done
    assert third.account["stalled"] == 0
    for f in (second, third):
        assert sum(f.account.values()) * 1e3 == pytest.approx(
            (f.t_retire_ns - f.t_submit_ns) / 1e3, abs=50.0)
    # the episodes say the same: nobody is blocked by itself
    requests = {a["request_id"] for _, _, a in _ring(tel, "serve_request")}
    for _, _, a in _ring(tel, "serve_phase"):
        if a["phase"] == "stalled":
            assert a["request_id"] not in a["blocked_by"].split(",")
    assert len(requests) == 3
    engine.close()


def test_chunked_prefill_is_one_stall_a_chunk(gpt, counted):
    engine = _engine(gpt, prefill_chunk=4)
    first = engine.submit(_prompt(5, 1), 14)
    for _ in range(3):
        engine.step()
    before = counted.count("hetu.serve.stall")
    assert before == 0                  # its own chunks met no row
    second = engine.submit(_prompt(15, 2), 3)    # four chunks of <= 4
    _drive(engine, [first, second])
    assert counted.count("hetu.serve.stall") == 4
    # the running row decoded between the chunks: it was stalled four
    # times, not for the whole prompt
    assert first.account["stalled"] > 0
    assert first.account["decode_device"] > 0
    assert sum(first.account.values()) * 1e3 == pytest.approx(
        (first.t_retire_ns - first.t_submit_ns) / 1e3, abs=50.0)
    engine.close()


def test_a_preempted_requests_lost_work_is_replay(gpt):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, num_blocks=7, reserve="lazy", telemetry=tel)
    futures = [engine.submit(_prompt(5, i), 6, temperature=0.8,
                             seed=40 + i) for i in range(4)]
    _drive(engine, futures)
    assert tel.counter_value("engine_preemptions") > 0, \
        "7-block lazy pool never preempted — the test lost its point"
    victims = [f for f in futures if f.account["replay"] > 0]
    assert victims
    for f in futures:
        assert sum(f.account.values()) * 1e3 == pytest.approx(
            (f.t_retire_ns - f.t_submit_ns) / 1e3, abs=50.0)
    # one replay episode a preemption, none for the others
    replays = [a["request_id"] for _, _, a in _ring(tel, "serve_phase")
               if a["phase"] == "replay"]
    preempts = {a["request_id"]: a["preempts"]
                for _, _, a in _ring(tel, "serve_request")}
    assert sorted(replays) == sorted(
        rid for rid, n in preempts.items() for _ in range(n))
    assert len(victims) == sum(1 for n in preempts.values() if n)
    engine.close()


def test_steps_dispatched_ahead_are_counted_once(gpt):
    """PR 33's tiling, on the account: programs overlap in time, a
    request's decode_device + decode_host + stalled do not — they are
    exactly what follows its first token."""
    engine = _engine(gpt)
    futures = _staggered(engine, REQUESTS)
    assert 0 < engine.decode_ahead_steps < engine.decode_steps
    for f in futures:
        a = f.account
        after_us = (a["decode_device"] + a["decode_host"]
                    + a["stalled"]) * 1e3
        assert after_us == pytest.approx(
            (f.t_retire_ns - f.t_first_token_ns) / 1e3, abs=1.0)
    # the engine's decode_device is one interval a sync, so no request
    # can hold more of it than the engine spent
    spent = engine.stats()["phase_ms"]["decode_device"]
    assert max(f.account["decode_device"] for f in futures) <= spent
    engine.close()


# ---------------------------------------------------------------------------
# the engine's account
# ---------------------------------------------------------------------------

def test_phase_ms_is_monotone_and_tiles_the_threads_life(gpt):
    t_made = time.perf_counter_ns()
    engine = _engine(gpt, start=True)
    seen = [engine.stats()["phase_ms"]]
    assert set(seen[0]) == set(ENGINE_PHASES) | {"stalled"}
    futures = []
    for prompt, new in REQUESTS:
        futures.append(engine.submit(prompt, new))
        time.sleep(0.01)
        seen.append(engine.stats()["phase_ms"])
    for f in futures:
        f.result(timeout=120)
        seen.append(engine.stats()["phase_ms"])
    time.sleep(0.25)                    # two slices of an idle wait
    seen.append(engine.stats()["phase_ms"])
    engine.close()
    t_closed = time.perf_counter_ns()
    seen.append(engine.stats()["phase_ms"])
    for a, b in zip(seen, seen[1:]):
        assert all(b[k] >= a[k] for k in a), (a, b)
    last = seen[-1]
    assert last["wait"] >= 200 and last["decode_device"] > 0
    assert last["prefill_host"] > 0 and last["stalled"] > 0
    life_ms = (t_closed - t_made) / 1e6
    tiled = sum(last[k] for k in ENGINE_PHASES)
    assert tiled == pytest.approx(life_ms, rel=0.01)
    # the overlay lies inside the phases it covers
    assert last["stalled"] <= last["prefill_host"] \
        + last["prefill_device"] + last["decode_host"]


def test_step_histogram_is_sound_through_a_stall(gpt):
    """``engine_step_ms`` (the scrape table's step time) observes every
    step's own length, the steps that hold a stall like the others."""
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, telemetry=tel)
    _staggered(engine, REQUESTS)
    assert _ring(tel, "serve.stall")
    hist = tel.metrics.histogram("engine_step_ms")
    steps_ms = [(e - s) / 1e3 for s, e, a in _ring(tel, "step")
                if a.get("subgraph") == "serving_engine"]
    assert hist.count == len(steps_ms)
    assert min(hist._ring) >= 0
    # an observation is the step span and the admission before it
    assert sum(steps_ms) <= hist.sum <= 1.25 * sum(steps_ms) + 5.0
    assert max(hist._ring) == pytest.approx(max(steps_ms), rel=0.25,
                                            abs=1.0)
    engine.close()


def test_an_idle_engine_waits_in_slices(gpt, counted):
    """A profile that starts inside a wait sees the next slice: each is
    a span of its own, at most 100 ms long."""
    engine = _engine(gpt, start=True)
    time.sleep(0.35)
    engine.close()
    assert 3 <= counted.count("hetu.serve.wait") <= 5


def test_request_account_in_stats(gpt):
    engine = _engine(gpt)
    assert engine.stats()["request_account"] == {"requests": 0}
    futures = _staggered(engine, REQUESTS)
    summary = engine.stats()["request_account"]
    assert summary["requests"] == len(REQUESTS)
    keys = set(PHASES) | {"total"}
    assert set(summary["p50_ms"]) == keys
    assert set(summary["p95_cohort_mean_ms"]) == keys
    # four requests: the cohort is the slowest one
    assert summary["p95_cohort_requests"] == 1
    slowest = max(futures, key=lambda f: f.t_retire_ns - f.t_submit_ns)
    assert summary["p95_cohort_mean_ms"]["total"] == pytest.approx(
        (slowest.t_retire_ns - slowest.t_submit_ns) / 1e6, abs=0.01)
    for phase in PHASES:
        assert summary["p95_cohort_mean_ms"][phase] == pytest.approx(
            slowest.account[phase], abs=0.01)
    engine.close()


# ---------------------------------------------------------------------------
# what telemetry-on exports, and what it must leave as it was
# ---------------------------------------------------------------------------

def test_episodes_are_merged_not_one_a_step(gpt):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, telemetry=tel)
    (future,) = _staggered(engine, [(_prompt(9), 20)])
    phases = [(a["phase"], s, e) for s, e, a in _ring(tel, "serve_phase")]
    assert [p for p, _, _ in phases] == ["queue", "prefill", "decode"]
    (request,) = _ring(tel, "serve_request")
    assert request[2]["tokens"] == 20
    for phase in PHASES:                # the account rides on the span
        assert request[2][f"{phase}_ms"] == pytest.approx(
            future.account[phase], abs=1e-3)
    # the prompt's token split is on the prefill episode
    prefill = next(a for _, _, a in _ring(tel, "serve_phase")
                   if a["phase"] == "prefill")
    assert (prefill["cached_tokens"], prefill["computed_tokens"]) == (0, 9)
    engine.close()


def test_the_three_histograms_read_what_the_stamps_say(gpt):
    """``serve_ttft_ms``, ``serve_tpot_ms`` and ``serve_queue_wait_ms``
    keep their meaning (three benchmark readers take their medians):
    for a fixed replay each observation is the Future's own stamps."""
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(gpt, telemetry=tel)
    futures = _staggered(engine, REQUESTS)
    new = [n for _, n in REQUESTS]
    want = {
        "serve_ttft_ms": [(f.t_first_token_ns - f.t_submit_ns) / 1e6
                          for f in futures],
        "serve_tpot_ms": [(f.t_retire_ns - f.t_first_token_ns) / 1e6
                          / (n - 1) for f, n in zip(futures, new)],
        "serve_queue_wait_ms": [f.account["queue"] for f in futures]}
    for name, values in want.items():
        hist = tel.metrics.histogram(name)
        assert hist.count == len(futures)
        assert sorted(hist._ring) == pytest.approx(sorted(values))
    engine.close()
