"""Program spans on the device profiler's clock (ISSUE 24).

``Telemetry.span`` has two sinks: the ring (when enabled) and, always,
a ``jax.profiler.TraceAnnotation`` named ``hetu.<name>``. These tests
run tiny programs under ``hetu_tpu.profiler.trace`` on the CPU backend
(Python tracer off) and read the profile back with nothing but JAX:
the scheduler's leaf spans tile its thread, the executor's run leaves
its three, programs carry stable names, the first-token stamp is
unconditional, and the number of spans a step opens with telemetry off
is pinned by count (conftest's counting stand-in for the annotation),
never by a timing.
"""
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import profiler, telemetry
from hetu_tpu.executor import Executor
from hetu_tpu.serving import ContinuousBatchingEngine
from hetu_tpu.telemetry import check, tracer

from gpt_reference import VOCAB, gpt_session

SEQ = 64

LEAVES = ("serve.wait", "serve.admit", "serve.prefill.build",
          "serve.prefill.device", "serve.prefill.sample",
          "serve.decode.build", "serve.decode.ahead",
          "serve.decode.device", "serve.decode.sample", "serve.finish")


def _gpt_session(seed=0):
    return gpt_session(seed=seed, seq=SEQ)


def _engine(**kw):
    cfg, sess = _gpt_session()
    kw.setdefault("telemetry", False)
    return ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=64, block_size=4, max_batch_size=4, **kw)


def _prompts(rng, lengths=(5, 9, 12, 20)):
    return [rng.randint(0, VOCAB, size=n) for n in lengths]


def _mlp(prefix):
    x = ht.Variable(f"{prefix}_x", trainable=False)
    y_ = ht.Variable(f"{prefix}_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name=f"{prefix}_w1")
    w2 = ht.init.xavier_normal((12, 4), name=f"{prefix}_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feeds = {x: rng.randn(8, 16).astype("f"),
             y_: np.eye(4, dtype="f")[rng.randint(0, 4, 8)]}
    return loss, train, feeds


def _host_events(trace_dir):
    """``[(thread line index, name, start_ns, end_ns, stats)]`` of the
    profile's host planes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, f"no profile under {trace_dir}"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((i, ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns), ev))
    return out


def _union_ns(intervals):
    total, hi = 0, None
    for s, e in sorted(intervals):
        if hi is None or s > hi:
            total, hi = total + (e - s), e
        elif e > hi:
            total, hi = total + (e - hi), e
    return total


# ---------------------------------------------------------------------------
# the scheduler thread is tiled
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kw", [
    {}, {"prefix_cache": True, "prefill_chunk": 8}],
    ids=["plain", "chunked_prefix"])
def test_leaf_spans_tile_the_scheduler_thread(tmp_path, engine_kw):
    """Every profile: one thread wrote the leaves, every leaf is seen,
    no two overlap. The gaps between them are small — held on the BEST
    of up to three profiles of one engine: a gap is a few lines of
    Python between two spans, and under six test workers a descheduled
    scheduler thread stretches one by milliseconds of a window that is
    tens of milliseconds long (the standing tree's one failure, PR 47),
    which says nothing of the tiling."""
    rng = np.random.RandomState(0)
    leaf_names = {"hetu." + n for n in LEAVES}
    best = 0.0
    with _engine(**engine_kw) as engine:
        assert not engine.telemetry.enabled
        for f in [engine.submit(p, 6) for p in _prompts(rng)]:
            f.result(timeout=120)       # compile outside the profile
        for attempt in range(3):
            trace_dir = str(tmp_path / f"profile{attempt}")
            with profiler.trace(trace_dir):
                for _ in range(3):
                    for f in [engine.submit(p, 6) for p in _prompts(rng)]:
                        f.result(timeout=120)
                    time.sleep(0.02)        # the scheduler goes to wait
            events = _host_events(trace_dir)
            leaves = [(line, s, e) for line, n, s, e, _ in events
                      if n in leaf_names]
            seen = {n for _, n, _, _, _ in events if n in leaf_names}
            assert seen == leaf_names, leaf_names - seen
            # one thread wrote them all: the scheduler's
            assert len({line for line, _, _ in leaves}) == 1
            spans = [(s, e) for _, s, e in leaves]
            lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
            covered = _union_ns(spans)
            # leaves do not overlap each other: the union loses nothing
            assert covered == sum(e - s for s, e in spans)
            best = max(best, covered / (hi - lo))
            if best >= 0.97:
                break
    assert best >= 0.97, best
    # attrs are the few integers: width and the buckets
    decode = [ev for _, n, _, _, ev in events
              if n == "hetu.serve.decode.device"]
    stats = dict(decode[0].stats)
    assert set(stats) == {"width", "batch_bucket", "ctx_bucket"}
    assert all(isinstance(v, int) for v in stats.values())
    # the Python tracer is off: no event of a Python frame
    assert not any(n.startswith("$") for _, n, _, _, _ in events)


def test_step_by_hand_emits_the_same_spans(counted):
    """``engine.step()`` driven by the caller (tests, the doctor) opens
    the leaves the scheduler thread opens, in order."""
    rng = np.random.RandomState(1)
    engine = _engine(start=False)
    future = engine.submit(_prompts(rng)[0], 2)
    engine.step()
    assert future.done()
    assert [n for n in counted if n != "hetu.jit_compile"] == [
        "hetu.serve.admit", "hetu.step", "hetu.serve.prefill.build",
        "hetu.serve.prefill.build", "hetu.serve.prefill.device",
        "hetu.serve.prefill.sync",      # the wait inside .device
        "hetu.serve.prefill.sample", "hetu.serve.finish",
        "hetu.serve.decode.build", "hetu.serve.decode.device",
        "hetu.serve.decode.sample", "hetu.serve.finish"]
    engine.close()


# ---------------------------------------------------------------------------
# the executor's run
# ---------------------------------------------------------------------------

def test_executor_without_telemetry_leaves_its_spans(tmp_path):
    loss, train, feeds = _mlp("ps1")
    exe = Executor([loss, train])
    assert not exe.config.telemetry.enabled
    with profiler.trace(str(tmp_path)):
        for _ in range(3):
            exe.run(feed_dict=feeds)
    events = _host_events(str(tmp_path))
    count = {}
    for _, n, _, _, _ in events:
        count[n] = count.get(n, 0) + 1
    for name in ("hetu.step", "hetu.executor.ingest",
                 "hetu.device_dispatch", "hetu.executor.outputs"):
        assert count.get(name) == 3, (name, count.get(name))
    # the compile shows with its shape key: which step recompiled
    compiles = [ev for _, n, _, _, ev in events if n == "hetu.jit_compile"]
    assert len(compiles) == 1
    stats = dict(compiles[0].stats)
    assert stats["subgraph"] == "default" and "shape_key" in stats
    # ingest, dispatch and outputs lie inside the step, in that order
    for line, n, s, e, _ in events:
        if n != "hetu.step":
            continue
        inner = sorted((s2, n2) for l2, n2, s2, e2, _ in events
                       if l2 == line and s <= s2 and e2 <= e
                       and n2 in ("hetu.executor.ingest",
                                  "hetu.device_dispatch",
                                  "hetu.executor.outputs"))
        assert [n2 for _, n2 in inner] == [
            "hetu.executor.ingest", "hetu.device_dispatch",
            "hetu.executor.outputs"]
    exe.close()


def test_enabled_ring_and_profile_agree(tmp_path):
    """Telemetry on: one ``span()`` feeds both sinks, and the ring's
    export passes the span-attr schema with the new names in it."""
    tel = telemetry.Telemetry(enabled=True)
    rng = np.random.RandomState(2)
    with _engine(telemetry=tel) as engine:
        with profiler.trace(str(tmp_path / "profile")):
            for f in [engine.submit(p, 4) for p in _prompts(rng)]:
                f.result(timeout=120)
    ring = [e["name"] for e in tel.tracer.drain() if e["ph"] == "X"]
    profile = [n for _, n, _, _, _ in _host_events(str(tmp_path / "profile"))
               if n.startswith("hetu.serve.")]
    for leaf in LEAVES:
        if leaf == "serve.wait":
            continue        # open until close(): after the profile
        assert ring.count(leaf) == profile.count("hetu." + leaf) > 0, leaf
    path = tel.tracer.export(str(tmp_path / "trace_rank0.json"))
    n, errors = check.validate(path)
    assert not errors, errors
    for name in LEAVES + ("executor.ingest", "executor.outputs"):
        assert name in check.SPAN_SCHEMA


# ---------------------------------------------------------------------------
# stable program names
# ---------------------------------------------------------------------------

def test_engine_programs_carry_stable_names():
    import jax.numpy as jnp
    engine = _engine(start=False, prefix_cache=True)
    row = jnp.zeros(1, jnp.int32)
    grid = jnp.zeros((1, 4), jnp.int32)
    lowered = {
        "jit_hetu_paged_prefill": engine._prefill_fn.lower(
            engine.params, engine.cache.pools, grid, grid),
        "jit_hetu_paged_decode": engine._step_fn.lower(
            engine.params, engine.cache.pools, row, row, grid, row),
        "jit_hetu_paged_decode_logits": engine._logits_step_fn.lower(
            engine.params, engine.cache.pools, row, row, grid, row),
        "jit_hetu_paged_suffix_prefill": engine._sprefill_fn.lower(
            engine.params, engine.cache.pools, grid, row, grid, grid),
    }
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text()[:200], name
    engine.close()


def test_executor_steps_are_named_by_subgraph():
    loss, train, feeds = _mlp("ps2")
    exe = Executor({"default": [loss, train], "validate": [loss]})
    for sub_name in ("default", "validate"):
        exe.run(sub_name, feed_dict=feeds)
        sub = exe.subexecutors[sub_name]
        (jitted,) = sub.compiled.values()
        feed_map = {n: sub._ingest(v) for n, v in feeds.items()}
        text = jitted.lower(*sub.trace_args(exe, feed_map)).as_text()
        assert f"module @jit_hetu_step_{sub_name} " in text[:200]
    exe.close()


# ---------------------------------------------------------------------------
# the first-token stamp does not depend on telemetry
# ---------------------------------------------------------------------------

def test_ttft_slo_trips_and_future_carries_stamps_with_telemetry_off():
    engine = _engine(start=False, slo_ttft_p99_ms=50.0)
    assert not engine.telemetry.enabled
    rng = np.random.RandomState(3)
    future = engine.submit(_prompts(rng)[1], 3)
    assert future.t_submit_ns > 0 and future.t_first_token_ns is None
    time.sleep(0.08)                    # a slow first token
    while not future.done():
        engine.step()
    assert future.t_first_token_ns - future.t_submit_ns >= 80e6
    healthy, reason = engine.health()
    assert not healthy and "serve_ttft_ms" in reason, reason
    # a prompt answer does not trip it
    quick = _engine(start=False, slo_ttft_p99_ms=60e3)
    f2 = quick.submit(_prompts(rng)[1], 3)
    while not f2.done():
        quick.step()
    assert f2.t_first_token_ns > f2.t_submit_ns
    assert quick.health()[0]
    engine.close()
    quick.close()


# ---------------------------------------------------------------------------
# what the disabled path costs, by count
# ---------------------------------------------------------------------------

def test_span_entries_per_decode_step_are_pinned(counted):
    rng = np.random.RandomState(4)
    engine = _engine(start=False)
    futures = [engine.submit(p, 8) for p in _prompts(rng)]
    engine.step()                       # admission, prefill, one decode
    engine.step()                       # compile-free from here
    del counted[:]
    engine.step()
    # the step is dispatched ahead of the read of the one in flight
    assert counted == [
        "hetu.serve.admit", "hetu.step", "hetu.serve.decode.build",
        "hetu.serve.decode.ahead", "hetu.device_dispatch",
        "hetu.serve.decode.device", "hetu.serve.decode.sample",
        "hetu.serve.finish"]
    assert not any(f.done() for f in futures)
    engine.close()


def test_span_entries_per_training_step_are_pinned(counted):
    loss, train, feeds = _mlp("ps3")
    exe = Executor([loss, train])
    exe.run(feed_dict=feeds)
    del counted[:]
    exe.run(feed_dict=feeds)
    assert counted == ["hetu.step", "hetu.executor.ingest",
                       "hetu.device_dispatch", "hetu.executor.outputs"]
    exe.close()


def test_disabled_span_is_the_bare_annotation(counted):
    """``NULL.span`` is the profiler annotation itself, no wrapper
    around it; the ring stays empty."""
    span = telemetry.NULL.span("x", width=3)
    assert type(span) is tracer._trace_annotation and span.name == "hetu.x"
    assert telemetry.NULL.tracer is None


def test_no_profiler_sink_in_a_process_without_jax(monkeypatch):
    """The telemetry package never imports jax itself: where nothing
    else did (a PS server child), a span is the shared no-op."""
    monkeypatch.setattr(tracer, "_trace_annotation", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert telemetry.NULL.span("a") is tracer.NULL_SPAN
    assert "jax" not in sys.modules
    tel = telemetry.Telemetry(enabled=True)
    with tel.span("ring_only", n=1):
        pass
    assert [e["name"] for e in tel.tracer.drain()
            if e["ph"] == "X"] == ["ring_only"]


def test_spans_from_two_threads_keep_their_lines(tmp_path):
    """Each thread's annotations land on its own line of the host
    plane, so a reader can tell the scheduler's from a caller's."""
    tel = telemetry.NULL

    def worker():
        with tel.span("executor.ingest"):
            time.sleep(0.002)

    with profiler.trace(str(tmp_path)):
        t = threading.Thread(target=worker)
        t.start()
        with tel.span("serve.wait"):
            t.join(timeout=30)
        assert not t.is_alive()
    lines = {n: line for line, n, _, _, _ in _host_events(str(tmp_path))
             if n in ("hetu.executor.ingest", "hetu.serve.wait")}
    assert len(lines) == 2
    assert lines["hetu.executor.ingest"] != lines["hetu.serve.wait"]
