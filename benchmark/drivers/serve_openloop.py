"""Open-loop requests against ``ContinuousBatchingEngine``.

Traffic parameters (``traffic/<mix>.json``): ``engine`` (keyword
arguments of the engine), ``rate_per_s`` and the length distributions
(``harness/arrivals.py``), ``pre_seconds`` (the same arrivals run this
long before the window, so the window starts in steady state),
``drain_seconds`` (how long after the window unfinished requests may
still complete), ``check_prompts`` / ``check_new_tokens``,
``trace_seconds``.

One thread — this one — submits each request when it is due and never
waits for an answer; completion times are stamped by a callback on the
request's Future. A request is timed from when it was DUE, so a stall
is charged to every request it delays; how late the generator itself
ran is logged.

``--trace 0`` (engine telemetry off, no profiler, no poller):

* ``serve_request_p95_ms`` — 95th percentile over the requests due in
  the window that completed, of completion - due time. A request that
  raised, or had not completed when the drain ended, counts as failed
  and has no latency.
* ``serve_out_tokens_per_s`` — output tokens of the requests (due at
  any time) that completed inside the window, over its length. Below
  the knee it is a constant of the schedule, so it is logged in every
  run and reported only by a cell whose ``BENCHMARK.json`` entry lists
  it (a cell above the knee).

``--trace 1``: the engine's telemetry on, a poller sampling
``engine.stats()``, the engine's step phases wrapped in ``bench.*``
annotations from here, and ``trace_seconds`` of the same arrivals under
``jax.profiler`` after the pre-window.

Warm-up is by enumeration, not replay: every (batch, prompt) prefill
bucket and (batch, context) decode bucket this traffic can reach is
dispatched once on scratch slots through the engine's own
``_dispatch`` (so its ``jit_compiles`` counts them), together with the
small eager gathers the engine runs on their logits. The engine offers
no public warm-up; this reaches into ``_dispatch``, ``_prefill_fn``,
``_step_fn`` and ``cache.pools`` (PERF.md, Open questions).

``correct``: for ``check_prompts`` seeded prompts, after the drain,
EVERY one of the ``check_new_tokens`` tokens the engine generates must
score within the reference's tolerance of the best logit at its
position under the family's plain float32 forward, teacher-forced on
the prompt and the engine's own earlier tokens (so the prefill AND the
paged decode path are held to the reference; logits, not token
equality: random weights flip the argmax on rounding); every completed
output has the asked length and lies in the vocabulary;
``jit_compiles`` does not rise inside the window; no request failed.
"""
import threading
import time

import numpy as np

from benchmark.harness import arrivals, compiles, device, stats
from benchmark.harness.outcome import Outcome
from benchmark.trace import xplane


def _reachable(ladder, lo, hi):
    """Entries of a bucket ladder that values in [lo, hi] snap to."""
    from hetu_tpu.serving.session import next_bucket
    first, last = next_bucket(lo, ladder), next_bucket(hi, ladder)
    return [b for b in ladder if first <= b <= last]


def warm(engine, traffic):
    """Dispatch every reachable bucket once, on the scratch block."""
    import jax.numpy as jnp
    p, o = traffic["prompt_len"], traffic["output_len"]
    p_lo, p_hi = (p["min"], p["max"]) if "min" in p else (p["value"],) * 2
    o_hi = o["max"] if "max" in o else o["value"]
    prompt_buckets = _reachable(engine.prompt_buckets, p_lo, p_hi)
    ctx_buckets = _reachable(engine.ctx_buckets, p_lo + 1, p_hi + o_hi)
    for bb in engine.batch_buckets:
        group_sizes = range(bb // 2 + 1, bb + 1)
        for pb in prompt_buckets:
            zeros = jnp.zeros((bb, pb), jnp.int32)
            logits, engine.cache.pools = engine._dispatch(
                ("prefill", bb, pb), engine._prefill_fn, engine.params,
                engine.cache.pools, zeros, zeros)
            for n in group_sizes:   # the engine's last-row gather
                np.asarray(logits[jnp.arange(n),
                                  jnp.asarray([pb - 1] * n)])
            del logits
        for cb in ctx_buckets:
            row = jnp.zeros(bb, jnp.int32)
            logits, engine.cache.pools = engine._dispatch(
                ("decode", bb, cb), engine._step_fn, engine.params,
                engine.cache.pools, row, row,
                jnp.zeros((bb, cb), jnp.int32), row)
            for n in group_sizes:
                np.asarray(logits[:n])
    return {"prompt_buckets": prompt_buckets, "ctx_buckets": ctx_buckets,
            "batch_buckets": list(engine.batch_buckets)}


class Poller(threading.Thread):
    """Samples ``engine.stats()`` every ``period`` seconds."""

    def __init__(self, engine, period=0.1):
        super().__init__(daemon=True, name="bench-poller")
        self.engine, self.period = engine, period
        self.samples = []       # (time, waiting, running, kv_blocks_used)
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(self.period):
            s = self.engine.stats()
            self.samples.append((time.perf_counter(), s["waiting"],
                                 s["running"], s["kv_blocks_used"]))

    def stop(self):
        self._halt.set()
        self.join()


def play(engine, requests, annotate=None):
    """Submit each request when it is due. Returns (t0, submit times,
    completion times or None, results or exceptions)."""
    import contextlib
    span = annotate or (lambda name: contextlib.nullcontext())
    n = len(requests)
    done_t, results, submit_t = [None] * n, [None] * n, [None] * n

    def on_done(i, future):
        done_t[i] = time.perf_counter()
        results[i] = future.exception() or future.result()

    t0 = time.perf_counter()
    for i, r in enumerate(requests):
        with span("bench.wait_arrival"):
            delay = t0 + r.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        submit_t[i] = time.perf_counter()
        with span("bench.submit"):
            future = engine.submit(r.prompt, r.max_new)
        future.add_done_callback(lambda f, i=i: on_done(i, f))
    return t0, submit_t, done_t, results


def drain(done_lists, seconds):
    """Wait until every completion time in every list is set, at most
    ``seconds``. The lists are the ones the Futures' callbacks write to
    (a concatenation would be a snapshot that never fills)."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline and \
            any(t is None for done_t in done_lists for t in done_t):
        time.sleep(0.02)


def summarize(requests, t0, submit_t, done_t, results, lo, hi,
              vocab_size):
    """Numbers of the window [lo, hi) (seconds after ``t0``)."""
    n = len(requests)
    due = [i for i in range(n) if lo <= requests[i].due_s < hi]
    ok = [i for i in due if done_t[i] is not None
          and isinstance(results[i], np.ndarray)]
    latencies = [(done_t[i] - t0 - requests[i].due_s) * 1e3 for i in ok]
    completed_in = [i for i in range(n) if done_t[i] is not None
                    and isinstance(results[i], np.ndarray)
                    and lo <= done_t[i] - t0 < hi]
    well_formed = all(
        results[i].shape == (requests[i].max_new,)
        and int(results[i].min()) >= 0
        and int(results[i].max()) < vocab_size
        for i in range(n) if isinstance(results[i], np.ndarray))
    late = [(submit_t[i] - t0 - requests[i].due_s) * 1e3 for i in due]
    return {"attempted": len(due), "failed": len(due) - len(ok),
            "latencies_ms": latencies,
            "out_tokens": sum(requests[i].max_new for i in completed_in),
            "completed_in_window": len(completed_in),
            "completed_of_due": len([i for i in ok
                                     if done_t[i] - t0 < hi]),
            "well_formed": well_formed,
            "generator_late_ms_p50": stats.median(late) if late else 0.0,
            "generator_late_ms_max": max(late) if late else 0.0}


def check_tokens(cell, engine, weights, requests, traffic, log):
    """Every token the engine generates for a sample of prompts against
    the plain reference, teacher-forced on the engine's own output;
    outside any window."""
    family = cell.family()
    new = traffic["check_new_tokens"]
    picks = requests[:traffic["check_prompts"]]
    futures = [engine.submit(r.prompt, new) for r in picks]
    ok = True
    pad_to = max(traffic["prompt_len"].get("max", 0),
                 max(len(r.prompt) for r in picks)) + new
    for r, f in zip(picks, futures):
        out = np.asarray(f.result(timeout=600))
        p = len(r.prompt)
        good = out.shape == (new,)
        gaps = []
        if good:
            # row k: the logits after prompt + out[:k], which out[k]
            # was picked from
            ref = family.engine_reference_logits(
                cell.config, weights, np.concatenate([r.prompt, out[:-1]]),
                np.arange(p - 1, p - 1 + new), pad_to)
            gaps = [float(ref[k].max() - ref[k, int(out[k])])
                    for k in range(new)]
            good = max(gaps) <= family.LOGIT_TOLERANCE
        log({"check": "generated_tokens_vs_reference", "prompt_len": p,
             "tokens": out.tolist(), "logit_gaps": gaps,
             "tolerance": family.LOGIT_TOLERANCE, "ok": good})
        ok = ok and good
    return ok


def _annotate_engine(engine):
    """Traced runs only: wrap the scheduler's phases in ``bench.*``
    host annotations from outside (the program has none yet)."""
    from jax.profiler import TraceAnnotation

    def wrap(method, name):
        inner = getattr(engine, method)

        def wrapped(*a, **kw):
            with TraceAnnotation(name):
                return inner(*a, **kw)
        setattr(engine, method, wrapped)

    wrap("step", "bench.engine.step")
    wrap("_prefill_admitted", "bench.engine.prefill")
    wrap("_decode_once", "bench.engine.decode")


def build(cell, opts):
    """(engine, weights, telemetry): everything before traffic."""
    phases = compiles.Phases(opts.process_start)
    traffic = cell.traffic
    telemetry = None
    if opts.trace:
        from hetu_tpu.telemetry import Telemetry
        telemetry = Telemetry(enabled=True)
    kw = dict(traffic["engine"], telemetry=telemetry or False)
    engine, weights = cell.family().build_engine(
        cell.config, kw, opts.seed)
    phases.mark("imports_device_weights_engine")
    if opts.trace:
        _annotate_engine(engine)
    report = warm(engine, traffic)
    phases.mark("warm_every_bucket")
    report["setup_phases_s"] = phases.rows
    report["jit_compiles_after_warmup"] = engine.jit_compiles
    report["kv_blocks"] = engine.cache.num_blocks
    report["kv_pool_bytes"] = engine.cache.hbm_bytes()
    opts.log({"warmup": report})
    return engine, weights, telemetry


def run(cell, opts):
    import jax
    from jax.profiler import TraceAnnotation

    traffic, config = cell.traffic, cell.config
    engine, weights, telemetry = build(cell, opts)
    try:
        pre = traffic["pre_seconds"]
        seconds = traffic["trace_seconds"] if opts.trace else opts.seconds
        if opts.rehearse:
            pre, seconds = min(pre, 1.0), min(seconds, 2.0)
        requests = arrivals.schedule(traffic, opts.seed, pre + seconds,
                                     config["vocab_size"])
        poller = Poller(engine) if opts.trace else None
        if poller:
            poller.start()

        compiles_ = {"before": engine.jit_compiles}
        # the pre-window and the window are one schedule: split it so
        # the profiler starts between them
        head = [r for r in requests if r.due_s < pre]
        tail = [arrivals.Request(r.due_s - pre, r.prompt, r.max_new)
                for r in requests if r.due_s >= pre]
        annotate = TraceAnnotation if opts.trace else None
        h_t0, h_sub, h_done, h_res = play(engine, head, annotate)
        time.sleep(max(0.0, h_t0 + pre - time.perf_counter()))
        compiles_["at_window_start"] = engine.jit_compiles
        backend_before = opts.compiles.backend_compiles
        if opts.trace:
            xplane.start(opts.trace_dir)
        setup_s = time.perf_counter() - opts.process_start
        with TraceAnnotation("bench.window"):
            t_t0, t_sub, t_done, t_res = play(engine, tail, annotate)
            time.sleep(max(0.0, t_t0 + seconds - time.perf_counter()))
        if opts.trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            opts.log({"profile_written_s": time.perf_counter() - t_stop})
        committed = device.committed_bytes(opts.devices)
        compiles_["at_window_end"] = engine.jit_compiles
        compiles_["backend_in_window"] = \
            opts.compiles.backend_compiles - backend_before
        drain([h_done, t_done], traffic["drain_seconds"])
        if poller:
            poller.stop()

        # one timeline: the head's requests on the tail's clock
        shift = h_t0 - t_t0
        merged = [arrivals.Request(r.due_s + shift, r.prompt, r.max_new)
                  for r in head] + tail
        summary = summarize(merged, t_t0, h_sub + t_sub, h_done + t_done,
                            h_res + t_res, 0.0, seconds,
                            config["vocab_size"])
        engine_p50 = {}
        if telemetry is not None:   # before the check's requests add theirs
            for name in ("serve_ttft_ms", "serve_tpot_ms",
                         "serve_queue_wait_ms"):
                h = telemetry.metrics.histogram(name)
                engine_p50[name + "_p50"] = \
                    h.percentile(50) if h.count else None
        tokens_ok = check_tokens(cell, engine, weights, requests, traffic,
                                 opts.log)
    finally:
        engine.close()

    latencies = summary.pop("latencies_ms")
    p95 = stats.percentile(latencies, 95) if latencies else float("nan")
    out_rate = summary["out_tokens"] / seconds
    opts.log({"window": dict(
        summary, seconds=seconds, rate_per_s=traffic["rate_per_s"],
        latency_ms_p50=stats.median(latencies) if latencies else None,
        latency_ms_p95=p95, out_tokens_per_s=out_rate,
        jit_compiles=compiles_)})
    facts = {"driver": "serve_openloop", "config": config,
             "traffic": traffic, "window_s": seconds,
             "device_kind": opts.devices[0].device_kind}
    if poller:
        facts["kv_blocks"] = engine.cache.num_blocks
        facts["kv_blocks_used_peak"] = max(
            (s[3] for s in poller.samples), default=None)
    facts.update(engine_p50)
    correct = (tokens_ok and summary["well_formed"]
               and summary["failed"] == 0 and bool(latencies)
               and compiles_["at_window_end"]
               == compiles_["at_window_start"])
    return Outcome(
        correct=correct, attempted=summary["attempted"],
        failed=summary["failed"], setup_s=setup_s,
        end_to_end={"serve_request_p95_ms": p95,
                    "serve_out_tokens_per_s": out_rate},
        facts=facts, traced=opts.trace, committed_bytes=committed)
