"""Open-loop requests against ``ContinuousBatchingEngine`` through its
public surface alone: ``submit``, ``warm_up``, ``stats``, ``close``.

The same measurement as ``drivers/serve_openloop.py`` (whose ``play``,
``drain``, ``summarize`` and ``Poller`` it uses, and whose docstring
says what the window, the pre-window, the drain and the two metrics
are), for a model that driver cannot warm: its ``warm()`` reaches into
the engine with GPT's argument lists and gathers a last row from
``[batch, prompt, vocabulary]`` logits, which a long-prompt model never
makes. Here the engine warms itself (``engine.warm_up`` over the
traffic file's prompt and output ranges), and nothing of the engine's
is freed, wrapped or replaced from outside.

What differs besides:

* ``correct`` is the family's ``check_generated`` where it has one (the
  ``sarvam_mla`` family's: the reference forced onto the routing the
  engine reports beside its tokens, ``Future.token_records``), else
  every generated token against ``engine_reference_logits`` as the
  older driver does;
* the program's own ``hetu.serve.*`` spans and ``jit_hetu_paged_*``
  program names are in a traced run's profile; the older driver's
  ``bench.engine.*`` annotations are put on too, for the one accepted
  reader that still wants them (``model.decode_device_ms``);
* the counters the model's programs return (``engine.stats()``) are
  logged over the window, and in a traced run each program's own counts
  and host times (``engine.program_log``) go to
  the per-layer readers as ``facts["programs"]`` with the window's
  start on the same clock, so that a reader can hold COUNTED work
  against the device time of exactly the programs it counted.
"""
import time

import numpy as np

from benchmark.drivers.serve_openloop import (Poller, _annotate_engine,
                                              check_tokens, drain, play,
                                              summarize)
from benchmark.harness import arrivals, compiles, device, stats
from benchmark.harness.outcome import Outcome
from benchmark.trace import xplane


def length_range(spec):
    return (spec["min"], spec["max"]) if "min" in spec \
        else (spec["value"],) * 2


def build(cell, opts):
    """(engine, weights, telemetry): everything before traffic."""
    phases = compiles.Phases(opts.process_start)
    traffic = cell.traffic
    telemetry = None
    if opts.trace:
        from hetu_tpu.telemetry import Telemetry
        telemetry = Telemetry(enabled=True)
    kw = dict(traffic["engine"], telemetry=telemetry or False)
    engine, weights = cell.family().build_engine(cell.config, kw, opts.seed)
    phases.mark("imports_device_weights_engine")
    ran = engine.warm_up(length_range(traffic["prompt_len"]),
                         length_range(traffic["output_len"])[1])
    phases.mark("warm_every_bucket")
    if opts.trace:
        _annotate_engine(engine)
    opts.log({"warmup": {
        "programs": {kind: len(keys) for kind, keys in ran.items()},
        "prefill_buckets": ran["prefill"],
        "prefill_token_cap": engine.prefill_token_cap,
        "setup_phases_s": phases.rows,
        "jit_compiles_after_warmup": engine.jit_compiles,
        "kv_blocks": engine.cache.num_blocks,
        "kv_pool_bytes": engine.cache.hbm_bytes()}})
    return engine, weights, telemetry


def model_counters(engine):
    """The model's device-side counters out of ``engine.stats()``."""
    return {k: v for k, v in engine.stats().items()
            if k.startswith(("prefill_", "decode_"))
            and isinstance(v, (int, list)) and k != "prefill_chunk"}


def counters_between(before, after):
    return {k: ([a - b for a, b in zip(after[k], before[k])]
                if isinstance(after[k], list) else after[k] - before[k])
            for k in after}


def programs_between(engine, t0_ns, t1_ns):
    """The engine's own records of the programs that ended in ``[t0,
    t1]`` (perf_counter ns; ``engine.program_log``, kept with telemetry
    on): ``[{"kind", "t0_ns", "t1_ns", <its counters>}]``. Absent on a
    program from before the log: nothing to pair, the rooflines are
    left out."""
    # list() first: the scheduler thread appends while this one reads
    return [dict(r) for r in list(getattr(engine, "program_log", ()))
            if t0_ns <= r["t1_ns"] <= t1_ns]


def check(cell, engine, weights, requests, traffic, log):
    family = cell.family()
    if not hasattr(family, "check_generated"):
        return check_tokens(cell, engine, weights, requests, traffic, log)
    new = traffic["check_new_tokens"]
    picks = requests[:traffic["check_prompts"]]
    futures = [engine.submit(r.prompt, new) for r in picks]
    outs = [np.asarray(f.result(timeout=600)) for f in futures]
    if any(out.shape != (new,) for out in outs):
        return False
    return family.check_generated(
        cell.config, weights, [r.prompt for r in picks], outs,
        [f.token_records for f in futures], log)


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
        counted_before = model_counters(engine)
        window_perf_ns = time.perf_counter_ns()
        with TraceAnnotation("bench.window"):
            t_t0, t_sub, t_done, t_res = play(engine, tail, annotate)
            time.sleep(max(0.0, t_t0 + seconds - time.perf_counter()))
        window_end_perf_ns = time.perf_counter_ns()
        counted = counters_between(counted_before, model_counters(engine))
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

        shift = h_t0 - t_t0
        merged = [arrivals.Request(r.due_s + shift, r.prompt, r.max_new)
                  for r in head] + tail
        summary = summarize(merged, t_t0, h_sub + t_sub, h_done + t_done,
                            h_res + t_res, 0.0, seconds,
                            config["vocab_size"])
        engine_p50, programs = {}, None
        if telemetry is not None:   # before the check's requests add theirs
            for name in ("serve_ttft_ms", "serve_tpot_ms",
                         "serve_queue_wait_ms"):
                h = telemetry.metrics.histogram(name)
                engine_p50[name + "_p50"] = \
                    h.percentile(50) if h.count else None
            programs = programs_between(engine, window_perf_ns,
                                        window_end_perf_ns)
        tokens_ok = check(cell, engine, weights, requests, traffic,
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
    routed = {kind: counted[f"{kind}_moe_routed_rows"]
              / max(1, counted[f"{kind}_moe_tokens"])
              / config["num_experts_per_tok"]
              for kind in ("prefill", "decode")
              if f"{kind}_moe_tokens" in counted}
    opts.log({"model_counters_in_window": counted,
              "moe_routed_rows_per_pick": routed,
              "programs_recorded": None if programs is None
              else len(programs),
              # dispatch to the end of the host sync, by program kind
              "program_host_ms_p50": {kind: stats.median(
                  [(r["t1_ns"] - r["t0_ns"]) / 1e6 for r in programs
                   if r["kind"] == kind])
                  for kind in {r["kind"] for r in programs or ()}}})
    facts = {"driver": "serve_openloop_public", "config": config,
             "traffic": traffic, "window_s": seconds,
             "device_kind": opts.devices[0].device_kind,
             "model_counters": counted}
    if poller:
        facts["kv_blocks"] = engine.cache.num_blocks
        facts["kv_blocks_used_peak"] = max(
            (s[3] for s in poller.samples), default=None)
    if programs is not None:
        facts["programs"] = programs
        facts["window_perf_ns"] = window_perf_ns
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
