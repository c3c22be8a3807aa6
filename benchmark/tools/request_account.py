"""Where the latency of a serving cell's requests goes, by phase, from
the engine's always-on accounts: ONE process, the set-up of
``drivers/serve_openloop_public.py`` (the engine warms itself through
``warm_up``; it builds either serving family), one window of the cell's
own arrivals.

    python benchmark/tools/request_account.py --workload <serve cell> --seconds 30 --seed 7
    ... --workload tiny-gpt2-serve --rehearse --seconds 2   # tiny files, any backend

For the requests DUE in the window (after ``pre_seconds`` of the same
arrivals, drained afterwards) it prints, for all of them and for the
p95 COHORT (those whose latency is at or above the 95th percentile:
what ``serve_request_p95_ms`` is made of), the mean milliseconds of
each phase of ``Future.account`` — ``queue``, ``prefill``, ``stalled``
(behind other requests' prefills), ``decode_device`` (the host blocked
on a decode program), ``decode_host``, ``replay`` — beside the latency
the benchmark measures (completion - due time; the account runs from
the submit, so the two differ by how late the generator was). It also
prints the medians of TTFT and TPOT from the Futures' stamps, and the
engine's own ``stats()["phase_ms"]`` over the window: what its
scheduler thread did with it. One JSON line each, then the two tables
as text. The engine's telemetry is off, as in a ``--trace 0`` run.
Needs the TPU like a run.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark.drivers import serve_openloop as base    # noqa: E402
from benchmark.drivers import serve_openloop_public as drv  # noqa: E402
from benchmark.harness import (arrivals, compiles, device,  # noqa: E402
                               spec, stats)
from benchmark.harness.outcome import Options           # noqa: E402


class KeepFutures:
    """The engine as ``base.play`` drives it, keeping the Futures: their
    stamps and accounts are what this tool reads."""

    def __init__(self, engine):
        self.engine, self.futures = engine, []

    def submit(self, prompt, max_new):
        self.futures.append(self.engine.submit(prompt, max_new))
        return self.futures[-1]


def mean_by_phase(rows):
    keys = [k for k in rows[0] if k != "tokens"]
    return dict({k: sum(r[k] for r in rows) / len(rows) for k in keys},
                requests=len(rows))


def table(title, groups):
    keys = [k for k in groups[0][1] if k != "requests"]
    lines = [title, "| requests | " + " | ".join(keys) + " |",
             "|---|" + "---|" * len(keys)]
    for name, g in groups:
        lines.append(f"| {name} ({g['requests']}) | " + " | ".join(
            f"{g[k]:.1f}" for k in keys) + " |")
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny files from benchmark/tests/data on any "
                        "backend, to try the control flow")
    args = p.parse_args(argv)
    import jax
    from hetu_tpu import cachedir
    if args.rehearse:
        cell = spec.resolve(args.workload, os.path.join(
            BENCH_DIR, "tests", "data", "BENCHMARK.json"))
        devices = jax.devices()[:cell.chips]
    else:
        cell = spec.resolve(args.workload)
        devices = device.require_tpu(cell.chips)
        cachedir.enable_compile_cache()
        for key, value in (
                ("jax_persistent_cache_min_compile_time_secs", 0),
                ("jax_persistent_cache_min_entry_size_bytes", 0),
                ("jax_compilation_cache_max_size", -1)):
            jax.config.update(key, value)   # as run.py keeps programs

    def log(fields):
        print(json.dumps(fields), flush=True)

    opts = Options(seed=args.seed, seconds=args.seconds,
                   trace=False, rehearse=False,
                   trace_dir=None, log=lambda fields: None,
                   process_start=time.perf_counter(), devices=devices,
                   compiles=compiles.CompileCounter())
    engine, _, _ = drv.build(cell, opts)
    traffic, pre = cell.traffic, cell.traffic["pre_seconds"]
    if args.rehearse:
        pre = min(pre, 1.0)
    try:
        requests = arrivals.schedule(traffic, args.seed, pre + args.seconds,
                                     cell.config["vocab_size"])
        head = [r for r in requests if r.due_s < pre]
        tail = [arrivals.Request(r.due_s - pre, r.prompt, r.max_new)
                for r in requests if r.due_s >= pre]
        h_t0, _, h_done, _ = base.play(engine, head)
        time.sleep(max(0.0, h_t0 + pre - time.perf_counter()))
        compiled = engine.jit_compiles
        before = engine.stats()["phase_ms"]
        kept = KeepFutures(engine)
        t0, _, done_t, _ = base.play(kept, tail)
        time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
        after = engine.stats()["phase_ms"]
        base.drain([h_done, done_t], traffic["drain_seconds"])
        rows = []
        for r, f, t_done in zip(tail, kept.futures, done_t):
            if t_done is None or f.exception() is not None:
                continue
            row = {"latency_ms": (t_done - t0 - r.due_s) * 1e3}
            row.update(f.account)
            row["ttft_ms"] = (f.t_first_token_ns - f.t_submit_ns) / 1e6
            row["tpot_ms"] = (f.t_retire_ns - f.t_first_token_ns) / 1e6 \
                / max(1, r.max_new - 1)
            row["tokens"] = r.max_new
            rows.append(row)
        log({"workload": cell.name, "seed": args.seed,
             "seconds": args.seconds, "due": len(tail),
             "completed": len(rows),
             "compiles_in_window": engine.jit_compiles - compiled})
        latencies = [r["latency_ms"] for r in rows]
        cut = stats.percentile(latencies, 95)
        cohort = [r for r in rows if r["latency_ms"] >= cut]
        groups = [("all", mean_by_phase(rows)),
                  ("p95 cohort", mean_by_phase(cohort))]
        log({"latency_ms_p50": stats.median(latencies),
             "latency_ms_p95": cut,
             "ttft_ms_p50": stats.median([r["ttft_ms"] for r in rows]),
             "tpot_ms_p50": stats.median([r["tpot_ms"] for r in rows]),
             "mean_ms_by_phase": dict(groups),
             "p95_cohort": cohort})     # a few requests: one by one
        window = {k: after[k] - before[k] for k in after}
        log({"engine_phase_ms_in_window": window,
             "engine_request_account": engine.stats()["request_account"]})
        print(table(f"{cell.name}, {args.seconds:g} s, seed {args.seed}: "
                    f"mean ms a request", groups))
        total = sum(v for k, v in window.items() if k != "stalled")
        print(table("the scheduler thread in the window, % of it",
                    [("engine", dict(
                        {k: 100.0 * v / total for k, v in window.items()},
                        requests=len(rows)))]))
    finally:
        engine.close()


if __name__ == "__main__":
    main()
