"""Run ONE cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, warms up, measures for
``--seconds``, checks the outputs, and prints one JSON object as the
last line of its standard output (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``). With
``--trace 0`` the metrics are the cell's end-to-end metrics, taken with
the profiler and the program's telemetry off; with ``--trace 1`` a
short window is profiled and the metrics are the cell's per-layer
metrics. Earlier lines (device stamp, utilisation, compile counts,
kernel tiles, memory) are JSON too and are for people.

It refuses to start unless ``jax.devices()`` are TPU chips and as many
as the cell asks for: there is no CPU fallback. ``--rehearse`` runs the
tiny files under ``benchmark/tests/data/`` on any backend to try the
control flow; it ends ``correct: false`` with exit code 4, so it can
never be read as a pass.

This file names no cell, model or metric: see ``harness/spec.py`` for
how each is found by name.
"""
import time
_PROCESS_START = time.perf_counter()

import argparse                                         # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import shutil                                           # noqa: E402
import sys                                              # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark.harness import compiles, contract, device, spec  # noqa: E402
from benchmark.harness.outcome import Options           # noqa: E402

EXIT_INCORRECT = 1
EXIT_REHEARSAL = 4
REHEARSAL_FILE = os.path.join(BENCH_DIR, "tests", "data", "BENCHMARK.json")


def log(fields):
    print(json.dumps(fields), flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window "
                        "(default: the benchmark file's run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the profile of a traced run on disk")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny files from benchmark/tests/data on any "
                        "backend; always correct=false, exit 4")
    return p.parse_args(argv)


def layer_metrics(cell, outcome, opts):
    """Per-layer metrics of a traced run and its breakdown: each metric
    is read by its own file; a reader that finds nothing returns None
    and the metric is left out of the line."""
    from benchmark.trace import xplane
    clock = compiles.Phases(time.perf_counter())
    trace = None
    if outcome.traced:
        trace = xplane.load(
            xplane.find_xplane(opts.trace_dir),
            keep_lines=lambda plane, line: plane.startswith("/device:")
            or plane.startswith("/host:"))
        clock.mark("load_profile")
    metrics = {}
    for entry in cell.per_layer:
        value = cell.reader(entry["name"]).reduce(trace, outcome.facts)
        if value is not None:
            metrics[entry["name"]] = (value, entry["unit"])
    clock.mark("readers")
    extra, breakdown = {}, None
    on_device = trace is not None and bool(xplane.device_planes(trace))
    if outcome.traced and not on_device and not opts.rehearse:
        raise RuntimeError("the trace holds no device plane")
    if on_device:
        busy_s, window_s, _ = xplane.busy(trace)
        extra = {"busy_s": busy_s, "window_s": window_s}
        breakdown = {"device_ops": xplane.top(xplane.op_seconds(trace)),
                     "idle_gaps": xplane.top(xplane.idle_gaps(trace))}
        clock.mark("breakdown")
    opts.log({"trace_reduction_s": clock.rows, "events": {
        plane["name"]: sum(len(line["events"]) for line in plane["lines"])
        for plane in (trace or {"planes": []})["planes"]}})
    return metrics, extra, breakdown


def main(argv=None):
    args = parse_args(argv)
    benchmark_file = REHEARSAL_FILE if args.rehearse \
        else os.path.join(spec.REPO_ROOT, "BENCHMARK.json")
    cell = spec.resolve(args.workload, benchmark_file)
    seconds = args.seconds if args.seconds is not None \
        else spec.read_json(benchmark_file)["run_seconds"]

    import jax
    from hetu_tpu import cachedir
    if args.rehearse:
        from hetu_tpu.ops import attention, pallas_attention
        # steer the platform-decided kernel dispatch from here, as the
        # tests and chip_smoke.py --rehearse do
        pallas_attention.INTERPRET = True
        attention._use_pallas = lambda: True
        devices = jax.devices()[:cell.chips]
    else:
        devices = device.require_tpu(cell.chips)
        cache_dir = cachedir.enable_compile_cache()
        # keep EVERY program of a run, however small and however many:
        # the serve cell's warm-up runs ~500 programs that compile in
        # under the default 1 s threshold (74 s of every run's set-up
        # when they are not kept), and a size cap that evicts one
        # cell's programs while another runs makes set-up unsteady
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
        log({"compile_cache": cache_dir})
    os.makedirs(cachedir.STATE_ROOT, exist_ok=True)
    counter = compiles.CompileCounter()
    log({"device": device.stamp(devices), "workload": cell.name,
         "seed": args.seed, "seconds": seconds, "trace": args.trace})

    trace_dir = os.path.join(cachedir.STATE_ROOT, "bench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = Options(seed=args.seed, seconds=seconds, trace=bool(args.trace),
                   rehearse=args.rehearse, trace_dir=trace_dir, log=log,
                   process_start=_PROCESS_START, devices=devices,
                   compiles=counter)
    outcome = cell.driver().run(cell, opts)

    stamp = dict(device.stamp(devices),
                 memory_peak_bytes=device.memory_peak_bytes(
                     devices, outcome.committed_bytes))
    log({"memory_peak_bytes": stamp["memory_peak_bytes"],
         "committed_bytes_at_window_end": outcome.committed_bytes,
         "memory_stats": devices[0].memory_stats(),
         "compiles": counter.snapshot()})
    breakdown = None
    if args.trace:
        metrics, extra, breakdown = layer_metrics(cell, outcome, opts)
        stamp.update(extra)
        if args.keep_trace:
            log({"trace_dir": trace_dir})
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
        metrics = {m["name"]: (values[m["name"]], m["unit"])
                   for m in cell.end_to_end}
    correct = outcome.correct and not args.rehearse
    print(contract.result_line(correct, outcome.attempted, outcome.failed,
                               metrics, stamp, breakdown), flush=True)
    if args.rehearse:
        return EXIT_REHEARSAL
    return 0 if correct else EXIT_INCORRECT


if __name__ == "__main__":
    sys.exit(main())
