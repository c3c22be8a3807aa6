"""Find the knee of a serving cell whose driver is
``drivers/serve_openloop_public.py`` (the engine warms itself through
its public ``warm_up``): ONE process, one set-up, a few rates in
geometric steps. The test of a sustained rate is
``tools/serve_sweep.py``'s, word for word; that tool imports the older
driver by name and cannot build such a cell.

    python benchmark/tools/serve_sweep_public.py --workload <cell> --rates 1,1.5,2.2,3.3 --seconds 30

At each rate the cell's traffic (same lengths, the given rate) runs for
``pre_seconds`` + ``--seconds`` and is drained. A rate is sustained
when at least 97% of the requests due in the window, its last
``--grace`` seconds apart (a request due at the very end cannot finish
inside it at any rate), complete inside it, and the waiting queue is
no deeper at the window's end than at its middle. The knee is the
highest sustained rate; the cell's traffic file then carries a share of
it as a number. Prints one JSON line per rate; needs the TPU like a run.
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


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--grace", type=float, default=5.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    cell = spec.resolve(args.workload)
    devices = device.require_tpu(cell.chips)
    from hetu_tpu import cachedir
    cachedir.enable_compile_cache()
    log = lambda fields: print(json.dumps(fields), flush=True)
    opts = Options(seed=args.seed, seconds=args.seconds, trace=False,
                   rehearse=False, trace_dir="", log=log,
                   process_start=time.perf_counter(), devices=devices,
                   compiles=compiles.CompileCounter())
    engine, _, _ = drv.build(cell, opts)
    traffic, pre = cell.traffic, cell.traffic["pre_seconds"]
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            requests = arrivals.schedule(
                traffic, args.seed, pre + args.seconds,
                cell.config["vocab_size"], rate_per_s=rate)
            poller = base.Poller(engine)
            poller.start()
            t0, sub, done, res = base.play(engine, requests)
            time.sleep(max(0.0, t0 + pre + args.seconds
                           - time.perf_counter()))
            base.drain([done], 60.0)
            poller.stop()
            s = base.summarize(requests, t0, sub, done, res, pre,
                              pre + args.seconds,
                              cell.config["vocab_size"])
            lat = s.pop("latencies_ms")

            def waiting_at(t):
                near = min(poller.samples, key=lambda x: abs(x[0] - t))
                return near[1]
            mid = waiting_at(t0 + pre + args.seconds / 2)
            end = waiting_at(t0 + pre + args.seconds)
            hi = pre + args.seconds
            early = [i for i, r in enumerate(requests)
                     if pre <= r.due_s < hi - args.grace]
            share = sum(done[i] is not None and done[i] - t0 < hi
                        for i in early) / max(1, len(early))
            log(dict(s, rate_per_s=rate,
                     completed_in_window_share=share,
                     waiting_mid=mid, waiting_end=end,
                     running_max=max(x[2] for x in poller.samples),
                     kv_blocks_used_peak=max(x[3] for x in poller.samples),
                     latency_ms_p50=stats.median(lat) if lat else None,
                     latency_ms_p95=stats.percentile(lat, 95)
                     if lat else None,
                     out_tokens_per_s=s["out_tokens"] / args.seconds,
                     sustained=bool(share >= 0.97 and end <= mid),
                     jit_compiles=engine.jit_compiles))
    finally:
        engine.close()


if __name__ == "__main__":
    main()
