"""Tracing / profiling (reference parity: the per-op profiler hooks in
gpu_ops/executor.py's p32/p16 timer paths and the HetuProfiler).

Three levels:

* ``StepLogger`` — per-step wall-time timeline appended as JSON lines
  (plus the PS runtime's phase counters when a PS session is active);
  enabled by ``Executor(..., log_path=...)``.
* ``profile_ops(executor, feed_dict)`` — per-op timing: runs the step
  eagerly op by op with a sync after each, returning (and optionally
  printing) the cost ranking. Eager timing is orders slower than the
  jitted step — it attributes cost, it does not measure the fused step.
  The fused step's own device time by graph op is in a ``trace`` of it:
  every ``node.compute`` is traced under ``hetu.<role>/<op_type>/<node>``
  (``Op.scope``), and ``python -m benchmark.tools.step_account <dir>``
  joins the profile's device events to those scopes (docs/tools.md,
  "Scopes: whose a device operation is").
* ``trace(logdir)`` — the operator's entry to ``jax.profiler``:
  ``with ht.profiler.trace(dir):`` around any training loop or serving
  engine writes ONE profile holding the device's operations and the
  program's own ``hetu.*`` spans (``Telemetry.span``'s second sink) on
  one clock, whether or not telemetry is on; docs/tools.md lists the
  span names.
"""
from __future__ import annotations

import contextlib
import json
import time

import numpy as np

__all__ = ["StepLogger", "profile_ops", "profile_op_records", "trace"]


class StepLogger:
    """Appends one JSON line per step: wall ms, step index, optional
    extra phase dict. Kept as a compat wrapper over the telemetry layer
    (hetu_tpu/telemetry): when constructed with a Telemetry instance it
    mirrors each step into the span trace and the ``step_wall_ms``
    histogram, so the JSONL timeline and the Perfetto trace agree."""

    def __init__(self, path, telemetry=None):
        self.path = path
        self._f = open(path, "a")
        self._t0 = None
        self._phase_snap = {}
        self.step = 0
        self.telemetry = telemetry

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def begin(self):
        self._t0 = time.perf_counter()

    def end(self, executor=None, **extra):
        dt = (time.perf_counter() - self._t0) * 1000 \
            if self._t0 is not None else None
        # `dt is not None`, NOT truthiness: a clock-granularity 0.0 ms
        # step is a real measurement, null means begin() never ran
        rec = {"step": self.step,
               "wall_ms": round(dt, 3) if dt is not None else None}
        rt = getattr(executor, "ps_runtime", None) if executor else None
        if rt is not None:
            # rt.times accumulates for the runtime's life: log the DELTA
            # since the previous step, which is this step's cost
            delta = {k: v - self._phase_snap.get(k, 0.0)
                     for k, v in rt.times.items()}
            self._phase_snap = dict(rt.times)
            rec["ps_phases_ms"] = {k: round(v * 1000, 3)
                                   for k, v in delta.items() if v > 0}
        rec.update(extra)
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()
        tel = self.telemetry
        if tel is not None and tel.enabled and dt is not None:
            tel.instant("step_logged", step=self.step,
                        wall_ms=rec["wall_ms"])
            tel.observe("steplogger_wall_ms", dt)
        self.step += 1

    def close(self):
        if not self._f.closed:
            self._f.close()

    @property
    def closed(self):
        return self._f.closed


def profile_op_records(executor, feed_dict=None, name="default",
                       costdb=None):
    """Per-op cost attribution with full op *identity*: execute the
    step's topo order eagerly, blocking after each op, and return one
    record per op — ``{"name", "kind", "shape", "dtype", "ms"}`` —
    with exactly the fields a ``telemetry.costdb.CostDB`` entry is
    keyed on. ``costdb=`` (a CostDB instance or a path) folds every
    record straight into the persistent database, source-tagged
    ``profile_ops``."""
    import jax

    from .graph.node import ExecContext
    from .ops.variable import PlaceholderOp

    sub = executor.subexecutors[name]
    feed_map = {}
    for node, value in (feed_dict or {}).items():
        feed_map[node] = sub._ingest(value)
    for dl in sub.dataloader_ops:
        feed_map[dl] = sub._ingest(dl.get_arr(sub.name))
    sub._infer_shapes(feed_map)
    sub._ensure_state(executor)

    ectx = ExecContext(training=False, base_rng=executor.base_rng,
                       config=sub.config)
    ectx.params = {n: executor.params[str(n.id)] for n in sub.param_nodes}
    ectx.state = {n: executor.state.get(str(n.id), {})
                  for n in sub.stateful_ops}
    ectx.opt_state = executor.opt_state
    ectx.lr = np.float32(0.0)
    ectx.step = 0

    env = dict(feed_map)
    records = []
    for node in sub.topo_order:
        if node in env or node in sub.optimizer_ops:
            continue
        if node in ectx.params:
            env[node] = ectx.params[node]
            continue
        if isinstance(node, PlaceholderOp):
            env[node] = None
            continue
        ins = [env[i] for i in node.inputs]
        t0 = time.perf_counter()
        out = node.compute(ins, ectx)
        try:
            jax.block_until_ready(out)
        except Exception:
            pass                      # pytree values (IndexedSlices etc.)
        ms = (time.perf_counter() - t0) * 1000
        dtype = getattr(out, "dtype", None)
        records.append({
            "name": node.name,
            "kind": type(node).__name__,
            "shape": getattr(node, "inferred_shape", None),
            "dtype": str(dtype) if dtype is not None else "float32",
            "ms": ms})
        env[node] = out
    records.sort(key=lambda r: -r["ms"])
    if costdb is not None:
        from .telemetry.costdb import CostDB, record_profile
        db = costdb if isinstance(costdb, CostDB) else CostDB(costdb)
        record_profile(db, records)
    return records


def profile_ops(executor, feed_dict=None, name="default", top=20,
                printout=True, costdb=None):
    """Per-op cost attribution: execute the step's topo order eagerly,
    blocking after each op (reference HetuProfiler's per-node timers).
    Returns [(op_name, ms)] sorted by cost; ``costdb=`` additionally
    persists each measurement (see ``profile_op_records``). Attribution
    only; the jitted step fuses these: what each graph op costs INSIDE
    the fused step is read from a ``trace`` of it, by the scopes the
    step runs its ops under (``benchmark/tools/step_account.py``)."""
    records = profile_op_records(executor, feed_dict, name=name,
                                 costdb=costdb)
    times = [(r["name"], r["ms"]) for r in records]
    if printout:
        total = sum(t for _, t in times)
        print(f"per-op profile ({len(times)} ops, eager total "
              f"{total:.1f} ms — attribution only; the jitted step "
              f"fuses these):")
        for opname, ms in times[:top]:
            print(f"  {ms:8.3f} ms  {opname}")
    return times


@contextlib.contextmanager
def trace(logdir):
    """Profile the enclosed code into ``logdir`` (an ``.xplane.pb``
    under ``plugins/profile/<time>/``, TensorBoard/Perfetto viewable):
    device operations and programs, and every ``hetu.*`` program span,
    on one clock. The profiler's Python tracer is off: it records every
    Python call of every thread, which buries the program's spans and
    slows the threads being measured (a serving engine's time per token
    read 6.4 ms with it against 5.3 ms without, PERF.md)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
