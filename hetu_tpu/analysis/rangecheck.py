"""Measured-range harness — the numerics verifier's dynamic twin.

The static pass (``analysis/numerics.py``) derives per-node value
intervals; this module measures them. A :class:`RangeRecorder`
attached to an executor makes the compiled step reduce every
float-valued node to ``(min, max)`` inside the trace (the same
fused-sentinel pattern PR 9's health monitor established — the
reductions run every step, the host fetch happens at the ``every_n``
cadence and costs one ``device_get`` of two scalars per node). The
twin relationship is enforced both ways:

* **soundness gate** — every measured per-op range must lie inside
  the static interval; an escape is an ``HT810`` error (the static
  model lied, which would silence every HT801/HT804 built on it), and
* **measured-range DB** — measured ranges persist in an
  atomic-JSON :class:`RangeDB` keyed by
  ``numerics.stable_keys`` (topo position + op type, stable across
  rebuilds), and ``numerics_pass(measured=...)`` re-seeds from them,
  turning loose initializer bounds into tight measured ones on
  re-analysis.

CLI::

    python -m hetu_tpu.analysis.rangecheck [models...] [--db PATH]

drives a few training steps of the named zoo models (default: mlp +
wdl_adult — a dense and a sparse path) on synthetic feeds, validates
the soundness gate, and persists the DB. Exit 1 on any violation.
"""
from __future__ import annotations

import json
import os
import tempfile

import numpy as np

from .findings import Report
from .numerics import stable_keys

__all__ = ["RangeDB", "RangeRecorder", "measure_ranges",
           "soundness_pass", "rangecheck_model", "main"]

# measured values may touch the static bound exactly; compare with a
# hair of slack so float32 round-trips don't fabricate violations
_SLACK_ABS = 1e-6
_SLACK_REL = 1e-5


def default_db_path():
    p = os.environ.get("HETU_RANGEDB")
    if p:
        return p
    from ..cachedir import store_path
    return store_path("ranges.json")


class RangeDB:
    """Persistent measured-range database (the CostDB atomic-
    JSON idiom): ``{model: {stable_key: {"lo", "hi", "n"}}}`` with
    running min/max merge across runs."""

    def __init__(self, path=None):
        self.path = path or default_db_path()
        self.data = {}
        self._load()

    def _load(self):
        try:
            with open(self.path) as f:
                raw = json.load(f)
            if isinstance(raw, dict):
                self.data = raw.get("models", {})
        except (OSError, ValueError):
            self.data = {}          # corrupt/absent: cold start

    def get(self, model):
        """{stable_key: (lo, hi)} for one model, or None."""
        ent = self.data.get(model)
        if not ent:
            return None
        return {k: (v["lo"], v["hi"]) for k, v in ent.items()
                if isinstance(v, dict) and "lo" in v and "hi" in v}

    def update(self, model, measured):
        """Merge ``{stable_key: (lo, hi)}`` with running min/max."""
        ent = self.data.setdefault(model, {})
        for key, (lo, hi) in measured.items():
            cur = ent.get(key)
            if cur is None:
                ent[key] = {"lo": float(lo), "hi": float(hi), "n": 1}
            else:
                cur["lo"] = min(cur["lo"], float(lo))
                cur["hi"] = max(cur["hi"], float(hi))
                cur["n"] = int(cur.get("n", 0)) + 1

    def save(self):
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": 1, "models": self.data}, f,
                          indent=1)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


class RangeRecorder:
    """Attach to an executor subgraph; fetch fused per-node ranges at
    cadence; accumulate the running measured min/max per node."""

    def __init__(self, executor, name="default", every_n=1):
        self.executor = executor
        self.name = name
        self.every_n = max(1, int(every_n))
        self.sub = executor.subexecutors[name]
        self.measured = {}          # node name -> [lo, hi]
        self.fetches = 0
        self._attached = False

    def attach(self):
        sub = self.sub
        sub._range_capture = True
        sub.compiled.clear()        # force a rebuild with the capture
        self._attached = True
        return self

    def detach(self):
        if self._attached:
            self.sub._range_capture = False
            self.sub.compiled.clear()
            self._attached = False

    def sample(self):
        """Fetch the last step's fused ranges (call after run(); the
        cadence check is one modulo, exactly the sentinel pattern)."""
        if self.sub.step_count % self.every_n:
            return
        h = getattr(self.sub, "_last_health", None)
        if not h or "ranges" not in h:
            return
        import jax
        host = jax.device_get(h["ranges"])
        self.fetches += 1
        tel = self.executor.config.telemetry
        if tel is not None and tel.enabled:
            tel.inc("rangecheck_fetches")
            tel.set_gauge("rangecheck_nodes", len(host))
        for name, (lo, hi) in host.items():
            # the block (lax.scan) path stacks the capture [nsteps]:
            # reduce over the scan axis
            lo, hi = float(np.min(lo)), float(np.max(hi))
            cur = self.measured.get(name)
            if cur is None:
                self.measured[name] = [lo, hi]
            else:
                cur[0] = min(cur[0], lo)
                cur[1] = max(cur[1], hi)

    def by_stable_key(self):
        """Measured ranges re-keyed by ``numerics.stable_keys`` (the
        DB key space; node names embed the process-global id counter
        and do not survive a rebuild)."""
        topo = self.sub.topo_order
        keys = stable_keys(topo)
        out = {}
        for node, key in zip(topo, keys):
            m = self.measured.get(node.name)
            if m is not None:
                out[key] = (m[0], m[1])
        return out


def measure_ranges(executor, feed_fn, steps=4, name="default",
                   every_n=1):
    """Drive ``steps`` ``run()`` calls feeding ``feed_fn(step)`` with a
    recorder attached; returns ``{stable_key: (lo, hi)}``."""
    rec = RangeRecorder(executor, name=name, every_n=every_n).attach()
    try:
        for i in range(steps):
            executor.run(name, feed_dict=feed_fn(i))
            rec.sample()
    finally:
        rec.detach()
    return rec.by_stable_key()


def soundness_pass(topo, static_ranges, measured, report=None):
    """Every measured range must lie inside its static interval —
    unknown static intervals are vacuous (reported in the summary, not
    as findings). Emits HT810 errors; returns (report, checked count).
    """
    if report is None:
        report = Report()
    keys = stable_keys(topo)
    by_key = {k: n for k, n in zip(keys, topo)}
    static_by_key = {k: static_ranges.get(n)
                     for k, n in zip(keys, topo)}
    import math
    checked = 0
    for key, m in measured.items():
        s = static_by_key.get(key)
        if s is None:
            continue
        checked += 1
        # per-endpoint slack from the FINITE endpoint being checked: a
        # half-bounded static interval (exp's [lo, inf)) must still
        # enforce its finite side, and a NaN measurement — the very
        # failure this verifier exists for — is always a violation
        viol = math.isnan(m[0]) or math.isnan(m[1])
        if not viol and math.isfinite(s[0]) \
                and m[0] < s[0] - (_SLACK_ABS + _SLACK_REL * abs(s[0])):
            viol = True
        if not viol and math.isfinite(s[1]) \
                and m[1] > s[1] + (_SLACK_ABS + _SLACK_REL * abs(s[1])):
            viol = True
        if viol:
            node = by_key.get(key)
            report.add(
                "HT810", "error",
                f"measured range [{m[0]:.4g}, {m[1]:.4g}] escapes the "
                f"static interval [{s[0]:.4g}, {s[1]:.4g}] for {key} — "
                f"the abstract interpretation is unsound here (fix the "
                f"transfer rule or the seed)", node=node)
    return report, checked


def _synth_feeds(feed_shapes, seed=0):
    """Deterministic synthetic feeds per (shape, dtype) spec: modest
    normals for floats, small non-negative ids for ints (always valid
    row indices for the zoo's tables)."""
    rng = np.random.RandomState(seed)
    feeds = {}
    for node, (shape, dt) in feed_shapes.items():
        dt = np.dtype(dt if dt is not None else np.float32)
        if dt.kind in "iu":
            feeds[node] = rng.randint(0, 8, size=shape).astype(dt)
        else:
            feeds[node] = (rng.standard_normal(shape) * 0.5).astype(dt)
    return feeds


def rangecheck_model(model, steps=4, every_n=1, db=None, seed=0):
    """Round-trip one zoo model: run ``steps`` training steps with the
    fused capture, soundness-check measured vs static, fold into the
    DB. Returns (report, measured, checked)."""
    from . import zoo
    from .numerics import numerics_pass
    from .shapes import shape_pass
    from ..executor import Executor
    from ..graph.autodiff import find_topo_sort

    eval_nodes, feed_shapes = zoo.build(model)
    from .shapes import _resolve_feed_shapes
    specs = _resolve_feed_shapes(feed_shapes, find_topo_sort(eval_nodes))

    exe = Executor(eval_nodes)
    measured = measure_ranges(
        exe, lambda i: _synth_feeds(specs, seed=seed + i), steps=steps,
        every_n=every_n)

    # the static side runs over the EXECUTOR's topo order (comm ops
    # spliced), so stable keys line up with the measured capture
    topo = exe.subexecutors["default"].topo_order
    dtypes = {}
    shapes = shape_pass(topo, Report(), feed_shapes=feed_shapes,
                        dtypes_out=dtypes)
    static = numerics_pass(topo, Report(), shapes=shapes, dtypes=dtypes)
    report, checked = soundness_pass(topo, static, measured)
    if db is not None:
        db.update(model, measured)
    return report, measured, checked


DEFAULT_MODELS = ("mlp", "wdl_adult")


def main(argv=None):
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m hetu_tpu.analysis.rangecheck",
        description="measured-range harness: run zoo models with fused "
                    "per-op range capture, validate every measured "
                    "range against the static interval, persist the "
                    "range DB")
    parser.add_argument("models", nargs="*",
                        help=f"zoo models (default: "
                             f"{' '.join(DEFAULT_MODELS)})")
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--every-n", type=int, default=1)
    parser.add_argument("--db", default=None, metavar="PATH",
                        help="range DB path (default: $HETU_RANGEDB or "
                             "the in-checkout store, "
                             "hetu_tpu/cachedir.py)")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    models = args.models or list(DEFAULT_MODELS)
    db = RangeDB(args.db)
    rc = 0
    out = {}
    for model in models:
        report, measured, checked = rangecheck_model(
            model, steps=args.steps, every_n=args.every_n, db=db,
            seed=0)
        ok = not report.errors
        out[model] = {"measured": len(measured), "checked": checked,
                      "violations": len(report.errors)}
        if not args.json:
            print(f"== {model}: {'ok' if ok else 'UNSOUND'} "
                  f"({len(measured)} node(s) measured, {checked} "
                  f"checked against a static interval, "
                  f"{len(report.errors)} violation(s))")
            for f in report.errors:
                print("   " + str(f))
        if not ok:
            rc = 1
    db.save()
    if args.json:
        print(json.dumps({"db": db.path, "models": out}, indent=2))
    else:
        print(f"range DB written to {db.path}")
    return rc


if __name__ == "__main__":
    import sys
    sys.exit(main())
