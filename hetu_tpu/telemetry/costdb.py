"""Measured per-op / per-collective cost database.

ROADMAP item 4's cost-model auto-parallelism planner needs costs that
are "estimated, then refined by measurement" — the reference Hetu picks
Hybrid vs AllReduce per table from *profiled* comm/compute ratios, not
from an analytic model alone. This module is the measurement substrate:
one persistent JSON table of measured milliseconds keyed
``(platform, kind, shape, dtype)``, so
an entry tuned on one chip generation is never served to another.

Three producers populate it:

* ``record_profile(db, records)`` — per-op timings from
  ``profiler.profile_op_records`` (eager per-op re-execution with a
  sync after each): one entry per (op kind, output shape, dtype).
* ``record_spans(db, events)`` — collective/transfer aggregates lifted
  from an exported Chrome trace: ``h2d_transfer`` / ``ps:pull`` /
  ``p2p_send`` / ``p2p_recv`` spans carry byte counts, so each becomes
  a (kind, pow2-bucketed bytes) cost point measured *in situ*.
* ``comm_microbench(db)`` — a dedicated sweep of h2d/d2h transfers and
  (on multi-device backends) allreduce/p2p collectives over a size
  ladder, plus ``ps_microbench(db, client)`` for SparsePull/SparsePush
  against a live PS server. The resulting points feed ``curve()`` —
  a least-squares latency+bandwidth fit per comm kind, the function a
  cost-model planner actually queries (``estimate_ms(kind, nbytes)``).

Entries keep a running mean, min and sample count, so repeated
measurement refines rather than overwrites. Persistence is atomic
temp+rename writes under an advisory flock, with
a read-merge so two processes measuring different kinds against one
file don't drop each other's entries.

CLI::

    python -m hetu_tpu.telemetry.costdb --show [--json]
    python -m hetu_tpu.telemetry.costdb --sweep          # comm microbench
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

__all__ = ["CostDB", "default_db_path", "record_profile", "record_spans",
           "comm_microbench", "ps_microbench", "COMM_KINDS",
           "cold_start_ms", "cold_start_flops_ms",
           "latency_crossover_bytes", "recommend_bucket_bytes", "main"]

_DB_ENV = "HETU_COSTDB"
_VERSION = 1

# the comm kinds the planner's cost model queries; doctor reports
# coverage gaps against this list
COMM_KINDS = ("h2d", "d2h", "allreduce", "p2p", "ps_sparse_pull",
              "ps_sparse_push", "ps_pull", "ps_push")


def default_db_path():
    p = os.environ.get(_DB_ENV)
    if not p:
        from ..cachedir import store_path
        return store_path("costdb.json")
    p = os.path.expanduser(p)
    if p.endswith(".json"):
        return p
    return os.path.join(p, "costdb.json")


def _platform():
    from ..tune.autotune import platform_tag
    return platform_tag()


def _shape_str(shape):
    if shape is None:
        return "?"
    if isinstance(shape, (int, float)):
        return str(int(shape))
    try:
        dims = [str(int(d)) for d in shape]
    except TypeError:
        return str(shape)
    return "x".join(dims) if dims else "scalar"


# ---------------------------------------------------------------------------
# cold-start heuristics: the analytic floor the planner trusts when the
# DB has never measured a kind. Deliberately conservative, round-number
# assumptions (documented in docs/parallelism.md "Cost-model inputs"):
# a cold estimate must RANK plans sensibly, not predict wall clocks —
# one comm_microbench sweep replaces all of these with measurements.
# ---------------------------------------------------------------------------

# assumed sustained bandwidth per comm kind, GB/s: PCIe-class for
# host<->device, ICI-class for in-slice collectives, NIC-class for the
# PS RPC path (each ~an order below marketing peak — sustained, not burst)
_COLD_GBPS = {"h2d": 8.0, "d2h": 8.0, "allreduce": 40.0, "p2p": 40.0,
              "ps_sparse_pull": 1.0, "ps_sparse_push": 1.0,
              "ps_pull": 1.0, "ps_push": 1.0,
              # a recompile is latency, not bytes: the GBps term only
              # keeps the arithmetic uniform for the efficiency pass
              "jit_compile": 1000.0}
_COLD_LATENCY_MS = {"h2d": 0.1, "d2h": 0.1, "allreduce": 0.05,
                    "p2p": 0.02, "ps_sparse_pull": 0.3,
                    "ps_sparse_push": 0.3, "ps_pull": 0.3,
                    "ps_push": 0.3,
                    # one XLA compile of a training step: hundreds of
                    # ms is the conservative floor the HT901 recompile
                    # lint prices against until a measured jit_compile
                    # entry replaces it
                    "jit_compile": 200.0}
# assumed achievable compute rate for the FLOPs-proportional compute
# fallback when NO op of a graph was ever profiled (GFLOP/s: a CPU-core
# class floor — any real accelerator measurement replaces it)
_COLD_GFLOPS = 50.0


def cold_start_ms(kind, nbytes):
    """Analytic latency+bandwidth floor for a comm kind the DB has no
    measurements for: ``latency + nbytes / bandwidth`` with the
    documented ``_COLD_*`` assumptions (unknown kinds get the slowest
    class). The planner's last resort — `coverage()` tells callers
    which estimates rest on it."""
    lat = _COLD_LATENCY_MS.get(kind, 0.3)
    gbps = _COLD_GBPS.get(kind, 1.0)
    return lat + max(0, int(nbytes)) / (gbps * 1e6)


def cold_start_flops_ms(flops):
    """FLOPs-proportional compute floor (``flops / _COLD_GFLOPS``) for
    ops with no profiled entry and no calibration anchor in the DB."""
    return max(0.0, float(flops)) / (_COLD_GFLOPS * 1e6)


def pow2_bucket(nbytes):
    """Round a byte count up to a power of two: span-derived transfer
    sizes vary per batch, but cost points only need size-class
    resolution to fit a latency/bandwidth curve."""
    n = max(1, int(nbytes))
    b = 1
    while b < n:
        b <<= 1
    return b


class CostDB:
    """Persistent measured-cost table; one JSON file, with
    ``platform|kind|shape|dtype`` keys."""

    def __init__(self, path=None):
        self.path = default_db_path() if path is None else os.fspath(path)
        self._entries = None
        self._lock = threading.RLock()

    # -- keys ------------------------------------------------------------
    @staticmethod
    def key(kind, shape, dtype="float32"):
        return "|".join((_platform(), str(kind), _shape_str(shape),
                         str(dtype)))

    # -- persistence ----------------------------------------------------
    def _load(self):
        if self._entries is not None:
            return self._entries
        entries = {}
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and doc.get("version") == _VERSION:
                entries = dict(doc.get("entries") or {})
        except (OSError, ValueError):
            pass                        # cold or corrupt: start fresh
        self._entries = entries
        return entries

    def save(self):
        """Atomic write (temp + rename) with a read-merge under an
        advisory flock, so two processes measuring different kinds
        against one file serialize instead of dropping entries. On-disk
        entries merge by sample count: whichever side has seen more
        measurements wins (our freshly-recorded side usually has)."""
        with self._lock:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            lf = None
            try:
                try:
                    import fcntl
                    lf = open(self.path + ".lock", "w")
                    fcntl.flock(lf, fcntl.LOCK_EX)
                except (ImportError, OSError):
                    pass
                entries = self._load()
                try:
                    with open(self.path) as f:
                        doc = json.load(f)
                    if isinstance(doc, dict) and \
                            doc.get("version") == _VERSION:
                        for k, ent in (doc.get("entries") or {}).items():
                            ours = entries.get(k)
                            if ours is None or ent.get("n", 0) > \
                                    ours.get("n", 0):
                                entries[k] = ent
                        self._entries = entries
                except (OSError, ValueError):
                    pass
                tmp = f"{self.path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump({"version": _VERSION, "entries": entries},
                              f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if lf is not None:
                    lf.close()

    # -- recording -------------------------------------------------------
    def record(self, kind, shape, dtype, ms, source="measure",
               nbytes=None):
        """Fold one measurement in: running mean + min + count. Returns
        the updated entry."""
        ks = self.key(kind, shape, dtype)
        with self._lock:
            entries = self._load()
            ent = entries.get(ks)
            ms = float(ms)
            if ent is None:
                ent = entries[ks] = {
                    "kind": str(kind), "shape": _shape_str(shape),
                    "dtype": str(dtype), "ms": round(ms, 5),
                    "min_ms": round(ms, 5), "n": 1, "source": source,
                    "ts": time.time()}
            else:
                n = int(ent.get("n", 1))
                ent["ms"] = round((ent["ms"] * n + ms) / (n + 1), 5)
                ent["min_ms"] = round(min(ent.get("min_ms", ms), ms), 5)
                ent["n"] = n + 1
                ent["source"] = source
                ent["ts"] = time.time()
            if nbytes is not None:
                # running mean like ms: ms is averaged over every
                # sample in the size class, so the curve-fit x-point
                # must be too — last-sample nbytes against mean ms
                # would skew the bandwidth fit by arrival order
                prev = ent.get("nbytes")
                n = int(ent.get("n", 1))
                if prev is None or n <= 1:
                    ent["nbytes"] = int(nbytes)
                else:
                    ent["nbytes"] = int(round(
                        (prev * (n - 1) + nbytes) / n))
        return dict(ent)

    # -- queries ---------------------------------------------------------
    def get(self, kind, shape, dtype="float32"):
        with self._lock:
            ent = self._load().get(self.key(kind, shape, dtype))
        return dict(ent) if ent else None

    def lookup_ms(self, kind, shape, dtype="float32"):
        ent = self.get(kind, shape, dtype)
        return None if ent is None else float(ent["ms"])

    def lookup_node(self, node):
        """Best measured cost for a graph node: exact (kind, inferred
        shape, float32) first, then any dtype with the same kind+shape.
        Returns an entry dict or None — graphboard's DB overlay."""
        kind = type(node).__name__
        shape = getattr(node, "inferred_shape", None)
        ent = self.get(kind, shape)
        if ent is not None:
            return ent
        prefix = "|".join((_platform(), kind, _shape_str(shape), ""))
        with self._lock:
            for ks, e in self._load().items():
                if ks.startswith(prefix):
                    return dict(e)
        return None

    def kinds(self):
        with self._lock:
            return sorted({e.get("kind", k.split("|")[1])
                           for k, e in self._load().items()})

    def entries(self):
        with self._lock:
            return {k: dict(v) for k, v in self._load().items()}

    def __len__(self):
        with self._lock:
            return len(self._load())

    def coverage(self, required=COMM_KINDS):
        """(measured, guessed) over ``required`` — the doctor's cost-DB
        coverage-gap report and the autoplan report's measured-vs-
        guessed split. Entries may be bare kinds (covered when ANY
        entry of that kind exists) or ``(kind, shape[, dtype])`` tuples
        (covered only by an exact entry — what the planner's per-op
        lookups actually hit). A kind in the second list is served by
        the cold-start heuristic, not a measurement."""
        have = set(self.kinds())
        measured, guessed = [], []
        for k in required:
            if isinstance(k, (tuple, list)):
                hit = self.get(*k) is not None
            else:
                hit = k in have
            (measured if hit else guessed).append(
                tuple(k) if isinstance(k, list) else k)
        return measured, guessed

    # -- comm curves -----------------------------------------------------
    def curve(self, kind):
        """Least-squares ``ms = latency + nbytes / bandwidth`` fit over
        every entry of ``kind`` that carries a byte count. Returns
        {latency_ms, GBps, points} or None with <2 points."""
        import numpy as np
        with self._lock:
            pts = [(e["nbytes"], e["ms"])
                   for e in self._load().values()
                   if e.get("kind") == kind and e.get("nbytes")]
        if len(pts) < 2:
            return None
        x = np.array([p[0] for p in pts], dtype=float)
        y = np.array([p[1] for p in pts], dtype=float)
        a = np.vstack([np.ones_like(x), x]).T
        (lat, slope), *_ = np.linalg.lstsq(a, y, rcond=None)
        lat = max(0.0, float(lat))
        # non-positive slope = latency-dominated over the measured
        # range (or noise): no bandwidth estimate, stay JSON-able
        gbps = round(1.0 / slope / 1e6, 3) if slope > 0 else None
        return {"latency_ms": round(lat, 5), "GBps": gbps,
                "points": len(pts)}

    def estimate_ms(self, kind, nbytes, cold_start=False):
        """Predicted milliseconds for moving ``nbytes`` through ``kind``
        from the fitted curve (exact entry preferred when one exists) —
        the query the cost-model planner makes. Size-class entries come
        from two producers with different dtype tags (span points are
        ``bytes``, microbench points ``float32``); try both.

        ``cold_start=True`` never returns None: a kind with no entries
        falls back to the documented link-speed heuristic
        (:func:`cold_start_ms`) so a fresh checkout can still rank
        plans — the planner reports which estimates came from
        measurement via :meth:`coverage` / :meth:`estimate_info`."""
        ms, _src = self.estimate_info(kind, nbytes,
                                      cold_start=cold_start)
        return ms

    def estimate_info(self, kind, nbytes, cold_start=True):
        """(ms, source) where source is ``"measured"`` (exact size-class
        entry), ``"curve"`` (latency+bandwidth fit), or
        ``"cold_start"`` (analytic heuristic; None when cold_start is
        off and the DB is empty for the kind)."""
        bucket = pow2_bucket(nbytes)
        ent = self.get(kind, bucket, "bytes") or self.get(kind, bucket)
        if ent is not None:
            return float(ent["ms"]), "measured"
        cv = self.curve(kind)
        if cv is not None:
            gbps = cv["GBps"]
            bw_ms = 0.0 if not gbps else nbytes / (gbps * 1e6)
            return cv["latency_ms"] + bw_ms, "curve"
        if not cold_start:
            return None, None
        return cold_start_ms(kind, nbytes), "cold_start"


# ---------------------------------------------------------------------------
# derived knob recommendations (the planner/efficiency-lint queries)
# ---------------------------------------------------------------------------

# bucket-size clamp for gradient-allreduce bucketing: below 1 MiB a
# bucket is still latency-dominated, above 64 MiB the tail collective
# stops overlapping the remaining backward (the DDP paper's regime)
_BUCKET_MIN = 1 << 20
_BUCKET_MAX = 64 << 20
_BUCKET_COLD = 4 << 20          # DDP's 25MB-class default, scaled down


def latency_crossover_bytes(db, kind="allreduce"):
    """Byte count where the fitted curve's bandwidth term equals its
    latency term — transfers below it are latency-dominated (the
    "fragmented collective" regime HT904 prices). Falls back to the
    cold-start constants when the DB has no curve for ``kind``."""
    cv = db.curve(kind) if db is not None else None
    if cv is not None and cv.get("GBps"):
        return int(cv["latency_ms"] * cv["GBps"] * 1e6)
    return int(_COLD_LATENCY_MS.get(kind, 0.3)
               * _COLD_GBPS.get(kind, 1.0) * 1e6)


def recommend_bucket_bytes(db=None):
    """CostDB-derived ``overlap_options.bucket_bytes`` default: 4x the
    measured allreduce latency-bandwidth crossover (so a bucket is
    ~80% bandwidth-bound), clamped to [1 MiB, 64 MiB]; the documented
    4 MiB cold-start default when no curve exists. The autoplan
    planner applies this to dp plans so ``parallel="auto"`` never
    ships the per-grad (HT904) collective pattern by default."""
    if db is None:
        return _BUCKET_COLD
    cv = db.curve("allreduce")
    if cv is None or not cv.get("GBps"):
        return _BUCKET_COLD
    return int(min(_BUCKET_MAX, max(
        _BUCKET_MIN, 4 * latency_crossover_bytes(db, "allreduce"))))


# ---------------------------------------------------------------------------
# producers
# ---------------------------------------------------------------------------

def record_profile(db, records, save=True):
    """Fold ``profiler.profile_op_records`` output into the DB; returns
    the number of entries touched."""
    n = 0
    for rec in records:
        db.record(rec["kind"], rec.get("shape"),
                  rec.get("dtype", "float32"), rec["ms"],
                  source="profile_ops")
        n += 1
    if save and n:
        db.save()
    return n


_SPAN_KIND = {"h2d_transfer": "h2d", "h2d_stacked": "h2d",
              "ps:pull": "ps_pull", "p2p_send": "p2p",
              "p2p_recv": "p2p"}


def record_spans(db, events, save=True):
    """Lift comm cost points from exported trace events: every complete
    span with a byte count becomes a (kind, pow2-bucketed bytes) entry
    measured in situ. Returns the number of points recorded."""
    n = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        kind = _SPAN_KIND.get(ev.get("name"))
        if kind is None:
            continue
        args = ev.get("args") or {}
        nbytes = args.get("bytes")
        dur = ev.get("dur")
        if not nbytes or dur is None:
            continue
        # KEY by the pow2 size class (stable across batches), but keep
        # the REAL byte count as the curve-fit x-point — fitting
        # against the rounded bucket would overstate bandwidth by up
        # to 2x
        db.record(kind, pow2_bucket(nbytes), "bytes", dur / 1000.0,
                  source="span", nbytes=nbytes)
        n += 1
    if save and n:
        db.save()
    return n


def _timeit_ms(run, sync, reps=3):
    from ..tune.autotune import timeit
    return timeit(run, sync=sync, reps=reps, windows=2) * 1000.0


def comm_microbench(db, sizes=None, reps=3, save=True):
    """Sweep h2d/d2h transfers (always) and allreduce/p2p collectives
    (multi-device backends) over a size ladder; every point lands in
    the DB as (kind, nbytes). Returns {kind: points_recorded}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = tuple(sizes or (1 << 14, 1 << 17, 1 << 20, 1 << 23))
    out = {}
    rng = np.random.RandomState(0)

    for nbytes in sizes:
        host = rng.randn(nbytes // 4).astype(np.float32)
        ms = _timeit_ms(lambda: jax.device_put(host),
                        lambda x: float(jnp.sum(x)), reps=reps)
        db.record("h2d", nbytes, "float32", ms, source="comm_bench",
                  nbytes=nbytes)
        dev = jax.device_put(host)
        ms = _timeit_ms(lambda: np.asarray(dev), lambda x: None,
                        reps=reps)
        db.record("d2h", nbytes, "float32", ms, source="comm_bench",
                  nbytes=nbytes)
    out["h2d"] = out["d2h"] = len(sizes)

    ndev = len(jax.devices())
    if ndev > 1:
        for nbytes in sizes:
            n = max(ndev, (nbytes // 4) // ndev * ndev)
            host = rng.randn(n).astype(np.float32).reshape(ndev, -1)
            # device-resident input: timing psum(host_numpy) would fold
            # a full H2D transfer into every rep and the curve would
            # measure link + collective, not the collective (the h2d
            # sweep above isolates transfer cost on its own)
            dev = jax.device_put_sharded(list(host),
                                         jax.devices()[:ndev])

            psum = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
            ms = _timeit_ms(lambda: psum(dev),
                            lambda x: float(np.asarray(x)[0, 0]),
                            reps=reps)
            db.record("allreduce", nbytes, "float32", ms,
                      source="comm_bench", nbytes=nbytes)

            shift = jax.pmap(
                lambda x: jax.lax.ppermute(
                    x, "i", [(j, (j + 1) % ndev) for j in range(ndev)]),
                axis_name="i")
            ms = _timeit_ms(lambda: shift(dev),
                            lambda x: float(np.asarray(x)[0, 0]),
                            reps=reps)
            db.record("p2p", nbytes, "float32", ms,
                      source="comm_bench", nbytes=nbytes)
        out["allreduce"] = out["p2p"] = len(sizes)
    if save:
        db.save()
    return out


def ps_microbench(db, client, tid=900_001, width=64, sizes=None,
                  reps=3, save=True):
    """SparsePull / SparsePush / dense Pull / dense Push size sweep
    against a live PS server (``client``: a ``ps.client.PSClient``).
    Registers its own scratch table under ``tid``. Returns
    {kind: points}."""
    import numpy as np

    sizes = tuple(sizes or (64, 512, 4096))   # rows per RPC
    nrows = max(sizes) * 2
    client.init_tensor(tid, (nrows, width), kind=1)
    client.init_tensor(tid + 1, (nrows * width,), kind=0)
    rng = np.random.RandomState(0)
    for rows in sizes:
        ids = rng.randint(0, nrows, rows).astype(np.int64)
        vals = rng.randn(rows, width).astype(np.float32)
        nbytes = rows * width * 4
        ms = _timeit_ms(lambda: client.sparse_pull(tid, ids, width),
                        lambda x: None, reps=reps)
        db.record("ps_sparse_pull", nbytes, "float32", ms,
                  source="ps_bench", nbytes=nbytes)
        ms = _timeit_ms(
            lambda: (client.sparse_push(tid, ids, vals, width),
                     client.wait(tid)),
            lambda x: None, reps=reps)
        db.record("ps_sparse_push", nbytes, "float32", ms,
                  source="ps_bench", nbytes=nbytes)
        dense_n = rows * width
        ms = _timeit_ms(lambda: client.pull(tid + 1, (dense_n,)),
                        lambda x: None, reps=reps)
        db.record("ps_pull", nbytes, "float32", ms, source="ps_bench",
                  nbytes=nbytes)
        grad = rng.randn(dense_n).astype(np.float32)
        ms = _timeit_ms(
            lambda: (client.push(tid + 1, grad), client.wait(tid + 1)),
            lambda x: None, reps=reps)
        db.record("ps_push", nbytes, "float32", ms, source="ps_bench",
                  nbytes=nbytes)
    if save:
        db.save()
    return {k: len(sizes) for k in ("ps_sparse_pull", "ps_sparse_push",
                                    "ps_pull", "ps_push")}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m hetu_tpu.telemetry.costdb",
        description="measured per-op/per-collective cost database")
    parser.add_argument("--db", default=None,
                        help=f"DB file (default ${_DB_ENV} or "
                             f"the in-checkout store, "
                             f"hetu_tpu/cachedir.py)")
    parser.add_argument("--sweep", action="store_true",
                        help="run the comm microbench and record curves")
    parser.add_argument("--show", action="store_true",
                        help="print the table summary")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    db = CostDB(args.db)
    if args.sweep:
        swept = comm_microbench(db)
        print(f"comm microbench: {swept}", file=sys.stderr)
    if args.json:
        doc = {"path": db.path, "entries": db.entries(),
               "curves": {k: cv for k in COMM_KINDS
                          for cv in [db.curve(k)] if cv}}
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0
    ents = db.entries()
    print(f"{db.path}: {len(ents)} entries, "
          f"{len(db.kinds())} kinds")
    if args.show or args.sweep:
        for ks in sorted(ents):
            e = ents[ks]
            print(f"  {ks}  {e['ms']:.4f} ms (min {e['min_ms']:.4f}, "
                  f"n={e['n']}, {e['source']})")
        present, missing = db.coverage()
        print(f"comm coverage: {present or '-'}; missing: "
              f"{missing or 'none'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
