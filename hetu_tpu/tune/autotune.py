"""Generic kernel autotuner: sweep-once, cache-forever config selection.

The production-attention lesson (FlashAttention, Megatron-LM) is that
tile-size choices dominate kernel throughput and the right choice is a
function of shape/dtype/platform, not a constant — so treat the chosen
config as a first-class cached artifact. This module is the
kernel-agnostic half: ``autotune(name, key, candidates, measure)``
sweeps ``candidates`` through the caller's ``measure`` on the first
compile of a given (platform, name, key), records the winner in an
in-process table backed by a persistent JSON file, and returns the
cached winner for free on every later lookup (including later
processes). ``ops/pallas_attention.py`` consumes it for flash-attention
block sizes; the engine carries nothing attention-specific, so scan
block sizes or pipeline tick fusing can ride the same cache later.

Environment:

* ``HETU_AUTOTUNE`` — ``0`` disables tuning entirely (callers keep
  their static defaults), ``1`` is use-cache-only (a miss returns the
  default with NO sweep — deterministic CI runs), ``force`` re-sweeps
  even on a cache hit; unset/``auto`` sweeps on miss, hits otherwise.
* ``HETU_AUTOTUNE_CACHE`` — cache file (or directory, file named
  ``autotune.json`` inside); default ``autotune.json`` under the
  in-checkout state root (``hetu_tpu/cachedir.py``), so which tiles a
  run compiles is a function of the checkout, not of ``~``.

Telemetry (process-global registry): ``autotune_cache_hit`` /
``autotune_cache_miss`` / ``autotune_sweeps`` counters, and one
``autotune_sweep`` span per sweep whose attrs carry the kernel, key,
chosen config and per-candidate milliseconds — the sweep is visible in
the trace instead of reading as an unexplained slow first step.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["AutotuneTable", "AutotuneSweepError", "autotune", "get_table",
           "configure",
           "reset", "tuning_mode", "default_cache_path", "platform_tag",
           "timeit"]

_MODE_ENV = "HETU_AUTOTUNE"
_CACHE_ENV = "HETU_AUTOTUNE_CACHE"
_VERSION = 1


class AutotuneSweepError(RuntimeError):
    """Every candidate of a sweep raised; the message carries each
    candidate's error text."""


def tuning_mode():
    """'off' | 'cache' | 'force' | 'auto' from ``HETU_AUTOTUNE``."""
    raw = os.environ.get(_MODE_ENV, "").strip().lower()
    if raw in ("0", "off", "false", "no"):
        return "off"
    if raw in ("1", "cache"):
        return "cache"
    if raw == "force":
        return "force"
    return "auto"


def default_cache_path():
    p = os.environ.get(_CACHE_ENV)
    if not p:
        from ..cachedir import store_path
        return store_path("autotune.json")
    p = os.path.expanduser(p)
    if p.endswith(".json"):
        return p
    return os.path.join(p, "autotune.json")


_PLATFORM = None


def platform_tag():
    """Cache partition for the attached accelerator: configs tuned on
    one chip generation must not be served to another. Memoized — the
    serving prefill path resolves blocks per request and must not pay
    a jax.devices() call each time. A backend that cannot initialise
    raises here: there is no tag for "no device"."""
    global _PLATFORM
    if _PLATFORM is None:
        import jax
        dev = jax.devices()[0]
        kind = dev.device_kind or dev.platform
        _PLATFORM = "".join(              # lock-ok: HT605 idempotent memo: racing writers compute identical values, swap is atomic
            c if c.isalnum() else "_" for c in str(kind).strip().lower())
    return _PLATFORM


def _key_string(name, key):
    parts = [platform_tag(), str(name)]
    if isinstance(key, (tuple, list)):
        parts += [str(k) for k in key]
    else:
        parts.append(str(key))
    return "|".join(parts)


def _freeze(cfg):
    """JSON round-trips tuples as lists; hand configs back frozen so a
    cache hit and a fresh sweep return the same type."""
    if isinstance(cfg, list):
        return tuple(_freeze(c) for c in cfg)
    return cfg


def timeit(run, sync=None, reps=3, windows=2):
    """Seconds per ``run()`` call: one warmup (compile), then the best
    of ``windows`` timed windows of ``reps`` back-to-back dispatches
    ended by ``sync(out)``, which must wait for the device (a scalar
    readback or ``block_until_ready``) — dispatch alone returns before
    the work is done."""
    out = run()
    if sync is not None:
        sync(out)
    best = float("inf")
    for _ in range(max(1, windows)):
        t0 = time.perf_counter()
        for _ in range(max(1, reps)):
            out = run()
        if sync is not None:
            sync(out)
        best = min(best, (time.perf_counter() - t0) / max(1, reps))
    return best


def _telemetry():
    from .. import telemetry
    return telemetry.get_telemetry()


class AutotuneTable:
    """In-process config table backed by one JSON cache file.

    ``mode=None`` re-reads ``HETU_AUTOTUNE`` at every lookup, so tests
    and CLI runs can flip the env without rebuilding the table.
    """

    def __init__(self, path=None, mode=None):
        self.path = default_cache_path() if path is None else \
            os.fspath(path)
        self._mode = mode
        self._entries = None            # lazy: {key_str: entry dict}
        self._lock = threading.RLock()
        self._inflight = {}             # key_str -> Event (sweep runs)

    # -- persistence -----------------------------------------------------
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
            pass                        # cold or corrupt cache: resweep
        self._entries = entries
        return entries

    def save(self):
        """Atomic write (temp + rename): a concurrently-reading process
        sees the old file or the new one, never a torn write. Merges
        with whatever is on disk first (our entries win) so two
        processes tuning DIFFERENT kernels against one cache file don't
        drop each other's winners — the read-merge-write runs under an
        advisory flock on a sidecar .lock file so two ranks saving
        simultaneously serialize instead of racing the re-read
        (best-effort: platforms without fcntl fall back to the atomic
        rename alone, where a lost entry just re-sweeps next run)."""
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
                        disk = dict(doc.get("entries") or {})
                        disk.update(entries)
                        self._entries = entries = disk
                except (OSError, ValueError):
                    pass
                tmp = f"{self.path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump({"version": _VERSION, "entries": entries},
                              f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if lf is not None:
                    lf.close()      # closing releases the flock

    # -- table access ----------------------------------------------------
    def get(self, name, key):
        with self._lock:
            ent = self._load().get(_key_string(name, key))
        return _freeze(ent["config"]) if ent else None

    def put(self, name, key, config, picked_ms=None, candidates_ms=None):
        """Record a config directly (tests, offline tuning runs)."""
        ent = {"config": list(config) if isinstance(config, tuple)
               else config, "ts": time.time()}
        if picked_ms is not None:
            ent["picked_ms"] = round(float(picked_ms), 4)
        if candidates_ms is not None:
            ent["candidates_ms"] = candidates_ms
        with self._lock:
            self._load()[_key_string(name, key)] = ent
            self.save()

    def chosen(self, prefix=None):
        """{key_string: config} of every cached decision (optionally
        filtered by kernel-name prefix) — what the bench records into
        each round's artifact."""
        with self._lock:
            items = list(self._load().items())
        out = {}
        for ks, ent in items:
            name = ks.split("|", 2)[1] if ks.count("|") >= 2 else ks
            if prefix is None or name.startswith(prefix):
                out[ks] = _freeze(ent["config"])
        return out

    # -- the engine ------------------------------------------------------
    def lookup(self, name, key, candidates, measure, default=None):
        """The cached winner for (platform, name, key), sweeping
        ``candidates`` through ``measure(config) -> seconds`` when the
        mode calls for it. ``default`` is returned when tuning is off
        or on a use-cache-only miss. A candidate that raises is
        recorded with its error text; a sweep in which EVERY candidate
        raises re-raises (:class:`AutotuneSweepError`)."""
        mode = self._mode or tuning_mode()
        if mode == "off" or not candidates:
            return default
        tel = _telemetry()
        ks = _key_string(name, key)
        # cache check and in-flight registration share ONE locked
        # section: checking in one section and claiming ownership in a
        # later one would let a thread that missed just before the
        # previous owner persisted re-run the whole multi-second sweep
        wait_ev = ev = None
        with self._lock:
            if mode != "force":
                ent = self._load().get(ks)
                if ent is not None:
                    tel.inc("autotune_cache_hit")
                    return _freeze(ent["config"])
            if mode != "cache":
                ev = self._inflight.get(ks)
                if ev is None:
                    self._inflight[ks] = ev = threading.Event()
                else:
                    wait_ev, ev = ev, None
        if mode == "cache":
            tel.inc("autotune_cache_miss")
            return default
        if wait_ev is not None:
            # single-flight per key: a second thread first-tracing the
            # same shape waits for the running sweep instead of
            # duplicating seconds of device time
            wait_ev.wait(timeout=600.0)
            with self._lock:
                ent = self._load().get(ks)
            if ent is not None:
                tel.inc("autotune_cache_hit")
                return _freeze(ent["config"])
            raise AutotuneSweepError(
                f"autotune {ks}: the sweep this lookup waited on "
                f"produced no config (it failed in the owning thread, "
                f"or is still running after 600 s)")
        try:
            return self._sweep(name, ks, candidates, measure)
        finally:
            with self._lock:
                self._inflight.pop(ks, None)
            ev.set()

    def _sweep(self, name, key_str, candidates, measure):
        tel = _telemetry()
        tel.inc("autotune_sweeps")
        t0 = tel.clock()
        wall0 = time.perf_counter()
        results = {}
        state = {"cfg": None, "dt": float("inf")}

        def run_candidates():
            # measure() runs jax computations eagerly. Lookups usually
            # fire at TRACE time of the caller's step function, and jax
            # trace state is thread-local — a dedicated thread gives the
            # measurements a clean (non-tracing) context, so candidate
            # inputs stay concrete and each timed call really executes.
            for cfg in candidates:
                try:
                    dt = float(measure(cfg))
                except Exception as e:      # noqa: BLE001 — recorded below
                    # the compiler refused this candidate (tiling, VMEM)
                    # or it failed to run: keep the reason beside the
                    # timings so the trace and the cache file say why
                    results[str(cfg)] = f"{type(e).__name__}: {e}"[:400]
                    continue
                results[str(cfg)] = round(dt * 1000, 4)
                if dt < state["dt"]:
                    state["cfg"], state["dt"] = cfg, dt

        worker = threading.Thread(target=run_candidates,
                                  name="hetu-autotune-sweep")
        worker.start()
        worker.join()
        best_cfg, best_dt = state["cfg"], state["dt"]
        picked_ms = round(best_dt * 1000, 4) if best_cfg is not None \
            else None
        if tel.enabled:
            tel.complete("autotune_sweep", t0,
                         t0 + int((time.perf_counter() - wall0) * 1e9),
                         args={"kernel": str(name), "key": key_str,
                               "chosen": str(best_cfg),
                               "picked_ms": picked_ms,
                               "candidates_ms": results})
        if best_cfg is None:
            # nothing ran: a broken kernel or backend, not a tuning
            # outcome — surface it instead of compiling the default
            raise AutotuneSweepError(
                f"autotune {key_str}: all {len(candidates)} candidates "
                f"failed: {results}")
        ent = {"config": list(best_cfg) if isinstance(best_cfg, tuple)
               else best_cfg, "picked_ms": picked_ms,
               "candidates_ms": results, "ts": time.time()}
        with self._lock:
            self._load()[key_str] = ent
            try:
                self.save()
            except OSError:
                pass                    # read-only FS: in-process only
        return _freeze(best_cfg) if isinstance(best_cfg, (tuple, list)) \
            else best_cfg


_table = None
_table_lock = threading.Lock()


def get_table():
    """The process-global table (default cache path, env-driven mode)."""
    global _table
    with _table_lock:
        if _table is None:
            _table = AutotuneTable()
        return _table


def configure(path=None, mode=None):
    """Install a fresh process-global table and return it."""
    global _table
    with _table_lock:
        _table = AutotuneTable(path=path, mode=mode)
        return _table


def reset():
    """Drop the process-global table (tests)."""
    global _table
    with _table_lock:
        _table = None


def autotune(name, key, candidates, measure, default=None):
    """Module-level shorthand for ``get_table().lookup(...)``."""
    return get_table().lookup(name, key, candidates, measure,
                              default=default)
