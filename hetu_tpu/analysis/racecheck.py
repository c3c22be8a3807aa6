"""Dynamic twin of the HT6xx static pass: instrumented-lock harness.

The static verifier (``analysis/concurrency.py``) proves properties of
the locks it can *see*; this harness measures the locks that actually
run. Inside a ``racecheck()`` region, ``threading.Lock`` / ``RLock`` /
``Condition`` construct instrumented primitives that record, per lock:

* the **measured acquisition-order graph** — an edge A -> B each time a
  thread acquires B while holding A (instance-level, so two instances
  of the same creation site never fake a cycle);
* **held-while-blocking** time — how long a thread stalled acquiring
  another lock while already holding this one (the dynamic face of
  HT603);
* **contention** — acquisitions that could not take the fast path, with
  wait-time histograms, published through telemetry/metrics as
  ``lock_wait_ms`` / ``lock_hold_ms`` / ``lock_contended`` when a
  telemetry instance is passed.

On exit (or via :meth:`RaceCheck.assert_acyclic`) the observed graph is
checked for cycles: a cycle is a lock-order deadlock that merely hasn't
fired yet, reported with every lock's creation site. The stress tests
in ``tests/test_concurrency.py`` run the batcher, ingest engine
and PS-client paths under this harness at >=8-thread
load; the pytest ``racecheck`` fixture (tests/conftest.py) dumps the
measured graph JSON beside the test for CI failure artifacts.

Scope: only locks *created* inside the region are instrumented — enter
the harness before constructing the object under test. Stdlib
internals that allocate raw ``_thread`` locks (Thread bookkeeping,
queue.SimpleQueue) are untouched; ``concurrent.futures.Future``
conditions are created through ``threading.Condition`` and so are
observed — which is exactly what the batcher/ingest tests need.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time

__all__ = ["racecheck", "RaceCheck", "LockCycleError"]

_real_lock = threading.Lock
_real_rlock = threading.RLock
_real_condition = threading.Condition


class LockCycleError(AssertionError):
    """The measured acquisition-order graph has a cycle — a lock-order
    deadlock waiting for the right interleaving."""


def _creation_site():
    """file:line of the frame that called the lock factory, skipping
    this module and the threading machinery."""
    import sys
    f = sys._getframe(2)
    while f is not None:
        fname = f.f_code.co_filename
        if not fname.endswith(("racecheck.py", "threading.py",
                               "_base.py")):
            return f"{fname}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


class _TracedLock:
    """Wrapper over a raw lock recording order edges, contention, and
    hold durations into the owning :class:`RaceCheck`. ``reentrant``
    gives RLock semantics (only the outermost acquire/release records,
    matching how lock *ordering* is defined)."""

    def __init__(self, harness, reentrant=False):
        self._h = harness
        self._reentrant = reentrant
        self._inner = _real_rlock() if reentrant else _real_lock()
        self.lid, self.site = harness._register(self)

    # -- lock protocol ---------------------------------------------------
    def acquire(self, blocking=True, timeout=-1):
        if self._reentrant and self._inner._is_owned():
            return self._inner.acquire(blocking, timeout)
        contended = False
        t0 = 0.0
        if not self._inner.acquire(False):
            if not blocking:
                return False
            contended = True
            t0 = time.perf_counter()
            if not self._inner.acquire(True, timeout):
                return False
        wait_ms = (time.perf_counter() - t0) * 1e3 if contended else 0.0
        self._h._note_acquire(self, contended, wait_ms)
        return True

    def release(self):
        if self._reentrant and self._inner._is_owned():
            # only the outermost release ends the "held" interval
            outermost = self._inner._recursion_count() == 1 \
                if hasattr(self._inner, "_recursion_count") else None
            if outermost is None:
                # pre-3.12: probe by releasing then checking ownership
                self._inner.release()
                if self._inner._is_owned():
                    return
                self._h._note_release(self)
                return
            if outermost:
                self._h._note_release(self)
            self._inner.release()
            return
        self._h._note_release(self)
        self._inner.release()

    def locked(self):
        return self._inner.locked()

    def _is_owned(self):
        """threading.Condition copies this at construction; without it
        the stdlib fallback probes with acquire(False), which SUCCEEDS
        on a reentrant lock the caller owns and makes cond.wait()
        raise 'cannot wait on un-acquired lock'."""
        if self._reentrant:
            return self._inner._is_owned()
        # plain lock: same locked-by-anyone approximation as stdlib
        return self._inner.locked()

    def _release_save(self):
        """Condition.wait() protocol: fully release (ALL recursion
        levels of an RLock) and return restore state. Without the
        passthrough, the stdlib fallback releases ONE level — a
        reentrantly-held traced RLock would stay held through wait()
        and deadlock every notifier, failing code that is correct
        under real locks."""
        self._h._note_release(self)
        if self._reentrant:
            return self._inner._release_save()
        self._inner.release()
        return None

    def _acquire_restore(self, state):
        if self._reentrant:
            self._inner._acquire_restore(state)
        else:
            self._inner.acquire()
        self._h._note_acquire(self, False, 0.0)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"<TracedLock #{self.lid} {self.site}>"


class RaceCheck:
    """Recording sink + patcher; use through :func:`racecheck`."""

    def __init__(self, name="racecheck", telemetry=None):
        self.name = name
        self.telemetry = telemetry
        self._mu = _real_lock()         # leaf lock: never held while
        self._tls = threading.local()   # acquiring an instrumented one
        self._locks = {}                # lid -> stats dict
        self._edges = {}                # (lid_a, lid_b) -> count
        self._nextid = 0
        self._patched = False

    # -- recording -------------------------------------------------------
    def _register(self, lock):
        site = _creation_site()
        with self._mu:
            lid = self._nextid
            self._nextid += 1
            self._locks[lid] = {"site": site, "acquires": 0,
                                "contended": 0, "wait_ms_max": 0.0,
                                "wait_ms_sum": 0.0, "hold_ms_max": 0.0,
                                "hold_ms_sum": 0.0,
                                "held_blocking_ms": 0.0}
        return lid, site

    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _note_acquire(self, lock, contended, wait_ms):
        stack = self._stack()
        tel = self.telemetry
        with self._mu:
            rec = self._locks[lock.lid]
            rec["acquires"] += 1
            if contended:
                rec["contended"] += 1
                rec["wait_ms_sum"] += wait_ms
                rec["wait_ms_max"] = max(rec["wait_ms_max"], wait_ms)
            for held, _t in stack:
                if held.lid != lock.lid:
                    key = (held.lid, lock.lid)
                    self._edges[key] = self._edges.get(key, 0) + 1
                    if contended:
                        # the dynamic HT603: stalled on `lock` while
                        # holding `held`
                        self._locks[held.lid]["held_blocking_ms"] += \
                            wait_ms
        stack.append((lock, time.perf_counter()))
        if contended and tel is not None and tel.enabled:
            # contended acquires only: the fast path would flood the
            # wait histogram with zeros and bury the convoying lock
            self._tel_hook(lambda: (tel.observe("lock_wait_ms", wait_ms),
                                    tel.inc("lock_contended")))

    def _note_release(self, lock):
        stack = self._stack()
        hold_ms = None
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is lock:
                hold_ms = (time.perf_counter() - stack[i][1]) * 1e3
                del stack[i]
                break
        if hold_ms is None:
            return                      # released on a different thread
        with self._mu:
            rec = self._locks[lock.lid]
            rec["hold_ms_sum"] += hold_ms
            rec["hold_ms_max"] = max(rec["hold_ms_max"], hold_ms)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            self._tel_hook(lambda: tel.observe("lock_hold_ms", hold_ms))

    def _tel_hook(self, fn):
        """Publish through telemetry without reentering ourselves: the
        registry's own (traced) lock would otherwise recurse
        acquire -> observe -> acquire and self-deadlock."""
        if getattr(self._tls, "in_hook", False):
            return
        self._tls.in_hook = True
        try:
            fn()
        finally:
            self._tls.in_hook = False

    # -- patching --------------------------------------------------------
    def _patch(self):
        harness = self

        def make_lock():
            return _TracedLock(harness, reentrant=False)

        def make_rlock():
            return _TracedLock(harness, reentrant=True)

        def make_condition(lock=None):
            return _real_condition(lock if lock is not None
                                   else make_rlock())

        threading.Lock = make_lock
        threading.RLock = make_rlock
        threading.Condition = make_condition
        self._patched = True

    def _unpatch(self):
        threading.Lock = _real_lock
        threading.RLock = _real_rlock
        threading.Condition = _real_condition
        self._patched = False

    # -- results ---------------------------------------------------------
    def result(self):
        """{locks: {lid: stats}, edges: [{from, to, site_from, site_to,
        count}]} — the measured lock graph artifact."""
        with self._mu:
            locks = {lid: dict(rec) for lid, rec in self._locks.items()}
            edges = [{"from": a, "to": b,
                      "site_from": locks[a]["site"],
                      "site_to": locks[b]["site"], "count": n}
                     for (a, b), n in sorted(self._edges.items())]
        return {"name": self.name, "locks": locks, "edges": edges}

    def to_json(self):
        return json.dumps(self.result(), indent=1, sort_keys=True)

    def find_cycle(self):
        """A list of lids forming a cycle in the measured acquisition
        graph, or None."""
        with self._mu:
            # snapshot under the lock: a daemon worker still inside the
            # patch window can _register mid-scan otherwise
            graph = {}
            for a, b in self._edges:
                graph.setdefault(a, set()).add(b)
            lids = list(self._locks)
        WHITE, GRAY, BLACK = 0, 1, 2
        color = {lid: WHITE for lid in lids}
        parent = {}

        def dfs(u):
            color[u] = GRAY
            for v in graph.get(u, ()):
                if color.get(v, WHITE) == GRAY:
                    cycle = [v, u]
                    w = u
                    while w != v:
                        w = parent[w]
                        cycle.append(w)
                    return list(reversed(cycle))
                if color.get(v, WHITE) == WHITE:
                    parent[v] = u
                    hit = dfs(v)
                    if hit:
                        return hit
            color[u] = BLACK
            return None

        for lid in list(graph):
            if color.get(lid, WHITE) == WHITE:
                hit = dfs(lid)
                if hit:
                    return hit
        return None

    def assert_acyclic(self):
        """Raise :class:`LockCycleError` when the *observed* lock graph
        has a cycle — the harness equivalent of a static HT602."""
        cycle = self.find_cycle()
        if cycle is None:
            return
        with self._mu:
            sites = {lid: self._locks[lid]["site"] for lid in cycle}
        names = " -> ".join(
            f"lock#{lid} ({sites[lid]})" for lid in cycle)
        raise LockCycleError(
            f"[{self.name}] measured lock acquisition graph has a "
            f"cycle: {names} — two threads taking these locks in "
            f"opposite orders will deadlock (dynamic HT602)")

    def contention(self):
        """{site: contended count} for quick assertions in tests."""
        with self._mu:
            out = {}
            for rec in self._locks.values():
                out[rec["site"]] = out.get(rec["site"], 0) \
                    + rec["contended"]
        return out


_active = None
_active_mu = _real_lock()


@contextlib.contextmanager
def racecheck(name="racecheck", telemetry=None, assert_acyclic=True):
    """Instrument every lock created in this region; on exit, verify
    the measured acquisition-order graph is acyclic (unless
    ``assert_acyclic=False`` — then call :meth:`RaceCheck.\
assert_acyclic` yourself after saving the artifact).

    ::

        with racecheck("batcher") as rc:
            b = MicroBatcher(fn)          # locks created here are traced
            hammer_from_many_threads(b)
            b.close()
        # exiting asserts acyclicity; rc.result() is the lock graph
    """
    global _active
    with _active_mu:
        if _active is not None:
            raise RuntimeError("racecheck() regions do not nest: the "
                               "lock patch is process-global")
        _active = rc = RaceCheck(name=name, telemetry=telemetry)
    rc._patch()
    try:
        yield rc
    finally:
        rc._unpatch()
        with _active_mu:
            _active = None
    if assert_acyclic:
        rc.assert_acyclic()
