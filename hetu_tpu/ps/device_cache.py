"""Device-resident (HBM) embedding cache with bounded staleness.

TPU-native analogue of the reference's cache-enabled embedding path
(python/hetu/cstable.py over hetu_cache; the HET design the reference
implements for trillion-parameter tables). The reference caches hot rows
in GPU memory and syncs with the parameter server under a staleness
bound; here the cache rows live in HBM as a regular jit-threaded
parameter, so the steady-state training step touches them with zero
host<->device traffic:

  * lookups gather from the cache array inside the compiled step,
  * the worker optimizer applies the local sparse update in-graph,
  * raw gradients also scatter-add into an HBM accumulator (``acc``
    state), and every ``push_bound`` steps the accumulated rows drain to
    the PS server on a background thread (PushEmbedding applies the
    server optimizer and bumps per-row versions),
  * misses / stale rows are fetched with SparsePull / SyncEmbedding and
    scattered into the cache by an async dispatched fill — the transfer
    rides the dispatch queue, never a blocking round trip.

Host side this module keeps only the id<->slot mapping, per-slot
versions and dirty counters (numpy); all row data stays on device.

Reference parity: python/hetu/cstable.py:19-211 (facade),
ps-lite cache semantics via SyncEmbedding/PushEmbedding
(hetu_tpu/ps/native/ps_server.cc kSyncEmbedding/kPushEmbedding).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import health as _health


# -- device-side helpers (shape-bucketed so jit cache stays small) ---------

@functools.partial(jax.jit, donate_argnums=0)
def _fill_rows(cache, slots, rows):
    return cache.at[slots].set(rows)


@jax.jit
def _gather_rows(arr, slots):
    return arr[slots]


@jax.jit
def _gather_rows_bf16(arr, slots):
    # drain compression: the gradient sums leave HBM as bf16 (half the
    # device-to-host bytes of the drain)
    return arr[slots].astype(jnp.bfloat16)


@functools.partial(jax.jit, donate_argnums=0)
def _zero_rows(arr, slots):
    return arr.at[slots].set(0.0)


def _pad_pow2(n, minimum=8):
    """Next power-of-two bucket >= n (bounds jit-cache churn from
    variable miss/drain counts)."""
    b = minimum
    while b < n:
        b *= 2
    return b


class DeviceCacheTable:
    """Host-side bookkeeping for one device-cached embedding table.

    The cache array itself lives in ``executor.params[cache_sid]`` (shape
    ``[capacity + 1, width]``; the last row is a scratch slot used as the
    scatter target for padding) and the push accumulator in
    ``executor.state[cache_sid]["acc"]``.
    """

    def __init__(self, table_node, cache_node, client, *, capacity, width,
                 rows, push_bound=100, pull_bound=100, nworkers=1,
                 drain_compress=False):
        self.table_node = table_node
        self.cache_node = cache_node
        self.cache_sid = str(cache_node.id)
        self.tid = table_node.id
        self.client = client
        self.capacity = int(capacity)
        self.width = int(width)
        self.rows = int(rows)
        self.push_bound = int(push_bound)
        self.pull_bound = int(pull_bound)
        self.nworkers = int(nworkers)
        self.drain_compress = bool(drain_compress)
        # owning executor's health monitor (stamped by the PS runtime
        # at registration) — scopes staleness observations so two
        # executors in one process never cross-attribute; None falls
        # back to the module broadcast (single-executor processes)
        self.health_monitor = None

        # id -> slot map: direct-indexed for tables that fit, dict above
        # (a 33.7M-row Criteo map is a 135MB int32 array; a trillion-row
        # table falls back to hashing)
        if self.rows <= (1 << 26):
            self._slot_of = np.full(self.rows, -1, np.int32)
        else:
            self._slot_of = None
            self._slot_dict = {}
        self.id_of = np.full(self.capacity, -1, np.int64)
        self.ver = np.zeros(self.capacity, np.int64)    # client row version
        self.upd = np.zeros(self.capacity, np.int64)    # updates since push
        self.dirty = np.zeros(self.capacity, bool)
        self._clock = np.zeros(self.capacity, bool)     # recency bit
        self._pinned = np.zeros(self.capacity, bool)    # current batch's rows
        self._hand = 0
        self._n_used = 0
        self.steps_since_drain = 0
        # perf counters (reference cstable.py:163-187)
        self.hits = 0
        self.misses = 0
        self.evicts = 0
        self.pushed_rows = 0
        self.pulled_rows = 0

    # -- id<->slot -------------------------------------------------------
    def _lookup_slots(self, uniq_ids):
        if self._slot_of is not None:
            return self._slot_of[uniq_ids]
        d = self._slot_dict
        return np.fromiter((d.get(int(i), -1) for i in uniq_ids),
                           np.int32, count=len(uniq_ids))

    def _set_slot(self, eid, slot):
        if self._slot_of is not None:
            self._slot_of[eid] = slot
        elif slot < 0:
            self._slot_dict.pop(int(eid), None)
        else:
            self._slot_dict[int(eid)] = slot

    def _alloc(self, n, inline_drain):
        """Allocate ``n`` slots, evicting clean rows by CLOCK. Rows the
        current batch touches are pinned and never candidates; dirty rows
        are never evicted silently — if only dirty rows remain, the
        caller drains first (``inline_drain`` callback)."""
        out = np.empty(n, np.int64)
        got = 0
        # fast path: never-used slots
        while got < n and self._n_used < self.capacity:
            s = self._n_used
            self._n_used += 1
            self._pinned[s] = True
            out[got] = s
            got += 1
        scanned = 0
        drained = False
        limit = 2 * self.capacity
        while got < n:
            if scanned >= limit:
                if drained:
                    raise RuntimeError(
                        f"device cache for tensor {self.tid} has capacity "
                        f"{self.capacity} but one batch needs more unique "
                        f"rows — raise cache_capacity")
                # every candidate is dirty: push pending updates, retry
                inline_drain()
                drained = True
                scanned = 0
                continue
            s = self._hand
            self._hand = (self._hand + 1) % self.capacity
            scanned += 1
            if self._pinned[s]:
                continue
            if self._clock[s]:
                self._clock[s] = False
                continue
            if self.dirty[s]:
                continue
            old = self.id_of[s]
            if old >= 0:
                self._set_slot(old, -1)
                self.evicts += 1
            self.id_of[s] = -1
            self._pinned[s] = True
            out[got] = s
            got += 1
        return out

    # -- per-step assignment ----------------------------------------------
    def assign(self, ids, inline_drain):
        """Map a batch of ids to slots, allocating for misses.

        Returns ``(slots, miss_ids, miss_slots, uniq_slots)`` — slots has
        ids' shape (int32); miss rows must be fetched and scattered into
        the cache before (in dispatch order) the step consumes it.
        """
        flat = np.asarray(ids).ravel().astype(np.int64)
        uniq, inv = np.unique(flat, return_inverse=True)
        slots = self._lookup_slots(uniq)
        miss = slots < 0
        n_miss = int(miss.sum())
        self.hits += len(uniq) - n_miss
        self.misses += n_miss
        # this batch's resident rows must survive its own miss evictions
        self._pinned[slots[~miss]] = True
        if n_miss:
            miss_ids = uniq[miss]
            new_slots = self._alloc(n_miss, inline_drain)
            if self._slot_of is not None:
                self._slot_of[miss_ids] = new_slots.astype(np.int32)
            else:
                for eid, s in zip(miss_ids, new_slots):
                    self._slot_dict[int(eid)] = int(s)
            self.id_of[new_slots] = miss_ids
            self.ver[new_slots] = 0
            self.upd[new_slots] = 0
            slots[miss] = new_slots
            self.pulled_rows += n_miss
        else:
            miss_ids = np.empty(0, np.int64)
            new_slots = np.empty(0, np.int64)
        self._clock[slots] = True
        # pins persist until release_pins(): a table consumed by several
        # lookups in one step must not evict slots an earlier assign()
        # already baked into its slots feed
        full = slots[inv].reshape(np.shape(ids)).astype(np.int32)
        return full, miss_ids, new_slots, slots

    def assign_block(self, ids_arr, inline_drain):
        """Vectorized :meth:`assign` for a whole scan block (round-3 review
        weak #6: the per-step unique/scatter slot map was the next WDL
        host hotspot). The block executes as ONE compiled scan with the
        cache array threaded through it, so every row any step touches
        must be resident for the whole block — the residency set is
        identical to running :meth:`assign` per step with pins held,
        which is exactly what this replaces (one unique / one alloc /
        one miss-fill instead of ``nsteps`` of each).

        ``ids_arr`` is ``[nsteps, ...]``.  Returns ``(slots int32 of
        ids_arr's shape, miss_ids, miss_slots, uniq_slots, counts)``
        where ``counts[i]`` is the number of steps touching unique row
        ``i`` — per-step upd/version accounting for the staleness
        protocol is preserved bit-for-bit.
        """
        ids_arr = np.asarray(ids_arr)
        nsteps = ids_arr.shape[0]
        flat = ids_arr.reshape(nsteps, -1).astype(np.int64)
        uniq, inv = np.unique(flat, return_inverse=True)
        inv = inv.reshape(flat.shape)
        nuniq = len(uniq)
        # dedup (step, row) pairs -> how many steps touch each row
        pairs = np.unique(inv + np.arange(nsteps)[:, None] * nuniq)
        counts = np.bincount(pairs % nuniq, minlength=nuniq)
        slots = self._lookup_slots(uniq)
        miss = slots < 0
        n_miss = int(miss.sum())
        # a block row's first touch is the miss; later steps re-hit it
        self.hits += int(counts.sum()) - n_miss
        self.misses += n_miss
        self._pinned[slots[~miss]] = True
        if n_miss:
            miss_ids = uniq[miss]
            new_slots = self._alloc(n_miss, inline_drain)
            if self._slot_of is not None:
                self._slot_of[miss_ids] = new_slots.astype(np.int32)
            else:
                for eid, s in zip(miss_ids, new_slots):
                    self._slot_dict[int(eid)] = int(s)
            self.id_of[new_slots] = miss_ids
            self.ver[new_slots] = 0
            self.upd[new_slots] = 0
            slots[miss] = new_slots
            self.pulled_rows += n_miss
        else:
            miss_ids = np.empty(0, np.int64)
            new_slots = np.empty(0, np.int64)
        self._clock[slots] = True
        full = slots[inv].reshape(ids_arr.shape).astype(np.int32)
        return full, miss_ids, new_slots, slots, counts

    def release_pins(self):
        """End-of-step: this step's resident rows become evictable."""
        self._pinned[:] = False

    def note_update(self, uniq_slots, counts=1):
        """Record that the step (or block: ``counts`` from
        :meth:`assign_block`) just dispatched updates to these rows
        (called once per lookup; step accounting is ``note_step``)."""
        self.dirty[uniq_slots] = True
        self.upd[uniq_slots] += counts
        self.ver[uniq_slots] += counts

    def note_step(self):
        self.steps_since_drain += 1

    # -- staleness refresh (multi-worker) ----------------------------------
    def stale_check(self, uniq_ids, uniq_slots):
        """SyncEmbedding: rows whose server version ran more than
        ``pull_bound`` ahead of ours come back refreshed. Returns
        ``(slots_to_fill, rows)`` or ``(None, None)``. Single-worker
        tables skip the RPC — no other writer exists."""
        if self.nworkers <= 1:
            return None, None
        vers = self.ver[uniq_slots].copy()
        out = np.zeros((len(uniq_ids), self.width), np.float32)
        n_ref = self.client.sync_embedding(
            self.tid, self.pull_bound, uniq_ids, vers, out, self.width)
        if not n_ref:
            return None, None
        pos = np.nonzero(vers != self.ver[uniq_slots])[0]
        if len(pos) and (self.health_monitor is not None
                         or _health.active()):
            # observed read staleness: how many server updates each
            # refreshed row actually ran behind before SyncEmbedding
            # caught it up — the paper's consistency knob, measured
            # (telemetry/health.py; pull_bound is the configured bound)
            _health.observe_staleness(
                "pull", self.tid, vers[pos] - self.ver[uniq_slots][pos],
                self.pull_bound, monitor=self.health_monitor)
        self.ver[uniq_slots[pos]] = vers[pos]
        self.pulled_rows += len(pos)
        return uniq_slots[pos], out[pos]

    # -- combined drain + refresh (kPushSyncEmbedding) ---------------------
    def push_sync(self, push_ids, push_rows, upds, uniq_ids, uniq_slots):
        """One RPC per shard that both applies the accumulated grads
        (PushEmbedding semantics: server optimizer runs, per-row
        versions bump by ``upds``) and refreshes the rows whose server
        version ran more than ``pull_bound`` ahead (SyncEmbedding
        semantics). The caller already claimed the dirty set with
        :meth:`take_dirty`; read bookkeeping mirrors
        :meth:`stale_check`. Returns ``(slots_to_fill, rows)`` or
        ``(None, None)``."""
        if self.nworkers > 1:
            push_rows = push_rows / self.nworkers
        vers = self.ver[uniq_slots].copy()
        out = np.zeros((len(uniq_ids), self.width), np.float32)
        n_ref = self.client.push_sync_embedding(
            self.tid, push_ids, push_rows, upds, self.pull_bound,
            uniq_ids, vers, out, self.width)
        if not n_ref:
            return None, None
        pos = np.nonzero(vers != self.ver[uniq_slots])[0]
        if len(pos) and (self.health_monitor is not None
                         or _health.active()):
            _health.observe_staleness(
                "pull", self.tid, vers[pos] - self.ver[uniq_slots][pos],
                self.pull_bound, monitor=self.health_monitor)
        self.ver[uniq_slots[pos]] = vers[pos]
        self.pulled_rows += len(pos)
        return uniq_slots[pos], out[pos]

    # -- drain --------------------------------------------------------------
    def take_dirty(self):
        """Claim the dirty set for a push; resets counters. Returns
        ``(slots int64[n], ids int64[n], upd_counts int64[n])``."""
        slots = np.nonzero(self.dirty)[0]
        ids = self.id_of[slots]
        upds = self.upd[slots].copy()
        self.dirty[slots] = False
        self.upd[slots] = 0
        self.steps_since_drain = 0
        keep = ids >= 0
        if keep.any() and (self.health_monitor is not None
                           or _health.active()):
            # observed write staleness: per-row local updates the
            # server had not seen when this drain claimed them. A count
            # past push_bound means the drain cadence failed to hold
            # the configured bound (deferred drains, long scan blocks)
            # — the health monitor trips on those (kind="staleness")
            _health.observe_staleness("push", self.tid, upds[keep],
                                      self.push_bound,
                                      monitor=self.health_monitor)
        return slots[keep].astype(np.int64), ids[keep], upds[keep]

    def invalidate(self):
        """Drop every cached row (e.g. after a checkpoint load replaced
        the server values). Pending updates must be drained first."""
        assert not self.dirty.any(), \
            "invalidate() with un-drained updates would lose them"
        if self._slot_of is not None:
            self._slot_of[:] = -1
        else:
            self._slot_dict.clear()
        self.id_of[:] = -1
        self.ver[:] = 0
        self.upd[:] = 0
        self._clock[:] = False
        self._pinned[:] = False
        self._hand = 0
        self._n_used = 0

    @property
    def perf(self):
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "evicts": self.evicts, "size": self._n_used,
                "pushed_rows": self.pushed_rows,
                "pulled_rows": self.pulled_rows,
                "miss_rate": self.misses / total if total else 0.0}


def pad_fill(cache, slots, rows, scratch_slot):
    """Scatter ``rows`` into ``cache`` at ``slots``, padding the batch to
    a power-of-two bucket (pad entries target the scratch row) so the jit
    cache sees O(log n) distinct shapes."""
    n = len(slots)
    b = _pad_pow2(n)
    pslots = np.full(b, scratch_slot, np.int32)
    pslots[:n] = slots
    prows = np.zeros((b, rows.shape[1]), np.float32)
    prows[:n] = rows
    return _fill_rows(cache, pslots, prows)


def pad_gather_zero(acc, slots, scratch_slot, compress=False):
    """Gather accumulator rows at ``slots`` then zero them, padded to a
    bucket. Returns (new_acc, gathered_rows_device, n_real).

    ``compress=True`` casts the gathered grad sums to bf16 on device —
    halving the drain's device->host bytes; the server applies SGD at
    f32 after widening, so the worker's own full-precision cache is
    untouched. What the transfer costs on an attached chip: not
    measured."""
    n = len(slots)
    b = _pad_pow2(n)
    pslots = np.full(b, scratch_slot, np.int64)
    pslots[:n] = slots
    pslots_dev = jnp.asarray(pslots)
    gather = _gather_rows_bf16 if compress else _gather_rows
    rows = gather(acc, pslots_dev)
    new_acc = _zero_rows(acc, pslots_dev)
    # transfer only the claimed rows, padded to a coarse chunk (a pow2
    # pad can double the D2H bytes; a 2048-row chunk wastes <1 chunk
    # while keeping the slice's jit cache small)
    m = min(b, -(-n // 2048) * 2048)
    if m < b:
        rows = rows[:m]
    return new_acc, rows, n
