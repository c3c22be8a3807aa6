"""Executor-side PS runtime: schedules host push/pull around the compiled
step (reference parity: the d2h-stream PS path of SubExecutor,
executor.py:1800-1825, and ParameterServerCommunicateOp's
_compute_asp_prefetch, ParameterServerCommunicate.py:38-70).

Two embedding paths:

* **host path** (default): per step, sparse-pull the rows this batch
  needs and feed them to the compiled step; push grads after. Every
  transfer is on the critical path — correct and simple, used by BSP
  and small tables.
* **device-cache path** (``cstable_policy="Device"``, the HET design):
  rows live in HBM as a jit-threaded parameter, the worker optimizer
  applies local updates in-graph, and the runtime only (a) maps ids to
  cache slots on the host, (b) scatters missed/stale rows in with async
  dispatches, and (c) drains the on-device gradient accumulator to the
  server on a background thread every ``cache_bound`` steps. The
  steady-state step does **zero** synchronous host<->device transfers —
  the property that matters when the host link is high-latency.

Dense PS parameters follow the same split: synchronous DDPushPull per
step under BSP, or a pipelined accumulate-and-swap under ASP (grads sum
on device; a background thread round-trips the sum through the server's
optimizer and the refreshed parameter swaps in one or two steps later —
the reference's asynchronous PS training mode).
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..ndarray import IndexedSlices
from .device_cache import DeviceCacheTable, pad_fill, pad_gather_zero


def _opt_spec(optimizer):
    """(server opt name, lrs[]) from a worker optimizer instance."""
    name = optimizer.name
    lr = float(optimizer.learning_rate)
    if name == "SGD":
        return "SGD", [lr]
    if name == "Momentum":
        kind = "Nesterov" if getattr(optimizer, "nesterov", False) \
            else "Momentum"
        return kind, [lr, float(optimizer.momentum)]
    if name == "AdaGrad":
        return "AdaGrad", [lr, float(optimizer.eps)]
    if name in ("Adam", "AdamW"):
        # lrs[4] (if present) is decoupled weight decay, applied by the
        # server's Adam after the moment update
        lrs = [lr, float(optimizer.beta1), float(optimizer.beta2),
               float(optimizer.epsilon)]
        if name == "AdamW":
            lrs.append(float(optimizer.weight_decay))
        return "Adam", lrs
    return "SGD", [lr]


@jax.jit
def _zeros_like_tree(t):
    return jax.tree_util.tree_map(jax.numpy.zeros_like, t)


class PSRuntime:
    def __init__(self, executor, config):
        self.executor = executor
        self.config = config
        self.client = config.ps_comm
        self.registered = set()
        self.caches = {}        # param.id -> CacheSparseTable (host cache)
        self.device_tables = {}  # table.id -> DeviceCacheTable
        self._sub_cached = {}   # sub.name -> [(table_rt, ids, slots), ...]
        # ASP pipelining (reference _compute_asp_prefetch): readback+push
        # of grads runs on this pool so the main loop can issue the next
        # step immediately; enabled by config.prefetch unless BSP (which
        # must see every push before its barrier)
        self._push_pool = None
        self._pending_push = []
        self.updates_dropped = False   # drain() skipped post-shutdown
        if config.prefetch and not config.bsp:
            # daemon workers with a bounded-join shutdown (ingest.py):
            # a push wedged in an RPC against a dead server must never
            # deadlock close()/interpreter exit (HT603/HT604)
            from ..ingest import DaemonPool
            self._push_pool = DaemonPool(max_workers=2,
                                         thread_name_prefix="hetu-ps-push")
        # dense HET pipeline (unified with the embedding cache): dense PS
        # params are locally optimizer-updated in-graph with grads
        # accumulated in HBM state (optimizer.backward_hook); the drain
        # here pushes the sums and, multi-worker, pulls rebased values
        self._dense_steps = 0
        self._dense_future = None
        self._dense_ready = None     # {sid: np value} to swap in
        # _dense_ready is handed from the push-pool cycle to the step
        # loop; _times_mu guards the phase counters the ingest worker's
        # prep phases and the step loop both accumulate (both were
        # HT601 lockset findings)
        self._dense_mu = threading.Lock()
        self._times_mu = threading.Lock()
        # step-phase timing (review: make the residual gap attributable)
        self.times = {"slot_assign": 0.0, "miss_fill": 0.0, "refresh": 0.0,
                      "dispatch": 0.0, "drain_submit": 0.0, "dense": 0.0,
                      "host_pull": 0.0, "sync_push": 0.0,
                      "feed_ingest": 0.0, "prefetch": 0.0,
                      "repull": 0.0}
        # pipelined-stream bookkeeping (run_stream_pipelined): which
        # table ids speculative pulls read from (None = not streaming),
        # the sparse ids the LAST run_step pushed — the driver merges
        # them into every in-flight prep's dirty set so an overlapped
        # pull never serves a pre-push row — and the ids of ASP pushes
        # still in flight on the async pool: those seed NEW preps'
        # dirty sets (a pull issued after the push was *submitted* can
        # still read the pre-push row until the push is flushed)
        self._track_push_tids = None
        self._last_pushed = {}
        self._inflight_pushed = {}
        # embedding tables converted to tiered row storage
        # (HETU_PS_STORE_* knobs): their measured-hot id set re-pins
        # into the server's DRAM pool at drain cadence
        self._store_tids = set()
        self._closed = False
        # eager registration so save()/load() work before the first step
        self._register_all()
        import atexit
        atexit.register(self._atexit)

    @contextlib.contextmanager
    def _phase(self, name):
        """One PS step phase: accumulates host seconds into the legacy
        ``times`` counter (StepLogger deltas, ``phase_breakdown``) AND — when
        telemetry is on — emits a ``ps:<name>`` span plus a per-phase
        latency histogram, so PS RPC cost shows up on the Perfetto
        timeline next to the device dispatches it delays."""
        tel = self.config.telemetry
        t0n = tel.clock() if tel.enabled else 0
        t0 = time.perf_counter()
        # black box: a PS phase that never completes (server hang, dead
        # van) is a pending flight entry naming the phase (flight.py);
        # the string concat only happens on the enabled path
        frec = (tel.flight.start("ps", "ps:" + name)
                if tel.enabled else None)
        try:
            yield
        finally:
            with self._times_mu:    # prep phases run on the ingest worker
                self.times[name] += time.perf_counter() - t0
            tel.flight_complete(frec)
            if tel.enabled:
                t1n = tel.clock()
                tel.complete("ps:" + name, t0n, t1n)
                tel.observe(f"ps_{name}_ms", (t1n - t0n) / 1e6)

    # ------------------------------------------------------------------
    def _register_all(self):
        fresh = False
        for op in self.config.ps_nodes:
            if not hasattr(op, "parameter"):
                continue
            if self._register_one(op):
                fresh = True
        for entry in self.config.device_cache_tables:
            if self._register_device_table(entry):
                fresh = True
        for param, opt in self.config.ps_dense_cached:
            if param.id in self.registered:
                continue
            opt_name, lrs = _opt_spec(opt)
            self.client.init_tensor(param.id, tuple(param.shape), kind=0,
                                    opt=opt_name, lrs=lrs)
            sid = str(param.id)
            value = self.executor.params.get(sid)
            if value is None:
                value = param.initial_value(seed=self.config.seed)
            self.client.set_param(param.id, np.asarray(value))
            self.registered.add(param.id)
            fresh = True
        if fresh and self.config.bsp:
            self.client.barrier()

    def _register_one(self, op):
        """Register one PS-managed parameter on the server; returns True
        when it was newly registered."""
        opt = getattr(op, "optimizer_info", None)
        opt_name, lrs = _opt_spec(opt) if opt is not None else ("SGD", [0.1])
        param = op.parameter
        if param.id in self.registered:
            return False
        tid = param.id
        shape = tuple(param.shape)
        if param.is_embed:
            kind = 2 if self.config.cstable_policy else 1
            init = None
            if param.initializer is not None:
                init = param.initializer.dist_spec()
            if init is not None:
                # on-server init: the table never materializes on the
                # worker (trillion-parameter scaling path)
                self.client.init_tensor(
                    tid, shape, kind=kind, init=init,
                    seed=self.config.seed + param.id, opt=opt_name,
                    lrs=lrs)
            else:
                self.client.init_tensor(tid, shape, kind=kind,
                                        opt=opt_name, lrs=lrs)
                self.client.set_param(tid, param.initial_value(
                    seed=self.config.seed))
            self._maybe_store_config(tid, opt_name)
            if self.config.cstable_policy:
                from ..cstable import CacheSparseTable
                bound = self.config.cache_bound
                cache = CacheSparseTable(
                    tid, shape[0], int(np.prod(shape[1:])),
                    limit=max(1, shape[0] // 5),
                    policy=self.config.cstable_policy,
                    pull_bound=bound, push_bound=bound)
                # scope staleness observations to this executor's
                # monitor (telemetry/health.py)
                cache.health_monitor = self.config.health_monitor
                self.caches[param.id] = cache
        else:
            self.client.init_tensor(tid, shape, kind=0, opt=opt_name,
                                    lrs=lrs)
            sid = str(param.id)
            value = self.executor.params.get(sid)
            if value is None:
                value = param.initial_value(seed=self.config.seed)
            self.client.set_param(tid, np.asarray(value))
        self.registered.add(param.id)
        return True

    def _maybe_store_config(self, tid, opt_name):
        """Apply the tiered/quantized row-store env knobs to a freshly
        registered embedding table (``HETU_PS_STORE_DTYPE`` = f32 | f16
        | int8, ``HETU_PS_STORE_DRAM_ROWS`` resident rows per shard,
        ``HETU_PS_STORE_DIR`` spill directory). Slot-carrying
        optimizers keep flat f32 storage — the tiered store tracks only
        the row payload, not Momentum/Adam slots, so the server refuses
        them (-4); skip with a warning instead of tripping that."""
        import os
        dt = os.environ.get("HETU_PS_STORE_DTYPE")
        dram = os.environ.get("HETU_PS_STORE_DRAM_ROWS")
        if dt is None and dram is None:
            return
        if opt_name not in ("SGD", "None"):
            import sys
            print(f"[hetu-ps] table {tid}: HETU_PS_STORE_* ignored — "
                  f"tiered rows need a stateless server optimizer, "
                  f"got {opt_name}", file=sys.stderr)
            return
        hm = self.config.health_monitor
        hot = hm.hot_ids(tid) if hm is not None else ()
        self.client.store_config(
            tid, dtype=dt or "f32", dram_rows=int(dram) if dram else -1,
            hot_ids=hot)
        self._store_tids.add(tid)

    def _refresh_hot_rows(self, tid, k=1024):
        """Re-pin the measured-hot ids (PR 9 skew telemetry) into the
        tiered store's DRAM pool — repeat StoreConfig on a tiered table
        is a read-promotion pass, so placement follows the observed id
        distribution instead of a guessed prefix."""
        hm = self.config.health_monitor
        if hm is None or tid not in self._store_tids:
            return
        hot = hm.hot_ids(tid, k)
        if len(hot):
            self.client.store_config(tid, hot_ids=hot)

    def _export_store_gauges(self):
        """Live tiered/replicated PS gauges, refreshed on the drain
        cadence (one kStoreStats round per tiered table every
        push_bound steps — off the per-step path): per-table
        ``ps_table_<tid>_spill_hit_rate`` / ``ps_table_<tid>_row_bytes``
        and the fleet-wide ``ps_repl_queue_depth`` backlog. Gauges are
        informational (the fleet timeline rides them into its
        records)."""
        tel = self.config.telemetry
        if not tel.enabled or not self._store_tids:
            return
        depth = 0
        for tid in sorted(self._store_tids):
            try:
                st = self.client.store_stats(tid)
            except AssertionError:
                continue        # shard mid-failover: skip this window
            hits = st["dram_hits"] + st["spill_hits"]
            if hits:
                tel.set_gauge(f"ps_table_{tid}_spill_hit_rate",
                              st["spill_hits"] / hits)
            tel.set_gauge(f"ps_table_{tid}_row_bytes", st["row_bytes"])
            depth += st.get("repl_queue", 0)
        tel.set_gauge("ps_repl_queue_depth", depth)

    def _register_device_table(self, entry):
        """Register a device-cached table on the server (kind=2 so the
        server keeps per-row versions for bounded-staleness sync)."""
        tbl = entry["table"]
        if tbl.id in self.registered:
            return False
        opt = entry.get("optimizer")
        opt_name, lrs = _opt_spec(opt) if opt is not None else ("SGD", [0.1])
        shape = tuple(tbl.shape)
        init = None
        if tbl.initializer is not None:
            init = tbl.initializer.dist_spec()
        if init is not None:
            self.client.init_tensor(tbl.id, shape, kind=2, init=init,
                                    seed=self.config.seed + tbl.id,
                                    opt=opt_name, lrs=lrs)
        else:
            self.client.init_tensor(tbl.id, shape, kind=2, opt=opt_name,
                                    lrs=lrs)
            self.client.set_param(tbl.id, tbl.initial_value(
                seed=self.config.seed))
        self._maybe_store_config(tbl.id, opt_name)
        push_bound = 1 if self.config.bsp else self.config.cache_bound
        rt = DeviceCacheTable(
            tbl, entry["cache"], self.client,
            capacity=entry["capacity"], width=entry["width"],
            rows=entry["rows"], push_bound=push_bound,
            pull_bound=self.config.cache_bound,
            nworkers=max(1, self.client.nworkers),
            drain_compress=getattr(self.config, "drain_compress", False))
        # scope staleness observations to this executor's monitor
        rt.health_monitor = self.config.health_monitor
        rt._drain_future = None
        self.device_tables[tbl.id] = rt
        self.registered.add(tbl.id)
        return True

    # ------------------------------------------------------------------
    def _cached_for(self, sub):
        """[(table_rt, ids_node, slots_node)] for this subgraph."""
        if sub.name in self._sub_cached:
            return self._sub_cached[sub.name]
        out = []
        topo = set(sub.topo_order)
        for entry in self.config.device_cache_tables:
            rt = self.device_tables[entry["table"].id]
            for ids_node, slots_node in entry["slots_by_ids"].items():
                if slots_node in topo:
                    out.append((rt, ids_node, slots_node))
        self._sub_cached[sub.name] = out
        return out

    # ------------------------------------------------------------------
    def run_step(self, sub, feed_dict, convert_to_numpy_ret_vals=False,
                 prepped=None, dirty=None):
        """One PS step. ``prepped`` (from :meth:`prep_step`, usually run
        on the async ingest worker while the previous step's compute was
        in flight) carries pre-transferred feeds and speculative
        SparsePull rows; ``dirty`` maps table id -> ids pushed since the
        prep was issued — those rows are re-pulled (after flushing
        in-flight pushes) so the overlapped pull observes exactly the
        post-push server state the synchronous loop would have read."""
        executor = self.executor
        client = self.client
        nworkers = max(1, client.nworkers)
        feed_dict = feed_dict or {}
        cached = self._cached_for(sub)
        topo_set = getattr(sub, "_topo_set", None)
        if topo_set is None:
            topo_set = sub._topo_set = set(sub.topo_order)

        # swap in dense parameters rebased by a completed drain cycle
        # (multi-worker: the server value folds the other workers' pushes)
        with self._dense_mu:
            ready, self._dense_ready = self._dense_ready, None
        if ready:
            for sid, (param, value) in ready.items():
                if sid in executor.params:
                    executor.params[sid] = jax.device_put(
                        value.reshape(param.shape))

        feed_map = {}
        host_feeds = {}      # node -> host-side value (skip device_get)
        spec_pulls = {}
        if prepped is not None:
            feed_map.update(prepped["feed_map"])
            host_feeds.update(prepped["host_feeds"])
            spec_pulls = prepped["pulls"]
        for node, value in feed_dict.items():
            if node in feed_map or node in host_feeds:
                continue        # pre-ingested on the worker
            if isinstance(value, np.ndarray):
                host_feeds[node] = value
            if node in topo_set:
                feed_map[node] = sub._ingest(value)
        for dl in sub.dataloader_ops:
            if dl in feed_map:
                continue        # pre-fetched in step order by the stream
            host_val, dev_val = sub.next_dl_batch(dl)
            if isinstance(host_val, np.ndarray):
                host_feeds[dl] = host_val
            feed_map[dl] = dev_val

        def host_ids(index_node, what, rows=None):
            from ..ops.embedding import check_id_dtype
            if index_node in host_feeds:
                idx = np.asarray(host_feeds[index_node])
            elif _detached_loader(index_node) \
                    and index_node not in feed_map:
                # ids dataloader detached from the graph by the cache
                # rewrite: drive it from here
                value = index_node.get_arr(sub.name)
                host_feeds[index_node] = np.asarray(value)
                idx = host_feeds[index_node]
            elif index_node in feed_map:
                # device-resident ids: one readback round trip
                idx = np.asarray(jax.device_get(feed_map[index_node]))
            else:
                raise RuntimeError(
                    f"PS {what} requires its indices to be a feed or "
                    f"dataloader output")
            # HT803's runtime twin: float ids silently truncate past
            # 2^24 and an id dtype narrower than the declared table is
            # the same cliff at 2^31 — reject instead of astype
            check_id_dtype(idx.dtype, rows, f"PS {what}")
            return idx

        def _detached_loader(index_node):
            from ..dataloader import DataloaderOp, GNNDataLoaderOp
            return isinstance(index_node, (DataloaderOp,
                                           GNNDataLoaderOp))

        # 0. device-cache path: ids -> slots, fill misses/stale rows with
        # async dispatches (data dependency orders them before the step)
        note = []
        tel = self.config.telemetry
        hm = self.config.health_monitor
        for rt, ids_node, slots_node in cached:
            with self._phase("slot_assign"):
                ids = host_ids(ids_node, "device-cached lookup",
                               rows=getattr(rt, "rows", None))
                if hm is not None:
                    hm.observe_ids(rt.tid, ids)   # hot-key skew
                slots, miss_ids, miss_slots, uniq_slots = rt.assign(
                    ids, functools.partial(self._drain_device_table, rt,
                                           wait=True))
            sid = rt.cache_sid
            if len(miss_ids):
                if tel.enabled:
                    tel.inc("dcache_miss_rows", len(miss_ids))
                with self._phase("miss_fill"):
                    # a re-missed id whose accumulated grads are still in
                    # an in-flight push would pull a pre-push server
                    # value: wait for that drain first (rare — only
                    # evict-then-refault)
                    fut = rt._drain_future
                    inflight = getattr(rt, "_inflight_ids", None)
                    if fut is not None and not fut.done() and \
                            inflight is not None and \
                            np.isin(miss_ids, inflight).any():
                        fut.result()
                        rt._drain_future = None
                    rows = client.sparse_pull(rt.tid, miss_ids, rt.width)
                    executor.params[sid] = pad_fill(
                        executor.params[sid], miss_slots, rows,
                        rt.capacity)
            if rt.nworkers > 1:
                with self._phase("refresh"):
                    uniq_ids = rt.id_of[uniq_slots]
                    fut = rt._drain_future
                    if (rt.steps_since_drain + 1 >= rt.push_bound
                            and rt.dirty.any()
                            and (fut is None or fut.done())):
                        # a drain falls due this step: fold it into the
                        # refresh as ONE kPushSyncEmbedding round trip
                        # per shard instead of PushEmbedding +
                        # SyncEmbedding back-to-back (take_dirty resets
                        # the cadence, so the post-step drain skips)
                        fill_slots, fill_rows = \
                            self._push_sync_device_table(rt, uniq_ids,
                                                         uniq_slots)
                    else:
                        fill_slots, fill_rows = rt.stale_check(
                            uniq_ids, uniq_slots)
                    if fill_slots is not None:
                        executor.params[sid] = pad_fill(
                            executor.params[sid], fill_slots, fill_rows,
                            rt.capacity)
            feed_map[slots_node] = sub._ingest(slots)
            if sub.training:
                note.append((rt, uniq_slots))

        # 1. embedding rows for this batch (reference SparsePull /
        # prefetch path, EmbeddingLookUp.py:27-40). Duplicate ids in the
        # batch are pulled once and scattered back on the host.
        for lk in sub.ps_lookups:
            if lk in spec_pulls:
                feed_map[lk] = self._settle_spec_pull(spec_pulls[lk],
                                                      dirty)
                continue
            with self._phase("host_pull"):
                idx = host_ids(lk.inputs[1], "embedding lookup",
                               rows=int(lk.inputs[0].shape[0]))
                if hm is not None:
                    hm.observe_ids(lk.inputs[0].id, idx)
                width = int(lk.inputs[0].shape[-1])
                cache = self.caches.get(lk.inputs[0].id)
                if cache is not None:
                    rows = cache.embedding_lookup(idx)
                else:
                    uniq, inv = np.unique(idx.ravel(),
                                          return_inverse=True)
                    rows = client.sparse_pull(
                        lk.inputs[0].id, uniq, width)[inv].reshape(
                            idx.shape + (width,))
                feed_map[lk] = jax.device_put(rows)
        # explicit sparse-pull ops (inference path, reference
        # ParameterServerCommunicate.py:236-288) feed the same way
        for op in sub.ps_pull_ops:
            if op in spec_pulls:
                feed_map[op] = self._settle_spec_pull(spec_pulls[op],
                                                      dirty)
                continue
            idx = host_ids(op.inputs[0], "sparse pull",
                           rows=int(op.parameter.shape[0]))
            if hm is not None:
                hm.observe_ids(op.parameter.id, idx)
            width = int(op.parameter.shape[-1])
            rows = client.sparse_pull(op.parameter.id, idx, width)
            feed_map[op] = jax.device_put(rows)

        with self._phase("dispatch"):
            key = sub._shape_key(feed_map)
            if key not in sub.compiled:
                with sub._compile_span(key):
                    sub._infer_shapes(feed_map)
                    sub._ensure_state(executor)
                    sub.compiled[key] = sub._compile_step(
                        sub.trace_args(executor, feed_map))
                    sub._note_copies(executor)
            fn = sub.compiled[key]
            outputs, *trees, ps_grads, health \
                = fn(*sub.trace_args(executor, feed_map))
            if sub.training:
                executor.adopt(*trees)
                for opt in sub.optimizer_ops:
                    opt.optimizer.lr_sched.step()
            sub.step_count += 1

        # 2. device-cache bookkeeping + periodic drain
        stepped = set()
        for rt, uniq_slots in note:
            rt.note_update(uniq_slots)
            stepped.add(rt.tid)
        for rt, _, _ in cached:
            rt.release_pins()
            if rt.tid in stepped:
                stepped.discard(rt.tid)
                rt.note_step()
                if rt.steps_since_drain >= rt.push_bound:
                    self._drain_device_table(rt, wait=self.config.bsp)
                    self._refresh_hot_rows(rt.tid)
                    self._export_store_gauges()

        # 3. push PS grads / pull updated params
        track = self._track_push_tids
        pushed = {} if track else None
        for op, g in zip(sub.ps_ops, ps_grads):
            param = op.parameter
            tid = param.id
            if isinstance(g, IndexedSlices):
                ids = None
                if pushed is not None and tid in track:
                    # ids this push dirties (an ids-only readback): the
                    # pipelined stream merges them into every in-flight
                    # prep's dirty set so overlapped speculative pulls
                    # revalidate against this push
                    ids = np.unique(np.asarray(
                        jax.device_get(g.indices)).ravel()).tolist()
                    pushed.setdefault(tid, set()).update(ids)
                # cache updates are host-memory cheap and the cache object
                # is driven from this thread — keep them inline
                if self._push_pool is not None and \
                        param.id not in self.caches:
                    # ASP: readback + push off the critical path — the
                    # next step's pull may see the table one push stale
                    # (the reference's asynchronous PS training mode)
                    if ids is not None:
                        # async: the server may not have applied these
                        # rows yet — preps submitted from now until the
                        # next flush must revalidate them too
                        self._inflight_pushed.setdefault(
                            tid, set()).update(ids)
                    self._drain_done()
                    self._pending_push.append(self._push_pool.submit(
                        self._push_sparse, param, g, nworkers))
                    continue
                with self._phase("sync_push"):
                    self._push_sparse(param, g, nworkers)
                    client.wait(tid)
            else:
                with self._phase("sync_push"):
                    grad = np.asarray(jax.device_get(g)).ravel()
                    if nworkers > 1:
                        grad = grad / nworkers
                    new_value = client.dd_pushpull(tid, grad)
                    client.wait(tid)
                    sid = str(param.id)
                    if sid in executor.params:
                        executor.params[sid] = jax.device_put(
                            new_value.reshape(param.shape))

        if pushed is not None:
            self._last_pushed = pushed

        # 3b. dense HET drain cadence (grads already accumulated in-graph)
        if self.config.ps_dense_cached and sub.training:
            with self._phase("dense"):
                self._dense_steps += 1
                if self._dense_steps >= max(1, self.config.cache_bound):
                    self._drain_dense_cached(nworkers)

        # 4. synchronization discipline: BSP barrier or ASP free-running
        # (reference ParameterServerCommunicate.py:226-231)
        if self.config.bsp:
            client.barrier()
        elif len(self._pending_push) > 4:
            self._pending_push[0].result()   # bound the pipeline depth
            self._drain_done()

        if hm is not None and health is not None:
            # after the pushes/barrier so a `raise`-ladder trip never
            # leaves this step's server updates half-applied; the
            # monitor also folds in this runtime's staleness/hot-key
            # observations and samples server-side table stats
            sub._last_health = health
            hm.after_step(sub, runtime=self)

        results = []
        from .. import ndarray as nd
        for out in outputs:
            if out is None:
                results.append(None)
            elif convert_to_numpy_ret_vals:
                results.append(np.asarray(out))
            else:
                results.append(nd.NDArray(out, None))
        return results

    # ------------------------------------------------------------------
    def prep_step(self, sub, feed_dict, dl_host=None):
        """The worker-safe host phase of ONE step: device-transfer the
        plain feeds (and pre-fetched dataloader batches, ``dl_host``)
        and speculatively ``SparsePull`` the embedding rows the step
        needs. Stateful work — host-cache lookups, device-cache slot
        assignment, pushes, barriers — stays on the caller;
        :meth:`run_step` revalidates the speculative pulls against
        pushes that landed after this prep was issued. Under
        multi-worker BSP pulls are NOT speculated (another worker's
        barrier-synchronized push is invisible to our dirty tracking);
        the feed transfer still overlaps."""
        topo_set = getattr(sub, "_topo_set", None)
        if topo_set is None:
            topo_set = sub._topo_set = set(sub.topo_order)
        feed_map, host_feeds = {}, {}
        for node, value in (feed_dict or {}).items():
            if isinstance(value, np.ndarray):
                host_feeds[node] = value
            if node in topo_set:
                feed_map[node] = sub._ingest(value)
        for dl, host_val in (dl_host or {}).items():
            host_val = np.asarray(host_val)
            host_feeds[dl] = host_val
            feed_map[dl] = sub._ingest(host_val)
        pulls = {}
        speculate = not (self.config.bsp
                         and max(1, self.client.nworkers) > 1)
        if speculate:
            for lk in sub.ps_lookups:
                if self.caches.get(lk.inputs[0].id) is not None:
                    continue      # host-cache: stateful, pull inline
                idx = host_feeds.get(lk.inputs[1])
                if idx is None:
                    continue      # device-resident ids: pull inline
                pulls[lk] = self._spec_pull(
                    lk.inputs[0].id, np.asarray(idx),
                    int(lk.inputs[0].shape[-1]))
            for op in sub.ps_pull_ops:
                idx = host_feeds.get(op.inputs[0])
                if idx is None:
                    continue
                pulls[op] = self._spec_pull(
                    op.parameter.id, np.asarray(idx),
                    int(op.parameter.shape[-1]))
        return {"feed_map": feed_map, "host_feeds": host_feeds,
                "pulls": pulls}

    def _spec_pull(self, tid, idx, width):
        """One speculative SparsePull (dedup'd), plus everything needed
        to revalidate and reassemble it at consumption time."""
        from ..ops.embedding import check_id_dtype
        check_id_dtype(idx.dtype, None, "PS speculative pull")
        hm = self.config.health_monitor
        if hm is not None:
            hm.observe_ids(tid, idx)     # hot-key skew (worker thread)
        with self._phase("prefetch"):
            uniq, inv = np.unique(idx.ravel(), return_inverse=True)
            rows = self.client.sparse_pull(tid, uniq, width)
        return {"tid": tid, "width": width, "uniq": uniq, "inv": inv,
                "shape": tuple(idx.shape), "rows": rows}

    def _settle_spec_pull(self, spec, dirty):
        """Speculative rows -> the device feed, re-pulling rows whose
        ids were pushed after the prep was issued (the pipelined
        stream's dirty map), so the fed value equals what a synchronous
        post-push pull would have read."""
        tid, rows = spec["tid"], spec["rows"]
        d = (dirty or {}).get(tid)
        if d:
            stale = np.isin(spec["uniq"],
                            np.fromiter(d, dtype=np.int64, count=len(d)))
            if stale.any():
                with self._phase("repull"):
                    self._flush_pushes(tid)
                    rows[stale] = self.client.sparse_pull(
                        tid, spec["uniq"][stale], spec["width"])
        full = rows[spec["inv"]].reshape(spec["shape"] + (spec["width"],))
        return jax.device_put(full)

    def _flush_pushes(self, tid):
        """Block until every submitted push that could touch ``tid``
        has reached the server: join the ASP push pool's futures, then
        wait out the client's outstanding requests for the tensor.
        Post-flush the table holds every submitted push, so the
        in-flight dirty seed for ``tid`` resets."""
        for f in self._pending_push:
            f.result()
        self._pending_push.clear()
        self.client.wait(tid)
        self._inflight_pushed.pop(tid, None)

    # ------------------------------------------------------------------
    def run_stream_pipelined(self, sub, blocks,
                             convert_to_numpy_ret_vals=False,
                             lookahead=2, sink=None):
        """Pipelined per-step execution for host-path PS and BSP
        streams — the configs :meth:`run_block` must execute
        step-by-step, which used to serialize every pull/transfer with
        compute. While step i's dispatched compute is in flight, the
        async ingest worker runs steps i+1..i+lookahead's host phase:
        feed ``device_put`` AND speculative ``SparsePull``
        (:meth:`prep_step`). Push/barrier order is untouched — each
        step still pushes (and BSP-barriers) before the next step
        executes, and speculative pulls revalidate against those pushes
        (:meth:`run_step`'s dirty re-pull) — so results are numerically
        identical to a synchronous run_step loop. Returns the last
        block's per-step results (the run_batches contract)."""
        from collections import deque
        from .. import ingest as ingest_mod
        from ..dataloader import GNNDataLoaderOp

        spec_tids = frozenset(
            lk.inputs[0].id for lk in sub.ps_lookups
            if lk.inputs[0].id not in self.caches) | frozenset(
            op.parameter.id for op in sub.ps_pull_ops)

        def step_stream():
            for block in blocks:
                n = len(block)
                for si, fd in enumerate(block):
                    yield fd, si == n - 1

        def fetch_dl():
            # dataloaders advance state: fetch host batches in step
            # order on the caller; the worker only device-transfers
            out = {dl: sub.dl_block(dl, 1)[0]
                   for dl in sub.dataloader_ops
                   if not isinstance(dl, GNNDataLoaderOp)}
            return out or None

        it = enumerate(step_stream())
        first = next(it, None)
        if first is None:
            return None
        engine = ingest_mod.IngestEngine(
            self.config.telemetry, lookahead=lookahead, name="ps-ingest",
            sink=sink)
        pending = deque()    # (fd, block_end, dirty) aligned with engine
        self._track_push_tids = spec_tids or None
        out, block_out = None, []
        try:
            with engine:     # error exit cancels queued preps

                def refill():
                    # low-reuse id streams grow the in-flight seed
                    # without ever tripping a dirty re-pull (which is
                    # what normally flushes it): past a bound, settle
                    # the pushes now so seed copies and isin checks
                    # stay O(bound) instead of O(stream)
                    for t in [t for t, s in
                              self._inflight_pushed.items()
                              if len(s) > 4096]:
                        self._flush_pushes(t)
                    while engine.depth < lookahead:
                        nxt = next(it, None)
                        if nxt is None:
                            return
                        i, (fd, block_end) = nxt
                        # seed with ids whose ASP pushes are still in
                        # flight: this prep's pull races those pushes
                        # even though they were submitted earlier
                        seed = {t: set(s) for t, s
                                in self._inflight_pushed.items() if s}
                        pending.append((fd, block_end, seed))
                        engine.submit(self.prep_step, sub, fd,
                                      fetch_dl(), tag=i)

                _, (fd, block_end) = first
                # settle pushes from any PRE-stream run() steps: they
                # predate the tracking, so the priming prep (and the
                # first refill batch) must not race them
                for tid in spec_tids:
                    self._flush_pushes(tid)
                pre = self.prep_step(sub, fd, fetch_dl())   # priming
                dirty = {}
                refill()
                tel = self.config.telemetry
                while fd is not None:
                    # per-step doctor window (pipelined path dispatches
                    # per step, there is no covering Executor.run span);
                    # the engine.pop wait lands inside it, so an
                    # exposed prep stall is attributable
                    with tel.span("step", subgraph=sub.name,
                                  pipelined=True):
                        res = self.run_step(sub, fd,
                                            convert_to_numpy_ret_vals,
                                            prepped=pre, dirty=dirty)
                        block_out.append(res)
                        if block_end:
                            out, block_out = block_out, []
                        pushed = self._last_pushed
                        if pushed:
                            # this step's pushes dirty every in-flight
                            # prep
                            for _fd, _be, d in pending:
                                for tid, ids in pushed.items():
                                    d.setdefault(tid, set()).update(ids)
                        if pending:
                            fd, block_end, dirty = pending.popleft()
                            _, pre = engine.pop()
                            refill()
                        else:
                            fd = None
        finally:
            self._track_push_tids = None
            self._last_pushed = {}
            self._inflight_pushed = {}
        return out

    # ------------------------------------------------------------------
    def ingest_feeds(self, sub, feed_dicts, dl_host=None):
        """Stack + device-transfer a block's plain feeds (the stateless
        part of run_block's host phase) and, when the caller fetched
        them in block order, its dataloader batches (``dl_host``: {dl:
        [per-step host arrays]}). Safe to run on the async ingest worker
        while the previous block executes — the stateful work (cache
        slot assignment, miss fills) stays on the caller. Returns the
        {node: (stacked, first_row)} map run_block accepts as
        ``pre_ingested``."""
        topo_set = getattr(sub, "_topo_set", None)
        if topo_set is None:
            topo_set = sub._topo_set = set(sub.topo_order)
        out = {}
        for node in (feed_dicts[0] or {}):
            if node not in topo_set:
                continue     # e.g. raw ids replaced by the slots feed
            out[node] = sub._stack_feed([fd[node] for fd in feed_dicts])
        for dl, arrs in (dl_host or {}).items():
            stacked = np.stack(arrs)
            out[dl] = (sub._ingest_stacked(stacked), stacked[0])
        return out

    def run_block(self, sub, feed_dicts, convert_to_numpy_ret_vals=False,
                  pre_ingested=None):
        """``len(feed_dicts)`` steps in ONE dispatch for device-cached
        graphs: slots for every step are assigned up front (misses fill
        before the block; pins persist across the whole block so no
        in-block row is evicted), feeds stack into single transfers, and
        the compiled lax.scan runs the steps back-to-back on device.
        Falls back to per-step run_step for host-path PS graphs and BSP
        (whose barrier is per-step by definition). ``pre_ingested``
        (from ingest_feeds, possibly on a lookahead thread) skips the
        in-line feed stacking — the double-buffered input path."""
        if (sub.ps_lookups or sub.ps_pull_ops or sub.ps_ops
                or self.config.bsp):
            return [self.run_step(sub, fd, convert_to_numpy_ret_vals)
                    for fd in feed_dicts]
        executor = self.executor
        client = self.client
        nsteps = len(feed_dicts)
        cached = self._cached_for(sub)

        with self._dense_mu:
            ready, self._dense_ready = self._dense_ready, None
        if ready:
            for sid, (param, value) in ready.items():
                if sid in executor.params:
                    executor.params[sid] = jax.device_put(
                        value.reshape(param.shape))

        with self._phase("feed_ingest"):
            ingested = (pre_ingested if pre_ingested is not None
                        else self.ingest_feeds(sub, feed_dicts))
            feed_map = {}
            first_map = {}
            for node, (stacked, first) in ingested.items():
                feed_map[node] = stacked
                first_map[node] = first
        for dl in sub.dataloader_ops:
            if dl in feed_map:
                continue     # pre-ingested (stream fetched in order)
            stacked = np.stack(sub.dl_block(dl, nsteps))
            feed_map[dl] = sub._ingest_stacked(stacked)
            first_map[dl] = stacked[0]

        # per-step ids, fetched once per source (a dataloader shared by
        # two cached tables must advance once per step, not once per
        # table — mirrors run_step's host_feeds memoization)
        from ..dataloader import DataloaderOp, GNNDataLoaderOp
        ids_block = {}
        for rt, ids_node, slots_node in cached:
            if ids_node in ids_block:
                continue
            rows = []
            for fd in feed_dicts:
                if ids_node in fd:
                    rows.append(np.asarray(fd[ids_node]))
                elif isinstance(ids_node, (DataloaderOp, GNNDataLoaderOp)):
                    rows.append(np.asarray(ids_node.get_arr(sub.name)))
                else:
                    raise RuntimeError(
                        "device-cached lookup needs host ids per step")
            ids_block[ids_node] = rows

        note = []
        tel = self.config.telemetry
        hm = self.config.health_monitor
        for rt, ids_node, slots_node in cached:
            # one vectorized assignment for the whole block: the scan
            # threads a single cache array, so the residency set equals
            # per-step assigns with pins held — see assign_block()
            with self._phase("slot_assign"):
                ids_stacked = np.stack(ids_block[ids_node])
                if hm is not None:
                    hm.observe_ids(rt.tid, ids_stacked)
                slots_full, miss_ids, miss_slots, uniq_slots, counts = \
                    rt.assign_block(
                        ids_stacked,
                        functools.partial(self._drain_device_table, rt,
                                          wait=True))
            if len(miss_ids):
                if tel.enabled:
                    tel.inc("dcache_miss_rows", len(miss_ids))
                with self._phase("miss_fill"):
                    fut = rt._drain_future
                    inflight = getattr(rt, "_inflight_ids", None)
                    if fut is not None and not fut.done() and \
                            inflight is not None and \
                            np.isin(miss_ids, inflight).any():
                        fut.result()
                        rt._drain_future = None
                    rows = client.sparse_pull(rt.tid, miss_ids, rt.width)
                    executor.params[rt.cache_sid] = pad_fill(
                        executor.params[rt.cache_sid], miss_slots, rows,
                        rt.capacity)
            if rt.nworkers > 1:
                # bounded-staleness refresh; mid-block refreshes would
                # collapse to this pre-block fill anyway (the compiled
                # scan never re-reads the server)
                with self._phase("refresh"):
                    uniq_ids = rt.id_of[uniq_slots]
                    fill_slots, fill_rows = rt.stale_check(uniq_ids,
                                                           uniq_slots)
                    if fill_slots is not None:
                        executor.params[rt.cache_sid] = pad_fill(
                            executor.params[rt.cache_sid], fill_slots,
                            fill_rows, rt.capacity)
            with self._phase("slot_assign"):
                feed_map[slots_node] = sub._ingest_stacked(slots_full)
                first_map[slots_node] = slots_full[0]
                if sub.training:
                    note.append((rt, uniq_slots, counts))

        with self._phase("dispatch"):
            results = sub._dispatch_block(executor, feed_map, first_map,
                                          nsteps,
                                          convert_to_numpy_ret_vals)

        stepped_tables = set()
        for rt, uniq_slots, counts in note:
            rt.note_update(uniq_slots, counts)
            stepped_tables.add(rt)
        for rt, _, _ in cached:
            rt.release_pins()
        for rt in stepped_tables:
            for _ in range(nsteps):
                rt.note_step()
            if rt.steps_since_drain >= rt.push_bound:
                self._drain_device_table(rt)
                self._export_store_gauges()
        if self.config.ps_dense_cached and sub.training:
            self._dense_steps += nsteps
            if self._dense_steps >= max(1, self.config.cache_bound):
                self._drain_dense_cached(max(1, client.nworkers))

        return results

    # ------------------------------------------------------------------
    def _drain_device_table(self, rt, wait=False):
        """Drain one device table's gradient accumulator to the server.

        Gathers the dirty rows from the HBM accumulator and zeroes them
        (async dispatches), then hands the readback+PushEmbedding to the
        push pool. ``wait=True`` (BSP / dirty eviction) blocks until the
        push reaches the server."""
        fut = rt._drain_future
        if fut is not None:
            if not fut.done() and not wait:
                return              # previous drain still in flight
            fut.result()
            rt._drain_future = None
        with self._phase("drain_submit"):
            slots, ids, upds = rt.take_dirty()
            if not len(slots):
                return
            executor = self.executor
            state = executor.state[rt.cache_sid]
            new_acc, rows_dev, n = pad_gather_zero(
                state["acc"], slots, rt.capacity,
                compress=rt.drain_compress)
            executor.state[rt.cache_sid] = {"acc": new_acc}
            rt.pushed_rows += n
            rt._inflight_ids = ids
            tel = self.config.telemetry

            def push():
                with tel.span("ps:drain_push", rows=int(n)):
                    rows = np.asarray(jax.device_get(rows_dev))[:n]
                    if rows.dtype != np.float32:
                        rows = rows.astype(np.float32)  # widen bf16
                    if rt.nworkers > 1:
                        rows = rows / rt.nworkers
                    self.client.push_embedding(rt.tid, ids, rows, upds,
                                               rt.width)
                    self.client.wait(rt.tid)

            if self._push_pool is not None and not wait:
                rt._drain_future = self._push_pool.submit(push)
            else:
                push()

    def _push_sync_device_table(self, rt, uniq_ids, uniq_slots):
        """Fold a due drain into the staleness refresh: claim the dirty
        rows, gather+zero their grad sums from HBM, and issue one
        combined kPushSyncEmbedding per shard that both applies the
        push and returns the refreshed rows. The push rides the
        refresh's critical path (it was about to happen post-step
        anyway), and the sync's answer reflects it."""
        fut = rt._drain_future
        if fut is not None:
            fut.result()        # done (the fold gate checked) — surface
            rt._drain_future = None
        slots, ids, upds = rt.take_dirty()
        if not len(slots):
            return rt.stale_check(uniq_ids, uniq_slots)
        executor = self.executor
        state = executor.state[rt.cache_sid]
        new_acc, rows_dev, n = pad_gather_zero(
            state["acc"], slots, rt.capacity, compress=rt.drain_compress)
        executor.state[rt.cache_sid] = {"acc": new_acc}
        rt.pushed_rows += n
        rows = np.asarray(jax.device_get(rows_dev))[:n]
        if rows.dtype != np.float32:
            rows = rows.astype(np.float32)      # widen bf16
        return rt.push_sync(ids, rows, upds, uniq_ids, uniq_slots)

    def _drain_dense_cached(self, nworkers, wait=False):
        """Drain the dense HET accumulators: claim each param's HBM grad
        sum (replacing it with zeros — two async dispatches), then push
        the sums through the server optimizer on the push pool.
        Multi-worker, the server value is pulled back and staged to
        replace the local param (bounded-staleness rebase)."""
        fut = self._dense_future
        if fut is not None:
            if not fut.done() and not wait:
                return
            fut.result()
            self._dense_future = None
        executor = self.executor
        accs, params = {}, {}
        for param, _opt in self.config.ps_dense_cached:
            sid = str(param.id)
            st = executor.state.get(sid)
            if st is None:
                continue
            accs[sid] = st["acc"]
            params[sid] = param
        if not accs:
            return
        zeros = _zeros_like_tree(accs)
        for sid in accs:
            executor.state[sid] = {"acc": zeros[sid]}
        self._dense_steps = 0

        def cycle():
            host = jax.device_get(accs)
            for sid, g in host.items():
                grad = np.asarray(g).ravel()
                if nworkers > 1:
                    grad = grad / nworkers
                self.client.push(params[sid].id, grad)
            ready = {}
            for sid, param in params.items():
                self.client.wait(param.id)
                if nworkers > 1:
                    ready[sid] = (param, self.client.pull(
                        param.id, (int(np.prod(param.shape)),)))
            if ready:
                with self._dense_mu:
                    self._dense_ready = ready

        if self._push_pool is not None and not wait:
            self._dense_future = self._push_pool.submit(cycle)
        else:
            cycle()

    # ------------------------------------------------------------------
    def _push_sparse(self, param, g, nworkers):
        """Readback one IndexedSlices grad and push it (runs on the push
        thread under ASP, inline under BSP)."""
        width = int(param.shape[-1])
        idx = np.asarray(jax.device_get(g.indices)).ravel()
        vals = np.asarray(jax.device_get(g.values)).reshape(
            idx.size, width)
        if nworkers > 1:
            vals = vals / nworkers
        cache = self.caches.get(param.id)
        if cache is not None:
            cache.embedding_update(idx, vals)
        else:
            self.client.sparse_push(param.id, idx, vals, width)

    def _drain_done(self):
        still = []
        for f in self._pending_push:
            if f.done():
                f.result()          # surface push-thread exceptions
            else:
                still.append(f)
        self._pending_push = still

    def drain(self):
        """Block until every in-flight push (sparse ASP pushes, device-
        cache drains, dense ASP cycles) has reached the server. If the
        fleet was already stopped, pending updates are dropped and
        ``self.updates_dropped`` is set so callers (save()) can tell a
        clean flush from a skipped one (ADVICE r4)."""
        if getattr(self.client, "servers_down", False):
            # the fleet was stopped under us (bench/test teardown
            # ordering): pending updates have nowhere to go — dropping
            # them beats minutes of doomed reconnect retries
            import sys
            self.updates_dropped = True
            print("[hetu-ps] drain skipped: servers already shut down",
                  file=sys.stderr)
            return
        for rt in self.device_tables.values():
            self._drain_device_table(rt, wait=True)
        if self.config.ps_dense_cached:
            self._drain_dense_cached(max(1, self.client.nworkers),
                                     wait=True)
        if self._dense_future is not None:
            self._dense_future.result()
            self._dense_future = None
        for f in self._pending_push:
            f.result()
        self._pending_push.clear()
        self.client.wait_all()

    def close(self):
        """Teardown drain (ADVICE r2: pending ASP pushes must not be
        dropped — or fail silently — when a script ends without save()).
        Exceptions from queued pushes re-raise here."""
        if self._closed:
            return
        self._closed = True
        import atexit
        atexit.unregister(self._atexit)   # don't pin HBM buffers for life
        self.drain()
        if self._push_pool is not None:
            # after drain() the workers are idle, so the bounded join
            # is immediate on the clean path; post-shutdown_servers()
            # (updates_dropped) a push may be wedged in an RPC retry —
            # cancel the queue and abandon the daemon worker rather
            # than deadlocking teardown on it
            ok = self._push_pool.shutdown(
                wait=not self.updates_dropped,
                cancel_futures=self.updates_dropped, timeout=30.0)
            if not self.updates_dropped and not ok:
                import sys
                print("[hetu-ps] close(): push worker still busy after "
                      "the shutdown timeout; abandoning the daemon "
                      "worker", file=sys.stderr)
        if self.config.telemetry.enabled:
            self.phase_breakdown()    # final cache-counter gauges

    def _atexit(self):
        try:
            self.close()
        except Exception as e:                       # noqa: BLE001
            import sys
            print(f"[hetu-ps] teardown drain failed: {e}", file=sys.stderr)

    def phase_breakdown(self):
        """Accumulated per-phase host seconds; also
        publishes the device-cache hit/miss/evict counters as telemetry
        gauges so a Prometheus scrape sees them."""
        with self._times_mu:
            out = dict(self.times)
        tel = self.config.telemetry
        for rt in self.device_tables.values():
            perf = rt.perf
            out.setdefault("cache_perf", {})[rt.table_node.name] = perf
            if tel.enabled:
                for k, v in perf.items():
                    if isinstance(v, (int, float)):
                        tel.set_gauge(
                            f"dcache_{rt.table_node.name}_{k}", v)
        return out

    def save(self, path):
        import os
        self.drain()
        if self.updates_dropped:
            raise RuntimeError(
                "PS save() after shutdown_servers(): pending updates "
                "were dropped, a checkpoint now would silently contain "
                "stale server values (save before shutting the fleet "
                "down)")
        for cache in self.caches.values():
            cache.flush()       # pending grads reach the server first
        for op_param_id in sorted(self.registered):
            self.client.save_param(
                op_param_id, os.path.join(path, f"ps_{op_param_id}.bin"))

    def load(self, path):
        import os
        # flush pending updates first: the checkpoint supersedes them,
        # and invalidate() refuses to discard un-drained rows
        self.drain()
        for op_param_id in sorted(self.registered):
            self.client.load_param(
                op_param_id, os.path.join(path, f"ps_{op_param_id}.bin"))
        # cached rows predate the load — invalidate so lookups refill
        for rt in self.device_tables.values():
            rt.invalidate()
        # dense HET params keep a worker-local copy in executor.params
        # that single-worker runs never pull back: refresh it from the
        # server so load() is not a silent no-op (ADVICE r3), and zero
        # the pre-load grad accumulators the checkpoint supersedes
        executor = self.executor
        for param, _opt in self.config.ps_dense_cached:
            sid = str(param.id)
            value = self.client.pull(
                param.id, (int(np.prod(param.shape)),))
            if sid in executor.params:
                executor.params[sid] = jax.device_put(
                    np.asarray(value).reshape(param.shape))
            st = executor.state.get(sid)
            if st is not None:
                executor.state[sid] = {
                    "acc": jnp.zeros_like(st["acc"])}
