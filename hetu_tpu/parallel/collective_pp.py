"""Collective (SPMD) pipeline: GPipe as ONE shard_map program.

The staged runners in pipeline.py dispatch per-stage jits and move
boundaries with device_put (in-process) or the host TCP channel
(cross-process). This module is the third mode — the whole pipeline is a
single XLA program over a ``stage`` mesh axis: every device holds one
stage's parameters (stacked ``[S, ...]`` arrays sharded on the stage
axis), and each schedule tick shifts the boundary activation to the next
stage with ``lax.ppermute``, so stage transfers ride ICI with no host in
the loop at all. The reference moves stage boundaries device-to-device
over NCCL p2p driven from Python (PipelineSend.py:8-74,
mpi_nccl_communication.cu:166-230); here the transfer is a compiler-
scheduled collective inside one jit — zero dispatches per boundary.

Heterogeneous-but-shape-compatible stages dispatch through ``lax.switch``
on the stage index (each device runs its own stage's subgraph).
Requirements, checked loudly at build time:

  * a linear chain: stage i consumes exactly one boundary tensor,
    produced by stage i-1, and all boundary tensors share one
    shape/dtype;
  * per-stage parameter lists of matching length and shapes, so
    position j of every stage stacks into one ``[S, ...]`` array.

That is the shape of every real pipelined model (uniform transformer
blocks); models that violate it keep the staged runners. The host TCP
channel (parallel/p2p.py) remains the cross-slice/DCN transport — this
mode covers the in-slice (single SPMD program) case.

Schedule math matches the staged GPipe runner exactly: microbatch m's
forward folds the same RNG (step*131 + m), the loss is the mean over
microbatches, and one optimizer step applies the summed gradients — so
losses are bit-comparable with pipeline.py's ``_run_gpipe_compiled``
(tests/test_collective_pp.py asserts it).

Tick-loop tuning knobs (the round-6 perf rewrite; every combination is
loss-equivalent to the staged runner, asserted per-variant by
tests/test_collective_pp.py — bf16 boundaries under a looser, documented
tolerance):

  * ``feed_mode`` — "sharded" (default) packs each stage's microbatch
    feeds into one byte row of a ``[S, row_bytes]`` uint8 array sharded
    over the stage axis, so a device receives ONLY its own stage's feed
    bytes (branch s decodes its slices at static offsets). "replicated"
    is the old transport: every feed enters with a replicated ``P()``
    spec, so all M microbatches of every stage's feeds stream through
    every device — S x the h2d bytes of the sharded path.
  * ``fuse_ticks=K`` — the schedule scan advances K ticks per iteration
    (XLA fuses across the tick boundary); trailing padded ticks compute
    masked garbage, which is safe at the END of the schedule only (the
    loss mask drops them and x_last is discarded).
  * ``unroll_fill_drain`` — the S-1 fill and S-1 drain ticks unroll out
    of the scan (they can fuse with program entry/exit); only the
    steady-state ticks loop.
  * ``boundary_dtype`` — "bf16" casts the ppermute payload at stage
    boundaries (halving boundary bytes on the wire); compute and the
    loss/gradient/optimizer math stay fp32.
  * ``virtual_stages=V`` — **interleaved schedule** (Megatron-style
    virtual stages, the round-10 bubble attack): the user's S*V stage
    contexts map onto S devices, device r owning chunks
    ``{v*S + r : v < V}`` stacked as a ``[S, V, ...]`` parameter axis.
    Each tick computes ONE chunk per device and the boundary rides a
    full ring ``ppermute`` (the S-1 -> 0 wraparound carries a chunk
    group transition); chunk ``v*S + r`` of microbatch ``m`` runs at
    tick ``r + v*M + m``, so the whole schedule is ``V*M + S - 1``
    ticks of 1/V-stage work each — fill/drain shrinks from ``(S-1)``
    stage-times to ``(S-1)/V``, i.e. bubble fraction
    ``(S-1)/(V*M + S - 1)`` vs GPipe's ``(S-1)/(M + S - 1)``. The
    wraparound arrives ``M - S + 1`` ticks before its consumer turn,
    buffered in a ``[M-S+1, ...]`` ring carry (read-before-write at
    slot ``t mod (M-S+1)`` is exactly the needed delay), which is why
    the schedule requires ``M >= S``. Losses/gradients are identical
    to GPipe on the same S*V-stage graph (the schedule only reorders
    work; every microbatch still traverses every stage once and one
    optimizer step applies the summed gradients).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import shard_map_unchecked as _shard_map_unchecked
from .. import telemetry as _telemetry

__all__ = ["CollectiveGPipe", "BOUNDARY_RTOL"]

# the declared loss tolerance of an opt-in low-precision boundary
# (PR 1's tested bf16 rtol): the numerics verifier's HT805 check holds
# the derived cast-error bound (hops * eps/2, numerics.
# boundary_error_bound) against this — widening the boundary dtype
# without retuning it trips statically before a run ships wrong losses.
# Overridable per session via pp_options={"boundary_rtol": ...}.
BOUNDARY_RTOL = 5e-3


def _canon_boundary_dtype(boundary_dtype):
    if boundary_dtype in (None, "fp32", "f32", "float32"):
        return None
    if boundary_dtype in ("bf16", "bfloat16"):
        return jnp.bfloat16
    return np.dtype(boundary_dtype)


class CollectiveGPipe:
    """Compiled SPMD GPipe step over a ``stage`` mesh axis.

    branches: list of S callables with the uniform signature
    ``branch(plist, x, feeds, rng) -> (boundary_out, loss)`` — plist is
    the device-local per-position parameter list, x the incoming boundary
    activation, feeds the per-microbatch feed list for that stage
    (already sliced at microbatch m by the feed transport), and loss a
    scalar (zero except the last stage).
    """

    def __init__(self, branches, boundary_aval, num_microbatches, mesh,
                 axis_name, optimizer, feed_mode="sharded", fuse_ticks=2,
                 unroll_fill_drain=True, boundary_dtype=None,
                 virtual_stages=1, telemetry=None):
        if feed_mode not in ("sharded", "replicated"):
            raise ValueError(
                f"feed_mode must be 'sharded' or 'replicated', got "
                f"{feed_mode!r}")
        self.branches = branches
        self.S = len(branches)          # total chunks (user stages)
        self.M = num_microbatches
        self.V = max(1, int(virtual_stages or 1))
        if self.S % self.V != 0:
            raise ValueError(
                f"virtual_stages={self.V} must divide the stage count "
                f"{self.S} (each device owns exactly V chunks)")
        self.S_dev = self.S // self.V   # devices on the stage axis
        if self.V > 1:
            if self.S_dev < 2:
                raise ValueError(
                    "interleaved schedule needs >= 2 devices after "
                    f"folding {self.S} stages by V={self.V}")
            if self.M < self.S_dev:
                raise ValueError(
                    f"interleaved schedule requires M >= device count "
                    f"({self.M} < {self.S_dev}): the S-1 -> 0 wraparound "
                    f"buffer depth is M - S + 1; raise num_microbatches "
                    f"or drop virtual_stages")
        self.mesh = mesh
        self.axis_name = axis_name
        self.optimizer = optimizer
        self.boundary_aval = boundary_aval
        self.feed_mode = feed_mode
        self.fuse_ticks = max(1, int(fuse_ticks))
        self.unroll_fill_drain = bool(unroll_fill_drain)
        self.boundary_dtype = _canon_boundary_dtype(boundary_dtype)
        self.telemetry = (telemetry if telemetry is not None
                          else _telemetry.NULL)
        self._step = None
        self._feed_cache = {}     # (stage, j) -> (src array, replicated)
        self._packed_cache = None  # (leaf refs, packed [S, row_bytes])
        self._layout = None       # per stage: [(offset, shape, dtype)]
        self._row_bytes = 1

    @property
    def n_ticks(self):
        """Schedule length in ticks (V*M + S_dev - 1; the V=1 case is
        the classic M + S - 1)."""
        return self.V * self.M + self.S_dev - 1

    # -- stage-sharded feed transport -----------------------------------
    def _build_layout(self, feeds_all):
        """Byte layout of each stage's feed bundle inside its row of the
        packed ``[S_dev, row_bytes]`` array: per feed, (byte offset,
        stacked [M, mb, ...] shape, dtype). Offsets are static per
        stage, so branch s decodes its feeds with static slices +
        bitcasts. Under V>1 the V chunks sharing a device concatenate
        into one row (chunk v*S_dev + r at increasing offsets of row
        r), so a device still receives only ITS chunks' feed bytes."""
        layout = [None] * self.S
        row_bytes = 0
        for r in range(self.S_dev):
            off = 0
            for v in range(self.V):
                c = v * self.S_dev + r
                stage = []
                for f in feeds_all[c]:
                    shape = tuple(int(d) for d in f.shape)
                    dt = np.dtype(f.dtype)
                    stage.append((off, shape, dt))
                    off += int(np.prod(shape)) * dt.itemsize
                layout[c] = stage
            row_bytes = max(row_bytes, off)
        self._layout = layout
        self._row_bytes = max(row_bytes, 1)

    def _pack_feeds(self, feeds_all):
        """Stage feeds -> one ``[S, row_bytes]`` uint8 array sharded over
        the stage axis: device s receives only stage s's feed bytes (the
        replicated transport moved every stage's feeds to every device).
        Identity-cached so pinned feeds pack + transfer once, not once
        per step. Packing is a host-side byte copy (jax feed arrays sync
        d2h once on first pack; steady-state steps hit the cache)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        leaves = tuple(f for fs in feeds_all for f in fs)
        hit = self._packed_cache
        if hit is not None and len(hit[0]) == len(leaves) and \
                all(a is b for a, b in zip(hit[0], leaves)):
            return hit[1]
        rows = np.zeros((self.S_dev, self._row_bytes), np.uint8)
        for s, fs in enumerate(feeds_all):
            if len(fs) != len(self._layout[s]):
                raise ValueError(
                    f"collective pipeline stage {s} got {len(fs)} feeds; "
                    f"built for {len(self._layout[s])}")
            for j, ((off, shape, dt), f) in enumerate(
                    zip(self._layout[s], fs)):
                if tuple(np.shape(f)) != shape or np.dtype(f.dtype) != dt:
                    # the byte layout is compiled into the program, so a
                    # shape change cannot retrace its way to correctness
                    # (the packed array stays [S, row_bytes]) — fail
                    # loudly instead of decoding garbage
                    raise ValueError(
                        f"collective pipeline feed changed shape/dtype "
                        f"after build: stage {s} feed {j} is "
                        f"{tuple(np.shape(f))}/{np.dtype(f.dtype)}, built "
                        f"for {shape}/{dt} — keep the batch size fixed "
                        f"or rebuild the executor")
                b = np.ascontiguousarray(np.asarray(f), dtype=dt)
                b = b.view(np.uint8).ravel()
                rows[s % self.S_dev, off:off + b.size] = b
        packed = jax.device_put(
            rows, NamedSharding(self.mesh, P(self.axis_name)))
        self._packed_cache = (leaves, packed)
        return packed

    def _decode_feeds(self, words, s, mc):
        """Stage s's microbatch-mc feed list out of its local byte row
        (static offsets/shapes; only the microbatch index is dynamic)."""
        out = []
        for off, shape, dt in self._layout[s]:
            M = shape[0]
            nb = int(np.prod(shape)) * dt.itemsize
            blk = words[off:off + nb].reshape((M, nb // M))
            row = jnp.take(blk, mc, axis=0)
            if dt.itemsize == 1:
                row = row.reshape(shape[1:])
            else:
                row = row.reshape(tuple(shape[1:]) + (dt.itemsize,))
            out.append(lax.bitcast_convert_type(row, dt))
        return out

    # -- the per-device schedule body (runs inside shard_map) -----------
    def _body(self, params_local, feed_arg, base_rng, step):
        """Forward schedule AND backward, differentiated per device: the
        body returns (partial loss, local param grads). Taking the grad
        INSIDE the shard_map is what makes the one-program design hold
        up — the transpose of each tick's ``ppermute`` is the inverse
        permute, so cotangents flow stage S-1 -> 0 across devices inside
        the same compiled program, and no jax AD machinery ever crosses
        the shard_map boundary."""
        axis = self.axis_name
        S, M, K = self.S, self.M, self.fuse_ticks
        r = lax.axis_index(axis)
        if self.feed_mode == "sharded":
            feed_local = jnp.squeeze(feed_arg, 0)
        else:
            feed_local = feed_arg
        shift = [(i, i + 1) for i in range(S - 1)]
        carry_dt = self.boundary_dtype or self.boundary_aval.dtype
        x0 = jnp.zeros(self.boundary_aval.shape, carry_dt)
        loss0 = jnp.float32(0.0)
        # loop carries change varying-over-mesh type inside the tick
        # loop; the initial values must already carry it
        x0 = lax.pcast(x0, (axis,), to="varying")
        loss0 = lax.pcast(loss0, (axis,), to="varying")

        if self.feed_mode == "sharded":
            def stage_call(s):
                br = self.branches[s]

                def call(plist, x, words, mc, rng):
                    return br(plist, x,
                              self._decode_feeds(words, s, mc), rng)
                return call
        else:
            def stage_call(s):
                br = self.branches[s]

                def call(plist, x, feeds_all, mc, rng):
                    feeds = [jnp.take(f, mc, axis=0)
                             for f in feeds_all[s]]
                    return br(plist, x, feeds, rng)
                return call
        wrapped = [stage_call(s) for s in range(S)]

        def schedule_loss(params_loc):
            plist = [jnp.squeeze(p, 0) for p in params_loc]

            def tick(carry, t):
                x_cur, loss_acc = carry
                m = t - r
                mc = jnp.clip(m, 0, M - 1)
                rng = jax.random.fold_in(base_rng, step * 131 + mc)
                # fill/drain ticks compute on zero lanes rather than
                # branching them out: an A/B with a lax.cond skip
                # measured ~1.5x SLOWER end-to-end (the per-tick branch
                # blocks fusion and costs more than the saved compute);
                # the garbage lanes' outputs receive zero cotangents, so
                # they contribute nothing to gradients. The inherent
                # overhead is (M+S-1)/M — amortize with M >> S.
                xin = x_cur.astype(self.boundary_aval.dtype)
                y, loss = lax.switch(r, wrapped, plist, xin, feed_local,
                                     mc, rng)
                valid = (m >= 0) & (m < M) & (r == S - 1)
                loss_acc = loss_acc + jnp.where(valid, loss, 0.0)
                y = y.astype(carry_dt)
                if shift:
                    y = lax.ppermute(y, axis, shift)
                return (y, loss_acc)

            # schedule driver: optional unrolled fill/drain around a
            # scan that advances K ticks per iteration. Padded extra
            # ticks (when K does not divide the looped count) spill
            # PAST the end of the region the scan covers — in-order, so
            # the schedule stays exact; ticks beyond M+S-2 only touch
            # the masked loss and the discarded x_last, never an
            # in-flight boundary.
            T = M + S - 1
            carry = (x0, loss0)
            n_pre = min(S - 1, T) if self.unroll_fill_drain else 0
            n_mid = max(M - S + 1, 0) if self.unroll_fill_drain else T
            niters = -(-n_mid // K) if n_mid else 0
            for t in range(n_pre):
                carry = tick(carry, t)
            if niters:
                def body(c, t0):
                    for k in range(K):
                        c = tick(c, t0 + k)
                    return c, None
                carry, _ = lax.scan(
                    body, carry, n_pre + K * jnp.arange(niters))
            for t in range(n_pre + K * niters, T):
                carry = tick(carry, t)
            # per-device partial of the mean-over-microbatches loss
            # (only the last stage's lane is nonzero): the cross-stage
            # reduction happens OUTSIDE the shard_map as a plain sum
            # over the [S] output — no in-body collective needed
            return carry[1] / M

        loss_part, grads_local = jax.value_and_grad(
            schedule_loss)(params_local)
        return loss_part[None], grads_local

    # -- interleaved (virtual-stage) schedule body ----------------------
    def _body_interleaved(self, params_local, feed_arg, base_rng, step):
        """The V>1 tick loop (see module docstring): one CHUNK per
        device per tick, boundary on a full-ring ppermute, the S-1 -> 0
        wraparound delayed through a [M-S+1] ring buffer in the carry
        (read slot ``t mod B`` before writing it — the value written
        there B ticks ago is exactly the chunk-group predecessor the
        device-0 lane consumes now). Differentiated in-body exactly
        like ``_body``: the transpose of the ring ppermute is the
        inverse ring, and the buffer's dynamic-slice transposes to a
        scatter-add, so cotangents retrace the schedule backwards
        inside the same compiled program."""
        axis = self.axis_name
        M, K, V, S = self.M, self.fuse_ticks, self.V, self.S_dev
        r = lax.axis_index(axis)
        if self.feed_mode == "sharded":
            feed_local = jnp.squeeze(feed_arg, 0)
        else:
            feed_local = feed_arg
        ring = [(i, (i + 1) % S) for i in range(S)]
        B = M - S + 1                   # wraparound delay (ticks)
        carry_dt = self.boundary_dtype or self.boundary_aval.dtype
        x0 = jnp.zeros(self.boundary_aval.shape, carry_dt)
        wbuf0 = jnp.zeros((B,) + tuple(self.boundary_aval.shape),
                          carry_dt)
        loss0 = jnp.float32(0.0)
        x0 = lax.pcast(x0, (axis,), to="varying")
        wbuf0 = lax.pcast(wbuf0, (axis,), to="varying")
        loss0 = lax.pcast(loss0, (axis,), to="varying")

        if self.feed_mode == "sharded":
            def chunk_call(c):
                br = self.branches[c]
                v = c // S              # static per branch

                def call(pstack, x, words, mc, rng):
                    plist = [p[v] for p in pstack]
                    return br(plist, x,
                              self._decode_feeds(words, c, mc), rng)
                return call
        else:
            def chunk_call(c):
                br = self.branches[c]
                v = c // S

                def call(pstack, x, feeds_all, mc, rng):
                    plist = [p[v] for p in pstack]
                    feeds = [jnp.take(f, mc, axis=0)
                             for f in feeds_all[c]]
                    return br(plist, x, feeds, rng)
                return call
        wrapped = [chunk_call(c) for c in range(self.S)]

        def schedule_loss(params_loc):
            # local leaves are [1, V, ...]: drop the stage-axis slice
            pstack = [jnp.squeeze(p, 0) for p in params_loc]

            def tick(carry, t):
                x_dir, wbuf, loss_acc = carry
                u = t - r
                vc = jnp.clip(u // M, 0, V - 1)
                mc = jnp.clip(u - vc * M, 0, M - 1)
                rng = jax.random.fold_in(base_rng, step * 131 + mc)
                slot = jnp.mod(t, B)
                # read BEFORE this tick's write: the slot holds the
                # value received B ticks ago — the device-0 lane's
                # chunk-group predecessor output
                x_wrap = lax.dynamic_index_in_dim(wbuf, slot, 0,
                                                  keepdims=False)
                x_in = jnp.where(r == 0, x_wrap, x_dir)
                xin = x_in.astype(self.boundary_aval.dtype)
                c = vc * S + r
                y, loss = lax.switch(c, wrapped, pstack, xin,
                                     feed_local, mc, rng)
                # the loss lane: last chunk (v = V-1) on the last
                # device, microbatch in range
                valid = ((u >= (V - 1) * M) & (u < V * M)
                         & (r == S - 1))
                loss_acc = loss_acc + jnp.where(valid, loss, 0.0)
                y = y.astype(carry_dt)
                y = lax.ppermute(y, axis, ring)
                wbuf = lax.dynamic_update_index_in_dim(wbuf, y, slot, 0)
                return (y, wbuf, loss_acc)

            T = V * M + S - 1
            niters = -(-T // K)
            carry = (x0, wbuf0, loss0)

            def body(cc, t0):
                for k in range(K):
                    cc = tick(cc, t0 + k)
                return cc, None

            carry, _ = lax.scan(body, carry, K * jnp.arange(niters))
            return carry[2] / M

        loss_part, grads_local = jax.value_and_grad(
            schedule_loss)(params_local)
        return loss_part[None], grads_local

    @staticmethod
    def _norm_feeds(feeds_all):
        return tuple(tuple(fs) for fs in feeds_all)

    def build(self, stacked_params, feeds_all):
        """Jit the full training step (forward schedule + backward +
        optimizer) with donated param/slot buffers."""
        from jax.sharding import PartitionSpec as P
        feeds_all = self._norm_feeds(feeds_all)
        p_specs = tuple(P(self.axis_name) for _ in stacked_params)
        if self.feed_mode == "sharded":
            self._build_layout(feeds_all)
            f_specs = P(self.axis_name)
        else:
            f_specs = jax.tree_util.tree_map(lambda _: P(), feeds_all)
        body = self._body if self.V == 1 else self._body_interleaved
        loss_and_grads = _shard_map_unchecked(
            body, mesh=self.mesh,
            in_specs=(p_specs, f_specs, P(), P()),
            out_specs=(P(self.axis_name), p_specs))
        opt = self.optimizer

        def train_step(params, opt_state, feeds, base_rng, step, lr):
            loss_parts, grads = loss_and_grads(params, feeds, base_rng,
                                               step)
            loss = jnp.sum(loss_parts)
            new_p, new_s = [], []
            for p, g, slots in zip(params, grads, opt_state):
                # stacked [S, ...] leaves: the optimizers are
                # elementwise, so one update IS the per-stage update
                pj, sj = opt.update_one(p, opt._apply_l2(p, g), slots,
                                        lr, step)
                new_p.append(pj)
                new_s.append(sj)
            return loss, new_p, new_s

        self._step = jax.jit(train_step, donate_argnums=(0, 1))
        return self._step

    def _replicate(self, feeds_all):
        """Replicated feed transport (feed_mode="replicated"): every
        feed enters the SPMD program on every device. Identity-cached so
        pinned feeds transfer once, not once per step."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P())
        out = []
        for s, fs in enumerate(feeds_all):
            row = []
            for j, f in enumerate(fs):
                key = (s, j)
                hit = self._feed_cache.get(key)
                if hit is not None and hit[0] is f:
                    row.append(hit[1])
                    continue
                fr = jax.device_put(f, sh)
                self._feed_cache[key] = (f, fr)
                row.append(fr)
            out.append(tuple(row))
        return tuple(out)

    def step(self, stacked_params, opt_state, feeds_all, base_rng, step,
             lr):
        tel = self.telemetry
        if self._step is None:
            with tel.span("cpp_build"):
                self.build(stacked_params, feeds_all)
            tel.inc("jit_compiles")
        if not tel.enabled:
            if self.feed_mode == "sharded":
                feeds = self._pack_feeds(feeds_all)
            else:
                feeds = self._replicate(feeds_all)
            return self._step(tuple(stacked_params), tuple(opt_state),
                              feeds, base_rng, jnp.int32(step),
                              jnp.float32(lr))
        # the whole schedule is ONE program — host-side spans can't see
        # individual ticks, so the dispatch span carries the tick-loop
        # structure (fill/steady/drain counts) as attributes instead
        if self.feed_mode == "sharded":
            with tel.span("cpp_pack_feeds",
                          bytes=self.S_dev * self._row_bytes):
                feeds = self._pack_feeds(feeds_all)
        else:
            with tel.span("cpp_replicate_feeds"):
                feeds = self._replicate(feeds_all)
        S, M = self.S, self.M
        fill = (S - 1 if self.unroll_fill_drain and self.V == 1
                else 0)
        # black box: the schedule is one SPMD program dispatched by
        # every rank in lockstep — a "collective"-group flight entry per
        # dispatch gives the blackbox CLI an aligned seq stream, so the
        # rank that stops dispatching (or dispatches one more than the
        # rest) is nameable by its first seq divergence
        frec = tel.flight_start("collective", "cpp_dispatch",
                                tag=f"step{int(step)}",
                                nbytes=self.S_dev * self._row_bytes)
        with tel.span("cpp_dispatch", ticks=self.n_ticks, fill=fill,
                      drain=fill, fuse_ticks=self.fuse_ticks,
                      stages=S, microbatches=M,
                      virtual_stages=self.V,
                      bytes=self.S_dev * self._row_bytes):
            out = self._step(tuple(stacked_params), tuple(opt_state),
                             feeds, base_rng, jnp.int32(step),
                             jnp.float32(lr))
        tel.flight_complete(frec)
        return out

    # -- placement helpers ----------------------------------------------
    def stack_stage_values(self, per_stage):
        """Host-stack one per-stage value list into the schedule's
        layout: [S, ...] for V=1, [S_dev, V, ...] with chunk
        ``v*S_dev + r`` at position ``[r, v]`` for the interleaved
        schedule — dim 0 is the stage mesh axis either way."""
        if self.V == 1:
            return np.stack([np.asarray(x) for x in per_stage])
        return np.stack([
            np.stack([np.asarray(per_stage[v * self.S_dev + r])
                      for v in range(self.V)])
            for r in range(self.S_dev)])

    def place_stacked(self, arrs_by_stage):
        """Stack per-stage host/device arrays into [S(,V), ...] sharded
        over the stage axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh, P(self.axis_name))
        out = []
        nper = len(arrs_by_stage[0])
        for j in range(nper):
            stacked = self.stack_stage_values(
                [arrs_by_stage[s][j] for s in range(self.S)])
            out.append(jax.device_put(stacked, sh))
        return out
