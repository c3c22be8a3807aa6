"""Pipeline-parallel executors: GPipe and PipeDream (1F1B).

Reference parity: SubExecutor4Gpipe (executor.py:457-809) and
SubExecutor4Pipedream (executor.py:812-1337). Users assign stages exactly
like the reference — ``with ht.context(ht.tpu(i)):`` around layer blocks —
and pass ``gpipe=True`` / ``pipedream=True`` to the Executor.

TPU-native architecture, instead of a translated scheduler:

  * The graph splits into stages at device boundaries; each stage's
    subgraph traces into jitted programs pinned to its chip. Boundary
    values move by ``jax.device_put`` (ICI DMA); async dispatch overlaps
    stages without the reference's NCCL group-call pairing dance
    (executor.py:1246-1277).
  * **GPipe is compiled**: each stage's whole microbatch loop is ONE
    ``lax.scan`` program — one forward dispatch per producing stage and
    one fused backward+optimizer dispatch per stage per step (2S-1
    dispatches for a linear S-stage pipeline), instead of one dispatch
    per microbatch per phase. The backward block rematerializes the
    forward inside ``jax.vjp`` — per-stage activation recomputation, the
    memory policy GPipe's paper prescribes, so only the stacked boundary
    tensors persist between dispatches.
  * Backward everywhere is the stage-level ``jax.vjp`` with forward
    recomputation inside the jitted program.
  * PipeDream weight stashing (reference deep-copies weights per in-flight
    microbatch, executor.py:896-1020) is just *keeping the old params
    pytree* for the microbatch's backward — functional updates make
    stashing a reference-count, not a copy. 1F1B's per-microbatch updates
    create a true cross-stage dependency zigzag (stage s's next forward
    needs the update from its last backward), so its schedule stays
    host-driven, with backward+apply fused into one dispatch per stage
    per microbatch.

LR-scheduler semantics (pinned round 4): the scheduler advances once per
**global step** under both schedules. 1F1B still applies one optimizer
update per microbatch (PipeDream semantics) but all M updates within a
step share the step's learning rate, so StepScheduler decays identically
under GPipe and PipeDream on the same config.
"""
from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.autodiff import find_topo_sort
from ..graph.node import ExecContext
from ..optimizer import OptimizerOp
from ..ops.variable import PlaceholderOp
from ..ops.comm import PipelineSendOp, PipelineReceiveOp
from .. import telemetry as _telemetry

__all__ = ["PipelineSubExecutor", "analytic_bubble_fraction",
           "virtual_stage_program"]

_NULL_CM = _telemetry._NULL_SPAN        # shared no-op context manager


class _FlightSpan:
    """Span context manager that also completes a flight-ring record on
    exit — one object so stage-block call sites stay a single `with`."""

    __slots__ = ("_tel", "_span", "_rec")

    def __init__(self, tel, span, rec):
        self._tel = tel
        self._span = span
        self._rec = rec

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._tel.flight_complete(self._rec)
        return self._span.__exit__(*exc)


class _Stage:
    __slots__ = ("index", "device", "devices", "mesh", "node_spec",
                 "nodes", "param_nodes", "feed_nodes",
                 "in_nodes", "out_nodes", "consumed_outs",
                 "fwd", "bwd_apply", "fwd_block", "bwd_block",
                 "fwd_block_raw", "bwd_block_raw", "params", "owner")

    def __init__(self, index, device, devices=None):
        self.index = index
        self.device = device
        self.owner = 0           # owning worker-process rank (multi-host)
        self.devices = devices or [device]  # >1 => TP/DP inside the stage
        self.mesh = None                    # per-stage mesh when sharded
        self.node_spec = {}                 # node -> PartitionSpec
        self.nodes = []
        self.param_nodes = []
        self.feed_nodes = []
        self.in_nodes = []       # boundary inputs (produced by earlier stages)
        self.out_nodes = []      # boundary outputs + eval nodes here
        self.consumed_outs = []  # out_nodes consumed by other stages
        self.fwd = None          # per-microbatch jit (1F1B)
        self.bwd_apply = None    # fused bwd+optimizer jit (1F1B)
        self.fwd_block = None    # scan-over-microbatches jit (GPipe)
        self.bwd_block = None    # scan bwd + optimizer jit (GPipe)
        self.fwd_block_raw = None   # untraced block fns — composed into a
        self.bwd_block_raw = None   # whole-step jit when stages co-reside
        self.params = {}

    def put(self, val, spec=None):
        """Move a value onto this stage: its single device, or its mesh
        (replicated unless a spec is given)."""
        if self.mesh is None:
            return jax.device_put(val, self.device)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(val, NamedSharding(
            self.mesh, spec if spec is not None else PartitionSpec()))


class _StageConfig:
    """Config view a TP/DP stage traces under: the stage's own mesh and
    spec table, everything else from the executor config (the composed
    PP+TP mode of reference context.py:652-656 — equal-width stage groups,
    each internally model-parallel)."""

    def __init__(self, base, mesh, node_spec):
        self._base = base
        self.mesh = mesh
        self.node_spec = node_spec

    def spec_for(self, node):
        return self.node_spec.get(node)

    def __getattr__(self, name):
        return getattr(self._base, name)


def _device_key(node):
    """Stage identity of a node from its raw_ctx (reference assigns stages
    by `with ht.context(gpu(i))`; a tuple context means the stage's devices
    cooperate on one model-parallel copy, context.py:652-656)."""
    ctx = node.raw_ctx
    if ctx is None or ctx.worker_num + ctx.server_num == 0:
        return None
    first = ctx[0]
    if isinstance(first, tuple):
        return tuple((d.hostname, d.device_id) for d in first)
    return ((first.hostname, first.device_id),)


def _drive_1f1b(forward, backward, nstages, M, telemetry=None):
    """The 1F1B order: min(nstages, M) warmup forwards, then alternate
    backward/forward, then drain. ONE definition — the in-process,
    fused (trace-time), and cross-process runners all execute exactly
    this sequence, which is what makes their losses bit-equivalent.
    ``telemetry`` (host-driven runners only — the fused runner replays
    this at trace time where wall clocks mean nothing) brackets the
    fill / steady-state / drain phases as spans, so the pipeline's
    bubble structure is visible on the Perfetto timeline."""
    warmup = min(nstages, M)
    tel = telemetry
    span = (tel.span if tel is not None and tel.enabled
            else lambda *a, **k: _NULL_CM)
    done_f = done_b = 0
    with span("pp_fill", warmup=warmup):
        for _ in range(warmup):
            forward(done_f)
            done_f += 1
    with span("pp_steady", ticks=max(M - warmup, 0)):
        while done_f < M:
            backward(done_b)
            done_b += 1
            forward(done_f)
            done_f += 1
    with span("pp_drain", ticks=M - done_b):
        while done_b < M:
            backward(done_b)
            done_b += 1


def analytic_bubble_fraction(nstages, M, V=1, schedule="1f1b"):
    """Inherent idle fraction of a pipeline schedule: ``nstages`` is
    the TOTAL user stage count; with ``V`` virtual stages per
    device/rank the pipeline depth folds to ``nstages/V`` and the
    schedule runs ``V*M`` chunk-ticks — the Megatron interleaving
    result, bubble ~ 1/V smaller at small M. GPipe and 1F1B share the
    same fill/drain analytics (1F1B reduces peak memory, not bubble).
    The cost-model planner and the telemetry both use this ONE
    definition."""
    del schedule
    V = max(1, int(V))
    S = max(1, int(nstages))
    if V > 1 and S % V == 0:
        sd = S // V
        return (sd - 1) / (V * M + sd - 1)
    return (S - 1) / (M + S - 1)


def virtual_stage_program(nranks, nstages, M):
    """Per-rank symbolic (phase, microbatch, stage) event program of
    the interleaved staged schedule: stages placed round-robin (stage s
    on rank s % nranks, i.e. V = nstages/nranks chunks per rank),
    driven by the SAME ``_drive_1f1b`` order the runtime executes —
    forward(m) visits a rank's chunks in ascending stage order,
    backward(m) in descending. This is the event-program form
    ``analysis/deadlock.py`` verifies (HT3xx) before a fleet launches
    with ``virtual_stages > 1``."""
    progs = {r: [] for r in range(nranks)}

    def forward(m):
        for s in range(nstages):
            progs[s % nranks].append(("fwd", m, s))

    def backward(m):
        for s in reversed(range(nstages)):
            progs[s % nranks].append(("bwd", m, s))

    _drive_1f1b(forward, backward, nstages, M)
    return progs


def _owner_of(hostname, nprocs):
    """Worker-process rank that owns a stage hostname (reference device
    specs 'hostname:gpu:i', context.py:59-63). Conventions:
      * 'worker<k>' -> rank k (unambiguous on shared machines),
      * a hostname listed in HETU_HOSTS -> its index,
      * 'localhost'/'127.0.0.1' (or any name, single-process) -> rank 0.
    In a multi-process run any OTHER hostname is a loud error — and
    deliberately so for the LOCAL nodename too (ADVICE round-5 #1): rank
    k's nodename is not rank j's, so a nodename escape hatch would
    resolve the same stage to different owners on different ranks and
    silently split the pipeline. Only names every rank maps identically
    ('worker<k>', HETU_HOSTS entries, localhost) are accepted; the
    launcher exports HETU_HOSTS for real multi-host fleets."""
    if hostname.startswith("worker") and hostname[6:].isdigit():
        return int(hostname[6:]) % max(nprocs, 1)
    hosts = os.environ.get("HETU_HOSTS", "")
    if hosts:
        names = hosts.split(",")
        if hostname in names:
            return names.index(hostname)
    if nprocs > 1 and hostname not in ("localhost", "127.0.0.1"):
        raise ValueError(
            f"stage hostname {hostname!r} does not map to any worker "
            f"rank (nprocs={nprocs}): use 'worker<k>' names or list it "
            "in HETU_HOSTS — refusing a rank-local fallback that would "
            "resolve differently on other ranks")
    return 0


def splice_send_recv(eval_nodes, topo=None):
    """Reference-style explicit PipelineSend/Receive markers: pair them
    in construction order (send k <-> recv k), bind each recv to its
    send, and splice consumers through to the payload — the boundary
    transfer itself is the stage executor's job (device_put over ICI
    in-process; DCN send/recv when stages span hosts), so the markers
    carry placement intent, not data. Mutates the graph; call before
    parameter materialization (HetuConfig does, for pipeline modes)."""
    if topo is None:
        topo = find_topo_sort(eval_nodes)
    recvs = [n for n in topo if isinstance(n, PipelineReceiveOp)]
    if not recvs:
        return
    # a recv has no input edge, so its send is unreachable from the
    # eval nodes — pull unconsumed sends from the construction registry.
    # Exact pairing: a count mismatch (e.g. stale sends from an
    # abandoned graph build) fails loudly rather than silently wiring
    # receives to another graph's payloads.
    sends = PipelineSendOp.pending()
    assert len(sends) == len(recvs), (
        f"unpaired pipeline markers: {len(sends)} pending sends vs "
        f"{len(recvs)} receives — stale sends from an abandoned graph? "
        f"build and run pipeline graphs one at a time")
    PipelineSendOp.consume(sends)
    payload = {}
    for s, r in zip(sorted(sends, key=lambda n: n.id),
                    sorted(recvs, key=lambda n: n.id)):
        r.bound_send = s
        payload[r] = s.inputs[0]
        payload[s] = s.inputs[0]
    for node in topo:
        if node in payload or not node.inputs:
            continue
        node.inputs = [payload.get(i, i) for i in node.inputs]


class PipelineSubExecutor:
    """Runs one training subgraph under a pipeline schedule."""

    def __init__(self, name, eval_node_list, config, schedule="gpipe",
                 num_microbatches=None):
        self.name = name
        self.config = config
        self.schedule = schedule
        self.optimizer_ops = [n for n in eval_node_list
                              if isinstance(n, OptimizerOp)]
        assert len(self.optimizer_ops) == 1, \
            "pipeline executor expects exactly one train_op"
        self.optimizer = self.optimizer_ops[0].optimizer
        self.eval_nodes = [n for n in eval_node_list
                           if not isinstance(n, OptimizerOp)]
        self.loss_node = self.eval_nodes[0]

        # forward graph only: the pipeline differentiates per stage with
        # jax.vjp — the graph-level adjoint subgraph is not traced here
        topo = find_topo_sort(self.eval_nodes)
        topo = self._splice_send_recv(topo)
        self._build_stages(topo)
        # interleaved (virtual-stage) schedule: V > 1 means the user's
        # S stages fold onto S/V devices (collective mode) or S/V
        # worker ranks (staged 1F1B with round-robin contexts); the
        # analytic bubble shrinks to (S/V - 1)/(V*M + S/V - 1)
        self.virtual_stages = max(1, int(
            (getattr(config, "pp_options", None) or {})
            .get("virtual_stages", 1) or 1))
        self.num_microbatches = num_microbatches or max(
            2, len(self.stages))
        if self.virtual_stages > 1 and self.multiproc:
            # staged interleaved 1F1B = round-robin stage->rank
            # placement under the unchanged 1F1B driver (the channel's
            # blocking recvs realize the interleaving); a blocked
            # placement would silently forfeit the bubble reduction
            owners = [s.owner for s in self.stages]
            nr = len(set(owners))
            if len(owners) % nr != 0 or any(
                    o != owners[i % nr] for i, o in enumerate(owners)):
                raise ValueError(
                    f"virtual_stages={self.virtual_stages} needs "
                    f"round-robin stage ownership (stage i on rank "
                    f"i % {nr}); got owners {owners} — cycle the "
                    f"worker contexts V times")
        self.step_count = 0
        self.batch_num = None
        self._losses_ema = None
        self._fused_step = None   # whole-step jit when stages co-reside
        self._feed_cache = {}     # (stage, node) -> (src jax.Array, stacked)
        self._cpp = None          # CollectiveGPipe (schedule="collective")
        self._cpp_params = None   # stacked [S, ...] param leaves
        self._cpp_slots = None    # stacked optimizer slots per position

    # ------------------------------------------------------------------
    def _build_stages(self, topo):
        devices = jax.devices()
        keys = []
        for node in topo:
            k = _device_key(node)
            if k is not None and k not in keys and not isinstance(
                    node, PlaceholderOp):
                keys.append(k)
        if not keys:
            keys = [(("localhost", 0),)]
        key_to_stage = {k: i for i, k in enumerate(keys)}
        nstages = len(keys)
        stages = []
        for i in range(nstages):
            devs = [devices[d[1] % len(devices)] for d in keys[i]]
            stages.append(_Stage(i, devs[0], devs))

        assign = {}
        for node in topo:
            if isinstance(node, PlaceholderOp):
                continue
            k = _device_key(node)
            s = key_to_stage.get(k)
            if s is None:
                # unplaced compute follows its deepest input's stage
                s = max((assign.get(i, 0) for i in node.inputs), default=0)
            assign[node] = s
            stages[s].nodes.append(node)
        for node in topo:
            if isinstance(node, PlaceholderOp):
                consumers = [assign[n] for n in topo
                             if not isinstance(n, PlaceholderOp)
                             and node in n.inputs]
                s = min(consumers) if consumers else 0
                assign[node] = s
                if node.tensor_value is not None or \
                        node.initializer is not None:
                    stages[s].param_nodes.append(node)
                else:
                    stages[s].feed_nodes.append(node)

        # boundary edges
        for node in topo:
            if isinstance(node, PlaceholderOp):
                continue
            s = assign[node]
            for inp in node.inputs:
                si = assign[inp]
                if si != s and not isinstance(inp, PlaceholderOp):
                    if inp not in stages[s].in_nodes:
                        stages[s].in_nodes.append(inp)
                    if inp not in stages[si].out_nodes:
                        stages[si].out_nodes.append(inp)
        for ev in self.eval_nodes:
            s = assign[ev]
            if ev not in stages[s].out_nodes:
                stages[s].out_nodes.append(ev)
        all_ins = set()
        for st in stages:
            all_ins.update(st.in_nodes)
        for st in stages:
            st.consumed_outs = [n for n in st.out_nodes if n in all_ins]
        self.assign = assign
        self.stages = stages
        # node -> consuming stages, precomputed once (both multiproc
        # runners walk boundary consumers per node)
        self._consumers = {}
        for st in stages:
            for node in st.in_nodes:
                self._consumers.setdefault(node, []).append(st)
        # multi-process ownership: stages whose hostname maps to another
        # worker rank execute there; boundaries cross via the p2p channel
        self.my_rank = int(os.environ.get("HETU_PROC_ID", "0"))
        nprocs = int(os.environ.get("HETU_NUM_PROCS", "1"))
        for st, key in zip(stages, keys):
            st.owner = _owner_of(key[0][0], nprocs)
        self.multiproc = (nprocs > 1
                          and len({s.owner for s in stages}) > 1)
        if self.multiproc:
            # a stage's device indexes the OWNER's local devices (after
            # jax.distributed, jax.devices() is global and remote entries
            # are not addressable here); unowned stages never dispatch
            local = jax.local_devices()
            for st, key in zip(stages, keys):
                if st.owner == self.my_rank:
                    st.devices = [local[d[1] % len(local)] for d in key]
                    st.device = st.devices[0]
        self._plan_stage_tp(topo)

    def _plan_stage_tp(self, topo):
        """PP+TP / PP+DP composition: propagate NodeStatus over the whole
        graph once, then build one mesh per multi-device stage and lower
        that stage's statuses to PartitionSpecs over it (reference pairs
        equal-width stage device groups the same way, context.py:652-656;
        here XLA's SPMD partitioner supplies the in-stage collectives)."""
        from .mesh import mesh_for_statuses
        from .planner import propagate_statuses, spec_for_status

        status = propagate_statuses(topo)
        if not status:
            return
        for stage in self.stages:
            if self.multiproc and stage.owner != self.my_rank:
                continue   # a remote process plans its own stages
            if len(stage.devices) < 2:
                continue
            stage_nodes = set(stage.nodes) | set(stage.param_nodes)
            sts = {n: st for n, st in status.items() if n in stage_nodes}
            if not any(st is not None and st.is_dist()
                       for st in sts.values()):
                continue  # degenerate (1,1)-only stage: no mesh needed
            mesh, model_axes = mesh_for_statuses(
                sts.values(), devices=stage.devices)
            stage.mesh = mesh
            for node, st in sts.items():
                spec = spec_for_status(st, model_axes, node=node)
                if spec is not None:
                    stage.node_spec[node] = spec

    # ------------------------------------------------------------------
    def _stage_machinery(self, stage):
        """Shared tracing machinery for a stage: the raw subgraph function,
        the in-jit optimizer apply, and the loss-cotangent injection."""
        nodes = stage.nodes
        param_order = list(stage.param_nodes)
        feed_order = list(stage.feed_nodes)
        in_order = list(stage.in_nodes)
        out_order = list(stage.out_nodes)
        # Always trace under the stage's own mesh view (None for plain
        # stages) — the executor's global mesh/spec table must not leak
        # into a stage jit, or a dispatch in a single-device stage would
        # be constrained onto foreign devices.
        config = _StageConfig(self.config, stage.mesh, stage.node_spec)
        opt = self.optimizer
        loss_idx = (out_order.index(self.loss_node)
                    if self.loss_node in out_order else -1)
        nodes_by_sid = {str(n.id): n for n in param_order}

        def stage_fn(params, boundary_in, feeds, rng):
            ectx = ExecContext(training=True, base_rng=rng, config=config)
            ectx.params = {n: params[str(n.id)] for n in param_order}
            env = {}
            env.update(zip(in_order, boundary_in))
            env.update(zip(feed_order, feeds))
            for n in param_order:
                env[n] = ectx.params[n]
            for node in nodes:
                if node in env:
                    continue
                env[node] = node.compute([env[i] for i in node.inputs],
                                         ectx)
            return [env[o] for o in out_order]

        def one_bwd(params, ins, feeds, rng, ext_cots, loss_scale):
            """vjp of the stage over one microbatch; forward rematerialized
            inside. ext_cots align with out_order; None entries mean
            zero cotangent, except the loss slot which gets loss_scale."""
            def f(p, b):
                return stage_fn(p, b, feeds, rng)
            outs, vjp = jax.vjp(f, params, ins)
            cots = []
            for i, (o, c) in enumerate(zip(outs, ext_cots)):
                if i == loss_idx:
                    base = jnp.full_like(o, loss_scale)
                    cots.append(base if c is None else c + base)
                else:
                    cots.append(jnp.zeros_like(o) if c is None else c)
            dparams, dins = vjp(cots)
            loss_val = outs[loss_idx] if loss_idx >= 0 else None
            return dparams, dins, loss_val

        def apply_params(params, gsum, opt_state, lr, step):
            if not param_order:
                return params, opt_state
            pv = {nodes_by_sid[sid]: v for sid, v in params.items()}
            gv = {nodes_by_sid[sid]: v for sid, v in gsum.items()}
            new_p, new_s = opt.update(pv, gv, opt_state, lr, step)
            return {str(n.id): v for n, v in new_p.items()}, new_s

        return stage_fn, one_bwd, apply_params, loss_idx

    def _make_stage_fns(self, stage):
        """Per-microbatch jitted fwd and fused bwd+apply (1F1B path).
        RNG derivation (fold_in of the constant base key by step and
        microbatch) happens inside the jit — no per-step host key
        dispatches."""
        stage_fn, one_bwd, apply_params, _ = self._stage_machinery(stage)

        def fwd_fn(params, boundary_in, feeds, base_rng, step, m):
            rng = jax.random.fold_in(base_rng, step * 131 + m)
            return stage_fn(params, boundary_in, feeds, rng)

        stage.fwd = jax.jit(fwd_fn)

        def bwd_apply_fn(stash_params, cur_params, boundary_in, feeds,
                         base_rng, step, m, cotangents, opt_state, lr):
            # backward against the *stashed* weights (PipeDream semantics:
            # the microbatch's forward weights), update the *current*
            # weights — fused so the 1F1B inner loop costs one dispatch
            # per stage per microbatch instead of two.
            rng = jax.random.fold_in(base_rng, step * 131 + m)
            dparams, dins, _ = one_bwd(stash_params, boundary_in, feeds,
                                       rng, cotangents, 1.0)
            new_p, new_s = apply_params(cur_params, dparams, opt_state,
                                        lr, step)
            return dins, new_p, new_s

        stage.bwd_apply = jax.jit(bwd_apply_fn)

    def _make_stage_blocks(self, stage):
        """Compiled GPipe phase programs (round-4 review #1): the stage's
        whole microbatch loop runs as ONE jitted ``lax.scan`` dispatch.

        * ``fwd_block`` scans the forward over M stacked microbatches and
          returns stacked boundary outputs — built only for stages whose
          outputs other stages consume.
        * ``bwd_block`` rematerializes the forward per microbatch inside
          ``jax.vjp``, accumulates parameter gradients in the scan carry,
          emits stacked input-cotangents, and finishes with the stage's
          optimizer apply — forward+backward+update of a terminal stage
          is a single dispatch.

        The raw (untraced) block functions are also kept: when every
        stage resolves to the same physical device, `_build_fused_step`
        composes them into ONE whole-step jit — a single dispatch per
        training step.
        """
        stage_fn, one_bwd, apply_params, loss_idx = \
            self._stage_machinery(stage)
        M = self.num_microbatches

        def fwd_block(params, stacked_ins, stacked_feeds, base_rng, step):
            def body(_, xs):
                ins, feeds, m = xs
                rng = jax.random.fold_in(base_rng, step * 131 + m)
                return None, stage_fn(params, ins, feeds, rng)
            _, outs = jax.lax.scan(
                body, None, (stacked_ins, stacked_feeds, jnp.arange(M)))
            return outs

        def bwd_block(params, stacked_ins, stacked_feeds, base_rng, step,
                      stacked_cots, opt_state, lr):
            gzero = jax.tree_util.tree_map(jnp.zeros_like, params)

            def body(acc, xs):
                ins, feeds, m, cots = xs
                rng = jax.random.fold_in(base_rng, step * 131 + m)
                dparams, dins, loss_val = one_bwd(params, ins, feeds, rng,
                                                  cots, 1.0 / M)
                acc = jax.tree_util.tree_map(jnp.add, acc, dparams)
                return acc, (dins, loss_val)

            gsum, (stacked_dins, losses) = jax.lax.scan(
                body, gzero,
                (stacked_ins, stacked_feeds, jnp.arange(M), stacked_cots))
            new_params, new_state = apply_params(params, gsum, opt_state,
                                                 lr, step)
            loss_mean = jnp.mean(losses) if losses is not None else None
            return new_params, new_state, stacked_dins, loss_mean

        stage.fwd_block_raw = fwd_block
        stage.bwd_block_raw = bwd_block
        stage.fwd_block = jax.jit(fwd_block)
        stage.bwd_block = jax.jit(bwd_block)

    # ------------------------------------------------------------------
    def _place_params(self, executor):
        for stage in self.stages:
            if self.multiproc and stage.owner != self.my_rank:
                continue   # remote stages materialize on their owner
            for p in stage.param_nodes:
                sid = str(p.id)
                arr = executor.params[sid]
                # dispatched params store sharded over the stage mesh
                stage.params[sid] = stage.put(arr, stage.node_spec.get(p))
            if self.schedule == "gpipe":
                if stage.bwd_block is None:
                    self._make_stage_blocks(stage)
                    # two jitted programs per stage (fwd/bwd blocks)
                    self.config.telemetry.inc("jit_compiles", 2)
            elif stage.fwd is None:
                self._make_stage_fns(stage)
                self.config.telemetry.inc("jit_compiles", 2)
        # when every stage resolves to the same physical chip (e.g. a
        # pipeline program exercised on one real device), boundary
        # transfers are no-ops and the whole schedule fuses into ONE
        # jitted program — a single dispatch per training step
        single = (not self.multiproc
                  and len(self.stages) > 0
                  and all(s.mesh is None for s in self.stages)
                  and all(s.device == self.stages[0].device
                          for s in self.stages))
        if single and self._fused_step is None:
            if self.schedule == "gpipe":
                self._build_fused_gpipe()
            else:
                self._build_fused_1f1b()
            self.config.telemetry.inc("jit_compiles")

    # ------------------------------------------------------------------
    def _build_fused_gpipe(self):
        """Whole-step GPipe program: the per-stage raw scan blocks
        composed into one jit (valid because all stages co-reside, so
        inter-stage movement is the identity)."""
        stages = self.stages
        assign = self.assign

        def step_fn(params_list, feeds_list, base_rng, step, opt_list,
                    lr):
            env = {}
            ins_store = {}
            for st in stages:
                ins = [env[assign[n]][
                    stages[assign[n]].out_nodes.index(n)]
                    for n in st.in_nodes]
                ins_store[st.index] = ins
                if st.consumed_outs:
                    env[st.index] = st.fwd_block_raw(
                        params_list[st.index], ins, feeds_list[st.index],
                        base_rng, step)
            cot_map = {}
            loss_mean = None
            new_params = [None] * len(stages)
            new_states = [None] * len(stages)
            for st in reversed(stages):
                cots = [cot_map.get(n) for n in st.out_nodes]
                np_, ns_, dins, lm = st.bwd_block_raw(
                    params_list[st.index], ins_store[st.index],
                    feeds_list[st.index], base_rng, step, cots,
                    opt_list[st.index], lr)
                if lm is not None:
                    loss_mean = lm
                for node, d in zip(st.in_nodes, dins):
                    prev = cot_map.get(node)
                    cot_map[node] = d if prev is None else prev + d
                new_params[st.index] = np_
                new_states[st.index] = ns_
            return new_params, new_states, loss_mean

        self._fused_step = jax.jit(step_fn)

    def _build_fused_1f1b(self):
        """Whole-step PipeDream program for co-resident stages: the exact
        host 1F1B schedule — per-microbatch weight stashing and updates —
        replayed as a pure function and compiled once. Stashing is free
        under functional updates: the 'stash' is just the params value
        captured at forward-trace time."""
        stages = self.stages
        assign = self.assign
        M = self.num_microbatches
        machinery = [self._stage_machinery(st) for st in stages]
        loss_node = self.loss_node

        def step_fn(params_list, feeds_list, base_rng, step, opt_list,
                    lr):
            cur = list(params_list)
            opt = list(opt_list)
            env_out = {}
            stage_ins = {}
            stash = {}
            losses = []
            cot_map = {}

            def rng_for(m):
                return jax.random.fold_in(base_rng, step * 131 + m)

            def forward(m):
                stash[m] = list(cur)
                for st in stages:
                    stage_fn = machinery[st.index][0]
                    ins = [env_out[(m, assign[n])][
                        stages[assign[n]].out_nodes.index(n)]
                        for n in st.in_nodes]
                    feeds_m = [f[m] for f in feeds_list[st.index]]
                    env_out[(m, st.index)] = stage_fn(
                        cur[st.index], ins, feeds_m, rng_for(m))
                    stage_ins[(m, st.index)] = ins
                ls = assign[loss_node]
                losses.append(env_out[(m, ls)][
                    stages[ls].out_nodes.index(loss_node)])

            def backward(m):
                for st in reversed(stages):
                    _, one_bwd, apply_params, _ = machinery[st.index]
                    cots = [cot_map.get((m, n)) for n in st.out_nodes]
                    feeds_m = [f[m] for f in feeds_list[st.index]]
                    dparams, dins, _ = one_bwd(
                        stash[m][st.index], stage_ins[(m, st.index)],
                        feeds_m, rng_for(m), cots, 1.0)
                    new_p, new_s = apply_params(
                        cur[st.index], dparams, opt[st.index], lr, step)
                    cur[st.index] = new_p
                    opt[st.index] = new_s
                    for node, d in zip(st.in_nodes, dins):
                        prev = cot_map.get((m, node))
                        cot_map[(m, node)] = (d if prev is None
                                              else prev + d)
                del stash[m]

            _drive_1f1b(forward, backward, len(stages), M)
            return cur, opt, jnp.mean(jnp.stack(losses))

        self._fused_step = jax.jit(step_fn)

    def _run_fused(self, executor, stacked_feeds):
        new_params, new_states, loss = self._fused_step(
            [dict(s.params) for s in self.stages], stacked_feeds,
            executor.base_rng, np.int32(self.step_count),
            [self._stage_opt_state(executor, s) for s in self.stages],
            np.float32(self.optimizer.learning_rate))
        for st, np_, ns_ in zip(self.stages, new_params, new_states):
            self._commit_stage_update(executor, st, np_, ns_)
        return loss

    @staticmethod
    def _feed_value(feed_dict, node):
        """Feed as a host array or, if already device-resident (pinned
        inputs / dataloader output), as the jax.Array itself — slicing
        and reshaping then happen on device instead of forcing a
        device->host sync per step."""
        v = feed_dict[node]
        if isinstance(v, jax.Array):
            return v
        from .. import ndarray
        if isinstance(v, ndarray.NDArray):
            return v.value
        return np.asarray(v)

    def _split_feeds(self, feed_dict, m_total):
        """Global batch -> per-microbatch feed lists per stage."""
        per_stage = []
        for stage in self.stages:
            if self.multiproc and stage.owner != self.my_rank:
                per_stage.append([])     # remote stage feeds itself
                continue
            feeds_m = []
            for m in range(m_total):
                vals = []
                for node in stage.feed_nodes:
                    v = self._feed_value(feed_dict, node)
                    mb = v.shape[0] // m_total
                    assert mb * m_total == v.shape[0], \
                        (f"batch {v.shape[0]} not divisible into "
                         f"{m_total} microbatches")
                    vals.append(stage.put(v[m * mb:(m + 1) * mb]))
                feeds_m.append(vals)
            per_stage.append(feeds_m)
        return per_stage

    def _stack_feeds(self, feed_dict, m_total, place=True):
        """Global batch -> per-stage [M, mb, ...] stacked feeds, one
        device transfer per feed node per step (GPipe compiled path).
        ``place=False`` skips the per-stage device placement — the
        collective mode replicates feeds over its own mesh instead, and
        placing them on a stage device first would double the
        host->device traffic."""
        per_stage = []
        for stage in self.stages:
            vals = []
            if self.multiproc and stage.owner != self.my_rank:
                per_stage.append(vals)   # remote stage feeds itself
                continue
            for node in stage.feed_nodes:
                v = self._feed_value(feed_dict, node)
                mb = v.shape[0] // m_total
                assert mb * m_total == v.shape[0], \
                    (f"batch {v.shape[0]} not divisible into "
                     f"{m_total} microbatches")
                stacked_shape = (m_total, mb) + v.shape[1:]
                if isinstance(v, jax.Array):
                    # jax.Arrays are immutable, so identity-keyed caching
                    # of the stacked view is sound — a pinned feed costs
                    # its reshape dispatch once, not once per step
                    ck = (stage.index, node)
                    hit = self._feed_cache.get(ck)
                    if hit is not None and hit[0] is v:
                        vals.append(hit[1])
                        continue
                    stacked = jnp.reshape(v[:mb * m_total], stacked_shape)
                    if place:
                        stacked = stage.put(stacked)
                    self._feed_cache[ck] = (v, stacked)
                else:
                    stacked = v[:mb * m_total].reshape(stacked_shape)
                    if place:
                        stacked = stage.put(stacked)
                vals.append(stacked)
            per_stage.append(vals)
        return per_stage

    # ------------------------------------------------------------------
    def run(self, executor, feed_dict=None, convert_to_numpy_ret_vals=False):
        if self.schedule == "collective":
            feed_dict = feed_dict or {}
            loss = self._run_collective(
                executor, self._stack_feeds(feed_dict,
                                            self.num_microbatches,
                                            place=False))
            return self._finish_step(executor, loss,
                                     convert_to_numpy_ret_vals)
        if not self.stages[0].params and not any(
                s.params for s in self.stages):
            self._place_params(executor)
        feed_dict = feed_dict or {}
        M = self.num_microbatches
        if self._fused_step is not None:
            loss = self._run_fused(executor,
                                   self._stack_feeds(feed_dict, M))
        elif self.multiproc and self.schedule != "gpipe":
            feeds = self._split_feeds(feed_dict, M)
            loss = self._run_1f1b_multiproc(executor, feeds, M)
        elif self.multiproc:
            loss = self._run_gpipe_multiproc(
                executor, self._stack_feeds(feed_dict, M), M)
        elif self.schedule == "gpipe":
            loss = self._run_gpipe_compiled(
                executor, self._stack_feeds(feed_dict, M), M)
        else:
            feeds = self._split_feeds(feed_dict, M)
            losses = self._run_1f1b(executor, feeds, M)
            loss = jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))
        return self._finish_step(executor, loss, convert_to_numpy_ret_vals)

    def _stage_span(self, name, stage_index):
        """Span for one stage-level dispatch (no-op when telemetry is
        off — the kwargs dict only builds on the enabled path). The
        enabled path also feeds the flight ring (group ``sched``): a
        fleet that hangs mid-schedule leaves "how far each rank's
        schedule got" in the black box even though the span never
        exports."""
        tel = self.config.telemetry
        if not tel.enabled:
            return _NULL_CM
        rec = tel.flight_start("sched", name, tag=f"stage{stage_index}")
        return _FlightSpan(tel, tel.span(name, stage=stage_index), rec)

    def _recv_traced(self, ch, tag, stage_index):
        """Blocking channel recv, recorded as that stage's idle (bubble)
        interval: the time a stage spends waiting on a boundary tensor
        from another rank IS its pipeline bubble."""
        tel = self.config.telemetry
        if not tel.enabled:
            return ch.recv(tag)
        t0 = tel.clock()
        val = ch.recv(tag)
        t1 = tel.clock()
        tel.complete("pp_stage_idle", t0, t1,
                     {"stage": stage_index, "tag": tag,
                      "bytes": int(val.nbytes)})
        tel.observe(f"pp_stage{stage_index}_idle_ms", (t1 - t0) / 1e6)
        return val

    def _finish_step(self, executor, loss, convert_to_numpy_ret_vals):
        # the LR scheduler advances once per GLOBAL step under all
        # schedules (pinned semantics; see module docstring)
        self.optimizer.lr_sched.step()
        self.step_count += 1
        tel = self.config.telemetry
        if tel.enabled:
            # analytic bubble at this (S, M, V): the inherent
            # (S-1)/(M+S-1) idle fraction, shrinking to
            # (S/V - 1)/(V*M + S/V - 1) under the interleaved
            # schedule; measured per-stage idle comes from the
            # pp_stage_idle spans on cross-process runs
            S, M = len(self.stages), self.num_microbatches
            V = self.virtual_stages
            if V > 1 and S % V == 0:
                sd = S // V
                tel.observe("pp_bubble_fraction",
                            (sd - 1) / (V * M + sd - 1))
            else:
                tel.observe("pp_bubble_fraction", (S - 1) / (M + S - 1))
        results = []
        for ev in self.eval_nodes:
            results.append(loss if ev is self.loss_node else None)
        results.append(None)     # train_op slot
        from .. import ndarray
        out = []
        for r in results:
            if r is None:
                out.append(None)
            elif convert_to_numpy_ret_vals:
                out.append(np.asarray(r))
            else:
                out.append(ndarray.NDArray(r, None))
        return out

    # -- forward of one microbatch through one stage (1F1B) --------------
    def _fwd_stage(self, stage, m, feeds, env_out, base_rng, step):
        ins = []
        for node in stage.in_nodes:
            src_stage = self.assign[node]
            val = env_out[(m, src_stage)][
                self.stages[src_stage].out_nodes.index(node)]
            ins.append(stage.put(val))
        outs = stage.fwd(stage.params, ins, feeds[stage.index][m],
                         base_rng, step, np.int32(m))
        env_out[(m, stage.index)] = outs
        return ins

    # ------------------------------------------------------------------
    def _splice_send_recv(self, topo):
        splice_send_recv(self.eval_nodes, topo)
        topo = find_topo_sort(self.eval_nodes)
        return [n for n in topo
                if not isinstance(n, (PipelineSendOp, PipelineReceiveOp))]

    # -- per-stage slices of the global optimizer state ------------------
    @staticmethod
    def _stage_opt_state(executor, stage):
        full = executor.opt_state or {}
        return {n.id: full[n.id] for n in stage.param_nodes
                if n.id in full}

    def _commit_stage_update(self, executor, stage, new_params, new_state):
        for sid, v in new_params.items():
            stage.params[sid] = v
            executor.params[sid] = v
        if new_state:
            executor.opt_state = {**(executor.opt_state or {}),
                                  **new_state}

    # ------------------------------------------------------------------
    def _run_gpipe_compiled(self, executor, stacked_feeds, M):
        """GPipe as compiled per-stage scan blocks: forward blocks in
        stage order, then fused backward+apply blocks in reverse — 2S-1
        dispatches for a linear pipeline (reference SubExecutor4Gpipe
        semantics, executor.py:716-784: all microbatch forwards, all
        backwards, one optimizer apply)."""
        base_rng = executor.base_rng
        lr = np.float32(self.optimizer.learning_rate)
        step = np.int32(self.step_count)

        env = {}        # stage.index -> stacked outs (aligned out_nodes)
        ins_store = {}  # stage.index -> stacked boundary ins
        for stage in self.stages:
            ins = []
            for node in stage.in_nodes:
                src = self.assign[node]
                val = env[src][self.stages[src].out_nodes.index(node)]
                ins.append(stage.put(val))
            ins_store[stage.index] = ins
            if stage.consumed_outs:
                with self._stage_span("pp_fwd_block", stage.index):
                    env[stage.index] = stage.fwd_block(
                        stage.params, ins, stacked_feeds[stage.index],
                        base_rng, step)

        cot_map = {}    # boundary node -> stacked cotangent (consumer-sum)
        loss_mean = None
        for stage in reversed(self.stages):
            cots = [cot_map.get(n) for n in stage.out_nodes]
            with self._stage_span("pp_bwd_block", stage.index):
                new_params, new_state, stacked_dins, lm = stage.bwd_block(
                    stage.params, ins_store[stage.index],
                    stacked_feeds[stage.index], base_rng, step, cots,
                    self._stage_opt_state(executor, stage), lr)
            if lm is not None:
                loss_mean = lm
            for node, d in zip(stage.in_nodes, stacked_dins):
                # a boundary node feeding several later stages gets one
                # cotangent per consumer — sum them, don't overwrite
                d = self.stages[self.assign[node]].put(d)
                prev = cot_map.get(node)
                cot_map[node] = d if prev is None else prev + d
            self._commit_stage_update(executor, stage, new_params,
                                      new_state)
        return loss_mean

    # ------------------------------------------------------------------
    def _build_collective(self, executor, stacked_feeds):
        """Lower the stage graph onto one SPMD program (collective_pp.py):
        validate the linear-chain/homogeneity contract, build uniform
        switch branches from the per-stage subgraph functions, stack
        params and optimizer slots over the stage axis."""
        from jax.sharding import Mesh
        from .collective_pp import CollectiveGPipe

        stages = self.stages
        S = len(stages)
        if self.multiproc:
            raise ValueError(
                "pipeline_mode='collective' is the in-slice SPMD mode; "
                "stages spanning worker processes keep the staged "
                "runners (the p2p channel is the DCN transport)")
        if S < 2:
            raise ValueError(
                "pipeline_mode='collective' needs >= 2 stages (wrap "
                "layer blocks in distinct ht.context(...) scopes)")
        devs = [s.device for s in stages]
        V = self.virtual_stages
        if V > 1:
            # interleaved schedule: S = S_dev * V user stages placed
            # round-robin (stage i on device i % S_dev), each device
            # owning V chunks — the Megatron virtual-stage layout
            if S % V != 0:
                raise ValueError(
                    f"virtual_stages={V} must divide the stage count "
                    f"{S}: build V chunks per device (contexts "
                    f"cycling over the same device list V times)")
            s_dev = S // V
            if len(set(devs[:s_dev])) != s_dev or any(
                    devs[i] != devs[i % s_dev] for i in range(S)):
                raise ValueError(
                    f"interleaved collective pipeline needs round-robin "
                    f"placement: stage i on device i % {s_dev} "
                    f"(got {devs}) — cycle the ht.context(...) device "
                    f"list V={V} times over the same devices")
        else:
            s_dev = S
            if len(set(devs)) != S:
                raise ValueError(
                    "pipeline_mode='collective' needs one distinct "
                    f"device per stage; got {devs} — on a single chip "
                    "use the staged/fused runners instead (or fold "
                    "stages with pp_options virtual_stages)")
        if any(s.mesh is not None for s in stages):
            raise ValueError(
                "pipeline_mode='collective' does not compose with "
                "in-stage TP/DP meshes yet; use the staged runners")
        loss_stage = self.assign[self.loss_node]
        if loss_stage != S - 1:
            raise ValueError(
                f"collective pipeline expects the loss on the last "
                f"stage (found on stage {loss_stage})")
        for i, st in enumerate(stages):
            if i == 0 and st.in_nodes:
                raise ValueError("stage 0 must not consume boundaries")
            if i > 0 and (len(st.in_nodes) != 1 or
                          self.assign[st.in_nodes[0]] != i - 1):
                raise ValueError(
                    f"collective pipeline needs a linear chain with one "
                    f"boundary tensor per stage; stage {i} consumes "
                    f"{[(n.name, self.assign[n]) for n in st.in_nodes]}")
            if i < S - 1 and len(st.consumed_outs) != 1:
                raise ValueError(
                    f"stage {i} must export exactly one boundary tensor "
                    f"(got {len(st.consumed_outs)})")
        shapes0 = [np.shape(executor.params[str(p.id)])
                   for p in stages[0].param_nodes]
        for st in stages[1:]:
            shp = [np.shape(executor.params[str(p.id)])
                   for p in st.param_nodes]
            if shp != shapes0:
                raise ValueError(
                    "collective pipeline needs homogeneous stages: "
                    f"stage {st.index} params {shp} != stage 0 "
                    f"{shapes0} — make the stage blocks uniform or use "
                    "the staged runners")

        machinery = [self._stage_machinery(st)[0] for st in stages]
        # boundary aval: trace the stage chain abstractly once
        rng_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        b_aval = None
        for i, st in enumerate(stages):
            p_avals = {str(p.id): jax.ShapeDtypeStruct(
                np.shape(executor.params[str(p.id)]),
                executor.params[str(p.id)].dtype)
                for p in st.param_nodes}
            f_avals = [jax.ShapeDtypeStruct(f.shape[1:], f.dtype)
                       for f in stacked_feeds[i]]
            ins = [b_aval] if st.in_nodes else []
            outs = jax.eval_shape(machinery[i], p_avals, ins, f_avals,
                                  rng_aval)
            if i < S - 1:
                out_aval = outs[st.out_nodes.index(st.consumed_outs[0])]
                if b_aval is not None and (
                        out_aval.shape != b_aval.shape
                        or out_aval.dtype != b_aval.dtype):
                    raise ValueError(
                        "collective pipeline needs one uniform boundary "
                        f"shape; stage {i} emits {out_aval} after "
                        f"{b_aval}")
                b_aval = out_aval

        loss_node = self.loss_node

        def make_branch(s):
            st = stages[s]
            stage_fn = machinery[s]
            pnodes = list(st.param_nodes)

            def branch(plist, x, feeds, rng):
                params = {str(n.id): v for n, v in zip(pnodes, plist)}
                ins = [x] if st.in_nodes else []
                outs = stage_fn(params, ins, feeds, rng)
                if s < S - 1:
                    y = outs[st.out_nodes.index(st.consumed_outs[0])]
                    # zero loss derived from y so every branch's outputs
                    # share the same varying-over-mesh type (shard_map
                    # rejects mixed unvarying/varying switch branches)
                    return y, (jnp.mean(y) * 0.0).astype(jnp.float32)
                loss = outs[st.out_nodes.index(loss_node)]
                loss = jnp.mean(loss).astype(jnp.float32)
                y = jnp.zeros(b_aval.shape, b_aval.dtype) + \
                    (loss * 0.0).astype(b_aval.dtype)
                return y, loss

            return branch

        mesh = Mesh(np.asarray(devs[:s_dev]), axis_names=("stage",))
        # tick-loop/feed-transport/boundary-dtype/virtual-stage knobs
        # (see CollectiveGPipe docstring); Executor(pp_options={...})
        opts = dict(getattr(self.config, "pp_options", None) or {})
        opts.setdefault("virtual_stages", V)
        cpp = CollectiveGPipe([make_branch(s) for s in range(S)],
                              b_aval, self.num_microbatches, mesh,
                              "stage", self.optimizer,
                              telemetry=self.config.telemetry, **opts)
        self._cpp = cpp
        self._cpp_params = cpp.place_stacked(
            [[executor.params[str(p.id)] for p in st.param_nodes]
             for st in stages])
        # stacked optimizer slots per position (same elementwise
        # update; the interleaved layout folds stages to [S_dev, V])
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("stage"))
        slots = []
        full = executor.opt_state or {}
        for j, p0 in enumerate(stages[0].param_nodes):
            keys = sorted(full.get(p0.id, {}))
            slots.append({
                k: jax.device_put(cpp.stack_stage_values(
                    [full[st.param_nodes[j].id][k] for st in stages]),
                    sh)
                for k in keys})
        self._cpp_slots = slots

    def _run_collective(self, executor, stacked_feeds):
        if self._cpp is None:
            self._build_collective(executor, stacked_feeds)
            # ONE jitted unstack for the whole write-back (S*P*slots
            # individual slice dispatches per step would re-introduce
            # the host-dispatch overhead this mode exists to remove).
            # Interleaved layout: stage s lives at [s % S_dev, s // S_dev]
            sd, v = self._cpp.S_dev, self._cpp.V

            def _at(arr, s):
                return arr[s] if v == 1 else arr[s % sd][s // sd]

            self._cpp_unstack = jax.jit(
                lambda ps, ss: (
                    [[_at(p, s) for p in ps]
                     for s in range(len(self.stages))],
                    [[{k: _at(x, s) for k, x in slot.items()}
                      for slot in ss]
                     for s in range(len(self.stages))]))
        loss, new_p, new_s = self._cpp.step(
            self._cpp_params, self._cpp_slots, stacked_feeds,
            executor.base_rng, self.step_count,
            self.optimizer.learning_rate)
        self._cpp_params, self._cpp_slots = new_p, new_s
        # async write-back so save()/tests read fresh values (no host
        # sync: the unstacked views materialize on demand)
        per_stage_p, per_stage_s = self._cpp_unstack(new_p, new_s)
        for s, st in enumerate(self.stages):
            for j, p in enumerate(st.param_nodes):
                executor.params[str(p.id)] = per_stage_p[s][j]
                if per_stage_s[s][j]:
                    executor.opt_state[p.id] = per_stage_s[s][j]
        return loss

    def _run_gpipe_multiproc(self, executor, stacked_feeds, M):
        """GPipe with stages spanning worker processes: each rank runs
        only the stages it owns; boundary activations and cotangents
        cross ranks through the host-mediated p2p channel (reference
        PipelineSend/Recv over NCCL p2p -> numpy over TCP/DCN here).
        Channel recv order doubles as the cross-rank schedule — no
        separate synchronization. Only the rank owning the loss stage
        returns a loss value."""
        from .p2p import get_channel
        ch = get_channel()
        base_rng = executor.base_rng
        lr = np.float32(self.optimizer.learning_rate)
        step = np.int32(self.step_count)
        sc = self.step_count

        consumers_of = lambda node: self._consumers.get(node, ())  # noqa: E731

        env = {}
        ins_store = {}
        for stage in self.stages:
            if stage.owner != self.my_rank:
                continue
            ins = []
            for node in stage.in_nodes:
                src = self.stages[self.assign[node]]
                if src.owner == self.my_rank:
                    val = env[src.index][src.out_nodes.index(node)]
                else:
                    val = self._recv_traced(
                        ch, f"f{sc}:{node.id}:{stage.index}",
                        stage.index)
                ins.append(stage.put(val))
            ins_store[stage.index] = ins
            if stage.consumed_outs:
                with self._stage_span("pp_fwd_block", stage.index):
                    outs = stage.fwd_block(stage.params, ins,
                                           stacked_feeds[stage.index],
                                           base_rng, step)
                env[stage.index] = outs
                for node in stage.consumed_outs:
                    val = None
                    for cons in consumers_of(node):
                        if cons.owner == self.my_rank:
                            continue
                        if val is None:   # one d2h sync per boundary
                            val = np.asarray(
                                outs[stage.out_nodes.index(node)])
                        ch.send(cons.owner,
                                f"f{sc}:{node.id}:{cons.index}", val)

        cot_map = {}
        loss_mean = None
        for stage in reversed(self.stages):
            if stage.owner != self.my_rank:
                continue
            cots = []
            for node in stage.out_nodes:
                c = cot_map.get(node)
                for cons in consumers_of(node):
                    if cons.owner == self.my_rank:
                        continue   # local consumers summed via cot_map
                    d = stage.put(self._recv_traced(
                        ch, f"b{sc}:{node.id}:{cons.index}",
                        stage.index))
                    c = d if c is None else c + d
                cots.append(c)
            with self._stage_span("pp_bwd_block", stage.index):
                new_params, new_state, stacked_dins, lm = stage.bwd_block(
                    stage.params, ins_store[stage.index],
                    stacked_feeds[stage.index], base_rng, step, cots,
                    self._stage_opt_state(executor, stage), lr)
            if lm is not None:
                loss_mean = lm
            for node, d in zip(stage.in_nodes, stacked_dins):
                src = self.stages[self.assign[node]]
                if src.owner == self.my_rank:
                    d = src.put(d)
                    prev = cot_map.get(node)
                    cot_map[node] = d if prev is None else prev + d
                else:
                    ch.send(src.owner,
                            f"b{sc}:{node.id}:{stage.index}",
                            np.asarray(d))
            self._commit_stage_update(executor, stage, new_params,
                                      new_state)
        return loss_mean

    def _run_1f1b_multiproc(self, executor, feeds, M):
        """1F1B across worker processes: each rank executes its
        projection of the SAME global schedule as the in-process
        `_run_1f1b` (uniform warmup, then alternate), so the math —
        which weight version each microbatch's forward sees — is
        bit-identical to single-process PipeDream; blocking channel
        recvs turn the data dependencies into the cross-rank schedule
        (the channel's reader thread drains sockets, so sends never
        rendezvous and the projected order cannot deadlock). Returns
        the per-step mean loss on the loss-owning rank, None elsewhere
        (same contract as `_run_gpipe_multiproc`)."""
        from .p2p import get_channel
        ch = get_channel()
        sc = self.step_count
        base_rng = executor.base_rng
        lr = np.float32(self.optimizer.learning_rate)
        step = np.int32(self.step_count)
        own = [s for s in self.stages if s.owner == self.my_rank]
        loss_sidx = self.assign[self.loss_node]
        env_out, stage_ins, stash, cot_map = {}, {}, {}, {}
        losses = []

        consumers_of = lambda node: self._consumers.get(node, ())  # noqa: E731

        def forward(m):
            stash[m] = {s.index: dict(s.params) for s in own}
            for stage in own:
                ins = []
                for node in stage.in_nodes:
                    src = self.stages[self.assign[node]]
                    if src.owner == self.my_rank:
                        val = env_out[(m, src.index)][
                            src.out_nodes.index(node)]
                    else:
                        val = self._recv_traced(
                            ch, f"pf{sc}:{m}:{node.id}:{stage.index}",
                            stage.index)
                    ins.append(stage.put(val))
                outs = stage.fwd(stage.params, ins,
                                 feeds[stage.index][m], base_rng, step,
                                 np.int32(m))
                env_out[(m, stage.index)] = outs
                stage_ins[(m, stage.index)] = ins
                for node in stage.consumed_outs:
                    val = None
                    for cons in consumers_of(node):
                        if cons.owner == self.my_rank:
                            continue
                        if val is None:   # one d2h per boundary tensor
                            val = np.asarray(
                                outs[stage.out_nodes.index(node)])
                        ch.send(cons.owner,
                                f"pf{sc}:{m}:{node.id}:{cons.index}",
                                val)
            if self.stages[loss_sidx].owner == self.my_rank:
                losses.append(env_out[(m, loss_sidx)][
                    self.stages[loss_sidx].out_nodes.index(
                        self.loss_node)])

        def backward(m):
            for stage in reversed(own):
                cots = []
                for node in stage.out_nodes:
                    c = cot_map.get((m, node))
                    for cons in consumers_of(node):
                        if cons.owner == self.my_rank:
                            continue   # local consumers summed in map
                        d = stage.put(self._recv_traced(
                            ch, f"pb{sc}:{m}:{node.id}:{cons.index}",
                            stage.index))
                        c = d if c is None else c + d
                    cots.append(c)
                dins, new_params, new_state = stage.bwd_apply(
                    stash[m][stage.index], stage.params,
                    stage_ins.pop((m, stage.index)),
                    feeds[stage.index][m], base_rng, step, np.int32(m),
                    cots, self._stage_opt_state(executor, stage), lr)
                for node, d in zip(stage.in_nodes, dins):
                    src = self.stages[self.assign[node]]
                    if src.owner == self.my_rank:
                        d = src.put(d)
                        prev = cot_map.get((m, node))
                        cot_map[(m, node)] = d if prev is None \
                            else prev + d
                    else:
                        ch.send(src.owner,
                                f"pb{sc}:{m}:{node.id}:{stage.index}",
                                np.asarray(d))
                self._commit_stage_update(executor, stage, new_params,
                                          new_state)
            del stash[m]
            for s in own:
                env_out.pop((m, s.index), None)
            # boundary cotangents were consumed within this backward
            # (reversed stage order): free them with the stash
            for key in [k for k in cot_map if k[0] == m]:
                del cot_map[key]

        _drive_1f1b(forward, backward, len(self.stages), M,
                    telemetry=self.config.telemetry)
        if losses:
            return jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))
        return None

    def _run_1f1b(self, executor, feeds, M):
        """1F1B: warmup forwards then alternate, per-microbatch updates
        with stashed weights (reference SubExecutor4Pipedream)."""
        env_out = {}
        stage_ins = {}
        stash = {}
        losses = []
        base_rng = executor.base_rng
        lr = np.float32(self.optimizer.learning_rate)
        step = np.int32(self.step_count)
        nstages = len(self.stages)
        cot_map = {}

        def forward(m):
            stash[m] = [dict(s.params) for s in self.stages]
            for stage in self.stages:
                ins = self._fwd_stage(stage, m, feeds, env_out,
                                      base_rng, step)
                stage_ins[(m, stage.index)] = ins
            loss_stage = self.assign[self.loss_node]
            losses.append(env_out[(m, loss_stage)][
                self.stages[loss_stage].out_nodes.index(self.loss_node)])

        def backward(m):
            for stage in reversed(self.stages):
                cots = [cot_map.get((m, n)) for n in stage.out_nodes]
                dins, new_params, new_state = stage.bwd_apply(
                    stash[m][stage.index], stage.params,
                    stage_ins[(m, stage.index)], feeds[stage.index][m],
                    base_rng, step, np.int32(m), cots,
                    self._stage_opt_state(executor, stage), lr)
                for node, d in zip(stage.in_nodes, dins):
                    d = self.stages[self.assign[node]].put(d)
                    prev = cot_map.get((m, node))
                    cot_map[(m, node)] = d if prev is None else prev + d
                self._commit_stage_update(executor, stage, new_params,
                                          new_state)
            del stash[m]
            # free this microbatch's activations/cotangents with its
            # stash — 1F1B's bounded in-flight memory depends on it
            for stage in self.stages:
                env_out.pop((m, stage.index), None)
                stage_ins.pop((m, stage.index), None)
            for key in [k for k in cot_map if k[0] == m]:
                del cot_map[key]

        _drive_1f1b(forward, backward, nstages, M,
                    telemetry=self.config.telemetry)
        return losses           # device values: no host sync per loss
