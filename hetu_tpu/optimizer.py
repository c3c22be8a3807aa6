"""Optimizers.

Reference parity: python/hetu/optimizer.py — SGD / Momentum(+Nesterov) /
AdaGrad / Adam / AdamW, each with an l2-regularizer and sparse
(IndexedSlices) variants, plus ``OptimizerOp`` whose ``backward_hook``
splices the per-parameter communication op chosen by the node strategy
(optimizer.py:130-148).

TPU-native: ``update`` is a *pure function* (params, grads, slots, lr) ->
(new params, new slots) executed inside the compiled train step, with
parameter donation making it in-place in HBM.

Each optimizer's arithmetic is written ONCE, as a rule over rows
(``sgd_rows``, ``adagrad_rows``, ``adam_rows``): the dense branch
applies it to the whole arrays, the sparse branch (an ``IndexedSlices``
gradient: an embedding table) to the rows the step looked up, lazily —
a row not looked up keeps its parameter and slots bit for bit. Which
tables take which sparse path (``sparse_update_path``, one
``sparse_update`` instant a traced table says which and why):

- ``kernel``: a float32 table of whole 128-lane rows, eight rows or
  more, in a step traced for a TPU that no mesh partitions (GPT-2's
  ``wte`` / ``wpe``, the sparse decoder's token table, BERT's position
  table). ``ops/pallas_sparse_update.py`` takes the ids sorted, each
  beside its gradient row (``IndexedSlices.sorted_rows``), adds the
  gradients of one row up, and reads and writes each looked-up row of
  the parameter and its slots once, in place.
- ``composed``: everything else — the CTR tables of width 4 to 16
  (``lanes``), BERT's two-row token-type table (``rows``), a step under
  ``dp`` (GSPMD cannot partition a Mosaic kernel: ``mesh``), any
  backend but a TPU (``platform``), a caller that hands no step context
  (``caller``: the pipeline drivers). ``IndexedSlices.dedup``, then one
  row gather and one row scatter a table; ``dedup``'s padding ids lie
  past the table and are dropped.

The bfloat16 working copy of a table (``OptimizerOp.compute``) is still
a whole ``astype`` of the new master: under the ``(8,128)(2,1)`` tiling
a bfloat16 row shares its 32-bit words with its neighbour, so a row-wise
write of the copy is a read-modify-write of pairs of rows, a different
problem from the float32 rows here; the whole convert costs what the
bandwidth allows (PERF.md §5).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .graph.node import Op
from .lr_scheduler import FixedScheduler
from .ndarray import IndexedSlices
from .ops.variable import PlaceholderOp

__all__ = ["Optimizer", "OptimizerOp", "SGDOptimizer", "MomentumOptimizer",
           "AdaGradOptimizer", "AdamOptimizer", "AdamWOptimizer",
           "sentinel_stats", "sparse_update_path"]


# ---------------------------------------------------------------------------
# The update rules: rule(g, rows, s, *hyper) -> new rows. ``g`` the
# gradient, ``rows`` the parameter and then each slot (whole arrays, or
# the looked-up rows of each), ``s`` the step's traced scalars (a
# sequence, or the kernel's SMEM ref), ``hyper`` Python numbers. Traced
# as they stand into the dense branch, the composed sparse branch and
# the body of ``hetu_sparse_rows_update``.
# ---------------------------------------------------------------------------

def sgd_rows(g, rows, s):
    (p,) = rows
    return [p - s[0] * g]


def adagrad_rows(g, rows, s, eps):
    p, accum = rows
    accum = accum + g * g
    return [p - s[0] * g / (jnp.sqrt(accum) + eps), accum]


def adam_rows(g, rows, s, beta1, beta2, epsilon):
    """``s[0]`` is the bias-corrected step size; a fourth array is
    amsgrad's running maximum."""
    p, m, v, *vmax = rows
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    vhat = jnp.maximum(vmax[0], v) if vmax else v
    return [p - s[0] * m / (jnp.sqrt(vhat) + epsilon), m, v,
            *([vhat] if vmax else [])]


def sparse_update_path(param, slots, site):
    """``(path, reason)`` of one table's sparse update, from what the
    code can see: ``"kernel"`` (``hetu_sparse_rows_update``; ``reason``
    None) in a step traced for a TPU that no mesh partitions, on
    float32 tables whose rows are whole lanes; else ``"composed"`` and
    the first condition that failed: ``caller`` (no step context came
    with the call), ``platform``, ``mesh``, ``lanes``, ``rows``,
    ``dtype``. ``site`` is ``(table name, ectx)`` or None."""
    from .ops import pallas_sparse_update as kernel
    from .ops.attention import _use_pallas
    ectx = site[1] if site else None
    mesh = getattr(getattr(ectx, "config", None), "mesh", None)
    for reason, holds in (
            ("caller", ectx is not None),
            ("platform", _use_pallas()),
            ("mesh", mesh is None or mesh.size == 1)):
        if not holds:
            return "composed", reason
    reason = kernel.supported(
        *(param.shape if param.ndim == 2 else (0, 0)),
        [param.dtype, *(v.dtype for v in slots.values())])
    return ("composed", reason) if reason else ("kernel", None)


def _update_rows(rule, hyper, scalars, param, grad, slots, site,
                 composed=None):
    """``rule`` applied to the rows of ``param`` and ``slots`` that the
    sparse ``grad`` names, the gradients of one row summed first; every
    other row is left as it lies. ``composed`` is a branch's own
    composed form, where it has one that needs no dedup. One
    ``sparse_update`` instant a traced call."""
    from . import telemetry
    path, reason = sparse_update_path(param, slots, site)
    telemetry.get_telemetry().instant(
        "sparse_update", table=site[0] if site else "", rows=param.shape[0],
        width=param.shape[-1], ids=grad.get_flat_indices().shape[0],
        slots=len(slots), path=path, **({"reason": reason} if reason else {}))
    if path == "composed" and composed is not None:
        return composed(), slots
    tables = [param, *slots.values()]
    if path == "kernel":
        from .ops.pallas_sparse_update import hetu_sparse_rows_update
        new = hetu_sparse_rows_update(rule, hyper, *grad.sorted_rows(),
                                      scalars, tables)
    else:
        # dedup pads with ids past the table: the gather clamps them,
        # the scatter drops them
        idx, g = grad.dedup()
        new = rule(g, [t[idx] for t in tables], scalars, *hyper)
        new = [t.at[idx].set(rows, mode="drop")
               for t, rows in zip(tables, new)]
    return new[0], dict(zip(slots, new[1:]))


def parameter_scope(node):
    """One more level under the OptimizerOp's scope (``Op.scope``):
    ``.../<parameter name>``, around everything a step traces for that
    parameter's update, so a profile's optimizer time divides by
    table (docs/tools.md)."""
    return jax.named_scope(node.name.replace("/", "."))


def sentinel_stats(param, grad, new_param):
    """Device-side health sentinels for one parameter (telemetry/
    health.py): gradient global-norm, nonfinite element count, and
    update/weight ratio — three scalar reductions fused into the
    compiled step, fetched by the monitor at cadence. ``param`` /
    ``new_param`` may be None (PS-pushed grads have no worker-side
    update); the ratio reports 0 there."""
    vals = grad.values if isinstance(grad, IndexedSlices) else grad
    vals32 = vals.astype(jnp.float32)
    grad_norm = jnp.sqrt(jnp.sum(jnp.square(vals32)))
    nonfinite = jnp.sum(~jnp.isfinite(vals32)).astype(jnp.int32)
    if param is None or new_param is None:
        ratio = jnp.zeros((), jnp.float32)
    else:
        p32 = param.astype(jnp.float32)
        upd = jnp.sqrt(jnp.sum(jnp.square(
            new_param.astype(jnp.float32) - p32)))
        ratio = upd / (jnp.sqrt(jnp.sum(jnp.square(p32))) + 1e-12)
    return {"grad_norm": grad_norm, "nonfinite": nonfinite,
            "update_ratio": ratio}


class Optimizer:
    name = "Optimizer"

    def __init__(self, learning_rate, l2reg=0, loss_scale=None):
        if isinstance(learning_rate, FixedScheduler):
            self.lr_sched = learning_rate
        else:
            assert learning_rate >= 0
            self.lr_sched = FixedScheduler(learning_rate)
        assert l2reg >= 0
        self.l2reg = l2reg
        # static loss scaling (Micikevicius et al.): ``minimize`` builds
        # the gradients of loss_scale * loss so an fp16 backward stays
        # above min-normal, and ``update`` unscales them before the
        # parameter step — exact in fp32 master math. Worker-local
        # only; the HT806 check names this knob as the remediation.
        assert loss_scale is None or loss_scale > 0
        self.loss_scale = loss_scale
        self.params = None
        self.initiated = False

    @property
    def learning_rate(self):
        return self.lr_sched.get()

    @staticmethod
    def get_var_list(loss):
        visited = set()
        trainable = []

        def dfs(node):
            if id(node) in visited:
                return
            visited.add(id(node))
            if isinstance(node, PlaceholderOp) and node.trainable:
                trainable.append(node)
                return
            for n in node.inputs:
                dfs(n)

        for l in (loss if isinstance(loss, list) else [loss]):
            dfs(l)
        return trainable

    def minimize(self, loss, var_list=None):
        from .graph.autodiff import gradients
        if not var_list:
            var_list = self.get_var_list(loss)
        self.params = var_list
        target = loss
        if self.loss_scale and self.loss_scale != 1:
            from .ops.basic import mul_byconst_op
            s = float(self.loss_scale)
            if isinstance(loss, list):
                target = [mul_byconst_op(l, s) for l in loss]
            else:
                target = mul_byconst_op(loss, s)
        grads = gradients(target, self.params)
        return OptimizerOp(grads, self)

    # ------------------------------------------------------- functional API
    def init_state(self, param_vals):
        """Slot variables per param node -> pytree dict."""
        return {}

    def _apply_l2(self, param, grad):
        # unscale here, not in update(): every update path — update(),
        # the staged-pipeline driver, collective_pp's direct
        # update_one — funnels raw grads through _apply_l2 exactly
        # once, and l2 must apply to the UNSCALED gradient
        grad = self._unscale(grad)
        if self.l2reg > 0 and not isinstance(grad, IndexedSlices):
            return grad + self.l2reg * param
        return grad

    def _unscale(self, grad):
        """Divide the loss-scaled gradient back down (in the master
        dtype — the scale's whole point is that the division happens
        AFTER the fp16 backward, not inside it)."""
        s = self.loss_scale
        if not s or s == 1:
            return grad
        inv = 1.0 / float(s)
        if isinstance(grad, IndexedSlices):
            return IndexedSlices(indices=grad.indices,
                                 values=grad.values * inv,
                                 dense_shape=grad.dense_shape)
        return grad * inv

    def update_one(self, param, grad, slots, lr, step, site=None):
        """(new_param, new_slots) for one parameter. ``site`` is
        ``(parameter name, ectx)`` where the caller has them: what a
        sparse update's path is chosen from (``sparse_update_path``)."""
        raise NotImplementedError

    def update(self, param_vals, grad_vals, state, lr, step, ectx=None):
        """Pure update over dicts keyed by param node. Empty slot dicts are
        not inserted, so opt_state keeps a stable pytree structure across
        steps (a structure change would force a full re-trace)."""
        new_params, new_state = {}, {}
        for node, param in param_vals.items():
            grad = grad_vals[node]
            slots = state.get(node.id, {})
            with parameter_scope(node):
                p, s = self.update_one(param, self._apply_l2(param, grad),
                                       slots, lr, step, (node.name, ectx))
            new_params[node] = p
            if s or node.id in state:
                new_state[node.id] = s
        return new_params, new_state


class SGDOptimizer(Optimizer):
    name = "SGD"

    def update_one(self, param, grad, slots, lr, step, site=None):
        if isinstance(grad, IndexedSlices):
            # no slot to keep lazy: composed, the scatter itself adds
            # duplicates up, and a row not looked up gets nothing
            return _update_rows(
                sgd_rows, (), [lr], param, grad, slots, site,
                composed=lambda: param.at[grad.get_flat_indices()].add(
                    -lr * grad.get_dense_rows()))
        return sgd_rows(grad, [param], [lr])[0], slots


class MomentumOptimizer(Optimizer):
    name = "Momentum"

    def __init__(self, learning_rate=0.01, momentum=0.9, nesterov=False,
                 l2reg=0, loss_scale=None):
        super().__init__(learning_rate, l2reg, loss_scale)
        self.momentum = momentum
        self.nesterov = nesterov

    def init_state(self, param_vals):
        return {node.id: {"velocity": jnp.zeros_like(v)}
                for node, v in param_vals.items()}

    def update_one(self, param, grad, slots, lr, step, site=None):
        if isinstance(grad, IndexedSlices):
            grad = grad.to_dense()
        v = self.momentum * slots["velocity"] - lr * grad
        if self.nesterov:
            new_param = param + self.momentum * v - lr * grad
        else:
            new_param = param + v
        return new_param, {"velocity": v}


class AdaGradOptimizer(Optimizer):
    name = "AdaGrad"

    def __init__(self, learning_rate=0.01, initial_accumulator_value=0.0,
                 eps=1e-7, l2reg=0, loss_scale=None):
        super().__init__(learning_rate, l2reg, loss_scale)
        self.initial_accumulator_value = initial_accumulator_value
        self.eps = eps

    def init_state(self, param_vals):
        return {node.id: {"accum": jnp.full_like(
            v, self.initial_accumulator_value)}
            for node, v in param_vals.items()}

    def update_one(self, param, grad, slots, lr, step, site=None):
        slots = {"accum": slots["accum"]}
        if isinstance(grad, IndexedSlices):
            return _update_rows(adagrad_rows, (self.eps,), [lr], param,
                                grad, slots, site)
        param, accum = adagrad_rows(grad, [param, slots["accum"]], [lr],
                                    self.eps)
        return param, {"accum": accum}


class AdamOptimizer(Optimizer):
    name = "Adam"

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7, l2reg=0, amsgrad=False, loss_scale=None):
        super().__init__(learning_rate, l2reg, loss_scale)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.amsgrad = amsgrad

    def init_state(self, param_vals):
        state = {}
        for node, v in param_vals.items():
            slots = {"m": jnp.zeros_like(v), "v": jnp.zeros_like(v)}
            if self.amsgrad:
                slots["vmax"] = jnp.zeros_like(v)
            state[node.id] = slots
        return state

    def _step_scale(self, lr, step):
        t = step + 1
        bc1 = 1 - self.beta1 ** t
        bc2 = 1 - self.beta2 ** t
        return lr * jnp.sqrt(bc2) / bc1

    def update_one(self, param, grad, slots, lr, step, site=None):
        names = ("m", "v", "vmax") if self.amsgrad else ("m", "v")
        slots = {name: slots[name] for name in names}
        hyper = (self.beta1, self.beta2, self.epsilon)
        scalars = [self._step_scale(lr, step)]
        if isinstance(grad, IndexedSlices):
            return _update_rows(adam_rows, hyper, scalars, param, grad,
                                slots, site)
        param, *new = adam_rows(grad, [param, *slots.values()], scalars,
                                *hyper)
        return param, dict(zip(names, new))


class AdamWOptimizer(AdamOptimizer):
    name = "AdamW"

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7, weight_decay=0.01, l2reg=0,
                 loss_scale=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, l2reg,
                         loss_scale=loss_scale)
        self.weight_decay = weight_decay

    def update_one(self, param, grad, slots, lr, step, site=None):
        new_param, out = super().update_one(param, grad, slots, lr, step,
                                            site)
        if not isinstance(grad, IndexedSlices):
            new_param = new_param - lr * self.weight_decay * param
        return new_param, out


class OptimizerOp(Op):
    """Graph node applying the optimizer to its gradient inputs
    (reference optimizer.py:88-177). Inside a compiled step it writes the
    functional parameter/slot updates into the ExecContext, and beside
    each new master whose working copy came into the step
    (``ectx.work``) that copy's successor (``ectx.new_work``); the
    executor threads them to the next step with buffer donation.
    """

    role = "opt"

    def __init__(self, grads, optimizer):
        super().__init__(OptimizerOp, grads, None)
        self.name = "Optimizer_%s" % optimizer.name
        self.optimizer = optimizer
        self.comm_mode = None

    def compute(self, input_vals, ectx):
        opt = self.optimizer
        params = opt.params
        if getattr(ectx, "allreduce_defer", None):
            # bucketed dp gradient sync (overlap_options["bucket_bytes"]):
            # comm ops solely feeding this optimizer skipped their
            # per-grad collective; reduce them here in size-targeted
            # reverse-order buckets — see ops/comm.py
            from .ops.comm import settle_deferred_allreduce
            input_vals = settle_deferred_allreduce(self.inputs,
                                                   input_vals, ectx)
        # mixed precision: update the fp32 masters, upcasting the (bf16)
        # gradients — ectx.params holds the compute-dtype copies
        masters = ectx.master_params or ectx.params
        grad_vals = {}
        param_vals = {}
        for node, gval in zip(params, input_vals):
            if gval is None:
                continue            # PS-managed parameter: updated server-side
            pval = masters[node]
            with parameter_scope(node):
                if hasattr(gval, "astype") and gval.dtype != pval.dtype:
                    gval = gval.astype(pval.dtype)
                elif hasattr(gval, "values") and \
                        gval.values.dtype != pval.dtype:
                    gval = type(gval)(
                        indices=gval.indices,
                        values=gval.values.astype(pval.dtype),
                        dense_shape=gval.dense_shape)
                if getattr(node, "device_cached", False):
                    # HET push accumulator: raw grads accumulate in HBM
                    # state; the PS runtime drains it to the server every
                    # cache_bound steps (ps/runtime.py drain paths)
                    acc = ectx.state[node]["acc"]
                    if isinstance(gval, IndexedSlices):
                        acc = acc.at[gval.get_flat_indices()].add(
                            gval.get_dense_rows().astype(acc.dtype))
                    else:
                        acc = acc + gval.astype(acc.dtype)
                    ectx.new_state[node] = {"acc": acc}
            grad_vals[node] = gval
            param_vals[node] = pval
        lr = getattr(ectx, "lr", None)
        if lr is None:
            lr = opt.learning_rate
        new_params, new_state = opt.update(
            param_vals, grad_vals, ectx.opt_state or {}, lr, ectx.step, ectx)
        sentinels = getattr(ectx, "health_sentinels", None)
        if sentinels is not None:
            # training health monitor: per-layer grad norm / nonfinite
            # count / update ratio, captured at trace time and returned
            # from the step as one auxiliary pytree (telemetry/health)
            for node, pval in param_vals.items():
                # sentinel the UNSCALED gradient: with loss_scale set
                # the raw grads are scale-times reality, which would
                # poison every grad_norm the health monitor records
                with parameter_scope(node):
                    sentinels.append((node.name, sentinel_stats(
                        pval, opt._unscale(grad_vals[node]),
                        new_params.get(node, pval))))
        ectx.new_params.update(new_params)
        # mixed precision: the compute-dtype copy the NEXT step's matmuls
        # read is one more result of this update (2 bytes a parameter
        # written where 12 are), so no step converts a master again
        for node, value in new_params.items():
            if node in ectx.work:
                with parameter_scope(node):
                    ectx.new_work[node] = value.astype(
                        ectx.work[node].dtype)
        ectx.new_opt_state = {**(ectx.opt_state or {}), **new_state}
        return jnp.zeros((1,), dtype=jnp.float32)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return (1,)

    # ------------------------------------------------------------- hooks
    def backward_hook(self, config):
        """Splice communication ops per gradient according to the node
        strategy (reference optimizer.py:130-148)."""
        from .ops.comm import (allreduceCommunicate_op,
                               parameterServerCommunicate_op)
        self.comm_mode = config.comm_mode
        new_inputs = []
        for grad, param in zip(self.inputs, self.optimizer.params):
            strategy = config.node_strategy.get(param) or config.comm_mode
            if strategy in ("PS", "Hybrid") and \
                    (self.optimizer.loss_scale or 1) != 1:
                # a PS-pushed gradient bypasses update()'s unscale and
                # would apply loss_scale-times too large server-side
                raise ValueError(
                    "loss_scale is worker-local (unscaled inside the "
                    "optimizer update); it cannot be combined with "
                    "PS-pushed gradients")
            if getattr(param, "device_cached", False):
                # HET device-cache path: the worker optimizer applies the
                # local sparse update in-graph; accumulated grads drain to
                # the server from the PS runtime, not via a comm op
                comm = grad
            elif (strategy == "PS" and not param.is_embed
                    and config.device_cache_tables
                    and config.prefetch and not config.bsp
                    and isinstance(self.optimizer, SGDOptimizer)):
                # unified HET treatment for dense PS params under the
                # device-cache ASP mode: locally optimizer-updated every
                # step (never frozen), with raw grads accumulated in HBM
                # state and drained to the server on the cache cadence —
                # one protocol for every parameter, zero per-step host
                # traffic (ps/runtime.py _drain_dense_cached).
                # SGD only: applying the summed raw grads server-side
                # commutes with the worker's per-step updates, so the
                # server value (what save() checkpoints) tracks the
                # worker's weights; stateful optimizers (Adam/Momentum)
                # would diverge and instead take the per-step PS comm op
                param.device_cached = True
                param.stateful = True
                param.state_shapes = \
                    lambda shapes, s=tuple(param.shape): {"acc": s}
                config.ps_dense_cached.append((param, self.optimizer))
                comm = grad
            elif strategy == "PS" or (strategy == "Hybrid"
                                      and param.is_embed):
                comm = parameterServerCommunicate_op(
                    grad, param, self.optimizer, ctx=grad.raw_ctx)
                config.ps_nodes.append(comm)
            elif strategy in ("AllReduce", "Hybrid"):
                comm = allreduceCommunicate_op(grad, ctx=grad.raw_ctx)
            else:
                comm = grad
            comm.role = grad.role       # a gradient's sync is backward's
            new_inputs.append(comm)
        self.inputs = new_inputs

    def forward_hook(self, config):
        if self.ctx is None:
            self.ctx = config.context
