"""Optimizers.

Reference parity: python/hetu/optimizer.py — SGD / Momentum(+Nesterov) /
AdaGrad / Adam / AdamW, each with an l2-regularizer and sparse
(IndexedSlices) variants, plus ``OptimizerOp`` whose ``backward_hook``
splices the per-parameter communication op chosen by the node strategy
(optimizer.py:130-148).

TPU-native: ``update`` is a *pure function* (params, grads, slots, lr) ->
(new params, new slots) executed inside the compiled train step, with
parameter donation making it in-place in HBM. Sparse gradients apply as
scatter-add / row-wise slot updates without densifying the table.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .graph.node import Op
from .lr_scheduler import FixedScheduler
from .ndarray import IndexedSlices
from .ops.variable import PlaceholderOp

__all__ = ["Optimizer", "OptimizerOp", "SGDOptimizer", "MomentumOptimizer",
           "AdaGradOptimizer", "AdamOptimizer", "AdamWOptimizer",
           "sentinel_stats"]


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

    def update_one(self, param, grad, slots, lr, step):
        """(new_param, new_slots) for one parameter."""
        raise NotImplementedError

    def update(self, param_vals, grad_vals, state, lr, step):
        """Pure update over dicts keyed by param node. Empty slot dicts are
        not inserted, so opt_state keeps a stable pytree structure across
        steps (a structure change would force a full re-trace)."""
        new_params, new_state = {}, {}
        for node, param in param_vals.items():
            grad = grad_vals[node]
            slots = state.get(node.id, {})
            p, s = self.update_one(param, self._apply_l2(param, grad),
                                   slots, lr, step)
            new_params[node] = p
            if s or node.id in state:
                new_state[node.id] = s
        return new_params, new_state


class SGDOptimizer(Optimizer):
    name = "SGD"

    def update_one(self, param, grad, slots, lr, step):
        if isinstance(grad, IndexedSlices):
            return (param.at[grad.get_flat_indices()].add(
                -lr * grad.get_dense_rows()), slots)
        return param - lr * grad, slots


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

    def update_one(self, param, grad, slots, lr, step):
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

    def update_one(self, param, grad, slots, lr, step):
        accum = slots["accum"]
        if isinstance(grad, IndexedSlices):
            idx, rows = grad.dedup()
            safe = jnp.clip(idx, 0, param.shape[0] - 1)
            picked = accum[safe] + rows * rows
            accum = accum.at[safe].set(picked)
            upd = lr * rows / (jnp.sqrt(picked) + self.eps)
            valid = (idx < param.shape[0])[:, None]
            param = param.at[safe].add(jnp.where(valid, -upd, 0.0))
            return param, {"accum": accum}
        accum = accum + grad * grad
        return (param - lr * grad / (jnp.sqrt(accum) + self.eps),
                {"accum": accum})


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

    def update_one(self, param, grad, slots, lr, step):
        if isinstance(grad, IndexedSlices):
            idx, rows = grad.dedup()
            safe = jnp.clip(idx, 0, param.shape[0] - 1)
            valid = (idx < param.shape[0])[:, None]
            m_rows = self.beta1 * slots["m"][safe] + (1 - self.beta1) * rows
            v_rows = (self.beta2 * slots["v"][safe]
                      + (1 - self.beta2) * rows * rows)
            m = slots["m"].at[safe].set(
                jnp.where(valid, m_rows, slots["m"][safe]))
            v = slots["v"].at[safe].set(
                jnp.where(valid, v_rows, slots["v"][safe]))
            out = {"m": m, "v": v}
            vhat_rows = v_rows
            if self.amsgrad:
                vhat_rows = jnp.maximum(slots["vmax"][safe], v_rows)
                out["vmax"] = slots["vmax"].at[safe].set(
                    jnp.where(valid, vhat_rows, slots["vmax"][safe]))
            scale = self._step_scale(lr, step)
            upd = scale * m_rows / (jnp.sqrt(vhat_rows) + self.epsilon)
            param = param.at[safe].add(jnp.where(valid, -upd, 0.0))
            return param, out
        m = self.beta1 * slots["m"] + (1 - self.beta1) * grad
        v = self.beta2 * slots["v"] + (1 - self.beta2) * grad * grad
        out = {"m": m, "v": v}
        vhat = v
        if self.amsgrad:
            vhat = jnp.maximum(slots["vmax"], v)
            out["vmax"] = vhat
        scale = self._step_scale(lr, step)
        return param - scale * m / (jnp.sqrt(vhat) + self.epsilon), out


class AdamWOptimizer(AdamOptimizer):
    name = "AdamW"

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7, weight_decay=0.01, l2reg=0,
                 loss_scale=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, l2reg,
                         loss_scale=loss_scale)
        self.weight_decay = weight_decay

    def update_one(self, param, grad, slots, lr, step):
        new_param, out = super().update_one(param, grad, slots, lr, step)
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
            if hasattr(gval, "astype") and gval.dtype != pval.dtype:
                gval = gval.astype(pval.dtype)
            elif hasattr(gval, "values") and \
                    gval.values.dtype != pval.dtype:
                gval = type(gval)(indices=gval.indices,
                                  values=gval.values.astype(pval.dtype),
                                  dense_shape=gval.dense_shape)
            grad_vals[node] = gval
            param_vals[node] = pval
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
        lr = getattr(ectx, "lr", None)
        if lr is None:
            lr = opt.learning_rate
        new_params, new_state = opt.update(
            param_vals, grad_vals, ectx.opt_state or {}, lr, ectx.step)
        sentinels = getattr(ectx, "health_sentinels", None)
        if sentinels is not None:
            # training health monitor: per-layer grad norm / nonfinite
            # count / update ratio, captured at trace time and returned
            # from the step as one auxiliary pytree (telemetry/health)
            for node, pval in param_vals.items():
                # sentinel the UNSCALED gradient: with loss_scale set
                # the raw grads are scale-times reality, which would
                # poison every grad_norm the health monitor records
                sentinels.append((node.name, sentinel_stats(
                    pval, opt._unscale(grad_vals[node]),
                    new_params.get(node, pval))))
        ectx.new_params.update(new_params)
        # mixed precision: the compute-dtype copy the NEXT step's matmuls
        # read is one more result of this update (2 bytes a parameter
        # written where 12 are), so no step converts a master again
        for node, value in new_params.items():
            if node in ectx.work:
                ectx.new_work[node] = value.astype(ectx.work[node].dtype)
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
            new_inputs.append(comm)
        self.inputs = new_inputs

    def forward_hook(self, config):
        if self.ctx is None:
            self.ctx = config.context
