"""Normalization ops.

Reference parity: gpu_ops/{BatchNorm,LayerNorm,InstanceNorm2d}.py. The
reference packs (dx, dscale, dbias) into one gradient kernel and unpacks
with *_gradient_of_data/scale/bias ops; we keep that graph structure — the
packed gradient op returns a tuple value (graph values are pytrees under
jit) and the unpack ops index it.

Batch-norm running statistics are functional op state: ``compute`` reads
``ectx.state[self]`` and writes ``ectx.put_state`` — the executor threads
them between steps like parameters (no in-place buffers).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..graph.node import Op

__all__ = [
    "batch_normalization_op", "batch_normalization_gradient_op",
    "batch_normalization_gradient_of_data_op",
    "batch_normalization_gradient_of_scale_op",
    "batch_normalization_gradient_of_bias_op",
    "layer_normalization_op", "layer_normalization_gradient_op",
    "layer_normalization_gradient_of_data_op",
    "layer_normalization_gradient_of_scale_op",
    "layer_normalization_gradient_of_bias_op",
    "instance_normalization2d_op", "instance_normalization2d_gradient_op",
    "rms_normalization_op", "rms_normalization_gradient_op",
]


def _bcast_c(v):
    """Reshape a (C,)/(1,C,1,1) param to broadcast over NCHW."""
    return v.reshape(1, -1, 1, 1)


def _norm_range(n, scale_range, bias_range):
    """Interval semantics for the HT8xx numerics verifier: a value
    standardized over ``n`` samples satisfies |x - mean| / std <=
    sqrt(n - 1), so the affine output is bounded by
    sqrt(n) * |scale| + |bias| regardless of the input's range (the
    eps > 0 contract keeps the rsqrt finite; eps <= 0 is HT804)."""
    import math
    if scale_range is None:
        return None
    k = math.sqrt(float(max(n, 1)))
    sm = max(abs(scale_range[0]), abs(scale_range[1]))
    bm = 0.0 if bias_range is None else max(abs(bias_range[0]),
                                            abs(bias_range[1]))
    m = k * sm + bm
    return (-m, m)


class BatchNormalizationOp(Op):
    def __init__(self, node_in, bn_scale, bn_bias, momentum=0.99, eps=0.01,
                 ctx=None):
        super().__init__(BatchNormalizationOp,
                         [node_in, bn_scale, bn_bias], ctx)
        self.momentum = momentum
        self.eps = eps
        self.stateful = True

    def state_shapes(self, input_shapes):
        c = input_shapes[0][1]
        return {"running_mean": (c,), "running_var": (c,)}

    def compute(self, input_vals, ectx):
        x, scale, bias = input_vals
        axes = (0, 2, 3)
        state = ectx.get_state(self)
        if ectx.training:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            if state is not None:
                m = self.momentum
                ectx.put_state(self, {
                    "running_mean": m * state["running_mean"] + (1 - m) * mean,
                    "running_var": m * state["running_var"] + (1 - m) * var,
                })
        else:
            assert state is not None, "inference BN needs running stats"
            mean, var = state["running_mean"], state["running_var"]
        inv = jnp.reciprocal(jnp.sqrt(var + self.eps))
        xhat = (x - _bcast_c(mean)) * _bcast_c(inv)
        return xhat * _bcast_c(scale) + _bcast_c(bias)

    def gradient(self, output_grad):
        packed = batch_normalization_gradient_op(
            output_grad, self.inputs[0], self.inputs[1], self, self.eps,
            ctx=self.raw_ctx)
        return [
            batch_normalization_gradient_of_data_op(packed, self.inputs[0],
                                                    ctx=self.raw_ctx),
            batch_normalization_gradient_of_scale_op(packed, self.inputs[1],
                                                     ctx=self.raw_ctx),
            batch_normalization_gradient_of_bias_op(packed, self.inputs[2],
                                                    ctx=self.raw_ctx),
        ]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        n = 1
        if input_shapes and input_shapes[0] and len(input_shapes[0]) == 4:
            s = input_shapes[0]
            n = s[0] * s[2] * s[3]
        return _norm_range(n, input_ranges[1], input_ranges[2])


class BatchNormalizationGradientOp(Op):
    """Packed (dx, dscale, dbias) — closed-form BN backward over batch
    statistics (reference BatchNorm.py:96-159 / src/ops/BatchNorm.cu)."""

    def __init__(self, out_gradient, in_node, bn_scale, forward_node, eps,
                 ctx=None):
        super().__init__(BatchNormalizationGradientOp,
                         [out_gradient, in_node, bn_scale], ctx)
        self.forward_node = forward_node
        self.eps = eps

    def compute(self, input_vals, ectx):
        dy, x, scale = input_vals
        scale = scale.reshape(-1)       # accept (C,) or (1, C, 1, 1) params
        axes = (0, 2, 3)
        n = x.shape[0] * x.shape[2] * x.shape[3]
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        inv = jnp.reciprocal(jnp.sqrt(var + self.eps))
        xhat = (x - _bcast_c(mean)) * _bcast_c(inv)
        dbias = jnp.sum(dy, axis=axes)
        dscale = jnp.sum(dy * xhat, axis=axes)
        dx = (_bcast_c(scale * inv) / n) * (
            n * dy - _bcast_c(dbias) - xhat * _bcast_c(dscale))
        return (dx, dscale, dbias)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        # packed value; consumers index it
        return input_shapes[0]


class _PackedIndexOp(Op):
    idx = None

    def __init__(self, op_type, packed, like_node, ctx=None):
        super().__init__(op_type, [packed, like_node], ctx)

    def compute(self, input_vals, ectx):
        return input_vals[0][self.idx]

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[1]


class PackedPartOp(_PackedIndexOp):
    """Entry ``idx`` of any packed gradient, shaped like ``like``: what
    an op without the reference's named unpack ops uses (RMS norm, the
    router, the held experts)."""

    def __init__(self, packed, like, idx, ctx=None):
        super().__init__(PackedPartOp, packed, like, ctx=ctx)
        self.idx = idx


class BatchNormalizationGradientOfDataOp(_PackedIndexOp):
    idx = 0

    def __init__(self, bn_gradient, in_arr, ctx=None):
        super().__init__(BatchNormalizationGradientOfDataOp, bn_gradient,
                         in_arr, ctx=ctx)


class BatchNormalizationGradientOfScaleOp(_PackedIndexOp):
    idx = 1

    def __init__(self, bn_gradient, in_scale, ctx=None):
        super().__init__(BatchNormalizationGradientOfScaleOp, bn_gradient,
                         in_scale, ctx=ctx)

    def compute(self, input_vals, ectx):
        out = input_vals[0][self.idx]
        return out.reshape(input_vals[1].shape)


class BatchNormalizationGradientOfBiasOp(_PackedIndexOp):
    idx = 2

    def __init__(self, bn_gradient, in_bias, ctx=None):
        super().__init__(BatchNormalizationGradientOfBiasOp, bn_gradient,
                         in_bias, ctx=ctx)

    def compute(self, input_vals, ectx):
        out = input_vals[0][self.idx]
        return out.reshape(input_vals[1].shape)


class LayerNormalizationOp(Op):
    def __init__(self, node_in, ln_scale, ln_bias, eps=0.01, ctx=None):
        super().__init__(LayerNormalizationOp,
                         [node_in, ln_scale, ln_bias], ctx)
        self.eps = eps

    def compute(self, input_vals, ectx):
        x, scale, bias = input_vals
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        xhat = (x - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))
        return xhat * scale + bias

    def gradient(self, output_grad):
        packed = layer_normalization_gradient_op(
            output_grad, self.inputs[0], self.inputs[1], self, self.eps,
            ctx=self.raw_ctx)
        return [
            layer_normalization_gradient_of_data_op(packed, self.inputs[0],
                                                    ctx=self.raw_ctx),
            layer_normalization_gradient_of_scale_op(packed, self.inputs[1],
                                                     ctx=self.raw_ctx),
            layer_normalization_gradient_of_bias_op(packed, self.inputs[2],
                                                    ctx=self.raw_ctx),
        ]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        n = 1
        if input_shapes and input_shapes[0]:
            n = input_shapes[0][-1]
        return _norm_range(n, input_ranges[1], input_ranges[2])


def layer_norm_backward_reference(dy, x, scale, eps):
    """``(dx, dscale, dbias)`` of LayerNorm over the last axis, composed
    from ``jax.numpy`` reductions: what runs off the TPU, in a step a
    mesh partitions and on a last axis the kernel does not tile, and
    what the kernel (``pallas_norm.hetu_layer_norm_bwd``) is tested
    against."""
    d = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    inv = jnp.reciprocal(jnp.sqrt(var + eps))
    xhat = (x - mean) * inv
    reduce_axes = tuple(range(x.ndim - 1))
    dscale = jnp.sum(dy * xhat, axis=reduce_axes)
    dbias = jnp.sum(dy, axis=reduce_axes)
    dxhat = dy * scale
    dx = inv / d * (
        d * dxhat
        - jnp.sum(dxhat, axis=-1, keepdims=True)
        - xhat * jnp.sum(dxhat * xhat, axis=-1, keepdims=True))
    return (dx, dscale, dbias)


class LayerNormalizationGradientOp(Op):
    def __init__(self, out_gradient, in_node, ln_scale, forward_node, eps,
                 ctx=None):
        super().__init__(LayerNormalizationGradientOp,
                         [out_gradient, in_node, ln_scale], ctx)
        self.forward_node = forward_node
        self.eps = eps

    def compute(self, input_vals, ectx):
        dy, x, scale = input_vals
        # one pass over the rows on a TPU (the kernel's rule is the
        # flash kernels': the platform alone, and a last axis it tiles),
        # in a step that no mesh partitions
        from . import pallas_norm
        from .attention import unpartitioned_tpu_step
        if unpartitioned_tpu_step(ectx) \
                and pallas_norm.supported(x.shape[-1], x.dtype.itemsize):
            return pallas_norm.hetu_layer_norm_bwd(
                dy, x, scale, eps=self.eps,
                interpret=pallas_norm.INTERPRET)
        return layer_norm_backward_reference(dy, x, scale, self.eps)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class LayerNormalizationGradientOfDataOp(_PackedIndexOp):
    idx = 0

    def __init__(self, ln_gradient, in_arr, ctx=None):
        super().__init__(LayerNormalizationGradientOfDataOp, ln_gradient,
                         in_arr, ctx=ctx)


class LayerNormalizationGradientOfScaleOp(_PackedIndexOp):
    idx = 1

    def __init__(self, ln_gradient, in_scale, ctx=None):
        super().__init__(LayerNormalizationGradientOfScaleOp, ln_gradient,
                         in_scale, ctx=ctx)

    def compute(self, input_vals, ectx):
        return input_vals[0][self.idx].reshape(input_vals[1].shape)


class LayerNormalizationGradientOfBiasOp(_PackedIndexOp):
    idx = 2

    def __init__(self, ln_gradient, in_bias, ctx=None):
        super().__init__(LayerNormalizationGradientOfBiasOp, ln_gradient,
                         in_bias, ctx=ctx)

    def compute(self, input_vals, ectx):
        return input_vals[0][self.idx].reshape(input_vals[1].shape)


def rms_norm_reference(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, the
    statistics and the product in float32, the result in ``x``'s
    dtype."""
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)


class RMSNormalizationOp(Op):
    """RMS norm over the last axis: no mean is taken off and there is
    no bias. Statistics in float32 whatever the stream's dtype (no
    reference equivalent: the reference's zoo stops at LayerNorm)."""

    def __init__(self, node_in, scale, eps=1e-6, ctx=None):
        super().__init__(RMSNormalizationOp, [node_in, scale], ctx)
        self.eps = eps

    def compute(self, input_vals, ectx):
        x, scale = input_vals
        return rms_norm_reference(x, scale, self.eps)

    def gradient(self, output_grad):
        packed = rms_normalization_gradient_op(
            output_grad, self.inputs[0], self.inputs[1], self.eps,
            ctx=self.raw_ctx)
        return [PackedPartOp(packed, node, i, ctx=self.raw_ctx)
                for i, node in enumerate(self.inputs)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        n = 1
        if input_shapes and input_shapes[0]:
            n = input_shapes[0][-1]
        return _norm_range(n, input_ranges[1], None)


class RMSNormalizationGradientOp(Op):
    """Packed ``(dx, dscale)`` of :class:`RMSNormalizationOp`, closed
    form in float32: with ``xhat = x * inv`` and ``g = dy * scale``,
    ``dx = inv * (g - xhat * mean(g * xhat))`` and ``dscale`` the sum of
    ``dy * xhat`` over the rows."""

    def __init__(self, out_gradient, in_node, scale, eps, ctx=None):
        super().__init__(RMSNormalizationGradientOp,
                         [out_gradient, in_node, scale], ctx)
        self.eps = eps

    def compute(self, input_vals, ectx):
        dy, x, scale = input_vals
        xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
        inv = jnp.reciprocal(jnp.sqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps))
        xhat = xf * inv
        g = dyf * scale.astype(jnp.float32)
        dx = inv * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
        dscale = jnp.sum(dyf * xhat, axis=tuple(range(x.ndim - 1)))
        return (dx.astype(x.dtype), dscale)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[1]


class InstanceNormalization2dOp(Op):
    def __init__(self, node_in, eps=0.01, ctx=None):
        super().__init__(InstanceNormalization2dOp, [node_in], ctx)
        self.eps = eps

    def compute(self, input_vals, ectx):
        x = input_vals[0]
        mean = jnp.mean(x, axis=(2, 3), keepdims=True)
        var = jnp.var(x, axis=(2, 3), keepdims=True)
        return (x - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))

    def gradient(self, output_grad):
        return [instance_normalization2d_gradient_op(
            output_grad, self.inputs[0], self, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        n = 1
        if input_shapes and input_shapes[0] and len(input_shapes[0]) == 4:
            n = input_shapes[0][2] * input_shapes[0][3]
        return _norm_range(n, (1.0, 1.0), None)


class InstanceNormalization2dGradientOp(Op):
    def __init__(self, out_gradient, in_node, forward_node, ctx=None):
        super().__init__(InstanceNormalization2dGradientOp,
                         [out_gradient, in_node], ctx)
        self.forward_node = forward_node

    def compute(self, input_vals, ectx):
        dy, x = input_vals
        eps = self.forward_node.eps
        n = x.shape[2] * x.shape[3]
        mean = jnp.mean(x, axis=(2, 3), keepdims=True)
        var = jnp.var(x, axis=(2, 3), keepdims=True)
        inv = jnp.reciprocal(jnp.sqrt(var + eps))
        xhat = (x - mean) * inv
        dsum = jnp.sum(dy, axis=(2, 3), keepdims=True)
        ddot = jnp.sum(dy * xhat, axis=(2, 3), keepdims=True)
        return inv / n * (n * dy - dsum - xhat * ddot)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[1]


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def batch_normalization_op(node_in, bn_scale, bn_bias, momentum=0.99,
                           eps=0.01, ctx=None):
    return BatchNormalizationOp(node_in, bn_scale, bn_bias,
                                momentum=momentum, eps=eps, ctx=ctx)


def batch_normalization_gradient_op(out_gradient, in_node, bn_scale,
                                    forward_node, eps, ctx=None):
    return BatchNormalizationGradientOp(out_gradient, in_node, bn_scale,
                                        forward_node, eps, ctx=ctx)


def batch_normalization_gradient_of_data_op(bn_gradient, in_arr, ctx=None):
    return BatchNormalizationGradientOfDataOp(bn_gradient, in_arr, ctx=ctx)


def batch_normalization_gradient_of_scale_op(bn_gradient, in_scale,
                                             ctx=None):
    return BatchNormalizationGradientOfScaleOp(bn_gradient, in_scale,
                                               ctx=ctx)


def batch_normalization_gradient_of_bias_op(bn_gradient, in_bias, ctx=None):
    return BatchNormalizationGradientOfBiasOp(bn_gradient, in_bias, ctx=ctx)


def layer_normalization_op(node_in, ln_scale, ln_bias, eps=0.01, ctx=None):
    return LayerNormalizationOp(node_in, ln_scale, ln_bias, eps=eps, ctx=ctx)


def layer_normalization_gradient_op(out_gradient, in_node, ln_scale,
                                    forward_node, eps, ctx=None):
    return LayerNormalizationGradientOp(out_gradient, in_node, ln_scale,
                                        forward_node, eps, ctx=ctx)


def layer_normalization_gradient_of_data_op(ln_gradient, in_arr, ctx=None):
    return LayerNormalizationGradientOfDataOp(ln_gradient, in_arr, ctx=ctx)


def layer_normalization_gradient_of_scale_op(ln_gradient, in_scale,
                                             ctx=None):
    return LayerNormalizationGradientOfScaleOp(ln_gradient, in_scale,
                                               ctx=ctx)


def layer_normalization_gradient_of_bias_op(ln_gradient, in_bias, ctx=None):
    return LayerNormalizationGradientOfBiasOp(ln_gradient, in_bias, ctx=ctx)


def rms_normalization_op(node_in, scale, eps=1e-6, ctx=None):
    return RMSNormalizationOp(node_in, scale, eps=eps, ctx=ctx)


def rms_normalization_gradient_op(out_gradient, in_node, scale, eps,
                                  ctx=None):
    return RMSNormalizationGradientOp(out_gradient, in_node, scale, eps,
                                      ctx=ctx)


def instance_normalization2d_op(node_in, eps=0.01, ctx=None):
    return InstanceNormalization2dOp(node_in, eps=eps, ctx=ctx)


def instance_normalization2d_gradient_op(out_gradient, in_node, forward_node,
                                         ctx=None):
    return InstanceNormalization2dGradientOp(out_gradient, in_node,
                                             forward_node, ctx=ctx)
