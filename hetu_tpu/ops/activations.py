"""Activation and dropout ops.

Reference parity: gpu_ops/{Relu,LeakyRelu,Sigmoid,Tanh,Softmax,Dropout,
Dropout2d}.py. Dropout's mask is derived from a deterministic per-op PRNG
key (fold_in of the op id), so the forward op and its gradient op
regenerate the identical mask inside one traced step — no side-channel
mask buffer like the reference's saved mask array (Dropout.py:12-63).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.node import Op
from .basic import mul_op, _iv_exp as _safe_exp

# saturating activations (sigmoid/tanh/erf) ROUND to their asymptote in
# finite precision long before float64 math reaches it: clamp a bound
# within this slack of the asymptote onto it, else the static interval
# wrongly excludes the saturated value (masking HT804's log/div-of-zero
# detection and tripping the HT810 soundness gate on correct runs).
# 5e-4 covers fp16's eps/2 rounding, the widest of the supported dtypes.
_SATURATE_SLACK = 5e-4


def _saturate(lo, hi, floor, ceil):
    if lo - floor < _SATURATE_SLACK:
        lo = floor
    if ceil - hi < _SATURATE_SLACK:
        hi = ceil
    return (lo, hi)

__all__ = [
    "relu_op", "relu_gradient_op", "leaky_relu_op", "leaky_relu_gradient_op",
    "sigmoid_op", "tanh_op", "gelu_op", "swiglu_op", "swiglu_gradient_op",
    "sign_op", "softmax_func",
    "softmax_op", "softmax_gradient_op", "dropout_op", "dropout_gradient_op",
    "dropout2d_op", "dropout2d_gradient_op",
]


class ReluOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(ReluOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.maximum(input_vals[0], 0)

    def gradient(self, output_grad):
        return [relu_gradient_op(self.inputs[0], output_grad,
                                 ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        # interval semantics for the HT8xx numerics verifier (see
        # ops/basic.py): (lo, hi) bound per input, None = unknown
        a = input_ranges[0]
        return None if a is None else (max(a[0], 0.0), max(a[1], 0.0))


class ReluGradientOp(Op):
    """grad * (x > 0) — same input contract as the reference
    (node_A = forward input, node_B = adjoint)."""

    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(ReluGradientOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        x, grad = input_vals
        return grad * (x > 0).astype(grad.dtype)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        g = input_ranges[1]
        return None if g is None else (min(g[0], 0.0), max(g[1], 0.0))


class LeakyReluOp(Op):
    def __init__(self, node_A, alpha, ctx=None):
        super().__init__(LeakyReluOp, [node_A], ctx)
        self.alpha = alpha

    def compute(self, input_vals, ectx):
        x = input_vals[0]
        return jnp.where(x > 0, x, self.alpha * x)

    def gradient(self, output_grad):
        return [leaky_relu_gradient_op(self.inputs[0], output_grad,
                                       self.alpha, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None:
            return None
        pts = (max(a[0], 0.0), max(a[1], 0.0),
               self.alpha * min(a[0], 0.0), self.alpha * min(a[1], 0.0))
        return (min(pts), max(pts))


class LeakyReluGradientOp(Op):
    def __init__(self, node_A, node_B, alpha, ctx=None):
        super().__init__(LeakyReluGradientOp, [node_A, node_B], ctx)
        self.alpha = alpha

    def compute(self, input_vals, ectx):
        x, grad = input_vals
        return jnp.where(x > 0, grad, self.alpha * grad)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class SigmoidOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(SigmoidOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jax.nn.sigmoid(input_vals[0])

    def gradient(self, output_grad):
        # y' = y * (1 - y); express on the graph so autodiff stays symbolic
        from .basic import addbyconst_op, opposite_op
        one_minus = addbyconst_op(opposite_op(self), 1.0)
        return [mul_op(output_grad, mul_op(self, one_minus),
                       ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None:
            # sigmoid underflows to exactly 0.0/1.0 in finite precision:
            # the closed interval is the honest bound (log(sigmoid(x))
            # with very negative x genuinely NaNs — HT804 catches it)
            return (0.0, 1.0)
        return _saturate(1.0 / (1.0 + _safe_exp(-a[0])),
                         1.0 / (1.0 + _safe_exp(-a[1])), 0.0, 1.0)


class TanhOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(TanhOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.tanh(input_vals[0])

    def gradient(self, output_grad):
        from .basic import addbyconst_op, opposite_op
        one_minus_sq = addbyconst_op(opposite_op(mul_op(self, self)), 1.0)
        return [mul_op(output_grad, one_minus_sq, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        import math
        a = input_ranges[0]
        if a is None:
            return (-1.0, 1.0)
        return _saturate(math.tanh(a[0]), math.tanh(a[1]), -1.0, 1.0)


class GeluOp(Op):
    """tanh-approximation GELU (transformer staple; the reference composes
    it from primitives in examples/nlp/bert/hetu_bert.py)."""

    def __init__(self, node_A, ctx=None):
        super().__init__(GeluOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jax.nn.gelu(input_vals[0], approximate=True)

    def gradient(self, output_grad):
        return [gelu_gradient_op(self.inputs[0], output_grad,
                                 ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        # gelu(x) in [-0.171, max(x, 0)]: the tanh-approximation (what
        # compute runs) dips to -0.17004 at x ~ -0.75, so the bound
        # must sit below it; bounded above by relu(x)
        a = input_ranges[0]
        if a is None:
            return None
        lo = -0.171 if a[0] < 0.0 else 0.0
        return (lo, max(a[1], 0.0))


class SwiGLUOp(Op):
    """``silu(gate) * up`` of a ``[..., 2 * width]`` product whose two
    halves lie side by side (gate first), ``[..., width]``: the gated
    unit between a dense feed-forward's two matmuls,
    ``down(silu(gate x) * up x)``. Float32 between the read and the
    write. No reference equivalent (its zoo stops at GELU)."""

    def __init__(self, node_A, ctx=None):
        super().__init__(SwiGLUOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        h = input_vals[0]
        width = h.shape[-1] // 2
        gate = h[..., :width].astype(jnp.float32)
        return (jax.nn.silu(gate) * h[..., width:].astype(jnp.float32)
                ).astype(h.dtype)

    def gradient(self, output_grad):
        return [swiglu_gradient_op(self.inputs[0], output_grad,
                                   ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        shape = tuple(input_shapes[0])
        assert shape[-1] % 2 == 0, f"swiglu halves {shape}"
        return shape[:-1] + (shape[-1] // 2,)


class SwiGLUGradientOp(Op):
    """``[dy * up * silu'(gate), dy * silu(gate)]``, side by side as the
    input lies: the matmul before it gets one gradient."""

    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(SwiGLUGradientOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        h, dy = input_vals
        width = h.shape[-1] // 2
        gate = h[..., :width].astype(jnp.float32)
        up = h[..., width:].astype(jnp.float32)
        dy = dy.astype(jnp.float32)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        slope = sig * (1.0 + gate * (1.0 - sig))
        return jnp.concatenate([dy * up * slope, dy * act],
                               axis=-1).astype(h.dtype)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class GeluGradientOp(Op):
    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(GeluGradientOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        x, grad = input_vals
        _, vjp = jax.vjp(lambda v: jax.nn.gelu(v, approximate=True), x)
        return vjp(grad)[0]

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class SignOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(SignOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.sign(input_vals[0])

    def gradient(self, output_grad):
        return [None]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return (-1.0, 1.0)


class SoftmaxOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(SoftmaxOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jax.nn.softmax(input_vals[0], axis=-1)

    def gradient(self, output_grad):
        return [softmax_gradient_op(self, output_grad, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return (0.0, 1.0)


class SoftmaxGradientOp(Op):
    """dx = y * (dy - sum(dy * y, -1, keepdims))"""

    def __init__(self, forward_node, grad_node, ctx=None):
        super().__init__(SoftmaxGradientOp, [forward_node, grad_node], ctx)

    def compute(self, input_vals, ectx):
        y, dy = input_vals
        return y * (dy - jnp.sum(dy * y, axis=-1, keepdims=True))

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        # |y (dy - sum(dy y))| <= |dy| + max|dy| <= 2 max|dy| since y is
        # a probability row (sum 1, entries in [0, 1])
        g = input_ranges[1]
        if g is None:
            return None
        m = 2.0 * max(abs(g[0]), abs(g[1]))
        return (-m, m)


def _dropout_range(input_ranges, keep_prob):
    """Mask elements are 0 or 1/keep_prob: hull of 0 and x/keep_prob."""
    a = input_ranges[0]
    if a is None or keep_prob <= 0:
        return None
    return (min(a[0] / keep_prob, 0.0), max(a[1] / keep_prob, 0.0))


def _dropout_mask(ectx, op, keep_prob, shape, dtype, per_channel=False):
    """0 or ``1/keep_prob`` per element (per ``(N, C)`` plane for
    dropout2d), a function of ``ectx.rng_for(op)`` alone: the gradient
    op passes the forward node and gets the forward's mask."""
    from . import pallas_dropout
    from .attention import unpartitioned_tpu_step
    rng = ectx.rng_for(op)
    if per_channel:
        # dropout2d: one decision per (N, C) plane
        keep = jax.random.bernoulli(
            rng, keep_prob, shape[:2] + (1,) * (len(shape) - 2))
    elif unpartitioned_tpu_step(ectx) and pallas_dropout.supported(shape):
        # the bits come from the core's generator, in a kernel that
        # takes the key's two words (the same decisions for the same
        # key on a TPU; not the ones threefry draws from it)
        keep = pallas_dropout.hetu_dropout_mask(
            pallas_dropout.seed_words(rng), tuple(shape), float(keep_prob),
            interpret=pallas_dropout.INTERPRET)
    else:
        keep = jax.random.bernoulli(rng, keep_prob, shape)
    return keep.astype(dtype) / keep_prob


class DropoutOp(Op):
    def __init__(self, node_in, keep_prob, ctx=None):
        super().__init__(DropoutOp, [node_in], ctx)
        self.keep_prob = keep_prob

    def compute(self, input_vals, ectx):
        x = input_vals[0]
        if not ectx.training:
            return x
        return x * _dropout_mask(ectx, self, self.keep_prob, x.shape, x.dtype)

    def gradient(self, output_grad):
        return [dropout_gradient_op(output_grad, self.keep_prob, self,
                                    ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return _dropout_range(input_ranges, self.keep_prob)


class DropoutGradientOp(Op):
    def __init__(self, node_in, keep_prob, forward_node, ctx=None):
        super().__init__(DropoutGradientOp, [node_in], ctx)
        self.keep_prob = keep_prob
        self.forward_node = forward_node

    def compute(self, input_vals, ectx):
        grad = input_vals[0]
        if not ectx.training:
            return grad
        # identical key as the forward op -> identical mask
        return grad * _dropout_mask(ectx, self.forward_node, self.keep_prob,
                                    grad.shape, grad.dtype)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return _dropout_range(input_ranges, self.keep_prob)


class Dropout2dOp(Op):
    def __init__(self, node_in, keep_prob, ctx=None):
        super().__init__(Dropout2dOp, [node_in], ctx)
        self.keep_prob = keep_prob

    def compute(self, input_vals, ectx):
        x = input_vals[0]
        if not ectx.training:
            return x
        return x * _dropout_mask(ectx, self, self.keep_prob, x.shape,
                                 x.dtype, per_channel=True)

    def gradient(self, output_grad):
        return [dropout2d_gradient_op(output_grad, self.keep_prob, self,
                                      ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return _dropout_range(input_ranges, self.keep_prob)


class Dropout2dGradientOp(Op):
    def __init__(self, node_in, keep_prob, forward_node, ctx=None):
        super().__init__(Dropout2dGradientOp, [node_in], ctx)
        self.keep_prob = keep_prob
        self.forward_node = forward_node

    def compute(self, input_vals, ectx):
        grad = input_vals[0]
        if not ectx.training:
            return grad
        return grad * _dropout_mask(ectx, self.forward_node, self.keep_prob,
                                    grad.shape, grad.dtype, per_channel=True)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return _dropout_range(input_ranges, self.keep_prob)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def relu_op(node, ctx=None):
    return ReluOp(node, ctx=ctx)


def relu_gradient_op(node_A, node_B, ctx=None):
    return ReluGradientOp(node_A, node_B, ctx=ctx)


def leaky_relu_op(node, alpha=0.01, ctx=None):
    return LeakyReluOp(node, alpha, ctx=ctx)


def leaky_relu_gradient_op(node_A, node_B, alpha=0.01, ctx=None):
    return LeakyReluGradientOp(node_A, node_B, alpha, ctx=ctx)


def sigmoid_op(node, ctx=None):
    return SigmoidOp(node, ctx=ctx)


def tanh_op(node, ctx=None):
    return TanhOp(node, ctx=ctx)


def swiglu_op(node, ctx=None):
    """``silu(gate) * up`` of ``node [..., 2 * width]`` (gate | up)."""
    return SwiGLUOp(node, ctx=ctx)


def swiglu_gradient_op(node_A, node_B, ctx=None):
    return SwiGLUGradientOp(node_A, node_B, ctx=ctx)


def gelu_op(node, ctx=None):
    return GeluOp(node, ctx=ctx)


def gelu_gradient_op(node_A, node_B, ctx=None):
    return GeluGradientOp(node_A, node_B, ctx=ctx)


def sign_op(node, ctx=None):
    return SignOp(node, ctx=ctx)


def softmax_func(node):
    return softmax_op(node)


def softmax_op(node, ctx=None):
    return SoftmaxOp(node, ctx=ctx)


def softmax_gradient_op(forward_node, grad_node, ctx=None):
    return SoftmaxGradientOp(forward_node, grad_node, ctx=ctx)


def dropout_op(node_in, keep_prob, ctx=None):
    return DropoutOp(node_in, keep_prob, ctx=ctx)


def dropout_gradient_op(node_in, keep_prob, forward_node, ctx=None):
    return DropoutGradientOp(node_in, keep_prob, forward_node, ctx=ctx)


def dropout2d_op(node_in, keep_prob, ctx=None):
    return Dropout2dOp(node_in, keep_prob, ctx=ctx)


def dropout2d_gradient_op(node_in, keep_prob, forward_node, ctx=None):
    return Dropout2dGradientOp(node_in, keep_prob, forward_node, ctx=ctx)
