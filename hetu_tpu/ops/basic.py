"""Elementwise arithmetic ops.

Reference parity: gpu_ops/{AddElewise,AddConst,MultiplyElewise,MultiplyConst,
Division,Opposite,Sqrt,Where,OneHot,MatrixDot}.py. Each lowers to one jnp
call; XLA fuses chains of these into neighboring matmuls/convs, which is
exactly the fusion the reference's hand-written elementwise CUDA kernels
(src/ops/*.cu) could not get.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..graph.node import Op

__all__ = [
    "add_op", "addbyconst_op", "mul_op", "mul_byconst_op", "div_op",
    "div_const_op", "div_handle_zero_op", "opposite_op", "sqrt_op",
    "rsqrt_op", "where_op", "one_hot_op", "matrix_dot_op", "power_op",
    "exp_op", "log_op", "abs_op", "erf_op", "cast_op", "clip_op",
    "clip_mask_op",
]


def _unbroadcast(grad_node, target_node):
    """Sum a broadcasted adjoint back down to the target input's shape.
    The reference sidesteps this by only broadcasting via explicit
    broadcastto ops; we keep that contract (elementwise ops require equal
    shapes) so the adjoint passes through unchanged."""
    return grad_node


# ---------------------------------------------------------------------------
# interval semantics (the HT8xx numerics verifier's transfer protocol)
# ---------------------------------------------------------------------------
# Ops may define ``infer_range(input_ranges, input_shapes=None)``
# returning a (lo, hi) float pair bounding every element of the output
# given per-input (lo, hi) bounds (None = unknown), mirroring the
# ``infer_shape`` protocol. analysis/numerics.py walks the topo order
# through it; ops without the method fall back to the central
# shape-aware table there (matmul/conv/reductions need shapes).

def _iv_sorted(lo, hi):
    return (min(lo, hi), max(lo, hi))


def _mul_ep(x, y):
    """Endpoint product with the standard interval-arithmetic rule
    0 * inf := 0 — a naive product NaNs there, and a (nan, nan)
    interval silently disarms every downstream HT801/HT804 check
    (half-bounded intervals from one-sided clips make this reachable
    in ordinary graphs)."""
    if x == 0.0 or y == 0.0:
        return 0.0
    return x * y


def _iv_mul(a, b):
    if any(v != v for v in (*a, *b)):   # NaN endpoint: no claim
        return None
    ps = (_mul_ep(a[0], b[0]), _mul_ep(a[0], b[1]),
          _mul_ep(a[1], b[0]), _mul_ep(a[1], b[1]))
    return (min(ps), max(ps))


def _iv_exp(x):
    if x >= 709.0:                  # float64 exp overflow knee
        return float("inf")
    try:
        return math.exp(x)
    except OverflowError:
        return float("inf")


class AddOp(Op):
    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(AddOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        from ..ndarray import IndexedSlices
        a, b = input_vals
        # partial adjoints of an embedding table arrive as IndexedSlices
        # (e.g. tied embeddings looked up twice); keep them sparse
        if isinstance(a, IndexedSlices) and isinstance(b, IndexedSlices):
            import jax.numpy as _jnp
            return IndexedSlices(
                _jnp.concatenate([a.get_flat_indices(),
                                  b.get_flat_indices()]),
                _jnp.concatenate([a.get_dense_rows(), b.get_dense_rows()]),
                a.dense_shape)
        # a table that is also a dense product's operand (a tied head):
        # the sum is ONE dense array, applied once by the dense update.
        # Where the dense adjoint is wider than the rows (float32 out of
        # a float32 head over a bfloat16 stream) the rows are added INTO
        # it, so rows of one id add up in float32
        for rows, dense in ((a, b), (b, a)):
            if isinstance(rows, IndexedSlices) \
                    and dense.dtype != rows.values.dtype \
                    and jnp.promote_types(dense.dtype, rows.values.dtype) \
                    == dense.dtype:
                return dense.at[rows.get_flat_indices()].add(
                    rows.get_dense_rows().astype(dense.dtype))
        if isinstance(a, IndexedSlices):
            return a.to_dense() + b
        if isinstance(b, IndexedSlices):
            return a + b.to_dense()
        return a + b

    def gradient(self, output_grad):
        return [_unbroadcast(output_grad, self.inputs[0]),
                _unbroadcast(output_grad, self.inputs[1])]

    def infer_shape(self, input_shapes):
        a, b = input_shapes
        if a == (1,):
            return b
        if b == (1,):
            return a
        assert tuple(a) == tuple(b), f"add shape mismatch {a} vs {b}"
        return a

    def infer_range(self, input_ranges, input_shapes=None):
        a, b = input_ranges
        if a is None or b is None:
            return None
        return (a[0] + b[0], a[1] + b[1])


class AddByConstOp(Op):
    def __init__(self, node_A, const_val, ctx=None):
        super().__init__(AddByConstOp, [node_A], ctx)
        self.const_attr = const_val

    def compute(self, input_vals, ectx):
        return input_vals[0] + self.const_attr

    def gradient(self, output_grad):
        return [output_grad]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        try:
            c = float(self.const_attr)
        except (TypeError, ValueError):
            return None
        return None if a is None else (a[0] + c, a[1] + c)


class MulOp(Op):
    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(MulOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        return input_vals[0] * input_vals[1]

    def gradient(self, output_grad):
        return [mul_op(self.inputs[1], output_grad, ctx=self.raw_ctx),
                mul_op(self.inputs[0], output_grad, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        a, b = input_shapes
        if a == (1,):
            return b
        if b == (1,):
            return a
        assert tuple(a) == tuple(b), f"mul shape mismatch {a} vs {b}"
        return a

    def infer_range(self, input_ranges, input_shapes=None):
        a, b = input_ranges
        if a is None or b is None:
            return None
        if self.inputs[0] is self.inputs[1]:
            # x * x is a square, not an interval product: correlation-
            # blind arithmetic would sign-flip it and hide every
            # "square + eps" zero-exclusion guard (HT804's bread)
            lo = 0.0 if a[0] <= 0.0 <= a[1] else min(a[0] * a[0],
                                                     a[1] * a[1])
            return (lo, max(a[0] * a[0], a[1] * a[1]))
        return _iv_mul(a, b)


class MulByConstOp(Op):
    def __init__(self, node_A, const_val, ctx=None):
        super().__init__(MulByConstOp, [node_A], ctx)
        self.const_attr = const_val

    def compute(self, input_vals, ectx):
        return input_vals[0] * self.const_attr

    def gradient(self, output_grad):
        return [mul_byconst_op(output_grad, self.const_attr,
                               ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        try:
            c = float(self.const_attr)
        except (TypeError, ValueError):
            return None
        return None if a is None else _iv_sorted(a[0] * c, a[1] * c)


class DivOp(Op):
    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(DivOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        return input_vals[0] / input_vals[1]

    def gradient(self, output_grad):
        # d(a/b)/da = 1/b ; d(a/b)/db = -a/b^2
        grad_a = div_op(output_grad, self.inputs[1], ctx=self.raw_ctx)
        grad_b = opposite_op(
            div_op(mul_op(output_grad, self.inputs[0]),
                   mul_op(self.inputs[1], self.inputs[1])),
            ctx=self.raw_ctx)
        return [grad_a, grad_b]

    def infer_shape(self, input_shapes):
        a, b = input_shapes
        if a == (1,):
            return b
        if b == (1,):
            return a
        assert tuple(a) == tuple(b)
        return a

    def infer_range(self, input_ranges, input_shapes=None):
        a, b = input_ranges
        if a is None or b is None or (b[0] <= 0.0 <= b[1]):
            return None           # zero-crossing denominator: HT804's job
        return _iv_mul(a, (1.0 / b[1], 1.0 / b[0]))


class DivConstOp(Op):
    """const / node (reference Division.py DivConstOp)."""

    def __init__(self, const_val, node_A, ctx=None):
        super().__init__(DivConstOp, [node_A], ctx)
        self.const_attr = const_val

    def compute(self, input_vals, ectx):
        return self.const_attr / input_vals[0]

    def gradient(self, output_grad):
        grad = opposite_op(
            div_op(mul_byconst_op(output_grad, self.const_attr),
                   mul_op(self.inputs[0], self.inputs[0])),
            ctx=self.raw_ctx)
        return [grad]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        try:
            c = float(self.const_attr)
        except (TypeError, ValueError):
            return None
        if a is None or (a[0] <= 0.0 <= a[1]):
            return None
        return _iv_sorted(c / a[1], c / a[0])


class DivHandleZeroOp(Op):
    """a/b with 0/0 := 0 (used by metrics / sparse paths)."""

    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(DivHandleZeroOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        a, b = input_vals
        return jnp.where(b == 0, jnp.zeros_like(a), a / jnp.where(b == 0, 1, b))

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class OppositeOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(OppositeOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return -input_vals[0]

    def gradient(self, output_grad):
        return [opposite_op(output_grad, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        return None if a is None else (-a[1], -a[0])


class SqrtOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(SqrtOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.sqrt(input_vals[0])

    def gradient(self, output_grad):
        # d sqrt(x) = 0.5 / sqrt(x)
        return [mul_op(output_grad,
                       mul_byconst_op(rsqrt_op(self.inputs[0]), 0.5),
                       ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None:
            return None
        # bound over the defined (x >= 0) region; a negative lo is
        # HT804's finding, not this bound's
        return (math.sqrt(max(a[0], 0.0)), math.sqrt(max(a[1], 0.0)))


class ErfOp(Op):
    """Gauss error function (ONNX Erf parity; gelu's erf form imports
    through this)."""

    def __init__(self, node_A, ctx=None):
        super().__init__(ErfOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        import jax
        return jax.lax.erf(input_vals[0])

    def gradient(self, output_grad):
        # d erf(x) = 2/sqrt(pi) * exp(-x^2)
        x = self.inputs[0]
        g = mul_byconst_op(exp_op(opposite_op(mul_op(x, x))),
                           2.0 / np.sqrt(np.pi))
        return [mul_op(output_grad, g, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        from .activations import _saturate
        a = input_ranges[0]
        if a is None:
            return (-1.0, 1.0)
        return _saturate(math.erf(a[0]), math.erf(a[1]), -1.0, 1.0)


class ReciprocalSqrtOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(ReciprocalSqrtOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.reciprocal(jnp.sqrt(input_vals[0]))

    def gradient(self, output_grad):
        # d x^{-1/2} = -1/2 x^{-3/2} = -1/2 * rsqrt(x) / x
        x = self.inputs[0]
        g = mul_byconst_op(div_op(rsqrt_op(x), x), -0.5)
        return [mul_op(output_grad, g, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None or a[0] <= 0.0:
            return None           # zero/negative operand: HT804's job
        return (1.0 / math.sqrt(a[1]), 1.0 / math.sqrt(a[0]))


class ExpOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(ExpOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.exp(input_vals[0])

    def gradient(self, output_grad):
        return [mul_op(output_grad, self, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None:
            return None
        # inf upper bound is exactly what HT801 wants to see for an
        # un-shifted exp whose operand reaches the overflow knee
        return (_iv_exp(a[0]), _iv_exp(a[1]))


class LogOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(LogOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.log(input_vals[0])

    def gradient(self, output_grad):
        return [div_op(output_grad, self.inputs[0], ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None or a[0] <= 0.0:
            return None           # log of a zero-reaching operand: HT804
        return (math.log(a[0]), math.log(a[1]))


class AbsOp(Op):
    def __init__(self, node_A, ctx=None):
        super().__init__(AbsOp, [node_A], ctx)

    def compute(self, input_vals, ectx):
        return jnp.abs(input_vals[0])

    def gradient(self, output_grad):
        from .activations import sign_op
        return [mul_op(output_grad, sign_op(self.inputs[0]),
                       ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        if a is None:
            return None
        lo = 0.0 if a[0] <= 0.0 <= a[1] else min(abs(a[0]), abs(a[1]))
        return (lo, max(abs(a[0]), abs(a[1])))


class PowerOp(Op):
    def __init__(self, node_A, p, ctx=None):
        super().__init__(PowerOp, [node_A], ctx)
        self.p = p

    def compute(self, input_vals, ectx):
        return jnp.power(input_vals[0], self.p)

    def gradient(self, output_grad):
        return [mul_op(output_grad,
                       mul_byconst_op(power_op(self.inputs[0], self.p - 1),
                                      self.p),
                       ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a = input_ranges[0]
        p = self.p
        if a is None or p != int(p) or p < 0:
            return None           # negative p over a zero crossing: HT804
        p = int(p)
        try:
            vals = (a[0] ** p, a[1] ** p)
        except OverflowError:
            return (0.0 if p % 2 == 0 else -float("inf"), float("inf"))
        if p % 2 == 0:
            lo = 0.0 if a[0] <= 0.0 <= a[1] else min(vals)
            return (lo, max(vals))
        return _iv_sorted(*vals)


class WhereOp(Op):
    def __init__(self, cond, node_A, node_B, ctx=None):
        super().__init__(WhereOp, [cond, node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        return jnp.where(input_vals[0] != 0, input_vals[1], input_vals[2])

    def gradient(self, output_grad):
        zero = mul_byconst_op(output_grad, 0.0)
        return [None,
                where_op(self.inputs[0], output_grad, zero,
                         ctx=self.raw_ctx),
                where_op(self.inputs[0], zero, output_grad,
                         ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[1]

    def infer_range(self, input_ranges, input_shapes=None):
        _, a, b = input_ranges
        if a is None or b is None:
            return None
        return (min(a[0], b[0]), max(a[1], b[1]))


class OneHotOp(Op):
    def __init__(self, node, num_classes, ctx=None):
        super().__init__(OneHotOp, [node], ctx)
        self.num_classes = num_classes

    def compute(self, input_vals, ectx):
        import jax.nn
        return jax.nn.one_hot(input_vals[0].astype(jnp.int32),
                              self.num_classes, dtype=jnp.float32)

    def gradient(self, output_grad):
        return [None]

    def infer_shape(self, input_shapes):
        return tuple(input_shapes[0]) + (self.num_classes,)

    def infer_range(self, input_ranges, input_shapes=None):
        return (0.0, 1.0)


class MatrixDotOp(Op):
    """Row-wise dot: elementwise multiply then sum over trailing axes
    (reference gpu_ops/MatrixDot.py)."""

    def __init__(self, node_A, node_B, axes=0, ctx=None):
        super().__init__(MatrixDotOp, [node_A, node_B], ctx)
        self.axes = axes

    def compute(self, input_vals, ectx):
        a, b = input_vals
        return a * b  # reference semantics: elementwise product kernel

    def gradient(self, output_grad):
        return [mul_op(output_grad, self.inputs[1], ctx=self.raw_ctx),
                mul_op(output_grad, self.inputs[0], ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        a, b = input_ranges
        if a is None or b is None:
            return None
        return _iv_mul(a, b)


class CastOp(Op):
    """Dtype cast (ONNX Cast). Gradient passes through for float->float
    casts (cast back happens implicitly at the consumer's dtype); casts
    to integer/bool are non-differentiable and contribute zeros."""

    def __init__(self, node_A, dtype, ctx=None):
        super().__init__(CastOp, [node_A], ctx)
        self.dtype = jnp.dtype(dtype)

    def compute(self, input_vals, ectx):
        return input_vals[0].astype(self.dtype)

    def gradient(self, output_grad):
        if not jnp.issubdtype(self.dtype, jnp.inexact):
            from .shape import zeroslike_op
            return [zeroslike_op(self.inputs[0], ctx=self.raw_ctx)]
        return [output_grad]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        # the value interval survives the cast unchanged; whether the
        # TARGET dtype can represent it is HT801's check, which reads
        # this op's (unclamped) interval against self.dtype's max
        return input_ranges[0]


class ClipOp(Op):
    """Clamp to [min_val, max_val]; gradient is masked to the interior
    (ONNX Clip)."""

    def __init__(self, node_A, min_val=None, max_val=None, ctx=None):
        super().__init__(ClipOp, [node_A], ctx)
        self.min_val = min_val
        self.max_val = max_val

    def compute(self, input_vals, ectx):
        return jnp.clip(input_vals[0], self.min_val, self.max_val)

    def gradient(self, output_grad):
        mask = clip_mask_op(self.inputs[0], self.min_val, self.max_val,
                            ctx=self.raw_ctx)
        return [mul_op(output_grad, mask, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        # a half-bounded result (e.g. [1e-12, inf) from a one-sided
        # clip of an unknown operand) still carries the zero-exclusion
        # guard HT804 looks for
        a = input_ranges[0]
        lo = -float("inf") if a is None else a[0]
        hi = float("inf") if a is None else a[1]
        if self.min_val is not None:
            lo = max(lo, float(self.min_val))
            hi = max(hi, float(self.min_val))
        if self.max_val is not None:
            hi = min(hi, float(self.max_val))
            lo = min(lo, float(self.max_val))
        return (lo, hi)


class ClipMaskOp(Op):
    def __init__(self, node_A, min_val, max_val, ctx=None):
        super().__init__(ClipMaskOp, [node_A], ctx)
        self.min_val = min_val
        self.max_val = max_val

    def compute(self, input_vals, ectx):
        x = input_vals[0]
        mask = jnp.ones_like(x)
        if self.min_val is not None:
            mask = mask * (x >= self.min_val)
        if self.max_val is not None:
            mask = mask * (x <= self.max_val)
        return mask

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        return (0.0, 1.0)


# ---------------------------------------------------------------------------
# builders (reference-named)
# ---------------------------------------------------------------------------

def add_op(node_A, node_B, ctx=None):
    return AddOp(node_A, node_B, ctx=ctx)


def addbyconst_op(node_A, const_val, ctx=None):
    return AddByConstOp(node_A, const_val, ctx=ctx)


def mul_op(node_A, node_B, ctx=None):
    return MulOp(node_A, node_B, ctx=ctx)


def mul_byconst_op(node_A, const_val, ctx=None):
    return MulByConstOp(node_A, const_val, ctx=ctx)


def div_op(node_A, node_B, ctx=None):
    return DivOp(node_A, node_B, ctx=ctx)


def div_const_op(const_val, node_A, ctx=None):
    return DivConstOp(const_val, node_A, ctx=ctx)


def div_handle_zero_op(node_A, node_B, ctx=None):
    return DivHandleZeroOp(node_A, node_B, ctx=ctx)


def opposite_op(node_A, ctx=None):
    return OppositeOp(node_A, ctx=ctx)


def sqrt_op(node, ctx=None):
    return SqrtOp(node, ctx=ctx)


def erf_op(node, ctx=None):
    return ErfOp(node, ctx=ctx)


def rsqrt_op(node, ctx=None):
    return ReciprocalSqrtOp(node, ctx=ctx)


def exp_op(node, ctx=None):
    return ExpOp(node, ctx=ctx)


def log_op(node, ctx=None):
    return LogOp(node, ctx=ctx)


def abs_op(node, ctx=None):
    return AbsOp(node, ctx=ctx)


def power_op(node, p, ctx=None):
    return PowerOp(node, p, ctx=ctx)


def where_op(cond, node_A, node_B, ctx=None):
    return WhereOp(cond, node_A, node_B, ctx=ctx)


def one_hot_op(node, num_classes, ctx=None):
    return OneHotOp(node, num_classes, ctx=ctx)


def matrix_dot_op(node_A, node_B, axes=0, ctx=None):
    return MatrixDotOp(node_A, node_B, axes=axes, ctx=ctx)


def cast_op(node, dtype, ctx=None):
    return CastOp(node, dtype, ctx=ctx)


def clip_op(node, min_val=None, max_val=None, ctx=None):
    return ClipOp(node, min_val=min_val, max_val=max_val, ctx=ctx)


def clip_mask_op(node, min_val, max_val, ctx=None):
    return ClipMaskOp(node, min_val, max_val, ctx=ctx)
