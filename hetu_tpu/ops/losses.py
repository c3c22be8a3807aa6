"""Loss ops.

Reference parity: gpu_ops/{SoftmaxCrossEntropy,SoftmaxCrossEntropySparse,
BinaryCrossEntropy}.py. Log-sum-exp is computed in a numerically stable
form; gradients are closed-form (softmax(y) - target), matching the
reference kernels (src/ops/SoftmaxCrossEntropy.cu).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.node import Op

__all__ = [
    "softmaxcrossentropy_op", "softmaxcrossentropy_gradient_op",
    "softmaxcrossentropy_sparse_op", "softmaxcrossentropy_sparse_gradient_op",
    "binarycrossentropy_op", "binarycrossentropy_gradient_op",
    "crossentropy_op",
]

# predictions/probabilities are clipped to [_PROB_EPS, 1 - _PROB_EPS]
# before any log/div — both in the BCE/CE compute bodies and in the
# gradient graphs, so neither direction can divide by (or log) zero
_PROB_EPS = 1e-12


def _label_on_simplex(label_range):
    """The CE bounds assume labels form a distribution (entries in
    [0, 1]); a KNOWN label interval outside that is off-contract —
    the transfer makes no claim rather than an unsound one."""
    return label_range is None or (label_range[0] >= 0.0
                                   and label_range[1] <= 1.0)


def _ce_range(logit_range, input_shapes, label_range=None):
    """[0, 2 max|logit| + ln C] — max_j l_j - min_j l_j + ln C bounds
    logsumexp(l) - l_label for any label distribution on the simplex."""
    import math
    if logit_range is None or not _label_on_simplex(label_range):
        return None
    c = None
    if input_shapes and input_shapes[0]:
        c = input_shapes[0][-1]
    m = max(abs(logit_range[0]), abs(logit_range[1]))
    return (0.0, 2.0 * m + math.log(float(c if c else 2)))


class SoftmaxCrossEntropyOp(Op):
    """Per-example CE of logits (node_A) vs one-hot/soft labels (node_B);
    output shape = batch dims (reference SoftmaxCrossEntropy.py)."""

    def __init__(self, node_A, node_B, use_cudnn=True, ctx=None):
        super().__init__(SoftmaxCrossEntropyOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        logits, labels = input_vals
        logz = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.sum(labels * (logits - logz), axis=-1)

    def gradient(self, output_grad):
        grad = softmaxcrossentropy_gradient_op(
            self.inputs[0], self.inputs[1], output_grad, ctx=self.raw_ctx)
        return [grad, None]

    def infer_shape(self, input_shapes):
        shape = tuple(input_shapes[0][:-1])
        return shape if shape else (1,)

    def infer_range(self, input_ranges, input_shapes=None):
        # interval semantics for the HT8xx numerics verifier: per-example
        # CE of C-way logits is within [0, 2 max|logit| + ln C]
        return _ce_range(input_ranges[0], input_shapes,
                         label_range=input_ranges[1])


class SoftmaxCrossEntropyGradientOp(Op):
    def __init__(self, node_A, node_B, grad_node, ctx=None):
        super().__init__(SoftmaxCrossEntropyGradientOp,
                         [node_A, node_B, grad_node], ctx)

    def compute(self, input_vals, ectx):
        logits, labels, grad = input_vals
        return (jax.nn.softmax(logits, axis=-1) - labels) * grad[..., None]

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        _, labels, grad = input_ranges
        if grad is None:
            return None
        lm = 1.0 if labels is None else max(1.0, abs(labels[0]),
                                            abs(labels[1]))
        m = (1.0 + lm) * max(abs(grad[0]), abs(grad[1]))
        return (-m, m)


def _log_sum_exp(logits):
    """float32 ``[rows]`` log-sum-exp over the class axis: the maximum,
    then the exponentials summed in float32 — elementwise work and
    reductions only, so the logits are read in the layout their
    producer left them. The sparse pair's one ``lse``: the forward
    computes it and hands it to its gradient op."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    m = m.astype(jnp.float32)
    e = jnp.exp(logits.astype(jnp.float32) - m)
    return m[..., 0] + jnp.log(jnp.sum(e, axis=-1))


def _label_hit(logits, labels):
    """``[..., C]`` bool, true at each row's label (clipped into the
    classes as a gather would be): a comparison with an iota, which a
    fusion reads in place where ``take_along_axis`` wants the class
    axis laid out its way — two copies of BERT's 2 GB of MLM logits a
    step (PERF.md, PR 48)."""
    nclass = logits.shape[-1]
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
    return classes == jnp.clip(labels, 0, nclass - 1)[..., None]


class SoftmaxCrossEntropySparseOp(Op):
    """CE vs integer labels with an ignored index (reference
    SoftmaxCrossEntropySparse.py — used by BERT MLM)."""

    def __init__(self, node_A, node_B, ignored_index=-1, ctx=None):
        super().__init__(SoftmaxCrossEntropySparseOp, [node_A, node_B], ctx)
        self.ignored_index = ignored_index

    def compute(self, input_vals, ectx):
        logits, labels = input_vals
        labels = labels.astype(jnp.int32)
        hit = _label_hit(logits, labels)
        lse = _log_sum_exp(logits)
        # the residual the gradient op reads, as the flash forward hands
        # its (o, lse) on (ops/attention.py)
        ectx.cache[("sparse_ce_lse", self.id)] = lse
        picked = jnp.sum(jnp.where(hit, logits, 0).astype(jnp.float32),
                         axis=-1)
        mask = (labels != self.ignored_index)
        return jnp.where(mask, lse - picked, 0.0).astype(logits.dtype)

    def gradient(self, output_grad):
        grad = softmaxcrossentropy_sparse_gradient_op(
            self.inputs[0], self.inputs[1], output_grad,
            self.ignored_index, forward_op=self, ctx=self.raw_ctx)
        return [grad, None]

    def infer_shape(self, input_shapes):
        shape = tuple(input_shapes[0][:-1])
        return shape if shape else (1,)

    def infer_range(self, input_ranges, input_shapes=None):
        return _ce_range(input_ranges[0], input_shapes)


class SoftmaxCrossEntropySparseGradientOp(Op):
    """``(softmax(logits) - onehot(label)) * grad`` from the forward's
    log-sum-exp: ``forward_op``'s residual where that op ran in this
    trace, else the same function of the logits here."""

    def __init__(self, node_A, node_B, node_C, ignored_index=-1,
                 forward_op=None, ctx=None):
        super().__init__(SoftmaxCrossEntropySparseGradientOp,
                         [node_A, node_B, node_C], ctx)
        self.ignored_index = ignored_index
        self.forward_op = forward_op

    def compute(self, input_vals, ectx):
        logits, labels, grad = input_vals
        labels = labels.astype(jnp.int32)
        fwd = self.forward_op
        lse = ectx.cache.get(("sparse_ce_lse", fwd.id)) if fwd else None
        if lse is None:
            lse = _log_sum_exp(logits)
        p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        d = p - _label_hit(logits, labels).astype(jnp.float32)
        g = jnp.where(labels != self.ignored_index, grad, 0)
        return (d * g[..., None].astype(jnp.float32)).astype(logits.dtype)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        grad = input_ranges[2]
        if grad is None:
            return None
        # |softmax - onehot| <= 1 elementwise
        m = max(abs(grad[0]), abs(grad[1]))
        return (-m, m)


class BinaryCrossEntropyOp(Op):
    """Elementwise BCE of predictions (node_A, already in (0,1)) vs labels
    (node_B) (reference BinaryCrossEntropy.py)."""

    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(BinaryCrossEntropyOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        pred, label = input_vals
        pred = jnp.clip(pred, _PROB_EPS, 1 - _PROB_EPS)
        return -(label * jnp.log(pred) + (1 - label) * jnp.log(1 - pred))

    def gradient(self, output_grad):
        grad = binarycrossentropy_gradient_op(
            self.inputs[0], self.inputs[1], output_grad, ctx=self.raw_ctx)
        return [grad, None]

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        import math
        label = input_ranges[1]
        if not _label_on_simplex(label):
            return None     # off-[0,1] labels make BCE go negative
        return (0.0, 2.0 * -math.log(_PROB_EPS))


class BinaryCrossEntropyGradientOp(Op):
    def __init__(self, node_A, node_B, node_C, ctx=None):
        super().__init__(BinaryCrossEntropyGradientOp,
                         [node_A, node_B, node_C], ctx)

    def compute(self, input_vals, ectx):
        pred, label, grad = input_vals
        pred = jnp.clip(pred, _PROB_EPS, 1 - _PROB_EPS)
        return grad * (pred - label) / (pred * (1 - pred))

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]

    def infer_range(self, input_ranges, input_shapes=None):
        grad = input_ranges[2]
        if grad is None:
            return None
        # |pred - label| / (pred (1 - pred)) <= (1 + |label|) / eps with
        # pred clipped to [eps, 1 - eps]
        label = input_ranges[1]
        lm = 1.0 if label is None else max(1.0, abs(label[0]),
                                           abs(label[1]))
        m = max(abs(grad[0]), abs(grad[1])) * (1.0 + lm) / _PROB_EPS
        return (-m, m)


class CrossEntropyOp(Op):
    """-sum(labels * log(probs)) per example, probs already normalized."""

    def __init__(self, node_A, node_B, ctx=None):
        super().__init__(CrossEntropyOp, [node_A, node_B], ctx)

    def compute(self, input_vals, ectx):
        probs, labels = input_vals
        return -jnp.sum(labels * jnp.log(jnp.clip(probs, _PROB_EPS, None)),
                        axis=-1)

    def gradient(self, output_grad):
        from .basic import clip_op, div_op, opposite_op, mul_op
        from .shape import broadcastto_op
        # clip the denominator exactly like the forward's log argument:
        # softmax probabilities legitimately underflow to 0.0, and the
        # unguarded -labels/probs was this repo's own HT804 finding
        d = opposite_op(div_op(self.inputs[1],
                               clip_op(self.inputs[0], _PROB_EPS, None)))
        g = broadcastto_op(output_grad, self.inputs[0])
        return [mul_op(d, g, ctx=self.raw_ctx), None]

    def infer_shape(self, input_shapes):
        shape = tuple(input_shapes[0][:-1])
        return shape if shape else (1,)

    def infer_range(self, input_ranges, input_shapes=None):
        import math
        labels = input_ranges[1]
        if not _label_on_simplex(labels):
            return None     # negative labels flip the sum's sign
        c = 2
        if input_shapes and input_shapes[0]:
            c = input_shapes[0][-1]
        return (0.0, float(c) * -math.log(_PROB_EPS))


def softmaxcrossentropy_op(node_A, node_B, use_cudnn=True, ctx=None):
    return SoftmaxCrossEntropyOp(node_A, node_B, ctx=ctx)


def softmaxcrossentropy_gradient_op(node_A, node_B, grad_node, ctx=None):
    return SoftmaxCrossEntropyGradientOp(node_A, node_B, grad_node, ctx=ctx)


def softmaxcrossentropy_sparse_op(node_A, node_B, ignored_index=-1,
                                  ctx=None):
    return SoftmaxCrossEntropySparseOp(node_A, node_B, ignored_index,
                                       ctx=ctx)


def softmaxcrossentropy_sparse_gradient_op(node_A, node_B, node_C,
                                           ignored_index=-1,
                                           forward_op=None, ctx=None):
    return SoftmaxCrossEntropySparseGradientOp(node_A, node_B, node_C,
                                               ignored_index, forward_op,
                                               ctx=ctx)


def binarycrossentropy_op(node_A, node_B, ctx=None):
    return BinaryCrossEntropyOp(node_A, node_B, ctx=ctx)


def binarycrossentropy_gradient_op(node_A, node_B, node_C, ctx=None):
    return BinaryCrossEntropyGradientOp(node_A, node_B, node_C, ctx=ctx)


def crossentropy_op(node_A, node_B, ctx=None):
    return CrossEntropyOp(node_A, node_B, ctx=ctx)
