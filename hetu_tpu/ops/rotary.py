"""Rotary position embedding as a graph op.

No reference equivalent (the reference's zoo has learned positions
alone). The rows are token-major, ``[B, S, heads * head_dim]`` as a
projection writes them, and the rotation is in HALVES: dimension ``i``
of a head pairs with ``i + head_dim // 2``. A rotation is orthogonal, so
the gradient op is the same op turned the other way (``inverse``), and
it needs nothing the forward kept.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..graph.node import Op

__all__ = ["rotary_op", "RotaryOp", "rotate_rows"]


def rotate_rows(x, num_heads, theta, inverse=False):
    """``x [B, S, heads * head_dim]`` with position ``s`` of every head
    rotated by ``s / theta ** (2 i / head_dim)`` (the other way with
    ``inverse``); angles, sines and the products in float32, the result
    in ``x``'s dtype."""
    b, s, width = x.shape
    d = width // num_heads
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if inverse:
        sin = -sin
    xf = x.astype(jnp.float32).reshape(b, s, num_heads, d)
    lo, hi = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                          axis=-1)
    return out.reshape(b, s, width).astype(x.dtype)


class RotaryOp(Op):
    def __init__(self, node_in, num_heads, theta=10000.0, inverse=False,
                 ctx=None):
        super().__init__(RotaryOp, [node_in], ctx)
        self.num_heads = num_heads
        self.theta = float(theta)
        self.inverse = inverse

    def compute(self, input_vals, ectx):
        return rotate_rows(input_vals[0], self.num_heads, self.theta,
                           self.inverse)

    def gradient(self, output_grad):
        return [RotaryOp(output_grad, self.num_heads, self.theta,
                         not self.inverse, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]


def rotary_op(node_in, num_heads, theta=10000.0, ctx=None):
    """Rows ``[B, S, heads * head_dim]`` with every head rotated by its
    position (halves; see :func:`rotate_rows`)."""
    return RotaryOp(node_in, num_heads, theta, ctx=ctx)
