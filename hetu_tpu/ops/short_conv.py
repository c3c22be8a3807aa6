"""A gated short convolution as a token mixer: a graph op with a
gradient op of its own (``short_conv_op``; ``models/hybrid_decoder.py``
is built from it).

No reference equivalent (the reference's ``ops/conv.py`` convolves
images). The mixer of the LFM2 family reads ONE projection of the normed
stream, ``proj = a W_in`` ``[B, S, 3C]``, as three ``[B, S, C]`` parts
``B | C | u`` side by side, and per channel ``c`` and token ``t``

    z_t = B_t * u_t
    v_t = sum_j taps[c, j] * z_{t - (K - 1 - j)}       (zeros before t = 0)
    y_t = C_t * v_t

causal, depthwise, ``K`` taps (3 as published), no bias and no
activation. The backward hands the projection ONE ``[B, S, 3C]``
gradient ``dB | dC | du`` — so ``W_in``'s weight gradient is one matmul —
and the taps a float32 ``[C, K]``:

    dC_t = dy_t * v_t           dv_t = dy_t * C_t
    dz_t = sum_j taps[c, j] * dv_{t + (K - 1 - j)}     (zeros past S - 1)
    dB_t = dz_t * u_t           du_t = dz_t * B_t
    dtaps[c, j] = sum over b, t of dv_t * z_{t - (K - 1 - j)}

Everything between the reads and the writes is float32. The op is bound
by memory (``benchmark/flops/short_conv.py``: the forward reads ``[T,
3C]`` and writes ``[T, C]``, the backward reads both and ``dy`` and
writes ``[T, 3C]``).

**The form** is composed ``jax.numpy`` (a shift is a pad and a slice),
one jitted function a direction under the stable names
``hetu_short_conv_fwd`` / ``hetu_short_conv_bwd``. XLA inlines both into
the step, so a profile shows them as fusions under the op's scopes
``hetu.fwd/ShortConvOp/`` and ``hetu.bwd/_ShortConvGradientOp/``
(``docs/tools.md``), and a traced call says so in a ``short_conv_plan``
instant (``form="composed"``, ``reason``: the first condition that
decided it — there is no kernel to choose, so ``"no_kernel"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.node import Op
from .norm import PackedPartOp as _Part

__all__ = ["short_conv", "short_conv_grads", "short_conv_op", "ShortConvOp",
           "FORWARD_NAME", "BACKWARD_NAME"]

FORWARD_NAME = "hetu_short_conv_fwd"
BACKWARD_NAME = "hetu_short_conv_bwd"


def _parts(proj, channels):
    """``B | C | u`` of a ``[B, S, 3C]`` projection, float32."""
    if proj.ndim != 3 or proj.shape[-1] != 3 * channels:
        raise ValueError(f"a short convolution over {channels} channels "
                         f"reads [B, S, {3 * channels}] rows, not "
                         f"{proj.shape}")
    return tuple(proj[..., i * channels:(i + 1) * channels]
                 .astype(jnp.float32) for i in range(3))


def _shifted(x, by):
    """``out_t = x_{t - by}`` along axis 1 (``by`` < 0: ``x_{t + |by|}``),
    zeros where the index leaves the sequence."""
    if by == 0:
        return x
    s = x.shape[1]
    pad = ((0, 0), (by, 0), (0, 0)) if by > 0 else ((0, 0), (0, -by), (0, 0))
    x = jnp.pad(x, pad)
    return x[:, :s] if by > 0 else x[:, -by:]


def short_conv(proj, taps):
    """``y [B, S, C]`` of ``proj [B, S, 3C]`` under ``taps [C, K]``, in
    ``proj``'s dtype."""
    channels, k = taps.shape
    gate_in, gate_out, u = _parts(proj, channels)
    taps = taps.astype(jnp.float32)
    z = gate_in * u
    v = sum(taps[:, j] * _shifted(z, k - 1 - j) for j in range(k))
    return (gate_out * v).astype(proj.dtype)


def short_conv_grads(proj, taps, dy):
    """``(dproj [B, S, 3C] in proj's dtype, dtaps [C, K] float32)``."""
    channels, k = taps.shape
    gate_in, gate_out, u = _parts(proj, channels)
    taps = taps.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    z = gate_in * u
    behind = [_shifted(z, k - 1 - j) for j in range(k)]
    v = sum(taps[:, j] * behind[j] for j in range(k))
    dv = dy * gate_out
    dz = sum(taps[:, j] * _shifted(dv, -(k - 1 - j)) for j in range(k))
    dproj = jnp.concatenate([dz * u, dy * v, dz * gate_in], axis=-1)
    dtaps = jnp.stack([jnp.sum(dv * behind[j], axis=(0, 1))
                       for j in range(k)], axis=-1)
    return dproj.astype(proj.dtype), dtaps


def _named(fn, name):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


_forward = _named(lambda proj, taps: short_conv(proj, taps), FORWARD_NAME)
_backward = _named(lambda proj, taps, dy: short_conv_grads(proj, taps, dy),
                   BACKWARD_NAME)


class ShortConvOp(Op):
    """``y [B, S, C]`` of a projection's rows ``[B, S, 3C]`` and the
    taps ``[C, K]``; see the module's docstring."""

    def __init__(self, proj, taps, ctx=None):
        super().__init__(ShortConvOp, [proj, taps], ctx)

    def compute(self, input_vals, ectx):
        proj, taps = input_vals
        from .. import telemetry
        telemetry.get_telemetry().instant(
            "short_conv_plan", form="composed", reason="no_kernel",
            rows=int(proj.shape[0] * proj.shape[1]),
            channels=int(taps.shape[0]), taps=int(taps.shape[1]))
        return _forward(proj, taps)

    def gradient(self, output_grad):
        packed = _ShortConvGradientOp(self, output_grad, ctx=self.raw_ctx)
        return [_Part(packed, self.inputs[0], 0, ctx=self.raw_ctx),
                _Part(packed, self.inputs[1], 1, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        b, s, width = input_shapes[0]
        return (b, s, width // 3)


class _ShortConvGradientOp(Op):
    """Packed ``(dproj, dtaps)`` of :class:`ShortConvOp`."""

    def __init__(self, forward_op, output_grad, ctx=None):
        super().__init__(_ShortConvGradientOp,
                         list(forward_op.inputs) + [output_grad], ctx)

    def compute(self, input_vals, ectx):
        return _backward(*input_vals)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


def short_conv_op(proj, taps, ctx=None):
    """The gated short convolution ``C * conv(B * u)`` of a projection's
    ``[B, S, 3C]`` rows ``B | C | u`` under ``taps [C, K]``."""
    return ShortConvOp(proj, taps, ctx=ctx)
