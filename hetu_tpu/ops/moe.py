"""Mixture-of-experts routing and the expert layer of ONE share of an
expert-parallel deployment (pure JAX, no graph nodes: the serving
block of ``models/latent_moe.py`` rides these).

No reference equivalent. The layer is told WHICH experts it holds
(``first``, and as many as its weight stack has) and computes their part
of the sum for the tokens routed to them; what the experts held
elsewhere would add is the exchange's business, not this module's.

* :func:`route` — the aux-loss-free family's router: sigmoid scores in
  float32 over ALL experts, a bias that moves the SELECTION only, the
  chosen ``top_k`` normalised to sum 1, then scaled.
* :func:`held_experts` — no capacity, no dropped token under any
  imbalance: the (token, pick) pairs are sorted by expert, the pairs of
  experts held elsewhere (and of padded tokens) sort behind them as one
  last group that is never computed, and two grouped matmuls (gate|up,
  then down) run over the held groups. Their cost follows the rows that
  are there, and an expert that got no row is not read.
* :func:`grouped_matmul` — ``lhs[group g's rows] @ rhs[g]``. On a TPU
  it is JAX's Pallas grouped-matmul kernel (``megablox``) under the
  stable name ``hetu_moe_experts`` (the name its events carry in a
  profile; ``ops/pallas_norm.py`` says why the jitted function carries
  it); elsewhere, and for widths the kernel's tiles do not take,
  ``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

__all__ = ["route", "held_experts", "grouped_matmul", "swiglu",
           "KERNEL_NAME", "TOKEN_CHUNK"]

KERNEL_NAME = "hetu_moe_experts"
LANES = 128
# tokens a caller should pass at a time: the sorted copies of a pass are
# [tokens * top_k, hidden], 268 MB at 4096 tokens x 8 picks x 4096 wide
# in bfloat16, whatever the prompt bucket
TOKEN_CHUNK = 4096


def _use_pallas():
    from .attention import _use_pallas as on_tpu
    return on_tpu()


def swiglu(x, w_gate_up, w_down):
    """``down(silu(gate x) * up x)`` with gate and up side by side in
    one ``[hidden, 2 * width]`` matrix."""
    h = x @ w_gate_up
    width = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :width]) * h[..., width:]) @ w_down


def route(x, w_router, bias, top_k, scale):
    """Router of the aux-loss-free family over ALL experts.

    ``x`` ``[T, hidden]``; ``w_router`` ``[hidden, E]`` and ``bias``
    ``[E]`` float32. Scores are ``sigmoid(x W)`` in float32 (the
    product at the highest precision: a TPU's default rounds float32
    operands to bfloat16, and two scores that nearly tie would flip);
    the bias is added for the SELECTION of the ``top_k`` and never
    enters a weight; the chosen scores are normalised to sum 1 and
    scaled. Returns ``(experts [T, k] int32, weights [T, k] float32,
    scores [T, E] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights, scores


def _kernel_tiles(m, k, n):
    """(tm, tk, tn) of the grouped-matmul kernel for ``[m, k] x [g, k,
    n]``, or None where its tiles do not take the widths (whole lanes,
    and a contraction its k tile divides)."""
    if k % LANES or n % LANES or m % LANES:
        return None
    tk = next(t for t in (1024, 512, 256, 128) if k % t == 0)
    tn = next(t for t in (1024, 512, 256, 128) if n % t == 0)
    tm = next(t for t in (512, 256, 128) if m % t == 0)
    return tm, tk, tn


@functools.lru_cache(maxsize=None)
def _kernel(tiles, out_dtype, interpret):
    """The megablox kernel behind a jitted function of the stable
    name. The library's own entry point is a ``jax.jit`` called
    ``gmm``, and a program's instructions are named for the innermost
    jitted function, so its body is wrapped anew."""
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

    def hetu_moe_experts(lhs, rhs, group_sizes):
        return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
                   tiling=tiles, interpret=interpret)

    hetu_moe_experts.__name__ = hetu_moe_experts.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_moe_experts)


# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

    ``lhs`` ``[m, k]`` with its rows sorted by group; ``rhs`` ``[G, k,
    n]``; ``group_sizes`` ``[G + 1]`` int32 — the last entry counts the
    rows behind the ``G`` groups (pairs of experts held elsewhere),
    which are not computed and come back as zeros. Returns ``[m, n]``
    in ``lhs``'s dtype, accumulated in float32."""
    m, k = lhs.shape
    tiles = _kernel_tiles(m, k, rhs.shape[-1]) \
        if (_use_pallas() or INTERPRET) else None
    if tiles is not None:
        return _kernel(tiles, jnp.dtype(lhs.dtype), INTERPRET)(
            lhs, rhs, group_sizes)
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1],
                             preferred_element_type=jnp.float32)
    computed = jnp.arange(m)[:, None] < jnp.sum(group_sizes[:-1])
    return jnp.where(computed, out, 0.0).astype(lhs.dtype)


def held_experts(x, experts, weights, valid, w_gate_up, w_down, first=0):
    """The held experts' part of an expert layer.

    ``x`` ``[T, hidden]``; ``experts`` / ``weights`` ``[T, k]`` from
    :func:`route`; ``valid`` ``[T]`` bool (a padded token is routed
    nowhere); ``w_gate_up`` ``[held, hidden, 2 * width]`` and
    ``w_down`` ``[held, width, hidden]`` are the stacks of the experts
    ``first .. first + held - 1``. Returns ``(sum over the held picks
    of weight * expert(x) [T, hidden] float32, rows by held expert
    [held] int32)``. The sorted copies are ``[T * k, hidden]``: a
    caller with many tokens passes ``TOKEN_CHUNK`` at a time."""
    t, k = experts.shape
    held_n = w_gate_up.shape[0]
    local = experts - first
    held = (local >= 0) & (local < held_n) & valid[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)       # [t * k]
    sizes = jnp.zeros(held_n + 1, jnp.int32).at[group].add(1)
    order = jnp.argsort(group, stable=True)
    rows = t * k
    pad = -rows % LANES if (_use_pallas() or INTERPRET) else 0
    if pad:     # the kernel's row tile; the pad rows join the last group
        order = jnp.concatenate([order, jnp.zeros(pad, order.dtype)])
        sizes = sizes.at[held_n].add(pad)
    xs = x[order // k]
    h = grouped_matmul(xs, w_gate_up, sizes)
    width = h.shape[-1] // 2
    act = (jax.nn.silu(h[:, :width].astype(jnp.float32))
           * h[:, width:].astype(jnp.float32)).astype(x.dtype)
    ys = grouped_matmul(act, w_down, sizes)
    # back to (token, pick) order; a pair held elsewhere weighs nothing
    back = jnp.zeros(rows, jnp.int32).at[order[:rows]].set(
        jnp.arange(rows, dtype=jnp.int32))
    pairs = ys[back].reshape(t, k, -1)
    out = jnp.einsum("tk,tkh->th", jnp.where(held, weights, 0.0),
                     pairs.astype(jnp.float32))
    return out, sizes[:held_n]
