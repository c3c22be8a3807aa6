"""Ring attention — sequence/context parallelism over an ICI ring.

NEW capability, absent in the reference (SURVEY.md §5: no sequence
parallelism anywhere; BERT caps at 512 tokens). The sequence axis shards
over a mesh axis; each device keeps its Q shard resident and rotates K/V
shards around the ring with ``lax.ppermute`` while merging partial
attention with the online-softmax rule — the distributed form of flash
attention. Peak memory per chip is O(S/n · D) and the KV transfers ride
ICI neighbor links, overlapping with the block matmuls.

Causal (decoder) attention uses the zigzag layout: with a contiguous
sequence split the causal mask leaves device 0 nearly idle and device
n-1 doing n× its share, so instead each device owns chunks ``(r,
2n-1-r)`` of a 2n-chunk split. Every ring step then does exactly half a
block's worth of useful scores on every device — the first-half keys
against both local query chunks when the incoming KV originates earlier
in the sequence, or the full keys against the second query chunk when it
originates later — so the chips stay load-balanced in lockstep
(ring-flash-attention's zigzag schedule, re-derived for ppermute).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ring_attention_sharded",
           "zigzag_ring_attention", "zigzag_indices"]


def _partial_attn(q_, k_, v_, bias, sm_scale):
    """One attention block: scores, running max m, normalizer l, and the
    unnormalized output o — the quantities the online-softmax merge
    combines (shared by the non-causal ring, the zigzag causal ring, and
    Ulysses' local blocking in ulysses.py)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_.dtype), v_)
    return m, l, o.astype(jnp.float32)


def _merge(acc, blk):
    """Online-softmax merge of two (m, l, o) partials — the flash
    attention rescale rule."""
    m_acc, l_acc, o_acc = acc
    m_blk, l_blk, o_blk = blk
    m_new = jnp.maximum(m_acc, m_blk)
    a_old = jnp.exp(m_acc - m_new)
    a_blk = jnp.exp(m_blk - m_new)
    return (m_new, l_acc * a_old + l_blk * a_blk,
            o_acc * a_old + o_blk * a_blk)


def ring_attention(q, k, v, axis_name, sm_scale=1.0, mask=None):
    """Per-shard ring attention body (call inside shard_map).

    q, k, v: local shards [B, H, S_local, D] (sequence dim sharded over
    ``axis_name``). mask: optional additive [B, 1, 1, S_local] shard.
    Non-causal (bidirectional-encoder semantics).
    """
    axis_size = lax.psum(1, axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry):
        acc, k_cur, v_cur, mask_cur = carry
        acc = _merge(acc, _partial_attn(q, k_cur, v_cur, mask_cur,
                                        sm_scale))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = (lax.ppermute(mask_cur, axis_name, perm)
                    if mask_cur is not None else None)
        return acc, k_nxt, v_nxt, mask_nxt

    b, h, s_loc, d = q.shape
    m0 = jnp.full((b, h, s_loc, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    carry = ((m0, l0, o0), k, v, mask)
    # static python loop: axis_size rotations; each iteration's ppermute
    # overlaps with the next block's matmuls under XLA latency hiding
    for _ in range(axis_size):
        carry = step(carry)
    _, l, o = carry[0]
    return (o / l).astype(q.dtype)


def shard_map_qkv(body_fn, q, k, v, mesh, axis_name, mask=None):
    """Shared shard_map plumbing for sequence-parallel attention bodies
    (ring and Ulysses): q/k/v are global [B, H, S, D] with the sequence
    dim sharded over ``axis_name``; the additive key mask shards on its
    last dim. ``body_fn(q, k, v, mask=...)`` runs per shard."""
    from jax.sharding import PartitionSpec as P
    from .mesh import shard_map_unchecked

    spec = P(None, None, axis_name, None)
    mask_spec = P(None, None, None, axis_name)
    # unchecked: the causal bodies branch per ring hop (lax.cond), and
    # the varying-axes checker rejects branches that disagree on it
    if mask is not None:
        body = lambda q_, k_, v_, m_: body_fn(q_, k_, v_, mask=m_)  # noqa: E731
        return shard_map_unchecked(body, mesh=mesh,
                                   in_specs=(spec, spec, spec, mask_spec),
                                   out_specs=spec)(q, k, v, mask)
    body = lambda q_, k_, v_: body_fn(q_, k_, v_)                   # noqa: E731
    return shard_map_unchecked(body, mesh=mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=spec)(q, k, v)


def zigzag_indices(s, n):
    """Index permutation mapping the natural sequence order to the zigzag
    shard layout: shard r holds chunks (r, 2n-1-r) of a 2n-chunk split.
    Returns (perm, inv): ``x[perm]`` is zigzag order, ``y[inv]`` undoes it.
    """
    if s % (2 * n):
        raise ValueError(
            f"causal ring needs seq len ({s}) divisible by 2*sp axis "
            f"({2 * n})")
    c = s // (2 * n)
    perm = np.concatenate([
        np.concatenate([np.arange(r * c, (r + 1) * c),
                        np.arange((2 * n - 1 - r) * c, (2 * n - r) * c)])
        for r in range(n)])
    return perm, np.argsort(perm)


def zigzag_ring_attention(q, k, v, axis_name, sm_scale=1.0, mask=None):
    """Causal ring attention body over the zigzag layout (call inside
    shard_map; inputs must already be zigzag-permuted — the sharded
    wrapper below does both permutes).

    q, k, v: local shards [B, H, 2c, D] — chunks (r, 2n-1-r) of the
    2n-chunk global sequence. mask: optional additive [B, 1, 1, 2c]
    key-padding shard (also zigzag order). At step t the KV block from
    src=(r-t)%n is, per the causal order, either entirely earlier than
    both local query chunks in its first half and entirely later in its
    second (src < r: attend q_full x k_first), or straddles so that only
    the second query chunk sees it (src > r: attend q_second x k_full).
    Both branches score 2c*c pairs — every device does identical work
    every step.
    """
    n = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    b, h, s2, d = q.shape
    c = s2 // 2

    def partial_attn(q_, k_, v_, bias):
        return _partial_attn(q_, k_, v_, bias, sm_scale)

    # global positions of the local query rows under the zigzag layout
    ar = jnp.arange(c)
    q_pos = jnp.concatenate([r * c + ar, (2 * n - 1 - r) * c + ar])

    # t = 0: diagonal — causal mask within the local 2-chunk block
    diag_bias = jnp.where(q_pos[:, None] >= q_pos[None, :],
                          0.0, -1e9)[None, None]
    if mask is not None:
        diag_bias = diag_bias + mask
    acc = partial_attn(q, k, v, diag_bias)

    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur, mask_cur = k, v, mask
    neg = jnp.float32(-1e30)
    for t in range(1, n):
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        if mask_cur is not None:
            mask_cur = lax.ppermute(mask_cur, axis_name, perm)
        src = (r - t) % n

        def earlier(k_, v_, m_):
            # src < r: first KV half precedes both q chunks (fully
            # visible), second half follows both (fully masked — skip).
            bias = None if m_ is None else m_[..., :c]
            return partial_attn(q, k_[:, :, :c], v_[:, :, :c], bias)

        def later(k_, v_, m_):
            # src > r: only the second q chunk (global chunk 2n-1-r)
            # sees this KV block, and sees all of it. Rows of the first
            # q chunk contribute nothing: pad with m=-inf / l,o=0.
            m_blk, l_blk, o_blk = partial_attn(q[:, :, c:], k_, v_, m_)
            pad = jnp.full((b, h, c, 1), neg)
            return (jnp.concatenate([pad, m_blk], axis=2),
                    jnp.concatenate([jnp.zeros((b, h, c, 1)), l_blk],
                                    axis=2),
                    jnp.concatenate([jnp.zeros((b, h, c, d)), o_blk],
                                    axis=2))

        if mask_cur is None:
            blk = lax.cond(src < r,
                           lambda kv: earlier(kv[0], kv[1], None),
                           lambda kv: later(kv[0], kv[1], None),
                           (k_cur, v_cur))
        else:
            blk = lax.cond(src < r,
                           lambda kv: earlier(*kv),
                           lambda kv: later(*kv),
                           (k_cur, v_cur, mask_cur))
        acc = _merge(acc, blk)

    _, l, o = acc
    return (o / l).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", sm_scale=1.0,
                           mask=None, causal=False):
    """shard_map wrapper: q/k/v are global [B, H, S, D]; the sequence dim
    shards over ``axis_name`` of ``mesh`` and the ring runs over ICI.

    ``causal=True`` routes through the load-balanced zigzag schedule:
    the global arrays are permuted into zigzag order (one resharding
    shuffle — a real ingest pipeline would pre-permute at the loader),
    the causal ring runs, and the output is permuted back.
    """
    if not causal:
        fn = functools.partial(ring_attention, axis_name=axis_name,
                               sm_scale=sm_scale)
        return shard_map_qkv(fn, q, k, v, mesh, axis_name, mask=mask)
    n = mesh.shape[axis_name]
    perm, inv = zigzag_indices(q.shape[2], n)
    qz = jnp.take(q, perm, axis=2)
    kz = jnp.take(k, perm, axis=2)
    vz = jnp.take(v, perm, axis=2)
    maskz = None if mask is None else jnp.take(mask, perm, axis=3)
    fn = functools.partial(zigzag_ring_attention, axis_name=axis_name,
                           sm_scale=sm_scale)
    out = shard_map_qkv(fn, qz, kz, vz, mesh, axis_name, mask=maskz)
    return jnp.take(out, inv, axis=2)
