"""Fused multi-head attention op.

No reference equivalent — the reference composes attention from
batch_matmul + softmax (examples/nlp/bert/hetu_bert.py:191-227) and has no
long-context support (SURVEY.md §5). This op is the single fusion point the
TPU build hangs its fast paths on:

  * default: one composed-XLA computation (fused softmax(QK^T)V) — XLA
    already keeps this on-chip for moderate S,
  * ``hetu_tpu.ops.pallas_attention``: a Pallas flash-attention kernel
    (blocked online-softmax, never materializes the S×S score matrix in
    HBM) selected automatically on TPU backends,
  * ring-attention context parallelism wraps this op per KV block
    (parallel/ring.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..graph.node import Op

__all__ = ["flash_attention_op", "FlashAttentionOp", "attention_reference",
           "flash_layout",
           "ring_attention_op", "RingAttentionOp",
           "ulysses_attention_op", "UlyssesAttentionOp",
           "prefill_attention",
           "paged_decode_attention", "paged_prefill_attention",
           "grouped_decode_attention", "grouped_ring_decode_attention",
           "ring_valid", "diff_rows_attention", "diff_rows_extent",
           "diff_prefill_attention", "diff_combine", "gather_rows_once",
           "bracketed", "event_markers", "kernels_run",
           "mla_expanded_attention", "mla_decode_attention",
           "mla_prefill_attention"]


def attention_reference(q, k, v, mask, sm_scale):
    """softmax(q k^T * scale + mask) v — [B, H, S, D] layout."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# HEAD-MAJOR callers only: the sequence length from which the fused
# Pallas backward (a head a program) beats XLA's composed vjp. A
# token-major call runs the fused backward at any length (its programs
# are as wide as the forward's): :func:`fused_backward`
FUSED_BWD_MIN_SEQ = 512


def fused_backward(s, token_major):
    """Whether a training call's backward is the fused kernel (so its
    forward keeps the logsumexp), from the shape alone: token-major at
    any S the form takes, head-major from ``FUSED_BWD_MIN_SEQ``."""
    return token_major or s >= FUSED_BWD_MIN_SEQ


# ---------------------------------------------------------------------------
# serving attention (pure JAX, no graph nodes) — the three calls the one
# serving block of models/gpt.py rides, one per cache backend
# ---------------------------------------------------------------------------

def _gather_pool_rows(k_pool, v_pool, slot_idx, heads_dim):
    """K/V rows at ``slot_idx`` ``[B, S]`` of one layer's pooled cache,
    as ``[B, S, H, D]``. The pool keeps a row's heads side by side
    (``H*D`` minor, serving/kvcache.py); only the gathered rows are
    split back into heads."""
    out = []
    for pool in (k_pool, v_pool):
        rows = pool.reshape(-1, pool.shape[-1])[slot_idx]
        out.append(rows.reshape(*slot_idx.shape, *heads_dim))
    return out


def paged_decode_attention(q, k_pool, v_pool, slot_idx, positions,
                           sm_scale):
    """One query token per sequence against a block-paged KV pool.

    ``q`` is ``[B, H, D]``; ``k_pool`` / ``v_pool`` are one layer's
    pooled cache, either ``[num_blocks, block_size, H*D]`` or already
    flattened ``[num_blocks * block_size, H*D]``; ``slot_idx`` is
    ``[B, S]`` int32 — the flat pool slot holding position ``j`` of
    sequence ``b`` (serving/kvcache.py block-table math, computed
    host-side; out-of-range positions point at the scratch block);
    ``positions`` is ``[B]`` int32, the 0-based position of each
    sequence's CURRENT token, so sequences of different lengths decode
    in the same call. Returns ``[B, H, D]``.

    There is no per-sequence dense ``S_max`` cache: K/V rows are
    gathered through the block table, so
    the per-step cost is O(S_bucket * D) over a *shared* pool and HBM
    holds only the blocks live sequences actually use. Causality/
    raggedness is the ``j <= positions[b]`` validity mask — scratch
    rows gathered past a sequence's length sit behind it."""
    k, v = _gather_pool_rows(k_pool, v_pool, slot_idx, q.shape[-2:])
    scores = jnp.einsum("bhd,bshd->bhs", q * sm_scale, k)
    valid = jnp.arange(slot_idx.shape[1])[None, :] <= positions[:, None]
    scores = jnp.where(valid[:, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhs,bshd->bhd", probs.astype(v.dtype), v)


def paged_prefill_attention(q, k_pool, v_pool, slot_idx, starts,
                            sm_scale):
    """A chunk of query tokens per sequence against a block-paged KV
    pool — the suffix-prefill analogue of :func:`paged_decode_attention`.

    ``q`` is ``[B, C, H, D]`` — ``C`` consecutive query positions per
    sequence starting at ``starts[b]`` (0-based); ``k_pool`` /
    ``v_pool`` are one layer's pooled cache (blocked or already
    flat, rows ``H*D`` wide); ``slot_idx`` is ``[B, S]`` int32 mapping
    position ``j`` of sequence ``b`` to its flat pool slot. The chunk's own K/V rows must
    already be scattered into the pool before the call; causality is
    the mask ``j <= starts[b] + i`` per chunk row ``i``, which makes
    prefix-cached prefill work unchanged: positions before ``starts``
    (the cached prefix, or earlier chunks of this prompt) are simply
    valid history gathered through the block table. Returns
    ``[B, C, H, D]``."""
    k, v = _gather_pool_rows(k_pool, v_pool, slot_idx, q.shape[-2:])
    scores = jnp.einsum("bihd,bshd->bhis", q * sm_scale, k)
    pos = starts[:, None] + jnp.arange(q.shape[1])[None, :]   # [B, C]
    valid = jnp.arange(slot_idx.shape[1])[None, None, :] \
        <= pos[:, :, None]                              # [B, C, S]
    scores = jnp.where(valid[:, None, :, :], scores, -1e9)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhis,bshd->bihd", probs.astype(v.dtype), v)


def prefill_attention(q, k, v, sm_scale, causal=True, window=None):
    """Dense prompt-phase attention for the serving decode path over
    ``[B, H, S, D]`` q/k/v: rides the Pallas flash kernel on TPU
    backends (blocked online softmax, no HBM score matrix), the
    composed reference elsewhere. The kernel's block sizes are the
    kernels' static rule (``pallas_attention._block_sizes``), and since
    the serving forward never consumes the logsumexp residual, it skips
    that output write. With a ``window`` (causal) a row sees the keys
    ``i - window < j <= i`` alone."""
    if _use_pallas():
        from .pallas_attention import flash_attention
        _, h, s, d = q.shape
        band = {} if window is None else {"window": window}
        return flash_attention(q, k, v, None, sm_scale=sm_scale,
                               causal=causal,
                               reason=flash_layout(s, d, h, False)[1],
                               **band)
    mask = None
    if window is not None:
        from .pallas_attention import _band
        mask = jnp.where(_band(q.shape[-2], window), 0.0, -1e9)[None, None]
    elif causal:
        s = q.shape[-2]
        mask = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                         -1e9)[None, None]
    return attention_reference(q, k, v, mask, sm_scale)


# ---------------------------------------------------------------------------
# grouped-query attention against a paged pool whose rows hold the
# KEY/VALUE heads alone (``G x D`` wide, ``G`` dividing the ``H`` query
# heads; one head: multi-query). No copy a query head is stored or
# gathered: the ``H / G`` queries of a group score against the group's
# one row.
# ---------------------------------------------------------------------------

def _grouped(q, k_pool, v_pool, slot_idx, valid, sm_scale):
    """``q [B, C, H, D]`` against the rows at ``slot_idx [B, S]``,
    ``valid [B, C, S]``; float32 softmax. Returns ``[B, C, H, D]``."""
    b, c, h, d = q.shape
    k, v = (_gather_latent_rows(pool, slot_idx).reshape(
        b, slot_idx.shape[1], -1, d) for pool in (k_pool, v_pool))
    g = k.shape[2]
    q = (q * sm_scale).astype(k.dtype).reshape(b, c, g, h // g, d)
    scores = jnp.einsum("bcgrd,bsgd->bgrcs", q, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(valid[:, None, None], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgrcs,bsgd->bcgrd", probs.astype(v.dtype), v)
    return ctx.reshape(b, c, h, d)


def grouped_decode_attention(q, k_pool, v_pool, slot_idx, positions,
                             sm_scale):
    """One query token a sequence, ``q [B, H, D]`` at ``positions
    [B]``, as :func:`paged_decode_attention` is. Returns ``[B, H, D]``."""
    valid = jnp.arange(slot_idx.shape[1])[None, :] <= positions[:, None]
    return _grouped(q[:, None], k_pool, v_pool, slot_idx, valid[:, None],
                    sm_scale)[:, 0]


def grouped_prefill_attention(q, k_pool, v_pool, slot_idx, starts,
                              sm_scale):
    """A chunk of query tokens a sequence, ``q [B, C, H, D]`` from
    ``starts [B]``, as :func:`paged_prefill_attention` is. Returns
    ``[B, C, H, D]``."""
    pos = starts[:, None] + jnp.arange(q.shape[1])[None, :]
    valid = jnp.arange(slot_idx.shape[1])[None, None, :] <= pos[:, :, None]
    return _grouped(q, k_pool, v_pool, slot_idx, valid, sm_scale)


def grouped_ring_decode_attention(q, k_pool, v_pool, ring_idx, positions,
                                  window, sm_scale):
    """One query token a sequence, ``q [B, H, D]`` at ``positions
    [B]``, against a layer whose pool keeps each sequence a RING:
    ``ring_idx [B, R]`` are the flat slots of the row's ring, and ring
    slot ``r`` holds the newest position ``p <= positions[b]`` with ``p
    % R == r`` (``serving/kvcache.py``; the current token's row is
    written before the call). A slot is read where that ``p`` exists
    and lies inside the window, ``positions[b] - window < p``: a ring
    is a block longer than the window, so its oldest rows are outside
    it. The shape is the ring's whatever the context. Returns ``[B, H,
    D]``."""
    valid = ring_valid(ring_idx.shape[1], positions, window)
    return _grouped(q[:, None], k_pool, v_pool, ring_idx, valid[:, None],
                    sm_scale)[:, 0]


def ring_valid(r, positions, window):
    """``[B, r]``: the slots of a ring of ``r`` that a query at
    ``positions [B]`` sees (:func:`grouped_ring_decode_attention` says
    what a slot holds)."""
    at = positions[:, None]
    held = at - (at - jnp.arange(r, dtype=at.dtype)[None, :]) % r
    return (held >= 0) & (at - held < window)


# ---------------------------------------------------------------------------
# differential attention (Diff Transformer, arXiv:2410.05258): heads in
# PAIRS. Query heads ``(2p, 2p + 1)`` are the two queries of pair ``p``,
# key heads ``(2r, 2r + 1)`` the two keys of key pair ``r`` and value
# heads ``(2r, 2r + 1)`` side by side its ONE value ``U_r`` (``2 D``
# wide); query pair ``p`` reads key pair ``p // (pairs / key pairs)``. A
# pair is two softmax maps over that one value,
#
#     o_p = rms(A1 U - lambda A2 U) * (1 - lambda_init)
#
# The calls below return BOTH maps' products (``[..., pairs, 2, 2 D]``:
# map 1, map 2) and :func:`diff_combine` makes ``o`` of them, so a cache
# backend chooses how the rows are read and the difference is written
# once.
# ---------------------------------------------------------------------------

def diff_rows_attention(q, k_rows, v_rows, valid, sm_scale, positions=None):
    """One query token a sequence, ``q [B, H, D]``, against rows that
    are already in position order: ``k_rows`` / ``v_rows [B, S, G x
    D]`` (the key/value heads side by side, as a pool row holds them),
    ``valid [B, S]`` the rows the query sees, or with ``positions [B]``
    the rows ``j <= positions[b]`` (``valid`` then ``None``; on a TPU
    the kernel ``hetu_diff_attn_decode`` runs,
    ``ops/pallas_diff_attention.py``, which reads a sequence's rows to
    its own position and not to the bucket's end). The rows are read AS THEY
    LIE, once for the scores and once for the products of every pair
    and both maps: each query head is laid out over a whole row, zeros
    but for its own key head's lanes, so the scores are ONE product of
    ``[H, G x D]`` queries with the rows' minor axis (no relayout of the
    rows into heads, which cost a transposed copy of them), and each
    map's product with ``U`` is one product with the ``v`` rows of which
    a head keeps its key pair's ``2 D`` lanes. The matrix unit multiplies
    zeros for it (``G`` times the useful work, on ``H`` rows: still
    under the rows' read time). Float32 softmax. Returns the two maps'
    products ``[B, H / 2, 2, 2 D]`` float32."""
    b, h, d = q.shape
    width = k_rows.shape[-1]
    key_pairs = width // (2 * d)
    per = h // 2 // key_pairs
    # head (r, p, m) reads key head (r, m): lanes [(2 r + m) D, + D)
    q = (q * sm_scale).astype(k_rows.dtype).reshape(b, key_pairs, per, 2, d)
    own = jnp.eye(key_pairs, dtype=q.dtype)[:, None, None, :, None, None] \
        * jnp.eye(2, dtype=q.dtype)[None, None, :, None, :, None]
    laid = (q[:, :, :, :, None, None, :] * own).reshape(b, h, width)
    if positions is not None and _diff_kernel(h, width, k_rows.shape[1]):
        from . import pallas_diff_attention as kernel
        wide = kernel.diff_decode(laid, k_rows, v_rows, positions)
    else:
        if valid is None:
            valid = jnp.arange(k_rows.shape[1])[None, :] \
                <= positions[:, None]
        scores = jnp.einsum("bhw,bsw->bhs", laid, k_rows,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(valid[:, None], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1)
        wide = jnp.einsum("bhs,bsw->bhw", probs.astype(v_rows.dtype),
                          v_rows, preferred_element_type=jnp.float32)
    # a head keeps the lanes of its key pair's value
    wide = wide.reshape(b, key_pairs, 2 * per, key_pairs, 2 * d)
    maps = jnp.sum(wide * jnp.eye(key_pairs, dtype=wide.dtype)[
        None, :, None, :, None], axis=3)
    return maps.reshape(b, h // 2, 2, 2 * d)


def _diff_kernel(heads, width, context):
    """Whether :func:`diff_rows_attention` by ``positions`` runs the
    kernel ``hetu_diff_attn_decode`` at these shapes."""
    from . import pallas_diff_attention as kernel
    return (_use_pallas() or kernel.INTERPRET) \
        and kernel.supported(heads, width, context)


def diff_rows_extent(heads, width, context, positions):
    """``[B]`` int32: how many of a sequence's rows, from the first,
    :func:`diff_rows_attention` by ``positions`` may multiply (the
    kernel's whole blocks up to the position; the composed form's
    products take every row of the bucket and select none away)."""
    if not _diff_kernel(heads, width, context):
        return jnp.full(positions.shape, context, jnp.int32)
    from .pallas_diff_attention import BLOCK_K
    block = min(BLOCK_K, context)
    return ((positions // block + 1) * block).astype(jnp.int32)


def diff_prefill_attention(q, k, v, sm_scale, window=None):
    """A whole prompt among its own tokens, token-major ``q [B, S, H,
    D]`` and ``k`` / ``v [B, S, G, D]``, causal, with ``window`` the
    band ``i - window < j <= i``. Each map is a head of the flash
    kernel: query and key padded with zeros to the value's ``2 D`` (the
    scores are the same; on a 128-wide matrix unit a contraction of 64
    costs what one of 128 does), each key pair's keys and ``U`` under
    the maps that read them; a banded call is the window kernel's
    (``hetu_flash_window``). Returns ``[B, S, H / 2, 2, 2 D]`` in
    ``q``'s dtype."""
    b, s, h, d = q.shape
    key_pairs = k.shape[2] // 2
    per = h // 2 // key_pairs
    pad = ((0, 0),) * 3 + ((0, d),)
    q = jnp.pad(q, pad)
    k = jnp.broadcast_to(
        jnp.pad(k, pad).reshape(b, s, key_pairs, 1, 2, 2 * d),
        (b, s, key_pairs, per, 2, 2 * d)).reshape(b, s, h, 2 * d)
    u = jnp.broadcast_to(
        v.reshape(b, s, key_pairs, 1, 2 * d),
        (b, s, key_pairs, 2 * per, 2 * d)).reshape(b, s, h, 2 * d)
    ctx = prefill_attention(
        *(t.transpose(0, 2, 1, 3) for t in (q, k, u)), sm_scale=sm_scale,
        causal=True, window=window)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, h // 2, 2, 2 * d)


def diff_combine(maps, lam, gain, out_scale, eps):
    """``o`` of the two maps' products ``[..., pairs, 2, 2 D]``:
    ``rms(map 1 - lam x map 2; gain [2 D], eps) x out_scale`` a pair,
    float32 (``lam`` and ``out_scale = 1 - lambda_init`` float32
    scalars of the layer). Returns ``[..., pairs x 2 D]`` float32."""
    maps = maps.astype(jnp.float32)
    x = maps[..., 0, :] - lam * maps[..., 1, :]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gain * out_scale).reshape(*x.shape[:-2], -1)


@functools.lru_cache(maxsize=None)
def _marker(name):
    """A jitted function called ``name`` that hands its arrays through
    ONE Pallas kernel unchanged: an event of that name in a profile."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        for src, dst in zip(refs[:len(refs) // 2], refs[len(refs) // 2:]):
            dst[...] = src[...]

    def marker(*arrays):
        from . import pallas_attention     # tests' and rehearsals' switch
        flat = [a.reshape(a.shape[0], -1) for a in arrays]
        out = pl.pallas_call(
            kernel, out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                               for a in flat],
            interpret=pallas_attention.INTERPRET)(*flat)
        return tuple(o.reshape(a.shape) for o, a in zip(out, arrays))

    marker.__name__ = marker.__qualname__ = name
    return jax.jit(marker)


def event_markers(name):
    """``(enter, leave)``: two functions that hand their arrays through
    a device event ``<name>_in`` / ``<name>_out`` (:func:`bracketed`
    says what lies between them and why), for a caller whose bracketed
    part is more than one call."""
    return _marker(name + "_in"), _marker(name + "_out")


def kernels_run():
    """Whether the Pallas kernels run here (a TPU backend, or a
    rehearsal that steers ``_use_pallas``): what a model asks to decide
    a static keyword of a program."""
    return _use_pallas()


def bracketed(name, attention, q, k_pool, v_pool, *rest, **static):
    """``attention(q, k_pool, v_pool, *rest, **static)`` between two
    device events ``<name>_in`` and ``<name>_out`` (a profile; TPU
    only). XLA inlines a composed function, jitted or not, and names its
    fusions for their roots, so a composed attention reaches no profile
    under a name of its own (found on the chip, PR 47): the query goes
    through a pass-through kernel of the first name on its way in, the
    context through one of the second on its way out, and since a core
    runs a program's operations one after another, what lies between
    the two events is the attention and whatever else XLA schedules
    there. Each kernel is also a fusion barrier, so a bracketed program
    is NOT the program an unprofiled engine runs: a caller brackets only
    where a profile is being taken (``PERF.md`` section 7 has the
    difference as measured)."""
    (q,) = _marker(name + "_in")(q)
    (out,) = _marker(name + "_out")(
        attention(q, k_pool, v_pool, *rest, **static))
    return out


# ---------------------------------------------------------------------------
# latent (MLA) attention — the three calls of models/latent_moe.py. A
# cache row is ``[c ; k_r]``: the normed latent and the one rotated key
# all heads share. A whole prompt attends EXPANDED (per-head keys and
# values rebuilt from the latent, the flash kernel); a decode step or a
# chunk behind a cached prefix attends ABSORBED, against the rows
# themselves: ``q~ = q_n W_k^T`` scores against ``c``, the context is
# ``softmax . c``, and the caller takes it through ``W_v``. The same
# numbers, and no per-head key or value is ever stored.
# ---------------------------------------------------------------------------

def mla_expanded_attention(q, k, v, sm_scale):
    """Causal attention among the tokens of this call, token-major
    ``q`` / ``k`` ``[B, S, H, Dq]`` and ``v`` ``[B, S, H, Dv]`` with
    ``Dv <= Dq`` (192 and 128 in the published models): the flash
    kernel takes one head size, so the values are padded to the keys'
    and the context cut back. Returns ``[B, S, H, Dv]``."""
    dq, dv = q.shape[-1], v.shape[-1]
    if dv < dq:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, dq - dv),))
    ctx = prefill_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                            sm_scale=sm_scale, causal=True)
    return ctx.transpose(0, 2, 1, 3)[..., :dv]


def _gather_latent_rows(pool, slot_idx):
    """One layer's latent rows at ``slot_idx`` ``[B, S]``, as ``[B, S,
    W]``. A sequence's slots are contiguous inside a block, so where
    the grid is whole blocks the gather moves blocks, not rows."""
    block_size, width = pool.shape[-2:]
    b, s = slot_idx.shape
    if pool.ndim == 3 and s % block_size == 0:
        blocks = slot_idx[:, ::block_size] // block_size
        return pool[blocks].reshape(b, s, width)
    return pool.reshape(-1, width)[slot_idx]


def gather_rows_once(groups, slot_idx, extent=None):
    """The rows at ``slot_idx [B, S]`` of several pools of ONE block
    table in position order, for readers that share the copy. ``groups``
    is a sequence of sequences of pools ``[blocks, block_size, W]``
    (the pools of a group of one shape: the same entry of several
    layers); returns one ``[len(group), B, S, W]`` array a group.
    ``extent [B]``: the rows of a sequence, from the first, that a
    reader may multiply. On a TPU the kernel ``hetu_block_gather``
    (``ops/pallas_block_gather.py``) copies a sequence's blocks up to
    its extent and leaves the rest of the bucket unwritten, WITHOUT A
    DEFINED VALUE; elsewhere, or where the grid is not whole blocks,
    XLA's gather of the whole bucket."""
    from . import pallas_block_gather as kernel
    first = groups[0][0]
    block_size = first.shape[-2]
    b, s = slot_idx.shape
    if (_use_pallas() or kernel.INTERPRET) and first.ndim == 3 \
            and s % block_size == 0:
        tables = slot_idx[:, ::block_size] // block_size
        counts = jnp.full((b,), s // block_size, jnp.int32) \
            if extent is None else -(-extent // block_size)
        return kernel.gather_blocks(groups, tables, counts)
    return [jnp.stack([_gather_latent_rows(pool, slot_idx)
                       for pool in group]) for group in groups]


def mla_decode_attention(q_abs, q_rope, pool, slot_idx, positions,
                         sm_scale):
    """One query token per sequence, absorbed, against a block-paged
    latent pool. ``q_abs`` ``[B, H, L]`` (the query through the key
    up-projection), ``q_rope`` ``[B, H, R]``; ``pool`` ``[num_blocks,
    block_size, L + R]``; ``slot_idx`` ``[B, S]`` and ``positions``
    ``[B]`` as :func:`paged_decode_attention` takes them. Returns the
    context in the latent space, ``[B, H, L]`` (float32 off the
    kernel, the queries' dtype on it)."""
    rows = _gather_latent_rows(pool, slot_idx)
    latent = q_abs.shape[-1]
    if _use_pallas():
        from . import pallas_mla
        if pallas_mla.supported(latent, q_rope.shape[-1], rows.shape[1]):
            q = jnp.concatenate([q_abs, q_rope], axis=-1).astype(rows.dtype)
            return pallas_mla.mla_decode(q, rows, positions, sm_scale,
                                         latent)
    c, k_r = rows[..., :latent], rows[..., latent:]
    scores = (jnp.einsum("bhl,bsl->bhs", q_abs, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_rope, k_r,
                           preferred_element_type=jnp.float32)) * sm_scale
    valid = jnp.arange(slot_idx.shape[1])[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(valid[:, None, :], scores, -1e9),
                           axis=-1)
    return jnp.einsum("bhs,bsl->bhl", probs.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)


def mla_prefill_attention(q_abs, q_rope, pool, slot_idx, starts, sm_scale):
    """A chunk of query tokens per sequence, absorbed: the
    suffix-prefill analogue of :func:`mla_decode_attention`. ``q_abs``
    ``[B, C, H, L]``, ``q_rope`` ``[B, C, H, R]``; chunk row ``i`` sees
    positions ``<= starts[b] + i``. The chunk's own rows must already
    be in the pool. Returns ``[B, C, H, L]`` float32."""
    rows = _gather_latent_rows(pool, slot_idx)
    latent = q_abs.shape[-1]
    c, k_r = rows[..., :latent], rows[..., latent:]
    scores = (jnp.einsum("bihl,bsl->bhis", q_abs, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bihr,bsr->bhis", q_rope, k_r,
                           preferred_element_type=jnp.float32)) * sm_scale
    pos = starts[:, None] + jnp.arange(q_abs.shape[1])[None, :]
    valid = jnp.arange(slot_idx.shape[1])[None, None, :] <= pos[:, :, None]
    probs = jax.nn.softmax(jnp.where(valid[:, None], scores, -1e9), axis=-1)
    return jnp.einsum("bhis,bsl->bihl", probs.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)


def _use_pallas():
    """The Pallas kernels run on a TPU backend, the composed reference
    everywhere else — decided by the platform alone. A kernel module
    that fails to import, lower or compile on the chip raises at its
    use; it is never traded for the reference."""
    return jax.default_backend() == "tpu"


def unpartitioned_tpu_step(ectx):
    """Whether an op may put a Pallas kernel of its own into the step
    it is traced in: ``_use_pallas()``, and no mesh of more than one
    device on the step's config — such a step is partitioned by GSPMD,
    which cannot split a Mosaic kernel, so the op keeps its composed
    form there."""
    mesh = getattr(getattr(ectx, "config", None), "mesh", None)
    return _use_pallas() and (mesh is None or mesh.size == 1)


def flash_layout(s, d, heads, token_major, ectx=None, kv_heads=None):
    """``(layout, reason)``: which operand form a flash call runs in,
    from what the code can see. ``"token_major"`` (the kernels read
    q, k and v out of the projections' own rows in both directions —
    :func:`fused_backward` — so nothing makes the trip through
    ``[B, H, S, D]``; ``reason`` None) where (a) the heads fill whole
    lane blocks and the rows whole lane tiles (``TokenMajor.fits``);
    (b) the step is not partitioned over a mesh
    (:func:`unpartitioned_tpu_step`); (c) the caller hands token-major
    operands. Else ``"head_major"`` and the first condition that
    failed: ``lanes``, ``mesh``, ``caller``. With fewer key/value
    heads than query heads (``kv_heads``) a head has to BE a lane
    block."""
    from .pallas_attention import TokenMajor
    mesh = getattr(getattr(ectx, "config", None), "mesh", None)
    for reason, holds in (
            ("lanes", TokenMajor(heads, d, kv_heads=kv_heads).fits(s)),
            ("mesh", mesh is None or mesh.size == 1),
            ("caller", token_major)):
        if not holds:
            return "head_major", reason
    return "token_major", None


def _seq_len(q, layout):
    """S of a q operand: ``[B, H, S, D]``, or rows ``[B, S, lanes]``
    under a ``TokenMajor`` layout."""
    return q.shape[-2] if layout is None else q.shape[1]


class FlashAttentionOp(Op):
    """Fused attention, in one of three operand forms. Head-major: q, k,
    v ``[B, H, S, D]``, the context ``[B, H, S, D]``. Token-major
    (``num_heads`` given), either a qkv projection's packed rows —
    ``q`` ``[B, S, 3H]``, ``k`` and ``v`` None, the one gradient
    ``[B, S, 3H]`` — or three projections' rows — q, k, v ``[B, S, H]``,
    three gradients ``[B, S, H]``, each its own projection's — and the
    context ``[B, S, H]``: where :func:`flash_layout` allows, the
    kernels read and write those rows as they lie and no transpose,
    split or merge runs around them; elsewhere the op makes the trip
    through ``[B, H, S, D]`` itself. The additive mask is
    ``[B, 1, 1, S]`` (or None) either way.

    Two things a decoder's three-projection call may add (causal, no
    mask). ``num_kv_heads`` < ``num_heads``: k and v are ``[B, S,
    num_kv_heads * D]``, query head ``h`` reads key/value head ``h //
    (num_heads // num_kv_heads)``, and dk / dv are the sums over a
    group's query heads. ``window``: a query at row ``i`` sees the keys
    ``i - window < j <= i`` alone, in both directions: the kernels
    leave out the tiles wholly behind the band
    (``pallas_attention.tile_walk``)."""

    def __init__(self, q, k=None, v=None, mask=None, sm_scale=1.0,
                 causal=False, num_heads=None, ctx=None, num_kv_heads=None,
                 window=None):
        if (k is None) != (v is None) or (k is None and not num_heads):
            raise ValueError("flash attention takes q, k, v [B, H, S, D], "
                             "or with num_heads their rows [B, S, H] or "
                             "packed qkv rows [B, S, 3H]")
        grouped = bool(num_kv_heads) and num_kv_heads != num_heads
        if (grouped or window is not None) and (
                k is None or not num_heads or not causal
                or mask is not None):
            raise ValueError(
                "num_kv_heads and window belong to a causal call over "
                "three projections' rows (num_heads given, no mask)")
        if grouped and num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not split over "
                             f"{num_kv_heads} key/value heads")
        self.num_kv_heads = num_kv_heads if grouped else None
        self.window = None if window is None else int(window)
        self.packed = k is None
        inputs = ([q] if self.packed else [q, k, v]) \
            + ([mask] if mask is not None else [])
        super().__init__(FlashAttentionOp, inputs, ctx)
        self.has_mask = mask is not None
        self.sm_scale = sm_scale
        self.causal = causal
        self.num_heads = num_heads

    def attention_shape(self, q_shape):
        """``(b, h, s, d)`` of the attention from the first input's
        shape, in any operand form (None where it is none of them)."""
        if self.num_heads and len(q_shape) == 3:
            b, s, width = (int(x) for x in q_shape)
            return b, self.num_heads, s, \
                width // ((3 if self.packed else 1) * self.num_heads)
        if not self.num_heads and len(q_shape) == 4:
            return tuple(int(x) for x in q_shape)
        return None

    def operands(self, input_vals, ectx):
        """``(q, k, v, mask, layout, reason)`` as the kernels or the
        reference take them: rows stay as they lie under a
        ``TokenMajor`` layout where the rule allows, and are split into
        ``[B, H, S, D]`` (layout None) where it does not."""
        mask = input_vals[-1] if self.has_mask else None
        if not self.num_heads:
            q, k, v = input_vals[:3]
            _, reason = flash_layout(q.shape[2], q.shape[3], q.shape[1],
                                     False, ectx)
            return q, k, v, mask, None, reason
        # packed: one array, read three times
        q, k, v = input_vals[:1] * 3 if self.packed else input_vals[:3]
        b, h, s, d = self.attention_shape(q.shape)
        form, reason = flash_layout(s, d, h, True, ectx, self.num_kv_heads)
        if form == "token_major" and _use_pallas():
            from .pallas_attention import TokenMajor
            if self.num_kv_heads:
                return q, k, v, mask, TokenMajor(
                    h, d, kv_heads=self.num_kv_heads), None
            return q, k, v, mask, (TokenMajor.packed if self.packed
                                   else TokenMajor)(h, d), None
        return *self._split(q, k, v), mask, None, reason

    def _split(self, q, k, v):
        """Token-major operands — packed rows ``[B, S, 3H]`` three
        times, or q, k, v rows ``[B, S, H]`` — -> q, k, v
        ``[B, H, S, D]``."""
        b, h, s, d = self.attention_shape(q.shape)
        if self.packed:
            return q.reshape(b, s, 3, h, d).transpose(2, 0, 3, 1, 4)
        # (grouped: k and v keep their own head count; ``_spread`` gives
        # each query head its group's)
        return [x.reshape(b, s, -1, d).transpose(0, 2, 1, 3)
                for x in (q, k, v)]

    def _spread(self, x):
        """Key/value heads ``[B, G, S, D]`` -> a copy a query head
        ``[B, H, S, D]`` (the form the head-major paths take; its vjp
        is the sum over a group)."""
        if not self.num_kv_heads:
            return x
        return jnp.repeat(x, self.num_heads // self.num_kv_heads, axis=1)

    def _band_mask(self, s):
        """The additive ``[1, 1, S, S]`` mask of a causal call off the
        kernels: the diagonal's, or with a window the band's."""
        from .pallas_attention import _band
        return jnp.where(_band(s, self.window), 0.0, -1e9)[None, None]

    def compute(self, input_vals, ectx):
        q, k, v, mask, layout, reason = self.operands(input_vals, ectx)
        o = self._attend(q, k, v, mask, layout, reason, ectx)
        if self.num_heads and layout is None:
            b, h, s, d = o.shape
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return o

    def _attend(self, q, k, v, mask, layout, reason, ectx):
        if layout is None:
            k, v = self._spread(k), self._spread(v)
        band = {} if self.window is None else {"window": self.window}
        if _use_pallas():
            # causal is a kernel flag; only the padding mask travels.
            # The logsumexp residual is stashed for the fused backward
            # (the grad op runs later in the same trace) — but only when
            # something will consume it: training, in a form and at a
            # length where the fused path engages (``fused_backward``).
            # Otherwise skip the residual write.
            from .pallas_attention import (flash_attention,
                                           flash_attention_with_lse)
            kw = dict(sm_scale=self.sm_scale, causal=self.causal,
                      layout=layout, reason=reason)
            if getattr(ectx, "training", False) and fused_backward(
                    _seq_len(q, layout), layout is not None):
                o, lse = flash_attention_with_lse(q, k, v, mask, **kw,
                                                  **band)
                if o is not None:
                    ectx.cache[("flash_res", self.id)] = (o, lse)
                    return o
            return flash_attention(q, k, v, mask, **kw, **band)
        if self.causal:
            cmask = self._band_mask(q.shape[-2])
            mask = cmask if mask is None else mask + cmask
        return attention_reference(q, k, v, mask, self.sm_scale)

    def gradient(self, output_grad):
        grads = [
            _FlashAttentionGradOp(self, output_grad, i, ctx=self.raw_ctx)
            for i in range(1 if self.packed else 3)]
        if self.has_mask:
            grads.append(None)
        return grads

    def infer_shape(self, input_shapes):
        if self.packed:
            b, s, width = input_shapes[0]
            return (b, s, width // 3)
        return input_shapes[0]


class _FlashAttentionGradOp(Op):
    """dq/dk/dv via jax.vjp over the fused forward — one op per operand so
    the graph stays an adjoint DAG (the reference packs/unpacks gradients
    the same way for BN/LN). A forward over packed rows has the one
    operand and the one gradient, ``[B, S, 3H]``; one over three
    projections' rows three gradients ``[B, S, H]``."""

    def __init__(self, forward_op, output_grad, which, ctx=None):
        super().__init__(_FlashAttentionGradOp,
                         list(forward_op.inputs) + [output_grad], ctx)
        self.forward_op = forward_op
        self.which = which
        self.attention_shape = forward_op.attention_shape

    def compute(self, input_vals, ectx):
        fwd = self.forward_op
        cache_key = ("flashattn_vjp", fwd.id)
        if cache_key not in ectx.cache:
            ectx.cache[cache_key] = self._grads(input_vals, ectx)
        return ectx.cache[cache_key][self.which]

    def _grads(self, input_vals, ectx):
        fwd = self.forward_op
        q, k, v, mask, layout, reason = fwd.operands(input_vals[:-1], ectx)
        dy = input_vals[-1]
        res = ectx.cache.get(("flash_res", fwd.id))
        if layout is not None and res is None:
            # gradients asked of a step that is not training: the
            # forward kept no residual, so the composed vjp, over heads
            q, k, v = fwd._split(q, k, v)
            layout = None
        if fwd.num_heads and layout is None:
            b, h, s, d = q.shape
            dy = dy.reshape(b, s, h, d).transpose(0, 2, 1, 3)
        if res is not None and fused_backward(_seq_len(q, layout),
                                              layout is not None):
            # fused Pallas backward: ONE kernel rebuilds each score
            # tile in VMEM from the forward's logsumexp and feeds dQ,
            # dK and dV from it — the S x S matrices never hit HBM on
            # the backward either (pallas_attention.py). Token-major
            # at any length: its programs take as many heads as the
            # forward's, and the rows need no trip through
            # ``[B, H, S, D]``, which the composed form can never
            # spare (BERT-base, S = 128: PERF.md PR 45). Head-major
            # below the threshold the composed vjp stays: a head a
            # program, the kernel is 3,072 grid steps a layer there
            # (PERF.md section 7 has both probes).
            from .pallas_attention import flash_attention_bwd
            o, lse = res
            band = {} if fwd.window is None else {"window": fwd.window}
            if layout is None and fwd.num_kv_heads:
                # head-major kernels take a copy a query head; a group's
                # dk / dv are the sums over its copies
                (k, v), spread = jax.vjp(
                    lambda k_, v_: (fwd._spread(k_), fwd._spread(v_)), k, v)
            grads = flash_attention_bwd(
                q, k, v, mask, o, lse, dy, sm_scale=fwd.sm_scale,
                causal=fwd.causal, layout=layout, reason=reason, **band)
            if layout is None and fwd.num_kv_heads:
                grads = (grads[0], *spread((grads[1], grads[2])))
        else:
            def f(q_, k_, v_):
                m = mask
                if fwd.causal:
                    cmask = fwd._band_mask(q_.shape[-2])
                    m = cmask if m is None else m + cmask
                return attention_reference(
                    q_, fwd._spread(k_), fwd._spread(v_), m, fwd.sm_scale)
            _, vjp = jax.vjp(f, q, k, v)
            grads = vjp(dy)
        if not fwd.num_heads:
            return grads
        if layout is None:      # back to the rows the operands came as
            b, _, s, d = q.shape
            grads = [g.transpose(0, 2, 1, 3).reshape(b, s, -1)
                     for g in grads]
        # the rows each projection's dW and dX matmuls read
        return (jnp.concatenate(grads, axis=-1),) if fwd.packed \
            else tuple(grads)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[self.which]


def flash_attention_op(q, k=None, v=None, mask=None, sm_scale=1.0,
                       causal=False, num_heads=None, ctx=None,
                       num_kv_heads=None, window=None):
    """Fused attention over q, k, v ``[B, H, S, D]``, or — ``num_heads``
    given — over their rows ``[B, S, H]`` or, k and v left out, over a
    qkv projection's packed rows ``[B, S, 3H]``; a causal call over
    three projections' rows may have fewer key/value heads
    (``num_kv_heads``) and a ``window``; see :class:`FlashAttentionOp`."""
    return FlashAttentionOp(q, k, v, mask, sm_scale, causal, num_heads,
                            ctx=ctx, num_kv_heads=num_kv_heads,
                            window=window)


# ---------------------------------------------------------------------------
# sequence parallelism (SURVEY §5 capability): ring attention as a graph op
# ---------------------------------------------------------------------------

def _sp_mesh(ectx):
    """The session mesh when it carries a sequence-parallel axis."""
    mesh = getattr(getattr(ectx, "config", None), "mesh", None)
    if mesh is not None and "sp" in mesh.axis_names:
        return mesh
    return None


class _SeqParallelAttentionOp(FlashAttentionOp):
    """Base for sequence-parallel attention ops: subclasses name the
    sharded implementation (parallel/ring.py or parallel/ulysses.py);
    compute/gradient plumbing — mesh detection, vjp-through-shard_map
    backward, fused-path fallback — lives here once.

    Falls back to the fused single-device path when the session mesh
    has no "sp" axis, so models declare sequence parallelism once and
    run anywhere. Causal (decoder) masking runs sharded too: the ring
    routes through the load-balanced zigzag schedule
    (parallel/ring.py), Ulysses applies the mask blockwise after its
    heads all-to-all (parallel/ulysses.py)."""

    _impl = None            # staticmethod (q, k, v, mesh, axis_name,
    _cache_prefix = None    #               sm_scale, mask, causal) -> out

    def _sharded(self, q, k, v, mask, mesh):
        return type(self)._impl(q, k, v, mesh, axis_name="sp",
                                sm_scale=self.sm_scale, mask=mask,
                                causal=self.causal)

    def compute(self, input_vals, ectx):
        mesh = _sp_mesh(ectx)
        if mesh is None:
            return super().compute(input_vals, ectx)
        q, k, v = input_vals[:3]
        mask = input_vals[3] if self.has_mask else None
        return self._sharded(q, k, v, mask, mesh)

    def gradient(self, output_grad):
        grads = [_SeqParallelAttentionGradOp(self, output_grad, i,
                                             ctx=self.raw_ctx)
                 for i in range(3)]
        if self.has_mask:
            grads.append(None)
        return grads


class _SeqParallelAttentionGradOp(_FlashAttentionGradOp):
    """dq/dk/dv through the sharded program itself (jax.vjp transposes
    the collectives — reverse ppermute rotation for the ring, mirrored
    all-to-alls for Ulysses), so the backward stays sequence-sharded."""

    def compute(self, input_vals, ectx):
        mesh = _sp_mesh(ectx)
        if mesh is None:
            return super().compute(input_vals, ectx)
        fwd = self.forward_op
        nin = 4 if fwd.has_mask else 3
        q, k, v = input_vals[:3]
        mask = input_vals[3] if fwd.has_mask else None
        dy = input_vals[nin]
        cache_key = (type(fwd)._cache_prefix, fwd.id)
        if cache_key not in ectx.cache:
            def f(q_, k_, v_):
                return fwd._sharded(q_, k_, v_, mask, mesh)
            _, vjp = jax.vjp(f, q, k, v)
            ectx.cache[cache_key] = vjp(dy)
        return ectx.cache[cache_key][self.which]


def _ring_impl(q, k, v, mesh, axis_name, sm_scale, mask, causal=False):
    from ..parallel.ring import ring_attention_sharded
    return ring_attention_sharded(q, k, v, mesh, axis_name=axis_name,
                                  sm_scale=sm_scale, mask=mask,
                                  causal=causal)


def _ulysses_impl(q, k, v, mesh, axis_name, sm_scale, mask, causal=False):
    from ..parallel.ulysses import ulysses_attention_sharded
    return ulysses_attention_sharded(q, k, v, mesh, axis_name=axis_name,
                                     sm_scale=sm_scale, mask=mask,
                                     causal=causal)


class RingAttentionOp(_SeqParallelAttentionOp):
    """Sequence-parallel attention over [B, H, S, D]: the sequence dim
    shards over the mesh's "sp" axis and K/V shards rotate around the
    ICI ring with online-softmax merging (parallel/ring.py). Forward AND
    backward run sharded — per-chip attention memory is O(S/n . D), the
    long-context scaling the reference lacks (SURVEY §5). ``causal=True``
    selects the load-balanced zigzag schedule."""

    _impl = staticmethod(_ring_impl)
    _cache_prefix = "ringattn_vjp"


class UlyssesAttentionOp(_SeqParallelAttentionOp):
    """Ulysses sequence parallelism: all-to-all swaps the sharded axis
    from sequence to heads, blocked full-sequence attention runs per
    head subset, a second all-to-all restores the sequence sharding
    (parallel/ulysses.py). Two collectives per attention vs the ring's
    n-1 ppermutes — prefer it when H >= n; needs H % n == 0."""

    _impl = staticmethod(_ulysses_impl)
    _cache_prefix = "ulyssesattn_vjp"


def ring_attention_op(q, k, v, mask=None, sm_scale=1.0, causal=False,
                      ctx=None):
    """Sequence-parallel (ring) attention; see RingAttentionOp."""
    return RingAttentionOp(q, k, v, mask, sm_scale, causal=causal, ctx=ctx)


def ulysses_attention_op(q, k, v, mask=None, sm_scale=1.0, causal=False,
                         ctx=None):
    """Sequence-parallel (Ulysses all-to-all) attention; see
    UlyssesAttentionOp."""
    return UlyssesAttentionOp(q, k, v, mask, sm_scale, causal=causal,
                              ctx=ctx)
