"""Pallas TPU flash-attention kernels (forward + fused backward).

No reference equivalent (the reference composes attention from cublas
batch-matmuls, examples/nlp/bert/hetu_bert.py:191-227). Both kernels
walk the (q-tile, k-tile) pairs of the score square in square REGIONS
(``_region_span``): a region's pairs are straight-line code, whose
independent chains the compiler interleaves (on this chip a loop
iteration is scheduled alone, so a chain walked by a loop pays its
matmul -> exp -> matmul latency every iteration), and the regions are
walked by a loop, so the code does not grow with S. With ``causal``
the walk follows the diagonal at any tile size: pairs wholly above it
are not even traced, and only the pairs it cuts carry the iota /
compare / select (``tile_walk``, the one function that says so for
both kernels and their tests). The [S, S] score matrix never exists in
HBM in either direction, so attention memory is O(S·D) instead of
O(S²) (the property training needs for long context).

A program's heads are ONE rule for both kernels
(``heads_per_program``): where a head is few tile pairs — one at
S = 128 — a program takes as many neighbouring heads as leave its
region within the bounds, each its own straight-line chains: all twelve
of a BERT-base batch row at S = 128 (256 programs a layer where a head
a program is 3,072 grid steps of 13 MFLOP each), one or two at S = 1024.

Forward: a program is one region ROW of its heads, whose whole K and V
stay resident in VMEM. Inside a region every q-tile meets its k-tiles
in ONE softmax step (all its score tiles, one row max, the exps, one
row sum, the context matmuls), so the running (max, sum, accumulator)
is rescaled once a region, and not at all where one region is the
whole head (the GPT-2 train cell: no loop, no running state, eight
chains a program). The scale goes onto q where that is exact
(``_scales_q``). It also emits the per-row logsumexp L when a backward
will read it.

Backward is the standard recompute form, in ONE kernel a call: the
kernel visits each pair once, rebuilds its score tile in VMEM from L
and feeds dV, dK and dQ from that one P / dS, every sum across tiles in
float32 and rounded once. The tile is built transposed
(``[block_k, block_q]``), so the row residuals L and D = rowsum(dO ∘ O)
(an XLA elementwise reduce outside the kernel; where one k-tile is a
head's whole row the kernel sums it itself, as rowsum(P ∘ dP) over the
tile's sublanes, and no pass over dO and O runs) travel as
``[B*H, 1, S]`` rows and every matmul of a pair is a plain one. A
program is a region row of K / V of its heads (head-major one head),
walking the q-regions from the diagonal down.

Operands come in one of two forms (``ops/attention.py:flash_layout``
picks by what the code can see). Head-major: q, k, v ``[B, H, S, D]``.
Token-major (:class:`TokenMajor`): ``[B, S, lanes]`` rows as a
projection writes them — one packed ``[B, S, 3H]`` array read three
times through three index maps, or the three ``[B, S, H]`` arrays of
three projections — in BOTH directions: a program owns whole lane
blocks of ``128 // D`` heads (one block at S = 1024, all six of a
BERT-base row at S = 128), split inside the kernel by static lane
windows; the context, dq, dk and dv leave as rows and the residuals as
``[B, H, 1, S]`` rows, so no transpose, split or merge runs around the
calls. The kernel bodies are shared (the head's lane window is a static
parameter) and the arithmetic a head is the same to the bit.

Two things a decoder's token-major call may add (causal, no padding
mask), in both directions. FEWER KEY/VALUE HEADS than query heads
(``TokenMajor.kv_heads``; a head a lane block): a program is one query
head whose k / v index maps name its group's block, so a group's rows
are read where they lie and nothing is broadcast in HBM; the backward
writes dk / dv a query head and sums a group's in float32 inside its
jit. A BAND (``window``): a query at row ``i`` sees the keys ``i -
window < j <= i``; both kernels walk the same tiles (``tile_walk``,
``_band_regions``), a tile wholly behind the band is neither loaded nor
computed and a tile either edge cuts is masked. Such calls are jitted
under names of their own (``_NAMED_FORWARD`` / ``_NAMED_BACKWARD``), so
a profile tells them from the equal-heads calls.

Block sizes are ONE static rule in what a call can see
(``_block_sizes``: kind, S, D, causal, mask), filled from the chip at
the shapes the benchmark's cells run; with ``causal`` the tiles also
decide how much of the square is skipped (a tile 1024 long on either
side of S=1024 is cut by the diagonal everywhere), and the two
directions weigh that differently. Batch and heads only size the
embarrassingly parallel grid axis and are no input of the rule.
"""
from __future__ import annotations

import functools
import math
import types
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_bwd", "TokenMajor"]

NEG_INF = -1e30
LANES = 128      # TPU minor-dim tile: the forward writes lse lane-tiled
_MOST_VMEM = 100 * 1024 * 1024


def _forward_compiler_params(s, span, block_q, block_k, lanes, heads,
                             itemsize):
    """The VMEM a forward program may use: its K and V, its region row
    of q and of the context (``lanes`` wide over all its ``heads``,
    double-buffered), and a region's float32 temporaries — every
    chain's score tiles, their exps and the running accumulators, which
    the compiler may hold all at once. At least the 16 MiB a kernel
    gets unasked (S = 2048 at D = 192 passed them by 0.36 MB)."""
    resident = 2 * 2 * (s + span) * lanes * itemsize
    tiles = 3 * 4 * heads * span * max(span, block_q, block_k) \
        + 3 * 4 * span * lanes
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(_MOST_VMEM,
                                 max(16 * 1024 * 1024,
                                     2 * (resident + tiles)))))


class TokenMajor(NamedTuple):
    """The operand form of a call whose q, k and v lie as a projection
    wrote them: ``[B, S, lanes]`` rows, a row's heads side by side. A
    grid step then owns whole lane BLOCKS of a batch row — a block
    ``width`` = ``max(head_dim, 128)`` lanes, ``per_block`` heads; how
    many blocks is ``heads_per_program``'s to say — and the kernels
    split them by static lane windows. ``tiles`` are the lane-block
    offsets of q, k and v in their arrays, so ONE packed ``[B, S, 3H]``
    array (a qkv projection's rows) may be passed three times, and
    three projections' ``[B, S, H]`` arrays as they are; the
    context, dq, dk and dv are ``[B, S, H]`` and the row residuals
    ``[B, heads, 1, S]``, the rows the backward reads (no reshape lies
    between the two kernels: XLA would copy one into other tiles).
    Hashable: a static argument of the jits.

    ``kv_heads`` (grouped-query attention; causal calls without a
    padding mask): k and v are ``[B, S, kv_heads * head_dim]``, query
    head ``h`` reads key/value head ``h // (heads // kv_heads)`` — the
    k / v index maps alone say so, nothing is broadcast in HBM — and a
    program is ONE head (a head a lane block: ``head_dim`` whole lane
    tiles). The backward writes dk and dv a QUERY head and sums a
    group's inside its jit."""
    heads: int
    head_dim: int
    tiles: tuple = (0, 0, 0)
    kv_heads: int | None = None

    @property
    def group(self):
        """Query heads a key/value head (1: as many of both)."""
        return self.heads // (self.kv_heads or self.heads)

    @property
    def width(self):
        return max(self.head_dim, LANES)

    @property
    def per_block(self):
        return self.width // self.head_dim

    @property
    def blocks(self):
        return self.heads * self.head_dim // self.width

    @classmethod
    def packed(cls, heads, head_dim):
        """q, k and v as the thirds of one ``[B, S, 3H]`` array."""
        n = cls(heads, head_dim).blocks
        return cls(heads, head_dim, (0, n, 2 * n))

    def fits(self, s):
        """Heads fill whole lane blocks, and the residual rows whole
        lane tiles; grouped, a head IS a lane block."""
        d, h = self.head_dim, self.heads * self.head_dim
        if self.group > 1 and d % LANES:
            return False
        return s % LANES == 0 and (
            d % LANES == 0 or (LANES % d == 0 and h % LANES == 0))


def _lane_windows(ref, head_dim):
    """The static lane windows of the heads a block holds: the whole
    block head-major (``head_dim`` None), else one window a head."""
    if head_dim is None:
        return [slice(None)]
    return [slice(i * head_dim, (i + 1) * head_dim)
            for i in range(ref.shape[-1] // head_dim)]


def _scales_q(sm_scale, dtype):
    """Whether ``sm_scale`` goes onto q (``[rows, D]``, once a program)
    and not onto every score tile: where that loses nothing in the
    operand dtype — a power of two is exact, and a float32 q trades the
    score's one rounding for one. A bf16 q times 1/sqrt(192), rounded
    before the dot, would be a lower precision than the caller states."""
    return jnp.dtype(dtype) == jnp.float32 \
        or math.frexp(sm_scale)[0] == 0.5


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, l_ref, *, sm_scale,
                block_q, block_k, span, seq_len, causal, head_dim=None,
                window=None):
    """One region row's program: the q rows ``[qi*span, (qi+1)*span)``
    of the program's heads (``heads_per_program``) — head-major a block
    of neighbouring heads, token-major the heads of its lane blocks,
    each its static lane window — against their whole K and V, resident
    in VMEM.
    The square is walked in REGIONS of ``span`` rows a side, as the
    backward walks it (``_region_span``): the regions left of the
    diagonal by a loop (every pair, no mask), the one ON it last, where
    ``tile_walk`` says while tracing which pairs are left out and which
    carry the iota / compare / select; regions right of it are not run.
    Where one region is the whole head (``span == seq_len``) there is no
    loop and no running state at all.

    Inside a region a q-tile meets its k-tiles in ONE softmax step: all
    its score tiles, one row max over them, the exps, one row sum, the
    context matmuls, and one rescale of the running (max, sum,
    accumulator) a region where there is one. The q-tiles of a head and
    the heads of a program are independent chains laid out as
    straight-line code, so the compiler fills one chain's matmul
    latency with another's elementwise work (a loop iteration is
    scheduled alone on this chip: PERF.md PRs 36, 40).

    With a ``window`` (causal only) a query at row ``i`` sees the keys
    ``i - window < j <= i``: the regions wholly behind the band are not
    run, the one or two its far edge cuts are run first, each at its
    static distance from the diagonal (``_band_regions``; skipped by a
    branch in the region rows that start before them), the whole ones
    between by the loop, the diagonal's last; ``tile_walk`` says for an
    edge region, as for the diagonal's, which pairs are left out and
    which carry which compare.

    Dots run in the INPUT dtype with f32 accumulation — on bf16 inputs
    the MXU's native mode; all softmax math stays f32."""
    heads = [(g, lanes) for g in range(q_ref.shape[0])
             for lanes in _lane_windows(q_ref, head_dim)]
    num_q, num_k = span // block_q, span // block_k
    qi = pl.program_id(1 if head_dim is None else 2)
    whole = span == seq_len
    nt = (((1,), (1,)), ((), ()))             # a @ b^T
    nn = (((1,), (0,)), ((), ()))
    on_q = _scales_q(sm_scale, q_ref.dtype)
    rows = [slice(i * block_q, (i + 1) * block_q) for i in range(num_q)]
    qs = [[q_ref[g, r, lanes] * sm_scale if on_q else q_ref[g, r, lanes]
           for r in rows] for g, lanes in heads]
    # row - column of a score tile; a pair the diagonal cuts keeps the
    # entries at or under its own offset
    below = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
        if causal else None

    def region(kr, state, diagonal, offset=0):
        """The region at k-region ``kr`` (a static 0, or traced) for
        every chain of the program; ``state`` None where nothing ran
        before it. ``offset``: the rows its q rows lie below its keys'
        diagonal (a region the window's far edge cuts). Returns the new
        state, ``[head][q-tile]``."""
        visited, masked = tile_walk(span, block_q, block_k, diagonal,
                                    window if diagonal else None, offset)
        keys = [slice(j * block_k, (j + 1) * block_k) if whole else
                pl.ds(pl.multiple_of(kr * span + j * block_k, block_k),
                      block_k) for j in range(num_k)]
        bias = None if mask_ref is None else \
            [mask_ref[0, 0, ks][None, :] for ks in keys]
        keep = {}
        out = []
        for n, (g, lanes) in enumerate(heads):
            ks = [k_ref[g, x, lanes] for x in keys]
            vs = [v_ref[g, x, lanes] for x in keys]
            chains = []
            for i in range(num_q):
                mine = [j for ii, j in visited if ii == i]
                if not mine:    # an edge region leaves this q-tile out
                    chains.append(state[n][i])
                    continue
                scores = []
                for j in mine:
                    s = jax.lax.dot_general(
                        qs[n][i], ks[j], nt,
                        preferred_element_type=jnp.float32)
                    if not on_q:
                        s = s * sm_scale
                    if bias is not None:
                        s = s + bias[j]
                    if (i, j) in masked:
                        at = j * block_k - i * block_q - offset
                        if window is None:
                            cuts = ("diagonal",)
                        else:
                            cuts = _band_cuts(i, j, block_q, block_k,
                                              offset, window)
                        if (at, cuts) not in keep:
                            kept = None
                            if "diagonal" in cuts:
                                kept = below >= at
                            if "edge" in cuts:
                                edge = below < at + window
                                kept = edge if kept is None \
                                    else kept & edge
                            keep[at, cuts] = kept
                        s = jnp.where(keep[at, cuts], s, NEG_INF)
                    scores.append(s)
                m = jnp.max(functools.reduce(jnp.maximum, scores),
                            axis=1, keepdims=True)
                if state is not None:
                    m_prev, l_prev, acc_prev = state[n][i]
                    m = jnp.maximum(m_prev, m)
                ps = [jnp.exp(s - m) for s in scores]
                l = jnp.sum(functools.reduce(jnp.add, ps), axis=1,
                            keepdims=True)
                acc = functools.reduce(jnp.add, [
                    jax.lax.dot_general(
                        p.astype(vs[j].dtype), vs[j], nn,
                        preferred_element_type=jnp.float32)
                    for p, j in zip(ps, mine)])
                if state is not None:
                    alpha = jnp.exp(m_prev - m)
                    l, acc = l_prev * alpha + l, acc_prev * alpha + acc
                chains.append((m, l, acc))
            out.append(chains)
        return out

    if whole:
        done = region(0, None, diagonal=causal)
    else:
        d = qs[0][0].shape[-1]
        start = [[(jnp.full((block_q, 1), NEG_INF, jnp.float32),
                   jnp.zeros((block_q, 1), jnp.float32),
                   jnp.zeros((block_q, d), jnp.float32))] * num_q
                 for _ in heads]
        first = 0
        if window is not None:
            whole_behind, edges = _band_regions(seq_len, span, window)
            for e in edges:
                start = jax.lax.cond(
                    qi >= e,
                    lambda state, e=e: region(qi - e, state, True,
                                              e * span),
                    lambda state: state, start)
            first = jnp.maximum(qi - whole_behind, 0)
        done = jax.lax.fori_loop(
            first, qi if causal else seq_len // span,
            lambda kr, state: region(kr, state, diagonal=False), start)
        if causal:
            done = region(qi, done, diagonal=True)
    # a program several lane blocks wide lays its heads' logsumexp
    # columns side by side and transposes them ONCE a q-tile (a
    # transpose a head is the longest thing such a short head does)
    gathered = l_ref is not None and head_dim is not None \
        and q_ref.shape[-1] > max(head_dim, LANES)
    for n, (g, lanes) in enumerate(heads):
        for r, (m, l, acc) in zip(rows, done[n]):
            o_ref[g, r, lanes] = (acc * (1.0 / l)).astype(o_ref.dtype)
            if l_ref is None or gathered:
                continue
            # per-row logsumexp, the backward's softmax residual
            lse = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))
            if head_dim is None:
                # head-major: lane-tiled [rows, 128] (TPU blocks need
                # 128-lane minors), read back at lane 0
                l_ref[g, r, :] = lse
            else:
                # token-major: the row the backward reads, [1, rows]
                l_ref[0, n, :, r] = lse.T[0:1]
    if gathered:
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, LANES), 1)
        for i, r in enumerate(rows):
            tile = jnp.zeros((block_q, LANES), jnp.float32)
            for n in range(len(heads)):
                m, l, _ = done[n][i]
                tile = jnp.where(lane == n, m + jnp.log(l), tile)
            tile = tile.T                       # a head a sublane
            for n in range(len(heads)):
                l_ref[0, n, :, r] = tile[n:n + 1]


def _largest_tile(seq_len, most):
    """``most`` halved until it divides S (at least 8)."""
    tile = min(most, seq_len)
    while seq_len % tile:
        tile //= 2
    return max(tile, 8)


def _block_sizes(seq_len, head_dim, kind="fwd", causal=False,
                 has_mask=False):
    """``(block_q, block_k)`` of a flash call (``kind`` one of ``fwd`` /
    ``fwd_lse`` / ``bwd``) from what the call can see: the ONE place
    tiles are decided. Nothing is measured while tracing and nothing is
    stored, so every process of a tree runs the same programs.

    Causal and unmasked — every call of the benchmark's cells that has
    a choice, read there on the chip (PERF.md section 6, PR 46): 256
    keys a tile; 256 rows too in the backward (1.17 ms a GPT-2 layer,
    1.24 at the next candidate) and for heads of a whole lane block or
    more (D = 192 at S = 8,192: 17.6 ms, 17.8 at (512, 512), 18.4 at
    (512, 256)); 512 rows in the forward where a head is half a lane
    block (D = 64: the GPT-2 train cell's forward with ``lse`` reads
    16.26 ms a step there, 16.29 at (512, 128), 16.44 at (512, 512)).
    What no cell runs (no diagonal, or a padding mask beside it) was
    never read on the chip and keeps the tiles it always had:
    bq <= 256, bk <= 512."""
    block_q, block_k = 256, 512
    if causal and not has_mask:
        block_k = 256
        if kind != "bwd" and head_dim <= 64:
            block_q = 512
    return _largest_tile(seq_len, block_q), _largest_tile(seq_len, block_k)


def _supported(s, d, block_q, block_k):
    # the grid covers s // block only when s divides evenly; max(bq, 8)
    # can break that for s % 8 != 0 (e.g. s=260), which would leave tail
    # rows unwritten — callers fall back to the composed reference
    return not (s < 8 or d % 8 or s % block_q or s % block_k)


# what this process's calls resolved, {(kind, token-major, S, D, dtype,
# causal, has_mask): (block_q, block_k)}: the one reader is the
# benchmark's ``flash_tiles`` log line (``tune/autotune.py:get_table``)
RESOLVED_TILES = {}


def _plan(kind, q, mask, causal, interpret, layout, reason):
    """What the three entries share: ``(interpret, blocks)`` of a call —
    blocks None where the kernel does not take the shape — and the
    ``flash_layout`` instant, once a traced call: the operand form the
    call runs in and, head-major, the first of the rule's conditions
    that kept it there (``ops/attention.py:flash_layout``; a caller that
    hands ``[B, H, S, D]`` operands and no reason is its own reason)."""
    if interpret is None:
        interpret = INTERPRET
    _, _, s, d = _dims(q, layout)
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "flash_layout", kernel=kind, seq=s, head_dim=d,
        layout="head_major" if layout is None else "token_major",
        heads_per_block=1 if layout is None else layout.per_block,
        **({"reason": reason or "caller"} if layout is None else {}))
    blocks = _block_sizes(s, d, kind, causal, mask is not None)
    if not _supported(s, d, *blocks):
        return interpret, None
    RESOLVED_TILES[(kind, layout is not None, s, d, jnp.dtype(q.dtype).name,
                    bool(causal), mask is not None)] = blocks
    return interpret, blocks


def _form(layout):
    """The trailing argument of a jit call: a head-major call is made
    with the arguments it always had."""
    return () if layout is None else (layout,)


def _forward(kind, q, k, v, mask, sm_scale, causal, interpret, layout,
             reason, window=None):
    """What the two forward entries share: the plan, the call, and the
    ``flash_fwd_walk`` instant, once a traced call — how far the walk
    engages at the tiles chosen (``fwd_walk_counts``). None where the
    kernel does not take the shape."""
    interpret, blocks = _plan(kind, q, mask, causal, interpret, layout,
                              reason)
    if blocks is None:
        return None
    _, h, s, d = _dims(q, layout)
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "flash_fwd_walk", seq=s, head_dim=d, block_q=blocks[0],
        block_k=blocks[1], causal=bool(causal),
        **fwd_walk_counts(h, s, *blocks, causal, layout, window),
        **({} if window is None else {"window": int(window)}))
    grouped = layout is not None and layout.group > 1
    if window is None and not grouped:
        return _flash_attention_jit(q, k, v, mask, sm_scale, causal,
                                    interpret, *blocks, kind == "fwd_lse",
                                    *_form(layout))
    if not causal or mask is not None:
        raise ValueError("a window is a band under the diagonal, and "
                         "grouped key/value heads are a decoder's: "
                         "causal=True and no padding mask")
    return _NAMED_FORWARD[grouped, window is not None](
        q, k, v, sm_scale, interpret, *blocks, kind == "fwd_lse", layout,
        None if window is None else int(window))


def flash_attention(q, k, v, mask=None, sm_scale=1.0, causal=False,
                    interpret=None, layout=None, reason=None, window=None):
    """softmax(q k^T * sm_scale + mask) v over [B, H, S, D], or with a
    :class:`TokenMajor` ``layout`` over ``[B, S, lanes]`` rows (the
    context then ``[B, S, H]``; the caller has checked
    ``layout.fits``).

    ``mask`` is an additive *padding* mask broadcastable to [B, 1, 1, S]
    (the BERT layout); causal masking is a kernel flag, not a mask
    argument. Tiny or oddly-shaped inputs fall back to the composed-XLA
    reference rather than violating TPU tiling constraints.

    ``window`` (with ``causal``, no mask): a query at row ``i`` sees the
    keys ``i - window < j <= i`` alone; the walk leaves out what lies
    wholly behind the band (``tile_walk``), and the call's device events
    are ``hetu_flash_window``'s.
    """
    out = _forward("fwd", q, k, v, mask, sm_scale, causal, interpret,
                   layout, reason, window)
    if out is None:
        from .attention import attention_reference
        s = q.shape[-2]
        m = mask
        if causal:
            cmask = jnp.where(_band(s, window), 0.0, NEG_INF)[None, None]
            m = cmask if m is None else m + cmask
        return attention_reference(q, k, v, m, sm_scale)
    return out


def _band(s, window=None):
    """``[s, s]`` bool: the keys a causal row sees, all ``j <= i`` or
    with a ``window`` those ``i - window < j <= i``."""
    seen = jnp.tril(jnp.ones((s, s), bool))
    if window is None:
        return seen
    return seen & ~jnp.tril(jnp.ones((s, s), bool), -int(window))


def flash_attention_with_lse(q, k, v, mask=None, sm_scale=1.0,
                             causal=False, interpret=None, layout=None,
                             reason=None, window=None):
    """(output, logsumexp [B, H, S]; token-major [B, H, 1, S]) — the
    pair the fused backward needs.
    Returns (None, None) on shapes the kernel does not support; callers
    then take the composed path for both directions."""
    out = _forward("fwd_lse", q, k, v, mask, sm_scale, causal, interpret,
                   layout, reason, window)
    return (None, None) if out is None else out


# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def _mask_rows(mask, b, h, s):
    """[B, 1, 1, S]-broadcastable additive mask -> [B, 1, S] rows."""
    return jnp.broadcast_to(mask, (b, 1, 1, s)).reshape(
        b, 1, s).astype(jnp.float32)


def _dims(q, layout):
    """(b, h, s, d) of a call from its q operand and operand form."""
    if layout is None:
        return q.shape
    return q.shape[0], layout.heads, q.shape[1], layout.head_dim


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal",
                                             "interpret", "block_q",
                                             "block_k", "need_lse",
                                             "layout"))
def _flash_attention_jit(q, k, v, mask, sm_scale, causal, interpret,
                         block_q, block_k, need_lse, layout=None):
    return _flash_forward(q, k, v, mask, sm_scale, causal, interpret,
                          block_q, block_k, need_lse, layout)


# a windowed call's events carry this function's name in a profile, as
# the others carry ``_flash_attention_jit``
def hetu_flash_window(q, k, v, sm_scale, interpret, block_q, block_k,
                      need_lse, layout, window):
    return _flash_forward(q, k, v, None, sm_scale, True, interpret,
                          block_q, block_k, need_lse, layout, window)


def _named(name, fn, static):
    """``fn`` jitted under ``name``: a program's instructions, and so a
    profile's events, are named for the innermost jitted function (a
    copy of the function, so the static names still find their
    positions)."""
    named = types.FunctionType(fn.__code__, fn.__globals__, name,
                               fn.__defaults__, fn.__closure__)
    named.__qualname__ = name
    return jax.jit(named, static_argnames=static)


_WINDOW_STATIC = ("sm_scale", "interpret", "block_q", "block_k", "need_lse",
                  "layout", "window")
_flash_attention_window_jit = jax.jit(hetu_flash_window,
                                      static_argnames=_WINDOW_STATIC)
# (grouped key/value heads, a band) -> the forward under its event name
_NAMED_FORWARD = {
    (False, True): _flash_attention_window_jit,
    (True, False): _named("hetu_flash_gqa_fwd", hetu_flash_window,
                          _WINDOW_STATIC),
    (True, True): _named("hetu_flash_gqa_window_fwd", hetu_flash_window,
                         _WINDOW_STATIC)}


def _flash_forward(q, k, v, mask, sm_scale, causal, interpret,
                   block_q, block_k, need_lse, layout=None, window=None):
    """``layout`` None: q, k, v ``[B, H, S, D]``, a grid step a region
    row of a block of neighbouring heads. A :class:`TokenMajor`:
    ``[B, S, lanes]`` rows, a grid step a region row of the lane blocks
    that hold its heads (``heads_per_program``); the same kernel body
    either way, and the same event name in a device trace."""
    b, h, s, d = _dims(q, layout)
    span = _region_span(s, block_q, block_k)
    group = heads_per_program(h, s, block_q, block_k, layout)
    if layout is None:  # jit-ok: static argname
        grid = (b * h // group, s // span)
        args = [x.reshape(b * h, s, d) for x in (q, k, v)]
        rows = lambda p, qi: (p, qi, 0)               # noqa: E731
        whole = lambda p, qi: (p, 0, 0)               # noqa: E731
        in_specs = [pl.BlockSpec((group, span, d), rows),
                    pl.BlockSpec((group, s, d), whole),
                    pl.BlockSpec((group, s, d), whole)]
        # a block's heads share a batch row (``group`` divides h)
        mask_spec = pl.BlockSpec(
            (1, 1, s), lambda p, qi, _n=h // group: (p // _n, 0, 0))
        o_shape = jax.ShapeDtypeStruct((b * h, s, d), q.dtype)
        o_spec = pl.BlockSpec((group, span, d), rows)
        l_shape = jax.ShapeDtypeStruct((b * h, s, LANES), jnp.float32)
        l_spec = pl.BlockSpec((group, span, LANES), rows)
        lanes = group * -(-d // LANES) * LANES
    else:
        # a program's block: ``wide`` lane blocks side by side, so the
        # offsets of q, k and v count in blocks that wide
        wide = group // layout.per_block
        lanes = wide * layout.width
        tq, tk, tv = (t // wide for t in layout.tiles)
        grid = (b, layout.blocks // wide, s // span)
        args = [q, k, v]
        in_specs = [
            pl.BlockSpec((1, span, lanes),
                         lambda bi, p, qi: (bi, qi, tq + p)),
            pl.BlockSpec((1, s, lanes), lambda bi, p, qi: (bi, 0, tk + p)),
            pl.BlockSpec((1, s, lanes), lambda bi, p, qi: (bi, 0, tv + p))]
        if layout.group > 1:  # jit-ok: static argname
            # a group's query heads (one a program) read ONE k / v block
            in_specs[1:] = [
                pl.BlockSpec((1, s, lanes), lambda bi, p, qi, t=t:
                             (bi, 0, t + p // layout.group))
                for t in (tk, tv)]
        mask_spec = pl.BlockSpec((1, 1, s), lambda bi, p, qi: (bi, 0, 0))
        o_shape = jax.ShapeDtypeStruct((b, s, h * d), q.dtype)
        o_spec = pl.BlockSpec((1, span, lanes),
                              lambda bi, p, qi: (bi, qi, p))
        # the residual leaves as the rows the backward takes
        l_shape = jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)
        l_spec = pl.BlockSpec((1, group, 1, span),
                              lambda bi, p, qi: (bi, p, 0, qi))
    body = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k, span=span,
                             seq_len=s, causal=causal,
                             head_dim=None if layout is None else d,
                             **({} if window is None
                                else {"window": window}))
    if mask is not None:  # jit-ok: structural None-check, not a traced read
        in_specs.append(mask_spec)
        args.append(_mask_rows(mask, b, h, s))
        if need_lse:  # jit-ok: static argname
            kernel = body
        else:
            def kernel(q_ref, k_ref, v_ref, mask_ref, o_ref):
                body(q_ref, k_ref, v_ref, mask_ref, o_ref, None)
    else:
        if need_lse:  # jit-ok: static argname
            def kernel(q_ref, k_ref, v_ref, o_ref, l_ref):
                body(q_ref, k_ref, v_ref, None, o_ref, l_ref)
        else:
            def kernel(q_ref, k_ref, v_ref, o_ref):
                body(q_ref, k_ref, v_ref, None, o_ref, None)

    params = _forward_compiler_params(s, span, block_q, block_k, lanes,
                                      group, q.dtype.itemsize)
    if need_lse:  # jit-ok: static argname
        # the lse residual is emitted only when a consumer exists (the
        # fused backward); the inference/serving forward skips the write
        out, lse = pl.pallas_call(
            kernel,
            out_shape=[o_shape, l_shape],
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec, l_spec],
            compiler_params=params, interpret=interpret,
        )(*args)
        if layout is None:  # jit-ok: static argname
            return out.reshape(b, h, s, d), lse[:, :, 0].reshape(b, h, s)
        return out, lse
    out = pl.pallas_call(
        kernel,
        out_shape=o_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        compiler_params=params, interpret=interpret,
    )(*args)
    return out if layout is not None else out.reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# fused backward (recompute form, one pass over the tile pairs)
# ---------------------------------------------------------------------------

def _first_q_tile(kj, block_q, block_k):
    """Causal: the first q-tile holding a row at or below k-tile
    ``kj``'s first key; every q-tile before it lies wholly above the
    diagonal and is never run. Python ints or traced ints alike."""
    return (kj * block_k) // block_q


def _first_unmasked_q_tile(kj, block_q, block_k):
    """Causal: the first q-tile whose first row is at or below k-tile
    ``kj``'s LAST key (not clipped to the tile count); from it on the
    diagonal cuts nothing and the tile body carries no mask."""
    return ((kj + 1) * block_k + block_q - 2) // block_q


def _band_cuts(i, j, block_q, block_k, offset, window):
    """Which of the band's two edges cut the pair (q-tile ``i``, k-tile
    ``j``) of a square whose q rows lie ``offset`` rows below its keys'
    diagonal: ``"diagonal"`` (some key of the pair is ahead of some
    row), ``"edge"`` (some key is ``window`` or more behind some row).
    None where the pair lies wholly outside the band."""
    nearest = i * block_q + offset - (j + 1) * block_k + 1
    farthest = (i + 1) * block_q - 1 + offset - j * block_k
    if farthest < 0 or nearest >= window:
        return None
    return (("diagonal",) if nearest < 0 else ()) \
        + (("edge",) if farthest >= window else ())


def _band_regions(s, span, window):
    """How a region row of the forward meets the band behind its
    diagonal region, in regions of ``span``: ``(the regions next to the
    diagonal's that lie wholly inside the band, the distances of those
    its far edge cuts)``. A region ``e`` behind holds the differences
    ``(e - 1) span < i - j < (e + 1) span``."""
    whole = max(window // span - 1, 0)
    edges = [e for e in range(whole + 1, s // span)
             if (e - 1) * span + 1 < window]
    return whole, edges


def tile_walk(s, block_q, block_k, causal, window=None, offset=0):
    """The (q-tile, k-tile) pairs a flash kernel runs over ``s`` rows a
    side, and of them those that carry the causal iota / compare /
    select — from the two bounds above, which the backward kernel leaves
    pairs out by and masks by, and which the forward reads through this
    function for the region on the diagonal (the regions beside it hold
    only pairs these bounds keep and do not mask). The full square,
    nothing masked, without ``causal``. With a ``window`` (the forward
    alone) a row sees the keys ``0 <= i - j < window``: the pairs
    wholly behind the band are left out as those above the diagonal
    are, and the pairs either edge cuts are masked; ``offset`` is how
    far the square's rows lie below its keys' diagonal (a region the
    band's far edge cuts)."""
    num_qb, num_kb = s // block_q, s // block_k
    visited, masked = [], []
    if window is not None:      # under the diagonal: ``_forward`` checks
        for kj in range(num_kb):
            for i in range(num_qb):
                cuts = _band_cuts(i, kj, block_q, block_k, offset, window)
                if cuts is not None:
                    visited.append((i, kj))
                    if cuts:
                        masked.append((i, kj))
        return visited, masked
    for kj in range(num_kb):
        first, unmasked = 0, 0
        if causal:
            first = _first_q_tile(kj, block_q, block_k)
            unmasked = min(num_qb,
                           _first_unmasked_q_tile(kj, block_q, block_k))
        visited += [(i, kj) for i in range(first, num_qb)]
        masked += [(i, kj) for i in range(first, unmasked)]
    return visited, masked


def tile_walk_counts(s, block_q, block_k, causal, window=None):
    """How far the tile walk engages at these tiles: tiles visited, tiles
    of the square, masked tiles, and the two shares a trace reader wants
    (visited / square, masked / visited)."""
    visited, masked = tile_walk(s, block_q, block_k, causal, window)
    square = (s // block_q) * (s // block_k)
    return {"tiles_visited": len(visited), "tiles_square": square,
            "tiles_masked": len(masked),
            "visited_share": round(len(visited) / square, 4),
            "masked_share": round(len(masked) / len(visited), 4)}


# a straight-line region of either kernel: at most this many tile pairs
# a head, and this many rows a side (16 pairs of 1024 x 1024 compile for
# half a minute and gain nothing over 4)
_REGION_TILES = 16
_REGION_ROWS = 2048


def _region_span(s, block_q, block_k):
    """Side of the square REGIONS both kernels walk: the largest
    divisor of S that is whole tiles both ways within the two bounds
    above (one tile pair where a single one is past them). A region's
    pairs are straight-line code, so the compiler overlaps one pair's
    matmuls with another's elementwise work — a dependent chain of
    matmul stages that a pair walked alone by a loop waits out
    (0.45 us a pair on a v5e, PERF.md PR 36); the regions themselves
    are walked by a loop, so the code does not grow with S."""
    tile = math.lcm(block_q, block_k)
    return max([m for m in range(tile, min(s, _REGION_ROWS) + 1, tile)
                if s % m == 0
                and (m // block_q) * (m // block_k) <= _REGION_TILES],
               default=tile)


def heads_per_program(h, s, block_q, block_k, layout=None):
    """Heads a program takes — ONE rule, read by both kernels' grids and
    block specs and by the walk instants. Where one region is the whole
    head and leaves room within the two bounds above, the region fills
    up with neighbouring heads, each its own straight-line chains — at
    S = 128 a head is ONE pair, and a program a head would be a grid
    step's price 3,072 times a layer for 13 MFLOP each (BERT-base,
    PERF.md PRs 40, 45). Head-major (the forward; the head-major
    backward is a head a program): the largest divisor of ``h``, so a
    program's heads share their batch row's mask row. Token-major (both
    directions): whole lane blocks, the largest count that divides the
    row's blocks and the q / k / v offsets (so the three index maps
    stay whole): all twelve heads of a BERT-base batch row at S = 128,
    256 programs a layer; ONE lane block at S = 1024, whose two heads
    alone are past the row bound. Grouped key/value heads: one head (its
    k / v block is its group's, which its neighbour may not share)."""
    unit = 1 if layout is None else layout.per_block
    if _region_span(s, block_q, block_k) != s or (
            layout is not None and layout.group > 1):
        return unit
    pairs = (s // block_q) * (s // block_k)
    units = h if layout is None else math.gcd(layout.blocks, *layout.tiles)
    return unit * max(g for g in range(1, units + 1)
                      if units % g == 0 and (g == 1 or (
                          g * unit * pairs <= _REGION_TILES
                          and g * unit * s <= _REGION_ROWS)))


def fwd_walk_counts(h, s, block_q, block_k, causal, layout=None,
                    window=None):
    """What a forward call at these tiles runs: the walk's counts over
    the square (the band's, with a ``window``), the heads a program
    takes and its independent chains (a q-tile of a head each)."""
    heads = heads_per_program(h, s, block_q, block_k, layout)
    span = _region_span(s, block_q, block_k)
    return {**tile_walk_counts(s, block_q, block_k, causal, window),
            "heads_per_program": heads,
            "chains": heads * (span // block_q)}


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, mask_ref,
                dq_ref, dk_ref, dv_ref, *acc, sm_scale, block_q, block_k,
                span, seq_len, causal, head_dim=None, window=None):
    """One region row's program: K / V rows ``[kj*span, (kj+1)*span)``
    stay resident and the program walks the q-regions the diagonal
    leaves them — the region ON the diagonal first (its tile pairs
    wholly above the diagonal are left out and only the pairs it cuts
    carry the iota / compare / select, all decided while tracing), then
    every region below it (all pairs, no mask); regions above it are
    not run. Each pair rebuilds its score tile ONCE, TRANSPOSED
    (``[block_k, block_q]``: the row residuals lse and D then lie on
    lanes and dV, dK are plain matmuls; dQ is summed transposed too,
    ``[D, block_q]``: K^T dS^T is a plain matmul where dS K would
    transpose every tile), and feeds all three gradients from that one
    P / dS. A region's sums are float32 values. Where one region is the
    whole head (``span == seq_len``) they are rounded straight into the
    outputs; else they add up in the float32 scratch ``acc`` — dK / dV
    over the program's walk, dQ (``[D, S]``) across the programs of a
    head — and are rounded once at the end. Token-major (``head_dim``
    given) the blocks hold several heads side by side
    (``heads_per_program``): each head makes that same walk over its
    own lane window, with its own residual rows and its own three
    accumulators — where a head is one pair, twelve heads of
    straight-line code a program.

    With a ``window`` (causal only) the program walks the tiles the
    forward walks, seen from the keys' side: the diagonal's region
    (``tile_walk`` with the band: a pair wholly behind it is neither
    loaded nor computed, a pair either edge cuts is masked), the
    regions below it that lie wholly inside the band by the loop, then
    the one or two the band's far edge cuts (``_band_regions``), each
    at its static distance and skipped by a branch where the sequence
    ends before it; a q-region wholly behind ``i - window`` is not
    run."""
    region_axis = 1 if head_dim is None else 2
    kj = pl.program_id(region_axis)
    nt = (((1,), (1,)), ((), ()))             # a @ b^T
    nn = (((1,), (0,)), ((), ()))
    whole = span == seq_len

    def head(n, lanes):
        def resid(ref, rows):     # this head's residual row, on lanes
            if head_dim is None:
                return ref[0, 0, rows][None, :]
            return ref[0, n, 0, rows][None, :]

        if not whole:
            dq_acc, dk_acc, dv_acc = acc[3 * n:3 * n + 3]

            @pl.when(kj == 0)
            def _():
                dq_acc[...] = jnp.zeros_like(dq_acc)

        def region(qi, diagonal, first, offset=None):
            """``offset``: the rows this region's q rows lie below its
            keys' diagonal where the band's far edge cuts it, else
            None."""
            q0 = 0 if whole else qi * span   # static where it can be
            rows = [pl.ds(q0 + i * block_q if isinstance(q0, int) else
                          pl.multiple_of(q0 + i * block_q, block_q),
                          block_q)
                    for i in range(span // block_q)]
            dqt = [0.0] * len(rows)
            # a region either edge of the band cuts: its pairs by the
            # forward's own walk
            cut = window is not None and (diagonal or offset is not None)
            if cut:
                below = offset or 0
                visited, masked = tile_walk(span, block_q, block_k, True,
                                            window, below)
            for j in range(span // block_k):
                keys = slice(j * block_k, (j + 1) * block_k)
                k = k_ref[0, keys, lanes]         # [block_k, d]
                v = v_ref[0, keys, lanes]
                kt = k.T
                begin, unmasked = 0, 0
                if diagonal and not cut:
                    # q0 is the keys' own offset: local indices
                    begin = _first_q_tile(j, block_q, block_k)
                    unmasked = _first_unmasked_q_tile(j, block_q, block_k)
                dk = dv = 0.0
                for i in range(begin, len(rows)):
                    if cut and (i, j) not in visited:
                        continue
                    q = q_ref[0, rows[i], lanes]  # [block_q, d]
                    do = do_ref[0, rows[i], lanes]
                    st = jax.lax.dot_general(
                        k, q, nt,
                        preferred_element_type=jnp.float32) * sm_scale
                    if mask_ref is not None:
                        st = st + mask_ref[0, keys, :]    # [block_k, 1]
                    if cut and (i, j) in masked:
                        # row - key of the transposed tile's entries
                        ahead = (i * block_q + below - j * block_k) \
                            + jax.lax.broadcasted_iota(
                                jnp.int32, (block_k, block_q), 1) \
                            - jax.lax.broadcasted_iota(
                                jnp.int32, (block_k, block_q), 0)
                        cuts = _band_cuts(i, j, block_q, block_k, below,
                                          window)
                        kept = None
                        if "diagonal" in cuts:
                            kept = ahead >= 0
                        if "edge" in cuts:
                            edge = ahead < window
                            kept = edge if kept is None else kept & edge
                        st = jnp.where(kept, st, NEG_INF)
                    elif i < unmasked:
                        k_pos = j * block_k + jax.lax.broadcasted_iota(
                            jnp.int32, (block_k, block_q), 0)
                        q_pos = i * block_q + jax.lax.broadcasted_iota(
                            jnp.int32, (block_k, block_q), 1)
                        st = jnp.where(q_pos >= k_pos, st, NEG_INF)
                    pt = jnp.exp(st - resid(l_ref, rows[i]))       # P^T
                    dv = dv + jax.lax.dot_general(
                        pt.astype(do.dtype), do, nn,
                        preferred_element_type=jnp.float32)
                    dpt = jax.lax.dot_general(
                        v, do, nt, preferred_element_type=jnp.float32)
                    # D: the row residual where k-tiles share a row,
                    # else (one k-tile IS the row) summed here:
                    # rowsum(P o dP) = rowsum(dO o O), keys on sublanes
                    row_d = resid(d_ref, rows[i]) if d_ref is not None \
                        else jnp.sum(pt * dpt, axis=0, keepdims=True)
                    dst = (pt * (dpt - row_d)
                           * sm_scale).astype(q.dtype)            # dS^T
                    dk = dk + jax.lax.dot_general(
                        dst, q, nn, preferred_element_type=jnp.float32)
                    dqt[i] = dqt[i] + jax.lax.dot_general(
                        kt, dst, nn, preferred_element_type=jnp.float32)
                if isinstance(dk, float):   # the band left the tile out
                    if not (whole or first):
                        continue
                    dk = dv = jnp.zeros(k.shape, jnp.float32)
                if whole:
                    dk_ref[0, keys, lanes] = dk.astype(dk_ref.dtype)
                    dv_ref[0, keys, lanes] = dv.astype(dv_ref.dtype)
                elif first:  # the program's first region: nothing to add to
                    dk_acc[keys, :] = dk
                    dv_acc[keys, :] = dv
                else:
                    dk_acc[keys, :] += dk
                    dv_acc[keys, :] += dv
            for i, dq in enumerate(dqt):
                if isinstance(dq, float):   # no pair of the tile was run
                    if not whole:
                        continue
                    dq = jnp.zeros((k_ref.shape[-1] if head_dim is None
                                    else head_dim, block_q), jnp.float32)
                if whole:
                    dq_ref[0, rows[i], lanes] = dq.T.astype(dq_ref.dtype)
                else:
                    dq_acc[:, rows[i]] += dq

        # the diagonal's region with ``causal``, else the first: traced
        # apart from the loop, for the pairs it leaves out or for
        # ``first`` alone
        region(kj if causal else 0, diagonal=causal, first=True)
        if whole:
            return

        def body(qi, carry):
            region(qi, diagonal=False, first=False)
            return carry

        regions = seq_len // span
        if window is None:
            jax.lax.fori_loop(kj + 1 if causal else 1, regions, body, 0)
        else:
            inside, edges = _band_regions(seq_len, span, window)
            jax.lax.fori_loop(kj + 1,
                              jnp.minimum(kj + inside + 1, regions),
                              body, 0)
            for e in edges:
                @pl.when(kj + e < regions)
                def _(e=e):
                    region(kj + e, diagonal=False, first=False,
                           offset=e * span)
        dk_ref[0, :, lanes] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, :, lanes] = dv_acc[...].astype(dv_ref.dtype)

        @pl.when(kj == pl.num_programs(region_axis) - 1)
        def _():
            dq_ref[0, :, lanes] = dq_acc[...].T.astype(dq_ref.dtype)

    for n, lanes in enumerate(_lane_windows(q_ref, head_dim)):
        head(n, lanes)


def _backward_compiler_params(s, d, span, block_q, block_k, itemsize,
                              heads=1, grid_rank=2):
    """The region axis is a reduction into ``dq_acc`` (sequential); the
    VMEM asked for covers a head's q, dO and dQ and a region's K, V, dK
    and dV (double-buffered), the float32 accumulators (dK's and dV's
    once a head of the program) and the score-tile temporaries of a
    region's pairs, which at the largest candidate tiles pass the
    16 MiB a kernel gets unasked."""
    lanes = -(-d * heads // LANES) * LANES
    resident = (3 * 2 * itemsize + 4) * s * lanes \
        + 2 * (4 * itemsize + 4 * heads) * span * lanes
    tiles = 6 * 4 * max(block_q * block_k, span * span // 4)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1)
        + ("arbitrary",),
        vmem_limit_bytes=int(min(_MOST_VMEM,
                                 max(16 * 1024 * 1024,
                                     2 * (resident + tiles)))))


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal",
                                             "interpret", "block_q",
                                             "block_k", "layout"))
def _flash_attention_bwd_jit(q, k, v, mask, o, lse, do, sm_scale, causal,
                             interpret, block_q, block_k, layout=None):
    return _flash_backward(q, k, v, mask, o, lse, do, sm_scale, causal,
                           interpret, block_q, block_k, layout)


def _banded_backward(q, k, v, o, lse, do, sm_scale, interpret, block_q,
                     block_k, layout, window):
    return _flash_backward(q, k, v, None, o, lse, do, sm_scale, True,
                           interpret, block_q, block_k, layout, window)


_BANDED_STATIC = ("sm_scale", "interpret", "block_q", "block_k", "layout",
                  "window")
# (grouped key/value heads, a band) -> the backward under its event name
_NAMED_BACKWARD = {
    (False, True): _named("hetu_flash_window_bwd", _banded_backward,
                          _BANDED_STATIC),
    (True, False): _named("hetu_flash_gqa_bwd", _banded_backward,
                          _BANDED_STATIC),
    (True, True): _named("hetu_flash_gqa_window_bwd", _banded_backward,
                         _BANDED_STATIC)}


def _flash_backward(q, k, v, mask, o, lse, do, sm_scale, causal, interpret,
                    block_q, block_k, layout=None, window=None):
    """``layout`` as :func:`_flash_attention_jit` takes it; token-major
    ``o`` and ``do`` are ``[B, S, H]`` and so are dq, dk and dv (grouped:
    dk and dv ``[B, S, kv_heads * head_dim]``, a group's query heads
    summed in float32)."""
    b, h, s, d = _dims(q, layout)
    span = _region_span(s, block_q, block_k)

    # one k-tile is a head's whole row: the kernel sums D from the P
    # and dP it holds anyway (``_bwd_kernel``), and neither the pass
    # over dO and O nor that residual exists
    sums_d = block_k == s

    def row_sums(heads_shape):
        # D = rowsum(dO * O): an XLA elementwise reduce. The row
        # residuals travel as rows (S on lanes, where the transposed
        # score tile wants them), not broadcast over lanes
        return jnp.sum((do.astype(jnp.float32)
                        * o.astype(jnp.float32)).reshape(heads_shape),
                       axis=-1)

    if layout is None:  # jit-ok: static argname
        grid = (b * h, s // span)
        args = [x.reshape(b * h, s, d) for x in (q, k, v, do)]
        resids = [lse.reshape(b * h, 1, s).astype(jnp.float32)]
        if not sums_d:  # jit-ok: static argnames
            resids.append(row_sums((b, h, s, d)).reshape(b * h, 1, s))
        head = lambda bh, kj: (bh, 0, 0)          # noqa: E731
        keys = lambda bh, kj: (bh, kj, 0)         # noqa: E731
        in_specs = [pl.BlockSpec((1, s, d), head),
                    pl.BlockSpec((1, span, d), keys),
                    pl.BlockSpec((1, span, d), keys),
                    pl.BlockSpec((1, s, d), head)]
        resid = pl.BlockSpec((1, 1, s), head)
        # per KEY, so a column of the transposed tile: [B, S, 1]
        mask_spec = pl.BlockSpec(
            (1, span, 1), lambda bh, kj, _h=h: (bh // _h, kj, 0))
        out_shape = [jax.ShapeDtypeStruct((b * h, s, d), x.dtype)
                     for x in (q, k, v)]
        out_specs = [pl.BlockSpec((1, s, d), head),
                     pl.BlockSpec((1, span, d), keys),
                     pl.BlockSpec((1, span, d), keys)]
        group = 1
    else:
        # as wide as the forward's programs where a head is few tiles
        # (``heads_per_program``, at the BACKWARD's tiles)
        group = heads_per_program(h, s, block_q, block_k, layout)
        wide = group // layout.per_block
        lanes = wide * layout.width
        tq, tk, tv = (t // wide for t in layout.tiles)
        grid = (b, layout.blocks // wide, s // span)
        args = [q, k, v, do]
        resids = [lse.reshape(b, h, 1, s).astype(jnp.float32)]
        if not sums_d:  # jit-ok: static argnames
            resids.append(row_sums((b, s, h, d)).transpose(
                0, 2, 1).reshape(b, h, 1, s))

        def head(tile):
            return lambda bi, p, kj: (bi, 0, tile + p)

        def keys(tile):
            return lambda bi, p, kj: (bi, kj, tile + p)

        resid = pl.BlockSpec((1, group, 1, s),
                             lambda bi, p, kj: (bi, p, 0, 0))
        in_specs = [pl.BlockSpec((1, s, lanes), head(tq)),
                    pl.BlockSpec((1, span, lanes), keys(tk)),
                    pl.BlockSpec((1, span, lanes), keys(tv)),
                    pl.BlockSpec((1, s, lanes), head(0))]
        if layout.group > 1:  # jit-ok: static argname
            # a program a QUERY head: it reads its group's k / v rows
            # and writes its own dk / dv
            in_specs[1:3] = [
                pl.BlockSpec((1, span, lanes), lambda bi, p, kj, t=t:
                             (bi, kj, t + p // layout.group))
                for t in (tk, tv)]
        mask_spec = pl.BlockSpec((1, span, 1),
                                 lambda bi, p, kj: (bi, kj, 0))
        out_shape = [jax.ShapeDtypeStruct((b, s, h * d), x.dtype)
                     for x in (q, k, v)]
        out_specs = [pl.BlockSpec((1, s, lanes), head(0)),
                     pl.BlockSpec((1, span, lanes), keys(0)),
                     pl.BlockSpec((1, span, lanes), keys(0))]
    body = functools.partial(_bwd_kernel, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k, span=span,
                             seq_len=s, causal=causal,
                             head_dim=None if layout is None else d,
                             **({} if window is None
                                else {"window": window}))
    args += resids
    in_specs += [resid] * len(resids)
    # of the kernel's optional inputs: D (5), the mask (6)
    absent = [5] if sums_d else []
    if mask is not None:  # jit-ok: structural None-check, not a traced read
        in_specs.append(mask_spec)
        args.append(_mask_rows(mask, b, h, s).reshape(b, s, 1))
    else:
        absent.append(6)
    if absent:
        def kernel(*refs):
            refs = list(refs)
            for at in absent:
                refs.insert(at, None)
            body(*refs)
    else:
        kernel = body

    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        # float32 sums across regions, a head: dQ^T over its programs,
        # dK and dV over a program's walk
        scratch_shapes=[] if span == s else group * [
            pltpu.VMEM((d, s), jnp.float32),
            pltpu.VMEM((span, d), jnp.float32),
            pltpu.VMEM((span, d), jnp.float32)],
        compiler_params=_backward_compiler_params(
            s, d, span, block_q, block_k, q.dtype.itemsize, group,
            len(grid)),
        interpret=interpret,
    )(*args)
    if layout is not None and layout.group > 1:  # jit-ok: static argname
        dk, dv = (x.astype(jnp.float32).reshape(
            b, s, layout.kv_heads, layout.group, d).sum(axis=3).reshape(
                b, s, layout.kv_heads * d).astype(x.dtype)
            for x in (dk, dv))
    if layout is not None:  # jit-ok: static argname
        return dq, dk, dv
    shape = (b, h, s, d)
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))


def flash_attention_bwd(q, k, v, mask, o, lse, do, sm_scale=1.0,
                        causal=False, interpret=None, layout=None,
                        reason=None, window=None):
    """(dq, dk, dv) via the fused recompute-form kernel, in the operand
    form of q, k and v. ``lse`` is the forward's logsumexp
    (flash_attention_with_lse). Block sizes tune independently of the
    forward's; with ``causal`` the tiles also decide how much of the
    square the walk skips (``tile_walk_counts``, recorded here at trace
    time as a ``flash_bwd_walk`` instant, beside the heads a program
    takes)."""
    interpret, (block_q, block_k) = _plan(
        "bwd", q, mask, causal, interpret, layout, reason)
    _, h, s, d = _dims(q, layout)
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "flash_bwd_walk", seq=s, head_dim=d, block_q=block_q,
        block_k=block_k, causal=bool(causal),
        heads_per_program=1 if layout is None else heads_per_program(
            h, s, block_q, block_k, layout),
        **tile_walk_counts(s, block_q, block_k, causal, window),
        **({} if window is None else {"window": int(window)}))
    grouped = layout is not None and layout.group > 1
    if window is None and not grouped:
        return _flash_attention_bwd_jit(q, k, v, mask, o, lse, do,
                                        sm_scale, causal, interpret,
                                        block_q, block_k, *_form(layout))
    if not causal or mask is not None:
        raise ValueError("a window is a band under the diagonal, and "
                         "grouped key/value heads are a decoder's: "
                         "causal=True and no padding mask")
    return _NAMED_BACKWARD[grouped, window is not None](
        q, k, v, o, lse, do, sm_scale, interpret, block_q, block_k, layout,
        None if window is None else int(window))
