"""Pallas TPU flash-attention kernels (forward + fused backward).

No reference equivalent (the reference composes attention from cublas
batch-matmuls, examples/nlp/bert/hetu_bert.py:191-227). Forward is the
blocked online-softmax kernel: per (batch*head, q-block) program, stream
K/V blocks through VMEM keeping a running (max, sum, accumulator) — the
[S, S] score matrix never exists in HBM, so attention memory is O(S·D)
instead of O(S²) and the MXU stays fed from VMEM.

Backward is the standard recompute form, in ONE kernel a call: the
forward also emits the per-row logsumexp L, and the kernel visits each
(q-tile, k-tile) pair once, rebuilds its score tile in VMEM and feeds
dV, dK and dQ from that one P / dS, every sum across tiles in float32
and rounded once. So the S×S matrices never exist in HBM on the backward
pass either (the property training needs for long context). With
``causal`` the walk follows the diagonal: tile pairs wholly above it are
never run at any tile size, and only the pairs it cuts carry the iota /
compare / select (``bwd_walk``). The tile is built transposed
(``[block_k, block_q]``), so the row residuals L and D = rowsum(dO ∘ O)
(a cheap XLA elementwise reduce outside the kernel) travel as
``[B*H, 1, S]`` rows and every matmul of a pair is a plain one. Pairs
are grouped into square REGIONS (``_bwd_span``): a region's pairs are
straight-line code the compiler interleaves, the regions are walked by a
loop from the diagonal down, a program per (batch*head, region row).

Block sizes are AUTOTUNED per (platform, kernel, S, D, dtype, causal,
mask): bq/bk sweep {128, 256, 512, 1024} (clipped to divisors of S)
independently for the forward, the forward-with-lse and the fused
backward through ``hetu_tpu/tune`` — the sweep runs once at first
compile of a shape, the winner persists in the autotune JSON cache, and
``HETU_AUTOTUNE=0`` falls back to the static ``_block_sizes`` defaults
(bq≤256, bk≤512). With ``causal`` the backward's tiles also decide how
much of the square is skipped (a tile 1024 long on either side of S=1024
is cut by the diagonal everywhere), so its best tiles differ from the
forward's —
that per-direction freedom is the point of tuning the three kernels
apart. Batch/heads are NOT in the key (they only size the embarrassingly
parallel grid axis; per-program work is S/D-shaped): the sweep times
the first caller's b/h and later batch sizes share that winner.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_bwd", "tune_key"]

NEG_INF = -1e30
LANES = 128      # TPU minor-dim tile: the forward writes lse lane-tiled
# A kernel gets 16 MiB of VMEM without asking. The forward holds a
# head's whole K and V on chip, double-buffered: past this many bytes of
# them (S = 8192 at D = 192 is 16.8e6) it asks for what it needs.
_DEFAULT_VMEM = 12 * 1024 * 1024
_MOST_VMEM = 100 * 1024 * 1024


def _forward_compiler_params(s, d, itemsize):
    """``{}`` at every shape that fits the default VMEM (so those
    kernels compile as they always have), else the limit to ask for."""
    resident = 2 * 2 * s * (-(-d // LANES) * LANES) * itemsize
    if resident <= _DEFAULT_VMEM:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(_MOST_VMEM, 2 * resident))}


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, l_ref, *, sm_scale,
                block_k, seq_len, causal, block_q):
    # dots run in the INPUT dtype with f32 accumulation — on bf16 inputs
    # that is the MXU's native mode; upcasting operands to f32 first
    # would decompose every matmul into multiple f32 passes (measured
    # ~2x whole-step cost at S=2048). All softmax math stays f32.
    q = q_ref[0]                              # [block_q, d]
    num_kb = seq_len // block_k
    qi = pl.program_id(1)
    if causal:
        # skip K-blocks strictly in the future of this q-block
        num_kb = jnp.minimum(
            num_kb, pl.cdiv((qi + 1) * block_q, block_k))

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if mask_ref is not None:
            s = s + mask_ref[0, 0, pl.ds(i * block_k, block_k)][None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((q.shape[0], 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if l_ref is not None:
        # per-row logsumexp, the backward's softmax residual — written
        # lane-tiled [block_q, 128] (TPU blocks need 128-lane minors)
        l_ref[0] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANES))


def _block_sizes(seq_len, head_dim):
    """Static default tiles (the pre-autotune behavior, and the
    ``HETU_AUTOTUNE=0`` / cache-only-miss fallback)."""
    bq = min(256, seq_len)
    while seq_len % bq:
        bq //= 2
    bk = min(512, seq_len)
    while seq_len % bk:
        bk //= 2
    return max(bq, 8), max(bk, 8)


def _supported(s, d, block_q, block_k):
    # the grid covers s // block only when s divides evenly; max(bq, 8)
    # can break that for s % 8 != 0 (e.g. s=260), which would leave tail
    # rows unwritten — callers fall back to the composed reference
    return not (s < 8 or d % 8 or s % block_q or s % block_k)


# ---------------------------------------------------------------------------
# block-size autotuning (engine: hetu_tpu/tune/autotune.py)
# ---------------------------------------------------------------------------

# the sweep space: every candidate is a whole multiple of the TPU tile
# and a divisor of S (enforced by _candidates), so any (bq, bk) pair in
# it produces a valid grid
_CANDIDATE_BLOCKS = (128, 256, 512, 1024)
# per-candidate timing: reps amortize the host dispatch latency (one
# readback sync per window, shared by `reps` queued kernel executions),
# windows take the min over host jitter — candidate deltas are ~ms
_MEASURE_REPS = 8
_MEASURE_WINDOWS = 3


def _candidates(s):
    return [c for c in _CANDIDATE_BLOCKS if c <= s and s % c == 0]


# a kernel whose tile walk changed is swept afresh: winners stored for
# the kernel it replaced (a checkout keeps its autotune.json across a
# pull) sit under the old name and are not read. ``bwd`` was two kernels
# (dK/dV, dQ) that ran the whole square at the tiles they liked best.
_KERNEL_REVISION = {"bwd": "bwd_onepass"}


def tune_key(kind, s, d, dtype, causal, has_mask, interpret=False):
    """(name, key) under which a flash kernel's block choice is cached —
    shared by the tuner, the probe and the tests. ``kind`` is one of
    ``fwd`` / ``fwd_lse`` / ``bwd``; interpret-mode entries are
    partitioned so CPU test sweeps never pollute a TPU cache."""
    key = (f"S{s}", f"D{d}", jnp.dtype(dtype).name,
           "causal" if causal else "full",
           "mask" if has_mask else "nomask")
    if interpret:
        key = key + ("interp",)
    return "flash_" + _KERNEL_REVISION.get(kind, kind), key


def _measure_factory(kind, b, h, s, d, dtype, sm_scale, causal, has_mask,
                     interpret):
    """measure(config) -> seconds for the autotune engine. Inputs are
    built lazily on the first call (a cache hit never pays for them)
    with the CALLER's b/h so the sweep times the shape that triggered
    it; timing syncs by scalar readback."""
    state = {}

    def _inputs():
        if state:
            return state
        rng = np.random.RandomState(0)

        def mk():
            return jnp.asarray(rng.randn(b, h, s, d) * 0.3, dtype)

        state["q"], state["k"], state["v"] = mk(), mk(), mk()
        state["mask"] = (jnp.zeros((b, 1, 1, s), jnp.float32)
                         if has_mask else None)
        if kind == "bwd":
            # consistent o/lse from the default-block forward: random
            # residuals would exp() into inf and time a garbage kernel
            bq0, bk0 = _block_sizes(s, d)
            o, lse = _flash_attention_jit(
                state["q"], state["k"], state["v"], state["mask"],
                sm_scale, causal, interpret, bq0, bk0, True)
            state["o"], state["lse"], state["do"] = o, lse, mk()
        return state

    def _sync(out):
        first = out[0] if isinstance(out, tuple) else out
        return float(jnp.sum(first.astype(jnp.float32)))

    def measure(cfg):
        # NOTE: the engine calls measure on a dedicated sweep thread.
        # The sweep fires at trace time of the surrounding step (the
        # executor jits the whole graph), and jax's trace state is
        # thread-local — on the caller's thread these jnp calls would
        # silently become traced equations and the timings garbage.
        bq, bk = int(cfg[0]), int(cfg[1])
        st = _inputs()
        if kind == "bwd":
            def run():
                return _flash_attention_bwd_jit(
                    st["q"], st["k"], st["v"], st["mask"], st["o"],
                    st["lse"], st["do"], sm_scale, causal, interpret,
                    bq, bk)
        else:
            need_lse = kind == "fwd_lse"

            def run():
                return _flash_attention_jit(
                    st["q"], st["k"], st["v"], st["mask"], sm_scale,
                    causal, interpret, bq, bk, need_lse)
        from ..tune import timeit
        return timeit(run, _sync, reps=_MEASURE_REPS,
                      windows=_MEASURE_WINDOWS)

    return measure


def _tuned_block_sizes(kind, b, h, s, d, dtype, sm_scale, causal,
                       has_mask, interpret):
    """(block_q, block_k) for one kernel direction: the autotuned winner
    when tuning is on and the shape has a real sweep space, the static
    default otherwise. Runs at trace time — once per compiled shape —
    so steady-state steps never touch the table."""
    default = _block_sizes(s, d)
    cands = [(bq, bk) for bq in _candidates(s) for bk in _candidates(s)]
    if len(cands) < 2:
        return default              # nothing to tune (short sequences)
    from ..tune import autotune
    name, key = tune_key(kind, s, d, dtype, causal, has_mask, interpret)
    cfg = autotune(name, key, cands,
                   _measure_factory(kind, b, h, s, d, dtype, sm_scale,
                                    causal, has_mask, interpret),
                   default=default)
    try:
        bq, bk = int(cfg[0]), int(cfg[1])
    except (TypeError, ValueError, IndexError):
        return default
    if bq < 8 or bk < 8 or s % bq or s % bk:
        return default              # stale/foreign cache entry
    return bq, bk


def flash_attention(q, k, v, mask=None, sm_scale=1.0, causal=False,
                    interpret=None):
    """softmax(q k^T * sm_scale + mask) v over [B, H, S, D].

    ``mask`` is an additive *padding* mask broadcastable to [B, 1, 1, S]
    (the BERT layout); causal masking is a kernel flag, not a mask
    argument. Tiny or oddly-shaped inputs fall back to the composed-XLA
    reference rather than violating TPU tiling constraints.
    """
    if interpret is None:
        interpret = INTERPRET
    b, h, s, d = q.shape
    if not _supported(s, d, *_block_sizes(s, d)):
        from .attention import attention_reference
        m = mask
        if causal:
            cmask = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                              NEG_INF)[None, None]
            m = cmask if m is None else m + cmask
        return attention_reference(q, k, v, m, sm_scale)
    block_q, block_k = _tuned_block_sizes(
        "fwd", b, h, s, d, q.dtype, sm_scale, causal, mask is not None,
        interpret)
    return _flash_attention_jit(q, k, v, mask, sm_scale, causal,
                                interpret, block_q, block_k, False)


def flash_attention_with_lse(q, k, v, mask=None, sm_scale=1.0,
                             causal=False, interpret=None):
    """(output, logsumexp [B, H, S]) — the pair the fused backward needs.
    Returns (None, None) on shapes the kernel does not support; callers
    then take the composed path for both directions."""
    if interpret is None:
        interpret = INTERPRET
    b, h, s, d = q.shape
    if not _supported(s, d, *_block_sizes(s, d)):
        return None, None
    block_q, block_k = _tuned_block_sizes(
        "fwd_lse", b, h, s, d, q.dtype, sm_scale, causal,
        mask is not None, interpret)
    return _flash_attention_jit(q, k, v, mask, sm_scale, causal,
                                interpret, block_q, block_k, True)


# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def _mask_rows(mask, b, h, s):
    """[B, 1, 1, S]-broadcastable additive mask -> [B, 1, S] rows."""
    return jnp.broadcast_to(mask, (b, 1, 1, s)).reshape(
        b, 1, s).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal",
                                             "interpret", "block_q",
                                             "block_k", "need_lse"))
def _flash_attention_jit(q, k, v, mask, sm_scale, causal, interpret,
                         block_q, block_k, need_lse):
    b, h, s, d = q.shape
    grid = (b * h, s // block_q)

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
    ]
    args = [qr, kr, vr]
    body = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                             block_k=block_k, seq_len=s, causal=causal,
                             block_q=block_q)
    if mask is not None:  # jit-ok: structural None-check, not a traced read
        in_specs.append(
            pl.BlockSpec((1, 1, s), lambda bh, qi, _h=h: (bh // _h, 0, 0)))
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

    o_shape = jax.ShapeDtypeStruct((b * h, s, d), q.dtype)
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0))
    more_vmem = _forward_compiler_params(s, d, q.dtype.itemsize)
    if need_lse:  # jit-ok: static argname
        # the lse residual is emitted only when a consumer exists (the
        # fused backward); the inference/serving forward skips the write
        out, lse = pl.pallas_call(
            kernel,
            out_shape=[o_shape,
                       jax.ShapeDtypeStruct((b * h, s, LANES),
                                            jnp.float32)],
            grid=grid,
            in_specs=in_specs,
            out_specs=[o_spec,
                       pl.BlockSpec((1, block_q, LANES),
                                    lambda bh, qi: (bh, qi, 0))],
            interpret=interpret, **more_vmem,
        )(*args)
        return out.reshape(b, h, s, d), lse[:, :, 0].reshape(b, h, s)
    out = pl.pallas_call(
        kernel,
        out_shape=o_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        interpret=interpret, **more_vmem,
    )(*args)
    return out.reshape(b, h, s, d)


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


def bwd_walk(s, block_q, block_k, causal):
    """The (q-tile, k-tile) pairs the backward kernel runs, and of them
    those that carry the causal iota / compare / select — from the same
    two bounds the kernel leaves pairs out by and masks by (inside the
    regions on the diagonal; the regions below it hold only pairs these
    bounds keep and do not mask). The full square, nothing masked,
    without ``causal``."""
    num_qb, num_kb = s // block_q, s // block_k
    visited, masked = [], []
    for kj in range(num_kb):
        first, unmasked = 0, 0
        if causal:
            first = _first_q_tile(kj, block_q, block_k)
            unmasked = min(num_qb,
                           _first_unmasked_q_tile(kj, block_q, block_k))
        visited += [(i, kj) for i in range(first, num_qb)]
        masked += [(i, kj) for i in range(first, unmasked)]
    return visited, masked


def bwd_walk_counts(s, block_q, block_k, causal):
    """How far the tile walk engages at these tiles: tiles visited, tiles
    of the square, masked tiles, and the two shares a trace reader wants
    (visited / square, masked / visited)."""
    visited, masked = bwd_walk(s, block_q, block_k, causal)
    square = (s // block_q) * (s // block_k)
    return {"tiles_visited": len(visited), "tiles_square": square,
            "tiles_masked": len(masked),
            "visited_share": round(len(visited) / square, 4),
            "masked_share": round(len(masked) / len(visited), 4)}


# a straight-line region of the backward: at most this many tile pairs,
# and this many rows a side (16 pairs of 1024 x 1024 compile for half a
# minute and gain nothing over 4)
_REGION_TILES = 16
_REGION_ROWS = 2048


def _bwd_span(s, block_q, block_k):
    """Side of the square REGIONS the backward walks: the largest
    divisor of S that is whole tiles both ways within the two bounds
    above (one tile pair where a single one is past them). A region's
    pairs are straight-line code, so the compiler overlaps one pair's
    matmuls with another's elementwise work — a dependent chain of
    three matmul stages that a pair walked alone by a loop waits out
    (0.45 us a pair on a v5e, PERF.md PR 36); the regions themselves
    are walked by a loop, so the code does not grow with S."""
    tile = math.lcm(block_q, block_k)
    return max([m for m in range(tile, min(s, _REGION_ROWS) + 1, tile)
                if s % m == 0
                and (m // block_q) * (m // block_k) <= _REGION_TILES],
               default=tile)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, mask_ref,
                dq_ref, dk_ref, dv_ref, *acc, sm_scale, block_q, block_k,
                span, seq_len, causal):
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
    head — and are rounded once at the end."""
    kj = pl.program_id(1)
    nt = (((1,), (1,)), ((), ()))             # a @ b^T
    nn = (((1,), (0,)), ((), ()))
    whole = span == seq_len
    if not whole:
        dq_acc, dk_acc, dv_acc = acc

        @pl.when(kj == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def region(qi, diagonal, first):
        q0 = 0 if whole else qi * span       # static where it can be
        rows = [pl.ds(q0 + i * block_q if isinstance(q0, int) else
                      pl.multiple_of(q0 + i * block_q, block_q), block_q)
                for i in range(span // block_q)]
        dqt = [0.0] * len(rows)
        for j in range(span // block_k):
            keys = slice(j * block_k, (j + 1) * block_k)
            k = k_ref[0, keys, :]             # [block_k, d]
            v = v_ref[0, keys, :]
            kt = k.T
            begin, unmasked = 0, 0
            if diagonal:    # q0 is the keys' own offset: local indices
                begin = _first_q_tile(j, block_q, block_k)
                unmasked = _first_unmasked_q_tile(j, block_q, block_k)
            dk = dv = 0.0
            for i in range(begin, len(rows)):
                q = q_ref[0, rows[i], :]      # [block_q, d]
                do = do_ref[0, rows[i], :]
                st = jax.lax.dot_general(
                    k, q, nt,
                    preferred_element_type=jnp.float32) * sm_scale
                if mask_ref is not None:
                    st = st + mask_ref[0, keys, :]    # [block_k, 1]
                if i < unmasked:
                    k_pos = j * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0)
                    q_pos = i * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 1)
                    st = jnp.where(q_pos >= k_pos, st, NEG_INF)
                pt = jnp.exp(st - l_ref[0, 0, rows[i]][None, :])  # P^T
                dv = dv + jax.lax.dot_general(
                    pt.astype(do.dtype), do, nn,
                    preferred_element_type=jnp.float32)
                dpt = jax.lax.dot_general(
                    v, do, nt, preferred_element_type=jnp.float32)
                dst = (pt * (dpt - d_ref[0, 0, rows[i]][None, :])
                       * sm_scale).astype(q.dtype)                # dS^T
                dk = dk + jax.lax.dot_general(
                    dst, q, nn, preferred_element_type=jnp.float32)
                dqt[i] = dqt[i] + jax.lax.dot_general(
                    kt, dst, nn, preferred_element_type=jnp.float32)
            if whole:
                dk_ref[0, keys, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)
            elif first:     # the program's first region: nothing to add to
                dk_acc[keys, :] = dk
                dv_acc[keys, :] = dv
            else:
                dk_acc[keys, :] += dk
                dv_acc[keys, :] += dv
        for i, dq in enumerate(dqt):
            if whole:
                dq_ref[0, rows[i], :] = dq.T.astype(dq_ref.dtype)
            else:
                dq_acc[:, rows[i]] += dq

    # the diagonal's region with ``causal``, else the first: traced apart
    # from the loop, for the pairs it leaves out or for ``first`` alone
    region(kj if causal else 0, diagonal=causal, first=True)
    if whole:
        return

    def body(qi, carry):
        region(qi, diagonal=False, first=False)
        return carry

    jax.lax.fori_loop(kj + 1 if causal else 1, seq_len // span, body, 0)
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)


def _backward_compiler_params(s, d, span, block_q, block_k, itemsize):
    """The region axis is a reduction into ``dq_acc`` (sequential); the
    VMEM asked for covers a head's q, dO and dQ and a region's K, V, dK
    and dV (double-buffered), the float32 accumulators and the
    score-tile temporaries of a region's pairs, which at the largest
    candidate tiles pass the 16 MiB a kernel gets unasked."""
    lanes = -(-d // LANES) * LANES
    resident = (3 * 2 * itemsize + 4) * s * lanes \
        + 2 * (4 * itemsize + 4) * span * lanes
    tiles = 6 * 4 * max(block_q * block_k, span * span // 4)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(min(_MOST_VMEM,
                                 max(16 * 1024 * 1024,
                                     2 * (resident + tiles)))))


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal",
                                             "interpret", "block_q",
                                             "block_k"))
def _flash_attention_bwd_jit(q, k, v, mask, o, lse, do, sm_scale, causal,
                             interpret, block_q, block_k):
    b, h, s, d = q.shape
    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)
    dor = do.reshape(b * h, s, d)
    # the row residuals travel as rows [B*H, 1, S] (S on lanes, where
    # the transposed score tile wants them), not broadcast over lanes
    lser = lse.reshape(b * h, 1, s).astype(jnp.float32)
    # D = rowsum(dO * O): cheap XLA elementwise reduce
    dr = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1).reshape(b * h, 1, s)

    span = _bwd_span(s, block_q, block_k)
    head = lambda bh, kj: (bh, 0, 0)          # noqa: E731
    keys = lambda bh, kj: (bh, kj, 0)         # noqa: E731
    in_specs = [
        pl.BlockSpec((1, s, d), head),
        pl.BlockSpec((1, span, d), keys),
        pl.BlockSpec((1, span, d), keys),
        pl.BlockSpec((1, s, d), head),
        pl.BlockSpec((1, 1, s), head),
        pl.BlockSpec((1, 1, s), head),
    ]
    args = [qr, kr, vr, dor, lser, dr]
    body = functools.partial(_bwd_kernel, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k, span=span,
                             seq_len=s, causal=causal)
    if mask is not None:  # jit-ok: structural None-check, not a traced read
        # per KEY, so a column of the transposed tile: [B, S, 1]
        in_specs.append(pl.BlockSpec(
            (1, span, 1), lambda bh, kj, _h=h: (bh // _h, kj, 0)))
        args.append(_mask_rows(mask, b, h, s).reshape(b, s, 1))
        kernel = body
    else:
        def kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, *rest):
            body(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, None, *rest)

    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), v.dtype)],
        grid=(b * h, s // span),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, s, d), head),
                   pl.BlockSpec((1, span, d), keys),
                   pl.BlockSpec((1, span, d), keys)],
        # float32 sums across regions: dQ^T a head, dK and dV a program
        scratch_shapes=[] if span == s else [
            pltpu.VMEM((d, s), jnp.float32),
            pltpu.VMEM((span, d), jnp.float32),
            pltpu.VMEM((span, d), jnp.float32)],
        compiler_params=_backward_compiler_params(
            s, d, span, block_q, block_k, q.dtype.itemsize),
        interpret=interpret,
    )(*args)
    shape = (b, h, s, d)
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))


def flash_attention_bwd(q, k, v, mask, o, lse, do, sm_scale=1.0,
                        causal=False, interpret=None):
    """(dq, dk, dv) via the fused recompute-form kernel. ``lse`` is the
    forward's logsumexp (flash_attention_with_lse). Block sizes tune
    independently of the forward's; with ``causal`` the tiles also
    decide how much of the square the walk skips (``bwd_walk_counts``,
    recorded here at trace time as a ``flash_bwd_walk`` instant)."""
    if interpret is None:
        interpret = INTERPRET
    b, h, s, d = q.shape
    block_q, block_k = _tuned_block_sizes(
        "bwd", b, h, s, d, q.dtype, sm_scale, causal, mask is not None,
        interpret)
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "flash_bwd_walk", seq=s, head_dim=d, block_q=block_q,
        block_k=block_k, causal=bool(causal),
        **bwd_walk_counts(s, block_q, block_k, causal))
    return _flash_attention_bwd_jit(q, k, v, mask, o, lse, do, sm_scale,
                                    causal, interpret, block_q, block_k)
