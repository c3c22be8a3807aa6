"""Pallas TPU flash-attention kernels (forward + fused backward).

No reference equivalent (the reference composes attention from cublas
batch-matmuls, examples/nlp/bert/hetu_bert.py:191-227). Forward is the
blocked online-softmax kernel: per (batch*head, q-block) program, stream
K/V blocks through VMEM keeping a running (max, sum, accumulator) — the
[S, S] score matrix never exists in HBM, so attention memory is O(S·D)
instead of O(S²) and the MXU stays fed from VMEM.

Backward is the standard recompute form: the forward also emits the
per-row logsumexp L, and two kernels rebuild score blocks in VMEM —
one gridded over K blocks producing dK/dV, one over Q blocks producing
dQ — so the S×S matrices never exist in HBM on the backward pass either
(the property training needs for long context; D = rowsum(dO ∘ O) is a
cheap XLA elementwise reduce outside the kernels).

Block sizes are AUTOTUNED per (platform, kernel, S, D, dtype, causal,
mask): bq/bk sweep {128, 256, 512, 1024} (clipped to divisors of S)
independently for the forward, the forward-with-lse and the fused
backward through ``hetu_tpu/tune`` — the sweep runs once at first
compile of a shape, the winner persists in the autotune JSON cache, and
``HETU_AUTOTUNE=0`` falls back to the static ``_block_sizes`` defaults
(bq≤256, bk≤512). The backward keeps a full K/V block resident across
its whole q-loop, so its best tiles differ from the forward's — that
per-direction freedom is the point of tuning the three kernels apart.
Batch/heads are NOT in the key (they only size the embarrassingly
parallel grid axis; per-program work is S/D-shaped): the sweep times
the first caller's b/h and later batch sizes share that winner.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_bwd", "tune_key"]

NEG_INF = -1e30
LANES = 128      # TPU minor-dim tile: residual vectors store lane-tiled
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
    return "flash_" + kind, key


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
# fused backward (recompute form)
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, mask_ref,
                    dk_ref, dv_ref, *, sm_scale, block_q, block_k,
                    seq_len, causal):
    kj = pl.program_id(1)
    k = k_ref[0]                              # [block_k, d]
    v = v_ref[0]
    num_qb = seq_len // block_q
    start = (kj * block_k) // block_q if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = l_ref[0, pl.ds(i * block_q, block_q), 0:1][:, 0]
        dd = d_ref[0, pl.ds(i * block_q, block_q), 0:1][:, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if mask_ref is not None:
            s = s + mask_ref[0, 0, pl.ds(kj * block_k, block_k)][None, :]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])         # f32 [block_q, block_k]
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None]) * sm_scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, num_qb, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, mask_ref,
                   dq_ref, *, sm_scale, block_q, block_k, seq_len,
                   causal):
    qi = pl.program_id(1)
    q = q_ref[0]                              # [block_q, d]
    do = do_ref[0]
    lse = l_ref[0, :, 0:1][:, 0]              # [block_q] (lane-tiled in)
    dd = d_ref[0, :, 0:1][:, 0]
    num_kb = seq_len // block_k
    if causal:
        num_kb = jnp.minimum(num_kb,
                             pl.cdiv((qi + 1) * block_q, block_k))

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if mask_ref is not None:
            s = s + mask_ref[0, 0, pl.ds(j * block_k, block_k)][None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dd[:, None]) * sm_scale
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, num_kb, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal",
                                             "interpret", "block_q",
                                             "block_k"))
def _flash_attention_bwd_jit(q, k, v, mask, o, lse, do, sm_scale, causal,
                             interpret, block_q, block_k):
    b, h, s, d = q.shape
    grid_kv = (b * h, s // block_k)
    grid_q = (b * h, s // block_q)

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)
    dor = do.reshape(b * h, s, d)
    # residual vectors travel lane-tiled (TPU 128-lane minors)
    lser = jnp.broadcast_to(lse.reshape(b * h, s)[:, :, None],
                            (b * h, s, LANES))
    # D = rowsum(dO * O): cheap XLA reduce, shared by both kernels
    dr = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1).reshape(b * h, s)[:, :, None],
        (b * h, s, LANES))

    full = lambda bh, i: (bh, 0, 0)         # noqa: E731
    in_specs_kv = [
        pl.BlockSpec((1, s, d), full),
        pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
        pl.BlockSpec((1, s, d), full),
        pl.BlockSpec((1, s, LANES), full),
        pl.BlockSpec((1, s, LANES), full),
    ]
    in_specs_q = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, s, d), full),
        pl.BlockSpec((1, s, d), full),
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda bh, qi: (bh, qi, 0)),
    ]
    args = [qr, kr, vr, dor, lser, dr]
    if mask is not None:  # jit-ok: structural None-check, not a traced read
        mrow = _mask_rows(mask, b, h, s)
        mask_spec = pl.BlockSpec((1, 1, s),
                                 lambda bh, i, _h=h: (bh // _h, 0, 0))
        in_specs_kv.append(mask_spec)
        in_specs_q.append(mask_spec)
        args = args + [mrow]
        kv_kernel = functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, seq_len=s, causal=causal)
        q_kernel = functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, seq_len=s, causal=causal)
    else:
        def kv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                      dk_ref, dv_ref):
            _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                            None, dk_ref, dv_ref, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k, seq_len=s,
                            causal=causal)

        def q_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref):
            _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
                           None, dq_ref, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k, seq_len=s,
                           causal=causal)

    dk, dv = pl.pallas_call(
        kv_kernel,
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), v.dtype)],
        grid=grid_kv,
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kj: (bh, kj, 0)),
        ],
        interpret=interpret,
    )(*args)
    dq = pl.pallas_call(
        q_kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        grid=grid_q,
        in_specs=in_specs_q,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi: (bh, qi, 0)),
        interpret=interpret,
    )(*args)
    shape = (b, h, s, d)
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))


def flash_attention_bwd(q, k, v, mask, o, lse, do, sm_scale=1.0,
                        causal=False, interpret=None):
    """(dq, dk, dv) via the fused recompute-form kernels. ``lse`` is the
    forward's logsumexp (flash_attention_with_lse). Block sizes tune
    independently of the forward's: the dK/dV kernel holds one K/V block
    resident across its whole q-loop, so it generally wants smaller bq /
    larger bk tiles than the forward at long S."""
    if interpret is None:
        interpret = INTERPRET
    b, h, s, d = q.shape
    block_q, block_k = _tuned_block_sizes(
        "bwd", b, h, s, d, q.dtype, sm_scale, causal, mask is not None,
        interpret)
    return _flash_attention_bwd_jit(q, k, v, mask, o, lse, do, sm_scale,
                                    causal, interpret, block_q, block_k)
