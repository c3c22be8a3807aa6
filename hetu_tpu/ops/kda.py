"""The delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) for serving.

A head of a sequence carries a MATRIX state ``S [d_k, d_v]``; a token
decays each key channel of it by ``a_t = exp(g_t)`` (``g_t <= 0``),
takes out what the state already answers to its key and writes the
value in, with strength ``b_t``::

    S'  = diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Everything is float32, the matmuls at the highest precision. The state
lies VALUE-MAJOR, ``S^T [d_v, d_k]``, in a program and in the cache's
slots alike: the decay and both products with ``k`` and ``q`` then run
along lanes. Two calls:

* :func:`kda_chunk` — many tokens a row (a prompt, or a chunk of one):
  the CHUNKED form. The rank-one correction ``(I - b k k^T)`` keeps a
  prefill from being an associative scan; inside a chunk of ``CHUNK``
  tokens the corrected values ``u`` solve a unit-lower-triangular
  system ``(I + diag(b) A) u = diag(b) (v - decayed K S_0)`` with ``A_ij
  = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``, and between chunks the
  state is carried on. ``exp(G_i - G_j)`` is never taken apart into
  ``exp(G_i) exp(-G_j)`` over a whole chunk (128 tokens at ``g = -5``
  would be ``e^640``): the chunk is cut into sub-blocks of ``SUB``
  tokens, a pair in one sub-block is referred to the block's start
  (``SUB x |g| <= 80`` stays inside float32) and a pair across blocks
  to the later block's start (both factors at most 1). The triangular
  inverse is exact products of nilpotent matrices: inside the diagonal
  sub-blocks ``(I - L)(I + L^2)(I + L^4)...`` (powers below ``SUB``),
  across them the same over block-lower-triangular ``M``. Takes each
  row's count of REAL tokens (a prompt is right-padded to its bucket)
  and returns the state AT THE LAST REAL TOKEN: past it the decay is 1
  and ``b`` is 0. Nothing of ``[T, d_k, d_v]`` is ever materialised:
  on a TPU a Pallas kernel (``hetu_kda_chunk``) walks a (row, head)'s
  chunks with the state in VMEM.
* :func:`kda_step` — one token a row (a decode step) against the
  cache's slots ``[slots, layers, heads, d_v, d_k]``: on a TPU a Pallas
  kernel (``hetu_kda_step``) reads each row's slot of the layer once and
  writes it once, in place (the pool is aliased to the result; the
  layer is an index the kernel takes).

Elsewhere, and for shapes the kernels do not take (:func:`supported`),
the composed ``jax.numpy`` form of the same arithmetic runs (the chunk
kernel's body IS the composed form's, a (row, head, chunk) at a time).
A traced call says which in a ``kda_plan`` instant.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _use_pallas

__all__ = ["kda_chunk", "kda_step", "supported", "CHUNK_NAME", "STEP_NAME"]

# what the kernels' events are called in a profile
# (``ops/pallas_norm.py`` says why the jitted function carries the name)
CHUNK_NAME = "hetu_kda_chunk"
STEP_NAME = "hetu_kda_step"

LANES = 128
# tokens of a chunk: what the triangular system is solved over and the
# state is carried across (4,096 tokens x 32 heads of 128 on a v5e: 6.11
# ms at 128, 7.10 at 64; my chip run, PR 54)
CHUNK = 128
# the least log-decay a token may have unless the caller states its
# model's own bound on ``g``: decides the sub-block a chunk's pairs are
# referred inside
MIN_LOG_DECAY = -5.0
# float32 holds e^88; a sub-block's ``exp(-G)`` stays under e^80
_EXP_ROOM = 80.0

# tests flip this to exercise the kernels without a TPU backend
INTERPRET = False

_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))      # [m, k] [k, n]
_NT = (((1,), (1,)), ((), ()))      # [m, k] [n, k]
_TN = (((0,), (0,)), ((), ()))      # [k, m] [k, n]


def _sub_block(chunk, min_log_decay=MIN_LOG_DECAY):
    """Tokens of a sub-block: the largest power of two (16 at most, and
    a divisor of the chunk) whose whole decay stays inside float32."""
    room = _EXP_ROOM / max(abs(float(min_log_decay)), 1e-6)
    sub = min(16, 1 << max(0, int(math.floor(math.log2(max(room, 1.0))))))
    while chunk % sub:
        sub //= 2
    return sub


def supported(d_k, d_v):
    """``None`` where the kernels take heads of ``d_k`` key and ``d_v``
    value channels, else why not."""
    if d_k != LANES or d_v != LANES:
        return "a head is not one whole lane block each way"
    return None


def _interpret():
    """Off a TPU a kernel can only be interpreted (a rehearsal steers
    ``_use_pallas`` to the kernels on any backend)."""
    return INTERPRET or jax.default_backend() != "tpu"


def _plan(op, why):
    """The form a traced call runs in, and the ``kda_plan`` instant
    that says so (once a traced call, never in a steady-state step)."""
    if not (_use_pallas() or INTERPRET):
        why = "platform"
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "kda_plan", op=op, form="composed" if why else "kernel",
        **({"reason": why} if why else {}))
    return not why


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


# ---------------------------------------------------------------------------
# one chunk of one head: the arithmetic both forms run
# ---------------------------------------------------------------------------

def _nilpotent_inverse(eye, low, index):
    """``(I + low)^-1`` of a matrix whose ``index``-th power is zero:
    ``(I - L)(I + L^2)(I + L^4)...``, exactly."""
    inv, power, reach = eye - low, low, 2
    while reach < index:
        power = _dot(power, power)
        inv = _dot(inv, eye + power)
        reach *= 2
    return inv


def _chunk_math(q, k, kb, vb, g, st, sub):
    """One chunk: ``q``, ``k``, ``kb = b k``, ``g`` ``[C, d_k]``, ``vb
    = b v [C, d_v]``, the state ``st [d_v, d_k]`` (value-major) the
    chunk starts from. Returns ``(o [C, d_v], the state after it)``."""
    c, dk = k.shape
    blocks = c // sub
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (row // sub) == (col // sub)
    eye = (row == col).astype(_F32)

    def block(a, j):
        return a[j * sub:(j + 1) * sub]

    # the log-decay summed from a sub-block's start through each token,
    # a sub-block's whole decay, and the sum from the CHUNK's start
    local = _dot(jnp.where(same & (col <= row), 1.0, 0.0).astype(_F32), g)
    whole = [block(local, j)[sub - 1:] for j in range(blocks)]
    before, so_far = [], jnp.zeros((1, dk), _F32)
    for j in range(blocks):
        before.append(so_far)
        so_far = so_far + whole[j]
    total = so_far
    since_start = local + jnp.concatenate(
        [jnp.broadcast_to(b, (sub, dk)) for b in before], axis=0)

    # A's rows of sub-block i against the columns of blocks j <= i,
    # referred to block i's start: both factors of an earlier block's
    # pair are at most 1, a pair inside the block reaches e^80 at most
    decayed_in = jnp.exp(local)
    kb_in, q_in = kb * decayed_in, q * decayed_in
    a_b, a_q = [], []
    for i in range(blocks):
        cols, since = [], jnp.zeros((1, dk), _F32)
        for j in range(i, -1, -1):
            cols.insert(0, block(k, j) * jnp.exp(since - block(local, j)))
            if j:
                since = since + whole[j - 1]
        if i + 1 < blocks:
            cols.append(jnp.zeros(((blocks - i - 1) * sub, dk), _F32))
        both = _dot(jnp.concatenate([block(kb_in, i), block(q_in, i)],
                                    axis=0),
                    jnp.concatenate(cols, axis=0), _NT)
        a_b.append(both[:sub])
        a_q.append(both[sub:])
    low = jnp.where(col < row, jnp.concatenate(a_b, axis=0), 0.0)
    a_q = jnp.where(col <= row, jnp.concatenate(a_q, axis=0), 0.0)

    # (I + low)^-1: the diagonal sub-blocks first, then across them
    inside = jnp.where(same, low, 0.0)
    inv = _nilpotent_inverse(eye, inside, sub)
    if blocks > 1:
        across = _dot(inv, low - inside)
        inv = _dot(_nilpotent_inverse(eye, across, blocks), inv)

    from_start = jnp.exp(since_start)
    solved = _dot(inv, jnp.concatenate([kb * from_start, vb], axis=1))
    w, u = solved[:, :dk], solved[:, dk:]
    read = _dot(jnp.concatenate([w, q * from_start], axis=0), st, _NT)
    u = u - read[:c]
    o = read[c:] + _dot(a_q, u)
    st = st * jnp.exp(total) + _dot(
        u, k * jnp.exp(total - since_start), _TN)
    return o, st


def _chunk_composed(q, k, kb, vb, g, s0, chunk, sub):
    """``[B, T, H, d]`` arrays, ``T`` whole chunks: a ``lax.scan`` over
    the chunks of :func:`_chunk_math` a (row, head)."""
    rows, t, heads, _ = q.shape

    def chunks(a):      # [chunks, B, H, C, d]
        return a.reshape(rows, t // chunk, chunk, heads, -1).transpose(
            1, 0, 3, 2, 4)

    math_ = jax.vmap(jax.vmap(
        functools.partial(_chunk_math, sub=sub)))

    def body(st, step):
        o, st = math_(*step, st)
        return st, o

    st, o = jax.lax.scan(body, s0, tuple(map(chunks, (q, k, kb, vb, g))))
    return o.transpose(1, 0, 3, 2, 4).reshape(rows, t, heads, -1), st


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _chunk_body(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref, s_ref,
                *, sub):
    """Grid ``(row, head, chunk)``: the head's state stays in ``s_ref``
    (VMEM) across the row's chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    o, st = _chunk_math(q_ref[0], k_ref[0], kb_ref[0], vb_ref[0], g_ref[0],
                        s_ref[0, 0], sub)
    o_ref[0] = o
    s_ref[0, 0] = st


def _chunk_kernel(q, k, kb, vb, g, s0, *, chunk, sub, interpret):
    """``q`` ... ``g`` ``[B, T, H x 128]`` (a head a lane block), ``s0
    [B, H, 128, 128]``."""
    rows, t, width = q.shape
    heads = width // LANES
    tokens = pl.BlockSpec((1, chunk, LANES), lambda r, h, c: (r, c, h))
    state = pl.BlockSpec((1, 1, LANES, LANES), lambda r, h, c: (r, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_body, sub=sub),
        out_shape=(jax.ShapeDtypeStruct(q.shape, _F32),
                   jax.ShapeDtypeStruct(s0.shape, _F32)),
        grid=(rows, heads, t // chunk),
        in_specs=[tokens] * 5 + [state],
        out_specs=(tokens, state),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, kb, vb, g, s0)


def _step_math(st, q, k, kb, v, decay, eye):
    """One token of one head: the state ``st [d_v, d_k]`` and ROWS
    ``[1, d]``; ``decay = expm1(g)``: the state loses ``-decay`` of
    itself (a slow channel's ``exp(g)`` rounds to the same float32
    short of 1 token after token, and a thousand steps would add the
    rounding up; the small loss is exact to its own last bit). A
    column is made of a row (and a row of a column) by a masked
    reduction over the diagonal. Returns ``(o [1, d_v], st)``."""
    st = st + st * decay
    answered = jnp.sum(st * k, axis=1, keepdims=True)
    v_col = jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)
    st = st + (v_col - answered) * kb
    o_col = jnp.sum(st * q, axis=1, keepdims=True)
    return jnp.sum(jnp.where(eye, o_col, 0.0), axis=0, keepdims=True), st


def _step_body(slots_ref, layer_ref, q_ref, k_ref, kb_ref, v_ref, decay_ref,
               pool_ref, o_ref, out_ref):
    """Grid ``(row,)``: the row's slot of the layer comes on chip, every
    head takes one token and it goes back to where it lay."""
    del slots_ref, layer_ref    # the block specs' index maps read them
    heads = q_ref.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    for h in range(heads):
        at = slice(h, h + 1)
        o, st = _step_math(pool_ref[0, 0, h], q_ref[0, at], k_ref[0, at],
                           kb_ref[0, at], v_ref[0, at], decay_ref[0, at], eye)
        out_ref[0, 0, h] = st
        o_ref[0, at] = o


def _step_kernel(pool, slots, layer, q, k, kb, v, decay, *, interpret):
    rows, heads, _ = q.shape
    token = pl.BlockSpec((1, heads, LANES), lambda r, slots, layer: (r, 0, 0))
    slot = pl.BlockSpec(
        (1, 1, heads, LANES, LANES),
        lambda r, slots, layer: (slots[r], layer[0], 0, 0, 0))
    return pl.pallas_call(
        _step_body,
        out_shape=(jax.ShapeDtypeStruct(q.shape, _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows,),
            in_specs=[token] * 5 + [slot],
            out_specs=(token, slot)),
        # the pool (the 8th operand, slots and layer counted) IS the result
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's slot of a layer in and out, each double-buffered
            vmem_limit_bytes=max(
                32 << 20, 6 * heads * LANES * LANES * 4)),
        interpret=interpret,
    )(slots, layer.reshape(1).astype(jnp.int32), q, k, kb, v, decay, pool)


@functools.lru_cache(maxsize=None)
def _jitted_chunk(chunk, sub, interpret):
    def hetu_kda_chunk(q, k, kb, vb, g, s0):
        return _chunk_kernel(q, k, kb, vb, g, s0, chunk=chunk, sub=sub,
                             interpret=interpret)

    hetu_kda_chunk.__name__ = hetu_kda_chunk.__qualname__ = CHUNK_NAME
    return jax.jit(hetu_kda_chunk)


@functools.lru_cache(maxsize=None)
def _jitted_step(interpret):
    def hetu_kda_step(pool, slots, layer, q, k, kb, v, decay):
        return _step_kernel(pool, slots, layer, q, k, kb, v, decay,
                            interpret=interpret)

    hetu_kda_step.__name__ = hetu_kda_step.__qualname__ = STEP_NAME
    return jax.jit(hetu_kda_step)


# ---------------------------------------------------------------------------
# what a mixer calls
# ---------------------------------------------------------------------------

def kda_chunk(q, k, v, g, beta, s0, lengths, min_log_decay=MIN_LOG_DECAY):
    """``(o [B, T, H, d_v], S [B, H, d_v, d_k])``, float32: the
    recurrence over ``q``, ``k`` ``[B, T, H, d_k]``, ``v [B, T, H,
    d_v]`` with the log-decay ``g [B, T, H, d_k]`` (``min_log_decay <=
    g <= 0``), the strength ``beta [B, T, H]`` and the state ``s0 [B, H,
    d_v, d_k]`` each row starts from. ``lengths [B]`` is each row's
    count of real tokens: ``S`` is the state at the last of them
    (``s0`` for a row with none), and ``o`` past it means nothing."""
    rows, t, heads, dk = k.shape
    dv = v.shape[-1]
    chunk = CHUNK
    sub = _sub_block(chunk, min_log_decay)
    real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
    b = jnp.where(real, beta.astype(_F32), 0.0)[..., None]
    k = k.astype(_F32)
    parts = [q.astype(_F32), k, k * b, v.astype(_F32) * b,
             jnp.where(real[..., None], g.astype(_F32), 0.0)]
    pad = -t % chunk
    if pad:     # whole chunks: a token of b = 0 and no decay moves nothing
        parts = [jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                 for a in parts]
    s0 = s0.astype(_F32)
    if _plan("chunk", supported(dk, dv)):
        o, s = _jitted_chunk(chunk, sub, _interpret())(
            *(a.reshape(rows, t + pad, heads * dk) for a in parts), s0)
        o = o.reshape(rows, t + pad, heads, dv)
    else:
        o, s = _chunk_composed(*parts, s0, chunk, sub)
    return o[:, :t], s


def kda_step(pool, slots, layer, q, k, v, g, beta):
    """One token a row against the slots: ``(o [B, H, d_v] float32,
    pool)`` from ``pool [slots, layers, H, d_v, d_k]`` float32 (a slot
    holds a sequence's state of every delta-rule layer), ``slots [B]``
    int32 (padded rows name the scratch slot, which takes their
    writes), the ``layer`` this is (an int32 scalar, traced or not),
    ``q``, ``k``, ``g`` ``[B, H, d_k]``, ``v [B, H, d_v]`` and ``beta
    [B, H]``. Donate the pool: the kernel updates it in place."""
    layer = jnp.asarray(layer, jnp.int32)
    q, k, v = (a.astype(_F32) for a in (q, k, v))
    kb = k * beta.astype(_F32)[..., None]
    decay = jnp.expm1(g.astype(_F32))
    if _plan("step", supported(k.shape[-1], v.shape[-1])):
        return _jitted_step(_interpret())(pool, slots, layer, q, k, kb, v,
                                          decay)
    st = pool[slots, layer]
    st = st + st * decay[..., None, :]
    answered = jnp.sum(st * k[..., None, :], axis=-1)
    st = st + (v - answered)[..., None] * kb[..., None, :]
    return (jnp.sum(st * q[..., None, :], axis=-1),
            pool.at[slots, layer].set(st))
