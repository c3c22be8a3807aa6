"""Differential attention of a decode step over rows in position order,
as a Pallas TPU kernel.

``ops/attention.py:diff_rows_attention`` lays every query head over a
whole cache row (zeros but for its own key head's lanes), so that the
scores of all heads and both maps are ONE product with the rows as they
lie, and each map's product with its value is one product with the
``v`` rows of which a head keeps its key pair's lanes. Composed, XLA
reads every row of the context BUCKET for it, whatever a sequence's own
length, and writes the scores and the weights between the two products.
The kernel streams one sequence's gathered ``k`` and ``v`` rows through
VMEM a block at a time with a running (max, sum, accumulator) and SKIPS
the blocks past the sequence's position: their index is clamped to the
last block that holds a row, so the pipeline fetches nothing new for
them (``ops/pallas_mla.py`` is the pattern). A step's rows are then
read to each sequence's own length, a block rounded.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the kernel's events are called in a profile; the jitted function
# carries the name (``ops/pallas_norm.py`` says why).
KERNEL_NAME = "hetu_diff_attn_decode"
NEG_INF = -1e30
LANES = 128
SUBLANES = 8
BLOCK_K = 512

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def supported(heads, width, context):
    """Query heads in whole sublane tiles, rows of whole lanes and a
    context of whole blocks; anything else takes the composed form."""
    return (heads % SUBLANES == 0 and width % LANES == 0
            and context % min(BLOCK_K, context) == 0 and context % 8 == 0)


def _interpret():
    """Off a TPU a kernel can only be interpreted (a rehearsal steers
    ``_use_pallas`` to the kernels on any backend)."""
    return INTERPRET or jax.default_backend() != "tpu"


def _body(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
          block_k):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pos = pos_ref[b]

    @pl.when(j * block_k <= pos)
    def _block():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [heads, block_k]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        o_ref[0] = acc_ref[...] / l_ref[:, :1]


def _decode(q, k_rows, v_rows, positions, *, interpret):
    b, heads, width = q.shape
    context = k_rows.shape[1]
    block_k = min(BLOCK_K, context)
    # past the sequence's last block: the same block again, which the
    # pipeline does not fetch a second time
    rows = pl.BlockSpec((1, block_k, width), lambda i, j, pos: (
        i, jnp.minimum(j, pos[i] // block_k), 0))
    return pl.pallas_call(
        functools.partial(_body, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((b, heads, width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, context // block_k),
            in_specs=[pl.BlockSpec((1, heads, width),
                                   lambda i, j, pos: (i, 0, 0)),
                      rows, rows],
            out_specs=pl.BlockSpec((1, heads, width),
                                   lambda i, j, pos: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(positions, q, k_rows, v_rows)


@functools.lru_cache(maxsize=None)
def _jitted(interpret):
    def hetu_diff_attn_decode(q, k_rows, v_rows, positions):
        return _decode(q, k_rows, v_rows, positions, interpret=interpret)

    hetu_diff_attn_decode.__name__ = \
        hetu_diff_attn_decode.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_diff_attn_decode)


def diff_decode(q, k_rows, v_rows, positions):
    """``softmax(q k_rows^T) v_rows`` a query row, over the rows ``j <=
    positions[b]``: ``q [B, heads, W]`` (each head scaled and laid over
    a whole row), ``k_rows`` / ``v_rows [B, S, W]`` (one sequence's
    cache rows in position order), ``positions [B]`` int32. Returns
    ``[B, heads, W]`` float32."""
    return _jitted(_interpret())(
        q, k_rows, v_rows, positions.astype(jnp.int32))
