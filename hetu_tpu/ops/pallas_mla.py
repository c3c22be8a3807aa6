"""Absorbed latent (MLA) attention for a decode step, as a Pallas TPU
kernel.

In a latent cache a token's row is ``[c ; k_r]``: the normed latent
(512 wide in the published models) and ONE rotated key (64) that all
heads share. With the key and value up-projections absorbed into the
query and the output (``ops/attention.py:mla_decode_attention``), every
head scores against the same rows: a decode step's attention is
``softmax([q~ ; q_r] [c ; k_r]^T) c`` with 64 heads as the ROWS of one
matmul. The kernel streams the gathered rows of one sequence through
VMEM a block at a time with a running (max, sum, accumulator), reads
each row once for scores and context both, and skips the blocks past
the sequence's position: their index is clamped to the last block that
holds a row, so the pipeline fetches nothing new for them.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the kernel's events are called in a profile; the jitted function
# carries the name (``ops/pallas_norm.py`` says why).
KERNEL_NAME = "hetu_mla_decode"
NEG_INF = -1e30
LANES = 128
BLOCK_K = 512

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def supported(latent, rope, context):
    """Whole-lane latent, a rope part the MXU contracts (a multiple of
    8), and a context of whole blocks; anything else takes the composed
    form."""
    return (latent % LANES == 0 and rope % 8 == 0
            and context % min(BLOCK_K, context) == 0 and context % 8 == 0)


def _body(pos_ref, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref, *,
          sm_scale, block_k, latent):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    pos = pos_ref[b]

    @pl.when(j * block_k <= pos)
    def _block():
        c = kv_ref[0, :, :latent]                    # [block_k, latent]
        s = jax.lax.dot_general(
            q_ref[0, :, :latent], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(
            q_ref[0, :, latent:], kv_ref[0, :, latent:],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s * sm_scale, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _end():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _decode(q, rows, positions, *, sm_scale, latent, interpret):
    b, heads, width = q.shape
    context = rows.shape[1]
    block_k = min(BLOCK_K, context)
    body = functools.partial(_body, sm_scale=sm_scale, block_k=block_k,
                             latent=latent)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((b, heads, latent), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, context // block_k),
            in_specs=[
                pl.BlockSpec((1, heads, width),
                             lambda i, j, pos: (i, 0, 0)),
                # past the sequence's last block: the same block again,
                # which the pipeline does not fetch a second time
                pl.BlockSpec((1, block_k, width), lambda i, j, pos: (
                    i, jnp.minimum(j, pos[i] // block_k), 0)),
            ],
            out_specs=pl.BlockSpec((1, heads, latent),
                                   lambda i, j, pos: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, latent), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(positions, q, rows)


@functools.lru_cache(maxsize=None)
def _jitted(sm_scale, latent, interpret):
    def hetu_mla_decode(q, rows, positions):
        return _decode(q, rows, positions, sm_scale=sm_scale,
                       latent=latent, interpret=interpret)

    hetu_mla_decode.__name__ = hetu_mla_decode.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_mla_decode)


def mla_decode(q, rows, positions, sm_scale, latent):
    """``softmax(q rows^T * sm_scale) rows[..., :latent]`` per head,
    over the rows ``j <= positions[b]``.

    ``q`` ``[B, heads, latent + rope]`` (the absorbed query beside the
    rotated one), ``rows`` ``[B, S, latent + rope]`` (one sequence's
    cache rows in position order), ``positions`` ``[B]`` int32.
    Returns ``[B, heads, latent]`` in ``q``'s dtype."""
    return _jitted(float(sm_scale), int(latent), INTERPRET)(
        q, rows, positions.astype(jnp.int32))
