"""LayerNorm's backward in one pass over the rows, as a Pallas TPU kernel.

The composed form (``ops/norm.py:layer_norm_backward_reference``) asks
XLA for two reductions over the last axis and two over ALL leading
axes, with the accumulators in the inputs' dtype. Here a block of rows
comes on chip once: its statistics are recomputed in float32 (a whole
row is inside the block, so the forward saves nothing), ``dx`` for the
block is written, and the block's part of ``dscale`` and ``dbias`` is
added to a float32 ``[8, D]`` accumulator that stays in VMEM across the
sequential row-block axis and is cast to the parameters' dtype once,
after the last block. ``x`` and ``dy`` are read once, ``dx`` is written
once.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# What the kernel's events are called in a profile. A jitted function
# that a program calls many times is one function of the module, and its
# instructions are named for it, not for the pallas_call inside: so the
# function below carries the same name (the flash kernels' events are
# ``_flash_attention_jit`` for that reason).
KERNEL_NAME = "hetu_layer_norm_bwd"
# Half of the 16 MiB of VMEM a kernel gets without asking for more (the
# flash kernels ask for none either); the other half is the compiler's.
VMEM_BUDGET = 8 * 1024 * 1024
# float32 [rows, D] values alive at once in the body: x, dy, xhat, dxhat
# and one product on its way into a reduction.
F32_TEMPORARIES = 5

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def _tile_rows(itemsize):
    """Rows of one packed sublane tile: 8 of float32, 16 of bfloat16."""
    return SUBLANES * max(1, 4 // itemsize)


def _row_bytes(d, itemsize):
    """VMEM one row of a block costs: two input tiles and one output
    tile, each double-buffered by the pipeline, and the float32
    temporaries."""
    return d * (3 * 2 * itemsize + F32_TEMPORARIES * 4)


def supported(d, itemsize):
    """The kernel tiles the last axis by whole lanes, and the smallest
    block (one sublane tile of rows) has to fit ``VMEM_BUDGET``; any
    other width takes the composed form."""
    return (d >= LANES and d % LANES == 0
            and _tile_rows(itemsize) * _row_bytes(d, itemsize)
            <= VMEM_BUDGET)


def block_rows(n, d, itemsize):
    """Rows a block holds: the largest multiple of a sublane tile that
    fits ``VMEM_BUDGET`` — a function of ``D`` and the dtype's size
    alone — and no more than the rows there are."""
    tile = _tile_rows(itemsize)
    rows = VMEM_BUDGET // _row_bytes(d, itemsize) // tile * tile
    return min(rows, -(-n // tile) * tile)


def _kernel(dy_ref, x_ref, scale_ref, dx_ref, dscale_ref, dbias_ref,
            dscale_acc, dbias_acc, *, eps, rows, block):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if rows % block:
        # the last block reaches past the array: what it read there is
        # undefined, and must not reach the column sums
        row = i * block + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(row < rows, x, 0.0)
        dy = jnp.where(row < rows, dy, 0.0)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    xhat = xc * inv
    dxhat = dy * scale_ref[...].astype(jnp.float32)
    dx = inv * (dxhat - jnp.mean(dxhat, axis=-1, keepdims=True)
                - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        dscale_acc[...] = jnp.zeros_like(dscale_acc)
        dbias_acc[...] = jnp.zeros_like(dbias_acc)

    # [block, D] -> [block/8, 8, D] splits whole (8, 128) tiles, so the
    # sum is elementwise adds of vregs; the 8 partial rows meet once,
    # after the last block
    d = x.shape[-1]
    dscale_acc[...] += (dy * xhat).reshape(-1, SUBLANES, d).sum(axis=0)
    dbias_acc[...] += dy.reshape(-1, SUBLANES, d).sum(axis=0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dscale_ref[...] = dscale_acc[...].sum(
            axis=0, keepdims=True).astype(dscale_ref.dtype)
        dbias_ref[...] = dbias_acc[...].sum(
            axis=0, keepdims=True).astype(dbias_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def hetu_layer_norm_bwd(dy, x, scale, eps, interpret=False):
    """``(dx, dscale, dbias)`` of ``y = xhat * scale + bias`` over the
    last axis of ``x``; ``dx`` in ``x``'s dtype, the two sums
    accumulated in float32 and cast to ``scale``'s dtype once."""
    d = x.shape[-1]
    n = x.size // d
    block = block_rows(n, d, x.dtype.itemsize)
    # dy is taken as its producer makes it: without the barrier XLA
    # cancels the flattening below against the reshape behind the matmul
    # that produces dy and plans that matmul, and the head's, anew
    dy = jax.lax.optimization_barrier(dy)
    rows_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vector_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    vector = jax.ShapeDtypeStruct((1, d), scale.dtype)
    accumulator = pltpu.VMEM((SUBLANES, d), jnp.float32)
    dx, dscale, dbias = pl.pallas_call(
        functools.partial(_kernel, eps=eps, rows=n, block=block),
        out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype), vector, vector],
        grid=(pl.cdiv(n, block),),
        in_specs=[rows_spec, rows_spec, vector_spec],
        out_specs=[rows_spec, vector_spec, vector_spec],
        scratch_shapes=[accumulator, accumulator],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(dy.reshape(n, d), x.reshape(n, d), scale.reshape(1, d))
    return (dx.reshape(x.shape), dscale.reshape(scale.shape),
            dbias.reshape(scale.shape))
