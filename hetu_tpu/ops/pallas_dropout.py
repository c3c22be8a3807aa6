"""A dropout mask's keep decisions from the core's hardware generator,
as a Pallas TPU kernel that takes only a seed.

``jax.random.bernoulli`` on a threefry key spends 32 bits and some 55
integer vector operations on every element, inside whatever fusion
consumes the mask, and again in the backward. Here one kernel per mask
seeds the core's generator per block of rows, draws 32 bits a decision,
compares them unsigned against ``keep_prob * 2**32`` and writes one
byte a decision, 0 or 1. It has no tensor operand, so XLA plans the
fusions around it as it did around the composed draw; they read a byte
where they made the bits. The same seed and shape give the same bytes,
which is how the backward gets the forward's mask: by calling again.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows of one packed int8 tile
TILE_ROWS = 32
# What the kernel's events are called in a profile: the jitted function
# carries the pallas_call's name, as ``pallas_norm.hetu_layer_norm_bwd``
# does and for its reason.
KERNEL_NAME = "hetu_dropout_mask"
# Half of the 16 MiB of VMEM a kernel gets without asking for more.
VMEM_BUDGET = 8 * 1024 * 1024
# VMEM one decision costs: its 32 bits, the compare's result before it
# is packed, and the byte in the pipeline's two output buffers.
DECISION_BYTES = 4 + 4 + 2

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def supported(shape):
    """The mask is written as ``[rows, D]`` bytes: the last axis whole
    lanes, the flattened rows whole int8 tiles, and the smallest block
    inside ``VMEM_BUDGET``. Any other shape keeps the composed draw."""
    if len(shape) < 2:
        return False
    d, rows = shape[-1], math.prod(shape[:-1])
    return (d >= LANES and d % LANES == 0
            and rows >= TILE_ROWS and rows % TILE_ROWS == 0
            and TILE_ROWS * d * DECISION_BYTES <= VMEM_BUDGET)


def block_rows(n, d):
    """Rows a block holds: the largest multiple of an int8 tile that
    fits ``VMEM_BUDGET`` and no more than the rows there are — a
    function of the mask's shape alone, so that the forward's and the
    backward's call cut the rows alike."""
    return min(VMEM_BUDGET // (d * DECISION_BYTES) // TILE_ROWS * TILE_ROWS,
               n)


def threshold(keep_prob):
    """A decision keeps where its 32 bits are ``<=`` this: ``keep_prob``
    to 32 bits (``bernoulli``'s float32 uniform resolves 23), with 1.0
    keeping every element."""
    return max(1, min(2 ** 32, round(float(keep_prob) * 2 ** 32))) - 1


def _rotate(x, r):
    return jax.lax.shift_left(x, r) | jax.lax.shift_right_logical(x, 32 - r)


def _block_key(k0, k1, block):
    """The two words a block seeds the generator with (it takes no
    more): the block's index added into the mask's key and four rounds
    of threefry's mix over the pair, on the scalar unit, once a block.
    JAX's own ``pallas_tpu`` keys fold data in with one such round."""
    k1 = k1 + block
    for r in (13, 15, 26, 6):
        k0 = k0 + k1
        k1 = _rotate(k1, r) ^ k0
    return k0, k1


def _hashed_bits(k0, k1, shape):
    """What stands in for the generator under interpret mode, which has
    none (the generic interpreter has no rule for ``prng_seed``, the
    TPU one returns zeros): murmur3's finalizer, twice, over the
    element's index and the block's key. The tests of the kernel's
    arithmetic run on it; the generator's own bits can be checked only
    on the chip."""
    def fmix(x):
        x = x ^ jax.lax.shift_right_logical(x, 16)
        x = x * jnp.int32(0x85EBCA6B - 2 ** 32)
        x = x ^ jax.lax.shift_right_logical(x, 13)
        x = x * jnp.int32(0xC2B2AE35 - 2 ** 32)
        return x ^ jax.lax.shift_right_logical(x, 16)
    index = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
             + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return fmix(fmix(index ^ k0) + k1)


def _kernel(seed_ref, keep_ref, *, limit, interpret):
    k0, k1 = _block_key(seed_ref[0], seed_ref[1], pl.program_id(0))
    if interpret:
        bits = _hashed_bits(k0, k1, keep_ref.shape)
    else:
        pltpu.prng_seed(k0, k1)
        bits = pltpu.prng_random_bits(keep_ref.shape)
    bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    keep_ref[...] = (bits <= jnp.uint32(limit)).astype(keep_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("shape", "keep_prob", "interpret"))
def hetu_dropout_mask(seed, shape, keep_prob, interpret=False):
    """int8 ``shape`` of independent Bernoulli(``keep_prob``) decisions,
    1 = keep, a function of ``seed`` (two int32 words), ``shape`` and
    ``keep_prob`` alone."""
    d, n = shape[-1], math.prod(shape[:-1])
    block = block_rows(n, d)
    keep = pl.pallas_call(
        functools.partial(_kernel, limit=threshold(keep_prob),
                          interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.int8),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(n, block),), in_specs=[],
            out_specs=pl.BlockSpec((block, d), lambda i, seed: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(seed)
    return keep.reshape(shape)


def seed_words(key):
    """The two int32 words the kernel takes, from a PRNG key (the
    executor's per-step, per-op ``fold_in``)."""
    return jax.lax.bitcast_convert_type(
        jax.random.key_data(key), jnp.int32).reshape(-1)[:2]
