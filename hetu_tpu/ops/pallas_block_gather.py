"""A sequence's blocks of a paged pool brought into position order by
DMA, as a Pallas TPU kernel.

``pool[blocks]`` is XLA's gather: sized by the batch and the context
BUCKETS whatever a sequence holds (4.4 ms a step for the ``k`` and
``v`` of 16 x 16,384 rows of 1,280 bfloat16 where the sequences held
1.5k-9k, and a second copy to stack several layers' results; PR 60).
Here the scalar core walks each sequence's block table to the
sequence's OWN extent and starts one copy a block, from the pool to its
place in the result, both in HBM: no block passes through VMEM, the
copies of one sequence fly while the next one's are started, several
layers' pools come back stacked, and what lies past a sequence's extent
is never written (1.9 ms there: 277 GB/s, a 40 KB copy a block).

The rows past the extent hold NO DEFINED VALUE: a reader masks them by
SELECTING (``ops/pallas_diff_attention.py`` skips the blocks past a
position and selects inside the last), never by a product with zero.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the kernel's events are called in a profile; the jitted function
# carries the name (``ops/pallas_norm.py`` says why).
KERNEL_NAME = "hetu_block_gather"

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def _interpret():
    """Off a TPU a kernel can only be interpreted (a rehearsal steers
    ``_use_pallas`` to the kernels on any backend)."""
    return INTERPRET or jax.default_backend() != "tpu"


def _body(tables_ref, counts_ref, *refs, groups, per_seq):
    pools = sum(groups)
    srcs, dsts, sem = refs[:pools], refs[pools:-1], refs[-1]
    b = pl.program_id(0)
    # pool i is layer ``at[i][1]`` of result ``at[i][0]``
    at = [(g, i) for g, n in enumerate(groups) for i in range(n)]

    def copies(seq, j, block):
        for src, (g, i) in zip(srcs, at):
            yield pltpu.make_async_copy(src.at[block], dsts[g].at[i, seq, j],
                                        sem.at[0])

    def start(j, carry):
        for copy in copies(b, j, tables_ref[b * per_seq + j]):
            copy.start()
        return carry

    def wait(count):
        def one(_, carry):
            # a wait needs the copy's shape alone, not its blocks
            for copy in copies(0, 0, 0):
                copy.wait()
            return carry
        jax.lax.fori_loop(0, count, one, None)

    jax.lax.fori_loop(0, counts_ref[b], start, None)

    # the copies of the sequence before were in flight while this one's
    # were started; the last sequence waits for its own too
    @pl.when(b > 0)
    def _():
        wait(counts_ref[jnp.maximum(b - 1, 0)])

    @pl.when(b == pl.num_programs(0) - 1)
    def _():
        wait(counts_ref[b])


@functools.lru_cache(maxsize=None)
def _jitted(groups, interpret):
    def hetu_block_gather(tables, counts, *pools):
        seqs, per_seq = tables.shape
        anywhere = pl.BlockSpec(memory_space=pl.ANY)
        firsts = [pools[sum(groups[:g])] for g in range(len(groups))]
        return pl.pallas_call(
            functools.partial(_body, groups=groups, per_seq=per_seq),
            out_shape=[jax.ShapeDtypeStruct(
                (n, seqs, per_seq, *p.shape[1:]), p.dtype)
                for n, p in zip(groups, firsts)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(seqs,),
                in_specs=[anywhere] * len(pools),
                out_specs=[anywhere] * len(groups),
                scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name=KERNEL_NAME,
            interpret=interpret,
        )(tables.reshape(-1), counts, *pools)

    hetu_block_gather.__name__ = hetu_block_gather.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_block_gather)


def gather_blocks(groups, tables, counts):
    """The first ``counts[b]`` blocks of each sequence's block table,
    out of every pool, in table order.

    ``groups``: a sequence of sequences of pools ``[blocks, block_size,
    W]`` (the pools of one group of one shape and dtype: the same entry
    of several layers, which come back stacked); ``tables [B, n]``
    int32, the block that holds positions ``[j x block_size, (j + 1) x
    block_size)`` of sequence ``b``; ``counts [B]`` int32, at most
    ``n``. Returns one ``[len(group), B, n x block_size, W]`` array a
    group; the rows of the blocks past ``counts[b]`` hold no defined
    value."""
    sizes = tuple(len(g) for g in groups)
    out = _jitted(sizes, _interpret())(
        tables.astype(jnp.int32),
        jnp.minimum(counts, tables.shape[1]).astype(jnp.int32),
        *(pool for group in groups for pool in group))
    return [o.reshape(*o.shape[:2], -1, o.shape[-1]) for o in out]
