"""The held experts' weight gradient as a Pallas TPU kernel: ``out[g] =
lhs[rows of group g]^T @ dy[rows of group g]`` for the groups held here,
float32 ``[held, k, n]`` from operands ``[m, k]`` and ``[m, n]`` whose
rows are sorted by group (``ops/moe.py:grouped_matmul_weights_grad``
calls it under the stable name ``hetu_moe_experts_dw``; it took the
place of megablox's ``tgmm`` there).

The grid is (column tiles, k tiles, row-tile VISITS): a group is visited
once for every ``tm``-row tile it touches, in row order, and a float32
``[tk, tn]`` tile of its result stays on chip over those visits. The
visit list (:func:`visits`) is built from the group sizes by
compare-and-sum, once a layer, and prefetched into scalar memory; an
empty group gets one visit, which writes its zeros.

What set ``tgmm``'s pace at the train cells' shapes, timed alone on the
chip (``PERF.md`` section 6, PR 64), was not what a visit does beside
its product but what happens when a group ENDS, and how often a row
block is read:

* **The result leaves on its own.** A group's ``[tk, tn]`` tile is 4-16
  MB of float32. As a pipelined output block it was written back while
  the next visit or two waited for it: a fixed 0.27-0.35 ms a call, the
  whole result's bytes over the memory's rate, on top of the visits.
  Here the result stays in HBM (``memory_space=pl.ANY``), a group's sums
  are kept in one of TWO accumulators in turn, the finished one is sent
  by a DMA of its own at the group's last visit, and nobody waits for it
  before that accumulator's next group, a whole group's visits later
  (and at the grid's end).
* **Each row block once, where the result fits.** The kernel asks for
  the on-chip memory its blocks take (``vmem_limit_bytes``), so its
  tiles are not held to the 16 MiB a kernel gets unasked
  (``ops/moe.py:WEIGHTS_BLOCK_BYTES``): at both cells' widths ``[tk,
  tn]`` is the whole ``[k, n]`` or half of it, and a row block is read
  once or twice a call where it was read two to four times.
* **A group's first visit adds to zeros selected in place** of what the
  accumulator held; the library zeroed the tile in a pass of its own.

What a visit does beside its product is the library's, in the operands'
own dtype: the rows outside the visit's group are zeroed in BOTH blocks
(two compares of a row iota against the group's prefetched offsets),
whether an edge cuts the tile or not. The rows behind the held extent
hold no defined value (``ops/moe.py:_fresh``) and are selected away so,
never multiplied by a zero. The contraction runs over the blocks' FIRST
axis (``dot_general`` with both contracting dimensions 0), so no float32
copy of a row block is made. ISSUE 64 set out to spare the uncut visits
their masks, to mask one operand between two held groups, and to end
``tgmm``'s float32 convert-select-transpose: each was built and timed
alone, and none moved the kernel by more than 1% either way: the vector
work hides under the matrix unit's. So there is ONE path: a body with an
unmasked branch, a one-sided and a two-sided masked branch, each with an
assigning and an adding form, read 0.1-0.3% LONGER than this one and
compiled in 4.2 s a call against 1.3.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def visits(sizes, m, tm):
    """The (group, row tile) visits of a weight gradient over the held
    groups of ``sizes [held + 1]`` (the last entry: the rows behind
    them) at ``m`` sorted rows in tiles of ``tm``: ``(group [V], tile
    [V], offsets [held + 1], count)`` int32, ``V = m / tm + held`` the
    most there can be and ``count`` how many there are; ``offsets[g]``
    is group ``g``'s first row and ``offsets[held]`` the end of the held
    extent. A group that got a row is visited once a tile it touches, an
    empty one once. Every entry is a compare against the groups and a
    sum over them: no ``searchsorted``, no scatter of a scalar."""
    landed = sizes[:-1]
    held = landed.shape[0]
    ends = jnp.cumsum(landed)
    first = (ends - landed) // tm
    each = jnp.where(landed > 0, (ends + tm - 1) // tm - first, 1)
    upto = jnp.cumsum(each)
    v = jnp.arange(m // tm + held, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= upto, axis=1, dtype=jnp.int32),
                        held - 1)
    # a visit's tile: its group's first tile plus its place in the group
    shift = jnp.sum(jnp.where(group[:, None] == jnp.arange(held),
                              first - (upto - each), 0), axis=1)
    tile = jnp.clip(v + shift, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               ends.astype(jnp.int32)])
    return group, tile, offsets, upto[-1].astype(jnp.int32)


def cut_visits(sizes, tm):
    """``(visits, cut)`` int32: the row-tile visits of the groups that
    got a row, and those of them that a group's edge cuts (the tile does
    not lie wholly inside its group: part of what it computes is masked
    away, the tiles' padding)."""
    landed = sizes[:-1]
    ends = jnp.cumsum(landed)
    starts = ends - landed
    each = jnp.where(landed > 0, (ends + tm - 1) // tm - starts // tm, 0)
    ragged = (starts % tm != 0).astype(jnp.int32) \
        + (ends % tm != 0).astype(jnp.int32)
    # a group inside ONE tile is cut once, whichever edges it misses
    cut = jnp.where(landed > 0, jnp.minimum(ragged, each), 0)
    return (jnp.sum(each, dtype=jnp.int32), jnp.sum(cut, dtype=jnp.int32))


def _body(group_of, tile_of, offsets, lhs_ref, dy_ref, out_ref, accs, sem,
          *, tm, held, tiles):
    n_i, k_i, v = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = group_of[v]
    start, end = offsets[g], offsets[g + 1]
    row0 = tile_of[v] * tm
    first, last = row0 <= start, row0 + tm >= end
    tk, tn = accs.shape[1:]
    # the result tiles in the order they are finished, one a group a
    # (column, k) tile, take the two accumulators in turn
    s = (n_i * pl.num_programs(1) + k_i) * held + g
    slot = jax.lax.rem(s, 2)
    acc_ref = accs.at[slot]

    def written(slot):
        return pltpu.make_async_copy(
            accs.at[slot], out_ref.at[g, pl.ds(k_i * tk, tk),
                                      pl.ds(n_i * tn, tn)], sem.at[slot])

    @pl.when(jnp.logical_and(first, s >= 2))
    def _():            # the tile that was in this accumulator has left
        written(slot).wait()

    def kept(ref):
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
        block = ref[...]
        return jnp.where(jnp.logical_and(row >= start, row < end), block,
                         jnp.zeros_like(block))

    # ONE path: both blocks masked by group on every visit, and a group's
    # first visit adds to zeros selected in place of what the accumulator
    # held (so an empty group's one visit writes zeros)
    acc_ref[...] = jnp.where(first, 0.0, acc_ref[...]) + jax.lax.dot_general(
        kept(lhs_ref), kept(dy_ref), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        written(slot).start()

    @pl.when(jnp.logical_and(last, s == tiles - 1))
    def _():
        written(slot).wait()
        if tiles > 1:
            written(1 - slot).wait()


def weights_grad(lhs, dy, visit_list, tiles, held, vmem_limit_bytes=None,
                 interpret=False):
    """``[held, k, n]`` float32: ``lhs[rows of g]^T @ dy[rows of g]``
    for the held groups ``g`` (an empty group: zeros). ``lhs [m, k]``
    and ``dy [m, n]`` sorted by group, ``visit_list`` :func:`visits` of
    their group sizes at ``tiles[0]``, ``tiles = (tm, tk, tn)`` dividing
    ``(m, k, n)``. The rows behind the held extent may hold anything."""
    m, k = lhs.shape
    n = dy.shape[1]
    tm, tk, tn = tiles
    group_of, tile_of, offsets, count = visit_list
    itemsize = jnp.dtype(lhs.dtype).itemsize
    body = functools.partial(_body, tm=tm, held=held,
                             tiles=(n // tn) * (k // tk) * held)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((held, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, v, group_of, tile_of,
                             offsets: (tile_of[v], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, v, group_of, tile_of,
                             offsets: (tile_of[v], n_i))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tk, tn), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        # each row block is read once a column / k tile of the other
        # side, the result written once
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=m * itemsize * (k * (n // tn) + n * (k // tk))
            + 4 * held * k * n),
        interpret=interpret)(group_of, tile_of, offsets, lhs, dy)
