"""The optimizer's sparse row update in one pass, as a Pallas TPU kernel.

An embedding step looked up at most ``n`` rows of a ``[V, D]`` table.
The composed form (``optimizer.py:_update_rows``) asks XLA for a
scatter-add of the gradient rows (``dedup``), then a row gather and a
row scatter a table (the parameter and each optimizer slot); XLA knows
nothing about the ids, so every scatter sorts them again, permutes its
``[n, D]`` updates into that order and walks the rows one by one:
26.8 + 3.6 ms of a 240 ms step at ``[37984, 2560]`` x 8,192 ids, where
the rows' bytes need 0.72 (PERF.md section 6, PR 51).

Here the ids come SORTED, each beside its gradient row
(``IndexedSlices.sorted_rows``), the ones inside the table first, with
their count. The tables stay in HBM and ARE the results
(``input_output_aliases``: no table is copied). What moves between HBM
and VMEM is a row GROUP, the 8 rows of one ``(8, 128)`` tile row,
contiguous in HBM: Mosaic copies no narrower slice of a tiled array. A
program takes a block of ids. It starts the copy of each id's group of
every table into VMEM, once a group (the ids of one group follow one
another); waits; walks its ids, adding the gradients of equal ids up
(they follow one another too) and applying the optimizer's rule in
float32, once an id, to the id's row where it lies in its group; copies
the groups back to where they lay, and waits again, because the next
program's first id may lie in this one's last group — or be this one's
last id, whose sum then carries over in VMEM. The grid axis is
sequential for both reasons. Ids past the count are skipped, and a
block that holds none does nothing. A row not looked up is at most
copied out and back unchanged.

The rule is a function ``rule(g, rows, scalars, *hyper) -> new rows``
(``optimizer.py``: ``sgd_rows``, ``adagrad_rows``, ``adam_rows``),
traced into the body: ``g`` an id's summed gradient ``[1, D]``, ``rows``
its row of each table (the parameter first), ``scalars`` the step's
traced scalars in SMEM, ``hyper`` Python numbers.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# What the kernel's events are called in a profile: the jitted function
# below carries the same name (see ``pallas_norm.KERNEL_NAME``).
KERNEL_NAME = "hetu_sparse_rows_update"
# Half of the 16 MiB of VMEM a kernel gets without asking for more.
VMEM_BUDGET = 8 * 1024 * 1024
# Ids a program takes at most: more amortise a program's two waits over
# more copies in flight, and stop paying once the DMA queues are full.
MAX_BLOCK_ROWS = 128

# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def supported(rows, width, dtypes):
    """``None`` where the kernel takes ``[rows, width]`` tables of these
    dtypes, else the condition that fails: ``lanes`` (a row is whole
    128-lane words), ``rows`` (a table of fewer rows than one sublane
    tile is tiled otherwise in HBM: there is no group of 8 to copy) or
    ``dtype`` (the rows are updated in float32 where they lie: a
    narrower table would round twice)."""
    if width < LANES or width % LANES:
        return "lanes"
    if rows < SUBLANES:
        return "rows"
    if any(jnp.dtype(d) != jnp.float32 for d in dtypes):
        return "dtype"
    return None


def block_rows(n, width, tables):
    """Ids a program takes: as many as leave every id a row GROUP of
    its own in ``VMEM_BUDGET`` (a function of the width and the number
    of tables alone), in whole sublane tiles, at most
    ``MAX_BLOCK_ROWS``, and no more than the ids there are."""
    per_id = width * 4 * (SUBLANES * tables + 2)
    rows = max(SUBLANES, min(VMEM_BUDGET // per_id, MAX_BLOCK_ROWS)
               // SUBLANES * SUBLANES)
    return min(rows, -(-n // SUBLANES) * SUBLANES)


def _kernel(ids_ref, count_ref, scalars_ref, g_ref, *refs,
            rule, hyper, tables, block):
    olds, news = refs[:tables], refs[tables:2 * tables]
    buf, slot_of, group_of, total, sems = refs[2 * tables:]
    base = pl.program_id(0) * block
    count = count_ref[0]
    live = jnp.clip(count - base, 0, block)

    def id_at(k):
        """ids[k], and -1 on either side of the real ones."""
        inside = (k >= 0) & (k < count)
        return jnp.where(inside, ids_ref[jnp.clip(k, 0, ids_ref.shape[0] - 1)],
                         -1)

    def copies(slot, group, inward):
        """The copies of one row group of every table: into its slot of
        the scratch, or back out of it."""
        rows = pl.ds(pl.multiple_of(group * SUBLANES, SUBLANES), SUBLANES)
        for t in range(tables):
            if inward:
                yield pltpu.make_async_copy(
                    olds[t].at[rows], buf.at[t, slot], sems.at[0])
            else:
                yield pltpu.make_async_copy(
                    buf.at[t, slot], news[t].at[rows], sems.at[1])

    def wait_all(slots, inward):
        def body(slot, carry):
            # a wait needs the copy's shape alone, not its rows
            for copy in copies(slot, 0, inward):
                copy.wait()
            return carry
        jax.lax.fori_loop(0, slots, body, None)

    def fetch(k, carry):
        # ids are sorted: the ids of one group follow one another, and
        # the group comes on chip once, for the first of them
        slots, last = carry
        group = ids_ref[base + k] // SUBLANES
        fresh = group != last

        @pl.when(fresh)
        def _():
            group_of[slots] = group
            for copy in copies(slots, group, inward=True):
                copy.start()

        slots = slots + fresh.astype(jnp.int32)
        slot_of[k] = slots - 1
        return slots, group

    def update(k, carry):
        # the gradients of one id follow one another too: they add up in
        # ``run``, and the id's row takes the rule once, with the last
        last, run = carry
        row_id = ids_ref[base + k]
        g = g_ref[pl.ds(k, 1), :]
        run = jnp.where(row_id == last, run + g, g)

        @pl.when(row_id != id_at(base + k + 1))
        def _():
            slot = slot_of[k]
            row = pl.ds(row_id % SUBLANES, 1)
            new = rule(run, [buf[t, slot, row, :] for t in range(tables)],
                       scalars_ref, *hyper)
            for t in range(tables):
                buf[t, slot, row, :] = new[t]

        return row_id, run

    def store(slot, carry):
        for copy in copies(slot, group_of[slot], inward=False):
            copy.start()
        return carry

    @pl.when(live > 0)
    def _():
        slots, _ = jax.lax.fori_loop(
            0, live, fetch, (jnp.int32(0), jnp.int32(-1)))
        wait_all(slots, inward=True)
        # an id's run may begin in the program before: its sum so far
        # waits in ``total``
        _, run = jax.lax.fori_loop(
            0, live, update, (id_at(base - 1), total[0:1, :]))
        total[0:1, :] = run
        jax.lax.fori_loop(0, slots, store, None)
        # the next program may need a group this one wrote
        wait_all(slots, inward=False)


@functools.lru_cache(maxsize=None)
def _jitted(rule, hyper, interpret):
    def hetu_sparse_rows_update(ids, count, scalars, g, *tables):
        n, width = g.shape
        block = block_rows(n, width, len(tables))
        rows = tables[0].shape[0]
        ragged = -rows % SUBLANES if interpret else 0
        if ragged:
            # on the chip the last group of a table reads into the
            # padding of its last tile; an interpreter has none
            tables = [jnp.pad(t, ((0, ragged), (0, 0))) for t in tables]

        def g_block(i, ids, count):
            # a block past the last real id is never read: it names the
            # last live one again, which the pipeline then keeps
            return (jnp.minimum(i, jnp.maximum(count[0] - 1, 0) // block), 0)

        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        new = pl.pallas_call(
            functools.partial(_kernel, rule=rule, hyper=hyper,
                              tables=len(tables), block=block),
            out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                       for t in tables],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(pl.cdiv(n, block),),
                in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                          pl.BlockSpec((block, width), g_block),
                          *[in_hbm] * len(tables)],
                out_specs=[in_hbm] * len(tables),
                scratch_shapes=[
                    pltpu.VMEM((len(tables), block, SUBLANES, width),
                               jnp.float32),
                    pltpu.SMEM((block,), jnp.int32),
                    pltpu.SMEM((block,), jnp.int32),
                    pltpu.VMEM((SUBLANES, width), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]),
            # every table (after ids, count, scalars and g) IS its result
            input_output_aliases={4 + t: t for t in range(len(tables))},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name=KERNEL_NAME,
            interpret=interpret,
        )(ids, count, scalars, g, *tables)
        return [t[:rows] for t in new] if ragged else new

    hetu_sparse_rows_update.__name__ = KERNEL_NAME
    hetu_sparse_rows_update.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_sparse_rows_update)


def hetu_sparse_rows_update(rule, hyper, ids, g, scalars, tables,
                            interpret=None):
    """The tables with ``rule`` applied to the rows ``ids`` name.

    ``ids`` ``s32[n]``: ascending, duplicates allowed; ``g`` ``f32[n,
    D]`` the gradient beside each, those of one id summed in the order
    they stand; ``scalars`` a sequence of traced float32 scalars;
    ``tables`` the ``[V, D]`` float32 parameter and slots. Ids past the
    table's last row are dropped."""
    if interpret is None:
        interpret = INTERPRET
    rows = tables[0].shape[0]
    ids = ids.astype(jnp.int32)
    count = jnp.sum(ids < rows, dtype=jnp.int32).reshape(1)
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in scalars])
    # an address below the table must never reach a copy
    return _jitted(rule, tuple(hyper), bool(interpret))(
        jnp.maximum(ids, 0), count, scalars, g.astype(jnp.float32), *tables)
