"""Mixture-of-experts routing and the expert layer of ONE share of an
expert-parallel deployment: pure-JAX functions (the serving blocks of
``models/latent_moe.py`` and ``models/window_moe.py`` ride these) and,
for training through ``ht.Executor``, graph ops over them with gradient
ops of their own (``router_op``, ``router_picks_op``,
``held_experts_op``; ``models/sparse_decoder.py`` is built from them).

No reference equivalent. The layer is told WHICH experts it holds
(``first``, and as many as its weight stack has) and computes their part
of the sum for the tokens routed to them; what the experts held
elsewhere would add is the exchange's business, not this module's.

* :func:`route` — the aux-loss-free family's router: sigmoid scores in
  float32 over ALL experts, a bias that moves the SELECTION only, the
  chosen ``top_k`` normalised to sum 1, then scaled.
* :func:`held_experts` — no capacity, no dropped token under any
  imbalance: the (token, pick) pairs are sorted by expert, the pairs of
  experts held elsewhere (and of padded tokens) sort behind them as one
  last group that is never computed, and two grouped matmuls (gate|up,
  then down) run over the held groups. Their cost follows the rows that
  are there, and an expert that got no row is not read.
* :func:`grouped_matmul` — ``lhs[group g's rows] @ rhs[g]``. On a TPU
  it is JAX's Pallas grouped-matmul kernel (``megablox``) under the
  stable name ``hetu_moe_experts`` (the name its events carry in a
  profile; ``ops/pallas_norm.py`` says why the jitted function carries
  it); elsewhere, and for widths the kernel's tiles do not take,
  ``jax.lax.ragged_dot``. Its tiles are arithmetic on the product's
  static shapes (**The tiles**, below).
* :func:`router_op` / :func:`router_picks_op` — a router as graph
  nodes: float32 logits at the highest matmul precision from the
  float32 MASTER of the router's weights; by default the ``top_k``
  largest and a softmax over those alone, with ``scoring="sigmoid"``
  :func:`route` itself (sigmoid scores, selection by ``score + bias``
  with the bias a non-trainable float32 buffer, the chosen scores
  normalised and scaled). The gradient flows through the chosen weights
  into the router's weights and its input, never through the indices
  nor into the bias.
* :func:`held_experts_op` — :func:`held_experts` as a graph node over
  the stacked parameters of the experts held here, with a counter the
  compiled training step accumulates on the device (rows by held
  expert, experts visited, row tiles, steps: ``Executor.moe_counters()``,
  which adds the picks that a router selecting by a bias counted as
  changed by it). Its backward is two grouped products for
  the rows (``hetu_moe_experts_dx``: the kernel with its right side
  transposed) and two transposed grouped products for the weights
  (``hetu_moe_experts_dw``: a kernel of the repo's own,
  ``ops/pallas_grouped.py``); the rows of experts held elsewhere are
  never computed in either direction.

**The held extent** (:func:`_held_extent_passes`, the ONE body of the
graph op's forward and of :func:`held_experts` wherever a pass has more
sorted rows than one row tile: ``T x k > ROW_TILE``, a STATIC fact of
the call and the only thing the choice reads. A prefill pass of the
serving blocks has 512 to 32,768 sorted rows and takes it from 2,049 on;
a decode step's ``B x k`` rows padded to 128, at most 256, and the
shortest prompts keep every pass over the whole arrays in
:func:`held_experts` itself. The graph op takes it at any size: its
training cells have 49,152 rows a layer, and its lowered text at the
tests' 192 is pinned. The border is where the chip put it: both forms
timed alone at sarvam's widths (``PERF.md`` section 6, PR 59), the
held-extent form read 0.05-0.09 ms a layer behind at 512 rows, level at
2,048, 2-6% ahead at 4,096 and 8,192 and 33-46% ahead at 32,768 at held
shares of 1/8 and 1/4; with every expert held it skips nothing and reads
within 3.5% either way at every size.) The sort puts the ``n =
sum(sizes[:-1])`` rows of the experts held here first, and ``n`` is on
the device. Every composed pass of the op whose result is indexed by
SORTED ROW — the gathers ``flat[token]`` and ``dy[token]``, the
activation between the two products, the backward's ``da``, ``da *
act``, ``w_row * act``, the activation's slope and ``dh`` — runs under
:func:`_over_held_rows`: a loop over row tiles of ``ROW_TILE`` rows
whose trip count is ``ceil(n / tile)``, each tile written in place into
a buffer of the full ``[T x k, ...]`` shape that starts with no value
(:func:`_fresh`). Nothing is chosen: no capacity, no fallback, no
dropped row; the extent follows the routing. The rows past the last tile
that ran hold whatever the allocation held, and every reader selects
them away: the grouped kernels mask their operands and their store by
group. The way back to token order (:func:`_token_sums`: the forward's
weighted sum and the backward's ``dx`` are one function) is indexed by
(token, pick) and visits the pairs that landed here, too: the tokens
sorted by how many picks they hold here, a loop over the tiles of
``TOKEN_TILE`` tokens that hold any, and inside it a loop over a token's
held picks that gathers a tile of the product's rows and adds it to a
float32 tile; a token then reads its sum from its place in that order.
So nothing reads a row of the op's four grouped products (``h``, ``ys``,
``da``, ``dxs``) behind the held groups, and the op has the kernels
write into buffers that start with no value (``grouped_matmul(...,
out=)``): the library's zero fill behind the groups is left to the
whole-array form of a decode step. The op's state counts the tiles
(``moe_row_tiles``, beside ``moe_row_tiles_of``, the tiles that all ``T
x k`` rows are: their quotient is the share of the passes' work that is
left) and the rows the way back read (``moe_back_rows``, beside
``moe_back_rows_of``, the ``T x k`` a direction that all the pairs are).
The permutations of a scalar a pair or a token (``back``, ``w_row``, the
pairs' weight gradient, a token's place in the way back's order) are
sorts (:func:`_moved`), not gathers or scatters; and where the layer
indexes by a scalar a pair it compares against the expert axis and
reduces over it, one fused pass: the COUNT of the pairs of each held
expert is ``sum(group[:, None] == arange(held + 1))``
(:func:`_sorted_pairs`) and a pick's SCORE the one score its compare
selects (:func:`_picked`, the router's and the graph router's counter
and gradient). XLA walks a scatter-add or a gather of a scalar an index
one by one: on the chip the count of a smallthinker layer's 49,152
pairs took 0.430 ms as a scatter-add and takes 0.008, the scores of an
lfm2 router's 32,768 picks 0.334 ms as a gather and 0.003 (``PERF.md``
section 6, PR 62).

**The tiles** of the three grouped kernels (:func:`_kernel_tiles`) are
read from what a product is given and from nothing else: ``m``, ``k``,
``n``, the operands' width in bytes, which kernel it is and whether it
writes into ``out``. No table by model, nothing measured at run time,
no option: two copies of one tree run the same programs. megablox walks ``gmm``'s grid as (column tiles, row-tile
visits, k-steps); with more than one k-step the weight block's index
changes on every grid step, so an expert's ``[tk, tn]`` block is
fetched again for EVERY row tile of its group and a step brings ``tm
tn / (tm + tn)`` operations a byte, at or under the chip's ridge (240)
for any tile that fits. So:

* ``gmm`` (forward, and the rows' gradient with its right side
  transposed): the contraction is ONE tile (``tk = k``) wherever the
  blocks fit ``KERNEL_BLOCK_BYTES`` (:func:`_block_bytes`: every block
  twice, and the float32 accumulator). The weight block ``(group, 0,
  n_i)`` then keeps its index across the consecutive row tiles of a
  group and is fetched once a column tile; what streams a step is the
  row tile and its output, ``k tn / (k + 2 tn)`` operations a byte
  whatever ``tm`` is. ``tn`` is the widest multiple of a lane block
  dividing ``n`` that still fits; where ``k`` whole does not fit, the
  fewest k-steps that do.
* the weights' gradient (``ops/pallas_grouped.py``, since PR 64 in the
  place of megablox's ``tgmm``) walks (column tiles, k tiles, row-tile
  visits): a step reads a ``[tm, tk]`` and a ``[tm, tn]`` row block for
  a float32 ``[tk, tn]`` tile that stays on chip over a group's rows,
  ``tk tn / (tk + tn)`` operations a byte, and the finished tile leaves
  by a DMA of its own while the next group's sums go into a second
  accumulator. The kernel asks for the on-chip memory its blocks take,
  so the output tile is the one that brings the most operations a byte
  under ``WEIGHTS_BLOCK_BYTES`` (two row blocks twice and two float32
  accumulators, ``8 tk tn`` bytes): the whole ``[k, n]`` at three of the
  train cells' four products and half of it at the fourth, so a row
  block is read once a call, or twice.
* the row tile ``tm`` is ``KERNEL_ROW_TILE`` (256) rows where that
  divides ``m``, else 128. Where the rows are ONE tile (serving's decode
  step, 128 padded rows) an expert is visited once and no block is used
  twice: ``gmm`` then takes blocks of at most ``ONE_TILE_SIDE`` (1,024)
  a side under the same budget, many short fetches that the pipeline
  hides, not two or three long ones whose first stands exposed. The
  boundary is ``m == tm``: 128 or 256 rows are one tile; 384 rows are
  three tiles of 128 and 512 two of 256, and take the contraction whole.

The op counts the rows its forward's row tiles compute
(``moe_kernel_rows``: visits x ``tm``; :func:`_kernel_rows`): over the
rows that landed it is what the tiles' padding costs under the ``tm``
chosen.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..graph.node import Op
from . import pallas_grouped
from .norm import PackedPartOp as _Part

__all__ = ["route", "held_experts", "grouped_matmul", "swiglu",
           "KERNEL_NAME", "ROWS_GRAD_KERNEL_NAME",
           "WEIGHTS_GRAD_KERNEL_NAME", "TOKEN_CHUNK", "ACTIVATIONS",
           "route_softmax_top_k", "router_op", "router_picks_op",
           "held_experts_op", "RouterOp", "HeldExpertsOp"]

KERNEL_NAME = "hetu_moe_experts"
# the backward's grouped products, as a profile names their events
ROWS_GRAD_KERNEL_NAME = "hetu_moe_experts_dx"
WEIGHTS_GRAD_KERNEL_NAME = "hetu_moe_experts_dw"
# the allocation of a row buffer that a pass fills to the held extent
FRESH_KERNEL_NAME = "hetu_moe_rows_buffer"
# the gate's activation of an expert: down(act(gate x) * up x)
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
LANES = 128
# tokens a caller should pass at a time: the sorted copies of a pass are
# [tokens * top_k, hidden], 268 MB at 4096 tokens x 8 picks x 4096 wide
# in bfloat16, whatever the prompt bucket
TOKEN_CHUNK = 4096
# sorted rows a composed pass takes at a time: its loop runs the row
# tiles that hold a held expert's row and no other (``_over_held_rows``).
# Also the border of serving's ``held_experts``: a pass of at most one
# such tile runs over the whole arrays. A tile's gather moves 10 MB at
# 2560 wide in bfloat16, tens of microseconds beside a loop iteration's
# few, and a pass rounds its rows up by half a tile on average
ROW_TILE = 2048
# sorted tokens the way back takes at a time (``_token_sums``): each of
# a token's k ranks of held picks rounds its tokens up to a tile, so a
# small one (14 us of gather); 256 and 1,024 both ran slower on the chip
TOKEN_TILE = 512
# what the compiler keeps on chip beside a grouped product's blocks
KERNEL_MARGIN_BYTES = 2 * 2 ** 20
# on-chip memory that the blocks of a grouped product may take
# (``_block_bytes``): the 16 MiB a kernel is given by default less that
# margin. Compiled for the described chip at row tiles of 128 and 256
# rows, every tile up to 15.0 MiB by this count fit and the first
# refusals came at 15.75 (``tests/test_chip_compile.py`` holds the train
# cells' tiles to it)
KERNEL_BLOCK_BYTES = 16 * 2 ** 20 - KERNEL_MARGIN_BYTES
# the same for the weights' gradient, a kernel that asks for what its
# blocks take (``vmem_limit_bytes``: their bytes and the same margin) of
# the chip's 128 MiB. Timed alone on the chip at both train cells' four
# products (``PERF.md`` section 6, PR 64) the tiles it admits, 18-34 MiB
# of blocks, ran 14-21% shorter than those under 14 MiB and 0-4% shorter
# than those under 28 MiB; a 59 MiB pair of accumulators (lfm2's
# ``[2048, 3584]`` whole) ran TWICE as long as its halves
WEIGHTS_BLOCK_BYTES = 40 * 2 ** 20
# rows of a grouped product's row tile where they divide the sorted
# rows. Timed alone on the chip at both train cells' shapes (groups of
# 800-1,800 rows) and at four serving models' prefill chunks (groups of
# 64-256 rows), 256 and 128 read within 3% of each other and 512 read
# 8-40% slower: a group is visited ``rows / tm + 1`` times, so the
# tiles' padding falls with ``tm``, and with the contraction whole a
# step's operations a byte do not depend on it. At 512 rows the
# compiler also keeps more beside the blocks (a refusal at 13.0 MiB)
KERNEL_ROW_TILE = 256
# the longest side of a weight block where a product's rows are ONE row
# tile (a decode step visits an expert once: each block is fetched once
# whatever its size, and a visit of two or three large blocks cannot
# hide the first one's fetch; 1,024 x 1,024 is what PR 56's rule gave)
ONE_TILE_SIDE = 1024


def _use_pallas():
    from .attention import _use_pallas as on_tpu
    return on_tpu()


def swiglu(x, w_gate_up, w_down):
    """``down(silu(gate x) * up x)`` with gate and up side by side in
    one ``[hidden, 2 * width]`` matrix."""
    h = x @ w_gate_up
    width = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :width]) * h[..., width:]) @ w_down


def route(x, w_router, bias, top_k, scale, n_group=1, topk_group=1,
          norm_eps=0.0):
    """Router of the aux-loss-free family over ALL experts.

    ``x`` ``[T, hidden]``; ``w_router`` ``[hidden, E]`` and ``bias``
    ``[E]`` float32. Scores are ``sigmoid(x W)`` in float32 (the
    product at the highest precision: a TPU's default rounds float32
    operands to bfloat16, and two scores that nearly tie would flip);
    the bias is added for the SELECTION of the ``top_k`` and never
    enters a weight; the chosen scores are normalised to sum 1 and
    scaled. With ``n_group > 1`` the selection is GROUP-LIMITED: the
    experts lie in ``n_group`` groups of consecutive ids, a group's
    score is the sum of its two largest ``score + bias``, and only the
    experts of the ``topk_group`` best groups may be picked (under
    expert parallelism a token then visits that many groups' chips at
    most). ``norm_eps`` is added under the chosen scores' sum where a
    family's code has one. Returns ``(experts [T, k] int32, weights
    [T, k] float32, scores [T, E] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = choice.reshape(*choice.shape[:-1], n_group, -1)
        best_two, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(n_group), axis=-2)
        choice = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
            choice.shape)
    _, experts = jax.lax.top_k(choice, top_k)
    picked = _picked(scores, experts)
    weights = scale * picked
    summed = jnp.sum(picked, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), \
        weights / (summed + norm_eps if norm_eps else summed), scores


def _picked(scores, experts):
    """``scores [..., E]`` at ``experts [..., k]``, ``[..., k]``: each
    pick compared against the expert axis and the one score that
    matches selected, bit for bit the gathered score. One fused
    compare-select-reduce over ``[..., k, E]``; ``take_along_axis`` is a
    gather of a scalar a pick, which the chip walks one by one (the
    module's docstring has the figures). The reduction is a MAX over
    ``-inf``, not a sum over zeros: XLA merges a sum over ``E`` with the
    callers' sum over the ``k`` picks into one reduction over ``[k,
    E]`` and adds the chosen scores in another order (weights one or two
    units in the last place off the gathered form's)."""
    at = experts[..., None] == jnp.arange(scores.shape[-1])
    return jnp.max(jnp.where(at, scores[..., None, :], -jnp.inf), axis=-1)


def _lane_divisors(x):
    """The multiples of a lane block that divide ``x``, largest first."""
    return [t for t in range(x, 0, -LANES) if x % t == 0]


def _block_bytes(kind, tm, tk, tn, itemsize, out):
    """What a grouped product's blocks take of on-chip memory at tiles
    ``(tm, tk, tn)``. ``"weights"`` (``ops/pallas_grouped.py``): the two
    row blocks twice (Pallas fetches the next while one is worked on)
    and the two float32 ``[tk, tn]`` accumulators that take the groups
    in turn; the result is no block, it leaves from an accumulator. The
    others (megablox's ``gmm``): every operand and result block twice
    and the float32 accumulator: the row block, the weight block, the
    result block in the operands' dtype, and ``out``'s block beside it
    where the product writes into one."""
    if kind == "weights":
        return 2 * tm * (tk + tn) * itemsize + 8 * tk * tn
    return 2 * (tm * tk + tk * tn) * itemsize \
        + (4 if out else 2) * tm * tn * itemsize + 4 * tm * tn


def _row_tile(m):
    """The rows of a grouped product's row tile over ``m`` sorted rows
    (a multiple of a lane block)."""
    return next(t for t in (KERNEL_ROW_TILE, LANES) if m % t == 0)


def _kernel_tiles(kind, m, k, n, itemsize=2, out=False):
    """``(tm, tk, tn)`` of a grouped product from its static shapes, or
    None where the kernel's tiles do not take the widths (whole lanes).
    ``kind``: ``"forward"`` (``[m, k] x [groups, k, n]``, megablox's
    ``gmm``), ``"rows"`` (the same with the right side transposed, ``[m,
    k] x [groups, n, k]^T``: the same blocks, so the same tiles) or
    ``"weights"`` (``ops/pallas_grouped.py``: ``[m, k]^T x [m, n]`` a
    group, float32 ``[groups, k, n]``). The module's docstring says what
    the rule is after; every tile is a multiple of a lane block that
    divides its extent, and the blocks stay under ``KERNEL_BLOCK_BYTES``
    (``WEIGHTS_BLOCK_BYTES`` for the kernel that asks for its own)."""
    if k % LANES or n % LANES or m % LANES:
        return None
    tm = _row_tile(m)
    budget = WEIGHTS_BLOCK_BYTES if kind == "weights" else KERNEL_BLOCK_BYTES

    def fits(tk, tn):
        return _block_bytes(kind, tm, tk, tn, itemsize, out) <= budget

    if kind == "weights":
        # the output tile whose two row blocks bring the most
        # operations a byte, tk tn / (tk + tn); of equals the larger tk
        _, tk, tn = max((tk * tn / (tk + tn), tk, tn)
                        for tk in _lane_divisors(k)
                        for tn in _lane_divisors(n) if fits(tk, tn))
        return tm, tk, tn
    # ONE row tile (serving's decode step, ``m == tm``): no block is used
    # twice, so the contraction whole gains nothing, and before the
    # pipeline fills the first block's fetch stands exposed: no side of
    # a weight block is longer than ``ONE_TILE_SIDE`` there
    side = ONE_TILE_SIDE if m == tm else max(k, n)
    # the fewest steps over the contraction, then the widest column tile
    for tk in _lane_divisors(k):
        tn = next((tn for tn in _lane_divisors(n)
                   if max(tk, tn) <= side and fits(tk, tn)), None)
        if tn is not None:
            return tm, tk, tn
    return None


@functools.lru_cache(maxsize=None)
def _kernel(tiles, out_dtype, interpret):
    """The megablox kernel behind a jitted function of the stable
    name. The library's own entry point is a ``jax.jit`` called
    ``gmm``, and a program's instructions are named for the innermost
    jitted function, so its body is wrapped anew. Given ``out`` (the
    held-extent form's: :func:`grouped_matmul`), the kernel writes the
    held groups' rows into it and the library fills nothing behind
    them."""
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

    def hetu_moe_experts(lhs, rhs, group_sizes, out=None):
        return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
                   tiling=tiles, interpret=interpret, existing_out=out)

    hetu_moe_experts.__name__ = hetu_moe_experts.__qualname__ = KERNEL_NAME
    return jax.jit(hetu_moe_experts)


@functools.lru_cache(maxsize=None)
def _grad_kernel(which, tiles, out_dtype, interpret, groups=None):
    """The backward's two kernels under their stable names: ``"rows"``
    is megablox's ``gmm`` with the right side transposed (``dy @
    rhs[g]^T``; it has no vjp of its own in the library's
    ``__wrapped__`` form: this IS the vjp), ``"weights"`` the repo's
    own kernel over the first ``groups`` groups (``lhs[rows of g]^T @
    dy[rows of g]``, ``ops/pallas_grouped.py``), given the visit list
    of its group sizes where the caller has one (a layer's two weight
    gradients share theirs)."""
    if which == "rows":
        gmm = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

        def fn(dy, rhs, group_sizes, out=None):
            return gmm(
                dy, rhs, group_sizes, preferred_element_type=out_dtype,
                tiling=tiles, transpose_rhs=True, interpret=interpret,
                existing_out=out)
        name = ROWS_GRAD_KERNEL_NAME
    else:
        def fn(lhs, dy, group_sizes, visits=None):
            if visits is None:
                visits = pallas_grouped.visits(group_sizes, lhs.shape[0],
                                               tiles[0])
            itemsize = jnp.dtype(lhs.dtype).itemsize
            return pallas_grouped.weights_grad(
                lhs, dy, visits, tiles, groups, interpret=interpret,
                vmem_limit_bytes=_block_bytes(
                    "weights", *tiles, itemsize, False) + KERNEL_MARGIN_BYTES)
        name = WEIGHTS_GRAD_KERNEL_NAME
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


# tests flip this to exercise the kernel without a TPU backend
INTERPRET = False


def _product_tiles(kind, lhs, n, out=None):
    """The kernel's tiles for a product of ``lhs [m, k]`` with ``n``
    result columns (``_kernel_tiles``), or None where the ragged product
    runs: off a TPU, or at widths the kernel does not take."""
    if not (_use_pallas() or INTERPRET):
        return None
    return _kernel_tiles(kind, *lhs.shape, n,
                         jnp.dtype(lhs.dtype).itemsize, out is not None)


def grouped_matmul(lhs, rhs, group_sizes, out=None):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

    ``lhs`` ``[m, k]`` with its rows sorted by group; ``rhs`` ``[G, k,
    n]``; ``group_sizes`` ``[G + 1]`` int32 — the last entry counts the
    rows behind the ``G`` groups (pairs of experts held elsewhere),
    which are not computed and come back as zeros. Returns ``[m, n]``
    in ``lhs``'s dtype, accumulated in float32.

    A caller that reads no row behind the held groups (the held-extent
    form) passes ``out``, an ``[m, n]`` array in ``lhs``'s dtype: the
    held groups' rows are written into it and the rows behind them keep
    what it held, so nothing is spent on zeros that nobody reads."""
    tiles = _product_tiles("forward", lhs, rhs.shape[-1], out)
    if tiles is not None:
        return _kernel(tiles, jnp.dtype(lhs.dtype), INTERPRET)(
            lhs, rhs, group_sizes, out)
    got = jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1],
                             preferred_element_type=jnp.float32)
    return _behind_kept(got, group_sizes, out).astype(lhs.dtype)


def _computed_rows(m, group_sizes):
    """``[m, 1]`` bool: the rows of the groups held here (the sorted
    rows before the last group)."""
    return jnp.arange(m)[:, None] < jnp.sum(group_sizes[:-1])


def _behind_kept(got, group_sizes, out):
    """A ragged product's rows with those behind the held groups as the
    kernels leave them: zeros, or what the caller's ``out`` held."""
    return jnp.where(_computed_rows(got.shape[0], group_sizes), got,
                     0.0 if out is None else out)


def grouped_matmul_rows_grad(dy, rhs, group_sizes, out=None):
    """``d lhs`` of :func:`grouped_matmul`: ``dy[rows of g] @ rhs[g]^T``,
    ``[m, k]`` in ``dy``'s dtype; the rows behind the held groups come
    back as zeros, or as ``out``'s where that is given."""
    tiles = _product_tiles("rows", dy, rhs.shape[1], out)
    if tiles is not None:
        return _grad_kernel("rows", tiles, jnp.dtype(dy.dtype), INTERPRET)(
            dy, rhs, group_sizes, out)
    got = jax.lax.ragged_dot(dy, rhs.swapaxes(1, 2), group_sizes[:-1],
                             preferred_element_type=jnp.float32)
    return _behind_kept(got, group_sizes, out).astype(dy.dtype)


def _weights_row_tile(m):
    """The row tile of the weight gradients' kernel over ``m`` sorted
    rows, or None where the composed form runs (off a TPU, or rows that
    are no whole lane blocks)."""
    if not (_use_pallas() or INTERPRET) or m % LANES:
        return None
    return _row_tile(m)


def _weights_grad_visits(group_sizes, m):
    """The visit list that the weight gradients over ``m`` sorted rows
    walk (``pallas_grouped.visits``; it follows the rows and the group
    sizes alone, so a layer's two gradients share one), or None where
    the composed form runs."""
    tm = _weights_row_tile(m)
    return None if tm is None else pallas_grouped.visits(group_sizes, m, tm)


def grouped_matmul_weights_grad(lhs, dy, group_sizes, visits=None):
    """``d rhs`` of :func:`grouped_matmul`: ``lhs[rows of g]^T @ dy[rows
    of g]`` for the ``G`` held groups, ``[G, k, n]`` float32 (a group
    that got no row is zeros). ``visits``: :func:`_weights_grad_visits`
    of the same sizes and rows, where the caller made one."""
    m = lhs.shape[0]
    groups = group_sizes.shape[0] - 1
    tiles = _product_tiles("weights", lhs, dy.shape[-1])
    if tiles is not None:
        return _grad_kernel("weights", tiles, jnp.dtype(jnp.float32),
                            INTERPRET, groups)(lhs, dy, group_sizes, visits)
    ends = jnp.cumsum(group_sizes[:-1])
    row = jnp.arange(m)
    member = (row[None, :] >= (ends - group_sizes[:-1])[:, None]) \
        & (row[None, :] < ends[:, None])                    # [G, m]
    # a row behind the held groups may hold anything: selected away
    # (the kernel selects by group too), never multiplied by a zero
    here = _computed_rows(m, group_sizes)
    return jnp.einsum("gm,mk,mn->gkn", member.astype(jnp.float32),
                      jnp.where(here, lhs, 0).astype(jnp.float32),
                      jnp.where(here, dy, 0).astype(jnp.float32))


def _held_pairs(experts, valid, held_n, first):
    """``[T, k]`` bool: the picks of real tokens whose expert is one of
    ``first .. first + held_n - 1``."""
    local = experts - first
    return (local >= 0) & (local < held_n) & valid[:, None]


def _sorted_pairs(experts, valid, held_n, first):
    """The (token, pick) pairs sorted by held expert: ``(order [rows +
    pad], sizes [held + 1], held [T, k] bool, rows)``. The pairs of
    experts held elsewhere and of padded tokens are the last group; on
    the kernel's path the rows are padded to its row tile and the pad
    joins that group."""
    t, k = experts.shape
    local = experts - first
    held = (local >= 0) & (local < held_n) & valid[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)       # [t * k]
    # each pair compared against the groups and summed: one fused pass
    # (a scatter-add of a one a pair is walked pair by pair on the chip)
    bins = jnp.arange(held_n + 1)
    sizes = jnp.sum(group[:, None] == bins, axis=0, dtype=jnp.int32)
    order = jnp.argsort(group, stable=True)
    rows = t * k
    pad = -rows % LANES if (_use_pallas() or INTERPRET) else 0
    if pad:     # the kernel's row tile; the pad rows join the last group
        order = jnp.concatenate([order, jnp.zeros(pad, order.dtype)])
        sizes = sizes + pad * (bins == held_n)
    return order, sizes, held, rows


def held_experts(x, experts, weights, valid, w_gate_up, w_down, first=0,
                 activation="silu"):
    """The held experts' part of an expert layer.

    ``x`` ``[T, hidden]``; ``experts`` / ``weights`` ``[T, k]`` from
    :func:`route`; ``valid`` ``[T]`` bool (a padded token is routed
    nowhere); ``w_gate_up`` ``[held, hidden, 2 * width]`` and
    ``w_down`` ``[held, width, hidden]`` are the stacks of the experts
    ``first .. first + held - 1``. Returns ``(sum over the held picks
    of weight * expert(x) [T, hidden] float32, rows by held expert
    [held] int32)``. The sorted copies are ``[T * k, hidden]``: a
    caller with many tokens passes ``TOKEN_CHUNK`` at a time.
    ``activation`` names the gate's (``ACTIVATIONS``).

    The form of the passes round the two products follows the STATIC
    number of sorted rows and nothing else (the module's docstring,
    **The held extent**): more than ``ROW_TILE`` of them (a prefill
    pass) run :func:`_held_extent_passes`, the graph op's body; a
    decode step's one or two hundred run every pass over the whole
    arrays, where the loops skip nothing worth their own cost."""
    t, k = experts.shape
    held_n = w_gate_up.shape[0]
    order, sizes, held, rows = _sorted_pairs(experts, valid, held_n, first)
    if order.shape[0] > ROW_TILE:
        back = _moved(jnp.arange(rows, dtype=jnp.int32), order[:rows])
        out, _, _ = _held_extent_passes_once(
            x, order // k, sizes, back, held, jnp.where(held, weights, 0.0),
            w_gate_up, w_down, activation, jnp.float32)
        return out, sizes[:held_n]
    xs = x[order // k]
    h = grouped_matmul(xs, w_gate_up, sizes)
    width = h.shape[-1] // 2
    act = (ACTIVATIONS[activation](h[:, :width].astype(jnp.float32))
           * h[:, width:].astype(jnp.float32)).astype(x.dtype)
    ys = grouped_matmul(act, w_down, sizes)
    # back to (token, pick) order; a pair held elsewhere weighs nothing.
    # This scatter of a scalar a row stays: the branch runs at most
    # ``ROW_TILE`` rows, a decode step's 128-256, where walking them
    # costs 1-3 us and ``_moved``'s sort has a larger fixed cost
    back = jnp.zeros(rows, jnp.int32).at[order[:rows]].set(
        jnp.arange(rows, dtype=jnp.int32))
    pairs = ys[back].reshape(t, k, -1)
    out = jnp.einsum("tk,tkh->th", jnp.where(held, weights, 0.0),
                     pairs.astype(jnp.float32))
    return out, sizes[:held_n]


# ---------------------------------------------------------------------------
# graph ops (training through ht.Executor)
# ---------------------------------------------------------------------------

def route_softmax_top_k(x, w_router, top_k):
    """A softmax router over ALL experts: ``x [..., hidden]``,
    ``w_router [hidden, E]``. Logits in float32 (the product at the
    highest precision, as :func:`route`'s), the ``top_k`` largest, a
    softmax over those alone — which is the softmax over all ``E``
    renormalised over the chosen. Returns ``(experts [..., k] int32,
    weights [..., k] float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    picked, experts = jax.lax.top_k(logits, top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(picked, axis=-1)


def _master(ectx, node, value):
    """The float32 master of parameter ``node`` where the step keeps
    one (mixed precision), else the value the step handed the op."""
    masters = getattr(ectx, "master_params", None)
    if masters is not None and node in masters:
        return masters[node]
    return value


SCORINGS = ("softmax", "sigmoid")


class RouterOp(Op):
    """The chosen experts' WEIGHTS ``[..., k]`` float32; the indices are
    :class:`RouterPicksOp`'s. The router's weights (and its bias) are
    read from their float32 masters whatever the step's compute dtype.

    ``scoring="softmax"`` (the default): :func:`route_softmax_top_k`,
    the ``top_k`` largest logits and a softmax over those alone.
    ``scoring="sigmoid"``: :func:`route`, the aux-loss-free family's —
    sigmoid scores over ALL experts, the ``top_k`` chosen by ``score +
    bias`` where a ``bias`` node ``[E]`` is given (a float32 BUFFER: a
    non-trainable variable that gets no gradient and enters no weight),
    the weights the chosen scores over their sum plus ``norm_eps``,
    times ``scale``."""

    def __init__(self, node_in, w_router, top_k, ctx=None,
                 scoring="softmax", bias=None, scale=1.0, norm_eps=0.0):
        if scoring not in SCORINGS:
            raise ValueError(f"scoring {scoring!r}: one of {SCORINGS}")
        if scoring == "softmax" and (bias is not None or scale != 1.0
                                     or norm_eps):
            raise ValueError("bias, scale and norm_eps belong to the "
                             "sigmoid router")
        super().__init__(RouterOp, [node_in, w_router]
                         + ([] if bias is None else [bias]), ctx)
        self.top_k = top_k
        self.scoring = scoring
        self.selects_by_bias = bias is not None
        self.scale = float(scale)
        self.norm_eps = float(norm_eps)
        # a router that selects by a bias counts, on the device, the
        # picks the bias changed (``Executor.moe_counters()``)
        self.stateful = self.selects_by_bias
        self.state_dtype = jnp.int32

    def state_shapes(self, input_shapes):
        return {"moe_bias_flipped_picks": ()}

    def routed(self, input_vals, ectx):
        """``(experts, weights)`` — the sigmoid router's ``(experts,
        weights, scores [..., E])`` —, computed once a trace."""
        key = ("router", self.id)
        if key not in ectx.cache:
            x, w = input_vals[:2]
            w = _master(ectx, self.inputs[1], w)
            if self.scoring == "softmax":
                ectx.cache[key] = route_softmax_top_k(x, w, self.top_k)
            else:
                bias = _master(ectx, self.inputs[2], input_vals[2]) \
                    if self.selects_by_bias \
                    else jnp.zeros(w.shape[-1], jnp.float32)
                ectx.cache[key] = route(x, w, bias, self.top_k, self.scale,
                                        norm_eps=self.norm_eps)
        return ectx.cache[key]

    def compute(self, input_vals, ectx):
        routed = self.routed(input_vals, ectx)
        state = ectx.get_state(self) if self.stateful and ectx.training \
            else None
        if state is not None:
            # the picks that are not among the ``top_k`` of the scores
            # alone, by each pick's RANK among them (ties to the lower
            # index, as ``top_k`` breaks them): one fused compare and
            # sum over ``[T, k, E]``, no second top-k
            experts, _, scores = routed
            picked = _picked(scores, experts)
            others, own = scores[..., None, :], picked[..., :, None]
            ahead = (others > own) | ((others == own) & (
                jnp.arange(scores.shape[-1]) < experts[..., :, None]))
            flipped = jnp.sum(ahead, axis=-1) >= self.top_k
            ectx.put_state(self, {
                "moe_bias_flipped_picks": state["moe_bias_flipped_picks"]
                + jnp.sum(flipped, dtype=jnp.int32)})
        return routed[1]

    def gradient(self, output_grad):
        packed = _RouterGradientOp(self, output_grad, ctx=self.raw_ctx)
        return [_Part(packed, self.inputs[0], 0, ctx=self.raw_ctx),
                _Part(packed, self.inputs[1], 1, ctx=self.raw_ctx)] \
            + [None] * self.selects_by_bias

    def infer_shape(self, input_shapes):
        return tuple(input_shapes[0][:-1]) + (self.top_k,)


class RouterPicksOp(Op):
    """The chosen experts' indices ``[..., k]`` int32 of a
    :class:`RouterOp`. No gradient: a pick is a discrete choice."""

    def __init__(self, router, ctx=None):
        super().__init__(RouterPicksOp, list(router.inputs), ctx)
        self.router = router

    def compute(self, input_vals, ectx):
        return self.router.routed(input_vals, ectx)[0]

    def gradient(self, output_grad):
        return [None] * len(self.inputs)

    def infer_shape(self, input_shapes):
        return self.router.infer_shape(input_shapes)


class _RouterGradientOp(Op):
    """Packed ``(dx, dw_router)``: the Jacobian of the weights over the
    chosen ``k`` — the softmax's, or the sigmoid's and the
    normalisation's: with ``p`` the chosen scores, ``D = sum(p) +
    norm_eps`` and ``w = scale * p / D``, ``dp_i = (scale * dw_i - sum_j
    dw_j w_j) / D`` and ``dlogit_i = dp_i p_i (1 - p_i)`` —, scattered
    onto the chosen columns of the ``E`` logits (every other logit's
    gradient is zero: the indices carry none, and neither does the
    bias)."""

    def __init__(self, forward_op, output_grad, ctx=None):
        super().__init__(_RouterGradientOp,
                         list(forward_op.inputs) + [output_grad], ctx)
        self.forward_op = forward_op

    def compute(self, input_vals, ectx):
        x, w, dweights = input_vals[0], input_vals[1], input_vals[-1]
        fwd = self.forward_op
        w = _master(ectx, fwd.inputs[1], w).astype(jnp.float32)
        routed = fwd.routed([x, w, *input_vals[2:-1]], ectx)
        experts, weights = routed[:2]
        dweights = dweights.astype(jnp.float32)
        if fwd.scoring == "softmax":
            dpicked = weights * (dweights - jnp.sum(
                weights * dweights, axis=-1, keepdims=True))
        else:
            p = _picked(routed[2], experts)
            total = jnp.sum(p, axis=-1, keepdims=True) + fwd.norm_eps
            dpicked = (fwd.scale * dweights - jnp.sum(
                weights * dweights, axis=-1, keepdims=True)) / total \
                * p * (1.0 - p)
        dlogits = jnp.einsum(
            "...k,...ke->...e", dpicked,
            jax.nn.one_hot(experts, w.shape[-1], dtype=jnp.float32))
        flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        dw = jnp.dot(flat.T, dlogits.reshape(-1, w.shape[-1]),
                     precision=jax.lax.Precision.HIGHEST)
        dx = jnp.dot(dlogits, w.T, precision=jax.lax.Precision.HIGHEST)
        return (dx.astype(x.dtype), dw)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


def _expert_rows(x, weights, experts, first, held_n, kept=None):
    """What both directions of :class:`HeldExpertsOp` share: the flat
    operands and the sort (``kept``: the forward's ``(order, sizes,
    back)``, where the backward runs in its trace). ``(x [T, hidden],
    weights [T, k] with 0 for a pair held elsewhere, held [T, k],
    order, sizes, back, token of each sorted row)``."""
    k = experts.shape[-1]
    flat = x.reshape(-1, x.shape[-1])
    experts = experts.reshape(-1, k)
    valid = jnp.ones(experts.shape[0], bool)
    if kept is None:
        order, sizes, held, rows = _sorted_pairs(experts, valid, held_n,
                                                 first)
        # a sorted row's place, by (token, pick): the sort's inverse
        back = _moved(jnp.arange(rows, dtype=jnp.int32), order[:rows])
    else:
        order, sizes, back = kept
        held = _held_pairs(experts, valid, held_n, first)
    weights = jnp.where(held, weights.reshape(-1, k), 0.0)
    return flat, weights, held, order, sizes, back, order // k


def _moved(values, place):
    """``out[place[i]] = values[i]`` for a permutation ``place``, by a
    sort on its keys: 0.05 ms for 49,152 scalars, where XLA's gather or
    scatter of scalars walks them one by one (0.23-0.47 ms)."""
    return jax.lax.sort((place, values), num_keys=1)[1]


def _interpret():
    """Off a TPU a kernel can only be interpreted (a rehearsal steers
    ``_use_pallas`` to the kernels on any backend)."""
    return INTERPRET or jax.default_backend() != "tpu"


def _fresh(shapes, after):
    """The starts of the buffers that :func:`_over_held_rows` fills tile
    by tile (``shapes``: a ``ShapeDtypeStruct`` each): no value at all.
    Every reader of such a buffer SELECTS the rows past the extent away
    (the grouped kernels mask by group, the way back reads none of
    them); none multiplies them by zero.

    Where the kernels run they are the results of ONE kernel that takes
    the pass's operands ``after`` and writes nothing: allocations the
    compiler cannot make before the pass could run, nor fold into one.
    (``jax.lax.empty`` depends on nothing, and the TPU's scheduler made
    every expert layer's buffers of both directions at the step's first
    instruction: 2.2 GB on top of the step's peak.) Elsewhere
    ``jax.lax.empty``, which is zeros on a backend without uninitialised
    memory."""
    if not (_use_pallas() or INTERPRET):
        return tuple(jax.lax.empty(s.shape, s.dtype) for s in shapes)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    # one element of each: the dependence is all that is wanted, and a
    # whole operand would be held to a kernel's row-major layout
    after = [jax.lax.slice(a, (0,) * a.ndim, (1,) * a.ndim) for a in after]
    return tuple(pl.pallas_call(
        lambda *refs: None, out_shape=list(shapes),
        in_specs=[anywhere] * len(after), out_specs=[anywhere] * len(shapes),
        interpret=_interpret(), name=FRESH_KERNEL_NAME)(*after))


def _held_row_tiles(sizes, rows):
    """``(tile, tiles)``: the row tile of a pass over ``rows`` sorted
    rows, and how many of them hold a row of an expert held here (a
    traced int32: ``ceil(sum(sizes[:-1]) / tile)``)."""
    tile = min(ROW_TILE, rows)
    return tile, (jnp.sum(sizes[:-1]) + (tile - 1)) // tile


def _kernel_rows(sizes, tm):
    """The rows that the row tiles of a grouped product over the held
    groups compute (a traced int32): a grouped kernel visits, for every
    group that got a row, each ``tm``-row tile the group touches, so
    visits x ``tm``; the landed rows themselves where the ragged product
    runs (``tm`` None). Over the landed rows it is what the tiles'
    padding costs under the chosen ``tm``."""
    if tm is None:
        return jnp.sum(sizes[:-1])
    return pallas_grouped.cut_visits(sizes, tm)[0] * tm


def _weights_grad_tiles(sizes, m):
    """``(row-tile visits, those a group's edge cuts)`` of ONE weight
    gradient over the held groups at ``m`` sorted rows (traced int32; a
    layer's two walk the same list): part of what a cut tile computes
    is another group's rows, masked away. Zeros where the composed form
    runs."""
    tm = _weights_row_tile(m)
    if tm is None:
        return jnp.int32(0), jnp.int32(0)
    return pallas_grouped.cut_visits(sizes, tm)


def _tile_start(i, tile, rows):
    """Where tile ``i`` of ``rows`` rows starts. A plain multiple of the
    tile where the tiles divide the rows: the compiler then knows the
    slices aligned and writes a tile's results in place (behind a
    ``minimum`` it computed them into a buffer of their own and copied
    that: 41 of 55 us a tile); else the last tile overlaps the one
    before it."""
    return i * tile if rows % tile == 0 \
        else jnp.minimum(i * tile, rows - tile)


def _over_held_rows(fn, sizes, by_row, whole=()):
    """``fn`` over the sorted rows that landed here, and over no other.

    ``by_row`` are ``[rows, ...]`` arrays indexed by sorted row, ``whole``
    arrays that every row may read, and ``fn(*tiles, *whole) -> tuple of
    [tile, ...] arrays`` works row by row. It is applied a row tile at a
    time under a loop whose TRIP COUNT is the number of tiles below the
    held extent (``_held_row_tiles``), each result written into a
    ``[rows, ...]`` buffer in place. The rows past the last tile that
    ran are never written and hold no defined value (``_fresh``). Where
    ``rows`` is no multiple of the tile the last tile overlaps the one
    before it and computes those rows again, to the same values."""
    rows = by_row[0].shape[0]
    tile, tiles = _held_row_tiles(sizes, rows)
    shapes = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (tile,) + a.shape[1:], a.dtype) for a in by_row), *whole)

    def body(i, filled):
        at = _tile_start(i, tile, rows)
        got = fn(*(jax.lax.dynamic_slice_in_dim(a, at, tile)
                   for a in by_row), *whole)
        return tuple(jax.lax.dynamic_update_slice_in_dim(f, g, at, 0)
                     for f, g in zip(filled, got))

    return jax.lax.fori_loop(0, tiles, body, _fresh(
        [jax.ShapeDtypeStruct((rows,) + s.shape[1:], s.dtype)
         for s in shapes], (*by_row, *whole)))


def _held_rows_of(source, token, sizes):
    """``source[token]`` (``[T, hidden]`` rows gathered to the sorted
    rows) for the rows that landed here. A source a loop: XLA keeps a
    loop's ``[T, hidden]`` source in on-chip memory (a tile's gather 14
    us for 67 from HBM), and two of them in one loop took the place of
    the head's operand there (its weight gradient ran 18.5 ms for
    11.6)."""
    return _over_held_rows(lambda t, source: (source[t],), sizes,
                           (token,), (source,))[0]


def _gate_up(h, activation):
    """``(act(gate), up)`` of a ``[rows, 2 * width]`` product, float32."""
    width = h.shape[-1] // 2
    return (ACTIVATIONS[activation](h[:, :width].astype(jnp.float32)),
            h[:, width:].astype(jnp.float32))


def _way_back_counted(state, sizes, back):
    """One direction's way back added to the op's two counters of it:
    the rows of a product's output that it read, and the ``T x k`` that
    all the pairs are (a state restored from before them has none)."""
    return {"moe_back_rows": state.get("moe_back_rows", 0)
            + jnp.sum(sizes[:-1]),
            "moe_back_rows_of": state.get("moe_back_rows_of", 0)
            + jnp.int32(back.shape[0])}


def _into_fresh(product, lhs, rhs, sizes):
    """A grouped product of the graph op (``grouped_matmul`` or
    ``grouped_matmul_rows_grad``) written into a buffer that starts with
    no value: every reader of the op's four row products stops at the
    held extent, so no zeros are written behind it."""
    (out,) = _fresh([jax.eval_shape(product, lhs, rhs, sizes)], (lhs,))
    return product(lhs, rhs, sizes, out=out)


def _token_sums(rows, back, held, coeff, dtype):
    """The way back to token order: ``sums[t] = sum over the picks j of
    token t that are held here of coeff[t, j] * rows[back[t, j]]``
    (``coeff`` None: 1), accumulated in float32 and cast to ``dtype``
    once. ``rows`` ``[T x k (+ pad), hidden]`` a product's output by
    sorted row, ``back`` ``[T x k]`` a pair's sorted row, ``held`` ``[T,
    k]``. Returns ``[T, hidden]``; a token none of whose picks is held
    gets exactly 0.

    It visits the pairs that landed here and reads their rows, none past
    the held extent (where a product of the op leaves no defined value).
    The tokens are sorted by HOW MANY of their picks are held, most
    first; the tokens that hold an ``r``-th pick are then the first
    ``live[r]`` of that order, whatever ``r``. A loop over tiles of
    ``TOKEN_TILE`` sorted tokens runs the tiles that hold a pair at all
    (``ceil(live[0] / tile)``); inside it a loop over ``r`` runs as far
    as the tile's first token holds picks, gathers the tile's ``r``-th
    rows and adds them to a float32 tile, selecting away the tokens past
    ``live[r]`` (their index reads row 0, a held row wherever a tile
    runs). The gathers read ``n`` rows plus under a tile a rank; nothing
    is shifted and no ``[T x k, ...]`` array is made. Last, a token
    reads the sum at its place in the sorted order."""
    tokens, k = held.shape
    count = jnp.sum(held, axis=1, dtype=jnp.int32)
    # a token's r-th held pick, [T, pick, r]: its row and coefficient
    # by r (row 0 and no weight where the token holds fewer)
    rank = jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1
    is_rth = held[:, :, None] & (rank[:, :, None] == jnp.arange(k))
    by_rank = [jnp.where(is_rth, a.reshape(tokens, k, 1), 0).sum(axis=1)
               for a in ([back] if coeff is None else [back, coeff])]
    # one sort carries a token's k rows (and coefficients) to its place
    most, order, *sorted_by_rank = jax.lax.sort(
        [-count, jnp.arange(tokens, dtype=jnp.int32)]
        + [a[:, r] for a in by_rank for r in range(k)], num_keys=1)
    source = jnp.stack(sorted_by_rank[:k])                      # [r, T]
    scale = jnp.stack(sorted_by_rank[k:]) if coeff is not None else None
    live = jnp.sum(count[None, :] > jnp.arange(k)[:, None], axis=1,
                   dtype=jnp.int32)
    tile = min(TOKEN_TILE, tokens)
    place_in_tile = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)

    def a_tile(i, sums):
        at = _tile_start(i, tile, tokens)

        def a_rank(r, acc):
            got = rows[jax.lax.dynamic_slice(source, (r, at), (1, tile))[0]] \
                .astype(jnp.float32)
            if scale is not None:
                got = got * jax.lax.dynamic_slice(
                    scale, (r, at), (1, tile))[0][:, None]
            return acc + jnp.where(at + place_in_tile < live[r], got, 0.0)

        acc = jax.lax.fori_loop(0, -most[at], a_rank, jnp.zeros(
            (tile, rows.shape[1]), jnp.float32))
        return jax.lax.dynamic_update_slice_in_dim(
            sums, acc.astype(dtype), at, 0)

    (sums,) = _fresh([jax.ShapeDtypeStruct((tokens, rows.shape[1]), dtype)],
                     (rows,))
    sums = jax.lax.fori_loop(0, (live[0] + tile - 1) // tile, a_tile, sums)
    place = _moved(jnp.arange(tokens, dtype=jnp.int32), order)
    return jnp.where((count > 0)[:, None], sums[place], 0)


def _held_extent_passes(x, token, sizes, back, held, weights, w_gate_up,
                        w_down, activation, dtype):
    """The forward of the held experts with every pass run to the held
    extent, the ONE body of :class:`HeldExpertsOp` and of
    :func:`held_experts` over ``ROW_TILE`` sorted rows: the gather of
    the tokens to the sorted rows that landed here (``x [T, hidden]``,
    ``token`` a sorted row's token), gate|up into a buffer with no
    value, the activation under :func:`_over_held_rows`, down into
    another such buffer, and the way back (:func:`_token_sums`: ``back``
    a pair's sorted row, ``held`` ``[T, k]``, ``weights`` ``[T, k]``
    with 0 for a pair held elsewhere). Returns ``(the sums [T, hidden]
    in dtype, xs, h)``; the last two are what the op's backward and its
    counters read again."""
    xs = _held_rows_of(x, token, sizes)
    h = _into_fresh(grouped_matmul, xs, w_gate_up, sizes)

    def activated(h):
        gate, up = _gate_up(h, activation)
        return ((gate * up).astype(x.dtype),)

    (act,) = _over_held_rows(activated, sizes, (h,))
    ys = _into_fresh(grouped_matmul, act, w_down, sizes)
    return _token_sums(ys, back, held, weights, dtype), xs, h


# serving's entry: a program's expert layers have one shape, so the
# passes are traced once a shape and lowered once a program, not once a
# layer (a 30-program warm-up spent 7 s more in tracing without it).
# The graph op calls the body itself: its step's text is pinned
_held_extent_passes_once = jax.jit(_held_extent_passes,
                                   static_argnums=(8, 9))


class HeldExpertsOp(Op):
    """:func:`held_experts` as a graph node: ``x [B, S, hidden]``, the
    router's ``weights`` and ``experts`` ``[B, S, k]``, and the stacked
    parameters ``w_gate_up [held, hidden, 2 * width]`` / ``w_down [held,
    width, hidden]`` of the experts ``first .. first + held - 1``.
    Returns their part of the layer's sum, ``[B, S, hidden]`` in ``x``'s
    dtype; what the experts held elsewhere would add is left out. No
    token is dropped under any imbalance.

    The passes round the two products run to the held extent (the
    module's docstring; ``_over_held_rows``).

    A training step counts on the device, in the op's state: rows by
    held expert, held experts that got a row, the row tiles its passes
    ran and the tiles that all the rows are, the rows the way back read
    and the pairs there are, the row-tile visits of a weight gradient
    and those of them that a group's edge cuts, steps
    (``Executor.moe_counters()``; nothing is read inside a step)."""

    stateful = True
    state_dtype = jnp.int32

    def __init__(self, node_in, weights, experts, w_gate_up, w_down,
                 first=0, activation="silu", ctx=None):
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(ACTIVATIONS)}")
        super().__init__(HeldExpertsOp,
                         [node_in, weights, experts, w_gate_up, w_down], ctx)
        self.first = first
        self.activation = activation

    def state_shapes(self, input_shapes):
        return {"moe_rows_by_expert": (input_shapes[3][0],),
                "moe_expert_visits": (), "moe_row_tiles": (),
                "moe_row_tiles_of": (), "moe_kernel_rows": (),
                "moe_back_rows": (), "moe_back_rows_of": (),
                "moe_dw_tiles": (), "moe_dw_cut_tiles": (), "steps": ()}

    def compute(self, input_vals, ectx):
        x, weights, experts, w_gate_up, w_down = input_vals
        flat, weights, held, order, sizes, back, token = _expert_rows(
            x, weights, experts, self.first, w_gate_up.shape[0])
        out, xs, h = _held_extent_passes(
            flat, token, sizes, back, held, weights, w_gate_up, w_down,
            self.activation, x.dtype)
        if ectx.training:
            # the backward reads the sort and the first product again
            ectx.cache[("held_experts", self.id)] = (order, sizes, back, h)
            state = ectx.get_state(self)
            if state is not None:
                landed = sizes[:-1]
                rows = order.shape[0]
                tile, tiles = _held_row_tiles(sizes, rows)
                # the row tile follows the rows alone: given ``out`` or
                # not, both of the forward's products walk the same
                product = _product_tiles("forward", xs, w_gate_up.shape[-1])
                # (the backward's weight gradients walk the same sizes)
                dw_tiles, dw_cut = _weights_grad_tiles(sizes, rows)
                ectx.put_state(self, {
                    "moe_rows_by_expert": state["moe_rows_by_expert"]
                    + landed,
                    "moe_expert_visits": state["moe_expert_visits"]
                    + jnp.sum(landed > 0, dtype=jnp.int32),
                    # (a state restored from before these three has none)
                    "moe_row_tiles": state.get("moe_row_tiles", 0) + tiles,
                    "moe_row_tiles_of": state.get("moe_row_tiles_of", 0)
                    + jnp.int32(-(-rows // tile)),
                    "moe_kernel_rows": state.get("moe_kernel_rows", 0)
                    + _kernel_rows(sizes, product and product[0]),
                    **_way_back_counted(state, sizes, back),
                    "moe_dw_tiles": state.get("moe_dw_tiles", 0) + dw_tiles,
                    "moe_dw_cut_tiles": state.get("moe_dw_cut_tiles", 0)
                    + dw_cut,
                    "steps": state["steps"] + 1})
        return out.reshape(x.shape)

    def gradient(self, output_grad):
        packed = _HeldExpertsGradientOp(self, output_grad, ctx=self.raw_ctx)
        return [_Part(packed, self.inputs[0], 0, ctx=self.raw_ctx),
                _Part(packed, self.inputs[1], 1, ctx=self.raw_ctx),
                None,
                _Part(packed, self.inputs[3], 2, ctx=self.raw_ctx),
                _Part(packed, self.inputs[4], 3, ctx=self.raw_ctx)]

    def infer_shape(self, input_shapes):
        return input_shapes[0]


class _HeldExpertsGradientOp(Op):
    """Packed ``(dx, dweights, dw_gate_up, dw_down)``. With the sorted
    rows ``xs``, ``h = xs W_in[g]``, ``a = act(gate) * up`` and ``dy_r``
    the output gradient at a sorted row's token:

    * ``da' = dy_r W_down[g]^T`` (a grouped product, rows), the pair's
      weight gradient ``<da', a>`` and ``da = w_r da'``;
    * ``dW_down[g] = (w_r a)^T dy_r`` (a transposed grouped product);
    * ``dh = [da * up * act'(gate), da * act(gate)]``;
    * ``dW_in[g] = xs^T dh`` (transposed), ``dxs = dh W_in[g]^T`` (rows),
      and a token's ``dx`` the sum of its pairs' rows.

    The rows of experts held elsewhere are computed by none of the four
    and are read by nothing. The gathers, the elementwise work between
    the products and the way back (``_token_sums``) run to the held
    extent (``_over_held_rows``)."""

    def __init__(self, forward_op, output_grad, ctx=None):
        super().__init__(_HeldExpertsGradientOp,
                         list(forward_op.inputs) + [output_grad], ctx)
        self.forward_op = forward_op

    def compute(self, input_vals, ectx):
        x, weights, experts, w_gate_up, w_down, dy = input_vals
        fwd = self.forward_op
        k = experts.shape[-1]
        kept = ectx.cache.get(("held_experts", fwd.id))
        flat, weights, held, order, sizes, back, token = _expert_rows(
            x, weights, experts, fwd.first, w_gate_up.shape[0],
            None if kept is None else kept[:3])
        xs = _held_rows_of(flat, token, sizes)
        dys = _held_rows_of(dy.reshape(flat.shape), token, sizes)
        h = grouped_matmul(xs, w_gate_up, sizes) if kept is None \
            else kept[3]
        width = h.shape[-1] // 2
        da = _into_fresh(grouped_matmul_rows_grad, dys, w_down,
                         sizes)                             # [rows, width]

        # a pair's weight at its sorted row; the pad rows weigh nothing
        w_row = _moved(weights.reshape(-1), back)
        w_row = jnp.pad(w_row, (0, order.shape[0] - w_row.shape[0]))

        def between(h, da, w_row):
            gate, up = _gate_up(h, fwd.activation)
            act = gate * up
            da = da.astype(jnp.float32)
            w_row = w_row[:, None]
            _, slope = jax.jvp(ACTIVATIONS[fwd.activation],
                               (h[:, :width].astype(jnp.float32),),
                               (jnp.ones_like(gate),))
            dw_row = jnp.sum(da * act, axis=-1)
            da = da * w_row
            dh = jnp.concatenate([da * up * slope, da * gate], axis=-1)
            return dw_row, (w_row * act).astype(x.dtype), dh.astype(x.dtype)

        dw_row, weighted, dh = _over_held_rows(between, sizes,
                                               (h, da, w_row))
        rows = back.shape[0]
        dweights = _moved(dw_row[:rows], order[:rows]).reshape(-1, k)
        dweights = jnp.where(held, dweights, 0.0).reshape(experts.shape)
        # one visit list for the layer's two weight gradients
        visits = _weights_grad_visits(sizes, order.shape[0])
        dw_down = grouped_matmul_weights_grad(weighted, dys, sizes, visits)
        dw_gate_up = grouped_matmul_weights_grad(xs, dh, sizes, visits)
        dxs = _into_fresh(grouped_matmul_rows_grad, dh, w_gate_up, sizes)
        dx = _token_sums(dxs, back, held, None, x.dtype)
        if kept is not None and ectx.get_state(fwd) is not None:
            put = ectx.new_state[fwd]       # the forward's, this step
            ectx.put_state(fwd, {**put, **_way_back_counted(put, sizes, back)})
        return (dx.reshape(x.shape), dweights.astype(jnp.float32),
                dw_gate_up, dw_down)

    def gradient(self, output_grad):
        raise NotImplementedError

    def infer_shape(self, input_shapes):
        return input_shapes[0]


def router_op(node_in, w_router, top_k, ctx=None, scoring="softmax",
              bias=None, scale=1.0, norm_eps=0.0):
    """The ``top_k`` chosen experts' weights ``[..., k]`` (float32) of
    ``node_in [..., hidden]`` under ``w_router [hidden, E]``: a softmax
    over the chosen, or with ``scoring="sigmoid"`` the chosen sigmoid
    scores normalised and scaled, selected by ``score + bias``; see
    :class:`RouterOp`."""
    return RouterOp(node_in, w_router, top_k, ctx=ctx, scoring=scoring,
                    bias=bias, scale=scale, norm_eps=norm_eps)


def router_picks_op(router, ctx=None):
    """The indices ``[..., k]`` int32 a :func:`router_op` node chose."""
    return RouterPicksOp(router, ctx=ctx)


def held_experts_op(node_in, weights, experts, w_gate_up, w_down, first=0,
                    activation="silu", ctx=None):
    """The part of an expert layer's sum that the experts ``first ..
    first + held - 1`` give; see :class:`HeldExpertsOp`."""
    return HeldExpertsOp(node_in, weights, experts, w_gate_up, w_down,
                         first, activation, ctx=ctx)
