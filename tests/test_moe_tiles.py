"""The tile rule of the grouped expert products (``ops/moe.py:
_kernel_tiles``): arithmetic on a product's static shapes, held here to
the shapes the benchmark's cells run, and the three kernels under the
rule's tiles against the ragged products; the counter of the rows the
row tiles compute (``moe_kernel_rows``); and the weights' gradient's own
kernel (``ops/pallas_grouped.py``) over the groupings that cut its row
tiles every way, with the counters of its visits (``moe_dw_tiles``,
``moe_dw_cut_tiles``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import attention, moe, pallas_grouped


def _layer(rows, hidden, gate_up):
    """The six grouped products of a held-expert layer's training step
    as ``(kind, m, k, n, out)``: forward gate|up and down, the
    backward's ``da`` and ``dxs`` (right side transposed), the two
    weight gradients."""
    width = gate_up // 2
    return [("forward", rows, hidden, gate_up, True),
            ("forward", rows, width, hidden, True),
            ("rows", rows, hidden, width, True),
            ("rows", rows, gate_up, hidden, True),
            ("weights", rows, width, hidden, False),
            ("weights", rows, hidden, gate_up, False)]


def _serving(rows, hidden, gate_up):
    """A serving pass's two products (no ``out``: the library's zero
    fill behind the groups is serving's)."""
    return [(kind, m, k, n, False)
            for kind, m, k, n, _ in _layer(rows, hidden, gate_up)[:2]]


# the two sparse train cells: S = 8,192 x 6 and x 4 picks
TRAIN = {"smallthinker": _layer(49152, 2560, 1536),
         "lfm2": _layer(32768, 2048, 3584)}
# a 4,096-token chunk of a prompt (``TOKEN_CHUNK``) and a decode step's
# 128 padded rows, at the four expert serve configurations' picks a
# token, hidden and gate|up widths
SERVE = {"sarvam": (8, 4096, 4096), "xing": (4, 3584, 2048),
         "trinity": (4, 3072, 6144), "ling": (8, 2560, 1536)}
PRODUCTS = [(f"{cell}-{i}", p) for cell, ps in TRAIN.items()
            for i, p in enumerate(ps)] \
    + [(f"{name}-{phase}-{i}", p) for name, (k, *widths) in SERVE.items()
       for phase, rows in (("prefill", moe.TOKEN_CHUNK * k), ("decode", 128))
       for i, p in enumerate(_serving(rows, *widths))]


def _budget(kind):
    return moe.WEIGHTS_BLOCK_BYTES if kind == "weights" \
        else moe.KERNEL_BLOCK_BYTES


@pytest.mark.parametrize("name,product", PRODUCTS,
                         ids=[name for name, _ in PRODUCTS])
def test_the_rule_gives_tiles_the_kernels_take(name, product):
    kind, m, k, n, out = product
    tm, tk, tn = moe._kernel_tiles(kind, m, k, n, 2, out)
    # whole lanes, tiles that divide their extents (megablox would take
    # a tile that covers k or n with a remainder, at a mask a step)
    assert tm % 128 == tk % 128 == tn % 128 == 0
    assert m % tm == k % tk == n % tn == 0
    # megablox's kernels live in the 16 MiB a kernel gets unasked; the
    # weights' gradient asks for its blocks' bytes itself
    assert moe._block_bytes(kind, tm, tk, tn, 2, out) <= _budget(kind)
    assert moe.KERNEL_BLOCK_BYTES < 16 * 2 ** 20 < moe.WEIGHTS_BLOCK_BYTES
    if m == 128:
        # a decode step: one row tile, blocks no longer than a side of
        # 1,024 (at sarvam's and trinity's widths the tiles PR 56 ran)
        assert tm == 128 and max(tk, tn) <= moe.ONE_TILE_SIDE
        if name.split("-")[0] in ("sarvam", "trinity"):
            assert (tk, tn) == (1024, 1024)
    if name.split("-")[0] in TRAIN and kind != "weights":
        assert tk == k, "an expert's weights once a column tile"
    if kind == "weights":
        # the two row blocks a step bring more operations a byte than
        # the chip's ridge (197e12 / 819e9 = 240), and a row block is
        # read at most twice a call (once: the result tile is whole)
        assert tk * tn / (tk + tn) > 240
        assert (k // tk) * (n // tn) <= 2
    # static shapes in, the same tiles out: nothing measured, no store
    assert moe._kernel_tiles(kind, m, k, n, 2, out) == (tm, tk, tn)


@pytest.mark.parametrize("kind", ["forward", "rows", "weights"])
@pytest.mark.parametrize("m,k,n", [(256, 100, 128), (256, 128, 192),
                                   (200, 128, 128)])
def test_the_rule_refuses_widths_off_whole_lanes(kind, m, k, n):
    assert moe._kernel_tiles(kind, m, k, n) is None


def test_wider_operands_get_narrower_tiles():
    """The budget is bytes: float32 operands (the tests' own, under
    ``INTERPRET``) halve what fits."""
    one_tile = [("forward", 256, 2048, 2048, True),
                ("rows", 256, 1024, 4096, False)]
    for kind, m, k, n, out in TRAIN["smallthinker"] + one_tile:
        tiles = moe._kernel_tiles(kind, m, k, n, 4, out)
        assert moe._block_bytes(kind, *tiles, 4, out) <= _budget(kind)
    # ONE 256-row tile writing into ``out``: blocks of 1,024 a side are
    # 15.0 MiB at float32, so the column tile gives way
    assert moe._kernel_tiles(*one_tile[0][:4], 4, True) == (256, 1024, 512)
    assert moe._kernel_tiles(*one_tile[0][:4], 2, True) == (256, 1024, 1024)


# -- the kernels under the rule's tiles --------------------------------------

@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setattr(moe, "INTERPRET", True)
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)


# 2,048 sorted rows, for a row tile of 256: an empty group, a
# group inside one tile, a group over six tiles, one that
# ends a tile, and 512 rows behind the held groups
SIZES = [0, 90, 1200, 150, 96, 512]
# a budget under which the rule cuts 384 columns into three tiles and
# keeps the contraction of 256 whole, as the cells' budget does at theirs
SMALL_BUDGET = 2_000_000


def _ragged_case(k, n, seed=3):
    rng = np.random.RandomState(seed)
    m, held = sum(SIZES), len(SIZES) - 1
    lhs = rng.randn(m, k).astype(np.float32)
    lhs[m - SIZES[-1]:] = np.nan          # nothing may read these rows
    return (jnp.asarray(lhs), jnp.asarray(0.1 * rng.randn(held, k, n),
                                          jnp.float32),
            jnp.asarray(SIZES, jnp.int32), m - SIZES[-1])


@pytest.mark.parametrize("product", ["forward", "rows"])
@pytest.mark.parametrize("out", ["absent", "given"])
def test_row_products_equal_the_ragged_product(kernels, monkeypatch,
                                               product, out):
    monkeypatch.setattr(moe, "KERNEL_BLOCK_BYTES", SMALL_BUDGET)
    k, n = 256, 384
    lhs, rhs, sizes, here = _ragged_case(k, n)
    if product == "rows":
        rhs = rhs.swapaxes(1, 2)
    fn = moe.grouped_matmul if product == "forward" \
        else moe.grouped_matmul_rows_grad
    tiles = moe._kernel_tiles(product, *lhs.shape, n, 4, out == "given")
    assert tiles[1] == k and tiles[2] < n and lhs.shape[0] // tiles[0] > 1
    kept = jnp.full((lhs.shape[0], n), 7.0, jnp.float32)
    got = np.asarray(fn(lhs, rhs, sizes) if out == "absent"
                     else fn(lhs, rhs, sizes, out=kept))
    assert (got[here:] == (0.0 if out == "absent" else 7.0)).all()
    mats = rhs if product == "forward" else rhs.swapaxes(1, 2)
    want = jax.lax.ragged_dot(jnp.nan_to_num(lhs), mats, sizes[:-1])
    np.testing.assert_allclose(got[:here], np.asarray(want)[:here],
                               rtol=1e-5, atol=1e-5)


def test_weights_product_equals_the_composed_form(kernels, monkeypatch):
    monkeypatch.setattr(moe, "KERNEL_BLOCK_BYTES", SMALL_BUDGET)
    # (the one line PR 64 added: the kernel's budget is its own now)
    monkeypatch.setattr(moe, "WEIGHTS_BLOCK_BYTES", SMALL_BUDGET)
    k, n = 256, 384
    lhs, _, sizes, here = _ragged_case(k, n)
    rng = np.random.RandomState(4)
    dy = rng.randn(lhs.shape[0], n).astype(np.float32)
    dy[here:] = np.nan
    tiles = moe._kernel_tiles("weights", *lhs.shape, n, 4)
    assert tiles[1] * tiles[2] < k * n and lhs.shape[0] // tiles[0] > 1
    got = moe.grouped_matmul_weights_grad(lhs, jnp.asarray(dy), sizes)
    monkeypatch.setattr(moe, "INTERPRET", False)
    monkeypatch.setattr(attention, "_use_pallas", lambda: False)
    want = moe.grouped_matmul_weights_grad(lhs, jnp.asarray(dy), sizes)
    assert not np.asarray(got[0]).any()            # the empty group
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


# -- the weights' gradient's own kernel --------------------------------------

# group sizes over row tiles of 256 (the last entry: the rows behind the
# held groups, which hold NaN below)
GROUPINGS = {
    "groups_end_on_tile_edges": [256, 512, 256, 1024],
    "several_groups_in_one_tile": [40, 50, 60, 70, 1828],
    "a_group_inside_one_tile_mid_tile": [300, 100, 400, 1248],
    "an_empty_group_first": [0, 300, 500, 1248],
    "empty_groups_in_the_middle": [300, 0, 0, 500, 1248],
    "an_empty_group_last": [300, 500, 0, 1248],
    "every_group_empty": [0, 0, 0, 2048],
    "one_group_holds_every_row": [2048, 0],
    "the_extent_ends_mid_tile": [300, 410, 1338],
    "the_extent_ends_on_a_tile_edge": [300, 468, 1280],
    "m_of_one_tile": [100, 60, 96],
    "m_of_one_tile_one_group": [256, 0],
}
# the four weight gradients of the two train cells at 1/8 of their rows,
# the widths whole: (m, k, n, held)
CELL_SHAPES = {
    "smallthinker_gate_up": (6144, 2560, 1536, 16),
    "smallthinker_down": (6144, 768, 2560, 16),
    "lfm2_gate_up": (4096, 2048, 3584, 8),
    "lfm2_down": (4096, 1792, 2048, 8),
}


def _by_group(lhs, dy, sizes):
    """The product a group at a time, float32 in numpy (NaN rows behind
    the extent are in no group)."""
    ends = np.cumsum(sizes[:-1])
    return np.stack([lhs[b - size:b].T @ dy[b - size:b]
                     for size, b in zip(sizes[:-1], ends)])


@pytest.mark.parametrize("case", list(GROUPINGS) + list(CELL_SHAPES))
def test_the_weights_kernel_equals_the_composed_form(kernels, monkeypatch,
                                                     case):
    """The kernel under ``INTERPRET`` over every way a group's edge can
    cut a row tile, with NaN in every row behind the held extent (the
    result finite and equal: those rows are selected away). The small
    cases against the composed form that the op keeps for widths off
    whole lanes, under a budget that cuts the result into several (k,
    column) tiles; the cells' shapes, bfloat16 operands under the cells'
    own tiles, against the product a group at a time."""
    rng = np.random.RandomState(len(case))
    if case in GROUPINGS:
        monkeypatch.setattr(moe, "WEIGHTS_BLOCK_BYTES", 1_400_000)
        sizes, k, n, dtype = GROUPINGS[case], 256, 384, jnp.float32
    else:
        m, k, n, held = CELL_SHAPES[case]
        # ragged groups of 100-400 rows, as a step's routing leaves them
        landed = rng.randint(100, 400, size=held)
        landed[rng.randint(held)] = 0
        sizes, dtype = [*landed, m - landed.sum()], jnp.bfloat16
    m, here = sum(sizes), sum(sizes[:-1])
    lhs = rng.randn(m, k).astype(np.float32)
    dy = rng.randn(m, n).astype(np.float32)
    lhs[here:] = dy[here:] = np.nan
    lhs, dy = jnp.asarray(lhs, dtype), jnp.asarray(dy, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    tiles = moe._kernel_tiles("weights", m, k, n, lhs.dtype.itemsize)
    got = np.asarray(moe.grouped_matmul_weights_grad(lhs, dy, sizes))
    assert got.shape == (len(sizes) - 1, k, n) and got.dtype == np.float32
    assert np.isfinite(got).all()
    if case in GROUPINGS:
        assert (k // tiles[1]) * (n // tiles[2]) > 1
        monkeypatch.setattr(moe, "INTERPRET", False)
        monkeypatch.setattr(attention, "_use_pallas", lambda: False)
        want = np.asarray(moe.grouped_matmul_weights_grad(lhs, dy, sizes))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    else:
        assert tiles == moe._kernel_tiles("weights", m * 8, k, n, 2)
        want = _by_group(np.asarray(lhs.astype(jnp.float32)),
                         np.asarray(dy.astype(jnp.float32)),
                         np.asarray(sizes))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    for g, size in enumerate(np.asarray(sizes)[:-1]):
        assert size or not got[g].any()         # an empty group: zeros


def test_a_layers_two_weight_gradients_share_one_visit_list(kernels):
    """The visit list follows the sizes and the rows alone: made once,
    it serves both products of a layer, and a product given none makes
    the same."""
    rng = np.random.RandomState(8)
    sizes = jnp.asarray(GROUPINGS["empty_groups_in_the_middle"], jnp.int32)
    m = int(sizes.sum())
    visits = moe._weights_grad_visits(sizes, m)
    for k, n in ((256, 128), (128, 384)):
        lhs = jnp.asarray(rng.randn(m, k), jnp.float32)
        dy = jnp.asarray(rng.randn(m, n), jnp.float32)
        np.testing.assert_array_equal(
            moe.grouped_matmul_weights_grad(lhs, dy, sizes, visits),
            moe.grouped_matmul_weights_grad(lhs, dy, sizes))


def _count_tiles(sizes, tm):
    """``(visits, cut)`` by walking every group's tiles in numpy."""
    visits = cut = 0
    start = 0
    for size in sizes[:-1]:
        end = start + size
        for tile in range(start // tm, -(-end // tm) if size else 0):
            visits += 1
            cut += tile * tm < start or (tile + 1) * tm > end
        start = end
    return visits, cut


@pytest.mark.parametrize("case", list(GROUPINGS))
@pytest.mark.parametrize("tm", [128, 256])
def test_dw_tiles_count_the_visits_and_those_an_edge_cuts(case, tm):
    """``moe_dw_tiles`` / ``moe_dw_cut_tiles`` (``pallas_grouped.
    cut_visits``) against a walk in numpy on the same sizes; and the
    kernel's visit list holds exactly those visits and one for every
    empty group."""
    sizes = GROUPINGS[case]
    m = sum(sizes)
    got = pallas_grouped.cut_visits(jnp.asarray(sizes, jnp.int32), tm)
    want = _count_tiles(sizes, tm)
    assert tuple(int(x) for x in got) == want
    group, tile, offsets, count = (np.asarray(a) for a in
                                   pallas_grouped.visits(
                                       jnp.asarray(sizes, jnp.int32), m, tm))
    empty = sum(1 for size in sizes[:-1] if size == 0)
    assert int(count) == want[0] + empty <= len(group)
    starts = np.cumsum([0] + sizes[:-1])
    assert offsets.tolist() == starts.tolist()
    walked = [(g, t) for g, size in enumerate(sizes[:-1])
              for t in (range(starts[g] // tm, -(-(starts[g] + size) // tm))
                        if size else [min(starts[g] // tm, m // tm - 1)])]
    assert list(zip(group[:count].tolist(), tile[:count].tolist())) == walked


# -- the counter -------------------------------------------------------------

@pytest.mark.parametrize("sizes,tm,want", [
    # by hand: rows 0-90 tile 0; 90-690 tiles 0-2 (three visits); 690-768
    # tile 2; 768-810 tile 3: six visits of 256 rows for 810 landed
    ([0, 90, 600, 78, 42, 214], 256, 6 * 256),
    ([0, 90, 600, 78, 42, 214], 128, (1 + 6 + 1 + 1) * 128),
    ([0, 0, 0, 512], 256, 0),
    ([256, 256, 0], 256, 512),
    ([0, 90, 600, 78, 42, 214], None, 810),
])
def test_kernel_rows_are_visits_times_the_row_tile(sizes, tm, want):
    assert int(moe._kernel_rows(jnp.asarray(sizes, jnp.int32), tm)) == want
