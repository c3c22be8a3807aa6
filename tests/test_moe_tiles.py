"""The tile rule of the grouped expert products (``ops/moe.py:
_kernel_tiles``): arithmetic on a product's static shapes, held here to
the shapes the benchmark's cells run, and the three kernels under the
rule's tiles against the ragged products; the counter of the rows the
row tiles compute (``moe_kernel_rows``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import attention, moe


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


@pytest.mark.parametrize("name,product", PRODUCTS,
                         ids=[name for name, _ in PRODUCTS])
def test_the_rule_gives_tiles_the_kernels_take(name, product):
    kind, m, k, n, out = product
    tm, tk, tn = moe._kernel_tiles(kind, m, k, n, 2, out)
    # whole lanes, tiles that divide their extents (megablox would take
    # a tile that covers k or n with a remainder, at a mask a step)
    assert tm % 128 == tk % 128 == tn % 128 == 0
    assert m % tm == k % tk == n % tn == 0
    assert moe._block_bytes(kind, tm, tk, tn, 2, out) \
        <= moe.KERNEL_BLOCK_BYTES < 16 * 2 ** 20
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
        # the chip's ridge (197e12 / 819e9 = 240)
        assert tk * tn / (tk + tn) > 240
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
        assert moe._block_bytes(kind, *tiles, 4, out) \
            <= moe.KERNEL_BLOCK_BYTES
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
