"""Flash attention (Pallas, interpret mode on CPU) and ring attention
(sequence parallelism over an 8-device mesh) against the composed-XLA
reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from hetu_tpu.ops.attention import attention_reference
from hetu_tpu.ops.pallas_attention import flash_attention
from hetu_tpu.parallel.ring import ring_attention_sharded


def _qkv(b=2, h=4, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    return mk(), mk(), mk()


def _mask(b=2, s=64, valid=48):
    m = np.zeros((b, 1, 1, s), np.float32)
    m[:, :, :, valid:] = -1e9
    return jnp.asarray(m)


def test_flash_attention_matches_reference():
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, None, 0.25)
    out = flash_attention(q, k, v, None, sm_scale=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_with_mask():
    q, k, v = _qkv(seed=1)
    mask = _mask()
    ref = attention_reference(q, k, v, mask, 0.25)
    out = flash_attention(q, k, v, mask, sm_scale=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal():
    q, k, v = _qkv(seed=2, s=32)
    s = 32
    cmask = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                      -1e9)[None, None]
    ref = attention_reference(q, k, v, cmask, 0.25)
    out = flash_attention(q, k, v, None, sm_scale=0.25, causal=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture
def mesh8():
    devs = np.asarray(jax.devices()[:8])
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return Mesh(devs, axis_names=("sp",))


def test_ring_attention_matches_reference(mesh8):
    q, k, v = _qkv(s=64, seed=3)
    ref = attention_reference(q, k, v, None, 0.25)
    out = ring_attention_sharded(q, k, v, mesh8, "sp", sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_with_mask(mesh8):
    q, k, v = _qkv(s=64, seed=4)
    mask = _mask(s=64, valid=40)
    ref = attention_reference(q, k, v, mask, 0.25)
    out = ring_attention_sharded(q, k, v, mesh8, "sp", sm_scale=0.25,
                                 mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients(mesh8):
    q, k, v = _qkv(s=32, b=1, h=2, d=8, seed=5)

    def loss_ring(q_, k_, v_):
        return jnp.sum(
            ring_attention_sharded(q_, k_, v_, mesh8, "sp",
                                   sm_scale=0.3) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_, None, 0.3) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_long_context_ring():
    """Sequence far beyond the reference's 512-token ceiling: 8k tokens
    sharded 8 ways runs in O(S/n) memory per device."""
    devs = np.asarray(jax.devices()[:8])
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(devs, axis_names=("sp",))
    rng = np.random.RandomState(0)
    s = 8192
    q = jnp.asarray(rng.randn(1, 2, s, 16), jnp.float32) * 0.1
    k = jnp.asarray(rng.randn(1, 2, s, 16), jnp.float32) * 0.1
    v = jnp.asarray(rng.randn(1, 2, s, 16), jnp.float32) * 0.1
    out = ring_attention_sharded(q, k, v, mesh, "sp", sm_scale=0.25)
    assert out.shape == (1, 2, s, 16)
    assert bool(jnp.isfinite(out).all())


def test_flash_attention_op_kernel_path(monkeypatch):
    """FlashAttentionOp -> Pallas kernel dispatch (interpret mode stands
    in for the TPU backend): pad mask, causal, and both together."""
    from hetu_tpu.ops import attention as attn_mod
    from hetu_tpu.ops import pallas_attention as pk
    from hetu_tpu.ops.attention import FlashAttentionOp
    from hetu_tpu.graph.node import ExecContext
    import hetu_tpu as ht

    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    monkeypatch.setattr(pk, "INTERPRET", True)

    q, k, v = _qkv(s=32, seed=7)
    mask = _mask(s=32, valid=20)
    ectx = ExecContext(training=False)
    qn, kn, vn, mn = [ht.Variable(n, trainable=False) for n in "qkvm"]
    for use_mask, causal in [(True, False), (False, True), (True, True)]:
        op = FlashAttentionOp(qn, kn, vn, mn if use_mask else None,
                              sm_scale=0.25, causal=causal)
        vals = [q, k, v] + ([mask] if use_mask else [])
        out = op.compute(vals, ectx)
        m = mask if use_mask else None
        if causal:
            cm = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), 0.0,
                           -1e9)[None, None]
            m = cm if m is None else m + cm
        ref = attention_reference(q, k, v, m, 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_attention_tiny_seq_fallback():
    q, k, v = _qkv(s=4, d=8, seed=8)
    ref = attention_reference(q, k, v, None, 0.5)
    out = flash_attention(q, k, v, None, sm_scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_odd_seq_falls_back():
    # s=260: block sizing would leave tail rows unwritten in the kernel;
    # must route to the composed reference and stay correct
    q, k, v = _qkv(b=1, h=2, s=260, d=16, seed=3)
    ref = attention_reference(q, k, v, None, 0.25)
    out = flash_attention(q, k, v, None, sm_scale=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_fused_backward():
    """Fused Pallas backward (recompute form) vs jax.grad of the composed
    reference: no-mask, padding-mask, causal, and both."""
    from hetu_tpu.ops.pallas_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)

    for use_mask, causal, s in [(False, False, 64), (True, False, 64),
                                (False, True, 64), (True, True, 128)]:
        q, k, v = _qkv(s=s, seed=11 + s)
        mask = _mask(s=s, valid=s - 10) if use_mask else None
        o, lse = flash_attention_with_lse(q, k, v, mask, sm_scale=0.25,
                                          causal=causal, interpret=True)
        assert o is not None
        rng = np.random.RandomState(5)
        dy = jnp.asarray(rng.randn(*q.shape), jnp.float32)

        def f(q_, k_, v_):
            m = mask
            if causal:
                cm = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                               -1e30)[None, None]
                m = cm if m is None else m + cm
            return attention_reference(q_, k_, v_, m, 0.25)

        ref_o, vjp = jax.vjp(f, q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                                   rtol=2e-5, atol=2e-5)
        want = vjp(dy)
        got = flash_attention_bwd(q, k, v, mask, o, lse, dy,
                                  sm_scale=0.25, causal=causal,
                                  interpret=True)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch (mask={use_mask}, "
                        f"causal={causal})")


def _causal_mask(s):
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                     -1e30)[None, None]


@pytest.mark.parametrize("tiles", [(128, 128), (128, 256), (256, 128),
                                   (128, 384), (384, 128), (64, 128)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
@pytest.mark.parametrize("use_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_one_pass_backward_matches_composed_vjp(causal, use_mask, tiles):
    """The one-pass backward at explicit tiles (three tiles a side at
    128, block_q != block_k in both orders, S = 384 and 768: neither a
    power of two) against jax.vjp of the composed reference."""
    from hetu_tpu.ops import pallas_attention as pk
    bq, bk = tiles
    s = 768 if 256 in tiles else 384
    q, k, v = _qkv(b=2, h=2, s=s, seed=17)
    mask = None
    if use_mask:
        m = np.zeros((2, 1, 1, s), np.float32)
        m[0, ..., s - 50:] = -1e9       # a padded tail ...
        m[1, ..., 130:141] = -1e9       # ... and keys masked mid-row
        mask = jnp.asarray(m)
    dy = jnp.asarray(np.random.RandomState(5).randn(*q.shape),
                     jnp.float32)
    o, lse = pk._flash_attention_jit(q, k, v, mask, 0.25, causal, True,
                                     128, 128, True)
    got = pk._flash_attention_bwd_jit(q, k, v, mask, o, lse, dy, 0.25,
                                      causal, True, bq, bk)
    m = mask
    if causal:
        m = _causal_mask(s) if m is None else m + _causal_mask(s)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, m, 0.25),
        q, k, v)
    for g, w, name in zip(got, vjp(dy), "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} at tiles {tiles}")


def _cut_by_the_diagonal(s, bq, bk):
    """From the causal matrix itself: the (q-tile, k-tile) pairs that
    hold a kept score, and of them those that also hold a masked one."""
    keep = np.tril(np.ones((s, s), bool)).reshape(s // bq, bq,
                                                  s // bk, bk)
    some, every = keep.any(axis=(1, 3)), keep.all(axis=(1, 3))
    order = lambda pairs: sorted(map(tuple, pairs.tolist()))  # noqa: E731
    return order(np.argwhere(some)), order(np.argwhere(some & ~every))


def _tile_sides(s):
    """Every tile side a rule could return: whole lane blocks up to
    1,024 that divide S."""
    return [c for c in (128, 256, 512, 1024) if c <= s and s % c == 0]


@pytest.mark.parametrize("s", [384, 1024, 2048])
def test_backward_walk_visits_what_the_diagonal_leaves(s):
    """At every tile pair the rule may return (and two it may not),
    with ``causal`` the walk runs exactly the pairs that hold a kept
    score and masks exactly those the diagonal cuts; without it, the
    whole square and no mask."""
    from hetu_tpu.ops import pallas_attention as pk
    sides = _tile_sides(s) + [64, 192 if s == 384 else 32]
    for bq in sides:
        for bk in sides:
            visited, masked = pk.tile_walk(s, bq, bk, True)
            want_visited, want_masked = _cut_by_the_diagonal(s, bq, bk)
            assert sorted(visited) == want_visited, (s, bq, bk)
            assert sorted(masked) == want_masked, (s, bq, bk)
            counts = pk.tile_walk_counts(s, bq, bk, True)
            assert counts["tiles_visited"] == len(want_visited)
            assert counts["tiles_masked"] == len(want_masked)
            full, none = pk.tile_walk(s, bq, bk, False)
            assert len(full) == (s // bq) * (s // bk) and none == []
    # what ISSUE 36 asks of the shares at the cell's S
    assert pk.tile_walk_counts(1024, 256, 256, True)["visited_share"] \
        == 0.625
    assert pk.tile_walk_counts(1024, 512, 512, True)["visited_share"] \
        == 0.75
    assert pk.tile_walk_counts(1024, 128, 128, True)["visited_share"] \
        == 0.5625


def _forward_walk(s, bq, bk, causal):
    """The pairs a forward call runs and masks, in tiles of the whole
    square, put together as ``_fwd_kernel`` walks them: a region row a
    program, the regions left of the diagonal whole, the one on it by
    ``tile_walk`` over the region's own rows (every region, none masked,
    without ``causal``)."""
    from hetu_tpu.ops import pallas_attention as pk
    span = pk._region_span(s, bq, bk)
    nq, nk = span // bq, span // bk
    visited, masked = [], []
    for row in range(s // span):
        for col in range(row if causal else s // span):
            visited += [(row * nq + i, col * nk + j)
                        for i in range(nq) for j in range(nk)]
        if causal:
            here, cut = pk.tile_walk(span, bq, bk, True)
            visited += [(row * nq + i, row * nk + j) for i, j in here]
            masked += [(row * nq + i, row * nk + j) for i, j in cut]
    return visited, masked


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("s", [256, 1024, 2048])
def test_forward_walk_visits_what_the_diagonal_leaves(s, causal):
    """At every tile pair the rule may return, the forward's regions
    add up to exactly the pairs that hold a kept score, each once, and
    mask exactly those the diagonal cuts: no pair above the diagonal is
    run at any tiles, whether a region is the whole head or one of
    several a loop walks; the counts a traced call records
    (``fwd_walk_counts``) are those."""
    from hetu_tpu.ops import pallas_attention as pk
    for bq in _tile_sides(s):
        for bk in _tile_sides(s):
            visited, masked = _forward_walk(s, bq, bk, causal)
            assert len(set(visited)) == len(visited), (s, bq, bk)
            if causal:
                want_visited, want_masked = _cut_by_the_diagonal(s, bq, bk)
            else:
                want_visited = [(i, j) for i in range(s // bq)
                                for j in range(s // bk)]
                want_masked = []
            assert sorted(visited) == want_visited, (s, bq, bk)
            assert sorted(masked) == want_masked, (s, bq, bk)
            assert all(i * bq + bq - 1 >= j * bk for i, j in visited) \
                or not causal
            counts = pk.fwd_walk_counts(12, s, bq, bk, causal)
            assert counts["tiles_visited"] == len(want_visited)
            assert counts["tiles_masked"] == len(want_masked)
            span = pk._region_span(s, bq, bk)
            assert counts["chains"] \
                == counts["heads_per_program"] * (span // bq)
    if causal and s == 1024:     # the GPT-2 train cell's square
        assert pk.fwd_walk_counts(12, s, 256, 256, True)[
            "tiles_visited"] == 10
        assert pk.fwd_walk_counts(12, s, 512, 512, True)[
            "visited_share"] == 0.75


@pytest.mark.parametrize("s,tiles", [(768, (128, 128)), (768, (256, 128)),
                                     (768, (128, 256)), (1024, (128, 128)),
                                     (1024, (256, 512))],
                         ids=lambda v: str(v).replace(" ", ""))
def test_forward_kernel_does_not_run_tiles_above_the_diagonal(s, tiles):
    """The kernel follows the walk: NaN values in ONE k-tile reach the
    context of a q-tile only through a pair that was run (0 x NaN is
    NaN even where the mask zeroes P), so a q-tile's rows are NaN
    exactly when its pair with the poisoned k-tile is visited — one
    region a head (S = 768) and regions walked by a loop (S = 1024 at
    128 x 128: two region rows)."""
    from hetu_tpu.ops import pallas_attention as pk
    bq, bk = tiles
    q, k, v = _qkv(b=1, h=2, s=s, seed=29)
    visited, _ = _forward_walk(s, bq, bk, True)
    assert len(visited) < (s // bq) * (s // bk)
    for j in range(s // bk):
        poison = (jnp.arange(s) // bk == j)[None, None, :, None]
        out = pk._flash_attention_jit(q, k, jnp.where(poison, jnp.nan, v),
                                      None, 0.25, True, True, bq, bk,
                                      False)
        for i in range(s // bq):
            rows = np.isnan(np.asarray(out[:, :, i * bq:(i + 1) * bq]))
            assert rows.all() == rows.any() == ((i, j) in visited), (i, j)


FORWARD_CASES = [  # heads, S, D, tiles, causal, mask, dtype
    (2, 1024, 64, (256, 256), True, False, jnp.float32),
    (2, 1024, 64, (512, 256), True, True, jnp.float32),
    (2, 1024, 64, (128, 128), True, False, jnp.float32),   # regions 512
    (2, 1024, 64, (128, 128), False, True, jnp.float32),
    (1, 512, 128, (256, 512), True, True, jnp.float32),
    (1, 512, 128, (128, 128), False, False, jnp.bfloat16),
    (2, 512, 192, (256, 256), True, False, jnp.float32),
    (2, 512, 192, (128, 256), True, True, jnp.bfloat16),
    (2, 4096, 64, (512, 512), True, False, jnp.float32),   # past _REGION_ROWS
    (2, 4096, 64, (512, 1024), False, True, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "heads,s,d,tiles,causal,use_mask,dtype", FORWARD_CASES,
    ids=[f"h{h}-s{s}-d{d}-{bq}x{bk}-{'causal' if c else 'full'}-"
         f"{'mask' if m else 'nomask'}-{jnp.dtype(t).name}"
         for h, s, d, (bq, bk), c, m, t in FORWARD_CASES])
def test_region_forward_matches_the_reference(heads, s, d, tiles, causal,
                                              use_mask, dtype):
    """Context and logsumexp of the region forward against the float32
    reference: a region the whole head and regions walked by a loop
    (S = 1,024 at 128 x 128; S = 4,096, past ``_REGION_ROWS``), the scale
    on q (1/8: exact) and on the scores (D = 192 in bfloat16), and the
    plain forward bit-equal to the one that also writes the residual.
    Where the heads fill lane blocks (D = 64, 128) the token-major form
    over the same numbers equals the head-major one BIT FOR BIT."""
    from hetu_tpu.ops import pallas_attention as pk
    b = 2
    scale = d ** -0.5
    rng = np.random.RandomState(s + d)
    q, k, v = (jnp.asarray(rng.randn(b, heads, s, d) * 0.5, dtype)
               for _ in range(3))
    mask = None
    if use_mask:
        m = np.zeros((b, 1, 1, s), np.float32)
        m[0, ..., s - 50:] = -1e9       # a padded tail ...
        m[1, ..., 130:141] = -1e9       # ... and keys masked mid-row
        mask = jnp.asarray(m)
    full = mask
    if causal:
        full = _causal_mask(s) if mask is None else mask + _causal_mask(s)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    want = attention_reference(q32, k32, v32, full, scale)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
    want_lse = jax.nn.logsumexp(scores if full is None else scores + full,
                                axis=-1)
    o, lse = pk._flash_attention_jit(q, k, v, mask, scale, causal, True,
                                     *tiles, True)
    plain = pk._flash_attention_jit(q, k, v, mask, scale, causal, True,
                                    *tiles, False)
    assert np.array_equal(np.asarray(plain), np.asarray(o))
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               **tol)
    layout = pk.TokenMajor(heads, d)
    if not layout.fits(s):
        assert d == 192
        return
    rows = [x.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            for x in (q, k, v)]
    o_t, lse_t = pk._flash_attention_jit(*rows, mask, scale, causal, True,
                                         *tiles, True, layout)
    assert np.array_equal(np.asarray(o_t),
                          np.asarray(o.transpose(0, 2, 1, 3).reshape(
                              b, s, heads * d)))
    assert np.array_equal(np.asarray(lse_t[:, :, 0]), np.asarray(lse))


@pytest.mark.parametrize("s,tiles,group", [
    (384, (128, 128), 1), (512, (256, 128), 2), (640, (128, 640), 3),
    (256, (128, 128), 4), (128, (128, 128), 12)],
    ids=lambda v: str(v).replace(" ", ""))
def test_grouped_heads_take_their_own_batch_rows_mask(s, tiles, group):
    """Where one region is a whole head with room to spare, a program
    takes a block of neighbouring heads (the largest divisor of H = 12
    within ``_REGION_TILES`` pairs and ``_REGION_ROWS`` rows): each head
    of a block still meets ITS batch row's padding mask (three rows
    masked differently, so 36 / G programs cross from one to the
    next), alone and with ``causal``."""
    from hetu_tpu.ops import pallas_attention as pk
    b, h, d = 3, 12, 16
    assert pk.heads_per_program(h, s, *tiles) == group
    assert pk.fwd_walk_counts(h, s, *tiles, False)["chains"] \
        == group * (s // tiles[0])
    q, k, v = _qkv(b=b, h=h, s=s, d=d, seed=31)
    m = np.zeros((b, 1, 1, s), np.float32)
    m[0, ..., s - 50:] = -1e9
    m[1, ..., 20:97] = -1e9
    m[2, ..., :11] = -1e9
    mask = jnp.asarray(m)
    for causal in (False, True):
        full = mask + _causal_mask(s) if causal else mask
        o, lse = pk._flash_attention_jit(q, k, v, mask, 0.25, causal, True,
                                         *tiles, True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(attention_reference(q, k, v, full,
                                                          0.25)),
            rtol=2e-5, atol=2e-5)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25 + full
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(scores, axis=-1)),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s,tiles,span", [
    (1024, (256, 256), 1024), (1024, (512, 512), 1024),
    (1024, (128, 128), 512), (1024, (128, 256), 512),
    (1024, (1024, 128), 1024), (2048, (256, 256), 1024),
    (2048, (512, 512), 2048), (768, (128, 256), 256),
    (768, (128, 128), 384), (384, (384, 128), 384), (128, (64, 32), 128),
    (8192, (128, 128), 512), (1536, (512, 512), 1536)],
    ids=lambda v: str(v).replace(" ", ""))
def test_backward_regions_are_whole_tiles_and_bounded(s, tiles, span):
    """The square regions whose tile pairs the kernel unrolls: whole
    tiles both ways, a divisor of S, never more than 16 pairs (so the
    code does not grow with S), and as large as that allows."""
    from hetu_tpu.ops import pallas_attention as pk
    bq, bk = tiles
    got = pk._region_span(s, bq, bk)
    assert got == span
    assert s % got == 0 and got % bq == 0 and got % bk == 0
    pairs = (got // bq) * (got // bk)
    assert pairs <= pk._REGION_TILES or got == max(bq, bk)


@pytest.mark.parametrize("tiles", [(128, 128), (128, 256), (256, 128)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_backward_kernel_does_not_run_tiles_above_the_diagonal(tiles):
    """The kernel follows the walk: NaN rows in q (and dO) of the FIRST
    q-tile reach dK / dV of a k-tile only through a tile that was run
    (0 x NaN is NaN even where the mask zeroes P), so the k-tiles wholly
    after that q-tile stay finite exactly when their pairs are skipped."""
    from hetu_tpu.ops import pallas_attention as pk
    bq, bk = tiles
    s = 768
    q, k, v = _qkv(b=1, h=2, s=s, seed=23)
    dy = jnp.asarray(np.random.RandomState(6).randn(*q.shape),
                     jnp.float32)
    o, lse = pk._flash_attention_jit(q, k, v, None, 0.25, True, True,
                                     128, 128, True)
    poison = jnp.arange(s)[None, None, :, None] < bq
    q, dy = jnp.where(poison, jnp.nan, q), jnp.where(poison, jnp.nan, dy)
    _, dk, dv = pk._flash_attention_bwd_jit(q, k, v, None, o, lse, dy,
                                            0.25, True, True, bq, bk)
    visited, _ = pk.tile_walk(s, bq, bk, True)
    for kj in range(s // bk):
        ran = (0, kj) in visited
        for g in (dk, dv):
            tile = np.asarray(g[:, :, kj * bk:(kj + 1) * bk])
            assert np.isnan(tile).any() == ran, (kj, ran)
    assert not all((0, kj) in visited for kj in range(s // bk))


def test_flash_attention_op_fused_backward_path(monkeypatch):
    """The graph op routes grads through the fused kernels when the
    forward stashed its logsumexp residual."""
    from hetu_tpu.ops import attention as attn_mod
    from hetu_tpu.ops import pallas_attention as pk
    from hetu_tpu.ops.attention import (FlashAttentionOp,
                                        _FlashAttentionGradOp)
    from hetu_tpu.graph.node import ExecContext
    import hetu_tpu as ht

    monkeypatch.setattr(attn_mod, "_use_pallas", lambda: True)
    monkeypatch.setattr(attn_mod, "FUSED_BWD_MIN_SEQ", 0)
    monkeypatch.setattr(pk, "INTERPRET", True)

    s = 32
    q, k, v = _qkv(s=s, seed=13)
    mask = _mask(s=s, valid=s - 6)
    rng = np.random.RandomState(7)
    dy = jnp.asarray(rng.randn(*q.shape), jnp.float32)

    ectx = ExecContext(training=True)
    qn, kn, vn, mn = [ht.Variable(n, trainable=False) for n in "qkvm"]
    fwd = FlashAttentionOp(qn, kn, vn, mn, sm_scale=0.25)
    out = fwd.compute([q, k, v, mask], ectx)
    assert ("flash_res", fwd.id) in ectx.cache
    dyn = ht.Variable("dy", trainable=False)
    grads = [_FlashAttentionGradOp(fwd, dyn, i).compute(
        [q, k, v, mask, dy], ectx) for i in range(3)]

    def f(q_, k_, v_):
        return attention_reference(q_, k_, v_, mask, 0.25)
    _, vjp = jax.vjp(f, q, k, v)
    want = vjp(dy)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)
