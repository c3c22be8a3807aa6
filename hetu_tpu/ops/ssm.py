"""The selective state-space recurrence (Mamba-1's) for serving.

A channel ``d`` of a sequence carries ``N`` state values, and a token
moves them by an input-dependent step ``delta_t[d] > 0``::

    S_t[n, d] = exp(delta_t[d] A[n, d]) S_{t-1}[n, d]
                + delta_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n S_t[n, d] C_t[n] + D[d] x_t[d]

The state is float32 and lies CHANNELS-MINOR, ``[N, d_inner]`` (sixteen
sublanes by whole lanes on a TPU), in a program and in the cache's slots
alike. Two calls:

* :func:`ssm_scan` — many tokens a row (a prompt, or a chunk of one):
  takes the state each row starts from and a count of REAL tokens a row
  (a prompt is right-padded to its bucket) and returns ``y`` for every
  position and the state AT THE LAST REAL TOKEN: past it ``delta`` is
  set to 0, which leaves the state as it is (``exp(0) = 1``, nothing
  added). Nothing of ``[T, d_inner, N]`` is ever materialised and no
  walk of ``T`` steps runs through HBM: on a TPU a Pallas kernel
  (``hetu_ssm_scan``) walks the tokens of a chunk with the state of 512
  channels in registers and carries it chunk to chunk in VMEM.
* :func:`ssm_step` — one token a row (a decode step) against the
  cache's slots ``[slots, layers, N, d_inner]``: on a TPU a Pallas
  kernel (``hetu_ssm_step``) reads each row's slot of the layer once
  and writes it once, in place (the pool is aliased to the result, and
  the layer is an index the kernel takes: the layers of a model can be
  a loop over ONE pool).

Elsewhere, and for shapes the kernels do not take (:func:`supported`),
the composed ``jax.numpy`` form of the same arithmetic runs. A traced
call says which in an ``ssm_plan`` instant. ``B_t`` and ``C_t`` reach a
kernel broadcast along 128 lanes (``[..., N, 128]``): a token's sixteen
values are then a tile that multiplies the state's as it lies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _use_pallas

__all__ = ["ssm_scan", "ssm_step", "supported", "SCAN_NAME", "STEP_NAME"]

# what the kernels' events are called in a profile
# (``ops/pallas_norm.py`` says why the jitted function carries the name)
SCAN_NAME = "hetu_ssm_scan"
STEP_NAME = "hetu_ssm_step"

LANES = 128
SUBLANES = 8
# channels whose state one walk over a chunk keeps in registers:
# [16, 512] float32 is 8 of them, the ``A`` beside it 8 more
TILE = 512
# tokens of a chunk: what the state is carried across in VMEM
CHUNK = 128

# tests flip this to exercise the kernels without a TPU backend
INTERPRET = False


def supported(t, d_inner, n, dtype):
    """``None`` where the scan kernel takes ``t`` tokens a row of
    ``d_inner`` channels with ``n`` state values in ``dtype`` (``t``
    ``None``: the step kernel, one token a row), else why not."""
    if d_inner % LANES or n % SUBLANES:
        return "channels are not whole lanes by whole sublanes"
    if t is None:       # one token a row: the step kernel
        return None
    rows = SUBLANES * max(1, 4 // jnp.dtype(dtype).itemsize)
    if t % min(t, CHUNK) or min(t, CHUNK) % rows:
        return "the tokens are not whole sublane tiles of a chunk"
    return None


def _interpret():
    """Off a TPU a kernel can only be interpreted (a rehearsal steers
    ``_use_pallas`` to the kernels on any backend)."""
    return INTERPRET or jax.default_backend() != "tpu"


def _plan(op, why):
    """The form a traced call runs in, and the ``ssm_plan`` instant
    that says so (once a traced call, never in a steady-state step)."""
    if not (_use_pallas() or INTERPRET):
        why = "platform"
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "ssm_plan", op=op, form="composed" if why else "kernel",
        **({"reason": why} if why else {}))
    return not why


# ---------------------------------------------------------------------------
# the composed form
# ---------------------------------------------------------------------------

def _update(s, x, delta, a_t, b, c):
    """One token: ``(S_t, y_t without the D x_t term)`` from ``s [..., N,
    d]``, ``x``, ``delta [..., d]`` and ``b``, ``c [..., N]``."""
    s = jnp.exp(delta[..., None, :] * a_t) * s \
        + (delta * x)[..., None, :] * b[..., :, None]
    return s, jnp.sum(s * c[..., :, None], axis=-2)


def _scan_composed(x, delta, a_t, b, c, s0):
    def body(s, step):
        return _update(s, *step[:2], a_t, *step[2:])

    s, y = jax.lax.scan(body, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1), s


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _scan_body(x_ref, dl_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
               y_ref, s_ref, st_ref, dx_ref, ys_ref, *, chunk, tile):
    """Grid ``(row, chunk of tokens, tile of channels)``: the tile's
    state comes out of ``st_ref`` (VMEM, all tiles of the row), walks
    the chunk's tokens in registers and goes back."""
    t_idx, j = pl.program_id(1), pl.program_id(2)
    tiles = st_ref.shape[0]
    groups = tile // LANES

    @pl.when(jnp.logical_and(t_idx == 0, j == 0))
    def _():
        for i in range(tiles):
            st_ref[i] = s0_ref[0, :, i * tile:(i + 1) * tile]

    x = x_ref[0].astype(jnp.float32)
    dx_ref[...] = dl_ref[0] * x
    a = a_ref[j]
    a = [a[:, g * LANES:(g + 1) * LANES] for g in range(groups)]
    s = st_ref[j]

    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def tokens(i, state):
        # a sublane tile of tokens: whole tiles come and go, a token's
        # row is taken out of them and put back by its (static) index
        state = list(state)
        at = pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)
        for g in range(groups):
            lanes = slice(g * LANES, (g + 1) * LANES)
            dl, dx = dl_ref[0, at, lanes], dx_ref[at, lanes]
            y = jnp.zeros((SUBLANES, LANES), jnp.float32)
            for k in range(SUBLANES):
                t = i * SUBLANES + k
                state[g] = jnp.exp(dl[k:k + 1] * a[g]) * state[g] \
                    + dx[k:k + 1] * b_ref[0, t]
                y = jnp.where(row == k, jnp.sum(
                    state[g] * c_ref[0, t], axis=0, keepdims=True), y)
            ys_ref[at, lanes] = y
        return tuple(state)

    state = jax.lax.fori_loop(
        0, chunk // SUBLANES, tokens,
        tuple(s[:, g * LANES:(g + 1) * LANES] for g in range(groups)))
    for g in range(groups):
        st_ref[j, :, g * LANES:(g + 1) * LANES] = state[g]
    y_ref[0] = (ys_ref[...] + d_ref[j] * x).astype(y_ref.dtype)

    @pl.when(jnp.logical_and(t_idx == pl.num_programs(1) - 1,
                             j == tiles - 1))
    def _():
        for i in range(tiles):
            s_ref[0, :, i * tile:(i + 1) * tile] = st_ref[i]


def _scan_kernel(x, delta, b, c, a_t, d, s0, *, interpret):
    rows, t, d_inner = x.shape
    n = a_t.shape[0]
    chunk = min(t, CHUNK)
    tile = TILE if d_inner % TILE == 0 else LANES
    tiles = d_inner // tile
    # a tile's A and D by a leading index: [tiles, N, tile], [tiles, 1, tile]
    a_tiles = a_t.reshape(n, tiles, tile).transpose(1, 0, 2)
    d_tiles = d.reshape(tiles, 1, tile)
    wide = (rows, t, n, LANES)
    b = jnp.broadcast_to(b[..., None], wide)
    c = jnp.broadcast_to(c[..., None], wide)
    tokens = pl.BlockSpec((1, chunk, tile), lambda r, i, j: (r, i, j))
    coef = pl.BlockSpec((1, chunk, n, LANES), lambda r, i, j: (r, i, 0, 0))
    whole = pl.BlockSpec((1, n, d_inner), lambda r, i, j: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_scan_body, chunk=chunk, tile=tile),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)),
        grid=(rows, t // chunk, tiles),
        in_specs=[tokens, tokens, coef, coef,
                  pl.BlockSpec(a_tiles.shape, lambda r, i, j: (0, 0, 0)),
                  pl.BlockSpec(d_tiles.shape, lambda r, i, j: (0, 0, 0)),
                  whole],
        out_specs=(tokens, whole),
        scratch_shapes=[pltpu.VMEM((tiles, n, tile), jnp.float32),
                        pltpu.VMEM((chunk, tile), jnp.float32),
                        pltpu.VMEM((chunk, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(x, delta, b, c, a_tiles, d_tiles, s0)


def _step_body(slots_ref, layer_ref, x_ref, dl_ref, b_ref, c_ref, a_ref,
               d_ref, pool_ref, y_ref, out_ref):
    """Grid ``(row,)``: the row's slot of the layer comes on chip, takes
    one token and goes back to where it lay."""
    del slots_ref, layer_ref    # the block specs' index maps read them
    bt, ct = b_ref[0], c_ref[0]
    for g in range(x_ref.shape[-1] // LANES):
        lanes = slice(g * LANES, (g + 1) * LANES)
        x, dl = x_ref[0, :, lanes], dl_ref[0, :, lanes]
        s = jnp.exp(dl * a_ref[:, lanes]) * pool_ref[0, 0, :, lanes] \
            + (dl * x) * bt
        out_ref[0, 0, :, lanes] = s
        y_ref[0, :, lanes] = jnp.sum(s * ct, axis=0, keepdims=True) \
            + d_ref[:, lanes] * x


def _step_kernel(pool, slots, layer, x, delta, b, c, a_t, d, *, interpret):
    rows, d_inner = x.shape
    n = a_t.shape[0]
    wide = (rows, n, LANES)
    token = pl.BlockSpec((1, 1, d_inner), lambda r, slots, layer: (r, 0, 0))
    coef = pl.BlockSpec((1, n, LANES), lambda r, slots, layer: (r, 0, 0))
    slot = pl.BlockSpec((1, 1, n, d_inner),
                        lambda r, slots, layer: (slots[r], layer[0], 0, 0))
    whole = lambda r, slots, layer: (0, 0)      # noqa: E731
    y, pool = pl.pallas_call(
        _step_body,
        out_shape=(jax.ShapeDtypeStruct((rows, 1, d_inner), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows,),
            in_specs=[token, token, coef, coef,
                      pl.BlockSpec((n, d_inner), whole),
                      pl.BlockSpec((1, d_inner), whole), slot],
            out_specs=(token, slot)),
        # the pool (the 9th operand, slots and layer counted) IS the result
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots, layer.reshape(1).astype(jnp.int32),
      x[:, None].astype(jnp.float32), delta[:, None],
      jnp.broadcast_to(b[..., None], wide),
      jnp.broadcast_to(c[..., None], wide), a_t, d[None], pool)
    return y[:, 0], pool


@functools.lru_cache(maxsize=None)
def _jitted_scan(interpret):
    def hetu_ssm_scan(x, delta, b, c, a_t, d, s0):
        return _scan_kernel(x, delta, b, c, a_t, d, s0,
                            interpret=interpret)

    hetu_ssm_scan.__name__ = hetu_ssm_scan.__qualname__ = SCAN_NAME
    return jax.jit(hetu_ssm_scan)


@functools.lru_cache(maxsize=None)
def _jitted_step(interpret):
    def hetu_ssm_step(pool, slots, layer, x, delta, b, c, a_t, d):
        return _step_kernel(pool, slots, layer, x, delta, b, c, a_t, d,
                            interpret=interpret)

    hetu_ssm_step.__name__ = hetu_ssm_step.__qualname__ = STEP_NAME
    return jax.jit(hetu_ssm_step)


# ---------------------------------------------------------------------------
# what a mixer calls
# ---------------------------------------------------------------------------

def ssm_scan(x, delta, a_t, b, c, d, s0, lengths):
    """``(y [B, T, d], S [B, N, d])``: the recurrence over ``x [B, T,
    d]`` (the model's dtype) with ``delta [B, T, d]``, ``b`` / ``c [B,
    T, N]``, ``a_t [N, d]``, ``d [d]`` and the state ``s0 [B, N, d]``
    each row starts from, all float32. ``lengths [B]`` is each row's
    count of real tokens: ``S`` is the state at the last of them
    (``s0`` for a row with none), and ``y`` past it means nothing."""
    t = x.shape[1]
    delta = jnp.where(jnp.arange(t)[None, :, None]
                      < lengths[:, None, None], delta, 0.0)
    if _plan("scan", supported(t, x.shape[2], a_t.shape[0], x.dtype)):
        return _jitted_scan(_interpret())(x, delta, b, c, a_t, d, s0)
    y, s = _scan_composed(x.astype(jnp.float32), delta, a_t, b, c, s0)
    return (y + d * x.astype(jnp.float32)).astype(x.dtype), s


def ssm_step(pool, slots, layer, x, delta, a_t, b, c, d):
    """One token a row against the slots: ``(y [B, d], pool)`` from
    ``pool [slots, layers, N, d]`` float32 (a slot holds a sequence's
    state of every state-space layer), ``slots [B]`` int32 (padded rows
    name the scratch slot, which takes their writes), the ``layer``
    this is (an int32 scalar, traced or not), ``x`` (the model's dtype)
    and ``delta [B, d]``, ``b`` / ``c [B, N]``. Donate the pool: the
    kernel updates it in place."""
    layer = jnp.asarray(layer, jnp.int32)
    if _plan("step", supported(None, x.shape[1], a_t.shape[0], x.dtype)):
        y, pool = _jitted_step(_interpret())(pool, slots, layer, x, delta, b,
                                          c, a_t, d)
        return y.astype(x.dtype), pool
    x32 = x.astype(jnp.float32)
    s, y = _update(pool[slots, layer], x32, delta, a_t, b, c)
    return (y + d * x32).astype(x.dtype), pool.at[slots, layer].set(s)
