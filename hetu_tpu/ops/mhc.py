"""Manifold-constrained hyper-connections (mHC): ``n`` residual streams
around a sublayer, mixed by learned maps that depend on the token
(pure JAX, no graph nodes: the serving block of
``models/latent_moe.py`` rides these).

No reference equivalent. The state between two sublayers is ``X [T, n,
C]`` a token; a sublayer ``F`` (attention or a feed-forward) reads ONE
learned mix of the streams and writes back through two more maps::

    x~    = vec(X) [nC];  r = rsqrt(mean(x~^2) + eps)            float32
    z     = r * (x~ @ phi)      phi [nC, n + n + n*n]   float32, as exact
    Hpre  = sigmoid(a_pre z[:n] + b_pre)                            [n]
    Hpost = 2 sigmoid(a_post z[n:2n] + b_post)                      [n]
    M     = exp(clip(a_res z[2n:] + b_res, clamp))               [n, n]
    Hres  = iters x { rows of M /= max(their sum, eps) ;
                      columns of M /= max(their sum, eps) }
    u     = sum_j Hpre[j] X[j]          -> y = F(norm(u))
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y

* :func:`maps` — the three maps of given rows, composed ``jax.numpy``.
* :func:`mhc_pre` — ``X -> (u, carry)``: ONE read of the stream (the
  norm is applied to the product, so no second pass is needed for it;
  the Sinkhorn iterations run on registers). ``carry`` holds ``Hpost``
  and ``Hres`` for :func:`mhc_post`.
* :func:`mhc_post` — ``(X, y, carry) -> X'``.

On a TPU each half is a Pallas kernel behind a jitted function of a
stable name (``hetu_mhc_pre`` / ``hetu_mhc_post``: the names their
events carry in a profile; ``ops/pallas_norm.py`` says why the jitted
function carries it); elsewhere, and for streams the kernels do not
take (:func:`supported`), the composed form. The kernels take
``phi`` PREPARED (:func:`prepare`): a float32 matrix split into three
bfloat16 terms side by side, so that a bfloat16 stream times it is ONE
pass of the matrix unit whose float32 accumulator holds the float32
product to the last bit that matters (the stream's values ARE
bfloat16; every product of two 8-bit mantissas is exact in float32).
A traced call says which form it runs in by an ``mhc_plan`` instant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["maps", "mhc_pre", "mhc_post", "prepare", "supported",
           "wants_prepared", "prepared_bytes", "map_width", "PRE_NAME",
           "POST_NAME"]

PRE_NAME = "hetu_mhc_pre"
POST_NAME = "hetu_mhc_post"
LANES = 128
SUBLANES = 8
# tokens one program of a kernel takes: one 128 x 128 transpose turns
# their maps from a row a token (what the matrix unit returns) into a
# lane a token (what the Sinkhorn iterations want) and back
ROWS = 128
# rows x lanes of the stream one pass of a kernel's body works on (a
# map's column is broadcast along the lanes once a row group; seven
# shapes from 8 x 128 to 64 x 128 ran within 2% of each other on the
# chip, PERF.md PR 39: the kernels are bound by memory)
ROW_GROUP = 32
LANE_CHUNK = 128
# the contraction a matmul of the body takes at a time
K_CHUNK = 2048
VMEM_LIMIT = 64 * 1024 * 1024

# tests flip this to exercise the kernels without a TPU backend
INTERPRET = False


def _use_pallas():
    from .attention import _use_pallas as on_tpu
    return on_tpu()


def map_width(n):
    """Columns of ``phi``: ``Hpre``, ``Hpost`` and ``Hres`` of ``n``
    streams."""
    return n + n + n * n


def _padded_width(n):
    return -(-map_width(n) // SUBLANES) * SUBLANES


def supported(n, c, dtype):
    """``None`` where the kernels take ``n`` streams of ``c`` lanes in
    ``dtype``, else the first condition that failed: a bfloat16 stream
    (its values times the split ``phi`` are exact), whole lanes, and
    three terms of the maps' columns inside one 128-lane tile."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "dtype"
    if c % LANES:
        return "lanes"
    if 3 * _padded_width(n) > LANES:
        return "streams"
    return None


def _form(n, c, dtype):
    """``("kernel", None)`` or ``("composed", why)``."""
    if not (_use_pallas() or INTERPRET):
        return "composed", "platform"
    why = supported(n, c, dtype)
    return ("composed", why) if why else ("kernel", None)


def _plan(n, c, dtype, iters):
    """The form a traced call runs in, and the ``mhc_plan`` instant
    that says so (once a traced call, never in a steady-state step)."""
    form, why = _form(n, c, dtype)
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "mhc_plan", streams=int(n), iters=int(iters), form=form,
        **({"reason": why} if why else {}))
    return form


# ---------------------------------------------------------------------------
# the composed form
# ---------------------------------------------------------------------------

def _sinkhorn(m, iters, eps):
    """``iters`` x (rows, then columns) of ``m [..., n, n]``."""
    def body(_, m):
        m = m / jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), eps)
        return m / jnp.maximum(jnp.sum(m, axis=-2, keepdims=True), eps)
    return jax.lax.fori_loop(0, iters, body, m)


def maps(x, phi, scale, bias, iters, eps, clamp):
    """``(Hpre [T, n], Hpost [T, n], Hres [T, n, n])`` float32 of the
    streams ``x [T, n, C]``. ``phi [nC, 2n + n*n]``, ``scale [3]``
    (``a_pre``, ``a_post``, ``a_res``) and ``bias [2n + n*n]`` are
    float32."""
    t, n, c = x.shape
    flat = x.reshape(t, n * c).astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    z = r * jnp.dot(flat, phi.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    bias = bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(scale[0] * z[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(scale[1] * z[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(scale[2] * z[:, 2 * n:] + bias[2 * n:],
                         clamp[0], clamp[1])).reshape(t, n, n)
    return pre, post, _sinkhorn(m, iters, eps)


def _mix_in(x, pre):
    """``u = sum_j Hpre[j] X[j]`` one stream at a time, in float32."""
    u = pre[:, 0, None] * x[:, 0].astype(jnp.float32)
    for j in range(1, x.shape[1]):
        u = u + pre[:, j, None] * x[:, j].astype(jnp.float32)
    return u.astype(x.dtype)


def _mix_out(x, y, post, res):
    n = x.shape[1]
    y32 = y.astype(jnp.float32)
    x32 = [x[:, j].astype(jnp.float32) for j in range(n)]
    out = []
    for i in range(n):
        acc = post[:, i, None] * y32
        for j in range(n):
            acc = acc + res[:, i, j, None] * x32[j]
        out.append(acc)
    return jnp.stack(out, axis=1).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def prepare(phi, scale, bias):
    """What the kernels take of one sublayer's maps: ``(phi split
    [nC, 128] bfloat16, table [2 * 128, 128] float32)``. The split's
    columns are three bands of ``W`` (``map_width`` padded to whole
    sublanes): ``phi`` rounded to bfloat16, what that left rounded
    again, and once more — 24 bits of mantissa, all a float32 has. The
    table's row ``k`` is column ``k``'s scale along the lanes, row
    ``128 + k`` its bias."""
    nc, k = phi.shape
    n = next(n for n in range(1, 12) if map_width(n) == k)
    w = _padded_width(n)
    phi = phi.astype(jnp.float32)
    terms, left = [], phi
    for _ in range(3):
        term = left.astype(jnp.bfloat16)
        left = left - term.astype(jnp.float32)
        terms.append(jnp.pad(term, ((0, 0), (0, w - k))))
    split = jnp.pad(jnp.concatenate(terms, axis=1),
                    ((0, 0), (0, LANES - 3 * w)))
    per_column = jnp.concatenate([
        jnp.full(n, scale[0]), jnp.full(n, scale[1]),
        jnp.full(n * n, scale[2])]).astype(jnp.float32)
    table = jnp.zeros((2 * LANES, LANES), jnp.float32)
    table = table.at[:k].set(per_column[:, None])
    table = table.at[LANES:LANES + k].set(
        bias.astype(jnp.float32)[:, None])
    return split, table


def prepared_bytes(n, c):
    """Bytes :func:`prepare` holds of one sublayer's maps."""
    return n * c * LANES * 2 + 2 * LANES * LANES * 4


def _groups(rows):
    """``(rows of a group, groups)`` of a kernel's inner row loop."""
    group = rows if rows % ROW_GROUP else ROW_GROUP
    return group, rows // group


def _over_row_groups(rows, body):
    """``body(row slice)`` over the block's row groups."""
    group, count = _groups(rows)
    if count == 1:
        body(pl.ds(0, group))
        return

    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * group, group), group))
        return carry

    jax.lax.fori_loop(0, count, step, 0)


def _along_lanes(tile, k):
    """Column ``k`` of ``tile [group, 128]`` along ``LANE_CHUNK`` lanes:
    made once a row group, so that every product with it is a plain
    elementwise one."""
    return jnp.broadcast_to(tile[:, k:k + 1], (tile.shape[0], LANE_CHUNK))


def _pre_body(x_ref, split_ref, table_ref, u_ref, coef_ref, ss_ref,
              zt_ref, *, n, c, rows, iters, eps, clamp):
    nc = n * c
    w = _padded_width(n)

    # the matrix unit: every column of the maps, three terms each
    z = jnp.zeros((rows, LANES), jnp.float32)
    for at in range(0, nc, K_CHUNK):
        size = min(K_CHUNK, nc - at)
        z = z + jnp.dot(x_ref[:, pl.ds(at, size)],
                        split_ref[pl.ds(at, size), :],
                        preferred_element_type=jnp.float32)

    # the norm's statistic, a row group at a time
    def sum_squares(rs):
        acc = jnp.zeros((rs.size, LANES), jnp.float32)
        for lane in range(0, nc, LANES):
            v = x_ref[rs, pl.ds(lane, LANES)].astype(jnp.float32)
            acc = acc + v * v
        ss_ref[rs, :] = jnp.broadcast_to(
            jnp.sum(acc, axis=1, keepdims=True), (rs.size, LANES))

    _over_row_groups(rows, sum_squares)
    r = jax.lax.rsqrt(ss_ref[pl.ds(0, rows), :] * (1.0 / nc) + eps)

    # a lane a token: what is past the block's rows stays zero
    zt_ref[...] = jnp.zeros(zt_ref.shape, jnp.float32)
    zt_ref[pl.ds(0, rows), :] = z * r
    zt = zt_ref[...].T                                      # [128, ROWS]
    z = zt[0:w] + zt[w:2 * w] + zt[2 * w:3 * w]             # [w, ROWS]
    lin = z * table_ref[pl.ds(0, w), :] \
        + table_ref[pl.ds(LANES, w), :]

    def row(i):
        return lin[i:i + 1, :]

    pre = [jax.nn.sigmoid(row(j)) for j in range(n)]
    post = [2.0 * jax.nn.sigmoid(row(n + i)) for i in range(n)]
    m = [[jnp.exp(jnp.clip(row(2 * n + n * i + j), clamp[0], clamp[1]))
          for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            s = m[i][0]
            for j in range(1, n):
                s = s + m[i][j]
            inv = 1.0 / jnp.maximum(s, eps)
            m[i] = [e * inv for e in m[i]]
        for j in range(n):
            s = m[0][j]
            for i in range(1, n):
                s = s + m[i][j]
            inv = 1.0 / jnp.maximum(s, eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv

    # back to a row a token: columns as ``maps`` orders them
    zt_ref[...] = jnp.zeros(zt_ref.shape, jnp.float32)
    for i, e in enumerate(pre + post + [e for r_ in m for e in r_]):
        zt_ref[pl.ds(i, 1), :] = e
    coef = zt_ref[...].T                                    # [ROWS, 128]
    coef_ref[...] = coef[0:rows]
    ss_ref[...] = coef

    def mix(rs):
        tile = ss_ref[rs, :]
        h = [_along_lanes(tile, j) for j in range(n)]
        for lane in range(0, c, LANE_CHUNK):
            acc = h[0] * x_ref[rs, pl.ds(lane, LANE_CHUNK)].astype(
                jnp.float32)
            for j in range(1, n):
                acc = acc + h[j] * x_ref[
                    rs, pl.ds(j * c + lane, LANE_CHUNK)].astype(jnp.float32)
            u_ref[rs, pl.ds(lane, LANE_CHUNK)] = acc.astype(u_ref.dtype)

    _over_row_groups(rows, mix)


def _post_body(x_ref, y_ref, coef_ref, o_ref, *, n, c, rows):
    def mix(rs):
        tile = coef_ref[rs, :]
        post = [_along_lanes(tile, n + i) for i in range(n)]
        res = [[_along_lanes(tile, 2 * n + n * i + j) for j in range(n)]
               for i in range(n)]
        for lane in range(0, c, LANE_CHUNK):
            y = y_ref[rs, pl.ds(lane, LANE_CHUNK)].astype(jnp.float32)
            x = [x_ref[rs, pl.ds(j * c + lane, LANE_CHUNK)].astype(
                jnp.float32) for j in range(n)]
            for i in range(n):
                acc = post[i] * y
                for j in range(n):
                    acc = acc + res[i][j] * x[j]
                o_ref[rs, pl.ds(i * c + lane, LANE_CHUNK)] = \
                    acc.astype(o_ref.dtype)

    _over_row_groups(rows, mix)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=VMEM_LIMIT)


def _pre_kernel(x, split, table, *, n, iters, eps, clamp, interpret):
    t, nc = x.shape
    c = nc // n
    rows = min(ROWS, t)
    body = functools.partial(_pre_body, n=n, c=c, rows=rows, iters=iters,
                             eps=eps, clamp=clamp)
    return pl.pallas_call(
        body,
        out_shape=(jax.ShapeDtypeStruct((t, c), x.dtype),
                   jax.ShapeDtypeStruct((t, LANES), jnp.float32)),
        grid=(pl.cdiv(t, rows),),
        in_specs=[pl.BlockSpec((rows, nc), lambda i: (i, 0)),
                  pl.BlockSpec(split.shape, lambda i: (0, 0)),
                  pl.BlockSpec(table.shape, lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((rows, c), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANES), lambda i: (i, 0))),
        scratch_shapes=[pltpu.VMEM((ROWS, LANES), jnp.float32),
                        pltpu.VMEM((LANES, ROWS), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(x, split, table)


def _post_kernel(x, y, coef, *, n, interpret):
    t, nc = x.shape
    c = nc // n
    rows = min(ROWS, t)
    body = functools.partial(_post_body, n=n, c=c, rows=rows)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((t, nc), x.dtype),
        grid=(pl.cdiv(t, rows),),
        in_specs=[pl.BlockSpec((rows, nc), lambda i: (i, 0)),
                  pl.BlockSpec((rows, c), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, nc), lambda i: (i, 0)),
        compiler_params=_params(), interpret=interpret,
    )(x, y, coef)


@functools.lru_cache(maxsize=None)
def _jitted_pre(n, iters, eps, clamp, interpret):
    def hetu_mhc_pre(x, split, table):
        return _pre_kernel(x, split, table, n=n, iters=iters, eps=eps,
                           clamp=clamp, interpret=interpret)

    hetu_mhc_pre.__name__ = hetu_mhc_pre.__qualname__ = PRE_NAME
    return jax.jit(hetu_mhc_pre)


@functools.lru_cache(maxsize=None)
def _jitted_post(n, interpret):
    def hetu_mhc_post(x, y, coef):
        return _post_kernel(x, y, coef, n=n, interpret=interpret)

    hetu_mhc_post.__name__ = hetu_mhc_post.__qualname__ = POST_NAME
    return jax.jit(hetu_mhc_post)


# ---------------------------------------------------------------------------
# what a sublayer calls
# ---------------------------------------------------------------------------

def mhc_pre(x, weights, iters, eps, clamp):
    """``(u [T, C], carry)`` of the streams ``x [T, n, C]``: the mix a
    sublayer reads, and what :func:`mhc_post` needs of this token's
    maps. ``weights`` is one sublayer's ``{"phi", "scale", "bias"}``
    (float32, see :func:`maps`) and, where the kernels run,
    ``"kernel"``: :func:`prepare` of them."""
    t, n, c = x.shape
    clamp = (float(clamp[0]), float(clamp[1]))
    if _plan(n, c, x.dtype, iters) == "kernel":
        split, table = weights["kernel"]
        return _jitted_pre(n, int(iters), float(eps), clamp, INTERPRET)(
            x.reshape(t, n * c), split, table)
    pre, post, res = maps(x, weights["phi"], weights["scale"],
                          weights["bias"], iters, eps, clamp)
    return _mix_in(x, pre), (post, res)


def mhc_post(x, y, carry):
    """``X' [T, n, C]`` from the streams ``x``, the sublayer's output
    ``y [T, C]`` and :func:`mhc_pre`'s ``carry``."""
    t, n, c = x.shape
    if isinstance(carry, tuple):
        return _mix_out(x, y, *carry)
    return _jitted_post(n, INTERPRET)(
        x.reshape(t, n * c), y.astype(x.dtype), carry).reshape(t, n, c)


def wants_prepared(n, c, dtype):
    """Whether :func:`mhc_pre` would run the kernel for such streams
    (and so wants ``weights["kernel"]``)."""
    return _form(n, c, dtype)[0] == "kernel"
