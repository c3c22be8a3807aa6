"""Plain reference for the ``smallthinker_moe`` family (SmallThinker,
arXiv:2507.20984; the 21B-A3B row of the catalog): the layer's
equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no kernel, no sort, a
dense loop over the experts held, the loss and the scores of ONE CHIP'S
SHARE (experts ``first .. first + held - 1``, the sliced vocabulary).
It imports nothing of ``hetu_tpu``. With ``x`` the residual stream
entering layer ``l`` (no bias anywhere):

    r   = x W_r                              [T, E] float32, un-normed x
    a   = RMSNorm_1(x)
    q, k, v = a W_q, a W_k, a W_v            H, G, G heads of D
    window layer (sliding_window_layout[l] = 1, rope_layout[l] = 1):
          q, k rotated in halves (theta, no scaling);
          query i sees keys  i - window < j <= i
    global layer (both 0): no positions at all; query i sees j <= i
    h   = x + Attn(q, k, v) W_o              scale 1/sqrt(D); head h
                                             reads k / v head h // (H/G)
    u   = RMSNorm_2(h)
    e_1..e_k = top-k of r;  w = softmax(r[e_1..e_k])
    y   = sum over the picks HELD here of
          w_i * W_down[e_i](relu(W_gate[e_i] u) * (W_up[e_i] u))
    out = h + y

then a final RMS norm and an untied head; the loss is the mean over ALL
positions of the next-token cross-entropy, a position labelled -1
contributing 0 (the graph's ``reduce_mean`` over the sparse-CE op). No
auxiliary loss. It works a layer at a time and walks the queries
(``QUERY_BLOCK``) and the feed-forward's tokens (``TOKEN_BLOCK``) in
blocks: on the chip it runs beside the training state.

**Routing is a discrete choice.** A bfloat16 stream and this float32
one pick differently where the k-th and (k+1)-th logits nearly tie, and
a flipped pick moves a row's scores by an expert's whole contribution.
So ``forced=`` takes the PROGRAM'S picks (outputs of the same
inference program as the scores): the weights stay this reference's own
logits at those picks, and ``loss_and_scores`` reports, layer by layer,
how many rows picked differently from the reference's own top-k and the
MARGIN of each (how far under the reference's own k-th logit the
program's worst pick lies, in logits). A flip whose margin the
bfloat16 stream cannot explain fails the comparison: ``PICK_MARGIN``.

Tolerances, each between the sound program's largest reading on the
chip and the 8-bit control's (``control="all_8bit"``: every matrix
rounded to float8_e4m3, the precision under bfloat16, through this same
forward, forced onto the same picks; my chip runs, PR 50, S = 8,192 at
the published widths: the program on eight seeds — 21, 77, 1000000007,
2147483659, 4242424243, 3000000023, 123456789, 11 —, the controls and
the faults on seeds 21 and 77; PERF.md section 4):

* ``OUTPUT_TOLERANCE`` — the worst position's RMS difference over the
  vocabulary as a share of the logits' standard deviation
  (``harness/stats.py:row_errors``). The program (bfloat16 stream and
  working copies, float32 norms / router / logits) reads 0.0072-0.0076
  at the worst of 8,192 positions (median 0.0054); the control reads
  0.0275 at its MEDIAN position and 0.034-0.035 at its worst. 0.016 is
  the geometric mean, 2.1 times of room on either side. What it sees of
  the attention, by this reference with a fault in its window layers
  against the PROGRAM's scores: no rotation 0.216-0.221, no band (every
  layer global) 0.104, a band 256 keys short — one tile of the kernel's
  walk — 0.034.
* ``PICK_MARGIN`` — in logits (their spread is about 1: 0.02 x the
  stream's norm, and the embedding has unit variance). The bfloat16
  stream's error grows with depth, and so do the flips it causes:
  89-100 / 121-137 / 154-206 / 187-218 rows of 8,192 by layer, the
  worst 0.0052 / 0.0116 / 0.0164 / 0.0198 under the reference's cut;
  the 8-bit control flips 1,174-1,372 rows a layer by up to 0.111-0.138.
  0.05 is about the geometric mean of 0.020 and 0.111. A router run in
  bfloat16 alone (``control="router_bf16"``) is NOT told apart by this,
  and that is said plainly: its flips (112-129 rows a layer, margins up
  to 0.014) are as few and as near as those the bfloat16 stream already
  causes. What the margin catches is a router that reads the wrong
  tensor or another layer's weights (margins of the logits' whole
  spread), and matrices in 8 bits.
* ``LOSS_TOLERANCE`` — relative; the harness's limit for its accepted
  train cells (``reference/gpt2.py``), because precision hardly moves
  this number: on uniform random ids the loss is ln(37,984) + 0.5 to
  three digits whatever the model computes, the 8-bit control moves it
  by 1.3e-5-3.5e-5 and the program reads 2.5e-6-1.8e-5 off the
  reference. It says the loss is the right reduction of the scores; the
  scores hold the mathematics.

One training step's GRADIENTS at the published widths (``python
scratch_chip/pr50.py grads 21`` on the chip, not committed: the
program's bfloat16 step against ``jax.grad`` of this file's ``layer``
and ``head``, a layer at a time, forced onto the program's picks), as
the norm of the difference over the norm of the reference's gradient, a
parameter: the attention's q / k 0.014-0.015, v / o 0.009-0.010, the
norms before attention 0.010-0.011, the head 0.007, the embedding
0.012, the experts 0.013-0.058, the routers 0.013-0.050 and the norms
before the experts 0.036-0.059, the deeper the layer the larger
(median 0.015 of 39 parameters, none over 0.06).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOLERANCE = 1e-2      # relative; see above
OUTPUT_TOLERANCE = 0.016   # worst position's error / std of the logits
PICK_MARGIN = 0.05         # logits; a flipped pick's distance from the cut
QUERY_BLOCK = 512
TOKEN_BLOCK = 1024
CONTROLS = ("all_8bit", "router_bf16")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotate(x, theta):
    """``x [S, heads, D]``, position = row; rotation in halves."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _blocks(n, most):
    """The largest block <= ``most`` that divides ``n``."""
    block = min(n, most)
    while n % block:
        block -= 1
    return block


def attention(q, k, v, window):
    """``q [S, H, D]``, ``k`` / ``v [S, G, D]`` -> ``[S, H * D]``:
    causal, with a ``window`` the keys ``i - window < j <= i`` alone;
    the queries in blocks, every score a float32 number."""
    s, h, d = q.shape
    g = k.shape[1]
    block = _blocks(s, QUERY_BLOCK)
    q = q.reshape(s // block, block, g, h // g, d)
    keys = jnp.arange(s)[None, :]

    def one(args):
        qb, start = args
        rows = start + jnp.arange(block)[:, None]
        seen = keys <= rows
        if window is not None:
            seen = seen & (rows - keys < window)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs, v)

    # (checkpointed: ``jax.grad`` keeps a block's inputs, not its scores)
    ctx = jax.lax.map(jax.checkpoint(one), (q, jnp.arange(0, s, block)))
    return ctx.reshape(s, h * d)


def route(x, w_router, top_k, forced, bf16=False):
    """``(picks [T, k], weights [T, k], differing [T] bool, margin
    [T])``: the softmax over the chosen logits; with ``forced`` the
    picks are taken and weighed by THIS router's logits, and a row
    whose set differs from the router's own top-k reports how far under
    the own k-th logit its worst pick lies."""
    if bf16:    # the control: the router's product in bfloat16
        logits = jnp.dot(x.astype(jnp.bfloat16),
                         w_router.astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = x @ w_router
    best, own = jax.lax.top_k(logits, top_k)
    picks = own if forced is None else forced
    chosen = jnp.take_along_axis(logits, picks, axis=-1)
    margin = best[:, -1] - jnp.min(chosen, axis=-1)
    differing = jnp.sort(picks, axis=-1) != jnp.sort(own, axis=-1)
    return (picks, jax.nn.softmax(chosen, axis=-1),
            jnp.any(differing, axis=-1), margin)


def held_experts(u, picks, weights, w_gate_up, w_down, first):
    """The held experts' part of the sum, ``[T, hidden]``: a dense loop
    over them, every token through every held expert and weighed by
    what the router gave it there (0 where it did not pick it)."""
    t, hidden = u.shape
    held, _, twice = w_gate_up.shape
    block = _blocks(t, TOKEN_BLOCK)

    def one(args):
        ub, pb, wb = args
        y = jnp.zeros((block, hidden), jnp.float32)
        for e in range(held):
            share = jnp.sum(jnp.where(pb == first + e, wb, 0.0), axis=-1)
            hid = ub @ w_gate_up[e]
            act = jax.nn.relu(hid[:, :twice // 2]) * hid[:, twice // 2:]
            y = y + share[:, None] * (act @ w_down[e])
        return y

    parts = (x.reshape(t // block, block, -1) for x in (u, picks, weights))
    return jax.lax.map(jax.checkpoint(one),
                       tuple(parts)).reshape(t, hidden)


def _eight_bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "head_dim", "window", "rotated", "theta", "eps",
    "top_k", "first", "control"))
def layer(x, w, forced, *, heads, groups, head_dim, window, rotated, theta,
          eps, top_k, first, control=None):
    """One layer over one sequence ``x [S, hidden]``; ``w`` its nine
    arrays by role. Returns ``(out, picks, differing, margin)``."""
    if control == "all_8bit":
        w = {role: a if a.ndim == 1 else _eight_bit(a)
             for role, a in w.items()}
    s = x.shape[0]
    picks, weights, differing, margin = route(
        x, w["router"], top_k, forced, control == "router_bf16")
    a = rms_norm(x, w["ln1_scale"], eps)
    q = (a @ w["attn_q"]).reshape(s, heads, head_dim)
    k = (a @ w["attn_k"]).reshape(s, groups, head_dim)
    v = (a @ w["attn_v"]).reshape(s, groups, head_dim)
    if rotated:
        q, k = rotate(q, theta), rotate(k, theta)
    h = x + attention(q, k, v, window) @ w["attn_o"]
    u = rms_norm(h, w["ln2_scale"], eps)
    y = held_experts(u, picks, weights, w["experts_gate_up"],
                     w["experts_down"], first)
    return h + y, picks, differing, margin


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, scale, w_head, labels, *, eps, control=None):
    """``(the sequence's summed cross-entropy, logits [S, V])``."""
    if control == "all_8bit":
        w_head = _eight_bit(w_head)
    logits = rms_norm(x, scale, eps) @ w_head
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, logz - picked, 0.0)), logits


ROLES = ("router", "ln1_scale", "attn_q", "attn_k", "attn_v", "attn_o",
         "ln2_scale", "experts_gate_up", "experts_down")


def layer_statics(config, i):
    """The static keywords of :func:`layer` for layer ``i`` of a
    configuration file's content."""
    return dict(
        heads=config["num_attention_heads"],
        groups=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window_size"]
        if config["sliding_window_layout"][i] else None,
        rotated=bool(config["rope_layout"][i]),
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        top_k=config["moe_num_active_primary_experts"],
        first=config["first_expert"])


def forward(params, config, ids, labels, forced=None, control=None):
    """``(loss, logits [B, S, V], routing)`` over ``ids [B, S]`` with
    ``params`` float32 arrays by checkpoint name. ``forced``: a list, a
    layer, of the picks to take ``[B, S, k]``. ``routing`` is a dict a
    layer: ``picks [B, S, k]``, ``differing [B, S]``, ``margin [B,
    S]``. Differentiable in ``params`` (``jax.grad`` of ``[0]``)."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    layers = config["num_hidden_layers"]
    eps = config["rms_norm_eps"]
    embed = params["sparse_embed"]
    if control == "all_8bit":
        embed = _eight_bit(embed)
    total, logits = 0.0, []
    routing = [{"picks": [], "differing": [], "margin": []}
               for _ in range(layers)]
    with jax.default_matmul_precision("highest"):
        for b in range(ids.shape[0]):
            x = embed[ids[b]]
            for i in range(layers):
                w = {role: params[f"sparse_h{i}_{role}"] for role in ROLES}
                x, picks, differing, margin = layer(
                    x, w, None if forced is None else forced[i][b],
                    control=control, **layer_statics(config, i))
                for key, value in (("picks", picks),
                                   ("differing", differing),
                                   ("margin", margin)):
                    routing[i][key].append(value)
            part, scores = head(x, params["sparse_ln_f_scale"],
                                params["sparse_lm_head"], labels[b],
                                eps=eps, control=control)
            total = total + part
            logits.append(scores)
    routing = [{k: jnp.stack(v) for k, v in r.items()} for r in routing]
    return total / ids.size, jnp.stack(logits), routing


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
            if k.startswith("sparse_")}


def loss_fn(params, config, ids, labels, forced=None):
    """The scalar the program trains on; ``jax.grad`` of it is the
    reference for every parameter's gradient."""
    return forward(params, config, jnp.asarray(ids, jnp.int32),
                   jnp.asarray(labels, jnp.int32), forced)[0]


def loss_and_scores(params, config, ids, labels, forced=None, control=None,
                    log=None):
    """``(loss, [logits [B, S, V]])`` for the driver's ``correct``.
    With ``forced`` (the program's picks, a layer) the flips are logged
    (``log``: a function of one dict) and a flip further than
    ``PICK_MARGIN`` under the reference's own cut makes the loss NaN,
    which no tolerance accepts (a ``control`` is read for its loss and
    scores: its flips are logged alone)."""
    forced = None if forced is None else \
        [jnp.asarray(f, jnp.int32) for f in forced]
    loss, logits, routing = forward(
        _f32(params), config, jnp.asarray(ids, jnp.int32),
        jnp.asarray(labels, jnp.int32), forced, control)
    loss = float(loss)
    if forced is not None:
        rows = [int(jnp.sum(r["differing"])) for r in routing]
        worst = [float(jnp.max(jnp.where(r["differing"], r["margin"], 0.0)))
                 for r in routing]
        ok = max(worst) <= PICK_MARGIN
        if log is not None:
            log({"check": "picks_vs_reference", "control": control,
                 "rows_differing_by_layer": rows,
                 "rows": int(routing[0]["differing"].size),
                 "worst_margin_by_layer": worst,
                 "pick_margin": PICK_MARGIN, "ok": ok})
        if not ok and control is None:
            loss = float("nan")
    return loss, [np.asarray(logits)]
