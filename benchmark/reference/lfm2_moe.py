"""Plain reference for the ``lfm2_moe`` family (LFM2-8B-A1B in the
catalog, https://huggingface.co/LiquidAI/LFM2-8B-A1B): the layer's
equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no kernel, no sort, a
dense loop over the experts held, shifts by pad and slice —, the loss
and the scores of ONE CHIP'S SHARE (experts ``first .. first + held -
1``, the sliced vocabulary). It imports nothing of ``hetu_tpu``. With
``x`` the residual stream entering layer ``l`` (no bias anywhere, RMS
norms with a learned gain, eps ``norm_eps``):

    a   = RMSNorm_op(x)
    conv layer (layer_types[l] = "conv"):
        B, C, u = split3(a W_in)                     W_in [hidden, 3 hidden]
        z_t = B_t * u_t
        v_t = w[:, 0] z_{t-2} + w[:, 1] z_{t-1} + w[:, 2] z_t
                                      (depthwise, zeros before t = 0)
        m   = (C * v) W_out
    attention layer ("full_attention"):
        q = a W_q (H heads of D), k = a W_k, v = a W_v (G heads of D)
        q = RMSNorm_D(q) g_q, k = RMSNorm_D(k) g_k   (a gain of D shared
                                      by the heads), THEN rotated in
                                      halves (theta, positions from 0)
        m   = Attn(q, k, v) W_o       causal, scale 1/sqrt(D), no window;
                                      head h reads k / v head h // (H/G)
    h   = x + m
    n   = RMSNorm_ffn(h)
    dense layer (l < num_dense_layers):
        f   = W_down(silu(W_gate n) * (W_up n))
    expert layer:
        s   = sigmoid(n W_r)                         [T, E] float32
        e_1..e_k = top-k of s + bias                 bias [E]: a buffer
        w_i = s[e_i] / (sum_j s[e_j] + 1e-6) * routed_scaling_factor
        f   = sum over the picks HELD here of
              w_i * W_down[e_i](silu(W_gate[e_i] n) * (W_up[e_i] n))
    out = h + f

then a final RMS norm and the TIED head ``logits = hidden E^T`` (``E``
the token table); the loss is the mean over ALL positions of the
next-token cross-entropy, a position labelled -1 contributing 0. No
auxiliary loss, and nothing moves the bias. It works a layer at a time
and walks the queries (``QUERY_BLOCK``) and the feed-forward's tokens
(``TOKEN_BLOCK``) in blocks: on the chip it runs beside the training
state.

**Routing is a discrete choice**, as ``reference/smallthinker_moe.py``
says at length: ``forced=`` takes the PROGRAM'S picks (outputs of the
same inference program as the scores), the weights stay this
reference's own scores at those picks, and a row whose set differs from
the reference's own top-k of ``s + bias`` reports how far under the
reference's own k-th ``s + bias`` its worst pick lies (``PICK_MARGIN``,
in units of the score: a sigmoid's, so at most 1).

Tolerances, each between the sound program's largest reading on the
chip and the 8-bit control's (``control="all_8bit"``: every matrix
rounded to float8_e4m3, the precision under bfloat16, through this same
forward, forced onto the same picks), with two planted faults that must
read past a limit (``control="no_bias"``: the bias left out of the
selection; ``control="two_taps"``: the convolution one tap short, its
oldest). READINGS (my chip runs, PR 56, S = 8,192 at the published
widths; PERF.md section 4):

* ``OUTPUT_TOLERANCE`` — the worst position's RMS difference over the
  vocabulary as a share of the logits' standard deviation
  (``harness/stats.py:row_errors``). The program (bfloat16 stream and
  working copies, float32 norms / router / convolution arithmetic /
  logits) reads 0.0186-0.0196 at the worst of 8,192 positions on nine
  seeds (median position 0.0158): three times the smallthinker
  reference's reading, because here every layer's own rounded output
  carries the stream (the table's rows are 0.02 wide) where there a
  unit-variance table does. The control reads 0.110 at its MEDIAN
  position and 0.135-0.140 at its worst (seeds 21, 77). 0.05 is about
  the geometric mean of 0.0196 and 0.110: 2.5 times of room above the
  program, 2.2 under the control's median position. One tap short
  (``two_taps``) reads 1.16 against the program's scores.
* ``PICK_MARGIN`` — in units of ``score + bias`` (a sigmoid's score: the
  spread of the scores is about 0.2, the seeded bias N(0, 0.02)). The
  bfloat16 stream's flips grow with depth: 263-312 / 327-365 / 384-434 /
  425-485 rows of 8,192 by expert layer, the worst 0.0060-0.0090 /
  0.0078-0.0109 / 0.0097-0.0124 / 0.0094-0.0149 under the reference's
  cut (nine seeds). The selection WITHOUT the bias (``no_bias``: the
  scores and so the weights are the same numbers, the picks are not)
  differs from the program's on 2,430-3,100 rows a layer by up to
  0.059-0.091 (the worst layer of a seed 0.088-0.091); the 8-bit control
  flips 2,030-2,880 rows a layer by up to 0.066-0.107. 0.035 is about
  the geometric mean of 0.0149 and 0.088: 2.3 times of room above the
  program's worst, 2.5 under the fault's. (With the bias at N(0, 0.01)
  the fault read 0.031-0.040 and no limit had room: the configuration
  file's ``assumed.expert_bias``.)
* ``LOSS_TOLERANCE`` — relative; the harness's limit for its accepted
  train cells, because precision hardly moves this number: on uniform
  random ids the loss is ln(16,384) + 0.41 whatever the model computes;
  the program reads 0.9-2.3e-5 off the reference, the 8-bit control
  1.0-2.4e-4, one tap short 0.3-7.6e-4. It says the loss is the right
  reduction of the scores; the scores hold the mathematics.

(Readings with the bias drawn plain. The committed draw takes each
chip's eight experts' mean off; the committed files' own readings, my
chip runs, PR 56, eight more seeds: the program 0.0186-0.0196 at its
worst position, its flips at most 0.0059-0.0149 under the cut on
261-489 rows a layer; ``no_bias`` 0.057-0.073 on 2,386-3,127 rows, the
worst layer of a seed 0.069 and 0.073; 8 bits 0.136-0.140 of the
scores' spread and 0.062-0.114 under the cut; ``two_taps`` 1.16. 0.035
keeps 2.3 times of room above the program and 2.0 under the fault.)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOLERANCE = 1e-2      # relative; the harness's accepted train cells'
OUTPUT_TOLERANCE = 0.05    # worst position's error / std of the logits
PICK_MARGIN = 0.035        # score + bias; a flipped pick's distance
QUERY_BLOCK = 512
TOKEN_BLOCK = 1024
NORM_TOPK_EPS = 1e-6
CONTROLS = ("all_8bit", "no_bias", "two_taps")


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


def attention(q, k, v):
    """``q [S, H, D]``, ``k`` / ``v [S, G, D]`` -> ``[S, H * D]``:
    causal; the queries in blocks, every score a float32 number."""
    s, h, d = q.shape
    g = k.shape[1]
    block = _blocks(s, QUERY_BLOCK)
    q = q.reshape(s // block, block, g, h // g, d)
    keys = jnp.arange(s)[None, :]

    def one(args):
        qb, start = args
        seen = keys <= start + jnp.arange(block)[:, None]
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs, v)

    ctx = jax.lax.map(jax.checkpoint(one), (q, jnp.arange(0, s, block)))
    return ctx.reshape(s, h * d)


def short_conv(proj, taps, skip_oldest=False):
    """``C * conv(B * u)`` of ``proj [S, 3C]`` (``B | C | u``) under
    ``taps [C, K]``: ``v_t = sum_j taps[:, j] z_{t - (K - 1 - j)}``.
    ``skip_oldest``: the planted fault, tap 0 left out."""
    channels, k = taps.shape
    s = proj.shape[0]
    gate_in, gate_out, u = (proj[:, i * channels:(i + 1) * channels]
                            for i in range(3))
    z = jnp.pad(gate_in * u, ((k - 1, 0), (0, 0)))
    v = sum(taps[:, j] * z[j:j + s]
            for j in range(1 if skip_oldest else 0, k))
    return gate_out * v


def route(n, w_router, bias, top_k, scale, forced, use_bias=True):
    """``(picks [T, k], weights [T, k], differing [T] bool, margin
    [T])``: sigmoid scores, the top-k of ``score + bias``, the chosen
    scores over their sum; with ``forced`` the picks are taken and
    weighed by THIS router's scores, and a row whose set differs from
    the router's own reports how far under the own k-th ``score + bias``
    its worst pick lies."""
    scores = jax.nn.sigmoid(n @ w_router)
    choice = scores + bias if use_bias else scores
    best, own = jax.lax.top_k(choice, top_k)
    picks = own if forced is None else forced
    margin = best[:, -1] - jnp.min(
        jnp.take_along_axis(choice, picks, axis=-1), axis=-1)
    chosen = jnp.take_along_axis(scores, picks, axis=-1)
    weights = scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    differing = jnp.sort(picks, axis=-1) != jnp.sort(own, axis=-1)
    return picks, weights, jnp.any(differing, axis=-1), margin


def held_experts(n, picks, weights, w_gate_up, w_down, first):
    """The held experts' part of the sum, ``[T, hidden]``: a dense loop
    over them, every token through every held expert and weighed by
    what the router gave it there (0 where it did not pick it)."""
    t, hidden = n.shape
    held, _, twice = w_gate_up.shape
    block = _blocks(t, TOKEN_BLOCK)

    def one(args):
        nb, pb, wb = args
        y = jnp.zeros((block, hidden), jnp.float32)
        for e in range(held):
            share = jnp.sum(jnp.where(pb == first + e, wb, 0.0), axis=-1)
            hid = nb @ w_gate_up[e]
            act = jax.nn.silu(hid[:, :twice // 2]) * hid[:, twice // 2:]
            y = y + share[:, None] * (act @ w_down[e])
        return y

    parts = (x.reshape(t // block, block, -1) for x in (n, picks, weights))
    return jax.lax.map(jax.checkpoint(one),
                       tuple(parts)).reshape(t, hidden)


def dense_ffn(n, w_gate_up, w_down):
    t = n.shape[0]
    block = _blocks(t, TOKEN_BLOCK)
    width = w_gate_up.shape[1] // 2

    def one(nb):
        hid = nb @ w_gate_up
        return (jax.nn.silu(hid[:, :width]) * hid[:, width:]) @ w_down

    return jax.lax.map(jax.checkpoint(one),
                       n.reshape(t // block, block, -1)).reshape(t, -1)


def _eight_bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dense", "heads", "groups", "head_dim", "theta", "eps",
    "top_k", "first", "scale", "control"))
def layer(x, w, forced, *, kind, dense, heads, groups, head_dim, theta,
          eps, top_k, first, scale, control=None):
    """One layer over one sequence ``x [S, hidden]``; ``w`` its arrays
    by role. Returns ``(out, picks, differing, margin)`` (the last three
    None for a dense layer)."""
    if control == "all_8bit":
        w = {role: a if a.ndim == 1 else _eight_bit(a)
             for role, a in w.items()}
    s = x.shape[0]
    a = rms_norm(x, w["op_norm_scale"], eps)
    if kind == "conv":
        m = short_conv(a @ w["conv_in"], w["conv_taps"],
                       control == "two_taps") @ w["conv_out"]
    else:
        q = (a @ w["attn_q"]).reshape(s, heads, head_dim)
        k = (a @ w["attn_k"]).reshape(s, groups, head_dim)
        v = (a @ w["attn_v"]).reshape(s, groups, head_dim)
        q = rotate(rms_norm(q, w["attn_q_norm_scale"], eps), theta)
        k = rotate(rms_norm(k, w["attn_k_norm_scale"], eps), theta)
        m = attention(q, k, v) @ w["attn_o"]
    h = x + m
    n = rms_norm(h, w["ffn_norm_scale"], eps)
    if dense:
        return h + dense_ffn(n, w["ffn_gate_up"], w["ffn_down"]), \
            None, None, None
    picks, weights, differing, margin = route(
        n, w["router"], w["expert_bias"], top_k, scale, forced,
        control != "no_bias")
    y = held_experts(n, picks, weights, w["experts_gate_up"],
                     w["experts_down"], first)
    return h + y, picks, differing, margin


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x, scale, table, labels, *, eps, control=None):
    """``(the sequence's summed cross-entropy, logits [S, V])`` under
    the tied head ``hidden E^T``."""
    if control == "all_8bit":
        table = _eight_bit(table)
    logits = rms_norm(x, scale, eps) @ table.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, logz - picked, 0.0)), logits


MIXER_ROLES = {
    "conv": ("conv_in", "conv_taps", "conv_out"),
    "full_attention": ("attn_q", "attn_k", "attn_v", "attn_o",
                       "attn_q_norm_scale", "attn_k_norm_scale")}
DENSE_ROLES = ("ffn_gate_up", "ffn_down")
EXPERT_ROLES = ("router", "expert_bias", "experts_gate_up", "experts_down")


def is_dense(config, i):
    return i < config["num_dense_layers"]


def layer_roles(config, i):
    return ("op_norm_scale", "ffn_norm_scale") \
        + MIXER_ROLES[config["layer_types"][i]] \
        + (DENSE_ROLES if is_dense(config, i) else EXPERT_ROLES)


def expert_layers(config):
    return [i for i in range(config["num_hidden_layers"])
            if not is_dense(config, i)]


def layer_statics(config, i):
    """The static keywords of :func:`layer` for layer ``i`` of a
    configuration file's content."""
    return dict(
        kind=config["layer_types"][i], dense=is_dense(config, i),
        heads=config["num_attention_heads"],
        groups=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        theta=float(config["rope_theta"]), eps=config["norm_eps"],
        top_k=config["num_experts_per_tok"],
        first=config["first_expert"],
        scale=float(config["routed_scaling_factor"]))


def forward(params, config, ids, labels, forced=None, control=None):
    """``(loss, logits [B, S, V], routing)`` over ``ids [B, S]`` with
    ``params`` float32 arrays by checkpoint name. ``forced``: a list, an
    EXPERT layer, of the picks to take ``[B, S, k]``. ``routing`` is a
    dict an expert layer: ``picks [B, S, k]``, ``differing [B, S]``,
    ``margin [B, S]``. Differentiable in ``params`` (``jax.grad`` of
    ``[0]``); the bias gets a zero gradient: it only selects."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    eps = config["norm_eps"]
    table = params["hybrid_embed"]
    embed = _eight_bit(table) if control == "all_8bit" else table
    sparse = expert_layers(config)
    total, logits = 0.0, []
    routing = [{"picks": [], "differing": [], "margin": []} for _ in sparse]
    with jax.default_matmul_precision("highest"):
        for b in range(ids.shape[0]):
            x = embed[ids[b]]
            for i in range(config["num_hidden_layers"]):
                w = {role: params[f"hybrid_h{i}_{role}"]
                     for role in layer_roles(config, i)}
                at = sparse.index(i) if i in sparse else None
                x, picks, differing, margin = layer(
                    x, w, None if forced is None or at is None
                    else forced[at][b], control=control,
                    **layer_statics(config, i))
                if at is not None:
                    for key, value in (("picks", picks),
                                       ("differing", differing),
                                       ("margin", margin)):
                        routing[at][key].append(value)
            part, scores = head(x, params["hybrid_ln_f_scale"], table,
                                labels[b], eps=eps, control=control)
            total = total + part
            logits.append(scores)
    routing = [{k: jnp.stack(v) for k, v in r.items()} for r in routing]
    return total / ids.size, jnp.stack(logits), routing


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
            if k.startswith("hybrid_")}


def loss_fn(params, config, ids, labels, forced=None):
    """The scalar the program trains on; ``jax.grad`` of it is the
    reference for every parameter's gradient."""
    return forward(params, config, jnp.asarray(ids, jnp.int32),
                   jnp.asarray(labels, jnp.int32), forced)[0]


def loss_and_scores(params, config, ids, labels, forced=None, control=None,
                    log=None):
    """``(loss, [logits [B, S, V]])`` for the driver's ``correct``.
    With ``forced`` (the program's picks, an expert layer) the flips are
    logged (``log``: a function of one dict) and a flip further than
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
