"""Plain reference for the ``trinity_afmoe`` family (grouped-query
attention over a sliding window on most layers and over everything on
every fourth, a gate on attention's output, sandwich norms, routed and
shared experts), one chip's share of an expert-parallel deployment:
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
— no cache, no kernel, no batching; attention by an explicit mask, a
block of queries at a time so that 16,384 tokens fit; a dense loop over
the held experts (every token through every held expert, weighted by 0
where it was not picked). It upcasts the SAME bfloat16-valued weights
the engine holds, a layer and an expert at a time.

The equations (``x`` the residual stream ``[T, hidden]``, every norm
RMS with ``rms_norm_eps`` and a learned gain; ISSUE 47):

* ``x = E[ids] sqrt(hidden)`` (``mup_enabled``); ``logits = rms(x)
  W_head`` over the held rows of the vocabulary, untied.
* every layer: ``x = x + rms_post_attn(attn(rms_in(x)))``, then ``x = x
  + rms_post_mlp(F(rms_pre_mlp(x)))``.
* ``attn(h)``: ``q = h W_q`` as ``heads`` of ``head_dim``, ``k = h
  W_k``, ``v = h W_v`` as ``kv_heads`` (query head ``n`` reads key/value
  head ``n // (heads / kv_heads)``), ``g = h W_g``; ``q`` and ``k``
  RMS-normed over each head (a gain a projection); on a
  ``sliding_attention`` layer both rotated (``rope_theta``, no scaling)
  and query ``i`` sees keys ``i - sliding_window < j <= i``; on a
  ``full_attention`` layer no rotation and every ``j <= i``; float32
  softmax of ``q k / sqrt(head_dim)``; ``attn = (ctx sigmoid(g)) W_o``.
* ``F`` in the first ``num_dense_layers`` layers: ``down(silu(gate h)
  up h)``; in the others ``s = sigmoid(h W_r)`` over ALL experts, the
  ``num_experts_per_tok`` largest ``s + b`` chosen (``b`` for the
  selection alone), weights ``route_scale s_k / sum of the chosen s``;
  ``F = shared(h) + sum over the chosen AND HELD experts``.

Departures from the published description, each shared with the
program under test so that both compute one function
(``configs/trinity-large-ep8.json`` ``assumed`` names them as
inferences): the gate's place (on the context, before ``W_o``) and
width (``heads x head_dim``), a sigmoid — from "gated", the row has no
key for it; the norm over ``q`` and ``k`` BEFORE the rotation; the
rotation pairs dimension ``i`` with ``i + head_dim / 2``; every norm's
gain 1 ("depth-scaled" is an initialisation); the window's edge (``i -
j < sliding_window``: the window holds the query's own key); experts
held elsewhere add nothing.

Nothing here comes from the program under test: the module imports
nothing of ``hetu_tpu``.

**Forced routing**, as ``reference/sarvam_mla.py`` has it:
``forward(..., forced=picks)`` takes the experts the ENGINE picked at
the checked rows (the weights stay the reference's own scores at those
picks) and reports each expert layer's selection scores there.

``MUTANTS`` are deliberate faults of this reference: a check that
cannot tell one of them from the engine is not a check
(``families/trinity_afmoe.py`` shows each failing). ``all_8bit`` is the
lower-precision control: every matrix rounded to 8 bits, the nearest
precision under the bfloat16 the configuration states.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"

# faults of the whole forward, seen in the logits ...
WHOLE_MUTANTS = ("rope_on_full", "no_rope_on_sliding", "no_gate",
                 "no_post_norms", "no_embed_scale", "no_route_scale")
# ... and of one sliding layer's attention, seen on that layer's rows:
# the window a key too wide / too narrow, and a decode step that reads
# every row its ring holds, those that have left the window too
# (``rope_on_full`` moves a logit by a tenth: it is ALSO held against
# the full layer's rows, where it moves a row by its whole length)
ATTENTION_MUTANTS = ("window_plus_1", "window_minus_1", "ring_unmasked")
MUTANTS = WHOLE_MUTANTS + ATTENTION_MUTANTS
CONTROL = "all_8bit"
# queries a block of the explicit mask, and tokens a pass of a
# feed-forward: a layer's temporaries then stay under 3 GB at 16,384
# tokens, beside the engine the check runs next to
QUERY_BLOCK = 128
TOKEN_BLOCK = 1024


def _f32(a):
    return a.astype(jnp.float32)


def _round_8bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def rope(x, positions, theta):
    """Rotate ``x [T, heads, D]`` in halves by ``positions [T]``."""
    d = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                      jnp.float32)
    ang = _f32(positions)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w_gate_up, w_down):
    h = x @ w_gate_up
    width = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :width]) * h[:, width:]) @ w_down


def qkvg(h, positions, w, config, sliding, mutant=None):
    """``(q [T, heads, D], k, v [T, kv_heads, D], gate [T, heads x
    D])`` of the normed rows ``h``."""
    c = config
    nq, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    t = h.shape[0]
    out = h @ _f32(w["qkvg"])
    q = rms(out[:, :nq * hd].reshape(t, nq, hd), w["q_norm"],
            c["rms_norm_eps"])
    k = rms(out[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd),
            w["k_norm"], c["rms_norm_eps"])
    v = out[:, (nq + nkv) * hd:(nq + 2 * nkv) * hd].reshape(t, nkv, hd)
    rotate = sliding
    if mutant == "rope_on_full" and not sliding:
        rotate = True
    if mutant == "no_rope_on_sliding" and sliding:
        rotate = False
    if rotate:
        q, k = rope(q, positions, c["rope_theta"]), \
            rope(k, positions, c["rope_theta"])
    return q, k, v, out[:, (nq + 2 * nkv) * hd:]


def seen(config, rows, cols, sliding, mutant=None, prompt_len=None,
         ring=None):
    """``[len(rows), len(cols)]`` bool: which keys (positions ``cols``)
    each query (positions ``rows``) sees."""
    d = rows[:, None] - cols[None, :]
    ok = d >= 0
    if not sliding:
        return ok
    window = config["sliding_window"] + {"window_plus_1": 1,
                                         "window_minus_1": -1}.get(mutant, 0)
    inside = d < window
    if mutant == "ring_unmasked":
        # a decode row reads all its ring holds: the last ``ring``
        # positions, of which a prefill wrote the prompt's last window
        written = cols[None, :] >= prompt_len - config["sliding_window"]
        loose = (d < ring) & (written | (cols[None, :] >= prompt_len))
        inside = jnp.where(rows[:, None] >= prompt_len, loose, inside)
    return ok & inside


def context(q, k, v, config, positions, sliding, mutant=None,
            prompt_len=None, ring=None):
    """Attention's context ``[T, heads x D]`` by an explicit mask, a
    block of ``QUERY_BLOCK`` queries at a time."""
    c = config
    t, nq, hd = q.shape
    nkv = c["num_key_value_heads"]
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    # query head n reads key/value head n // (heads / kv_heads)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, nkv, nq // nkv, hd)
    rows = jnp.pad(positions, (0, pad), constant_values=positions[-1])

    def one(args):
        qb, rb = args               # [block, kv_heads, group, D], [block]
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) / np.sqrt(hd)
        ok = seen(c, rb, positions, sliding, mutant, prompt_len, ring)
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    ctx = jax.lax.map(one, (qp, rows.reshape(-1, block)))
    return ctx.reshape(-1, nq * hd)[:t]


def router(x, w_router, bias, config, mutant=None, forced=None):
    """``(experts [T, k], weights [T, k], scores [T, E], margin [T])``:
    the picks, their weights, what the selection compares (``s + b``
    over ALL experts) and the distance between the last score picked and
    the first left out. ``forced = (experts [T, k], rows [T] bool)``
    replaces the picks on the marked rows; their weights are still this
    router's own scores at those picks."""
    k = config["num_experts_per_tok"]
    p = jax.nn.sigmoid(x @ _f32(w_router))
    scores = p + _f32(bias)
    top, experts = jax.lax.top_k(scores, k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k].astype(jnp.int32)
    if forced is not None:
        experts = jnp.where(forced[1][:, None], forced[0], experts)
    picked = jnp.take_along_axis(p, experts, axis=-1)
    scale = 1.0 if mutant == "no_route_scale" else config["route_scale"]
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, weights, scores, margin


def held_experts(x, experts, weights, w_gate_up, w_down, first,
                 eight_bit=False):
    """``sum over the held picks of weight * expert(x)``: a dense loop,
    every token through every held expert."""

    def body(e, acc):
        gu, dn = _f32(w_gate_up[e]), _f32(w_down[e])
        if eight_bit:
            gu, dn = _round_8bit(gu), _round_8bit(dn)
        coef = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return acc + coef[:, None] * swiglu(x, gu, dn)

    return jax.lax.fori_loop(0, w_gate_up.shape[0], body,
                             jnp.zeros_like(x))


def feed_forward(h, w, config, mutant=None, forced=None):
    """``(F(h), what the router did or None)`` of the normed rows."""
    if "mlp_gate_up" in w:
        return swiglu(h, _f32(w["mlp_gate_up"]), _f32(w["mlp_down"])), None
    experts, weights, scores, margin = router(
        h, w["router"], w["router_bias"], config, mutant, forced)
    y = swiglu(h, _f32(w["shared_gate_up"]), _f32(w["shared_down"])) \
        + held_experts(h, experts, weights, w["experts_gate_up"],
                       w["experts_down"],
                       config["deployment"]["experts_first"],
                       mutant == CONTROL)
    return y, {"experts": experts, "scores": scores, "margin": margin,
               "input": h}


def _token_blocks(fn, h, *rest):
    """``fn`` over ``TOKEN_BLOCK`` tokens at a time (a feed-forward is
    a function of each token alone), its results put together again."""
    t = h.shape[0]
    if t <= TOKEN_BLOCK:
        return fn(h, *rest)
    pad = -t % TOKEN_BLOCK
    blocks = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        -1, TOKEN_BLOCK, *a.shape[1:]) for a in (h, *rest)]
    out = jax.lax.map(lambda args: fn(*args), tuple(blocks))
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:t], out)


def _layer(x, positions, w, forced, config, sliding, mutant, prompt_len,
           ring):
    c = config
    eps = c["rms_norm_eps"]
    if mutant == CONTROL:       # every matrix in 8 bits; norms stay
        w = {k: _round_8bit(_f32(v)) if v.ndim == 2 and k != "router"
             else v for k, v in w.items()}
    h = rms(x, w["in_norm"], eps)
    q, k, v, gate = qkvg(h, positions, w, c, sliding, mutant)
    ctx = context(q, k, v, c, positions, sliding, mutant, prompt_len, ring)
    if mutant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(gate)
    a = ctx @ _f32(w["o"])
    x = x + (a if mutant == "no_post_norms"
             else rms(a, w["post_attn_norm"], eps))
    y, seen_ = _token_blocks(
        lambda h, picks, rows: feed_forward(h, w, c, mutant, (picks, rows)),
        rms(x, w["pre_mlp_norm"], eps), *forced)
    x = x + (y if mutant == "no_post_norms"
             else rms(y, w["post_mlp_norm"], eps))
    return x, seen_, {"input": h, "q": q, "k": k, "v": v}


def _hashable(config):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in config.items()
        if isinstance(v, (int, float, str, bool, list))
        and k != "layer_types")) + (
            ("experts_first", config["deployment"]["experts_first"]),)


@functools.lru_cache(maxsize=None)
def _jitted_layer(config_key, sliding, mutant, want_attention):
    config = {k: v for k, v in config_key}
    config["deployment"] = {"experts_first": config.pop("experts_first")}

    def layer(x, positions, w, forced, prompt_len, ring):
        x, seen_, att = _layer(x, positions, w, forced, config, sliding,
                               mutant, prompt_len, ring)
        return x, seen_, (att if want_attention else None)

    return jax.jit(layer)


def layer_weights(weights, i):
    p = f"lm_h{i}_"
    return {k[len(p):]: weights[k] for k in weights if k.startswith(p)}


def forward(weights, config, tokens, positions, pad_to=None, mutant=None,
            forced=None, prompt_len=None, ring=None, want_layers=()):
    """The whole forward over a 1-D token sequence, layer by layer.
    Returns ``(logits [len(positions), V] float32, layers)``; ``layers``
    holds, for each expert layer, what its router did at ``positions``
    (``experts``, ``scores``, ``margin``, the normed ``input``).
    ``forced [n, expert layers, k]`` are picks to take at ``positions``
    in place of the router's own. The sequence is padded to ``pad_to``
    (causal attention keeps the padding out of the real positions).
    With ``want_layers`` the third value is ``{layer: its attention
    inputs over the real tokens, {"input", "q", "k", "v"}}``."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros(max(pad_to or n, n), np.int32)
    ids[:n] = tokens
    rows = np.asarray(positions, np.int64)
    k = config["num_experts_per_tok"]
    marked = np.zeros(len(ids), bool)
    marked[rows] = forced is not None
    whole = mutant if mutant in WHOLE_MUTANTS + (CONTROL,) else None
    key = _hashable(config)
    attention = {}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["lm_embed"][jnp.asarray(ids)])
        head = _f32(weights["lm_head"])
        if mutant == CONTROL:
            x, head = _round_8bit(x), _round_8bit(head)
        if config["mup_enabled"] and mutant != "no_embed_scale":
            x = x * np.sqrt(config["hidden_size"])
        pos = jnp.arange(len(ids), dtype=jnp.int32)
        layers = []
        for i, kind in enumerate(config["layer_types"]):
            picks = np.zeros((len(ids), k), np.int32)
            if forced is not None and i >= config["num_dense_layers"]:
                picks[rows] = np.asarray(forced)[:, len(layers)]
            layer = _jitted_layer(key, kind == SLIDING, whole,
                                  i in want_layers)
            x, seen_, att = layer(
                x, pos, layer_weights(weights, i),
                (jnp.asarray(picks), jnp.asarray(marked)),
                jnp.int32(prompt_len or 0), jnp.int32(ring or 0))
            if seen_ is not None:
                layers.append({k_: np.asarray(v[jnp.asarray(rows)])
                               for k_, v in seen_.items()})
            if att is not None:
                attention[i] = {k_: v[:n] for k_, v in att.items()}
        last = rms(x[jnp.asarray(rows)], weights["lm_norm"],
                   config["rms_norm_eps"])
        logits = np.asarray(last @ head)
    if not want_layers:
        return logits, layers
    return logits, layers, attention


def logits_at(weights, config, tokens, positions, pad_to=None):
    return forward(weights, config, tokens, positions, pad_to)[0]


def attention_context(config, att, sliding, mutant=None, prompt_len=None,
                      ring=None):
    """One layer's context ``[T, heads x D]`` (before the gate) of the
    attention inputs ``att`` (:func:`forward`'s ``want_layers``), or a
    mutant's (``rope_on_full``: the full layer's ``q`` and ``k``
    rotated after all)."""
    with jax.default_matmul_precision("highest"):
        t = att["q"].shape[0]
        positions = jnp.arange(t, dtype=jnp.int32)

        def run(q, k, v):
            if mutant == "rope_on_full" and not sliding:
                q, k = rope(q, positions, config["rope_theta"]), \
                    rope(k, positions, config["rope_theta"])
            return context(q, k, v, config, positions, sliding, mutant,
                           prompt_len, ring)

        return np.asarray(jax.jit(run)(att["q"], att["k"], att["v"]))


def expert_layer_parts(weights, config, layer, x, mutant=None):
    """One expert layer's router and routed sum on given normed inputs
    ``x [n, hidden]`` (float32): ``(experts, weights, margin, routed
    [n, hidden])``. The control rounds the held experts to 8 bits (the
    router is float32 in the configuration, and stays)."""
    w = layer_weights(weights, layer)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        experts, wts, _, margin = router(x, w["router"], w["router_bias"],
                                         config, mutant)
        routed = held_experts(
            x, experts, wts, w["experts_gate_up"], w["experts_down"],
            config["deployment"]["experts_first"], mutant == CONTROL)
    return (np.asarray(experts), np.asarray(wts), np.asarray(margin),
            np.asarray(routed))


def uncut_expert_layer(weights, config, layer, x):
    """The UNCUT layer's ``F`` on normed inputs ``x``: the shared expert
    once plus every chosen expert, where ``weights`` holds ALL experts
    (the share test: ``experts_first`` 0 and every expert held)."""
    w = layer_weights(weights, layer)
    with jax.default_matmul_precision("highest"):
        y, _ = feed_forward(jnp.asarray(x, jnp.float32), w,
                            dict(config, deployment={"experts_first": 0}))
    return np.asarray(y)
