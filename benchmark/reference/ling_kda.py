"""Plain reference of the ``ling_kda`` family: a decoder whose layers mix
tokens through a delta-rule LINEAR attention with a per-channel decay
(Kimi Delta Attention, arXiv:2510.26692) except every
``layer_group_size``-th, which is multi-head LATENT attention; one dense
SwiGLU layer, then routed experts under a GROUP-LIMITED sigmoid router
and one shared expert; one chip's share of an expert-parallel deployment
(the held experts and the held rows of the vocabulary, nothing standing
in for the rest).

``jax.numpy`` in float32 at ``jax.default_matmul_precision("highest")``;
the recurrence a plain ``lax.scan`` a TOKEN on a state laid out as
published (``[d_k, d_v]`` a head) — no chunks, no kernel, no cache, no
batching, and nothing of ``hetu_tpu``. One sequence at a time, layer by
layer; a delta-rule layer and a feed-forward in BLOCKS of tokens (the
first hands the convolutions' tail and the state on), latent attention
in blocks of queries with explicit scores, so that the published widths
fit beside a resident engine.

The equations (``u`` the normed input ``[T, hidden]``, a head ``h`` of
``d = 128`` channels; ISSUE 54, Tentpole 1):

* delta-rule mixer: ``[q~ k~ v~] = u W_qkv``; ``q, k, v = silu(conv4(.))``
  (depthwise, causal, no bias); ``q = l2(q) d^-0.5``, ``k = l2(k)``;
  ``g = lower_bound sigmoid(exp(A_log[h]) (u W_f + dt_bias))`` a
  channel, ``a = exp(g)``; ``b = sigmoid(u W_b)`` a head; ``S' =
  diag(a_t) S_{t-1}``, ``S_t = S' + b_t k_t (v_t - S'^T k_t)^T``, ``o_t
  = S_t^T q_t``; out ``= (rms_head(o) w_norm sigmoid(u W_g)[h]) W_o``.
* latent mixer: ``families/sarvam_mla.py``'s, with no YaRN, an RMS norm
  a head on the query, and the same sigmoid gate a head on the context.
* router: ``s = sigmoid(x W_r)``; selection on ``s + bias``: a group's
  score is the sum of its two largest, the best ``topk_group`` groups
  stay, the ``k`` largest inside them are picked; weights are the picked
  ``s`` normalised to sum 1, times the scaling factor.

**Forced routing** as ``reference/sarvam_mla.py`` has it: ``forward(...,
forced=picks)`` takes the experts the ENGINE picked at the checked rows;
each layer's selection scores there (``s + bias`` over ALL experts) go
back beside the logits, for the family's reading of how far the engine's
picks lie from this router's own (``pick_readings``).

``MUTANTS`` are deliberate faults for the checker to catch, ``CONTROLS``
the lower precisions. Faults of the whole forward (``WHOLE_MUTANTS``):

* ``head_gate_dropped`` — no gate on any mixer's output;
* ``layer_pattern_shifted`` — the latent layer runs one place early (the
  layers keep their weights: ``kda x 5, mla, kda`` for ``kda x 6, mla``);
* ``group_limit_ignored`` — the router picks among all experts (seen by
  the picks' reading alone: the logits run forced).

Faults of the delta-rule mixer (``MIXER_MUTANTS``), played by giving the
reference the wrong thing where the engine could make the mistake:

* ``state_at_bucket_end`` — the recurrence and the convolutions see the
  prompt right-padded to its bucket (with its last token) before the
  generated tokens (the family builds that sequence);
* ``conv_tail_dropped`` — from the first generated token on, the
  convolutions' taps on earlier positions read zeros;
* ``decay_off`` — ``a = 1``; ``beta_one`` — ``b = 1``; ``no_k_l2norm``;
* ``slot_not_zeroed`` — the layer starts from the state this very
  sequence left behind, not from zero (the family runs it twice).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

WHOLE_MUTANTS = ("head_gate_dropped", "layer_pattern_shifted",
                 "group_limit_ignored")
MIXER_MUTANTS = ("state_at_bucket_end", "conv_tail_dropped", "decay_off",
                 "beta_one", "no_k_l2norm", "slot_not_zeroed")
MUTANTS = WHOLE_MUTANTS + MIXER_MUTANTS
# every matrix rounded to 8 bits | the state slots in bfloat16 (played by
# the family on the PROGRAM's slots, against this reference as it is)
CONTROLS = ("all_8bit", "state_bf16")
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
KDA, MLA = "kda", "mla"
# a layer's parameters that its mixer reads
_KDA_KEYS = ("kda_qkv", "kda_conv", "kda_f", "kda_dt_bias", "kda_a_log",
             "kda_b", "kda_norm", "gate", "o")
_MLA_KEYS = ("kv_a", "kv_norm", "q", "q_norm", "kv_b", "gate", "o")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _round_8bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def layer_weights(weights, i, mutant=None):
    """Layer ``i``'s parameters by their short names (as they are held:
    a function here upcasts what it uses)."""
    p = f"lm_h{i}_"
    w = {k[len(p):]: weights[k] for k in weights if k.startswith(p)}
    if mutant == "all_8bit":    # every matrix in 8 bits; the rest stays
        # (the held experts one at a time, where they are used)
        w = {k: _round_8bit(_f32(v)) if v.ndim == 2 and k not in
             ("router", "kda_conv") else v for k, v in w.items()}
    return w


def swiglu(x, w_gate_up, w_down):
    h = x @ w_gate_up
    width = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :width]) * h[:, width:]) @ w_down


def head_gate(w, u, heads, mutant=None):
    """``heads [T, nh, d]`` times the sigmoid gate a head of ``u``."""
    if mutant == "head_gate_dropped":
        return heads
    return heads * jax.nn.sigmoid(u @ _f32(w["gate"]))[..., None]


# ---------------------------------------------------------------------------
# the delta-rule mixer, a block of tokens at a time
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("heads", "lower_bound", "eps",
                                             "mutant"))
def kda_block(w, u, tail, state, before, heads, lower_bound, eps,
              mutant=None):
    """``u [T, hidden]`` (normed) from the tail ``[K - 1, 3 d]`` and the
    state ``[heads, d_k, d_v]`` the block starts with; ``before [K - 1 +
    T]`` says which positions of the window lie before the prompt's end
    (read by ``conv_tail_dropped`` alone). Returns ``(out [T, hidden],
    tail, state)``."""
    with jax.default_matmul_precision("highest"):
        w = {k: _f32(v) for k, v in w.items()}
        t = u.shape[0]
        taps_n = w["kda_conv"].shape[0]
        window = jnp.concatenate([tail, u @ w["kda_qkv"]])
        # tap j of position i lies on window[i + j]; the last is itself
        taps = jnp.stack([window[j:j + t] for j in range(taps_n)], axis=1)
        if mutant == "conv_tail_dropped":
            # a position past the prompt's end sees none before it
            early = jnp.stack([before[j:j + t] for j in range(taps_n)],
                              axis=1)
            taps = jnp.where((early & ~before[taps_n - 1:, None])[..., None],
                             0.0, taps)
        x = jax.nn.silu(jnp.einsum("tkd,kd->td", taps, w["kda_conv"]))
        q, k, v = (a.reshape(t, heads, -1) for a in jnp.split(x, 3, axis=1))
        d = q.shape[-1]

        def l2(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q = l2(q) * d ** -0.5
        if mutant != "no_k_l2norm":
            k = l2(k)
        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(w["kda_a_log"])[:, None]
            * (u @ w["kda_f"] + w["kda_dt_bias"]).reshape(t, heads, d))
        if mutant == "decay_off":
            g = jnp.zeros_like(g)
        b = jax.nn.sigmoid(u @ w["kda_b"])
        if mutant == "beta_one":
            b = jnp.ones_like(b)

        def token(s, step):
            q_t, k_t, v_t, g_t, b_t = step
            # diag(a) S as S - (1 - a) S: a slow channel's a = exp(g)
            # rounds to the same float32 short of 1 token after token,
            # and thousands of tokens add that rounding up (5e-8 a
            # token: my chip run, PR 54); the small loss is exact
            s = s + jnp.expm1(g_t)[:, :, None] * s
            answered = jnp.einsum("hk,hkv->hv", k_t, s)
            s = s + b_t[:, None, None] * k_t[:, :, None] \
                * (v_t - answered)[:, None, :]
            return s, jnp.einsum("hk,hkv->hv", q_t, s)

        state, o = jax.lax.scan(token, state, (q, k, v, g, b))
        o = head_gate(w, u, rms(o, w["kda_norm"], eps), mutant)
        return o.reshape(t, -1) @ w["o"], window[t:], state


def kda_layer(w, u, config, mutant=None, cut=None, state=None):
    """The mixer over a whole sequence ``u [T, hidden]`` in blocks, from
    ``state`` (zero unless given). ``cut``: where the prompt ends.
    Returns ``(out, the state the last block left)``. The last block is
    filled up with zero rows behind the sequence (one compiled block for
    every length; nothing before them sees them)."""
    heads = config["num_attention_heads"]
    w = {k: v for k, v in w.items() if k in _KDA_KEYS}
    taps, width = w["kda_conv"].shape
    d = width // 3 // heads
    t_real = len(u)
    u = jnp.concatenate([jnp.asarray(u, jnp.float32), jnp.zeros(
        (-t_real % TOKEN_BLOCK, u.shape[1]), jnp.float32)])
    tail = jnp.zeros((taps - 1, width), jnp.float32)
    if state is None:
        state = jnp.zeros((heads, d, d), jnp.float32)
    outs = []
    for at in range(0, len(u), TOKEN_BLOCK):
        before = np.arange(at - (taps - 1), at + TOKEN_BLOCK) < (cut or 0)
        out, tail, state = kda_block(
            w, u[at:at + TOKEN_BLOCK], tail, state, jnp.asarray(before),
            heads, float(config["kda_lower_bound"]),
            float(config["rms_norm_eps"]), mutant)
        outs.append(out)
    return jnp.concatenate(outs)[:t_real], state


# ---------------------------------------------------------------------------
# latent attention, expanded, with explicit scores
# ---------------------------------------------------------------------------

def rope(x, positions, theta):
    """Rotate ``x [T, ..., rope]`` in halves by ``positions [T]``."""
    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = _f32(positions)[:, None] * inv
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("key", "mutant"))
def mla_layer(w, u, key, mutant=None):
    """Causal latent attention over ``u [T, hidden]`` (normed), the
    queries in blocks of ``QUERY_BLOCK``. ``key``: the configuration's
    numbers, hashable (:func:`_key`)."""
    c = dict(key)
    nh, latent = c["num_attention_heads"], c["kv_lora_rank"]
    nope, dv, eps = c["qk_nope_head_dim"], c["v_head_dim"], c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        w = {k: _f32(v) for k, v in w.items() if k in _MLA_KEYS}
        t = u.shape[0]
        positions = jnp.arange(t)
        kv = u @ w["kv_a"]
        lat = rms(kv[:, :latent], w["kv_norm"], eps)
        k_r = rope(kv[:, latent:], positions, c["rope_theta"])
        q = rms((u @ w["q"]).reshape(t, nh, -1), w["q_norm"], eps)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, c["rope_theta"])],
            -1)
        kvh = (lat @ w["kv_b"]).reshape(t, nh, nope + dv)
        k = jnp.concatenate([kvh[..., :nope], jnp.broadcast_to(
            k_r[:, None], (t, nh, k_r.shape[-1]))], -1)
        v = kvh[..., nope:]
        scale = (nope + c["qk_rope_head_dim"]) ** -0.5
        pad = -t % QUERY_BLOCK

        def block(args):
            q_b, pos_b = args
            s = jnp.einsum("qhd,khd->hqk", q_b, k) * scale
            s = jnp.where(pos_b[None, :, None] >= positions[None, None, :],
                          s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        ctx = jax.lax.map(block, (
            jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
                -1, QUERY_BLOCK, nh, q.shape[-1]),
            jnp.pad(positions, (0, pad)).reshape(-1, QUERY_BLOCK)))
        ctx = ctx.reshape(-1, nh, dv)[:t]
        if c["attn_output_gate"]:
            ctx = head_gate(w, u, ctx, mutant)
        return ctx.reshape(t, nh * dv) @ w["o"]


# ---------------------------------------------------------------------------
# router, experts, a layer's feed-forward
# ---------------------------------------------------------------------------

def router(x, w_router, bias, c, mutant=None, forced=None):
    """``(experts [T, k], weights [T, k], scores [T, E], margin [T])``:
    the picks, their weights, what the selection compares (``s + bias``
    over ALL experts, the group limit not applied to it) and the smaller
    of two distances: between the last score picked and the first one
    left out among the experts that may be picked, and between the last
    group kept and the first one dropped. ``forced = (experts [T, k], rows [T]
    bool)`` replaces the picks on the marked rows; their weights are
    still this router's own scores at those picks."""
    k, groups = c["num_experts_per_tok"], c["n_group"]
    p = jax.nn.sigmoid(x @ _f32(w_router))
    scores = p + _f32(bias)
    allowed, group_margin = scores, jnp.inf
    if groups > 1 and mutant != "group_limit_ignored":
        grouped = scores.reshape(len(x), groups, -1)
        best_two, _ = jax.lax.top_k(grouped, 2)
        g_top, kept = jax.lax.top_k(jnp.sum(best_two, -1),
                                    c["topk_group"] + 1)
        group_margin = g_top[:, -2] - g_top[:, -1]
        keep = jnp.any(kept[:, :-1, None] == jnp.arange(groups), axis=1)
        allowed = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(
            scores.shape)
    top, experts = jax.lax.top_k(allowed, k + 1)
    margin = jnp.minimum(top[:, k - 1] - top[:, k], group_margin)
    experts = experts[:, :k].astype(jnp.int32)
    if forced is not None:
        experts = jnp.where(forced[1][:, None], forced[0], experts)
    picked = jnp.take_along_axis(p, experts, axis=-1)
    weights = c["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
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

    return jax.lax.fori_loop(0, w_gate_up.shape[0], body, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("key", "mutant"))
def ffn_block(w, x, forced, key, mutant=None):
    """``x [T, hidden]`` through the layer's norm and feed-forward:
    ``(x + y, what the router did or None)``."""
    c = dict(key)
    with jax.default_matmul_precision("highest"):
        h = rms(x, _f32(w["ffn_norm"]), c["rms_norm_eps"])
        if "mlp_gate_up" in w:
            return x + swiglu(h, _f32(w["mlp_gate_up"]),
                              _f32(w["mlp_down"])), None
        experts, weights, scores, margin = router(
            h, w["router"], w["router_bias"], c, mutant, forced)
        y = swiglu(h, _f32(w["shared_gate_up"]), _f32(w["shared_down"])) \
            + held_experts(h, experts, weights, w["experts_gate_up"],
                           w["experts_down"], c["experts_first"],
                           mutant == "all_8bit")
        return x + y, {"experts": experts, "scores": scores,
                       "margin": margin, "input": h}


def _key(config):
    """The configuration's numbers as a hashable (a jitted function's
    static argument)."""
    flat = {k: v for k, v in config.items()
            if isinstance(v, (int, float, str, bool))}
    flat["experts_first"] = config["deployment"]["experts_first"]
    flat["attn_output_gate"] = bool(config["assumed"]["attn_output_gate"])
    return tuple(sorted(flat.items()))


def layer_order(config, mutant=None):
    """The layers in the order they run."""
    order = list(range(config["num_hidden_layers"]))
    if mutant == "layer_pattern_shifted":
        at = config["layer_types"].index(MLA)
        order[at - 1], order[at] = order[at], order[at - 1]
    return order


def forward(weights, config, tokens, positions, mutant=None, forced=None,
            want_layer=None):
    """The whole forward over a 1-D token sequence, layer by layer.
    Returns ``(logits [len(positions), V] float32, layers)``; ``layers``
    holds, for each expert layer (in the published order), what its
    router did at ``positions``: ``experts [n, k]``, ``scores [n, E]``
    (``s + bias``), ``margin [n]`` and the layer's normed ``input [n,
    hidden]``. ``forced [n, expert layers, k]`` are picks to take at
    ``positions`` in place of the router's own (every other position
    runs free). With ``want_layer`` the third value is the NORMED input
    ``[T, hidden]`` of that layer's mixer."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    rows = np.asarray(positions, np.int64)
    k = config["num_experts_per_tok"]
    eps = config["rms_norm_eps"]
    key = _key(config)
    whole = mutant if mutant in WHOLE_MUTANTS + ("all_8bit",) else None
    dense = config["first_k_dense_replace"]
    marked = np.zeros(n, bool)
    marked[rows] = forced is not None
    wanted, layers = None, {}
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["lm_embed"][jnp.asarray(tokens)])
        head = _f32(weights["lm_head"])
        if mutant == "all_8bit":
            x, head = _round_8bit(x), _round_8bit(head)
        for i in layer_order(config, whole):
            w = layer_weights(weights, i, whole)
            u = rms(x, _f32(w["attn_norm"]), eps)
            if i == want_layer:
                wanted = np.asarray(u)
            if config["layer_types"][i] == KDA:
                mixed, _ = kda_layer(w, u, config, whole)
            else:
                mixed = mla_layer(w, u, key, whole)
            x = x + mixed
            picks = np.zeros((n, k), np.int32)
            if forced is not None and i >= dense:
                picks[rows] = np.asarray(forced)[:, i - dense]
            out, seen = [], []
            for at in range(0, n, TOKEN_BLOCK):
                part = slice(at, at + TOKEN_BLOCK)
                y, did = ffn_block(
                    w, x[part], (jnp.asarray(picks[part]),
                                 jnp.asarray(marked[part])), key, whole)
                out.append(y)
                seen.append(did)
            x = jnp.concatenate(out)
            if seen[0] is not None:
                layers[i] = {k_: np.concatenate(
                    [np.asarray(s[k_]) for s in seen])[rows]
                    for k_ in seen[0]}
        last = rms(x[jnp.asarray(rows)], _f32(weights["lm_norm"]), eps)
        logits = np.asarray(last @ head)
    layers = [layers[i] for i in sorted(layers)]
    if want_layer is None:
        return logits, layers
    return logits, layers, wanted


def logits_at(weights, config, tokens, positions, pad_to=None):
    del pad_to      # every length runs as it is: the blocks are padded
    return forward(weights, config, tokens, positions)[0]


def expert_layer_parts(weights, config, layer, x, mutant=None):
    """One expert layer's router and routed sum on given normed inputs
    ``x [n, hidden]`` (float32): ``(experts, weights, margin, routed [n,
    hidden])`` — what the program's own router and grouped matmul are
    held to on identical inputs."""
    w = layer_weights(weights, layer)
    c = dict(_key(config))
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        experts, wts, _, margin = router(x, w["router"], w["router_bias"],
                                         c, mutant)
        routed = held_experts(
            x, experts, wts, w["experts_gate_up"], w["experts_down"],
            c["experts_first"], mutant == "experts_8bit")
    return (np.asarray(experts), np.asarray(wts), np.asarray(margin),
            np.asarray(routed))


def uncut_expert_layer(x, w, config):
    """An expert layer's feed-forward with EVERY routed expert held
    (``w["experts_*"]`` as wide as the router) and the shared expert
    once, on normed rows ``x``: what the shares of an expert-parallel
    deployment add up to."""
    c = dict(dict(_key(config)), experts_first=0)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        experts, wts, _, _ = router(x, w["router"], w["router_bias"], c)
        return np.asarray(
            swiglu(x, _f32(w["shared_gate_up"]), _f32(w["shared_down"]))
            + held_experts(x, experts, wts, w["experts_gate_up"],
                           w["experts_down"], 0))
