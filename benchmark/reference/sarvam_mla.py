"""Plain reference for the ``sarvam_mla`` family (latent attention,
routed and shared experts), one chip's share of an expert-parallel
deployment: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no cache, no kernel,
attention EXPANDED only (per-head keys and values rebuilt from the
latent, one head at a time), a dense loop over the held experts (every
token through every held expert, weighted by 0 where it was not
picked). It upcasts the SAME bfloat16-valued weights the engine holds,
one layer and one expert at a time, so it fits beside them on the chip.

The equations (``h`` is ``[T, hidden]``; ISSUE 32, Tentpole 2):

* attention: ``c = rms(h W_kva[:, :L])``, ``k_r = rope(h W_kva[:, L:])``
  (one rotated key a token, shared by all heads); ``q = rms_head(h
  W_q)`` per head of ``nope + rope``, ``q = [q_n ; rope(q_r)]``;
  ``[k_n ; v] = c W_kvb`` per head, ``k = [k_n ; k_r]``, ``o =
  softmax(q k^T s + causal) v``, out ``= o W_o``; ``s = (nope +
  rope)^-0.5 m^2``, ``m = 0.1 ln(factor) + 1`` (YaRN with
  ``mscale_all_dim``; the rotary's own factor is then 1).
* dense layer: ``h + attn(rms h)``, then ``+ swiglu(rms .)``.
* expert layer: ``p = sigmoid(x W_r)`` (float32, all experts wide),
  ``S = top_k(p + b)``, ``w_e = scale p_e / sum_{j in S} p_j``; out ``=
  shared(x) + sum_{e in S, e held} w_e expert_e(x)``.
* final RMS norm, head over the held rows of the vocabulary.

Departures from the source model, each shared with the program under
test so that both compute one function (``configs/sarvam-105b-ep4.json``
``assumed``): the router's scoring (sigmoid, selection-only bias,
normalised top-k times the scaling factor) and where ``use_qk_norm``
acts are inferences from the published keys; the rotation pairs
``(i, i + rope/2)`` where the source interleaves (a fixed permutation
of the projections' columns); experts held elsewhere add nothing.

Nothing here comes from the program under test: the rotary tables
and the softmax scale are written out below from the published YaRN
formulas (``yarn_inv_freq``, ``yarn_mscale``), and the module imports
nothing of ``hetu_tpu``.

**Forced routing.** Routing is discrete, so a reference that runs free
and an engine whose activations are bfloat16 part ways wherever two
scores nearly tie. ``forward(..., forced=picks)`` takes the experts
the ENGINE picked at the checked rows (weights are still the
reference's own: its scores at those picks, normalised and scaled) and
reports, beside the logits, each layer's selection scores there: how
far the engine's picks lie from the reference's own is then a reading
of its own, and the logits no longer carry the swap.

``MUTANTS`` are deliberate faults of this reference, for the run's
log: a check that cannot tell one of them from the engine is not a
check (``families/sarvam_mla.py`` shows each failing). ``all_8bit`` is
the lower-precision control: every matrix rounded to 8 bits, the
nearest precision under the bfloat16 the configuration states.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MUTANTS = ("expert_dropped", "top_k_minus_1", "bias_in_weights",
           "no_rope", "experts_8bit", "bf16_routing")
CONTROL = "all_8bit"
# the faults that are a property of the whole forward (the others are
# faults of one expert layer's router or routed sum)
_WHOLE = ("no_rope", CONTROL)


def yarn_mscale(factor, mscale):
    """YaRN's attention-temperature term: ``0.1 mscale ln(factor) + 1``
    (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """``[dim / 2]`` rotary inverse frequencies under ``deepseek_yarn``
    (the YaRN paper's NTK-by-parts): a pair that turns more than
    ``beta_fast`` times over the original context keeps ``theta ** (-2i
    / dim)``, one that turns fewer than ``beta_slow`` times is slowed
    by ``factor``, and a linear ramp over the pair index blends the two
    between the dimensions where those turn counts fall."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    original = scaling["original_max_position_embeddings"]

    def pair_that_turns(n):     # the (fractional) pair index
        return dim * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scaling["beta_slow"])), dim - 1)
    slowed = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / scaling["factor"] * slowed + plain * (1.0 - slowed)


def _f32(a):
    return a.astype(jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def rope(x, positions, config, off=False):
    """Rotate ``x [T, ..., rope]`` in halves by ``positions [T]``."""
    if off:
        return x
    inv = jnp.asarray(yarn_inv_freq(config["qk_rope_head_dim"],
                                    config["rope_theta"],
                                    config["rope_scaling"]), jnp.float32)
    s = config["rope_scaling"]
    factor = yarn_mscale(s["factor"], s["mscale"]) \
        / yarn_mscale(s["factor"], s["mscale_all_dim"])
    ang = _f32(positions)[:, None] * inv
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), -1)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(config):
    s = config["rope_scaling"]
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) \
        ** -0.5 * yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2


def attention(h, positions, w, config, no_rope=False):
    """Expanded causal attention over ``h [T, hidden]`` (normed)."""
    c = config
    nh, latent = c["num_attention_heads"], c["kv_lora_rank"]
    nope, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    t = h.shape[0]
    kv = h @ _f32(w["kv_a"])
    lat = rms(kv[:, :latent], w["kv_norm"], c["rms_norm_eps"])
    k_r = rope(kv[:, latent:], positions, c, off=no_rope)       # [T, r]
    q = rms((h @ _f32(w["q"])).reshape(t, nh, -1), w["q_norm"],
            c["rms_norm_eps"])
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], positions, c, off=no_rope)],
                        -1)
    kv_b = _f32(w["kv_b"]).reshape(latent, nh, nope + dv)
    causal = positions[:, None] >= positions[None, :]
    scale = softmax_scale(c)

    def head(args):
        q_h, w_h = args                     # [T, nope + r], [L, nope + dv]
        kvh = lat @ w_h
        k = jnp.concatenate([kvh[:, :nope], k_r], -1)
        s = jnp.where(causal, (q_h @ k.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ kvh[:, nope:]

    ctx = jax.lax.map(head, (q.transpose(1, 0, 2),
                             kv_b.transpose(1, 0, 2)))          # [nh, T, dv]
    return ctx.transpose(1, 0, 2).reshape(t, nh * dv) @ _f32(w["o"])


def swiglu(x, w_gate_up, w_down):
    h = x @ w_gate_up
    width = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :width]) * h[:, width:]) @ w_down


def router(x, w_router, bias, config, mutant=None, forced=None):
    """``(experts [T, k], weights [T, k], scores [T, E], margin [T])``:
    the picks, their weights, what the selection compares (``p + b``
    over ALL experts) and the distance between the last score picked
    and the first one left out. ``forced = (experts [T, k], rows [T]
    bool)`` replaces the picks on the marked rows; their weights are
    still this router's own scores at those picks."""
    k = config["num_experts_per_tok"]
    if mutant == "bf16_routing":
        p = _f32(jax.nn.sigmoid(
            (x.astype(jnp.bfloat16) @ w_router.astype(jnp.bfloat16))))
    else:
        p = jax.nn.sigmoid(x @ _f32(w_router))
    scores = p + _f32(bias)
    top, experts = jax.lax.top_k(scores, k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k].astype(jnp.int32)
    if forced is not None:
        experts = jnp.where(forced[1][:, None], forced[0], experts)
    picked = jnp.take_along_axis(
        scores if mutant == "bias_in_weights" else p, experts, axis=-1)
    if mutant == "top_k_minus_1":       # the weakest pick goes
        picked = jnp.where(
            picked == jnp.min(picked, axis=-1, keepdims=True), 0.0, picked)
    weights = config["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, weights, scores, margin


def _round_8bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def held_experts(x, experts, weights, w_gate_up, w_down, first,
                 mutant=None):
    """``sum over the held picks of weight * expert(x)``: a dense loop,
    every token through every held expert."""
    held = w_gate_up.shape[0]
    skip = -1
    if mutant == "expert_dropped":
        # the held expert the most rows picked
        counts = jnp.sum(experts[..., None] == first + jnp.arange(held),
                         axis=(0, 1))
        skip = jnp.argmax(counts)

    def body(e, acc):
        gu, dn = _f32(w_gate_up[e]), _f32(w_down[e])
        if mutant in ("experts_8bit", CONTROL):
            gu, dn = _round_8bit(gu), _round_8bit(dn)
        coef = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        coef = jnp.where(e == skip, 0.0, coef)
        return acc + coef[:, None] * swiglu(x, gu, dn)

    return jax.lax.fori_loop(0, held, body, jnp.zeros_like(x))


def _layer(x, positions, w, forced, config, mutant):
    c = config
    if mutant == CONTROL:       # every matrix in 8 bits; norms stay
        w = {k: _round_8bit(_f32(v)) if v.ndim == 2 and k != "router"
             else v for k, v in w.items()}
    x = x + attention(rms(x, w["attn_norm"], c["rms_norm_eps"]),
                      positions, w, c, no_rope=mutant == "no_rope")
    h = rms(x, w["ffn_norm"], c["rms_norm_eps"])
    if "mlp_gate_up" in w:
        return x + swiglu(h, _f32(w["mlp_gate_up"]),
                          _f32(w["mlp_down"])), None
    experts, weights, scores, margin = router(
        h, w["router"], w["router_bias"], c, mutant, forced)
    y = swiglu(h, _f32(w["shared_gate_up"]), _f32(w["shared_down"])) \
        + held_experts(h, experts, weights, w["experts_gate_up"],
                       w["experts_down"], c["deployment"]["experts_first"],
                       mutant)
    return x + y, {"experts": experts, "scores": scores, "margin": margin,
                   "input": h}


@functools.lru_cache(maxsize=None)
def _jitted_layer(config_key, mutant):
    config = dict(config_key[0], rope_scaling=dict(config_key[1]),
                  deployment=dict(config_key[2]))
    return jax.jit(lambda x, positions, w, forced: _layer(
        x, positions, w, forced, config, mutant))


def _config_key(config):
    flat = tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))
    return (flat, tuple(sorted(config["rope_scaling"].items())),
            tuple(sorted((k, v) for k, v in config["deployment"].items()
                         if isinstance(v, (int, float, str, bool)))))


def layer_weights(weights, i):
    p = f"lm_h{i}_"
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def forward(weights, config, tokens, positions, pad_to=None, mutant=None,
            forced=None):
    """The whole forward over a 1-D token sequence, layer by layer.
    Returns ``(logits [len(positions), V] float32, layers)``; ``layers``
    holds, for each expert layer, what its router did at ``positions``:
    ``experts [n, k]``, ``scores [n, E]`` (``p + b``), ``margin [n]``
    and the layer's normed ``input [n, hidden]``. ``forced [n, expert
    layers, k]`` are picks to take at ``positions`` in place of the
    router's own (every other position runs free). The sequence is
    padded to ``pad_to`` (causal attention keeps the padding out of the
    real positions)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros(max(pad_to or n, n), np.int32)
    ids[:n] = tokens
    rows = np.asarray(positions, np.int64)
    k = config["num_experts_per_tok"]
    marked = np.zeros(len(ids), bool)
    marked[rows] = forced is not None
    layer = _jitted_layer(_config_key(config),
                          mutant if mutant in _WHOLE else None)
    with jax.default_matmul_precision("highest"):
        if mutant == CONTROL:
            embed = _round_8bit(_f32(weights["lm_embed"][jnp.asarray(ids)]))
            head = _round_8bit(_f32(weights["lm_head"]))
        else:
            embed = _f32(weights["lm_embed"][jnp.asarray(ids)])
            head = _f32(weights["lm_head"])
        x = embed
        pos = jnp.arange(len(ids), dtype=jnp.int32)
        layers = []
        for i in range(config["num_hidden_layers"]):
            picks = np.zeros((len(ids), k), np.int32)
            if forced is not None and i >= config["first_k_dense_replace"]:
                picks[rows] = np.asarray(forced)[:, len(layers)]
            x, seen = layer(x, pos, layer_weights(weights, i),
                            (jnp.asarray(picks), jnp.asarray(marked)))
            if seen is not None:
                layers.append({k_: np.asarray(v[jnp.asarray(rows)])
                               for k_, v in seen.items()})
        last = rms(x[jnp.asarray(rows)], weights["lm_norm"],
                   config["rms_norm_eps"])
        logits = np.asarray(last @ head)
    return logits, layers


def logits_at(weights, config, tokens, positions, pad_to=None):
    return forward(weights, config, tokens, positions, pad_to)[0]


def expert_layer_parts(weights, config, layer, x, mutant=None):
    """One expert layer's router and routed sum on given normed inputs
    ``x [n, hidden]`` (float32): ``(experts, weights, margin, routed
    [n, hidden])`` — what the program's own router and grouped matmul
    are held to on identical inputs."""
    w = layer_weights(weights, layer)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        experts, wts, _, margin = router(x, w["router"], w["router_bias"],
                                         config, mutant)
        routed = held_experts(
            x, experts, wts, w["experts_gate_up"], w["experts_down"],
            config["deployment"]["experts_first"], mutant)
    return (np.asarray(experts), np.asarray(wts), np.asarray(margin),
            np.asarray(routed))
