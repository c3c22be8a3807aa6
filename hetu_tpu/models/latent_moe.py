"""Latent-attention mixture-of-experts decoders, on the serving path.

The family of DeepSeek-V2/V3-shaped models (``sarvam_mla`` is one):
RMS norm, multi-head LATENT attention (MLA: keys and values live as one
low-rank latent row a token plus one rotated key all heads share),
YaRN-scaled rotary position on part of each head, SwiGLU feed-forward,
a few leading dense layers and then expert layers (a sigmoid router
with a selection-only bias over all experts, ``top_k`` routed experts
and shared experts a token), untied head. Three things are
configuration, not forks:

* the QUERY is either one projection with an RMS norm over each head
  (``q_lora_rank=None, qk_norm=True``: ``sarvam_mla``) or a low-rank
  pair with an RMS norm over the rank between them and none a head
  (``q_lora_rank=r, qk_norm=False``: ``h q_a -> norm -> q_b``);
* the RESIDUAL is either one stream, ``x + F(norm(x))``, or
  (``hyper_connections``) ``n`` streams a token that every sublayer
  reads through one learned mix and writes back through two more
  (``ops/mhc.py``: manifold-constrained hyper-connections; the streams
  start as ``n`` copies of the embedding and are summed before the
  final norm). The stream lives inside a program: nothing of it is
  cached, so the cache, the scheduler and a replay know nothing of it;
* the MIXER of a layer is latent attention or (``layer_types[i] ==
  "kda"``) a delta-rule LINEAR attention with a per-channel decay
  (Kimi Delta Attention, ``ops/kda.py``): ``q``, ``k``, ``v`` through a
  short causal depthwise convolution and a SiLU, ``q`` and ``k``
  l2-normed a head, a bounded per-channel log-decay and a write
  strength a token, a MATRIX state ``[d_v, d_k]`` a head, an RMS norm a
  head and a sigmoid gate a head on the way out (``attn_output_gate``
  puts the same gate on the latent layers' context). Such a layer
  keeps no rows: a sequence carries its state and the convolutions'
  last ``K - 1`` inputs in a SLOT of the cache (``pool_kinds =
  ("state", "rows", ...)``: one entry of slots for every such layer,
  then the latent pools of the others), every program takes each row's
  slot behind its other arguments, and a prefill leaves the state AT
  THE PROMPT'S LAST REAL TOKEN (``models/ssm_hybrid.py`` has the same
  contract). The router may be GROUP-LIMITED (``n_group``,
  ``topk_group``: ``ops/moe.py:route``).

This module is the serving side only: ONE forward
(:func:`_serve_forward`) behind the same three cache backends as
``models/gpt.py`` — whole-prompt prefill (attention EXPANDED, over the
flash kernel), paged decode and suffix prefill (attention ABSORBED,
against the latent rows themselves) — plus
:class:`LatentMoEServingModel`, the object the engine takes it as
(``docs/serving.md``, "The serving-model interface"). Training these
blocks through ``ht.Executor`` is open (``ROADMAP.md``).

**One share of an expert-parallel deployment.** ``experts_held``
``(first, count)`` says which routed experts THIS program holds. The
router is as wide as the model (every token is scored against every
expert); the expert layer computes the held experts' part and the
shared expert's, and what the experts held elsewhere would add is left
out: on one chip the layer runs without its exchange. ``vocab_size``
is likewise the rows of the vocabulary held here.

Weights and activations are in ``dtype`` (bfloat16 as deployed); norm
statistics, router scores, softmax and logits are float32.
"""
from __future__ import annotations

import math

import numpy as np

from .decoder_parts import (feed_forward, pool_scatter, records, rms, rope,
                            token_chunks)

__all__ = ["LatentMoEConfig", "LatentMoEServingModel",
           "latent_moe_param_shapes", "latent_moe_serving_params",
           "latent_moe_forward", "latent_moe_paged_prefill",
           "latent_moe_paged_step", "latent_moe_paged_suffix_prefill",
           "yarn_inv_freq", "yarn_mscale", "COUNTERS", "MHC_COUNTERS",
           "KDA_COUNTERS", "MLA", "KDA", "kda_prefill", "kda_step"]

# what every program returns beside its tokens or logits, int32, in
# this order, followed by the rows of each held expert and then, for
# each row of the batch, its record (``decoder_parts.records``)
COUNTERS = ("moe_tokens", "moe_routed_rows", "moe_expert_visits",
            "mla_context_rows", "mla_score_pairs")
# ... and, of a model with hyper-connections, behind those: real tokens
# x sublayers (two a layer) whose streams the program mixed
MHC_COUNTERS = ("mhc_rows",)
_HC_KEYS = ("streams", "sinkhorn_iters", "eps", "clamp")
# ... and, of a model with delta-rule layers, behind those: real tokens x
# such layers; (row, chunk, layer) triples the chunk kernel walked
# (padding included: its own cost); (real token, expert layer, held
# group) triples in which the router picked inside the group
KDA_COUNTERS = ("kda_rows", "kda_chunks", "moe_group_kept")
# a layer's mixer
MLA, KDA = "mla", "kda"


class LatentMoEConfig:
    """Widths are the published ones; what a deployment cuts is depth
    (``num_hidden_layers``), the experts held (``experts_held``) and
    the vocabulary rows held (``vocab_size``)."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, num_routed_experts,
                 num_experts_per_tok, num_shared_experts=1,
                 first_k_dense_replace=1, experts_held=None,
                 routed_scaling_factor=1.0, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=131072, dtype="bfloat16",
                 q_lora_rank=None, qk_norm=True, hyper_connections=None,
                 layer_types=None, kda_head_dim=128, kda_conv_width=4,
                 kda_lower_bound=-5.0, attn_output_gate=False, n_group=1,
                 topk_group=1):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_routed_experts = num_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        first, count = experts_held or (0, num_routed_experts)
        if first < 0 or count < 1 or first + count > num_routed_experts:
            raise ValueError(
                f"experts_held {(first, count)} is not a range of the "
                f"{num_routed_experts} routed experts")
        self.experts_held = (int(first), int(count))
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype
        self.q_lora_rank = q_lora_rank
        self.qk_norm = bool(qk_norm)
        if hyper_connections is not None:
            if sorted(hyper_connections) != sorted(_HC_KEYS):
                raise ValueError(
                    f"hyper_connections takes {_HC_KEYS}, got "
                    f"{sorted(hyper_connections)}")
            hyper_connections = {
                "streams": int(hyper_connections["streams"]),
                "sinkhorn_iters": int(hyper_connections["sinkhorn_iters"]),
                "eps": float(hyper_connections["eps"]),
                "clamp": tuple(float(v)
                               for v in hyper_connections["clamp"])}
        self.hyper_connections = hyper_connections
        layer_types = tuple(layer_types or (MLA,) * num_hidden_layers)
        if len(layer_types) != num_hidden_layers \
                or set(layer_types) - {MLA, KDA}:
            raise ValueError(
                f"layer_types names {num_hidden_layers} layers as "
                f"{MLA!r} or {KDA!r}, got {layer_types}")
        if KDA in layer_types and hyper_connections:
            raise ValueError("delta-rule layers with hyper-connections "
                             "are not implemented")
        self.layer_types = layer_types
        self.kda_head_dim = int(kda_head_dim)
        self.kda_conv_width = int(kda_conv_width)
        self.kda_lower_bound = float(kda_lower_bound)
        self.attn_output_gate = bool(attn_output_gate)
        if num_routed_experts % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(
                f"{n_group} groups (the best {topk_group} kept) do not "
                f"divide {num_routed_experts} routed experts")
        self.n_group, self.topk_group = int(n_group), int(topk_group)

    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row_width(self):
        """Lanes of one cache row: the latent beside the rotated key,
        padded with zeros to whole 128-lane tiles (576 -> 640 at the
        published widths). A TPU tiles a row's minor dimension to 128
        lanes whatever the program says; asked for 576 it lays the pool
        out with the BLOCK index minor instead, and every program then
        copies each layer's whole pool into a row-major layout and
        back: 1.24 s of a 4 s window went to those copies (my chip
        run, PR 32)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def streams(self):
        """Residual streams a token: 1 without hyper-connections."""
        hc = self.hyper_connections
        return hc["streams"] if hc else 1

    def is_dense(self, layer):
        return layer < self.first_k_dense_replace

    def is_kda(self, layer):
        return self.layer_types[layer] == KDA

    @property
    def kda_layers(self):
        return self.layer_types.count(KDA)

    @property
    def kda_width(self):
        """Channels of ``q`` (``k``, ``v``) over all heads."""
        return self.num_attention_heads * self.kda_head_dim

    def serving_model(self):
        return LatentMoEServingModel(self)


# ---------------------------------------------------------------------------
# rotary position, YaRN-scaled (the deepseek_yarn convention)
# ---------------------------------------------------------------------------

def yarn_mscale(scale, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """``[dim / 2]`` inverse frequencies. Without scaling ``theta **
    (-2i / dim)``; with ``deepseek_yarn``, dimensions that turn more
    than ``beta_fast`` times over the original context keep theirs,
    those that turn less than ``beta_slow`` times are slowed by
    ``factor``, and a linear ramp blends between."""
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** exponent
    if not scaling:
        return extra
    inter = extra / scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(config):
    """``q_head_dim ** -0.5``, times ``mscale ** 2`` under YaRN with
    ``mscale_all_dim`` (the rotary tables' own factor is then
    ``mscale / mscale_all_dim``'s ratio, 1 for the published file)."""
    scale = config.q_head_dim ** -0.5
    s = config.rope_scaling
    if s and s.get("mscale_all_dim"):
        scale *= yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
    return scale


def _rope_tables(config, positions):
    """(cos, sin) ``[..., rope / 2]`` float32 for int32 ``positions``."""
    import jax.numpy as jnp
    inv = jnp.asarray(yarn_inv_freq(config.qk_rope_head_dim,
                                    config.rope_theta, config.rope_scaling),
                      jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv
    s = config.rope_scaling
    factor = 1.0 if not s else (
        yarn_mscale(s["factor"], s.get("mscale", 1.0))
        / yarn_mscale(s["factor"], s.get("mscale_all_dim", 0.0)))
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def latent_moe_param_shapes(config):
    """``{name: (shape, kind)}`` of every serving parameter; ``kind``
    is ``"matrix"`` (in the model's dtype), ``"norm"`` (float32 ones at
    initialisation), ``"router"`` or ``"router_bias"`` (float32), with
    hyper-connections each sublayer's ``"hc_phi"``, ``"hc_scale"`` and
    ``"hc_bias"`` (float32; ``ops/mhc.py:maps``) and, in a delta-rule
    layer, ``"conv"`` (the taps ``[K, 3 d]`` over ``q | k | v``),
    ``"dt_bias"`` and ``"a_log"`` (float32)."""
    c = config
    h, nh = c.hidden_size, c.num_attention_heads
    held = c.experts_held[1]
    out = {"lm_embed": ((c.vocab_size, h), "matrix"),
           "lm_norm": ((h,), "norm"),
           "lm_head": ((h, c.vocab_size), "matrix")}
    for i in range(c.num_hidden_layers):
        p = f"lm_h{i}_"
        out[p + "attn_norm"] = ((h,), "norm")
        if c.attn_output_gate or c.is_kda(i):
            out[p + "gate"] = ((h, nh), "matrix")
        if c.is_kda(i):
            d = c.kda_width
            out.update({
                # q, k and v side by side, as gate and up are
                p + "kda_qkv": ((h, 3 * d), "matrix"),
                p + "kda_conv": ((c.kda_conv_width, 3 * d), "conv"),
                p + "kda_f": ((h, d), "matrix"),
                p + "kda_dt_bias": ((d,), "dt_bias"),
                p + "kda_a_log": ((nh,), "a_log"),
                p + "kda_b": ((h, nh), "matrix"),
                p + "kda_norm": ((c.kda_head_dim,), "norm"),
                p + "o": ((d, h), "matrix"),
                p + "ffn_norm": ((h,), "norm")})
            _ffn_shapes(c, i, p, out)
            continue
        if c.q_lora_rank:
            out[p + "q_a"] = ((h, c.q_lora_rank), "matrix")
            out[p + "q_a_norm"] = ((c.q_lora_rank,), "norm")
            out[p + "q_b"] = ((c.q_lora_rank, nh * c.q_head_dim), "matrix")
        else:
            out[p + "q"] = ((h, nh * c.q_head_dim), "matrix")
        if c.qk_norm:
            out[p + "q_norm"] = ((c.q_head_dim,), "norm")
        if c.hyper_connections:
            from ..ops.mhc import map_width
            width = map_width(c.streams)
            for sub in ("attn", "ffn"):
                out[p + f"hc_{sub}_phi"] = ((c.streams * h, width), "hc_phi")
                out[p + f"hc_{sub}_scale"] = ((3,), "hc_scale")
                out[p + f"hc_{sub}_bias"] = ((width,), "hc_bias")
        out.update({
            p + "kv_a": ((h, c.kv_lora_rank + c.qk_rope_head_dim),
                         "matrix"),
            p + "kv_norm": ((c.kv_lora_rank,), "norm"),
            p + "kv_b": ((c.kv_lora_rank,
                          nh * (c.qk_nope_head_dim + c.v_head_dim)),
                         "matrix"),
            p + "o": ((nh * c.v_head_dim, h), "matrix"),
            p + "ffn_norm": ((h,), "norm")})
        _ffn_shapes(c, i, p, out)
    return out


def _ffn_shapes(c, i, p, out):
    """Layer ``i``'s feed-forward parameters into ``out``."""
    h, held = c.hidden_size, c.experts_held[1]
    if c.is_dense(i):
        out[p + "mlp_gate_up"] = ((h, 2 * c.intermediate_size), "matrix")
        out[p + "mlp_down"] = ((c.intermediate_size, h), "matrix")
        return
    shared = c.num_shared_experts * c.moe_intermediate_size
    out.update({
        p + "router": ((h, c.num_routed_experts), "router"),
        p + "router_bias": ((c.num_routed_experts,), "router_bias"),
        p + "shared_gate_up": ((h, 2 * shared), "matrix"),
        p + "shared_down": ((shared, h), "matrix"),
        p + "experts_gate_up": (
            (held, h, 2 * c.moe_intermediate_size), "matrix"),
        p + "experts_down": (
            (held, c.moe_intermediate_size, h), "matrix")})


def latent_moe_serving_params(config, lookup):
    """The serving block's parameter pytree from ``lookup(name)``.
    Matrices are taken in the model's dtype (an array that already has
    it is not copied), norms, the router and the hyper-connections'
    maps in float32. A sublayer's maps become one group ``hc_attn`` /
    ``hc_ffn`` (``{"phi", "scale", "bias"}`` and, where the kernels of
    ``ops/mhc.py`` run, ``"kernel"``: their prepared form)."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config.dtype)

    def get(name, kind):
        return jnp.asarray(lookup(name),
                           dtype if kind == "matrix" else jnp.float32)

    flat = {name: get(name, kind) for name, (_, kind)
            in latent_moe_param_shapes(config).items()}
    blocks = []
    for i in range(config.num_hidden_layers):
        p = f"lm_h{i}_"
        blk = {k[len(p):]: v for k, v in flat.items() if k.startswith(p)}
        if config.hyper_connections:
            from ..ops import mhc
            for sub in ("attn", "ffn"):
                maps = {k: blk.pop(f"hc_{sub}_{k}")
                        for k in ("phi", "scale", "bias")}
                if mhc.wants_prepared(config.streams, config.hidden_size,
                                      dtype):
                    maps["kernel"] = mhc.prepare(**maps)
                blk["hc_" + sub] = maps
        blocks.append(blk)
    return {"embed": flat["lm_embed"], "norm": flat["lm_norm"],
            "head": flat["lm_head"], "blocks": blocks}


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _serve_forward(params, config, x, positions, attend, valid, mix=None):
    """THE decoder stack, written once: embedded tokens ``x [..., H]``
    at int32 ``positions [...]`` through every layer and the final
    norm. The cache backend is ``attend(i, blk, q, row)``: layer
    ``i``'s queries ``[..., heads, nope + rope]`` (normed, rotated) and
    cache rows ``[..., row width]`` (``[c ; k_r ; 0]``) arrive, it
    writes the rows wherever its cache lives and returns the context
    ``[..., heads, v]``; a delta-rule layer's is ``mix(j, blk, h)``:
    the ``j``-th such layer's normed input arrives, it moves the state
    wherever that lives and returns the mixer's output ``[..., H]``.
    ``valid [...]`` marks real tokens: a padded
    one is routed to no expert and counted nowhere. Returns ``(hidden
    states, each expert layer's picks [..., expert layers, k], rows by
    held expert [held], held experts visited)``."""
    import jax.numpy as jnp
    c = config
    lead = x.shape[:-1]
    nh, latent, nope = c.num_attention_heads, c.kv_lora_rank, \
        c.qk_nope_head_dim
    cos, sin = _rope_tables(c, positions)
    pad = c.cache_row_width - latent - c.qk_rope_head_dim
    flat_valid = valid.reshape(-1)
    read, write, leave = _residual(c, lead)
    if c.hyper_connections:     # n copies of the embedding, token-major
        x = jnp.broadcast_to(x.reshape(-1, 1, x.shape[-1]),
                             (flat_valid.shape[0], c.streams, x.shape[-1]))
    rows_total = jnp.zeros(c.experts_held[1], jnp.int32)
    visits = jnp.int32(0)
    picks = []
    for i, blk in enumerate(params["blocks"]):
        if c.is_kda(i):
            h = rms(x, blk["attn_norm"], c.rms_norm_eps)
            x = x + mix(c.layer_types[:i].count(KDA), blk, h)
            x, rows_total, visits = _ffn(c, i, blk, x, flat_valid, read,
                                         write, picks, rows_total, visits)
            continue
        h, carry = read(x, blk, "hc_attn")
        h = rms(h, blk["attn_norm"], c.rms_norm_eps)
        kv = h @ blk["kv_a"]
        row = jnp.concatenate(
            [rms(kv[..., :latent], blk["kv_norm"], c.rms_norm_eps),
             rope(kv[..., latent:], cos, sin),
             jnp.zeros((*lead, pad), kv.dtype)], axis=-1)
        if "q_a" in blk:
            q = rms(h @ blk["q_a"], blk["q_a_norm"], c.rms_norm_eps) \
                @ blk["q_b"]
        else:
            q = h @ blk["q"]
        q = q.reshape(*lead, nh, c.q_head_dim)
        if "q_norm" in blk:
            q = rms(q, blk["q_norm"], c.rms_norm_eps)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], cos[..., None, :],
                                  sin[..., None, :])], axis=-1)
        ctx = attend(i, blk, q, row).astype(x.dtype)
        if "gate" in blk:
            ctx = _head_gated(blk, h, ctx)
        x = write(x, ctx.reshape(*lead, nh * c.v_head_dim) @ blk["o"],
                  carry)
        x, rows_total, visits = _ffn(c, i, blk, x, flat_valid, read, write,
                                     picks, rows_total, visits)
    picks = jnp.stack(picks, axis=-2) if picks else jnp.zeros(
        (*lead, 0, c.num_experts_per_tok), jnp.int32)
    return (rms(leave(x), params["norm"], c.rms_norm_eps), picks,
            rows_total, visits)


def _ffn(config, i, blk, x, flat_valid, read, write, picks, rows_total,
         visits):
    """Layer ``i``'s feed-forward sublayer round the residual ``x``;
    an expert layer's picks are appended to ``picks``. Returns ``(x,
    rows by held expert so far, held experts visited so far)``."""
    c = config
    h, carry = read(x, blk, "hc_ffn")
    h = rms(h, blk["ffn_norm"], c.rms_norm_eps)
    y, picked, (rows, seen) = token_chunks(
        lambda xc, vc, blk=blk: feed_forward(c, blk, xc, vc),
        h.reshape(-1, h.shape[-1]), flat_valid)
    x = write(x, y.reshape(h.shape), carry)
    rows_total, visits = rows_total + rows, visits + seen
    if not c.is_dense(i):
        picks.append(picked.reshape(*h.shape[:-1], -1))
    return x, rows_total, visits


def _head_gated(blk, h, heads):
    """``heads [..., heads, d]`` times the sigmoid gate a HEAD of the
    sublayer's normed input ``h`` (float32, back to ``heads``' dtype)."""
    import jax
    import jax.numpy as jnp
    gate = jax.nn.sigmoid(jnp.dot(h, blk["gate"],
                                  preferred_element_type=jnp.float32))
    return (heads.astype(jnp.float32) * gate[..., None]).astype(heads.dtype)


def _residual(config, lead):
    """The residual path around a sublayer, as three functions:
    ``read(x, blk, name) -> (what the sublayer reads [*lead, H],
    carry)``, ``write(x, y [*lead, H], carry) -> x'`` and ``leave(x) ->
    [*lead, H]`` before the final norm. One stream: ``x`` itself, ``x +
    y``, ``x``. Hyper-connections: ``x`` is ``[tokens, n, H]``, read
    and written through ``blk[name]``'s maps (``ops/mhc.py``), and the
    streams are summed (in float32) on the way out."""
    hc = config.hyper_connections
    if not hc:
        return (lambda x, blk, name: (x, None),
                lambda x, y, carry: x + y,
                lambda x: x)
    import jax.numpy as jnp
    from ..ops import mhc

    def read(x, blk, name):
        u, carry = mhc.mhc_pre(x, blk[name], hc["sinkhorn_iters"],
                               hc["eps"], hc["clamp"])
        return u.reshape(*lead, u.shape[-1]), carry

    def write(x, y, carry):
        return mhc.mhc_post(x, y.reshape(-1, y.shape[-1]), carry)

    def leave(x):
        return jnp.sum(x.astype(jnp.float32), axis=1).astype(
            x.dtype).reshape(*lead, x.shape[-1])

    return read, write, leave


# ---------------------------------------------------------------------------
# the delta-rule mixer
# ---------------------------------------------------------------------------

def _kda_inputs(config, blk, h, window):
    """What the recurrence takes, float32, of the normed rows ``h [...,
    T, H]`` and the convolution's window ``[..., K - 1 + T, 3 d]`` (the
    ``K - 1`` rows before them, then their own ``h W_qkv``): ``(q, k, v,
    g [..., T, heads, 128], beta [..., T, heads])``."""
    import jax
    import jax.numpy as jnp
    c = config
    nh, hd, taps = c.num_attention_heads, c.kda_head_dim, c.kda_conv_width
    t = window.shape[-2] - taps + 1
    acc = 0.0
    for j in range(taps):
        acc = acc + blk["kda_conv"][j] * jax.lax.slice_in_dim(
            window, j, j + t, axis=-2).astype(jnp.float32)
    q, k, v = (a.reshape(*a.shape[:-1], nh, hd) for a in jnp.split(
        jax.nn.silu(acc), 3, axis=-1))

    def l2(a):
        return a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    rate = jnp.exp(blk["kda_a_log"])[:, None]
    f = jnp.dot(h, blk["kda_f"], preferred_element_type=jnp.float32) \
        + blk["kda_dt_bias"]
    g = c.kda_lower_bound * jax.nn.sigmoid(
        rate * f.reshape(*f.shape[:-1], nh, hd))
    beta = jax.nn.sigmoid(jnp.dot(h, blk["kda_b"],
                                  preferred_element_type=jnp.float32))
    return l2(q) * hd ** -0.5, l2(k), v, g, beta


def _kda_out(config, blk, h, o):
    """The recurrence's output ``o [..., heads, 128]`` (float32) through
    the RMS norm a head, the gate a head and the output projection."""
    o = _head_gated(blk, h, rms(o, blk["kda_norm"], config.rms_norm_eps))
    return o.reshape(*o.shape[:-2], -1).astype(h.dtype) @ blk["o"]


# tokens (over a program's rows) one pass of a delta-rule mixer holds:
# its temporaries are a segment's whatever the prompt bucket
KDA_SEGMENT_TOKENS = 4096


def kda_prefill(config, blk, h, tail, s0, lengths):
    """The mixer over normed rows ``h [B, T, H]`` from the tail ``[B, K
    - 1, 3 d]`` and the state ``s0 [B, heads, 128, 128]`` each row
    starts with; ``lengths [B]`` real tokens a row. Returns ``(out [B,
    T, H], tail, S)``, the last two as of each row's last real token. A
    long bucket is walked a SEGMENT of tokens at a time (one pass after
    another, tail and state handed on), as ``token_chunks`` walks a
    feed-forward."""
    import jax
    import jax.numpy as jnp
    from ..ops.kda import CHUNK
    rows, t, hidden = h.shape
    seg = max(CHUNK, KDA_SEGMENT_TOKENS // rows)
    if t <= seg or t % seg:
        return _kda_segment(config, blk, h, tail, s0, lengths)

    def body(carry, step):
        h_seg, start = step
        out, tail, s = _kda_segment(config, blk, h_seg, *carry,
                                    jnp.clip(lengths - start, 0, seg))
        return (tail, s), out

    (tail, s), out = jax.lax.scan(
        body, (tail, s0.astype(jnp.float32)),
        (h.reshape(rows, t // seg, seg, hidden).transpose(1, 0, 2, 3),
         jnp.arange(t // seg, dtype=lengths.dtype) * seg))
    return out.transpose(1, 0, 2, 3).reshape(rows, t, hidden), tail, s


def _kda_segment(config, blk, h, tail, s0, lengths):
    import jax.numpy as jnp
    from ..ops.kda import kda_chunk
    window = jnp.concatenate(
        [tail, (h @ blk["kda_qkv"]).astype(tail.dtype)], axis=1)
    # the K - 1 rows that end at the last real token; none: the old tail
    at = lengths[:, None] + jnp.arange(tail.shape[1])[None, :]
    new_tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
    o, s = kda_chunk(*_kda_inputs(config, blk, h, window), s0, lengths,
                     min_log_decay=config.kda_lower_bound)
    return _kda_out(config, blk, h, o), new_tail, s


def kda_step(config, blk, h, tail, pool, slots, layer):
    """One token a row: ``h [B, H]``, ``tail [B, K - 1, 3 d]``, the
    state in ``pool [slots, layers, heads, 128, 128]`` at ``[slots [B],
    layer]``. Returns ``(out [B, H], tail, pool)``."""
    import jax.numpy as jnp
    from ..ops import kda
    window = jnp.concatenate(
        [tail, (h @ blk["kda_qkv"]).astype(tail.dtype)[:, None]], axis=1)
    q, k, v, g, beta = (a[:, 0] for a in _kda_inputs(
        config, blk, h[:, None], window))
    o, pool = kda.kda_step(pool, slots, layer, q, k, v, g, beta)
    return _kda_out(config, blk, h, o), window[:, 1:], pool


class _Slots:
    """The state entry of the pools as the three programs' delta-rule
    backends see it: each batch row's slot, read and written a layer at
    a time (``kda [slots, layers, heads, 128, 128]`` float32; ``conv
    [slots, layers x (K - 1), 3 d]``: a layer's tail rows one under the
    other)."""

    def __init__(self, config, entry, slots):
        self.entry, self.slots = entry, slots
        self.k = config.kda_conv_width - 1

    def _tail_rows(self, layer):
        import jax.numpy as jnp
        return (layer * self.k + jnp.arange(self.k))[None, :]

    def tails(self, layer, fresh=None):
        """``[B, K - 1, 3 d]``; zeros where ``fresh [B]`` says the row
        starts a sequence."""
        import jax.numpy as jnp
        tail = self.entry["conv"][self.slots[:, None], self._tail_rows(layer)]
        return tail if fresh is None else jnp.where(
            fresh[:, None, None], jnp.zeros_like(tail), tail)

    def state(self, layer, fresh):
        import jax.numpy as jnp
        return jnp.where(fresh[:, None, None, None], 0.0,
                         self.entry["kda"][self.slots, layer])

    def write(self, layer, tail, state=None, pool=None):
        """The layer's new tails and either its rows' new ``state`` or
        the whole ``pool`` a step updated in place."""
        self.entry = {
            "conv": self.entry["conv"].at[
                self.slots[:, None], self._tail_rows(layer)].set(tail),
            "kda": pool if state is None else
            self.entry["kda"].at[self.slots, layer].set(state)}


def _prefill_mix(config, slots, fresh, lengths):
    """``mix`` of a program that walks many tokens a row: the recurrence
    from zero where ``fresh [B]`` (a whole prompt: everywhere; the slot
    is then written and never read), else continued from the slot."""
    def mix(j, blk, h):
        out, tail, s = kda_prefill(config, blk, h, slots.tails(j, fresh),
                                   slots.state(j, fresh), lengths)
        slots.write(j, tail, state=s)
        return out

    return mix


def _split_pools(config, pools):
    """``(the state entry or None, the latent pools a layer with rows)``."""
    return (pools[0], pools[1:]) if config.kda_layers else (None, pools)


def _joined_pools(slots, rows):
    return rows if slots is None else [slots.entry] + rows


def _kv_b(config, blk):
    """The up-projection split: ``(W_k [latent, heads, nope], W_v
    [latent, heads, v])``."""
    c = config
    w = blk["kv_b"].reshape(c.kv_lora_rank, c.num_attention_heads,
                            c.qk_nope_head_dim + c.v_head_dim)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def _expanded(config, blk, q, row):
    """Causal attention among this call's tokens with per-head keys
    and values rebuilt from the latent (``q``, ``row`` ``[B, S, ...]``)."""
    import jax.numpy as jnp
    from ..ops.attention import mla_expanded_attention
    c = config
    latent = c.kv_lora_rank
    w_k, w_v = _kv_b(c, blk)
    k_n = jnp.einsum("bsl,lhd->bshd", row[..., :latent], w_k)
    v = jnp.einsum("bsl,lhd->bshd", row[..., :latent], w_v)
    k_r = jnp.broadcast_to(
        row[..., None, latent:latent + c.qk_rope_head_dim],
        (*k_n.shape[:-1], c.qk_rope_head_dim))
    return mla_expanded_attention(
        q, jnp.concatenate([k_n, k_r], axis=-1), v, softmax_scale(c))


def _absorb(config, blk, q):
    """``(q~ [..., heads, latent], q_r [..., heads, row - latent])``:
    the query through the key up-projection, and its rotated part
    padded with zeros as the cache row is."""
    import jax.numpy as jnp
    c = config
    w_k, _ = _kv_b(c, blk)
    nope = c.qk_nope_head_dim
    pad = c.cache_row_width - c.kv_lora_rank - c.qk_rope_head_dim
    return (jnp.einsum("...hd,lhd->...hl", q[..., :nope], w_k),
            jnp.pad(q[..., nope:], ((0, 0),) * (q.ndim - 1) + ((0, pad),)))


def _unabsorb(config, blk, ctx_latent, dtype):
    import jax.numpy as jnp
    _, w_v = _kv_b(config, blk)
    return jnp.einsum("...hl,lhd->...hd", ctx_latent.astype(dtype), w_v)


def _paged_attend(pools, write_slots, attention):
    """The block-paged backend: a latent layer's rows scatter into its
    pool (``pools`` holds one a layer WITH rows, in order) at
    ``write_slots``, then ``attention(blk, q, row, pool)`` reads the
    updated pool. Returns ``(attend, new_pools)``."""
    new_pools = []

    def attend(i, blk, q, row):
        pool = pool_scatter(pools[len(new_pools)]["c"], write_slots, row)
        new_pools.append({"c": pool})
        return attention(blk, q, row, pool)

    return attend, new_pools


def _counters(config, valid, rows, visits, context_rows, score_pairs,
              records, picks=None, chunk_tokens=0):
    """The int32 vector every program returns (``COUNTERS``, with
    hyper-connections ``MHC_COUNTERS``, then the rows of each held
    expert, of the REAL tokens of this call; then each batch row's
    record, row after row)."""
    import jax.numpy as jnp
    c = config
    moe_layers = c.num_hidden_layers - c.first_k_dense_replace
    latent_layers = c.num_hidden_layers - c.kda_layers
    head = [
        jnp.sum(valid).astype(jnp.int32) * moe_layers,
        jnp.sum(rows), visits,
        context_rows.astype(jnp.int32) * latent_layers,
        score_pairs.astype(jnp.int32) * latent_layers]
    if c.hyper_connections:     # MHC_COUNTERS
        head.append(jnp.sum(valid).astype(jnp.int32)
                    * 2 * c.num_hidden_layers)
    if c.kda_layers:            # KDA_COUNTERS
        from ..ops.kda import CHUNK
        first, held = c.experts_held
        size = c.num_routed_experts // c.n_group
        groups = range(first // size, -(-(first + held) // size))
        # picks [..., expert layers, k] of every token of the call
        kept = sum(jnp.sum(jnp.any(picks // size == g, axis=-1)
                           & valid[..., None]) for g in groups)
        head += [jnp.sum(valid).astype(jnp.int32) * c.kda_layers,
                 jnp.int32(-(-chunk_tokens // CHUNK) * valid.shape[0]
                           * c.kda_layers if chunk_tokens else 0),
                 kept.astype(jnp.int32)]
    head = jnp.stack(head)
    return jnp.concatenate(
        [head, rows, records.reshape(-1)]).astype(jnp.int32)


def _logits(params, x):
    import jax.numpy as jnp
    return jnp.dot(x, params["head"], preferred_element_type=jnp.float32)


def latent_moe_forward(params, ids, config):
    """Plain causal forward over ``ids [B, S]`` with no cache: the
    serving block with an ``attend`` that writes nothing. Returns
    float32 logits ``[B, S, V]``."""
    import jax.numpy as jnp
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x, _, _, _ = _serve_forward(
        params, config, params["embed"][ids], positions,
        lambda i, blk, q, row: _expanded(config, blk, q, row),
        jnp.ones(ids.shape, bool))
    return _logits(params, x)


def latent_moe_paged_prefill(params, pools, ids, slot_idx, last_pos,
                             state_slots=None, *, config):
    """Prompt phase over a block-paged latent pool: causal forward over
    ``ids [B, P]`` that scatters every position's ``[c ; k_r]`` row
    into ``slot_idx [B, P]`` (padded rows and positions point at the
    scratch block) and attends expanded, over the flash kernel.
    ``last_pos [B]`` is each prompt's last real position: the program
    takes that row alone through the head, so ``[B, V]`` float32 logits
    leave it and never ``[B, P, V]``. A model with delta-rule layers
    also takes ``state_slots [B]``: each row's state and tails, AS OF
    ITS LAST REAL TOKEN, are left in that slot (padded rows: the scratch
    slot 0), written from a zero state and never read. Returns
    ``((logits, counters), pools)``; jit with ``pools`` donated."""
    import jax.numpy as jnp
    c = config
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    state, pools = _split_pools(c, pools)
    valid = slot_idx >= pools[0]["c"].shape[1]      # off the scratch block
    attend, new_pools = _paged_attend(
        pools, slot_idx,
        lambda blk, q, row, pool: _expanded(config, blk, q, row))
    slots = mix = None
    if state is not None:
        slots = _Slots(c, state, state_slots)
        mix = _prefill_mix(c, slots, jnp.ones(ids.shape[0], bool),
                           jnp.sum(valid, axis=1).astype(jnp.int32))
    x, all_picks, rows, visits = _serve_forward(
        params, config, params["embed"][ids], positions, attend, valid, mix)
    at = last_pos.astype(jnp.int32)
    last = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
    picks = jnp.take_along_axis(all_picks, at[:, None, None, None],
                                axis=1)[:, 0]
    lengths = jnp.sum(valid, axis=1)
    logits = _logits(params, last)
    counters = _counters(config, valid, rows, visits, jnp.sum(lengths),
                         jnp.sum(lengths * (lengths + 1) // 2),
                         records(picks, logits), all_picks, ids.shape[1])
    return (logits, counters), _joined_pools(slots, new_pools)


def latent_moe_paged_step(params, pools, tokens, positions, slot_idx,
                          write_slots, state_slots=None, *, config,
                          pick=None):
    """Paged single-token forward for a RAGGED batch, as
    ``models/gpt.py:gpt_paged_step`` is: ``tokens [B]`` each at its own
    ``positions [B]``, rows written to ``write_slots [B]``, attention
    ABSORBED against the rows gathered through ``slot_idx [B, S]``.
    A model with delta-rule layers also takes ``state_slots [B]``: each
    row's state and tails are updated in that slot, in place.
    With ``pick="greedy"`` returns ``(int32 [B + n]: the argmax of each
    lane's float32 logits, then the counters; pools)`` — one vector,
    one host sync; with ``pick=None`` ``((logits [B, V], counters),
    pools)``."""
    import jax.numpy as jnp
    from ..ops.attention import mla_decode_attention
    if pick not in (None, "greedy"):
        raise ValueError(f"pick must be None or 'greedy', got {pick!r}")
    scale = softmax_scale(config)
    dtype = params["embed"].dtype
    state, pools = _split_pools(config, pools)
    valid = write_slots >= pools[0]["c"].shape[1]

    def attention(blk, q, row, pool):
        q_abs, q_rope = _absorb(config, blk, q)
        ctx = mla_decode_attention(q_abs, q_rope, pool, slot_idx,
                                   positions, scale)
        return _unabsorb(config, blk, ctx, dtype)

    attend, new_pools = _paged_attend(pools, write_slots, attention)
    slots = mix = None
    if state is not None:
        slots = _Slots(config, state, state_slots)

        def mix(j, blk, h):
            out, tail, pool = kda_step(config, blk, h, slots.tails(j),
                                       slots.entry["kda"], state_slots, j)
            slots.write(j, tail, pool=pool)
            return out

    x, picks, rows, visits = _serve_forward(
        params, config, params["embed"][tokens], positions, attend, valid,
        mix)
    context = jnp.sum(jnp.where(valid, positions + 1, 0))
    logits = _logits(params, x)
    counters = _counters(config, valid, rows, visits, context, context,
                         records(picks, logits), picks)
    new_pools = _joined_pools(slots, new_pools)
    if pick == "greedy":
        return jnp.concatenate(
            [jnp.argmax(logits, axis=-1).astype(jnp.int32),
             counters]), new_pools
    return (logits, counters), new_pools


def latent_moe_paged_suffix_prefill(params, pools, ids, starts, slot_idx,
                                    write_slots, state_slots=None,
                                    lengths=None, *, config):
    """A CHUNK of prompt positions into an existing block table, as
    ``models/gpt.py:gpt_paged_suffix_prefill`` is (chunked prefill, and
    the suffix behind a prefix-cache hit): ``ids [B, C]`` from token
    offset ``starts [B]``, attention ABSORBED over the whole history
    gathered through ``slot_idx [B, S]``. Returns ``((logits [B, C, V],
    counters), pools)``. A model with delta-rule layers also takes
    ``state_slots [B]`` and the count of real tokens a row ``lengths
    [B]`` (``ids`` right-padded): the recurrence is CONTINUED from the
    slot's state and tails (from zero where ``starts`` is 0), and each
    row's last real position alone goes through the head: ``logits [B,
    V]``."""
    import jax.numpy as jnp
    from ..ops.attention import mla_prefill_attention
    scale = softmax_scale(config)
    dtype = params["embed"].dtype
    positions = starts[:, None] + jnp.arange(ids.shape[1])[None, :]
    state, pools = _split_pools(config, pools)
    valid = write_slots >= pools[0]["c"].shape[1]

    def attention(blk, q, row, pool):
        q_abs, q_rope = _absorb(config, blk, q)
        ctx = mla_prefill_attention(q_abs, q_rope, pool, slot_idx, starts,
                                    scale)
        return _unabsorb(config, blk, ctx, dtype)

    attend, new_pools = _paged_attend(pools, write_slots, attention)
    slots = mix = None
    if state is not None:
        slots = _Slots(config, state, state_slots)
        lengths = lengths.astype(jnp.int32)
        mix = _prefill_mix(config, slots, starts == 0, lengths)
    x, picks, rows, visits = _serve_forward(
        params, config, params["embed"][ids], positions, attend, valid, mix)
    context = jnp.sum(jnp.where(valid, positions + 1, 0))
    if state is not None:
        at = jnp.maximum(lengths - 1, 0)
        logits = _logits(params, jnp.take_along_axis(
            x, at[:, None, None], axis=1)[:, 0])
        counters = _counters(
            config, valid, rows, visits, context, context, records(
                jnp.take_along_axis(picks, at[:, None, None, None],
                                    axis=1)[:, 0], logits),
            picks, ids.shape[1])
        return (logits, counters), _joined_pools(slots, new_pools)
    logits = _logits(params, x)
    # a row's record is of its last real position in this chunk
    at = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0).astype(jnp.int32)
    counters = _counters(
        config, valid, rows, visits, context, context, records(
            jnp.take_along_axis(picks, at[:, None, None, None],
                                axis=1)[:, 0],
            jnp.take_along_axis(logits, at[:, None, None], axis=1)[:, 0]))
    return (logits, counters), new_pools


# ---------------------------------------------------------------------------
# what the engine takes the model as
# ---------------------------------------------------------------------------

class LatentMoEServingModel:
    """The serving-model interface (``docs/serving.md``) for a
    :class:`LatentMoEConfig`."""

    # the prefill program takes each row's last position and returns
    # [B, V] logits ([8, 8192, 65536] float32 would be 17 GB)
    prefill_last_row = True

    def __init__(self, config):
        self.config = config
        self.vocab_size = config.vocab_size
        self.max_positions = config.max_position_embeddings
        self.num_cache_layers = config.num_hidden_layers
        if config.kda_layers:
            # the cache's entries: ONE of slots for every delta-rule
            # layer's state, then the latent pool of each other layer
            self.pool_kinds = ("state",) + ("rows",) * (
                config.num_hidden_layers - config.kda_layers)
        self.counter_names = COUNTERS + (
            MHC_COUNTERS if config.hyper_connections else ()) + (
            KDA_COUNTERS if config.kda_layers else ())
        self.vector_counter = ("moe_rows_by_expert",
                               config.experts_held[1])
        # int32 words a batch row's record takes behind the counters
        self._moe_layers = config.num_hidden_layers \
            - config.first_k_dense_replace
        self.row_record_width = \
            self._moe_layers * config.num_experts_per_tok + 1

    def read_records(self, records):
        """``Future.token_records [n, width]`` taken apart:
        ``{"router_picks": [n, expert layers, k] int32, "best_logit":
        [n] float32}`` of the row that decided each generated token."""
        records = np.ascontiguousarray(records, np.int32)
        return {"router_picks": records[:, :-1].reshape(
                    len(records), self._moe_layers, -1),
                "best_logit": records[:, -1].view(np.float32)}

    def cache_layout(self):
        """One pool a layer: the latent row beside the rotated key, in
        whole 128-lane tiles (``LatentMoEConfig.cache_row_width``)."""
        return (("c", self.config.cache_row_width, self.config.dtype),)

    def state_layout(self):
        """What a slot holds: every delta-rule layer's state ``[layers,
        heads, d_v, d_k]`` float32 (value-major: ``ops/kda.py``) and
        the three convolutions' tails, ``K - 1`` rows a layer of ``q |
        k | v`` one under the other (a ``[layers, K - 1, 3 d]`` buffer
        would pad each layer's three rows to a sixteen-row tile)."""
        c = self.config
        return (("kda", (c.kda_layers, c.num_attention_heads,
                         c.kda_head_dim, c.kda_head_dim), "float32"),
                ("conv", (c.kda_layers * (c.kda_conv_width - 1),
                          3 * c.kda_width), c.dtype))

    def params(self, lookup):
        return latent_moe_serving_params(self.config, lookup)

    @property
    def _itemsize(self):
        import jax.numpy as jnp     # numpy alone does not know bfloat16
        return jnp.dtype(self.config.dtype).itemsize

    def param_bytes(self):
        """Matrices in the model's dtype; norms, the router and the
        hyper-connections' maps float32, and beside each sublayer's
        maps what the kernels hold of them prepared
        (``ops/mhc.py:prepare``)."""
        c = self.config
        held = sum(
            int(np.prod(shape)) * (self._itemsize if kind == "matrix" else 4)
            for shape, kind in latent_moe_param_shapes(c).values())
        if c.hyper_connections:
            from ..ops import mhc
            if mhc.wants_prepared(c.streams, c.hidden_size, c.dtype):
                held += 2 * c.num_hidden_layers * mhc.prepared_bytes(
                    c.streams, c.hidden_size)
        return int(held)

    def prefill_bytes_per_token(self):
        """Bytes of temporaries one prompt token costs a prefill
        program at its widest point (with delta-rule layers the wider
        of theirs and this), the expanded attention: the
        queries, keys and padded values, each token-major and
        head-major, the up-projection's output and the context, six
        float32 rows of what a sublayer reads and writes (``hidden``
        wide whatever the residual path) and the residual state itself
        as it enters and leaves a sublayer: ``streams`` rows in the
        model's dtype each way (one stream: the two rows are among the
        six)."""
        c = self.config
        per_head = 7 * c.q_head_dim + c.qk_nope_head_dim + c.v_head_dim
        state = 2 * c.streams * c.hidden_size * self._itemsize \
            if c.hyper_connections else 0
        latent = (c.num_attention_heads * per_head * self._itemsize
                  + 6 * c.hidden_size * 4 + state)
        if not c.kda_layers:
            return latent
        # a delta-rule mixer holds a SEGMENT's temporaries whatever the
        # bucket (``KDA_SEGMENT_TOKENS``: 4,096 tokens x 184 KB = 0.75e9
        # at the published widths), so a token costs it the mixer's
        # output and the six float32 rows of a sublayer
        kda = 7 * c.hidden_size * 4
        return max(latent, kda)

    def program(self, kind):
        """``(function, static keywords)`` of one of the engine's four
        programs."""
        fn = {"prefill": latent_moe_paged_prefill,
              "decode": latent_moe_paged_step,
              "decode_logits": latent_moe_paged_step,
              "suffix_prefill": latent_moe_paged_suffix_prefill}[kind]
        static = {"config": self.config}
        if kind == "decode":
            static["pick"] = "greedy"
        return fn, static
