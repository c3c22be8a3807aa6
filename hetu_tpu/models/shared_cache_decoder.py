"""A decoder-hybrid-decoder for serving: a SELF-decoder of state-space
and window-attention layers, ONE full-attention layer whose keys and
values are the only long cache, and a CROSS-decoder whose attention
layers have no key/value projection and read that one cache, beside
gated memory units that reuse the last state-space layer's scan output
(the ``phi4flash`` family's shape; SambaY, arXiv:2507.06607).

``L`` layers, ``half = L / 2``, ``x`` the residual, every norm a
LayerNorm with gain and bias, no positional encoding anywhere::

    x = x + mixer_i(ln(x));  x = x + swiglu(ln(x))

=====================  ================================================
layer ``i``            mixer
=====================  ================================================
even ``i <= half``     Mamba-1 as published (``models/ssm_hybrid.py``'s
                       mixer WITHOUT its norms on ``dt``, ``B``, ``C``);
                       layer ``half`` also hands on ``m_t = y_t``, its
                       scan's output BEFORE the gate
odd ``i < half``       differential attention over the band
                       ``t - window < j <= t``
``i = half + 1``       differential attention over every ``j <= t``;
                       its ``k`` / ``v`` rows are THE cache of the
                       layers behind it
even ``i > half + 1``  gated memory unit: ``(silu(a W_g) * m_t) W_u``,
                       no state, no cache
odd ``i > half + 1``   differential CROSS-attention: a query alone,
                       against layer ``half + 1``'s rows
=====================  ================================================

Differential attention (``ops/attention.py``): heads in neighbouring
pairs, two float32 softmax maps over one value ``2 x head_dim`` wide,
their difference under the layer's ``lambda = exp(lq1 . lk1) - exp(lq2 .
lk2) + lambda_init(i)``, ``lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)``, an
RMS norm a pair, times ``1 - lambda_init(i)``. ``lambda`` depends on
the parameters alone: it is computed when they are built.

**What a sequence carries between programs**: one state SLOT (every
Mamba layer's ``S [N, d]`` float32 and convolution tail, as
``models/ssm_hybrid.py`` lays them), a RING of the last ``window`` rows
in each window layer's own pool (as ``models/window_moe.py``), and rows
in ONE paged pool, ``k`` and ``v`` of the key/value heads a token:
``pool_kinds = ("state", "window" x (half / 2), "rows")``. ``m_t`` lives
inside a program.

**A prefill runs the layers behind the one cache on ONE row a prompt.**
Nothing behind layer ``half + 1``'s keys and values is read at any
prompt position but the last (its queries, the gated memory units and
the cross-attention all feed the same position's residual alone), so
the self-decoder and that layer's ``k`` / ``v`` projection run over the
whole (right-padded) prompt, and that layer's query, attention and
feed-forward and the whole cross-decoder on row ``last_pos``. Exact,
not an approximation; ``cross_rows`` / ``self_rows`` count it.

**A decode step brings the shared rows into position order once**
(after the full layer's new row is written;
``ops/attention.py:gather_rows_once``: on a TPU one DMA a block up to
each sequence's own extent, ``hetu_block_gather``) and the eight reading
layers attend over that one copy; a pair's two maps read ``k`` and
``U`` once (``ops/attention.py:diff_rows_attention``; on a TPU the
kernel ``hetu_diff_attn_decode``, which reads each sequence's rows to
its own position, not to the bucket's end). The window layers read
their rings, brought by one call for all of them before the layers'
loop, the step's own row put in.

**Equal pairs are a loop**: the (Mamba, window) pairs and the (gate,
cross) pairs are held stacked and walked with ``lax.scan``, so a
program compiles two pair bodies and the two layers between them.

Matrices and activations are in ``dtype`` (bfloat16); the state,
``delta``, the convolution, every norm's statistics, ``lambda``, both
softmaxes, the difference and logits float32. Embedding and head are
tied.
"""
from __future__ import annotations

import math

import numpy as np

from .ssm_hybrid import (_gated_out, _tails, _write_kv, _write_tails,
                         scan_prefill, scan_step)

__all__ = ["SharedCacheConfig", "SharedCacheServingModel",
           "shared_cache_param_shapes", "layer_params", "lambda_init",
           "diff_attention_rows", "gated_memory", "COUNTERS", "KINDS"]

MAMBA, WINDOW, FULL, GATE, CROSS = KINDS = (
    "mamba", "window", "full", "gate", "cross")

# int32 counters every program returns: (real token, Mamba layer)
# pairs; rows attended inside the window x window layers; shared rows
# read x the layers that read them (the full layer and every cross
# layer: a decode row at position t counts readers x (t + 1)); rows the
# cross-decoder processed (a prefill: one a real prompt); real tokens
# through the self-decoder
COUNTERS = ("ssm_rows", "attn_window_rows", "attn_full_rows", "cross_rows",
            "self_rows")

CROSS_DECODER_NAME = "hetu_cross_decoder"
DIFF_DECODE_NAME = "hetu_diff_attn_decode"


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class SharedCacheConfig:
    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads,
                 intermediate_size, sliding_window, mb_per_layer=2,
                 head_dim=None, ssm_state_size=16, ssm_conv_width=4,
                 ssm_dt_rank=None, ssm_expand=2, layer_norm_eps=1e-5,
                 max_position_embeddings=262144, dtype="bfloat16"):
        if mb_per_layer != 2:
            raise ValueError(
                f"mb_per_layer {mb_per_layer}: only a Mamba layer every "
                "second layer of the self-decoder is implemented")
        if num_hidden_layers % 4 or num_hidden_layers < 8:
            raise ValueError(
                f"{num_hidden_layers} layers: the layer rule wants a "
                "multiple of 4, at least 8 (whole pairs on both sides of "
                "the one full-attention layer)")
        pairs, key_pairs = num_attention_heads // 2, num_key_value_heads // 2
        if (num_attention_heads % 2 or num_key_value_heads % 2
                or pairs % key_pairs):
            raise ValueError(
                f"{num_attention_heads} query and {num_key_value_heads} "
                "key/value heads do not pair: differential attention "
                "wants both even and the key pairs to divide the pairs")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.intermediate_size = intermediate_size
        self.sliding_window = int(sliding_window)
        self.mb_per_layer = mb_per_layer
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.ssm_state_size = ssm_state_size
        self.ssm_conv_width = ssm_conv_width
        self.ssm_dt_rank = ssm_dt_rank or -(-hidden_size // 16)
        self.d_inner = ssm_expand * hidden_size
        # Mamba-1 as published: ``ssm_hybrid``'s mixer without its norms
        self.ssm_inner_norms = False
        self.layer_norm_eps = layer_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype

    @property
    def half(self):
        return self.num_hidden_layers // 2

    def kind(self, layer):
        if layer <= self.half:
            return WINDOW if layer % 2 else MAMBA
        if layer == self.half + 1:
            return FULL
        return CROSS if layer % 2 else GATE

    def layers_of(self, kind):
        return sum(self.kind(i) == kind
                   for i in range(self.num_hidden_layers))

    @property
    def ssm_layers(self):
        return self.layers_of(MAMBA)

    @property
    def readers(self):
        """Layers that read the one paged cache."""
        return 1 + self.layers_of(CROSS)

    def serving_model(self):
        return SharedCacheServingModel(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def shared_cache_param_shapes(config):
    """``{name: (shape, kind)}`` of every serving parameter; ``kind``
    is ``"matrix"`` (the model's dtype) or one of the float32 kinds
    ``"norm"`` (a gain), ``"proj_bias"`` (a norm's or a projection's
    bias), ``"lambda"`` and, in a Mamba layer, ``ssm_hybrid``'s
    ``"conv"``, ``"bias"``, ``"dt_bias"``, ``"a_log"`` and ``"skip"``."""
    c = config
    h, d, n, hd = c.hidden_size, c.d_inner, c.ssm_state_size, c.head_dim
    wide, narrow = c.num_attention_heads * hd, c.num_key_value_heads * hd
    out = {"lm_embed": ((c.vocab_size, h), "matrix"),
           "lm_norm": ((h,), "norm"), "lm_norm_bias": ((h,), "proj_bias")}
    for i in range(c.num_hidden_layers):
        p, kind = f"lm_h{i}_", c.kind(i)
        out.update({
            p + "mixer_norm": ((h,), "norm"),
            p + "mixer_norm_bias": ((h,), "proj_bias"),
            p + "ffn_norm": ((h,), "norm"),
            p + "ffn_norm_bias": ((h,), "proj_bias"),
            p + "mlp_gate_up": ((h, 2 * c.intermediate_size), "matrix"),
            p + "mlp_down": ((c.intermediate_size, h), "matrix")})
        if kind == MAMBA:
            out.update({
                p + "in_proj": ((h, 2 * d), "matrix"),
                p + "conv_w": ((c.ssm_conv_width, d), "conv"),
                p + "conv_b": ((d,), "bias"),
                p + "x_proj": ((d, c.ssm_dt_rank + 2 * n), "matrix"),
                p + "dt_proj": ((c.ssm_dt_rank, d), "matrix"),
                p + "dt_bias": ((d,), "dt_bias"),
                p + "a_log": ((d, n), "a_log"),
                p + "d": ((d,), "skip"),
                p + "out_proj": ((d, h), "matrix")})
            continue
        if kind == GATE:
            out.update({p + "gate_in": ((h, d), "matrix"),
                        p + "gate_out": ((d, h), "matrix")})
            continue
        # an attention layer of some kind: q, k and v side by side in a
        # window layer; the full layer's k / v apart from its q (a
        # prefill runs them on different rows); a cross layer's q alone
        if kind == WINDOW:
            out.update({p + "qkv": ((h, wide + 2 * narrow), "matrix"),
                        p + "qkv_bias": ((wide + 2 * narrow,), "proj_bias")})
        else:
            out.update({p + "q": ((h, wide), "matrix"),
                        p + "q_bias": ((wide,), "proj_bias")})
        if kind == FULL:
            out.update({p + "kv": ((h, 2 * narrow), "matrix"),
                        p + "kv_bias": ((2 * narrow,), "proj_bias")})
        out.update({p + "o": ((wide, h), "matrix"),
                    p + "o_bias": ((h,), "proj_bias"),
                    p + "pair_norm": ((2 * hd,), "norm")})
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            out[p + name] = ((hd,), "lambda")
    return out


def layer_params(config, lookup, i):
    """Layer ``i``'s parameters by their short names from
    ``lookup(name)``: matrices in the model's dtype, the rest float32.
    ``a_log`` becomes ``a_t = -exp(a_log)^T``; an attention layer's four
    ``lambda`` vectors become the two float32 scalars the layer uses,
    ``lam`` and ``out_scale = 1 - lambda_init(i)``."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config.dtype)
    p = f"lm_h{i}_"
    blk = {k[len(p):]: jnp.asarray(
               lookup(k), dtype if kind == "matrix" else jnp.float32)
           for k, (_, kind) in shared_cache_param_shapes(config).items()
           if k.startswith(p)}
    if "a_log" in blk:
        blk["a_t"] = -jnp.exp(blk.pop("a_log")).T
    if "lambda_q1" in blk:
        q1, k1, q2, k2 = (blk.pop(f"lambda_{n}")
                          for n in ("q1", "k1", "q2", "k2"))
        init = lambda_init(i)
        blk["lam"] = jnp.exp(jnp.sum(q1 * k1)) \
            - jnp.exp(jnp.sum(q2 * k2)) + jnp.float32(init)
        blk["out_scale"] = jnp.float32(1.0 - init)
    return blk


def shared_cache_serving_params(config, lookup):
    """The parameter pytree from ``lookup(name)``: ``lower`` the
    (Mamba, window) pairs and ``upper`` the (gate, cross) pairs, each
    ``{"first": ..., "second": ...}`` with a leading axis, the pairs;
    ``mamba`` and ``full`` the two layers between them. A run is stacked
    as soon as its layers are read, so a lookup that hands its arrays
    over never holds the model twice."""
    import jax
    import jax.numpy as jnp
    c = config
    dtype = jnp.dtype(c.dtype)
    stack = jax.jit(lambda *blks: jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *blks))

    def pairs(first, last):
        return {"first": stack(*(layer_params(c, lookup, i)
                                 for i in range(first, last, 2))),
                "second": stack(*(layer_params(c, lookup, i)
                                  for i in range(first + 1, last, 2)))}

    return {"embed": jnp.asarray(lookup("lm_embed"), dtype),
            "norm": jnp.asarray(lookup("lm_norm"), jnp.float32),
            "norm_bias": jnp.asarray(lookup("lm_norm_bias"), jnp.float32),
            "lower": pairs(0, c.half),
            "mamba": layer_params(c, lookup, c.half),
            "full": layer_params(c, lookup, c.half + 1),
            "upper": pairs(c.half + 2, c.num_hidden_layers)}


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _ln(x, gain, bias, eps):
    """LayerNorm, float32 statistics, in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gain + bias).astype(x.dtype)


def _mixer_in(config, blk, x):
    return _ln(x, blk["mixer_norm"], blk["mixer_norm_bias"],
               config.layer_norm_eps)


def _feed_forward(config, blk, x):
    from ..ops.moe import swiglu
    return x + swiglu(_ln(x, blk["ffn_norm"], blk["ffn_norm_bias"],
                          config.layer_norm_eps),
                      blk["mlp_gate_up"], blk["mlp_down"])


def _scale(config):
    return 1.0 / math.sqrt(config.head_dim)


def _project(a, w, bias):
    return a @ w + bias.astype(a.dtype)


def _attention_out(config, blk, maps, dtype):
    """The two maps' products ``[..., pairs, 2, 2 D]`` through the
    difference, the pair norm and ``W_o``."""
    from ..ops.attention import diff_combine
    o = diff_combine(maps, blk["lam"], blk["pair_norm"], blk["out_scale"],
                     config.layer_norm_eps)
    return _project(o.astype(dtype), blk["o"], blk["o_bias"])


def diff_attention_rows(config, blk, a, k_rows, v_rows, positions,
                        attend=None):
    """A differential attention layer's mixer for ONE normed row a
    sequence, ``a [B, hidden]`` at ``positions [B]``, against rows in
    position order (``k_rows`` / ``v_rows [B, S, kv_heads x D]``; row
    ``j <= positions[b]`` is seen): the full layer's and a cross layer's
    alike. ``attend`` stands in for
    ``ops/attention.py:diff_rows_attention`` (a profiled program's
    bracket). Returns ``[B, hidden]``."""
    from ..ops.attention import diff_rows_attention
    c = config
    q = _project(a, blk["q"], blk["q_bias"]).reshape(
        a.shape[0], c.num_attention_heads, c.head_dim)
    maps = (attend or diff_rows_attention)(q, k_rows, v_rows, None,
                                           _scale(c), positions)
    return _attention_out(c, blk, maps, a.dtype)


def gated_memory(blk, a, m):
    """A gated memory unit: ``(silu(a W_g) * m) W_u`` of the normed
    rows ``a [..., hidden]`` and the memory ``m [..., d]``, the gate and
    the product float32."""
    import jax
    import jax.numpy as jnp
    gate = jax.nn.silu(jnp.dot(a, blk["gate_in"],
                               preferred_element_type=jnp.float32))
    return (gate * m.astype(jnp.float32)).astype(a.dtype) @ blk["gate_out"]


def _between(name, bracket):
    """``(enter, leave)``: each hands its arrays through a device event
    ``<name>_in`` / ``<name>_out`` of a profiled program
    (``ops/attention.py:bracketed`` says why), or back as they are."""
    if not bracket:
        return (lambda *a: a), (lambda *a: a)
    from ..ops.attention import event_markers
    return event_markers(name)


def _cross_decoder(params, config, x, m, k_rows, v_rows, positions, attend,
                   bracket):
    """The (gate, cross) pairs over ONE row a sequence ``x [B,
    hidden]`` with its memory ``m [B, d]`` and the shared rows."""
    import jax
    c = config
    enter, leave = _between(CROSS_DECODER_NAME, bracket)
    (x,) = enter(x)

    def pair(x, blk):
        gate, cross = blk["first"], blk["second"]
        x = _feed_forward(c, gate, x + gated_memory(
            gate, _mixer_in(c, gate, x), m))
        x = _feed_forward(c, cross, x + diff_attention_rows(
            c, cross, _mixer_in(c, cross, x), k_rows, v_rows, positions,
            attend))
        return x, None

    x, _ = jax.lax.scan(pair, x, params["upper"])
    (x,) = leave(x)
    return x


def _head(params, config, x):
    """Float32 logits of rows ``x [B, hidden]``: the final norm, then
    the tied embedding."""
    import jax.numpy as jnp
    x = _ln(x, params["norm"], params["norm_bias"], config.layer_norm_eps)
    return jnp.einsum("bh,vh->bv", x, params["embed"],
                      preferred_element_type=jnp.float32)


def _counted(config, tokens, positions, rows, last, logits):
    """The int32 vector a program returns: ``COUNTERS`` of its real
    tokens (``tokens [...]`` bool at ``positions [...]``) and of the
    ``rows [B]`` (bool) whose position ``last [B]`` went through the
    cross-decoder, then a record a batch row, the bits of its best
    float32 logit."""
    import jax
    import jax.numpy as jnp
    c = config
    n = jnp.sum(tokens).astype(jnp.int32)
    inside = jnp.sum(jnp.where(tokens, jnp.minimum(
        positions + 1, c.sliding_window), 0)).astype(jnp.int32)
    shared = jnp.sum(jnp.where(rows, last + 1, 0)).astype(jnp.int32)
    best = jax.lax.bitcast_convert_type(
        jnp.max(logits, axis=-1).astype(jnp.float32), jnp.int32)
    return jnp.concatenate([jnp.stack([
        n * c.ssm_layers, inside * c.layers_of(WINDOW), shared * c.readers,
        jnp.sum(rows).astype(jnp.int32), n]), best])


def _window_qkv(config, blk, a):
    """A window layer's ``(q [..., heads, D], k, v [..., kv_heads x
    D])`` of the normed rows."""
    c = config
    wide = c.num_attention_heads * c.head_dim
    narrow = c.num_key_value_heads * c.head_dim
    qkv = _project(a, blk["qkv"], blk["qkv_bias"])
    q = qkv[..., :wide].reshape(*a.shape[:-1], c.num_attention_heads,
                                c.head_dim)
    return q, qkv[..., wide:wide + narrow], qkv[..., wide + narrow:]


# ---------------------------------------------------------------------------
# the engine's programs
# ---------------------------------------------------------------------------

def shared_cache_paged_prefill(params, pools, ids, slot_idx, last_pos,
                               state_slots, window_slot_idx, config):
    """Prompt phase over ``ids [B, P]`` (right-padded). The
    self-decoder runs on every position: each Mamba layer leaves its
    state and tail AS OF THE ROW'S LAST REAL TOKEN in slot
    ``state_slots [B]`` (from a zero state: the slot is written, never
    read), each window layer writes its last ``window`` rows through
    ``window_slot_idx [B, P]`` (a ring slot for those, the scratch block
    for the rest), the full layer every position's ``k`` / ``v`` row
    through ``slot_idx [B, P]``. Everything behind those rows runs on
    row ``last_pos [B]`` alone. Returns ``((logits [B, V], counters),
    pools)``; jit with ``pools`` donated."""
    import jax
    import jax.numpy as jnp
    from ..ops.attention import diff_prefill_attention
    c = config
    rows, span = ids.shape
    n_window = c.layers_of(WINDOW)
    valid = slot_idx >= pools[-1]["k"].shape[1]     # off the scratch block
    lengths = jnp.sum(valid, axis=1).astype(jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(span), ids.shape)
    # the positions a ring keeps: each row's last ``window`` real ones
    kept = min(c.sliding_window, span)
    at = jnp.maximum(lengths - kept, 0)[:, None] + jnp.arange(kept)[None, :]
    ring_slots = jnp.take_along_axis(window_slot_idx, at, axis=1)

    def mamba(blk, state, layer, x):
        y, z, tail, s = scan_prefill(
            c, blk, _mixer_in(c, blk, x),
            jnp.zeros((rows, c.ssm_conv_width - 1, c.d_inner), x.dtype),
            jnp.zeros((rows, c.ssm_state_size, c.d_inner), jnp.float32),
            lengths)
        state = {"conv": _write_tails(c, state, state_slots, layer, tail),
                 "ssm": state["ssm"].at[state_slots, layer].set(s)}
        return _feed_forward(c, blk, x + _gated_out(blk, y, z)), state, y

    def pair(carry, step):
        x, state = carry
        blk, layer = step
        x, state, _ = mamba(blk["first"], state, layer, x)
        blk = blk["second"]
        q, k, v = _window_qkv(c, blk, _mixer_in(c, blk, x))
        heads = (rows, span, c.num_key_value_heads, c.head_dim)
        maps = diff_prefill_attention(q, k.reshape(heads), v.reshape(heads),
                                      _scale(c), window=c.sliding_window)
        x = _feed_forward(c, blk, x + _attention_out(c, blk, maps, x.dtype))
        return (x, state), tuple(
            jnp.take_along_axis(t, at[:, :, None], axis=1) for t in (k, v))

    (x, state), (ring_k, ring_v) = jax.lax.scan(
        pair, (params["embed"][ids], pools[0]),
        (params["lower"], jnp.arange(n_window, dtype=jnp.int32)))
    window_pools = [_write_kv(pool, ring_slots, ring_k[i], ring_v[i])
                    for i, pool in enumerate(pools[1:-1])]
    x, state, y = mamba(params["mamba"], state, n_window, x)

    # the full layer: k / v of every position, the rest on one row
    full = params["full"]
    kv = _project(_mixer_in(c, full, x), full["kv"], full["kv_bias"])
    k_rows, v_rows = jnp.split(kv, 2, axis=-1)
    shared = _write_kv(pools[-1], slot_idx, k_rows, v_rows)
    last = last_pos.astype(jnp.int32)
    x, m = (jnp.take_along_axis(t, last[:, None, None], axis=1)[:, 0]
            for t in (x, y))
    x = _feed_forward(c, full, x + diff_attention_rows(
        c, full, _mixer_in(c, full, x), k_rows, v_rows, last))
    x = _cross_decoder(params, c, x, m, k_rows, v_rows, last, None, False)
    logits = _head(params, c, x)
    counted = _counted(c, valid, positions, lengths > 0, last, logits)
    return (logits, counted), [state, *window_pools, shared]


def shared_cache_paged_step(params, pools, tokens, positions, slot_idx,
                            write_slots, state_slots, ring_idx,
                            ring_write_slots, config, pick=None,
                            bracket=False):
    """One token a row of a RAGGED batch (``models/gpt.py:gpt_paged_step``
    has the first six arguments): each Mamba layer updates slot
    ``state_slots [B]`` in place; a window layer writes its row to
    ``ring_write_slots [B]`` and reads its ring as it lies, ``ring_idx
    [B, ring slots]``, behind the window's mask; the full layer writes
    its row to ``write_slots [B]``, and the rows at ``slot_idx [B, S]``
    are brought into position order ONCE for it and every cross layer
    (up to each row's position: what lies behind is not defined). With
    ``pick="greedy"`` returns ``(int32 [B + n]: each lane's argmax, then
    the counters; pools)``, with ``pick=None`` ``((logits [B, V],
    counters), pools)``. ``bracket``: the attentions over the shared
    rows (the gather with the first) and the cross-decoder each between
    two device events of their name (a profiled engine's program)."""
    import jax
    import jax.numpy as jnp
    from ..ops.attention import (diff_rows_attention, diff_rows_extent,
                                 gather_rows_once, ring_valid)
    if pick not in (None, "greedy"):
        raise ValueError(f"pick must be None or 'greedy', got {pick!r}")
    c = config
    n_window = c.layers_of(WINDOW)
    valid = write_slots >= pools[-1]["k"].shape[1]
    lanes = jnp.arange(tokens.shape[0])
    # the rings as they lie, every layer's by one call before the
    # pairs' loop (a loop cannot index the pools by layer); the step's
    # own row goes in at its ring position inside the loop, to the pool
    # after it
    ring = ring_idx.shape[1]
    rings = gather_rows_once([[pool[name] for pool in pools[1:-1]]
                              for name in "kv"], ring_idx)
    ring_at = positions % ring
    in_window = ring_valid(ring, positions, c.sliding_window)

    def mamba(blk, state, layer, x):
        y, z, window, pool = scan_step(
            c, blk, _mixer_in(c, blk, x), _tails(c, state, state_slots,
                                                 layer),
            state["ssm"], state_slots, layer)
        state = {"conv": _write_tails(c, state, state_slots, layer,
                                      window[:, 1:]), "ssm": pool}
        return _feed_forward(c, blk, x + _gated_out(blk, y, z)), state, y

    def pair(carry, step):
        x, state = carry
        blk, layer, ring_k, ring_v = step
        x, state, _ = mamba(blk["first"], state, layer, x)
        blk = blk["second"]
        q, k, v = _window_qkv(c, blk, _mixer_in(c, blk, x))
        maps = diff_rows_attention(
            q, ring_k.at[lanes, ring_at].set(k),
            ring_v.at[lanes, ring_at].set(v), in_window, _scale(c))
        x = _feed_forward(c, blk, x + _attention_out(c, blk, maps, x.dtype))
        return (x, state), (k, v)

    (x, state), (new_k, new_v) = jax.lax.scan(
        pair, (params["embed"][tokens], pools[0]),
        (params["lower"], jnp.arange(n_window, dtype=jnp.int32), *rings))
    window_pools = [_write_kv(pool, ring_write_slots, new_k[i], new_v[i])
                    for i, pool in enumerate(pools[1:-1])]
    x, state, m = mamba(params["mamba"], state, n_window, x)

    # the full layer's row joins the pool, then ONE gather for the eight
    # layers that read the rows
    full = params["full"]
    a = _mixer_in(c, full, x)
    k, v = jnp.split(_project(a, full["kv"], full["kv_bias"]), 2, axis=-1)
    shared = _write_kv(pools[-1], write_slots, k, v)
    enter, leave = _between(DIFF_DECODE_NAME, bracket)
    # a profiled program's gather lies behind the first attention's
    # ``_in`` event: it takes the slots from it
    a, slot_idx = enter(a, slot_idx)
    extent = diff_rows_extent(c.num_attention_heads, shared["k"].shape[-1],
                              slot_idx.shape[1], positions)
    k_rows, v_rows = jax.lax.optimization_barrier(tuple(
        rows[0] for rows in gather_rows_once(
            [[shared[name]] for name in "kv"], slot_idx, extent)))
    def first(*args):
        return leave(diff_rows_attention(*args))[0]

    def attend(q, *rest):
        return leave(diff_rows_attention(enter(q)[0], *rest))[0]

    x = _feed_forward(c, full, x + diff_attention_rows(
        c, full, a, k_rows, v_rows, positions, first))
    x = _cross_decoder(params, c, x, m, k_rows, v_rows, positions,
                       attend if bracket else None, bracket)
    logits = _head(params, c, x)
    counted = _counted(c, valid, positions, valid, positions, logits)
    new_pools = [state, *window_pools, shared]
    if pick == "greedy":
        return jnp.concatenate(
            [jnp.argmax(logits, axis=-1).astype(jnp.int32),
             counted]), new_pools
    return (logits, counted), new_pools


def _no_suffix_prefill(*args, **kw):
    raise NotImplementedError(
        "a model with window layers and recurrent state has no "
        "suffix-prefill program (the engine refuses prefix_cache and "
        "prefill_chunk for it)")


# ---------------------------------------------------------------------------
# what the engine takes the model as
# ---------------------------------------------------------------------------

class SharedCacheServingModel:
    """The serving-model interface (``docs/serving.md``) for a
    :class:`SharedCacheConfig`: a state entry, a window entry a window
    layer and ONE rows entry, which the full layer writes and it and
    every cross layer read."""

    prefill_last_row = True
    counter_names = COUNTERS
    vector_counter = None
    # a row's record: the bits of its best float32 logit
    row_record_width = 1

    def __init__(self, config):
        self.config = config
        self.vocab_size = config.vocab_size
        self.max_positions = config.max_position_embeddings
        self.pool_kinds = ("state",) + ("window",) * config.layers_of(
            WINDOW) + ("rows",)
        self.window_size = config.sliding_window

    def read_records(self, records):
        """``Future.token_records [n, 1]`` taken apart: ``{"best_logit":
        [n] float32}`` of the row that decided each generated token."""
        records = np.ascontiguousarray(records, np.int32)
        return {"best_logit": records[:, 0].view(np.float32)}

    def cache_layout(self):
        """A pool of either kind: ONE ``k`` and ONE ``v`` row a token,
        ``kv_heads x head_dim`` wide, in the model's dtype. The rows
        entry is one layer's: its readers hold none of their own."""
        c = self.config
        width = c.num_key_value_heads * c.head_dim
        return (("k", width, c.dtype), ("v", width, c.dtype))

    def state_layout(self):
        """What a slot holds (``models/ssm_hybrid.py`` has the reasons):
        every Mamba layer's state ``[layers, N, d]`` float32 and its
        convolution tail, ``K - 1`` rows a layer one under the other."""
        c = self.config
        return (("ssm", (c.ssm_layers, c.ssm_state_size, c.d_inner),
                 "float32"),
                ("conv", (c.ssm_layers * (c.ssm_conv_width - 1),
                          c.d_inner), c.dtype))

    def params(self, lookup):
        return shared_cache_serving_params(self.config, lookup)

    @property
    def _itemsize(self):
        import jax.numpy as jnp     # numpy alone does not know bfloat16
        return jnp.dtype(self.config.dtype).itemsize

    def param_bytes(self):
        return int(sum(
            int(np.prod(shape)) * (self._itemsize if kind == "matrix"
                                   else 4)
            for shape, kind
            in shared_cache_param_shapes(self.config).values()))

    def prefill_bytes_per_token(self):
        """Bytes of temporaries one prompt token costs a prefill
        program at its widest point if nothing were fused, the larger
        of the Mamba mixer's (``models/ssm_hybrid.py`` counts it) and a
        window layer's: the projection's output, each map's query, key
        and value ``2 x head_dim`` wide token-major and head-major, both
        maps' products both ways and the float32 difference; beside
        either the SwiGLU's two rows and six float32 ``hidden``-wide
        rows."""
        c = self.config
        maps = c.num_attention_heads * 2 * c.head_dim
        mamba = (c.d_inner * (5 * self._itemsize + 2 * 4)
                 + 2 * c.ssm_state_size * 128 * 4)
        window = ((c.num_attention_heads + 2 * c.num_key_value_heads)
                  * c.head_dim + 8 * maps) * self._itemsize + 2 * maps
        return (max(mamba, window)
                + 3 * c.intermediate_size * self._itemsize
                + 6 * c.hidden_size * 4)

    def program(self, kind):
        """``(function, static keywords)`` of one of the engine's four
        programs (``suffix_prefill`` raises: the engine refuses the
        modes that would run it)."""
        fn = {"prefill": shared_cache_paged_prefill,
              "decode": shared_cache_paged_step,
              "decode_logits": shared_cache_paged_step,
              "suffix_prefill": _no_suffix_prefill}[kind]
        static = {"config": self.config}
        if kind == "decode":
            static["pick"] = "greedy"
        if fn is shared_cache_paged_step:
            # a profiled engine's decode programs show where the shared
            # rows' attentions and the cross-decoder lie (TPU; decided
            # here, once an engine: ``models/window_moe.py`` does so too)
            from .. import telemetry
            from ..ops.attention import kernels_run
            static["bracket"] = bool(
                telemetry.get_telemetry().enabled and kernels_run())
        return fn, static
