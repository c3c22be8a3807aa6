"""Grouped-query decoders whose layers attend over a sliding WINDOW or
over everything, with gated attention, sandwich norms and routed
experts, on the serving path.

The ``afmoe`` family's shape (``layer_types``: most layers
``"sliding_attention"``, every n-th ``"full_attention"``). One layer,
``x`` the residual stream, every norm RMS with a learned gain::

    x = x + rms(attn(rms(x; g_in)); g_post_attn)
    x = x + rms(F(rms(x; g_pre_mlp)); g_post_mlp)

``attn(h)``: ``q = h W_q`` as ``heads`` of ``head_dim``, ``k = h W_k``
and ``v = h W_v`` as ``kv_heads`` (a group of ``heads / kv_heads``
queries reads one key/value head), ``g = h W_g``; ``q`` and ``k`` each
RMS-normed over the head (a gain a projection); on a SLIDING layer
``q`` and ``k`` are rotated (halves paired) and a query at position
``i`` sees the keys ``i - window < j <= i``; on a FULL layer nothing is
rotated and it sees every ``j <= i``; float32 softmax of ``q k /
sqrt(head_dim)``; ``attn = (ctx * sigmoid(g)) W_o``. ``F`` is a SwiGLU
in the first ``num_dense_layers`` layers and, in the others, the shared
expert plus the routed experts of ``ops/moe.py`` (sigmoid scores over
ALL experts, a selection-only bias, the chosen ``top_k`` normalised and
scaled by ``route_scale``). The embedding is scaled by
``sqrt(hidden)`` (``mup_enabled``); the head is untied.

**What a sequence carries between programs** is a ``k`` and a ``v`` row
a token a layer (``kv_heads x head_dim`` wide, rotated where the layer
rotates). A full layer keeps them all, through the block table every
paged model has; a sliding layer keeps the last ``window`` in a RING of
``ceil(window / block_size) + 1`` blocks of its own pool
(``serving/kvcache.py``: ``pool_kinds`` entries ``"window"``,
``window_size``), so its bytes a sequence do not grow past ``window +
one block``. A prefill has the whole prompt's keys inside the program
for the flash call (with the band, ``ops/pallas_attention.py``) and
writes only the last ``window`` of them to the ring; a decode step
reads the ring as it lies, a fixed shape whatever the context, behind
the mask of ``ops/attention.py:grouped_ring_decode_attention``.

**One share of an expert-parallel deployment**, as
``models/latent_moe.py`` has it: ``experts_held`` ``(first, count)``
says which routed experts this program holds, the router stays as wide
as the model, the experts held elsewhere add nothing here, and
``vocab_size`` is the rows of the vocabulary held.

Weights and activations are in ``dtype`` (bfloat16 as deployed); norm
statistics, router scores, the gate, softmax and logits float32.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .decoder_parts import (feed_forward, pool_scatter, records, rms, rope,
                            token_chunks)

__all__ = ["WindowMoEConfig", "WindowMoEServingModel",
           "window_moe_param_shapes", "window_moe_serving_params",
           "window_moe_paged_prefill", "window_moe_paged_step", "COUNTERS",
           "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"

# what every program returns beside its tokens or logits, int32, in
# this order, followed by the rows of each held expert and then, for
# each row of the batch, its record (``decoder_parts.records``). The
# first three are ``models/latent_moe.py``'s; ``attn_*_rows`` are the
# rows the program's real tokens attend to, each times the layers of its
# kind (a token at position i: min(i + 1, window) a sliding layer, i + 1
# a full one) — in a decode step the cached rows read, in a prefill the
# (query, key) pairs inside the band / under the diagonal
COUNTERS = ("moe_tokens", "moe_routed_rows", "moe_expert_visits",
            "attn_window_rows", "attn_full_rows")


class WindowMoEConfig:
    """Widths are the published ones; what a deployment cuts is depth
    (``layer_types``, ``num_dense_layers``), the experts held
    (``experts_held``) and the vocabulary rows held (``vocab_size``)."""

    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, intermediate_size,
                 moe_intermediate_size, layer_types, sliding_window,
                 num_dense_layers, num_experts, num_experts_per_tok,
                 num_shared_experts=1, route_norm=True, route_scale=1.0,
                 mup_enabled=False, experts_held=None, rms_norm_eps=1e-5,
                 rope_theta=10000.0, max_position_embeddings=262144,
                 dtype="bfloat16"):
        if num_attention_heads % num_key_value_heads:
            raise ValueError(
                f"{num_key_value_heads} key/value heads do not divide "
                f"{num_attention_heads} query heads")
        layer_types = tuple(layer_types)
        if not layer_types or set(layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types takes {SLIDING!r} and {FULL!r}, got "
                f"{sorted(set(layer_types))}")
        if not route_norm:
            raise ValueError(
                "route_norm=False (routed weights not normalised over "
                "the chosen experts) is not implemented: ops/moe.py:route "
                "normalises")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.layer_types = layer_types
        self.num_hidden_layers = len(layer_types)
        self.sliding_window = int(sliding_window)
        self.num_dense_layers = num_dense_layers
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_norm = True
        self.route_scale = route_scale
        self.mup_enabled = bool(mup_enabled)
        first, count = experts_held or (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(
                f"experts_held {(first, count)} is not a range of the "
                f"{num_experts} routed experts")
        self.experts_held = (int(first), int(count))
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype

    # what ``decoder_parts.feed_forward`` calls the same thing
    @property
    def routed_scaling_factor(self):
        return self.route_scale

    # and what it asks besides: this router picks among all its experts
    n_group = topk_group = 1

    def is_dense(self, layer):
        return layer < self.num_dense_layers

    def is_sliding(self, layer):
        return self.layer_types[layer] == SLIDING

    def layers_of(self, kind):
        return self.layer_types.count(kind)

    def serving_model(self):
        return WindowMoEServingModel(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def window_moe_param_shapes(config):
    """``{name: (shape, kind)}`` of every serving parameter; ``kind``
    is ``"matrix"`` (the model's dtype), ``"norm"``, ``"router"`` or
    ``"router_bias"`` (float32). ``qkvg`` is the four projections side
    by side: queries, keys, values, the gate."""
    c = config
    h, hd = c.hidden_size, c.head_dim
    nq, nkv = c.num_attention_heads, c.num_key_value_heads
    held = c.experts_held[1]
    out = {"lm_embed": ((c.vocab_size, h), "matrix"),
           "lm_norm": ((h,), "norm"),
           "lm_head": ((h, c.vocab_size), "matrix")}
    for i in range(c.num_hidden_layers):
        p = f"lm_h{i}_"
        for norm in ("in_norm", "post_attn_norm", "pre_mlp_norm",
                     "post_mlp_norm"):
            out[p + norm] = ((h,), "norm")
        out.update({
            p + "qkvg": ((h, (2 * nq + 2 * nkv) * hd), "matrix"),
            p + "q_norm": ((hd,), "norm"),
            p + "k_norm": ((hd,), "norm"),
            p + "o": ((nq * hd, h), "matrix")})
        if c.is_dense(i):
            out[p + "mlp_gate_up"] = ((h, 2 * c.intermediate_size),
                                      "matrix")
            out[p + "mlp_down"] = ((c.intermediate_size, h), "matrix")
            continue
        shared = c.num_shared_experts * c.moe_intermediate_size
        out.update({
            p + "router": ((h, c.num_experts), "router"),
            p + "router_bias": ((c.num_experts,), "router_bias"),
            p + "shared_gate_up": ((h, 2 * shared), "matrix"),
            p + "shared_down": ((shared, h), "matrix"),
            p + "experts_gate_up": (
                (held, h, 2 * c.moe_intermediate_size), "matrix"),
            p + "experts_down": (
                (held, c.moe_intermediate_size, h), "matrix")})
    return out


def window_moe_serving_params(config, lookup):
    """The parameter pytree from ``lookup(name)``: matrices in the
    model's dtype (an array that already has it is not copied), norms
    and the router float32."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config.dtype)
    flat = {name: jnp.asarray(lookup(name),
                              dtype if kind == "matrix" else jnp.float32)
            for name, (_, kind) in window_moe_param_shapes(config).items()}
    blocks = []
    for i in range(config.num_hidden_layers):
        p = f"lm_h{i}_"
        blocks.append({k[len(p):]: v for k, v in flat.items()
                       if k.startswith(p)})
    return {"embed": flat["lm_embed"], "norm": flat["lm_norm"],
            "head": flat["lm_head"], "blocks": blocks}


# ---------------------------------------------------------------------------
# the forward, behind the engine's programs
# ---------------------------------------------------------------------------

def _scale(config):
    return 1.0 / math.sqrt(config.head_dim)


def _rope_tables(config, positions):
    """(cos, sin) ``[..., 1, head_dim / 2]`` float32 for int32
    ``positions [...]``: no scaling, ``theta ** (-2 i / head_dim)``."""
    import jax.numpy as jnp
    inv = jnp.asarray(config.rope_theta ** (
        -np.arange(0, config.head_dim, 2, dtype=np.float64)
        / config.head_dim), jnp.float32)
    angles = positions[..., None, None].astype(jnp.float32) * inv
    return jnp.cos(angles), jnp.sin(angles)


def _embed(params, config, ids):
    import jax.numpy as jnp
    x = params["embed"][ids]
    if not config.mup_enabled:
        return x
    return (x.astype(jnp.float32)
            * math.sqrt(config.hidden_size)).astype(x.dtype)


def attention_inputs(config, blk, h, cos, sin, sliding):
    """A layer's ``(q [..., heads, D], k, v [..., kv_heads, D], gate
    [..., heads x D])`` of the normed rows ``h [..., hidden]``: the four
    projections, the norms over each head of ``q`` and ``k``, and on a
    sliding layer their rotation."""
    c = config
    nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lead = h.shape[:-1]
    qkvg = h @ blk["qkvg"]
    q = qkvg[..., :nq * hd].reshape(*lead, nq, hd)
    k = qkvg[..., nq * hd:(nq + nkv) * hd].reshape(*lead, nkv, hd)
    v = qkvg[..., (nq + nkv) * hd:(nq + 2 * nkv) * hd].reshape(
        *lead, nkv, hd)
    gate = qkvg[..., (nq + 2 * nkv) * hd:]
    q = rms(q, blk["q_norm"], c.rms_norm_eps)
    k = rms(k, blk["k_norm"], c.rms_norm_eps)
    if sliding:
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    return q, k, v, gate


def _forward(params, config, x, positions, attend, valid):
    """THE decoder stack, written once: embedded tokens ``x [...,
    H]`` at int32 ``positions [...]`` through every layer. The cache
    backend is ``attend(i, sliding, q, k, v) -> context [..., heads,
    D]``: it writes layer ``i``'s ``k`` / ``v`` rows wherever its cache
    lives. ``valid [...]`` marks real tokens. Returns ``(x before the
    final norm, each expert layer's picks [..., expert layers, k], rows
    by held expert [held], held experts visited)``."""
    import jax
    import jax.numpy as jnp
    c = config
    eps = c.rms_norm_eps
    lead = x.shape[:-1]
    cos, sin = _rope_tables(c, positions)
    flat_valid = valid.reshape(-1)
    rows_total = jnp.zeros(c.experts_held[1], jnp.int32)
    visits = jnp.int32(0)
    picks = []
    for i, blk in enumerate(params["blocks"]):
        sliding = c.is_sliding(i)
        q, k, v, gate = attention_inputs(
            c, blk, rms(x, blk["in_norm"], eps), cos, sin, sliding)
        ctx = attend(i, sliding, q, k, v).reshape(*lead, -1)
        gated = (ctx.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        x = x + rms(gated @ blk["o"], blk["post_attn_norm"], eps)
        h = rms(x, blk["pre_mlp_norm"], eps)
        y, picked, (rows, seen) = token_chunks(
            lambda xc, vc, blk=blk: feed_forward(c, blk, xc, vc),
            h.reshape(-1, h.shape[-1]), flat_valid)
        x = x + rms(y.reshape(h.shape), blk["post_mlp_norm"], eps)
        rows_total, visits = rows_total + rows, visits + seen
        if not c.is_dense(i):
            picks.append(picked.reshape(*lead, -1))
    picks = jnp.stack(picks, axis=-2) if picks else jnp.zeros(
        (*lead, 0, c.num_experts_per_tok), jnp.int32)
    return x, picks, rows_total, visits


def _logits(params, config, x):
    """Float32 logits of rows ``x [B, hidden]``: the final norm, then
    the untied head."""
    import jax.numpy as jnp
    return jnp.dot(rms(x, params["norm"], config.rms_norm_eps),
                   params["head"], preferred_element_type=jnp.float32)


def _counters(config, valid, positions, rows, visits, records):
    """The int32 vector every program returns (``COUNTERS``, then the
    rows of each held expert, of the REAL tokens of this call at
    ``positions``; then each batch row's record)."""
    import jax.numpy as jnp
    c = config
    tokens = jnp.sum(valid).astype(jnp.int32)
    context = jnp.where(valid, positions + 1, 0).astype(jnp.int32)
    inside = jnp.sum(jnp.minimum(context, c.sliding_window)) \
        * c.layers_of(SLIDING)
    under = jnp.sum(context) * c.layers_of(FULL)
    head = jnp.stack([
        tokens * (c.num_hidden_layers - c.num_dense_layers),
        jnp.sum(rows), visits, inside, under])
    return jnp.concatenate(
        [head, rows, records.reshape(-1)]).astype(jnp.int32)


def _repeat_kv(config, rows):
    """``[..., kv_heads, D]`` as ``[..., heads, D]``: each key/value
    head under the query heads that read it."""
    import jax.numpy as jnp
    c = config
    group = c.num_attention_heads // c.num_key_value_heads
    rows = rows[..., :, None, :]
    return jnp.broadcast_to(
        rows, (*rows.shape[:-2], group, c.head_dim)).reshape(
            *rows.shape[:-3], c.num_attention_heads, c.head_dim)


def _write_kv(layer, slots, k, v):
    width = layer["k"].shape[-1]
    return {"k": pool_scatter(layer["k"], slots,
                               k.reshape(*k.shape[:-2], width)),
            "v": pool_scatter(layer["v"], slots,
                               v.reshape(*v.shape[:-2], width))}


def _plan(config, op, form, reason=None):
    """The ``attn_window_plan`` instant, once a traced call: which form
    the window layers' attention runs in."""
    from .. import telemetry
    telemetry.get_telemetry().instant(
        "attn_window_plan", op=op, window=config.sliding_window, form=form,
        **({"reason": reason} if reason else {}))


def prefill_context(config, q, k, v, sliding):
    """A whole prompt's attention among its own tokens, ``q [B, S,
    heads, D]`` and ``k`` / ``v [B, S, kv_heads, D]``: the flash kernel
    on a TPU (with the band on a sliding layer), key/value heads
    broadcast to the query heads that read them. Returns ``[B, S,
    heads, D]``."""
    from ..ops.attention import prefill_attention
    ctx = prefill_attention(
        *(t.transpose(0, 2, 1, 3)
          for t in (q, _repeat_kv(config, k), _repeat_kv(config, v))),
        sm_scale=_scale(config), causal=True,
        window=config.sliding_window if sliding else None)
    return ctx.transpose(0, 2, 1, 3)


def window_moe_paged_prefill(params, pools, ids, slot_idx, last_pos,
                             window_slot_idx, config):
    """Prompt phase: causal forward over ``ids [B, P]`` (right-padded).
    A full layer scatters every position's ``k`` / ``v`` row into
    ``slot_idx [B, P]``, a sliding layer into ``window_slot_idx [B,
    P]``, which names a ring slot for a prompt's last ``window``
    positions and the scratch block for the rest (as both do for
    padding); the whole prompt's keys are inside the program for the
    flash call either way. ``last_pos [B]``: that row alone goes through
    the head. Returns ``((logits [B, V], counters), pools)``; jit with
    ``pools`` donated."""
    import jax.numpy as jnp
    from ..ops.attention import _use_pallas
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    valid = slot_idx >= pools[0]["k"].shape[1]      # off the scratch block
    _plan(config, "prefill", *(("kernel",) if _use_pallas()
                               else ("composed", "platform")))
    new_pools = list(pools)

    def attend(i, sliding, q, k, v):
        new_pools[i] = _write_kv(
            pools[i], window_slot_idx if sliding else slot_idx, k, v)
        return prefill_context(config, q, k, v, sliding)

    x, picks, rows, visits = _forward(
        params, config, _embed(params, config, ids), positions, attend,
        valid)
    at = last_pos.astype(jnp.int32)
    last = jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]
    picks = jnp.take_along_axis(picks, at[:, None, None, None], axis=1)[:, 0]
    logits = _logits(params, config, last)
    counters = _counters(config, valid, positions, rows, visits,
                         records(picks, logits))
    return (logits, counters), new_pools


def window_moe_paged_step(params, pools, tokens, positions, slot_idx,
                          write_slots, ring_idx, ring_write_slots, config,
                          pick=None, bracket=False):
    """One token a row of a RAGGED batch (``models/gpt.py:gpt_paged_step``
    has the first six arguments). A full layer writes its row to
    ``write_slots [B]`` and reads the rows gathered through ``slot_idx
    [B, S]``; a sliding layer writes to ``ring_write_slots [B]`` and
    reads its ring as it lies, ``ring_idx [B, ring slots]``, behind the
    window's mask. With ``pick="greedy"`` returns ``(int32 [B + n]: each
    lane's argmax, then the counters; pools)``, with ``pick=None``
    ``((logits [B, V], counters), pools)``. ``bracket``: each decode
    attention between two device events of its name
    (``ops/attention.py:bracketed``; a profiled engine's program)."""
    import jax.numpy as jnp
    from ..ops.attention import (bracketed, grouped_decode_attention,
                                 grouped_ring_decode_attention)
    if pick not in (None, "greedy"):
        raise ValueError(f"pick must be None or 'greedy', got {pick!r}")
    valid = write_slots >= pools[0]["k"].shape[1]
    _plan(config, "decode", "composed", "no_paged_grouped_kernel")
    new_pools = list(pools)
    scale = _scale(config)
    window, full = grouped_ring_decode_attention, grouped_decode_attention
    if bracket:
        window = functools.partial(bracketed, "hetu_gqa_decode_window",
                                   window)
        full = functools.partial(bracketed, "hetu_gqa_decode_full", full)

    def attend(i, sliding, q, k, v):
        if sliding:
            layer = _write_kv(pools[i], ring_write_slots, k, v)
            ctx = window(q, layer["k"], layer["v"], ring_idx, positions,
                         window=config.sliding_window, sm_scale=scale)
        else:
            layer = _write_kv(pools[i], write_slots, k, v)
            ctx = full(q, layer["k"], layer["v"], slot_idx, positions,
                       sm_scale=scale)
        new_pools[i] = layer
        return ctx

    x, picks, rows, visits = _forward(
        params, config, _embed(params, config, tokens), positions, attend,
        valid)
    logits = _logits(params, config, x)
    counters = _counters(config, valid, positions, rows, visits,
                         records(picks, logits))
    if pick == "greedy":
        return jnp.concatenate(
            [jnp.argmax(logits, axis=-1).astype(jnp.int32),
             counters]), new_pools
    return (logits, counters), new_pools


def _no_suffix_prefill(*args, **kw):
    raise NotImplementedError(
        "a model with window layers has no suffix-prefill program: a "
        "chunk would read the previous chunk's tail out of the ring "
        "(the engine refuses prefix_cache and prefill_chunk for it)")


# ---------------------------------------------------------------------------
# what the engine takes the model as
# ---------------------------------------------------------------------------

class WindowMoEServingModel:
    """The serving-model interface (``docs/serving.md``) for a
    :class:`WindowMoEConfig`: layers with rows (full attention) and
    layers with a window of rows (sliding attention)."""

    prefill_last_row = True
    counter_names = COUNTERS

    def __init__(self, config):
        self.config = config
        self.vocab_size = config.vocab_size
        self.max_positions = config.max_position_embeddings
        self.num_cache_layers = config.num_hidden_layers
        # the cache's entries, a layer each
        self.pool_kinds = tuple("window" if config.is_sliding(i) else "rows"
                                for i in range(config.num_hidden_layers))
        self.window_size = config.sliding_window
        self.vector_counter = ("moe_rows_by_expert", config.experts_held[1])
        self._moe_layers = config.num_hidden_layers - config.num_dense_layers
        # int32 words a batch row's record takes behind the counters
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
        """A layer's pools, of either kind: ONE ``k`` and ONE ``v`` row
        a token, ``kv_heads x head_dim`` wide, in the model's dtype."""
        c = self.config
        width = c.num_key_value_heads * c.head_dim
        return (("k", width, c.dtype), ("v", width, c.dtype))

    def params(self, lookup):
        return window_moe_serving_params(self.config, lookup)

    @property
    def _itemsize(self):
        import jax.numpy as jnp     # numpy alone does not know bfloat16
        return jnp.dtype(self.config.dtype).itemsize

    def param_bytes(self):
        return int(sum(
            int(np.prod(shape)) * (self._itemsize if kind == "matrix"
                                   else 4)
            for shape, kind in window_moe_param_shapes(self.config).values()))

    def prefill_bytes_per_token(self):
        """Bytes of temporaries one prompt token costs a prefill
        program at its widest point, the attention, if nothing were
        fused: the four projections' output, the queries and the
        key/value heads broadcast to them, each token-major and
        head-major, the context both ways and the gated product in the
        model's dtype, beside six float32 ``hidden``-wide rows of what a
        sublayer reads and writes."""
        c = self.config
        wide = c.num_attention_heads * c.head_dim
        narrow = c.num_key_value_heads * c.head_dim
        return ((2 * wide + 2 * narrow + 9 * wide) * self._itemsize
                + 6 * c.hidden_size * 4)

    def program(self, kind):
        """``(function, static keywords)`` of one of the engine's four
        programs (``suffix_prefill`` raises: the engine refuses the
        modes that would run it)."""
        fn = {"prefill": window_moe_paged_prefill,
              "decode": window_moe_paged_step,
              "decode_logits": window_moe_paged_step,
              "suffix_prefill": _no_suffix_prefill}[kind]
        static = {"config": self.config}
        if kind == "decode":
            static["pick"] = "greedy"
        if fn is window_moe_paged_step:
            # a profiled engine's decode programs show where their
            # attentions lie (TPU; decided here, once an engine, so a
            # later change of the tracing leaves its programs alone)
            from .. import telemetry
            from ..ops.attention import _use_pallas
            static["bracket"] = bool(
                telemetry.get_telemetry().enabled and _use_pallas())
        return fn, static
