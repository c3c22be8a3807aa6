"""A hybrid of state-space and attention layers, for serving.

Most layers mix tokens through a selective state-space recurrence
(Mamba-1's, with the ``jamba`` family's RMS norms on ``dt``, ``B`` and
``C``); every ``attn_layer_period``-th layer (at ``attn_layer_offset``)
is causal softmax attention with FEWER key/value heads than query heads
and NO positional encoding. Every layer ends in the same dense SwiGLU.
One layer, ``h`` the residual::

    h = h + mixer(rms(h; g1));  h = h + swiglu(rms(h; g2))

The Mamba mixer (``d = expand x hidden`` channels, ``N`` state values a
channel, a causal depthwise convolution of ``K`` taps)::

    [x, z]   = u W_in
    x_t      = silu(b_c + sum_j w_c[j] x_{t-K+1+j})
    [dt,B,C] = x_t W_x, each through its own RMS norm
    delta_t  = softplus(dt W_dt + b_dt)
    S_t      = exp(delta_t A) S_{t-1} + (delta_t x_t) B_t     (float32)
    y_t      = S_t C_t + D x_t
    out      = (y_t silu(z_t)) W_out

**What a sequence carries between programs** is, a Mamba layer, the
state ``S [N, d]`` (float32) and the last ``K - 1`` pre-convolution
``x`` (the model's dtype) — a fixed size whatever its length — and, an
attention layer, one ``k`` and one ``v`` row a token (``kv_heads x
head_dim`` wide: no copy a query head). The first lives in a SLOT of the
cache (``serving/kvcache.py``: ``pool_kinds`` / ``state_layout``; ONE
entry of slots holds every Mamba layer's state, ``[slots, layers, N,
d]``), the second in the paged pool, and every program takes each row's
slot beside its block table. A prefill starts from a zero state (it never
reads the slot, so a reused slot needs no clearing) and leaves the state
AT THE PROMPT'S LAST REAL TOKEN: the prompt is right-padded to its
bucket, and ``ops/ssm.py:ssm_scan`` stops each row at its own count. A
decode step updates slot and tail in place; a suffix chunk continues
from the slot where its ``starts`` is not 0.

The three RMS norms are the CONFIGURATION's (its ``ssm_inner_norms``,
True here): ``models/shared_cache_decoder.py``'s configuration says
False and runs this mixer as Mamba-1 publishes it, without them, and
takes the scan's output BEFORE the gate (:func:`scan_prefill`,
:func:`scan_step`).

Matrices are in the model's dtype (bfloat16); norms' gains, the
convolution, ``A``, ``D``, ``b_dt``, the state, ``delta``, softmax and
logits float32. Embedding and head are tied.

**A run of Mamba layers is a loop.** The parameters of consecutive
Mamba layers are held stacked and a program walks them with
``lax.scan`` (the state entry is the loop's carry, the layer an index
into it), so a program compiles one Mamba layer a run and each
attention layer, not every layer: 2 s a program against 8 s unrolled
at 28 layers, and a serving cell warms about a hundred programs.
"""
from __future__ import annotations

import numpy as np

__all__ = ["SSMHybridConfig", "SSMHybridServingModel",
           "ssm_hybrid_param_shapes", "layer_params", "mixer_prefill",
           "mixer_step", "scan_prefill", "scan_step"]

# int32 counters every program returns: real tokens x Mamba layers
COUNTERS = ("ssm_rows",)


class SSMHybridConfig:
    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads,
                 intermediate_size, attn_layer_period, attn_layer_offset,
                 ssm_state_size=16, ssm_conv_width=4, ssm_dt_rank=None,
                 ssm_expand=2, head_dim=None, rms_norm_eps=1e-6,
                 max_position_embeddings=262144, dtype="bfloat16"):
        if num_attention_heads % num_key_value_heads:
            raise ValueError(
                f"{num_key_value_heads} key/value heads do not divide "
                f"{num_attention_heads} query heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.intermediate_size = intermediate_size
        self.attn_layer_period = attn_layer_period
        self.attn_layer_offset = attn_layer_offset
        self.ssm_state_size = ssm_state_size
        self.ssm_conv_width = ssm_conv_width
        self.ssm_dt_rank = ssm_dt_rank or -(-hidden_size // 16)
        self.d_inner = ssm_expand * hidden_size
        # the jamba family's RMS norms on dt, B and C (Mamba-1 as
        # published has none: ``models/shared_cache_decoder.py``)
        self.ssm_inner_norms = True
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype

    def is_attention(self, layer):
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def ssm_layers(self):
        return sum(not self.is_attention(i)
                   for i in range(self.num_hidden_layers))

    def serving_model(self):
        return SSMHybridServingModel(self)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def ssm_hybrid_param_shapes(config):
    """``{name: (shape, kind)}`` of every serving parameter; ``kind``
    is ``"matrix"`` (the model's dtype) or one of the float32 kinds
    ``"norm"``, ``"conv"`` (the taps, ``[K, d]``), ``"bias"``,
    ``"dt_bias"``, ``"a_log"`` (``[d, N]``, as published) and
    ``"skip"`` (``D``)."""
    c = config
    h, d, n = c.hidden_size, c.d_inner, c.ssm_state_size
    heads = c.num_attention_heads + 2 * c.num_key_value_heads
    out = {"lm_embed": ((c.vocab_size, h), "matrix"),
           "lm_norm": ((h,), "norm")}
    for i in range(c.num_hidden_layers):
        p = f"lm_h{i}_"
        out.update({
            p + "mixer_norm": ((h,), "norm"),
            p + "ffn_norm": ((h,), "norm"),
            p + "mlp_gate_up": ((h, 2 * c.intermediate_size), "matrix"),
            p + "mlp_down": ((c.intermediate_size, h), "matrix")})
        if c.is_attention(i):
            out.update({
                # q, k and v side by side, as gate and up are
                p + "qkv": ((h, heads * c.head_dim), "matrix"),
                p + "o": ((c.num_attention_heads * c.head_dim, h),
                          "matrix")})
            continue
        out.update({
            p + "in_proj": ((h, 2 * d), "matrix"),
            p + "conv_w": ((c.ssm_conv_width, d), "conv"),
            p + "conv_b": ((d,), "bias"),
            p + "x_proj": ((d, c.ssm_dt_rank + 2 * n), "matrix"),
            p + "dt_norm": ((c.ssm_dt_rank,), "norm"),
            p + "b_norm": ((n,), "norm"),
            p + "c_norm": ((n,), "norm"),
            p + "dt_proj": ((c.ssm_dt_rank, d), "matrix"),
            p + "dt_bias": ((d,), "dt_bias"),
            p + "a_log": ((d, n), "a_log"),
            p + "d": ((d,), "skip"),
            p + "out_proj": ((d, h), "matrix")})
    return out


def layer_params(config, lookup, i):
    """Layer ``i``'s parameters by their short names from
    ``lookup(name)``: matrices in the model's dtype, the rest float32;
    ``a_log`` becomes ``a_t = -exp(a_log)^T`` (``[N, d]``: how the
    state lies)."""
    import jax.numpy as jnp
    dtype = jnp.dtype(config.dtype)
    p = f"lm_h{i}_"
    blk = {k[len(p):]: jnp.asarray(
               lookup(k), dtype if kind == "matrix" else jnp.float32)
           for k, (_, kind) in ssm_hybrid_param_shapes(config).items()
           if k.startswith(p)}
    if "a_log" in blk:
        blk["a_t"] = -jnp.exp(blk.pop("a_log")).T
    return blk


def ssm_hybrid_serving_params(config, lookup):
    """The parameter pytree from ``lookup(name)`` (:func:`layer_params`
    a layer). ``runs`` is the layers in order, as ``(stacked Mamba
    layers or None, the attention layer after them or None)``: each
    leaf of the first has a leading axis, the run's layers."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(config.dtype)

    # a run is stacked as soon as it ends and its layers' own arrays
    # let go: a lookup that hands its arrays over (not a copy of them)
    # then never holds the model twice
    stack = jax.jit(lambda *blks: jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *blks))
    runs, run = [], []
    for i in range(config.num_hidden_layers):
        if not config.is_attention(i):
            run.append(layer_params(config, lookup, i))
            continue
        runs.append((stack(*run) if run else None,
                     layer_params(config, lookup, i)))
        run = []
    if run:
        runs.append((stack(*run), None))
    return {"embed": jnp.asarray(lookup("lm_embed"), dtype),
            "norm": jnp.asarray(lookup("lm_norm"), jnp.float32),
            "runs": runs}


# ---------------------------------------------------------------------------
# the Mamba mixer
# ---------------------------------------------------------------------------

def _rms32(x, weight, eps):
    """RMS norm of a float32 row, left float32."""
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _conv(config, blk, window):
    """The convolution's output where its ``K`` taps lie on ``window
    [..., K + T - 1, d]``: ``[..., T, d]`` in the window's dtype."""
    import jax
    import jax.numpy as jnp
    k = config.ssm_conv_width
    t = window.shape[-2] - k + 1
    acc = blk["conv_b"]
    for j in range(k):
        acc = acc + blk["conv_w"][j] * jax.lax.slice_in_dim(
            window, j, j + t, axis=-2).astype(jnp.float32)
    return jax.nn.silu(acc).astype(window.dtype)


def _selective(config, blk, x):
    """``(delta [..., d], B, C [..., N])``, float32, of the convolved
    ``x``."""
    import jax
    import jax.numpy as jnp
    c = config
    r, n = c.ssm_dt_rank, c.ssm_state_size
    dbc = jnp.dot(x, blk["x_proj"], preferred_element_type=jnp.float32)

    def normed(part, gain):
        # the jamba family's norms are the CONFIGURATION's: Mamba-1 as
        # published has none
        return _rms32(part, blk[gain], c.rms_norm_eps) \
            if c.ssm_inner_norms else part

    dt = normed(dbc[..., :r], "dt_norm")
    b = normed(dbc[..., r:r + n], "b_norm")
    cc = normed(dbc[..., r + n:], "c_norm")
    delta = jax.nn.softplus(
        jnp.dot(dt.astype(x.dtype), blk["dt_proj"],
                preferred_element_type=jnp.float32) + blk["dt_bias"])
    return delta, b, cc


def _gated_out(blk, y, z):
    import jax
    import jax.numpy as jnp
    gate = jax.nn.silu(z.astype(jnp.float32))
    return (y.astype(jnp.float32) * gate).astype(y.dtype) @ blk["out_proj"]


def mixer_prefill(config, blk, u, tail, s0, lengths):
    """The mixer over ``u [B, T, hidden]`` from the tail ``[B, K - 1,
    d]`` and the state ``s0 [B, N, d]`` each row starts with; ``lengths
    [B]`` real tokens a row. Returns ``(out [B, T, hidden], tail, S)``,
    the last two as of each row's last real token."""
    y, z, new_tail, s = scan_prefill(config, blk, u, tail, s0, lengths)
    return _gated_out(blk, y, z), new_tail, s


def scan_prefill(config, blk, u, tail, s0, lengths):
    """:func:`mixer_prefill` up to the gate: ``(y [B, T, d], z, tail,
    S)``, the scan's output before ``silu(z)`` and ``W_out``."""
    import jax.numpy as jnp
    from ..ops.ssm import ssm_scan
    d = config.d_inner
    xz = u @ blk["in_proj"]
    x, z = xz[..., :d], xz[..., d:]
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    # the K - 1 rows that end at the last real token; none: the old tail
    at = lengths[:, None] + jnp.arange(tail.shape[1])[None, :]
    new_tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
    x = _conv(config, blk, window)
    delta, b, c = _selective(config, blk, x)
    y, s = ssm_scan(x, delta, blk["a_t"], b, c, blk["d"], s0, lengths)
    return y, z, new_tail, s


def mixer_step(config, blk, u, tail, pool, slots, layer):
    """One token a row: ``u [B, hidden]``, ``tail [B, K - 1, d]``, the
    state in ``pool [slots, layers, N, d]`` at ``[slots [B], layer]``.
    Returns ``(out [B, hidden], tail, pool)``."""
    y, z, window, pool = scan_step(config, blk, u, tail, pool, slots, layer)
    return _gated_out(blk, y, z), window[:, 1:], pool


def scan_step(config, blk, u, tail, pool, slots, layer):
    """:func:`mixer_step` up to the gate: ``(y [B, d], z, window [B,
    K, d], pool)``; the new tail is the window's last ``K - 1`` rows."""
    import jax.numpy as jnp
    from ..ops.ssm import ssm_step
    d = config.d_inner
    xz = u @ blk["in_proj"]
    x, z = xz[..., :d], xz[..., d:]
    window = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    x = _conv(config, blk, window)[:, 0]
    delta, b, c = _selective(config, blk, x)
    y, pool = ssm_step(pool, slots, layer, x, delta, blk["a_t"], b, c,
                       blk["d"])
    return y, z, window, pool


# ---------------------------------------------------------------------------
# the forward, behind the engine's programs
# ---------------------------------------------------------------------------

def _forward(params, config, pools, x, mamba, attention):
    """Every layer over the residual ``x``. ``pools`` is the state
    entry, then an entry an attention layer. ``mamba(blk, state, layer,
    u) -> (out, state)`` and ``attention(blk, layer pools, q, k, v) ->
    (context, layer pools)`` are the program's two backends; a run of
    Mamba layers is a ``lax.scan`` over its stacked parameters with the
    state entry as the carry. Returns ``(x before the final norm, new
    pools)``."""
    import jax
    import jax.numpy as jnp
    from ..ops.moe import swiglu
    from .decoder_parts import rms
    c = config
    eps = c.rms_norm_eps
    nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

    def feed_forward(blk, x):
        return x + swiglu(rms(x, blk["ffn_norm"], eps),
                          blk["mlp_gate_up"], blk["mlp_down"])

    def mamba_layer(carry, step):
        x, state = carry
        blk, layer = step
        out, state = mamba(blk, state, layer,
                           rms(x, blk["mixer_norm"], eps))
        return (feed_forward(blk, x + out), state), None

    state, rows = pools[0], list(pools[1:])
    done = 0
    for i, (run, blk) in enumerate(params["runs"]):
        if run is not None:
            n = run["mixer_norm"].shape[0]
            (x, state), _ = jax.lax.scan(
                mamba_layer, (x, state),
                (run, done + jnp.arange(n, dtype=jnp.int32)))
            done += n
        if blk is None:
            continue
        qkv = rms(x, blk["mixer_norm"], eps) @ blk["qkv"]
        q = qkv[..., :nq * hd].reshape(*x.shape[:-1], nq, hd)
        k = qkv[..., nq * hd:(nq + nkv) * hd]
        v = qkv[..., (nq + nkv) * hd:]
        ctx, rows[i] = attention(blk, rows[i], q, k, v)
        x = feed_forward(blk, x + ctx.reshape(
            *x.shape[:-1], nq * hd).astype(x.dtype) @ blk["o"])
    return x, [state] + rows


def _head(params, config, x):
    """Float32 logits of rows ``x [B, hidden]``: the final norm, then
    the tied embedding."""
    import jax.numpy as jnp
    from .decoder_parts import rms
    x = rms(x, params["norm"], config.rms_norm_eps)
    return jnp.einsum("bh,vh->bv", x, params["embed"],
                      preferred_element_type=jnp.float32)


def _counted(config, valid, logits):
    """The int32 vector a program returns: ``COUNTERS``, then a record
    a batch row — the bits of its best float32 logit."""
    import jax
    import jax.numpy as jnp
    best = jax.lax.bitcast_convert_type(
        jnp.max(logits, axis=-1).astype(jnp.float32), jnp.int32)
    rows = jnp.sum(valid).astype(jnp.int32) * config.ssm_layers
    return jnp.concatenate([rows[None], best])


def _repeat_kv(config, rows):
    """``[..., kv_heads x D]`` rows as ``[..., heads, D]``: each
    key/value head under the query heads that read it."""
    import jax.numpy as jnp
    c = config
    rows = rows.reshape(*rows.shape[:-1], c.num_key_value_heads, 1,
                        c.head_dim)
    group = c.num_attention_heads // c.num_key_value_heads
    return jnp.broadcast_to(
        rows, (*rows.shape[:-2], group, c.head_dim)).reshape(
            *rows.shape[:-3], c.num_attention_heads, c.head_dim)


def _write_kv(layer, slots, k, v):
    from .decoder_parts import pool_scatter
    return {"k": pool_scatter(layer["k"], slots, k),
            "v": pool_scatter(layer["v"], slots, v)}


def _scale(config):
    return 1.0 / float(np.sqrt(config.head_dim))


def _tails(config, state, slots, layer, fresh=None):
    """Each row's tail of ``layer``, ``[B, K - 1, d]``, out of its slot;
    zeros where ``fresh [B]`` says the row starts a sequence."""
    import jax
    import jax.numpy as jnp
    k = config.ssm_conv_width - 1
    tail = jax.lax.dynamic_slice_in_dim(state["conv"], layer * k, k,
                                        axis=1)[slots]
    return tail if fresh is None else jnp.where(
        fresh[:, None, None], jnp.zeros_like(tail), tail)


def _write_tails(config, state, slots, layer, tail):
    import jax.numpy as jnp
    k = config.ssm_conv_width - 1
    return state["conv"].at[slots[:, None],
                            (layer * k + jnp.arange(k))[None, :]].set(tail)


def ssm_hybrid_paged_prefill(params, pools, ids, slot_idx, last_pos,
                             state_slots, config):
    """Prompt phase: causal forward over ``ids [B, P]`` (right-padded)
    that scatters every position's ``k`` / ``v`` row into ``slot_idx
    [B, P]`` (padding points at the scratch block) and leaves each
    row's state and tail, AS OF ITS LAST REAL TOKEN, in slot
    ``state_slots [B]`` (padded rows: the scratch slot 0). Starts from
    a zero state: the slot is written, never read. ``last_pos [B]``:
    that row alone goes through the head. Returns ``((logits [B, V],
    counters), pools)``; jit with ``pools`` donated."""
    import jax.numpy as jnp
    from ..ops.attention import mla_expanded_attention
    valid = slot_idx >= pools[1]["k"].shape[1]      # off the scratch block
    lengths = jnp.sum(valid, axis=1).astype(jnp.int32)
    c = config
    rows = ids.shape[0]

    def mamba(blk, state, layer, u):
        out, tail, s = mixer_prefill(
            c, blk, u,
            jnp.zeros((rows, c.ssm_conv_width - 1, c.d_inner), u.dtype),
            jnp.zeros((rows, c.ssm_state_size, c.d_inner), jnp.float32),
            lengths)
        return out, {
            "conv": _write_tails(c, state, state_slots, layer, tail),
            "ssm": state["ssm"].at[state_slots, layer].set(s)}

    def attention(blk, layer, q, k, v):
        ctx = mla_expanded_attention(q, _repeat_kv(config, k),
                                     _repeat_kv(config, v), _scale(config))
        return ctx, _write_kv(layer, slot_idx, k, v)

    x, new_pools = _forward(params, config, pools, params["embed"][ids],
                            mamba, attention)
    at = last_pos.astype(jnp.int32)
    logits = _head(params, config, jnp.take_along_axis(
        x, at[:, None, None], axis=1)[:, 0])
    return (logits, _counted(config, valid, logits)), new_pools


def ssm_hybrid_paged_step(params, pools, tokens, positions, slot_idx,
                          write_slots, state_slots, config, pick=None):
    """One token a row of a RAGGED batch (``models/gpt.py:gpt_paged_step``
    has the arguments): each row's ``k`` / ``v`` row goes to
    ``write_slots [B]`` and attention reads the rows gathered through
    ``slot_idx [B, S]``; each row's state and tail are updated in slot
    ``state_slots [B]``. With ``pick="greedy"`` returns ``(int32 [B +
    n]: each lane's argmax, then the counters; pools)``, with
    ``pick=None`` ``((logits [B, V], counters), pools)``."""
    import jax.numpy as jnp
    from ..ops.attention import grouped_decode_attention
    if pick not in (None, "greedy"):
        raise ValueError(f"pick must be None or 'greedy', got {pick!r}")
    valid = write_slots >= pools[1]["k"].shape[1]

    def mamba(blk, state, layer, u):
        out, tail, pool = mixer_step(
            config, blk, u, _tails(config, state, state_slots, layer),
            state["ssm"], state_slots, layer)
        return out, {"conv": _write_tails(config, state, state_slots,
                                          layer, tail), "ssm": pool}

    def attention(blk, layer, q, k, v):
        layer = _write_kv(layer, write_slots, k, v)
        return grouped_decode_attention(
            q, layer["k"], layer["v"], slot_idx, positions,
            _scale(config)), layer

    x, new_pools = _forward(params, config, pools,
                            params["embed"][tokens], mamba, attention)
    logits = _head(params, config, x)
    counted = _counted(config, valid, logits)
    if pick == "greedy":
        return jnp.concatenate(
            [jnp.argmax(logits, axis=-1).astype(jnp.int32),
             counted]), new_pools
    return (logits, counted), new_pools


def ssm_hybrid_paged_suffix_prefill(params, pools, ids, starts, slot_idx,
                                    write_slots, state_slots, lengths,
                                    config):
    """A CHUNK of ``lengths [B]`` real prompt tokens a row (``ids [B,
    C]``, right-padded) from token offset ``starts [B]``: attention over
    the whole history gathered through ``slot_idx [B, S]``, the scan
    CONTINUED from the slot's state and tail (from zero where
    ``starts`` is 0). Each row's last real position alone goes through
    the head: returns ``((logits [B, V], counters), pools)``."""
    import jax.numpy as jnp
    from ..ops.attention import grouped_prefill_attention
    lengths = lengths.astype(jnp.int32)
    valid = jnp.arange(ids.shape[1])[None, :] < lengths[:, None]
    fresh = starts == 0

    def mamba(blk, state, layer, u):
        s0 = jnp.where(fresh[:, None, None], 0.0,
                       state["ssm"][state_slots, layer])
        out, tail, s = mixer_prefill(
            config, blk, u,
            _tails(config, state, state_slots, layer, fresh), s0, lengths)
        return out, {
            "conv": _write_tails(config, state, state_slots, layer, tail),
            "ssm": state["ssm"].at[state_slots, layer].set(s)}

    def attention(blk, layer, q, k, v):
        layer = _write_kv(layer, write_slots, k, v)
        return grouped_prefill_attention(
            q, layer["k"], layer["v"], slot_idx, starts,
            _scale(config)), layer

    x, new_pools = _forward(params, config, pools, params["embed"][ids],
                            mamba, attention)
    at = jnp.maximum(lengths - 1, 0)
    logits = _head(params, config, jnp.take_along_axis(
        x, at[:, None, None], axis=1)[:, 0])
    return (logits, _counted(config, valid, logits)), new_pools


# ---------------------------------------------------------------------------
# what the engine takes the model as
# ---------------------------------------------------------------------------

class SSMHybridServingModel:
    """The serving-model interface (``docs/serving.md``) for an
    :class:`SSMHybridConfig`: layers with rows (attention) and layers
    with state (Mamba)."""

    prefill_last_row = True
    counter_names = COUNTERS
    vector_counter = None
    # a row's record: the bits of its best float32 logit
    row_record_width = 1

    def __init__(self, config):
        self.config = config
        self.vocab_size = config.vocab_size
        self.max_positions = config.max_position_embeddings
        # the cache's entries: ONE of slots for every Mamba layer's
        # state, then the paged pools of each attention layer
        self.pool_kinds = ("state",) + ("rows",) * (
            config.num_hidden_layers - config.ssm_layers)

    def read_records(self, records):
        """``Future.token_records [n, 1]`` taken apart: ``{"best_logit":
        [n] float32}`` of the row that decided each generated token."""
        records = np.ascontiguousarray(records, np.int32)
        return {"best_logit": records[:, 0].view(np.float32)}

    def cache_layout(self):
        """An attention layer's pools: ONE ``k`` and ONE ``v`` row a
        token, ``kv_heads x head_dim`` wide, in the model's dtype."""
        c = self.config
        width = c.num_key_value_heads * c.head_dim
        return (("k", width, c.dtype), ("v", width, c.dtype))

    def state_layout(self):
        """What a slot holds: every Mamba layer's state ``[layers, N,
        d]`` float32 and convolution tail, ``K - 1`` rows a layer one
        under the other (a ``[layers, K - 1, d]`` buffer would pad each
        layer's three rows to a sixteen-row tile)."""
        c = self.config
        return (("ssm", (c.ssm_layers, c.ssm_state_size, c.d_inner),
                 "float32"),
                ("conv", (c.ssm_layers * (c.ssm_conv_width - 1),
                          c.d_inner), c.dtype))

    def params(self, lookup):
        return ssm_hybrid_serving_params(self.config, lookup)

    @property
    def _itemsize(self):
        import jax.numpy as jnp     # numpy alone does not know bfloat16
        return jnp.dtype(self.config.dtype).itemsize

    def param_bytes(self):
        return int(sum(
            int(np.prod(shape)) * (self._itemsize if kind == "matrix"
                                   else 4)
            for shape, kind
            in ssm_hybrid_param_shapes(self.config).values()))

    def prefill_bytes_per_token(self):
        """Bytes of temporaries one prompt token costs a prefill
        program at its widest point, the Mamba mixer, if nothing were
        fused: ``x`` before and after the convolution, ``z``, ``y`` and
        the gated product in the model's dtype, ``delta`` float32,
        ``B`` and ``C`` broadcast along 128 lanes for the scan kernel;
        beside them the SwiGLU's two rows and six float32
        ``hidden``-wide rows: 219,136 at the published widths. The
        largest programs compiled for the described v5e hold 79-83 KB
        a token (``memory_analysis``, PR 43: the layers' loop reuses
        one layer's buffers): the count errs to the safe side."""
        c = self.config
        return (c.d_inner * (5 * self._itemsize + 2 * 4)
                + 2 * c.ssm_state_size * 128 * 4
                + 3 * c.intermediate_size * self._itemsize
                + 6 * c.hidden_size * 4)

    def program(self, kind):
        """``(function, static keywords)`` of one of the engine's four
        programs."""
        fn = {"prefill": ssm_hybrid_paged_prefill,
              "decode": ssm_hybrid_paged_step,
              "decode_logits": ssm_hybrid_paged_step,
              "suffix_prefill": ssm_hybrid_paged_suffix_prefill}[kind]
        static = {"config": self.config}
        if kind == "decode":
            static["pick"] = "greedy"
        return fn, static
