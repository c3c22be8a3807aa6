"""Plain reference of the ``jamba_ssm`` family: a decoder whose layers
are Mamba-1 state-space mixers (with the ``jamba`` family's RMS norms on
``dt``, ``B`` and ``C``) except every ``attn_layer_period``-th, which is
causal softmax attention with one key/value head a group of query heads
and no positional encoding; the same dense SwiGLU in every layer; tied
embedding and head.

``jax.numpy`` in float32 at ``jax.default_matmul_precision("highest")``,
the recurrence a plain ``lax.scan`` over tokens on a state laid out as
published (``[d_inner, d_state]``): no kernel, no cache, no batching, and
nothing of ``hetu_tpu``. One sequence at a time, layer by layer, each
layer in BLOCKS of tokens that hand the convolution's tail and the
state on, so that the published widths fit beside a resident engine.

``forward(..., mutant=...)`` runs a FAULT of the mechanism instead, for
the checker to catch (``MUTANTS``), or a lower-precision CONTROL
(``CONTROLS``). The faults that are the engine's to make (which state a
prompt leaves, what a reused slot holds) are played here by giving the
reference the wrong thing at the same place:

* ``state_at_bucket_end`` — the sequence the layers see is the prompt
  right-padded to its bucket (with its last token) and then the
  generated tokens; attention does not see the padding, the recurrence
  and the convolution do;
* ``conv_tail_dropped`` — from the first generated token on, the
  convolution's taps on earlier positions read zeros;
* ``no_dt_bc_norm`` — ``dt``, ``B`` and ``C`` skip their RMS norms;
* ``no_skip`` — ``D x_t`` is left out of ``y_t``;
* ``slot_not_zeroed`` — every Mamba layer starts from the state this
  very sequence left behind, not from zero.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

MUTANTS = ("state_at_bucket_end", "conv_tail_dropped", "no_dt_bc_norm",
           "no_skip", "slot_not_zeroed")
# every matrix rounded to 8 bits | the state rounded to bfloat16 a token
CONTROLS = ("all_8bit", "state_bf16")
BLOCK = 1024


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _round_8bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def layer_weights(weights, i, mutant=None):
    """Layer ``i``'s parameters as float32, by their short names."""
    p = f"lm_h{i}_"
    w = {k[len(p):]: _f32(weights[k]) for k in weights if k.startswith(p)}
    if mutant == "all_8bit":    # every matrix in 8 bits; the rest stays
        w = {k: _round_8bit(v) if v.ndim == 2 and k not in
             ("conv_w", "a_log") else v for k, v in w.items()}
    return w


def is_attention(config, layer):
    return layer % config["attn_layer_period"] \
        == config["attn_layer_offset"]


# ---------------------------------------------------------------------------
# one block of tokens through a Mamba mixer
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "mutant"))
def mamba_block(w, u, tail, state, before, eps, mutant=None):
    """``u [T, hidden]`` (normed) from the tail ``[K - 1, d]`` and the
    state ``[d, N]`` the block starts with; ``before [K - 1 + T]`` says
    which positions of the window lie before the prompt's end (read by
    ``conv_tail_dropped`` alone). Returns ``(out [T, hidden], tail,
    state, parts)``."""
    with jax.default_matmul_precision("highest"):
        d, n = w["a_log"].shape
        k = w["conv_w"].shape[0]
        xz = u @ w["in_proj"]
        x, z = xz[:, :d], xz[:, d:]
        window = jnp.concatenate([tail, x])
        t = x.shape[0]
        # tap j of position i lies on window[i + j]; the last is itself
        taps = jnp.stack([window[j:j + t] for j in range(k)], axis=1)
        if mutant == "conv_tail_dropped":
            # a position past the prompt's end sees none before it
            early = jnp.stack([before[j:j + t] for j in range(k)], axis=1)
            taps = jnp.where((early & ~before[k - 1:, None])[..., None],
                             0.0, taps)
        xc = jax.nn.silu(w["conv_b"] + jnp.einsum("tkd,kd->td", taps,
                                                  w["conv_w"]))
        r = w["dt_norm"].shape[0]
        dbc = xc @ w["x_proj"]
        dt, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
        if mutant != "no_dt_bc_norm":
            dt = rms(dt, w["dt_norm"], eps)
            b = rms(b, w["b_norm"], eps)
            c = rms(c, w["c_norm"], eps)
        delta = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])
        a = -jnp.exp(w["a_log"])
        skip = 0.0 if mutant == "no_skip" else w["d"]

        def token(s, step):
            x_t, delta_t, b_t, c_t = step
            s = jnp.exp(delta_t[:, None] * a) * s \
                + (delta_t * x_t)[:, None] * b_t[None, :]
            if mutant == "state_bf16":
                # an explicit rounding: a cast there and back is one
                # the compiler may drop (excess precision is allowed)
                s = jax.lax.reduce_precision(s, exponent_bits=8,
                                             mantissa_bits=7)
            return s, s @ c_t + skip * x_t

        state, y = jax.lax.scan(token, state, (xc, delta, b, c))
        out = (y * jax.nn.silu(z)) @ w["out_proj"]
        return out, window[t:], state, {"y": y, "state": state}


def mamba_layer(w, u, eps, mutant=None, cut=None, state=None):
    """The mixer over a whole sequence ``u [T, hidden]`` in blocks, from
    ``state`` (zero unless given). ``cut``: where the prompt ends.
    Returns ``(out, the state the last block left)``. The last block is
    filled up with zero rows behind the sequence (one compiled block
    for every length; nothing before them sees them)."""
    d, n = w["a_log"].shape
    k = w["conv_w"].shape[0]
    t_real = len(u)
    u = jnp.concatenate([jnp.asarray(u, jnp.float32), jnp.zeros(
        (-t_real % BLOCK, u.shape[1]), jnp.float32)])
    tail = jnp.zeros((k - 1, d), jnp.float32)
    if state is None:
        state = jnp.zeros((d, n), jnp.float32)
    outs = []
    for at in range(0, len(u), BLOCK):
        t = len(u[at:at + BLOCK])
        before = np.arange(at - (k - 1), at + t) < (cut or 0)
        out, tail, state, _ = mamba_block(
            w, u[at:at + BLOCK], tail, state, jnp.asarray(before), eps,
            mutant)
        outs.append(out)
    return jnp.concatenate(outs)[:t_real], state


# ---------------------------------------------------------------------------
# attention, feed-forward
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("heads", "kv_heads"))
def qkv_block(w, u, heads, kv_heads):
    with jax.default_matmul_precision("highest"):
        qkv = u @ w["qkv"]
        hd = qkv.shape[1] // (heads + 2 * kv_heads)
        return (qkv[:, :heads * hd], qkv[:, heads * hd:(heads + kv_heads) * hd],
                qkv[:, (heads + kv_heads) * hd:])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads"))
def attend_block(w, q, k, v, q_pos, k_seen, heads, kv_heads):
    """Queries ``q [T, heads x D]`` at positions ``q_pos [T]`` over all
    keys ``k [S, kv_heads x D]``: key ``j`` counts where ``j <= q_pos``
    and ``k_seen[j]``."""
    with jax.default_matmul_precision("highest"):
        t, s = q.shape[0], k.shape[0]
        hd = q.shape[1] // heads
        q = q.reshape(t, kv_heads, heads // kv_heads, hd)
        k = k.reshape(s, kv_heads, hd)
        v = v.reshape(s, kv_heads, hd)
        scores = jnp.einsum("tgrd,sgd->grts", q, k) / jnp.sqrt(float(hd))
        ok = (jnp.arange(s)[None, :] <= q_pos[:, None]) & k_seen[None, :]
        probs = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("grts,sgd->tgrd", probs, v).reshape(t, heads * hd)
        return ctx @ w["o"]


def attention_layer(w, u, config, k_seen):
    heads = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    parts = [qkv_block(w, u[at:at + BLOCK], heads, kv)
             for at in range(0, len(u), BLOCK)]
    q, k, v = (jnp.concatenate(p) for p in zip(*parts))
    pos = jnp.arange(len(u))
    return jnp.concatenate([
        attend_block(w, q[at:at + BLOCK], k, v, pos[at:at + BLOCK],
                     k_seen, heads, kv)
        for at in range(0, len(u), BLOCK)])


@functools.partial(jax.jit, static_argnames=("eps",))
def ffn_block(w, x, eps):
    with jax.default_matmul_precision("highest"):
        h = rms(x, w["ffn_norm"], eps) @ w["mlp_gate_up"]
        width = h.shape[1] // 2
        return x + (jax.nn.silu(h[:, :width]) * h[:, width:]) \
            @ w["mlp_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_rows(embed, gain, x, eps):
    with jax.default_matmul_precision("highest"):
        return rms(x, gain, eps) @ embed.T


# ---------------------------------------------------------------------------
# the whole forward
# ---------------------------------------------------------------------------

def forward(weights, config, tokens, rows, mutant=None, prompt_len=None,
            bucket=None, want_layer=None):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of the
    1-D sequence ``tokens``. ``prompt_len`` (and, for
    ``state_at_bucket_end``, the prompt's ``bucket``) say where the
    prompt ends, for the faults that happen there. ``want_layer``: also
    return that layer's normed mixer input ``[T, hidden]``."""
    if mutant is not None and mutant not in MUTANTS + CONTROLS:
        raise ValueError(f"no such fault: {mutant!r}")
    tokens = np.asarray(tokens).reshape(-1)
    rows = np.asarray(rows)
    k_seen = np.ones(len(tokens), bool)
    if mutant == "state_at_bucket_end":
        pad = bucket - prompt_len
        tokens = np.concatenate([
            tokens[:prompt_len], np.full(pad, tokens[prompt_len - 1]),
            tokens[prompt_len:]])
        k_seen = np.ones(len(tokens), bool)
        k_seen[prompt_len:bucket] = False
        rows = np.where(rows >= prompt_len, rows + pad, rows)
    # whole blocks: zero tokens behind the sequence, which no row sees
    t_real = len(tokens)
    tokens = np.concatenate([tokens, np.zeros(-len(tokens) % BLOCK,
                                              tokens.dtype)])
    k_seen = np.concatenate([k_seen, np.ones(len(tokens) - len(k_seen),
                                             bool)])
    eps = config["rms_norm_eps"]
    embed = _f32(weights["lm_embed"])
    if mutant == "all_8bit":
        embed = _round_8bit(embed)
    x = embed[jnp.asarray(tokens)]
    wanted = None
    passes = 2 if mutant == "slot_not_zeroed" else 1
    for i in range(config["num_hidden_layers"]):
        w = layer_weights(weights, i, mutant)
        u = jax.jit(rms, static_argnums=2)(x, w["mixer_norm"], eps)
        if i == want_layer:
            wanted = u[:t_real]
        if is_attention(config, i):
            out = attention_layer(w, u, config, jnp.asarray(k_seen))
        else:
            state = None
            for _ in range(passes):
                out, state = mamba_layer(w, u, eps, mutant, prompt_len,
                                         state)
        x = x + out
        x = jnp.concatenate([ffn_block(w, x[at:at + BLOCK], eps)
                             for at in range(0, len(x), BLOCK)])
    logits = head_rows(embed, _f32(weights["lm_norm"]),
                       x[jnp.asarray(rows)], eps)
    logits = np.asarray(logits)
    return logits if want_layer is None else (logits, wanted)


def logits_at(weights, config, tokens, positions, pad_to=None):
    del pad_to      # blocks of BLOCK tokens: nothing to pad to
    return forward(weights, config, tokens, positions)
