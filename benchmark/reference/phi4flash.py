"""Plain reference of the ``phi4flash`` family: a decoder-hybrid-decoder
(SambaY, arXiv:2507.06607). ``L`` layers, ``half = L / 2``; layer ``i``
is, from ``mb_per_layer = 2``, ``num_hidden_layers`` and
``sliding_window`` alone:

* even ``i <= half``: Mamba-1 (arXiv:2312.00752) as published, no norm
  on ``dt``, ``B`` or ``C``; layer ``half`` also hands on ``m_t = y_t``,
  its scan's output before the gate (``D x`` included);
* odd ``i < half``: differential attention (arXiv:2410.05258), query
  ``t`` sees keys ``t - window < j <= t``;
* ``i = half + 1``: differential attention over every ``j <= t``; its
  ``k`` / ``v`` are what the cross layers read;
* even ``i > half + 1``: a gated memory unit, ``(silu(a W_g) * m_t)
  W_u``;
* odd ``i > half + 1``: differential cross-attention, ``q = a W_q +
  b_q`` alone against layer ``half + 1``'s ``k`` / ``v`` at ``j <= t``.

Every layer is ``h = h + mixer(LN(h)); h = h + (silu(gate) * up) W_2``
with ``[gate | up] = LN(h) W_1``; LayerNorms with gain and bias; no
positional encoding; a final LayerNorm and the token table as the head.
Differential attention pairs NEIGHBOURING heads: query heads ``(2p, 2p
+ 1)`` are pair ``p``'s two queries, key/value heads ``(2r, 2r + 1)``
key pair ``r``'s two keys and, side by side, its one value ``U_r``;
pair ``p`` reads ``r = p // (pairs / key pairs)``; ``o_p = rms(A1 U -
lambda A2 U; gain, eps) (1 - lambda_init(i))`` with ``lambda = exp(lq1 .
lk1) - exp(lq2 . lk2) + lambda_init(i)``, ``lambda_init(i) = 0.8 - 0.6
exp(-0.3 i)``.

``jax.numpy`` in float32 at ``jax.default_matmul_precision("highest")``:
no kernel, no cache, no batching, nothing of ``hetu_tpu``. One sequence,
layer by layer with each layer's weights cast as it is reached, tokens
in blocks. EVERY layer runs at EVERY position: that the layers behind
the one cache need only a prompt's last row is the program's to prove.

**Departures from the published description** (each also in the
configuration file's ``assumed``): the sizes the published config does
not hold (``head_dim``, the Mamba sizes, which projections have a bias)
are the family's code defaults as the configuration's author knows
them; the pair norm's ``eps`` is the LayerNorms'; the band counts the
query's own key among its ``window``; weights are seeded, not trained.

``forward(..., mutant=...)`` runs a FAULT of a mechanism instead, for
the checker to catch (``MUTANTS``), or a lower-precision CONTROL
(``CONTROLS``):

* ``second_map_dropped`` — ``lambda = 0`` in every attention layer;
* ``lambda_init_constant`` — ``lambda_init = 0.8`` whatever the layer;
* ``pair_norm_dropped`` — no RMS norm a pair (the gain stays);
* ``pairs_by_halves`` — pair ``p`` is heads ``(p, p + heads / 2)``, of
  queries, keys and values alike;
* ``window_halved`` — a window layer sees ``window / 2`` keys;
* ``cross_reads_window`` — the cross layers see the last ``window``
  rows alone;
* ``memory_after_gate`` — ``m_t = y_t silu(z_t)``;
* ``memory_of_layer_14`` — ``m`` is taken from the Mamba layer before
  (``half - 2``: 14 of 32);
* ``state_at_bucket_end`` — the layers see the prompt right-padded to
  its bucket (with its last token) and then the generated tokens;
  attention does not see the padding, recurrence and convolution do;
* ``slot_not_zeroed`` — every Mamba layer starts from the state this
  very sequence left behind.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MUTANTS = ("second_map_dropped", "lambda_init_constant",
           "pair_norm_dropped", "pairs_by_halves", "window_halved",
           "cross_reads_window", "memory_after_gate", "memory_of_layer_14",
           "state_at_bucket_end", "slot_not_zeroed")
# every matrix rounded to 8 bits | the state rounded to bfloat16 a token
CONTROLS = ("all_8bit", "state_bf16")
BLOCK = 1024
# a sequence is filled up to whole multiples of this, so that the
# requests of a run meet a few compiled shapes
PAD = 2048
# queries an attention call scores at once (both maps of 20 pairs over
# every key, float32: 0.7 GB at 17k keys) and vocabulary rows a head
# call casts, so that the published widths fit beside a resident engine
QUERY_BLOCK = 256
VOCAB_BLOCK = 32768

MAMBA, WINDOW, FULL, GATE, CROSS = "mamba", "window", "full", "gate", "cross"


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _round_8bit(w):
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def layer_kind(config, i):
    half = config["num_hidden_layers"] // 2
    if i <= half:
        return WINDOW if i % config["mb_per_layer"] else MAMBA
    if i == half + 1:
        return FULL
    return CROSS if i % 2 else GATE


def lambda_init(i, mutant=None):
    return 0.8 if mutant == "lambda_init_constant" \
        else 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_weights(weights, i, mutant=None):
    """Layer ``i``'s parameters as float32, by their short names."""
    p = f"lm_h{i}_"
    w = {k[len(p):]: _f32(weights[k]) for k in weights if k.startswith(p)}
    if mutant == "all_8bit":    # every matrix in 8 bits; the rest stays
        w = {k: _round_8bit(v) if v.ndim == 2 and k not in
             ("conv_w", "a_log") else v for k, v in w.items()}
    return w


@functools.partial(jax.jit, static_argnames=("eps",))
def layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain + bias


# ---------------------------------------------------------------------------
# Mamba
# ---------------------------------------------------------------------------

def mamba_block(w, u, tail, state, mutant=None):
    """``u [T, hidden]`` (normed) from the tail ``[K - 1, d]`` and the
    state ``[d, N]`` the block starts with. Returns ``(out [T, hidden],
    memory [T, d], tail, state)``."""
    with jax.default_matmul_precision("highest"):
        d, n = w["a_log"].shape
        k = w["conv_w"].shape[0]
        xz = u @ w["in_proj"]
        x, z = xz[:, :d], xz[:, d:]
        window = jnp.concatenate([tail, x])
        t = x.shape[0]
        # tap j of position i lies on window[i + j]; the last is itself
        taps = jnp.stack([window[j:j + t] for j in range(k)], axis=1)
        xc = jax.nn.silu(w["conv_b"] + jnp.einsum("tkd,kd->td", taps,
                                                  w["conv_w"]))
        r = w["dt_proj"].shape[0]
        dbc = xc @ w["x_proj"]
        dt, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
        delta = jax.nn.softplus(dt @ w["dt_proj"] + w["dt_bias"])
        a = -jnp.exp(w["a_log"])

        def token(s, step):
            x_t, delta_t, b_t, c_t = step
            s = jnp.exp(delta_t[:, None] * a) * s \
                + (delta_t * x_t)[:, None] * b_t[None, :]
            if mutant == "state_bf16":
                # an explicit rounding: a cast there and back is one
                # the compiler may drop
                s = jax.lax.reduce_precision(s, exponent_bits=8,
                                             mantissa_bits=7)
            return s, s @ c_t + w["d"] * x_t

        state, y = jax.lax.scan(token, state, (xc, delta, b, c))
        gated = y * jax.nn.silu(z)
        memory = gated if mutant == "memory_after_gate" else y
        return gated @ w["out_proj"], memory, window[t:], state


@functools.partial(jax.jit, static_argnames=("mutant",))
def mamba_blocks(w, u, state, mutant=None):
    """:func:`mamba_block` over ``u [blocks, BLOCK, hidden]``, one block
    after the other, each handing its tail and state on."""
    d = w["a_log"].shape[0]
    tail = jnp.zeros((w["conv_w"].shape[0] - 1, d), jnp.float32)

    def block(carry, rows):
        out, memory, *carry = mamba_block(w, rows, *carry, mutant)
        return tuple(carry), (out, memory)

    (_, state), (out, memory) = jax.lax.scan(block, (tail, state), u)
    return (out.reshape(-1, out.shape[-1]),
            memory.reshape(-1, memory.shape[-1]), state)


def _filled(x, block):
    """``x [T, ...]`` with zero rows behind it up to whole blocks
    (nothing before them sees them)."""
    return jnp.concatenate([x, jnp.zeros((-len(x) % block, *x.shape[1:]),
                                         x.dtype)])


def mamba_layer(w, u, mutant=None, state=None):
    """The mixer over a whole sequence ``u [T, hidden]`` in blocks, from
    ``state`` (zero unless given). Returns ``(out, memory, the state the
    last block left)``."""
    t_real = len(u)
    u = _filled(jnp.asarray(u, jnp.float32), BLOCK)
    if state is None:
        state = jnp.zeros(w["a_log"].shape, jnp.float32)
    if mutant not in ("state_bf16", "memory_after_gate"):
        mutant = None       # the faults this layer plays
    out, memory, state = mamba_blocks(
        w, u.reshape(-1, BLOCK, u.shape[1]), state, mutant)
    return out[:t_real], memory[:t_real], state


# ---------------------------------------------------------------------------
# differential attention, the gate, the feed-forward
# ---------------------------------------------------------------------------

@jax.jit
def project(u, w, bias):
    with jax.default_matmul_precision("highest"):
        return u @ w + bias


def _pairs(x, heads, mutant):
    """``x [T, heads x D]`` as ``[T, heads / 2, 2, D]``: a pair's two
    heads."""
    t = x.shape[0]
    x = x.reshape(t, heads, -1)
    if mutant == "pairs_by_halves":
        return jnp.stack([x[:, :heads // 2], x[:, heads // 2:]], axis=2)
    return x.reshape(t, heads // 2, 2, x.shape[-1])


_ATTEND_STATIC = ("heads", "kv_heads", "window", "eps", "mutant")


def _attend_block(w, q, k, v, q_pos, k_seen, init, heads, kv_heads, window,
                  eps, mutant=None):
    """Queries ``q [T, heads x D]`` at positions ``q_pos [T]`` over all
    keys ``k`` / ``v [S, kv_heads x D]``: key ``j`` counts where ``j <=
    q_pos``, ``k_seen[j]`` and, with a ``window``, ``q_pos - window <
    j``. ``init`` is the layer's ``lambda_init`` (an argument, so that
    one compiled call serves every layer). Returns the layer's mixer
    output ``[T, hidden]``."""
    with jax.default_matmul_precision("highest"):
        t, s = q.shape[0], k.shape[0]
        q = _pairs(q, heads, mutant)                    # [T, P, 2, D]
        k = _pairs(k, kv_heads, mutant)                 # [S, R, 2, D]
        v = _pairs(v, kv_heads, mutant)
        u = v.reshape(s, kv_heads // 2, -1)             # [S, R, 2 D]
        hd = q.shape[-1]
        per = heads // kv_heads
        q = q.reshape(t, kv_heads // 2, per, 2, hd)
        scores = jnp.einsum("trpmd,srmd->rpmts", q, k) / math.sqrt(hd)
        ok = (jnp.arange(s)[None, :] <= q_pos[:, None]) & k_seen[None, :]
        if window is not None:
            ok &= jnp.arange(s)[None, :] > q_pos[:, None] - window
        # finite: a padded row of ``state_at_bucket_end`` whose band
        # holds no seen key averages, and nothing real reads it
        maps = jnp.einsum("rpmts,sre->trpme", jax.nn.softmax(
            jnp.where(ok, scores, -1e30), axis=-1), u)
        lam = 0.0 if mutant == "second_map_dropped" else (
            jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
            - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + init)
        o = maps[..., 0, :] - lam * maps[..., 1, :]
        if mutant != "pair_norm_dropped":
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + eps)
        o = (o * w["pair_norm"] * (1.0 - init)).reshape(t, -1)
        return o @ w["o"] + w["o_bias"]


attend_block = jax.jit(_attend_block, static_argnames=_ATTEND_STATIC)


@functools.partial(jax.jit, static_argnames=_ATTEND_STATIC)
def attend_blocks(w, q, k, v, k_seen, init, heads, kv_heads, window, eps,
                  mutant=None):
    """:func:`attend_block` over ``q [blocks, QUERY_BLOCK, heads x D]``,
    the queries of positions 0, 1, ... in order, a block at a time."""
    pos = jnp.arange(q.shape[0] * q.shape[1]).reshape(q.shape[:2])
    out = jax.lax.map(lambda step: _attend_block(
        w, step[0], k, v, step[1], k_seen, init, heads, kv_heads, window,
        eps, mutant), (q, pos))
    return out.reshape(-1, out.shape[-1])


def attention_layer(w, u, config, layer, k_seen, kv=None, mutant=None):
    """A differential attention layer of any kind over ``u [T,
    hidden]``. ``kv``: the ``(k, v)`` a cross layer reads. Returns
    ``(out, (k, v))``."""
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    wide = w["o"].shape[0]
    narrow = wide // heads * kv_heads
    kind = layer_kind(config, layer)
    if kind == WINDOW:
        qkv = project(u, w["qkv"], w["qkv_bias"])
        q, kv = qkv[:, :wide], (qkv[:, wide:wide + narrow],
                                qkv[:, wide + narrow:])
    else:
        q = project(u, w["q"], w["q_bias"])
    if kind == FULL:
        both = project(u, w["kv"], w["kv_bias"])
        kv = (both[:, :narrow], both[:, narrow:])
    window = None
    if kind == WINDOW:
        window = config["sliding_window"] // (
            2 if mutant == "window_halved" else 1)
    elif kind == CROSS and mutant == "cross_reads_window":
        window = config["sliding_window"]
    init = jnp.float32(lambda_init(layer, mutant))
    if mutant not in ("second_map_dropped", "pair_norm_dropped",
                      "pairs_by_halves"):
        mutant = None       # the faults the call itself plays
    out = attend_blocks(
        w, _filled(q, QUERY_BLOCK).reshape(-1, QUERY_BLOCK, q.shape[1]),
        *kv, jnp.asarray(k_seen), init, heads, kv_heads, window,
        config["layer_norm_eps"], mutant)
    return out[:len(u)], kv


@jax.jit
def gate_layer(w, u, m):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ w["gate_in"]) * m) @ w["gate_out"]


@functools.partial(jax.jit, static_argnames=("eps",))
def ffn_layer(w, x, eps):
    """The feed-forward sublayer with its residual over ``x [T,
    hidden]``, ``T`` whole blocks, a block at a time."""
    def block(x):
        h = layer_norm(x, w["ffn_norm"], w["ffn_norm_bias"], eps) \
            @ w["mlp_gate_up"]
        width = h.shape[1] // 2
        return x + (jax.nn.silu(h[:, :width]) * h[:, width:]) \
            @ w["mlp_down"]

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(block, x.reshape(-1, BLOCK, x.shape[1])) \
            .reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps", "eight_bit"))
def head_rows(table, gain, bias, x, eps, eight_bit=False):
    """Logits of rows ``x`` over the rows ``table`` of the vocabulary
    (as stored: cast here, a block at a time)."""
    with jax.default_matmul_precision("highest"):
        table = _f32(table)
        if eight_bit:
            table = _round_8bit(table)
        return layer_norm(x, gain, bias, eps) @ table.T


# ---------------------------------------------------------------------------
# the whole forward
# ---------------------------------------------------------------------------

def forward(weights, config, tokens, rows, mutant=None, prompt_len=None,
            bucket=None, want_layers=()):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of the
    1-D sequence ``tokens``. ``prompt_len`` and ``bucket`` say where
    the prompt ends and what it is padded to, for
    ``state_at_bucket_end``. ``want_layers``: also return ``{layer:
    {"input": its normed mixer input [T, hidden], "memory": the memory
    [T, d] a gate reads, "kv": the (k, v) a cross layer reads}}``."""
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
    tokens = np.concatenate([tokens, np.zeros(-len(tokens) % PAD,
                                              tokens.dtype)])
    k_seen = jnp.asarray(np.concatenate([k_seen, np.ones(
        len(tokens) - len(k_seen), bool)]))
    eps = config["layer_norm_eps"]
    half = config["num_hidden_layers"] // 2
    table = weights["lm_embed"]
    x = _f32(table[jnp.asarray(tokens)])
    if mutant == "all_8bit":
        x = _round_8bit(x)
    wanted, memory, shared = {}, None, None
    memory_from = half - 2 if mutant == "memory_of_layer_14" else half
    passes = 2 if mutant == "slot_not_zeroed" else 1
    for i in range(config["num_hidden_layers"]):
        w = layer_weights(weights, i, mutant)
        u = layer_norm(x, w["mixer_norm"], w["mixer_norm_bias"], eps)
        kind = layer_kind(config, i)
        if i in want_layers:
            wanted[i] = {"input": u[:t_real]}
            if kind == GATE:
                wanted[i]["memory"] = memory[:t_real]
            if kind == CROSS:
                wanted[i]["kv"] = tuple(t[:t_real] for t in shared)
        if kind == MAMBA:
            state = None
            for _ in range(passes):
                out, m, state = mamba_layer(w, u, mutant, state)
            if i == memory_from:
                memory = m
        elif kind == GATE:
            out = gate_layer(w, u, memory)
        else:
            out, kv = attention_layer(w, u, config, i, k_seen, shared,
                                      mutant)
            if kind == FULL:
                shared = kv
        x = ffn_layer(w, x + out, eps)
    last = x[jnp.asarray(rows)]
    logits = np.concatenate([np.asarray(head_rows(
        table[at:at + VOCAB_BLOCK], _f32(weights["lm_norm"]),
        _f32(weights["lm_norm_bias"]), last, eps, mutant == "all_8bit"))
        for at in range(0, table.shape[0], VOCAB_BLOCK)], axis=1)
    return (logits, wanted) if want_layers else logits


def logits_at(weights, config, tokens, positions, pad_to=None):
    del pad_to      # blocks of BLOCK tokens: nothing to pad to
    return forward(weights, config, tokens, positions)
