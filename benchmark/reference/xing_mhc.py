"""Plain reference for the ``xing_mhc`` family: a latent-attention
mixture-of-experts decoder whose residual path is ``n`` streams mixed
by learned, Sinkhorn-projected maps (mHC: manifold-constrained
hyper-connections, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606) and whose query is a low-rank pair. float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no
cache, no kernel, attention EXPANDED one head at a time, a dense loop
over the experts, its own Sinkhorn loop and its own query LoRA. It
upcasts the SAME bfloat16-valued weights the engine holds, one layer
and one expert at a time, so it fits beside them on the chip.

The equations (``X`` is ``[T, n, C]``, n = ``hc_mult``; ISSUE 39):

* a sublayer ``F`` with its own maps ``(phi, a, b)``::

      x~ = vec(X) [T, nC];  x' = x~ rsqrt(mean(x~^2) + hc_eps)
      z = x' phi                                   [T, n + n + n*n]
      Hpre  = sigmoid(a_pre z[:n] + b_pre)
      Hpost = 2 sigmoid(a_post z[n:2n] + b_post)
      M = exp(clip(a_res z[2n:] + b_res, clamp_min, clamp_max)) [n, n]
      Hres = hc_sinkhorn_iters x { rows of M /= max(sum, hc_eps) ;
                                   columns of M /= max(sum, hc_eps) }
      u = sum_j Hpre[j] X[j];  y = F(rms(u))
      X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y

* attention: ``c = rms(h W_kva[:, :L])``, ``k_r = rope(h W_kva[:, L:])``;
  ``q = rms(h W_qa) W_qb`` per head of ``nope + rope`` with NO norm a
  head, ``q = [q_n ; rope(q_r)]``; the rest as
  ``reference/sarvam_mla.py`` has it (per-head keys and values rebuilt
  from the latent, YaRN's softmax scale).
* dense and expert feed-forward, the router (sigmoid scores, a bias
  that moves the selection only, the chosen ``top_k`` normalised and
  scaled: the published ``scoring_func`` / ``topk_method`` /
  ``norm_topk_prob``; ``n_group = topk_group = 1``: no group limit):
  ``reference/sarvam_mla.py``'s own functions, which are as plain.
* the streams start as ``n`` copies of the embedding and are SUMMED
  before the final RMS norm; head over the whole vocabulary.

Departures from the source model, each shared with the program under
test so that both compute one function
(``configs/xing4.0-29b-a4b-stage.json`` ``assumed`` / ``not_served``):
entry by copies and exit by sum; rows normalised before columns;
``hc_eps`` as the norm's epsilon and the floor under each Sinkhorn sum;
the stream rounded to the serving dtype between sublayers and every map
in float32; the rotation in halves; the prediction module
(``num_nextn_predict_layers``) not loaded.

Nothing here comes from the program under test: the module imports
nothing of ``hetu_tpu`` (``reference/sarvam_mla.py``, whose rotary
tables, router and experts it uses, imports nothing of it either).

``MUTANTS`` are deliberate faults of this reference, for the run's log
(``families/xing_mhc.py`` shows each failing the part that answers for
it): the sarvam reference's six, and five of the residual path
(``BEHIND_THE_NORM`` says why the issue's sixth, a fault of the exit,
is logged and not counted).
``all_8bit`` is the lower-precision control: every bfloat16 matrix
rounded to 8 bits (the maps are float32 as stated and stay).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import sarvam_mla as base

MHC_MUTANTS = ("sinkhorn_1_iter", "sinkhorn_columns_only", "hpost_no_2",
               "hpre_softmax", "no_clamp")
MUTANTS = base.MUTANTS + MHC_MUTANTS
# Faults of the EXIT, which the final RMS norm hides: ISSUE 39 asked for
# "the streams averaged for summed at exit", and the norm divides any
# positive factor out again, so no output can show it; the first stream
# alone for the sum of all is nearly as well hidden (the streams start
# as copies and every doubly stochastic Hres draws them together, so
# each is close to a multiple of their sum: a bfloat16 engine cannot
# be told from it within the limits the 8-bit control fails; the
# float32 CPU test tells it). Both are run and logged as what they are,
# and neither has to be caught.
BEHIND_THE_NORM = ("exit_mean", "exit_first_stream")
CONTROL = base.CONTROL
# the faults that are a property of the whole forward
_WHOLE = ("no_rope", CONTROL) + BEHIND_THE_NORM

_f32 = base._f32
rms = base.rms


def sinkhorn(m, iters, eps, columns_only=False):
    """``iters`` x (rows, then columns) of ``m [T, n, n]``."""
    for _ in range(iters):
        if not columns_only:
            m = m / jnp.maximum(jnp.sum(m, axis=2, keepdims=True), eps)
        m = m / jnp.maximum(jnp.sum(m, axis=1, keepdims=True), eps)
    return m


def hc_maps(x, phi, scale, bias, config, mutant=None):
    """``(Hpre [T, n], Hpost [T, n], Hres [T, n, n])`` of the streams
    ``x [T, n, C]`` (float32)."""
    c = config
    n, eps = c["hc_mult"], c["hc_eps"]
    t = x.shape[0]
    flat = x.reshape(t, -1)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    z = normed @ _f32(phi)
    pre = scale[0] * z[:, :n] + bias[:n]
    pre = jax.nn.softmax(pre, axis=-1) if mutant == "hpre_softmax" \
        else jax.nn.sigmoid(pre)
    post = jax.nn.sigmoid(scale[1] * z[:, n:2 * n] + bias[n:2 * n])
    if mutant != "hpost_no_2":
        post = 2.0 * post
    m = scale[2] * z[:, 2 * n:] + bias[2 * n:]
    if mutant != "no_clamp":
        m = jnp.clip(m, c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(m).reshape(t, n, n),
                   1 if mutant == "sinkhorn_1_iter"
                   else c["hc_sinkhorn_iters"], eps,
                   columns_only=mutant == "sinkhorn_columns_only")
    return pre, post, res


def read(x, pre):
    return jnp.einsum("tj,tjc->tc", pre, x)


def write(x, y, post, res, dtype):
    """``X'``, rounded to the serving dtype as the stream is held."""
    out = jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None]
    return _f32(out.astype(dtype))


def sublayer_maps(w, sub):
    return w[f"hc_{sub}_phi"], w[f"hc_{sub}_scale"], w[f"hc_{sub}_bias"]


def attention(h, positions, w, config, no_rope=False):
    """Expanded causal attention over ``h [T, hidden]`` (normed), the
    query through its low-rank pair."""
    c = config
    nh, latent = c["num_attention_heads"], c["kv_lora_rank"]
    nope, dv = c["qk_nope_head_dim"], c["v_head_dim"]
    t = h.shape[0]
    kv = h @ _f32(w["kv_a"])
    lat = rms(kv[:, :latent], w["kv_norm"], c["rms_norm_eps"])
    k_r = base.rope(kv[:, latent:], positions, c, off=no_rope)
    q = rms(h @ _f32(w["q_a"]), w["q_a_norm"], c["rms_norm_eps"]) \
        @ _f32(w["q_b"])
    q = q.reshape(t, nh, -1)
    q = jnp.concatenate(
        [q[..., :nope], base.rope(q[..., nope:], positions, c, off=no_rope)],
        -1)
    kv_b = _f32(w["kv_b"]).reshape(latent, nh, nope + dv)
    causal = positions[:, None] >= positions[None, :]
    scale = base.softmax_scale(c)

    def head(args):
        q_h, w_h = args
        kvh = lat @ w_h
        k = jnp.concatenate([kvh[:, :nope], k_r], -1)
        s = jnp.where(causal, (q_h @ k.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ kvh[:, nope:]

    ctx = jax.lax.map(head, (q.transpose(1, 0, 2),
                             kv_b.transpose(1, 0, 2)))
    return ctx.transpose(1, 0, 2).reshape(t, nh * dv) @ _f32(w["o"])


def _layer(x, positions, w, forced, config, mutant):
    c = config
    dtype = jnp.dtype(c["serve_dtype"])
    if mutant == CONTROL:       # every bfloat16 matrix in 8 bits
        w = {k: base._round_8bit(_f32(v))
             if v.ndim == 2 and k != "router" and not k.startswith("hc_")
             else v for k, v in w.items()}
    hc_mutant = mutant if mutant in MHC_MUTANTS else None   # parts only
    pre, post, res = hc_maps(x, *sublayer_maps(w, "attn"), c, hc_mutant)
    y = attention(rms(read(x, pre), w["attn_norm"], c["rms_norm_eps"]),
                  positions, w, c, no_rope=mutant == "no_rope")
    x = write(x, y, post, res, dtype)
    pre, post, res = hc_maps(x, *sublayer_maps(w, "ffn"), c, hc_mutant)
    h = rms(read(x, pre), w["ffn_norm"], c["rms_norm_eps"])
    seen = {"streams": x, "post": post, "res": res}
    if "mlp_gate_up" in w:
        y = base.swiglu(h, _f32(w["mlp_gate_up"]), _f32(w["mlp_down"]))
    else:
        experts, weights, scores, margin = base.router(
            h, w["router"], w["router_bias"], c, None, forced)
        y = base.swiglu(h, _f32(w["shared_gate_up"]),
                        _f32(w["shared_down"])) \
            + base.held_experts(h, experts, weights, w["experts_gate_up"],
                                w["experts_down"], 0,
                                CONTROL if mutant == CONTROL else None)
        seen.update(experts=experts, scores=scores, margin=margin, input=h)
    seen["y"] = y
    return write(x, y, post, res, dtype), seen


@functools.lru_cache(maxsize=None)
def _jitted_layer(config_key, mutant):
    config = dict(config_key[0], rope_scaling=dict(config_key[1]))
    return jax.jit(lambda x, positions, w, forced: _layer(
        x, positions, w, forced, config, mutant))


def _config_key(config):
    flat = tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))
    return flat, tuple(sorted(config["rope_scaling"].items()))


layer_weights = base.layer_weights


def forward(weights, config, tokens, positions, pad_to=None, mutant=None,
            forced=None):
    """The whole forward over a 1-D token sequence, layer by layer.
    Returns ``(logits [len(positions), V] float32, layers, streams)``.
    ``layers`` holds, for each EXPERT layer, what its router did at
    ``positions`` (``experts``, ``scores``, ``margin``, the normed
    ``input``), as ``reference/sarvam_mla.py:forward`` does; ``streams``
    holds, for EVERY layer, what its feed-forward sublayer's residual
    path saw there: the ``streams [n_pos, n, C]`` it read, the
    sublayer's output ``y`` and this reference's own ``post`` / ``res``
    maps. ``forced [n, expert layers, k]`` are picks to take at
    ``positions``."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros(max(pad_to or n, n), np.int32)
    ids[:n] = tokens
    rows = np.asarray(positions, np.int64)
    at = jnp.asarray(rows)
    k = config["num_experts_per_tok"]
    marked = np.zeros(len(ids), bool)
    marked[rows] = forced is not None
    layer = _jitted_layer(_config_key(config),
                          mutant if mutant in _WHOLE else None)
    with jax.default_matmul_precision("highest"):
        embed = _f32(weights["lm_embed"][jnp.asarray(ids)])
        if mutant == CONTROL:
            embed = base._round_8bit(embed)
        x = jnp.broadcast_to(embed[:, None],
                             (len(ids), config["hc_mult"], embed.shape[1]))
        pos = jnp.arange(len(ids), dtype=jnp.int32)
        layers, streams = [], []
        for i in range(config["num_hidden_layers"]):
            picks = np.zeros((len(ids), k), np.int32)
            if forced is not None and i >= config["first_k_dense_replace"]:
                picks[rows] = np.asarray(forced)[:, len(layers)]
            x, seen = layer(x, pos, layer_weights(weights, i),
                            (jnp.asarray(picks), jnp.asarray(marked)))
            seen = {k_: np.asarray(v[at]) for k_, v in seen.items()}
            streams.append({k_: seen.pop(k_)
                            for k_ in ("streams", "y", "post", "res")})
            if seen:
                layers.append(seen)
        out = x[at]
        out = {"exit_mean": jnp.mean(out, axis=1),
               "exit_first_stream": out[:, 0]}.get(
                   mutant, jnp.sum(out, axis=1))
        last = rms(out, weights["lm_norm"], config["rms_norm_eps"])
        logits = _head(last, weights["lm_head"], mutant == CONTROL)
    return logits, layers, streams


# vocabulary columns the head is upcast by at a time: the whole
# [3584, 131072] matrix in float32 is 1.9 GB beside an engine that
# fills the chip
HEAD_COLUMNS = 16384


def _head(last, head, round_8bit):
    out = []
    for at in range(0, head.shape[1], HEAD_COLUMNS):
        w = head[:, at:at + HEAD_COLUMNS]
        w = base._round_8bit(w) if round_8bit else _f32(w)
        out.append(np.asarray(last @ w))
    return np.concatenate(out, axis=1)


def logits_at(weights, config, tokens, positions, pad_to=None):
    return forward(weights, config, tokens, positions, pad_to)[0]


def residual_parts(weights, config, layer, streams, y, mutant=None,
                   bias=None):
    """One feed-forward sublayer's residual path on given rows
    (``streams [n_pos, n, C]``, ``y [n_pos, C]``, float32): ``(u,
    Hpost, Hres, X')``, ``u`` and ``X'`` rounded to the serving dtype
    — what the program's own ``ops/mhc.py`` is held to on identical
    inputs. ``bias`` replaces the sublayer's own (the clamp
    shows only where ``b_res`` is large)."""
    phi, scale, own = sublayer_maps(layer_weights(weights, layer), "ffn")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(streams, jnp.float32)
        dtype = jnp.dtype(config["serve_dtype"])
        pre, post, res = hc_maps(
            x, phi, scale, own if bias is None else jnp.asarray(bias),
            config, mutant)
        u = _f32(read(x, pre).astype(dtype))
        out = write(x, jnp.asarray(y, jnp.float32), post, res, dtype)
    return (np.asarray(u), np.asarray(post), np.asarray(res),
            np.asarray(out))
