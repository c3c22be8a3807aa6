"""Plain reference for the GPT-2 family: the published forward pass in
straightforward float32 ``jax.numpy`` — no kernels, no cache, no
batching tricks — over parameters looked up by checkpoint name.

It follows "Language Models are Unsupervised Multitask Learners"
(pre-LN decoder, learned positions, tanh-approximated GELU) with the
departures the program under test makes, so that both compute the same
function: an UNTIED output head (``gpt_lm_head_weights``; GPT-2 ties it
to ``wte``), LayerNorm epsilon 1e-12 (GPT-2: 1e-5), no dropout (the
comparison runs the program in inference mode).

Every matmul runs under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul is otherwise rounded to bfloat16 passes.

Tolerances (why these numbers):

* ``LOSS_TOLERANCE`` — the training graph computes in bfloat16 with
  float32 masters AND hands its loss back as a bfloat16 scalar: 8
  mantissa bits, so at a loss of 10.8 the value moves in steps of
  0.0625 (on the chip the program read 10.8125 against this
  reference's 10.8358, 10.8298 and 10.8308 for three seeds, PR 23).
  1e-2 relative is three ulps of that output. On the random-token feed
  it has NO power: a model of all-zero logits scores ln(50257) x
  1023/1024 = 10.814, which rounds to the same bfloat16. It only says
  the loss is the right reduction of the logits.
* ``OUTPUT_TOLERANCE`` — what holds the math: the program's logits at
  EVERY position of the checked sequences against this forward. Per
  position the RMS of the difference over the vocabulary, as a share
  of the standard deviation of the reference's logits
  (``harness/stats.py:row_errors``); the worst position must stay
  under the tolerance. On the chip bfloat16 compute reads 0.0118 and
  0.0127 at the worst of 2048 positions (logits' std 0.174; my chip
  runs, PR 23); the float32 reference against itself with a fault, at
  this size, reads: a dropped layer 0.47, a non-causal mask 1.24, a
  mask that leaks the next token 0.90, a kernel that drops the second
  block of keys 0.31, one dead head of twelve in layer 0 0.32 and in
  the last layer 0.081. 0.05 is four times the rounding and under the
  smallest of these.
* ``LOGIT_TOLERANCE`` — the engine holds float32 weights but the TPU's
  default matmul precision rounds operands to bfloat16; with
  N(0, 0.02) weights the logits have a spread of about 0.6 and the top
  two lie closer than the rounding error, so token equality is not a
  test. Each generated token must score within 0.05 of the reference's
  best logit at its position, the reference teacher-forced on the
  prompt and the engine's own earlier tokens (observed gap on the chip
  for first tokens: under 0.01, PR 23). What that can see, by the
  float32 reference with a fault picking the tokens (this size, four to
  eight prompts of 160 tokens): a dropped layer, a non-causal mask or a
  missing softmax scale show at the first token (gaps 0.3-1.4); a
  decode path that ignores the generated tokens' keys shows in 1 of 4
  prompts within 8 tokens and in 8 of 8 within 32 (gaps 0.12-0.41),
  which is why a cell checks 32; one dead head in the last layer shows
  in 3 of 8 (it moves the logits by 0.08 of their std). Tokens are all
  the engine returns; logits would allow a tighter test.
"""
import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOLERANCE = 1e-2      # relative; see above
OUTPUT_TOLERANCE = 0.05    # worst position's error / std of the logits
LOGIT_TOLERANCE = 0.05     # absolute, on the chosen token's logit
LN_EPS = 1e-12


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def logits_fn(p, ids, num_layers, num_heads):
    """[B, S] token ids -> [B, S, V] logits. ``p`` maps checkpoint
    names to float32 arrays."""
    b, s = ids.shape
    x = p["gpt_wte"][ids] + p["gpt_wpe"][:s][None]
    hidden = x.shape[-1]
    hs = hidden // num_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(num_layers):
        n = f"gpt_h{i}_"
        h = layer_norm(x, p[n + "ln1_scale"], p[n + "ln1_bias"])
        qkv = h @ p[n + "attn_qkv_weights"] + p[n + "attn_qkv_bias"]
        q, k, v = (qkv[..., j * hidden:(j + 1) * hidden]
                   .reshape(b, s, num_heads, hs).transpose(0, 2, 1, 3)
                   for j in range(3))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hs)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        ctx = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(scores, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, hidden)
        x = x + ctx @ p[n + "attn_proj_weights"] + p[n + "attn_proj_bias"]
        h = layer_norm(x, p[n + "ln2_scale"], p[n + "ln2_bias"])
        h = gelu(h @ p[n + "mlp_fc_weights"] + p[n + "mlp_fc_bias"])
        x = x + h @ p[n + "mlp_proj_weights"] + p[n + "mlp_proj_bias"]
    x = layer_norm(x, p["gpt_ln_f_scale"], p["gpt_ln_f_bias"])
    return x @ p["gpt_lm_head_weights"]


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
            if k.startswith("gpt_")}


def lm_outputs(params, config, ids, labels):
    """(loss, [logits]): the mean over ALL positions of the next-token
    cross entropy, a position labelled -1 contributing 0 (the graph's
    reduce_mean over the sparse-CE op's output), and the [B, S, V]
    logits it was taken from."""
    def f(p, ids, labels):
        logits = logits_fn(p, ids, config["num_hidden_layers"],
                           config["num_attention_heads"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return (jnp.mean(jnp.where(labels >= 0, logz - picked, 0.0)),
                logits)

    with jax.default_matmul_precision("highest"):
        loss, logits = jax.jit(f)(_f32(params),
                                  jnp.asarray(ids, jnp.int32),
                                  jnp.asarray(labels, jnp.int32))
    return float(loss), [np.asarray(logits)]


def logits_at(params, config, tokens, positions, pad_to=None):
    """[len(positions), V] logits of a 1-D token sequence at the given
    positions (each predicts the token after it). The sequence is
    padded to ``pad_to`` (causal attention keeps the padding out of the
    real positions), so one compiled program serves every length."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    ids = np.zeros((1, max(pad_to or n, n)), np.int32)
    ids[0, :n] = tokens

    def f(weights, ids, positions):
        return logits_fn(weights, ids, config["num_hidden_layers"],
                         config["num_attention_heads"])[0][positions]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(f)(
            _f32(params), jnp.asarray(ids),
            jnp.asarray(positions, jnp.int32)))
