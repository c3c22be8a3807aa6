"""Plain reference for the BERT family: BertForPreTraining's forward
and its MLM + NSP loss in straightforward float32 ``jax.numpy`` over
parameters looked up by checkpoint name.

It follows "BERT: Pre-training of Deep Bidirectional Transformers"
(post-LN encoder, learned position and segment embeddings, pooler over
the first token, MLM transform + decoder tied to the word embeddings,
NSP classifier) with the program's departures, so both compute the
same function: tanh-approximated GELU (BERT: erf), LayerNorm epsilon
1e-12 as published, the additive padding mask (mask - 1) * 10000, and
the loss as the program's graph defines it — the mean over ALL
positions of the MLM cross entropy with unlabelled positions
contributing 0 (BERT divides by the number of labelled positions), plus
the mean NSP cross entropy. No dropout: the comparison runs the program
in inference mode.

``LOSS_TOLERANCE``: the training graph computes in bfloat16 and hands
its loss back as a bfloat16 scalar (8 mantissa bits: steps of 0.0156 at
a loss of 2.5, so rounding alone is worth up to 0.31%). On the chip the
program read 2.5625 / 2.34375 / 1.921875 against this reference's
2.5725 / 2.3505 / 1.9143 for three seeds, 0.29-0.40% apart (PR 23).
1e-2 is three ulps of that output. ``OUTPUT_TOLERANCE`` is what holds
the math: the program's MLM scores at every position against this
forward, as reference/gpt2.py describes it (on the chip bfloat16
compute reads 0.0123 and 0.0116 at the worst of 256 positions, scores'
std 0.221; my chip runs, PR 23).
"""
import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOLERANCE = 1e-2      # relative; see above
OUTPUT_TOLERANCE = 0.05    # worst position's error / std of the scores
LN_EPS = 1e-12


def layer_norm(x, p, name):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + LN_EPS) * p[name + "_scale"]
            + p[name + "_bias"])


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def dense(x, p, name):
    return x @ p[name + "_weights"] + p[name + "_bias"]


def cross_entropy(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(labels != -1, logz - picked, 0.0)


def outputs_fn(p, num_layers, num_heads, input_ids, token_type_ids,
               attention_mask, mlm_labels, nsp_label):
    """(loss, [B, S, V] MLM scores)."""
    b, s = input_ids.shape
    x = (p["word_embeddings"][input_ids]
         + p["token_type_embeddings"][token_type_ids]
         + p["position_embeddings"][:s][None])
    x = layer_norm(x, p, "embeddings_layer_norm")
    hidden = x.shape[-1]
    hs = hidden // num_heads
    bias = (attention_mask.reshape(b, 1, 1, s) - 1.0) * 10000.0

    def heads(t):
        return t.reshape(b, s, num_heads, hs).transpose(0, 2, 1, 3)

    for i in range(num_layers):
        n = f"layer{i}_"
        q, k, v = (heads(dense(x, p, n + "attn_" + w))
                   for w in ("query", "key", "value"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hs) + bias
        ctx = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(scores, axis=-1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, hidden)
        x = layer_norm(dense(ctx, p, n + "attn_output") + x, p,
                       n + "attn_output_layer_norm")
        h = gelu(dense(x, p, n + "intermediate"))
        x = layer_norm(dense(h, p, n + "ffn_output") + x, p,
                       n + "ffn_output_layer_norm")
    pooled = jnp.tanh(dense(x[:, 0], p, "pooler"))
    t = layer_norm(gelu(dense(x, p, "mlm_transform")), p,
                   "mlm_transform_layer_norm")
    mlm_logits = t @ p["word_embeddings"].T + p["mlm_decoder_bias"]
    nsp_logits = dense(pooled, p, "nsp")
    loss = (jnp.mean(cross_entropy(mlm_logits, mlm_labels))
            + jnp.mean(cross_entropy(nsp_logits, nsp_label)))
    return loss, mlm_logits


def pretraining_outputs(params, config, input_ids, token_type_ids,
                        attention_mask, mlm_labels, nsp_label):
    """(loss, [MLM scores]) of one batch."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    fn = jax.jit(outputs_fn, static_argnums=(1, 2))
    with jax.default_matmul_precision("highest"):
        loss, scores = fn(
            p, config["num_hidden_layers"], config["num_attention_heads"],
            jnp.asarray(input_ids, jnp.int32),
            jnp.asarray(token_type_ids, jnp.int32),
            jnp.asarray(attention_mask, jnp.float32),
            jnp.asarray(mlm_labels, jnp.int32),
            jnp.asarray(nsp_label, jnp.int32))
    return float(loss), [np.asarray(scores)]
