"""A sparse-expert decoder that TRAINS through ``ht.Executor``:
sliding-window and full grouped-query attention layers in one stack, a
softmax router placed before attention, ReGLU experts — one chip's
SHARE of an expert-parallel job.

No reference equivalent (the reference's MoE layers are all-to-all
dispatch over every expert; ``docs/parallelism.md`` has the
comparison). The block is SmallThinker's (arXiv:2507.20984) as
``benchmark/reference/smallthinker_moe.py`` writes it out, with ``x``
the residual stream entering a layer:

    w, e = top-k softmax router of x               (float32, un-normed x)
    a    = RMSNorm_1(x)
    q, k, v = a W_q, a W_k, a W_v                  (H, G, G heads of D)
    window layer: q, k rotated; query i sees keys i - window < j <= i
    global layer: no positions at all; query i sees j <= i
    h    = x + Attn(q, k, v) W_o
    out  = h + sum_i w_i * W_down[e_i](act(W_gate[e_i] u) * W_up[e_i] u),
           u = RMSNorm_2(h), over the picks whose expert is HELD here

then a final RMS norm and an untied head. No bias anywhere. Every node
is a graph op with gradient ops of its own (``rms_normalization_op``,
``rotary_op``, ``flash_attention_op(num_kv_heads=, window=)``,
``router_op``, ``held_experts_op``); the experts' parameters are two
stacks over the experts held (``[held, hidden, 2 * width]``, ``[held,
width, hidden]``) that go through masters, working copies and Adam as
any other parameter does.

The chip's share: ``experts_held = (first, count)`` of ``num_experts``
routed ones (the router stays ``num_experts`` wide), and ``vocab_size``
is the slice of the vocabulary held here. What the absent experts would
add is left out, and no code stands in for the exchange.
"""
from __future__ import annotations

import numpy as np

from .. import initializers as init
from ..ops import (array_reshape_op, embedding_lookup_op,
                   flash_attention_op, held_experts_op, matmul_op,
                   rms_normalization_op, rotary_op, router_op,
                   router_picks_op, softmaxcrossentropy_sparse_op)

__all__ = ["SparseDecoderConfig", "SparseDecoderModel",
           "SparseDecoderLMHeadModel", "sparse_decoder_param_shapes"]


class SparseDecoderConfig:
    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, window_layout, rope_layout,
                 sliding_window, moe_ffn_hidden_size, num_experts,
                 num_experts_per_tok, experts_held=None, activation="relu",
                 rope_theta=10000.0, rms_norm_eps=1e-6,
                 initializer_range=0.02, embedding_range=None):
        if len(window_layout) != len(rope_layout):
            raise ValueError("window_layout and rope_layout name the same "
                             "layers")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.window_layout = [bool(w) for w in window_layout]
        self.rope_layout = [bool(r) for r in rope_layout]
        self.num_hidden_layers = len(window_layout)
        self.sliding_window = sliding_window
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.experts_held = tuple(experts_held or (0, num_experts))
        first, held = self.experts_held
        if first < 0 or held < 1 or first + held > num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{num_experts}")
        self.activation = activation
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        # the token embedding's own spread (None: as every other matrix)
        self.embedding_range = initializer_range \
            if embedding_range is None else embedding_range


def sparse_decoder_param_shapes(config):
    """``{checkpoint name: shape}`` of every parameter the builders
    below make."""
    c = config
    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    held, w = c.experts_held[1], c.moe_ffn_hidden_size
    out = {"sparse_embed": (c.vocab_size, c.hidden_size),
           "sparse_ln_f_scale": (c.hidden_size,),
           "sparse_lm_head": (c.hidden_size, c.vocab_size)}
    for i in range(c.num_hidden_layers):
        p = f"sparse_h{i}"
        out.update({
            f"{p}_router": (c.hidden_size, c.num_experts),
            f"{p}_ln1_scale": (c.hidden_size,),
            f"{p}_attn_q": (c.hidden_size, q),
            f"{p}_attn_k": (c.hidden_size, kv),
            f"{p}_attn_v": (c.hidden_size, kv),
            f"{p}_attn_o": (q, c.hidden_size),
            f"{p}_ln2_scale": (c.hidden_size,),
            f"{p}_experts_gate_up": (held, c.hidden_size, 2 * w),
            f"{p}_experts_down": (held, w, c.hidden_size)})
    return out


def _rows(x, w, width, seq_len, out_dtype=None):
    """``x [B, S, in] @ w [in, width]`` -> ``[B, S, width]`` (one 2-D
    product on the MXU)."""
    flat = array_reshape_op(x, [-1, w.shape[0]])
    return array_reshape_op(matmul_op(flat, w, out_dtype=out_dtype),
                            [-1, seq_len, width])


class SparseDecoderBlock:
    def __init__(self, config, layer, shapes):
        c = self.config = config
        self.window = c.sliding_window if c.window_layout[layer] else None
        self.rotated = c.rope_layout[layer]
        p = f"sparse_h{layer}"

        def normal(name):
            return init.random_normal(shapes[name],
                                      stddev=c.initializer_range, name=name)

        self.router = normal(f"{p}_router")
        self.ln1 = init.ones(shapes[f"{p}_ln1_scale"], name=f"{p}_ln1_scale")
        self.wq, self.wk, self.wv, self.wo = (
            normal(f"{p}_attn_{r}") for r in "qkvo")
        self.ln2 = init.ones(shapes[f"{p}_ln2_scale"], name=f"{p}_ln2_scale")
        self.gate_up = normal(f"{p}_experts_gate_up")
        self.down = normal(f"{p}_experts_down")

    def __call__(self, x, seq_len):
        c = self.config
        heads, groups, d = (c.num_attention_heads, c.num_key_value_heads,
                            c.head_dim)
        # the router reads the layer's INPUT, before attention
        weights = router_op(x, self.router, c.num_experts_per_tok)
        picks = router_picks_op(weights)
        a = rms_normalization_op(x, self.ln1, eps=c.rms_norm_eps)
        q = _rows(a, self.wq, heads * d, seq_len)
        k = _rows(a, self.wk, groups * d, seq_len)
        v = _rows(a, self.wv, groups * d, seq_len)
        if self.rotated:
            q = rotary_op(q, heads, c.rope_theta)
            k = rotary_op(k, groups, c.rope_theta)
        ctx = flash_attention_op(
            q, k, v, sm_scale=1.0 / float(np.sqrt(d)), causal=True,
            num_heads=heads, num_kv_heads=groups, window=self.window)
        h = x + _rows(ctx, self.wo, c.hidden_size, seq_len)
        u = rms_normalization_op(h, self.ln2, eps=c.rms_norm_eps)
        y = held_experts_op(u, weights, picks, self.gate_up, self.down,
                            first=c.experts_held[0],
                            activation=c.activation)
        return h + y, picks


class SparseDecoderModel:
    """Token embedding, the blocks, the final RMS norm. ``picks`` keeps
    each layer's router indices node (an inference group may return
    them)."""

    def __init__(self, config):
        self.config = config
        shapes = self.shapes = sparse_decoder_param_shapes(config)
        self.embed = init.random_normal(
            shapes["sparse_embed"], stddev=config.embedding_range,
            name="sparse_embed")
        self.blocks = [SparseDecoderBlock(config, i, shapes)
                       for i in range(config.num_hidden_layers)]
        self.ln_f = init.ones(shapes["sparse_ln_f_scale"],
                              name="sparse_ln_f_scale")
        self.picks = []

    def __call__(self, input_ids, seq_len):
        x = embedding_lookup_op(self.embed, input_ids)
        self.picks = []
        for block in self.blocks:
            x, picks = block(x, seq_len)
            self.picks.append(picks)
        return rms_normalization_op(x, self.ln_f,
                                    eps=self.config.rms_norm_eps)


class SparseDecoderLMHeadModel:
    """The decoder and an UNTIED head whose logits leave in float32;
    with ``labels`` (shifted by the caller, -1 ignored) also the
    per-position next-token loss. The loss is cross-entropy alone: no
    load-balancing term."""

    def __init__(self, config):
        self.config = config
        self.decoder = SparseDecoderModel(config)
        self.lm_head = init.random_normal(
            self.decoder.shapes["sparse_lm_head"],
            stddev=config.initializer_range, name="sparse_lm_head")

    @property
    def picks(self):
        return self.decoder.picks

    def __call__(self, input_ids, labels=None, seq_len=None):
        hidden = self.decoder(input_ids, seq_len)
        logits = _rows(hidden, self.lm_head, self.config.vocab_size,
                       seq_len, out_dtype=np.float32)
        if labels is None:
            return logits
        return logits, softmaxcrossentropy_sparse_op(logits, labels)
