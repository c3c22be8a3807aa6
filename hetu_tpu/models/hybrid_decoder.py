"""A convolution-hybrid sparse-expert decoder that TRAINS through
``ht.Executor``: gated short-convolution layers beside grouped-query
attention layers with a norm a head, dense SwiGLU layers before expert
layers under a sigmoid router that selects by a bias, a tied head — one
chip's SHARE of an expert-parallel job.

No reference equivalent. The block is LFM2's (``lfm2_moe``; LFM2-8B-A1B
in the catalog) as ``benchmark/reference/lfm2_moe.py`` writes it out.
A layer is ``h = x + Mixer(RMSNorm_op(x))``, ``out = h +
FFN(RMSNorm_ffn(h))``, and which mixer and which feed-forward a layer
has is CONFIGURATION: ``layer_types[i]`` is ``"conv"`` or
``"full_attention"``, the first ``num_dense_layers`` layers are dense.

* ``conv``: ``B | C | u = a W_in``; ``y = (C * causal_conv_K(B * u))
  W_out`` (``short_conv_op``; depthwise, ``K = conv_L_cache`` taps, no
  bias, no activation).
* ``full_attention``: q on ``H`` heads, k and v on ``G``, of ``D``; q
  and k take an RMS norm A HEAD (one gain of ``D`` shared by the heads)
  BEFORE the rotation in halves; causal softmax attention, no window.
* dense: ``W_down(silu(W_gate n) * (W_up n))`` (``swiglu_op``; gate and
  up side by side in one matrix).
* experts: ``s = sigmoid(n W_r)`` float32 over ALL experts; the
  ``top_k`` of ``s + bias`` (the bias a non-trainable float32 buffer:
  no gradient, no part in a weight); ``w = s[picked] / (sum + eps) *
  scale``; the sum over the picks whose expert is HELD here
  (``router_op(scoring="sigmoid", bias=)``, ``held_experts_op``).

then a final RMS norm and the head ``logits = hidden E^T`` with ``E``
the token table ITSELF: one parameter with two gradients, the lookup's
rows and the head's matrix, which the graph sums (``ops/basic.py:
AddOp``) into one float32 array that the dense update applies once (the
in-place sparse row update never sees such a table). No bias anywhere.
Every node is a graph op with gradient ops of its own.

**Why a file of its own** beside ``sparse_decoder.py``: that file's
layers are one kind (a router BEFORE attention reading the un-normed
stream, every layer experts, an untied head), and its lowered step is
held to the text it had (``tests/test_hybrid_decoder.py``). What the two
share is ``_rows`` and the ops; a per-layer list there would have put
five switches into a block that takes none of them.

The chip's share is as ``sparse_decoder.py`` has it: ``experts_held =
(first, count)`` of ``num_experts`` routed ones (the router stays
``num_experts`` wide), ``vocab_size`` the slice of the vocabulary held.
"""
from __future__ import annotations

import numpy as np

from .. import initializers as init
from ..ops import (array_reshape_op, embedding_lookup_op,
                   flash_attention_op, held_experts_op, matmul_op,
                   rms_normalization_op, rotary_op, router_op,
                   router_picks_op, short_conv_op,
                   softmaxcrossentropy_sparse_op, swiglu_op)
from .sparse_decoder import _rows

__all__ = ["HybridDecoderConfig", "HybridDecoderModel",
           "HybridDecoderLMHeadModel", "hybrid_decoder_param_shapes",
           "LAYER_TYPES"]

LAYER_TYPES = ("conv", "full_attention")


class _GroupCentredNormal(init.NormalInit):
    """``N(0, stddev)`` over ``[E]`` with the mean of every ``group``
    consecutive entries taken off: a selection bias drawn so reorders
    near-ties between experts and favours no chip's group of them (a
    plain draw moves the share of the picks that lands on one chip's
    group by a few percent of itself, seed by seed)."""

    def __init__(self, shape, stddev, group):
        super().__init__(shape, 0.0, stddev)
        if len(self.shape) != 1 or self.shape[0] % group:
            raise ValueError(f"groups of {group} do not divide {shape}")
        self.group = group

    def init_numpy(self, seed=0):
        drawn = super().init_numpy(seed).reshape(-1, self.group)
        return (drawn - drawn.mean(axis=1, keepdims=True)).reshape(
            self.shape)


class HybridDecoderConfig:
    def __init__(self, vocab_size, hidden_size, layer_types,
                 num_dense_layers, intermediate_size,
                 moe_intermediate_size, num_experts, num_experts_per_tok,
                 num_attention_heads, num_key_value_heads, head_dim=None,
                 experts_held=None, conv_L_cache=3, rope_theta=1000000.0,
                 norm_eps=1e-5, routed_scaling_factor=1.0,
                 norm_topk_eps=1e-6, initializer_range=0.02,
                 embedding_range=None, expert_bias_range=0.0,
                 conv_taps_range=0.5):
        for kind in layer_types:
            if kind not in LAYER_TYPES:
                raise ValueError(f"layer type {kind!r}: one of "
                                 f"{LAYER_TYPES}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = list(layer_types)
        self.num_hidden_layers = len(layer_types)
        self.num_dense_layers = num_dense_layers
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.experts_held = tuple(experts_held or (0, num_experts))
        first, held = self.experts_held
        if first < 0 or held < 1 or first + held > num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{num_experts}")
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.conv_L_cache = conv_L_cache
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_eps = norm_topk_eps
        self.initializer_range = initializer_range
        self.embedding_range = initializer_range \
            if embedding_range is None else embedding_range
        # the spread of the seeded selection bias (a buffer, never
        # trained, centred within every chip's ``experts_held[1]``
        # consecutive experts) and the half-width of the taps' uniform
        # draw
        self.expert_bias_range = expert_bias_range
        self.conv_taps_range = conv_taps_range

    def is_dense(self, layer):
        return layer < self.num_dense_layers


def hybrid_decoder_param_shapes(config):
    """``{checkpoint name: shape}`` of every parameter AND buffer the
    builders below make (a buffer's name ends in ``_expert_bias``)."""
    c = config
    hidden, d = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * d, c.num_key_value_heads * d
    held, w = c.experts_held[1], c.moe_intermediate_size
    out = {"hybrid_embed": (c.vocab_size, hidden),
           "hybrid_ln_f_scale": (hidden,)}
    for i, kind in enumerate(c.layer_types):
        p = f"hybrid_h{i}"
        out[f"{p}_op_norm_scale"] = (hidden,)
        out[f"{p}_ffn_norm_scale"] = (hidden,)
        if kind == "conv":
            out.update({f"{p}_conv_in": (hidden, 3 * hidden),
                        f"{p}_conv_taps": (hidden, c.conv_L_cache),
                        f"{p}_conv_out": (hidden, hidden)})
        else:
            out.update({f"{p}_attn_q": (hidden, q),
                        f"{p}_attn_k": (hidden, kv),
                        f"{p}_attn_v": (hidden, kv),
                        f"{p}_attn_o": (q, hidden),
                        f"{p}_attn_q_norm_scale": (d,),
                        f"{p}_attn_k_norm_scale": (d,)})
        if c.is_dense(i):
            out.update({
                f"{p}_ffn_gate_up": (hidden, 2 * c.intermediate_size),
                f"{p}_ffn_down": (c.intermediate_size, hidden)})
        else:
            out.update({f"{p}_router": (hidden, c.num_experts),
                        f"{p}_expert_bias": (c.num_experts,),
                        f"{p}_experts_gate_up": (held, hidden, 2 * w),
                        f"{p}_experts_down": (held, w, hidden)})
    return out


class HybridDecoderBlock:
    def __init__(self, config, layer, shapes):
        c = self.config = config
        self.kind = c.layer_types[layer]
        self.dense = c.is_dense(layer)
        p = f"hybrid_h{layer}"

        def normal(role, std=c.initializer_range, **kw):
            name = f"{p}_{role}"
            return init.random_normal(shapes[name], stddev=std, name=name,
                                      **kw)

        def ones(role):
            name = f"{p}_{role}"
            return init.ones(shapes[name], name=name)

        self.op_norm, self.ffn_norm = ones("op_norm_scale"), \
            ones("ffn_norm_scale")
        if self.kind == "conv":
            self.conv_in, self.conv_out = normal("conv_in"), \
                normal("conv_out")
            name = f"{p}_conv_taps"
            self.taps = init.random_uniform(
                shapes[name], -c.conv_taps_range, c.conv_taps_range,
                name=name)
        else:
            self.wq, self.wk, self.wv, self.wo = (
                normal(f"attn_{r}") for r in "qkvo")
            self.q_norm, self.k_norm = ones("attn_q_norm_scale"), \
                ones("attn_k_norm_scale")
        if self.dense:
            self.gate_up, self.down = normal("ffn_gate_up"), \
                normal("ffn_down")
        else:
            self.router = normal("router")
            # a BUFFER: seeded, never trained, float32 where it is read
            name = f"{p}_expert_bias"
            self.expert_bias = _GroupCentredNormal(
                shapes[name], c.expert_bias_range,
                c.experts_held[1])(name, trainable=False)
            self.experts_gate_up = normal("experts_gate_up")
            self.experts_down = normal("experts_down")

    def _head_norm(self, rows, scale, heads, seq_len):
        """An RMS norm a head of ``[B, S, heads * D]`` rows."""
        c = self.config
        by_head = array_reshape_op(rows, [-1, seq_len, heads, c.head_dim])
        return array_reshape_op(
            rms_normalization_op(by_head, scale, eps=c.norm_eps),
            [-1, seq_len, heads * c.head_dim])

    def _mixer(self, a, seq_len):
        c = self.config
        if self.kind == "conv":
            proj = _rows(a, self.conv_in, 3 * c.hidden_size, seq_len)
            return _rows(short_conv_op(proj, self.taps), self.conv_out,
                         c.hidden_size, seq_len)
        heads, groups, d = (c.num_attention_heads, c.num_key_value_heads,
                            c.head_dim)
        q = self._head_norm(_rows(a, self.wq, heads * d, seq_len),
                            self.q_norm, heads, seq_len)
        k = self._head_norm(_rows(a, self.wk, groups * d, seq_len),
                            self.k_norm, groups, seq_len)
        v = _rows(a, self.wv, groups * d, seq_len)
        ctx = flash_attention_op(
            rotary_op(q, heads, c.rope_theta),
            rotary_op(k, groups, c.rope_theta), v,
            sm_scale=1.0 / float(np.sqrt(d)), causal=True,
            num_heads=heads, num_kv_heads=groups)
        return _rows(ctx, self.wo, c.hidden_size, seq_len)

    def __call__(self, x, seq_len):
        """``(out, picks)``; ``picks`` None for a dense layer."""
        c = self.config
        h = x + self._mixer(
            rms_normalization_op(x, self.op_norm, eps=c.norm_eps), seq_len)
        n = rms_normalization_op(h, self.ffn_norm, eps=c.norm_eps)
        if self.dense:
            unit = swiglu_op(_rows(n, self.gate_up,
                                   2 * c.intermediate_size, seq_len))
            return h + _rows(unit, self.down, c.hidden_size, seq_len), None
        weights = router_op(
            n, self.router, c.num_experts_per_tok, scoring="sigmoid",
            bias=self.expert_bias, scale=c.routed_scaling_factor,
            norm_eps=c.norm_topk_eps)
        picks = router_picks_op(weights)
        y = held_experts_op(n, weights, picks, self.experts_gate_up,
                            self.experts_down, first=c.experts_held[0],
                            activation="silu")
        return h + y, picks


class HybridDecoderModel:
    """Token embedding, the blocks, the final RMS norm. ``picks`` keeps
    each EXPERT layer's router indices node, in layer order."""

    def __init__(self, config):
        self.config = config
        shapes = self.shapes = hybrid_decoder_param_shapes(config)
        self.embed = init.random_normal(
            shapes["hybrid_embed"], stddev=config.embedding_range,
            name="hybrid_embed")
        self.blocks = [HybridDecoderBlock(config, i, shapes)
                       for i in range(config.num_hidden_layers)]
        self.ln_f = init.ones(shapes["hybrid_ln_f_scale"],
                              name="hybrid_ln_f_scale")
        self.picks = []

    def __call__(self, input_ids, seq_len):
        x = embedding_lookup_op(self.embed, input_ids)
        self.picks = []
        for block in self.blocks:
            x, picks = block(x, seq_len)
            if picks is not None:
                self.picks.append(picks)
        return rms_normalization_op(x, self.ln_f, eps=self.config.norm_eps)


class HybridDecoderLMHeadModel:
    """The decoder under a TIED head: the logits are the final hidden
    rows times the token table transposed, float32; with ``labels``
    (shifted by the caller, -1 ignored) also the per-position next-token
    loss. Cross-entropy alone: no load-balancing term."""

    def __init__(self, config):
        self.config = config
        self.decoder = HybridDecoderModel(config)

    @property
    def picks(self):
        return self.decoder.picks

    def __call__(self, input_ids, labels=None, seq_len=None):
        c = self.config
        hidden = self.decoder(input_ids, seq_len)
        flat = array_reshape_op(hidden, [-1, c.hidden_size])
        logits = array_reshape_op(
            matmul_op(flat, self.decoder.embed, trans_B=True,
                      out_dtype=np.float32),
            [-1, seq_len, c.vocab_size])
        if labels is None:
            return logits
        return logits, softmaxcrossentropy_sparse_op(logits, labels)
