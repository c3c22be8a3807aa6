"""GPT family — decoder-only causal language models.

No direct reference equivalent (the reference's NLP zoo stops at BERT,
examples/nlp/bert/hetu_bert.py); this family exists to make the causal
attention stack a first-class, user-reachable model path: the Pallas
flash kernel's ``causal=True`` mode on one chip, and the zigzag causal
ring / blockwise-causal Ulysses sequence parallelism
(parallel/ring.py, parallel/ulysses.py) for long-context training —
``GPTConfig(sequence_parallel="ring"|"ulysses")`` is all a user writes.

Architecture: GPT-2-shaped pre-LN transformer decoder (learned position
embeddings, gelu MLP, LayerNorm before each sublayer and at the output),
built from the same layer utilities as models/bert.py. Next-token loss:
the caller feeds ``labels`` already shifted by one (``ids[:, 1:]`` plus
a pad), matching the examples' host-side shift.
"""
from __future__ import annotations

import numpy as np

from ..ops import (array_reshape_op, broadcastto_op,
                   softmaxcrossentropy_sparse_op, split_op, squeeze_op,
                   transpose_op)
from ..ops.variable import Variable
from .bert import (BertLayerNorm as LayerNorm, Dropout, Embedding,
                   Linear, _act)

__all__ = ["GPTConfig", "GPTModel", "GPTLMHeadModel", "GPTServingModel",
           "gpt_param_names", "gpt_serving_params", "gpt_forward",
           "gpt_paged_prefill", "gpt_paged_step",
           "gpt_paged_suffix_prefill", "gpt_param_bytes"]


class GPTConfig:
    def __init__(self, vocab_size, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 hidden_act="gelu", hidden_dropout_prob=0.1,
                 max_position_embeddings=1024, initializer_range=0.02,
                 use_flash_attention=False, sequence_parallel=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention
        # None/False: single-device attention. "ring": zigzag causal
        # ring over the mesh's "sp" axis. "ulysses": causal all-to-all.
        # Both fall back to the fused path off-mesh, so a model declares
        # its parallelism once and runs anywhere.
        if sequence_parallel is True:
            sequence_parallel = "ring"
        self.sequence_parallel = sequence_parallel or None

    def serving_model(self):
        return GPTServingModel(self)


def gpt_param_names(config):
    """Checkpoint layout of a ``GPTLMHeadModel``: the parameter NAMES the
    builders above assign, structured the way the serving forward wants
    them. ``Executor.save`` writes one ``<name>.npy`` per parameter, so
    this is the bridge from a training checkpoint (or a live executor's
    ``params``) to the pure-JAX serving block below — no re-tracing of
    the graph, just a name lookup."""
    blocks = []
    for i in range(config.num_hidden_layers):
        p = f"gpt_h{i}"
        blocks.append({
            "ln1": (f"{p}_ln1_scale", f"{p}_ln1_bias"),
            "qkv": (f"{p}_attn_qkv_weights", f"{p}_attn_qkv_bias"),
            "proj": (f"{p}_attn_proj_weights", f"{p}_attn_proj_bias"),
            "ln2": (f"{p}_ln2_scale", f"{p}_ln2_bias"),
            "fc": (f"{p}_mlp_fc_weights", f"{p}_mlp_fc_bias"),
            "mlp_proj": (f"{p}_mlp_proj_weights", f"{p}_mlp_proj_bias"),
        })
    return {"wte": "gpt_wte", "wpe": "gpt_wpe", "blocks": blocks,
            "ln_f": ("gpt_ln_f_scale", "gpt_ln_f_bias"),
            "lm_head": "gpt_lm_head_weights"}


def gpt_serving_params(config, lookup):
    """Assemble the serving block's parameter pytree. ``lookup(name)``
    returns the array for one checkpoint name (a dict's ``__getitem__``,
    an ``np.load`` closure over a checkpoint dir, ...).

    ``"blocks"`` is ONE dict of the block's six entries (``ln1``,
    ``qkv``, ``proj``, ``ln2``, ``fc``, ``mlp_proj``), each a ``(w, b)``
    pair OVER THE LAYERS, indexed ``[i]`` either way:

    * a VECTOR (a LayerNorm's scale and bias, a projection's bias: 8 of
      the 12) is one array stacked over the layers, ``[L, n]``, made
      here once. A jitted call walks every array of the trees handed to
      it, on every call, and the host was the slower side of GPT-2
      small's decode step: 8 arrays whatever the depth, not ``8 L``;
    * a MATRIX (the four projections' weights) stays an array a layer, a
      tuple of ``L``. XLA's TPU compiler streams a whole parameter into
      VMEM in slices while the layer before computes, and does NOT for a
      static slice of a stacked one: with ``[L, 768, 2304]`` stacks the
      same decode program read 1.37 ms for 0.97 on the chip (PERF.md,
      PR 44), more than the walk over ``4 L`` arrays costs the host.

    The caller's own per-name arrays stay the caller's."""
    import jax.numpy as jnp

    def get(name):
        return jnp.asarray(lookup(name), jnp.float32)

    def over_layers(names):
        arrays = tuple(get(name) for name in names)
        return jnp.stack(arrays) if arrays[0].ndim == 1 else arrays

    names = gpt_param_names(config)
    return {"wte": get(names["wte"]), "wpe": get(names["wpe"]),
            "ln_f": tuple(get(n) for n in names["ln_f"]),
            "lm_head": get(names["lm_head"]),
            "blocks": {role: tuple(over_layers([blk[role][j]
                                                for blk in names["blocks"]])
                                   for j in range(2))
                       for role in names["blocks"][0]}}


def _serve_ln(x, scale_bias, eps=1e-12):
    import jax.numpy as jnp
    scale, bias = scale_bias
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * scale + bias


def _serve_act(name):
    """Serving-side activation matching the graph builders' ``_act``
    (ops/activations.py numerics: tanh-approx gelu)."""
    import jax
    import jax.numpy as jnp
    try:
        return {"gelu": lambda x: jax.nn.gelu(x, approximate=True),
                "relu": jax.nn.relu, "tanh": jnp.tanh}[name]
    except KeyError:
        raise ValueError(
            f"unsupported hidden_act for the serving forward: {name!r} "
            f"(gelu/relu/tanh)") from None


def _serve_forward(params, x, attend, num_heads, hidden_act):
    """THE serving-side decoder stack, written once: embedded tokens
    ``x`` ``[..., hidden]`` (``[B, H]`` for a decode step, ``[B, S, H]``
    for a prefill) through every pre-LN block and ``ln_f``; returns the
    hidden states, same shape. Layer ``i``'s arrays are ``[i]`` of
    ``params["blocks"]``' entries (:func:`gpt_serving_params`: the
    ``i``-th of a tuple, or a static index into a stack, a view to the
    compiler and not a copy). The cache backend is ``attend(i, q, k,
    v)``: layer ``i``'s q/k/v arrive token-major ``[..., nh, hs]``, it
    writes K/V wherever its cache lives, reads what it must, and returns
    the context shaped like ``q``. It is called once per layer while
    tracing, so whatever it collects costs nothing per step."""
    act = _serve_act(hidden_act)
    hidden = x.shape[-1]
    heads = (*x.shape[:-1], num_heads, hidden // num_heads)
    blocks = params["blocks"]
    for i in range(len(blocks["qkv"][0])):
        blk = {role: (w[i], b[i]) for role, (w, b) in blocks.items()}
        qkv = _serve_ln(x, blk["ln1"]) @ blk["qkv"][0] + blk["qkv"][1]
        q, k, v = (qkv[..., j * hidden:(j + 1) * hidden].reshape(heads)
                   for j in range(3))
        ctx = attend(i, q, k, v).reshape(x.shape)
        x = x + (ctx @ blk["proj"][0] + blk["proj"][1])
        h = act(_serve_ln(x, blk["ln2"]) @ blk["fc"][0] + blk["fc"][1])
        x = x + (h @ blk["mlp_proj"][0] + blk["mlp_proj"][1])
    return _serve_ln(x, params["ln_f"])


def _serve_head(params, x, pick=None):
    """What leaves a serving program: the float32 logits of hidden
    states ``x``, or with ``pick="greedy"`` their int32 argmax."""
    import jax.numpy as jnp
    if pick not in (None, "greedy"):
        raise ValueError(f"pick must be None or 'greedy', got {pick!r}")
    logits = x @ params["lm_head"]
    if pick == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return logits


def _sm_scale(q):
    return 1.0 / float(np.sqrt(q.shape[-1]))


def _causal_attention(q, k, v):
    """Causal attention among the tokens of this call alone, token-major
    ``[B, S, nh, hs]`` in and out around
    :func:`~hetu_tpu.ops.attention.prefill_attention`'s head-major
    layout (the Pallas flash kernel on TPU)."""
    from ..ops.attention import prefill_attention
    ctx = prefill_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                            sm_scale=_sm_scale(q), causal=True)
    return ctx.transpose(0, 2, 1, 3)


def gpt_forward(params, ids, num_heads, hidden_act="gelu"):
    """Plain causal forward over ``ids`` ``[B, S]`` with no cache at
    all: the serving block with an ``attend`` that writes nothing.
    Returns logits ``[B, S, V]``."""
    x = params["wte"][ids] + params["wpe"][:ids.shape[1]][None]
    x = _serve_forward(params, x, lambda i, q, k, v:
                       _causal_attention(q, k, v), num_heads, hidden_act)
    return _serve_head(params, x)


def _pool_scatter(pool, slots, rows):
    """Write ``rows [..., H, D]`` into flat slots ``slots [...]`` of one
    layer's pooled cache ``[num_blocks, block_size, H*D]`` (heads side
    by side in a row — serving/kvcache.py says why). Duplicate slots
    (padded lanes all targeting the scratch block) resolve to SOME
    written row — fine, scratch content is never read unmasked."""
    shape = pool.shape
    flat = pool.reshape(-1, shape[-1])
    return flat.at[slots.reshape(-1)].set(rows.reshape(-1, shape[-1]),
                                          mode="drop").reshape(shape)


def _paged_attend(pools, write_slots, attention):
    """The block-paged cache backend of :func:`_serve_forward`: layer
    ``i``'s K/V rows scatter into ``pools[i]`` at ``write_slots`` (one
    flat slot per token), then ``attention(q, k, v, pool)`` reads from
    the updated layer pool. Returns ``(attend, new_pools)``; the list
    fills, layer by layer, as the forward is traced."""
    new_pools = []

    def attend(i, q, k, v):
        pool = {"k": _pool_scatter(pools[i]["k"], write_slots, k),
                "v": _pool_scatter(pools[i]["v"], write_slots, v)}
        new_pools.append(pool)
        return attention(q, k, v, pool)

    return attend, new_pools


def gpt_paged_prefill(params, pools, ids, slot_idx, num_heads,
                      hidden_act="gelu"):
    """Prompt phase over a block-paged pool: full causal forward over
    ``ids`` ``[B, P]`` that scatters every position's K/V row into the
    flat pool slots ``slot_idx`` ``[B, P]`` (kvcache.py block-table
    math; padded rows/positions point at the scratch block). Prompts in
    the batch may have different true lengths — rows past a prompt's
    end are edge-repeat padding whose K/V lands in scratch, and causal
    attention keeps them out of the real rows' context. Returns
    ``(logits [B, P, V], pools)``; jit with ``pools`` donated."""
    x = params["wte"][ids] + params["wpe"][:ids.shape[1]][None]
    attend, new_pools = _paged_attend(
        pools, slot_idx, lambda q, k, v, pool: _causal_attention(q, k, v))
    x = _serve_forward(params, x, attend, num_heads, hidden_act)
    return _serve_head(params, x), new_pools


def gpt_paged_step(params, pools, tokens, positions, slot_idx,
                   write_slots, num_heads, hidden_act="gelu", pick=None):
    """Paged single-token forward for a RAGGED batch: ``tokens`` ``[B]``
    each at its own position ``positions`` ``[B]`` (traced int32 — one
    jit program serves every mix of sequence lengths at this batch/
    context bucket). Writes each token's K/V row to flat pool slot
    ``write_slots`` ``[B]`` and attends through the gathered slot grid
    ``slot_idx`` ``[B, S_bucket]`` via
    :func:`~hetu_tpu.ops.attention.paged_decode_attention`. Padded
    lanes carry ``write_slots`` = scratch and gather behind the length
    mask. Returns ``(logits [B, V], pools)``; jit with ``pools``
    donated so updates stay in-HBM.

    ``pick`` (static) says what leaves the program in place of the
    logits: ``"greedy"`` returns ``(argmax(logits, -1) [B] int32,
    pools)`` — the same float32 logits, ties to the first index as
    ``np.argmax`` breaks them, so the token is the one a host-side pick
    over the returned logits would choose."""
    from ..ops.attention import paged_decode_attention

    x = params["wte"][tokens] + params["wpe"][positions]    # [B, H]
    attend, new_pools = _paged_attend(
        pools, write_slots, lambda q, k, v, pool: paged_decode_attention(
            q, pool["k"], pool["v"], slot_idx, positions,
            sm_scale=_sm_scale(q)))
    x = _serve_forward(params, x, attend, num_heads, hidden_act)
    return _serve_head(params, x, pick), new_pools


def gpt_paged_suffix_prefill(params, pools, ids, starts, slot_idx,
                             write_slots, num_heads, hidden_act="gelu"):
    """Prefill a CHUNK of prompt positions into an existing block table:
    ``ids`` ``[B, C]`` are each sequence's next ``C`` prompt tokens
    starting at token offset ``starts`` ``[B]`` (traced int32 — one jit
    program per batch/chunk/context bucket serves every offset mix).
    This is both halves of the prefix story: a prefix-cache hit starts
    prefill at the first non-cached position with the cached blocks
    already resident in ``slot_idx``'s grid, and chunked prefill feeds
    a long prompt through here one chunk per engine step.

    Each chunk row's K/V scatters to flat pool slot ``write_slots``
    ``[B, C]`` and attention gathers the whole history (cached prefix +
    earlier chunks + this chunk) through the slot grid ``slot_idx``
    ``[B, S_bucket]`` via
    :func:`~hetu_tpu.ops.attention.paged_prefill_attention` (causality:
    chunk row ``i`` sees positions ``<= starts[b] + i``). Padded lanes
    write to scratch and rows past a chunk's true width are edge
    padding, same contract as :func:`gpt_paged_prefill`. Returns
    ``(logits [B, C, V], pools)``; jit with ``pools`` donated."""
    import jax.numpy as jnp
    from ..ops.attention import paged_prefill_attention

    positions = starts[:, None] + jnp.arange(ids.shape[1])[None, :]
    x = params["wte"][ids] + params["wpe"][positions]       # [B, C, H]
    attend, new_pools = _paged_attend(
        pools, write_slots, lambda q, k, v, pool: paged_prefill_attention(
            q, pool["k"], pool["v"], slot_idx, starts,
            sm_scale=_sm_scale(q)))
    x = _serve_forward(params, x, attend, num_heads, hidden_act)
    return _serve_head(params, x), new_pools


def gpt_param_bytes(config, dtype_bytes=4):
    """Parameter bytes of a ``GPTLMHeadModel`` with this config (the
    serving-params pytree :func:`gpt_serving_params` builds) — what
    the pool sizing subtracts from the HBM budget."""
    h = config.hidden_size
    i = config.intermediate_size
    per_layer = (2 * h                      # ln1
                 + h * 3 * h + 3 * h        # qkv
                 + h * h + h                # attn proj
                 + 2 * h                    # ln2
                 + h * i + i                # mlp fc
                 + i * h + h)               # mlp proj
    total = (config.vocab_size * h          # wte
             + config.max_position_embeddings * h   # wpe
             + config.num_hidden_layers * per_layer
             + 2 * h                        # ln_f
             + h * config.vocab_size)       # lm_head
    return total * dtype_bytes


class GPTServingModel:
    """What ``ContinuousBatchingEngine`` and ``PagedKVCache`` take a
    :class:`GPTConfig` as: the serving-model interface of
    ``docs/serving.md`` (parameters, the cache's row layout, the
    programs of the three cache backends, sizes)."""

    # the prefill program returns [B, P, V] logits and the engine
    # gathers each prompt's last row
    prefill_last_row = False
    counter_names = ()
    vector_counter = None
    row_record_width = 0

    def __init__(self, config):
        self.config = config
        self.vocab_size = config.vocab_size
        # learned positions: the table is the longest sequence
        self.max_positions = config.max_position_embeddings
        self.num_cache_layers = config.num_hidden_layers

    def cache_layout(self):
        """A K and a V pool a layer, rows ``hidden`` wide (a row keeps
        its heads side by side), float32."""
        return (("k", self.config.hidden_size, "float32"),
                ("v", self.config.hidden_size, "float32"))

    def params(self, lookup):
        return gpt_serving_params(self.config, lookup)

    def param_bytes(self):
        return gpt_param_bytes(self.config)

    def prefill_bytes_per_token(self):
        """Bytes one prompt token costs a prefill program: its
        ``[B, P, V]`` float32 logits and as much again in temporaries
        (3.29e9 + 3.31e9 for 16,384 tokens of GPT-2 small compiled for
        the described v5e, PR 23), and the block's own rows."""
        c = self.config
        return 2 * 4 * c.vocab_size + 4 * (4 * c.hidden_size
                                            + 2 * c.intermediate_size)

    def program(self, kind):
        """``(function, static keywords)`` of one of the engine's four
        programs."""
        fn = {"prefill": gpt_paged_prefill, "decode": gpt_paged_step,
              "decode_logits": gpt_paged_step,
              "suffix_prefill": gpt_paged_suffix_prefill}[kind]
        static = {"num_heads": self.config.num_attention_heads,
                  "hidden_act": getattr(self.config, "hidden_act", "gelu")}
        if kind == "decode":
            static["pick"] = "greedy"
        return fn, static


class CausalSelfAttention:
    """Multi-head causal attention. On the flash and sequence-parallel
    paths the mask is a kernel/schedule flag — no [S, S] tensor exists;
    the composed fallback (use_flash_attention=False, off-mesh)
    broadcasts an additive [1, 1, S, S] causal-mask constant like the
    encoder's composed path does."""

    def __init__(self, config, name="attn"):
        if config.hidden_size % config.num_attention_heads:
            raise ValueError(
                f"hidden size {config.hidden_size} not a multiple of "
                f"num heads {config.num_attention_heads}")
        self.num_heads = config.num_attention_heads
        self.head_size = config.hidden_size // config.num_attention_heads
        self.hidden_size = config.hidden_size
        self.seq_len = config.max_position_embeddings
        self.config = config
        self.name = name
        self.qkv = Linear(config.hidden_size, 3 * config.hidden_size,
                          name=name + "_qkv")
        self.proj = Linear(config.hidden_size, config.hidden_size,
                           name=name + "_proj")
        self.dropout = Dropout(config.hidden_dropout_prob)

    def __call__(self, hidden_states, seq_len=None):
        from ..ops.attention import (flash_attention_op,
                                     ring_attention_op,
                                     ulysses_attention_op)
        seq_len = seq_len or self.seq_len
        qkv = self.qkv(hidden_states, [-1, seq_len, 3 * self.hidden_size])
        scale = 1.0 / float(np.sqrt(self.head_size))
        sp = self.config.sequence_parallel
        if sp is None and self.config.use_flash_attention:
            # the flash op takes the projection's rows as they lie and
            # hands the context back as the output projection reads it
            ctx = flash_attention_op(qkv, num_heads=self.num_heads,
                                     sm_scale=scale, causal=True)
        else:
            # [B, S, 3H] -> [3, B, nh, S, hs] -> q, k, v [B, nh, S, hs]
            heads = transpose_op(
                array_reshape_op(qkv, [-1, seq_len, 3, self.num_heads,
                                       self.head_size]), [2, 0, 3, 1, 4])
            q, k, v = (squeeze_op(split_op(heads, [0], [i], [3]), axes=[0])
                       for i in range(3))
            if sp == "ring":
                ctx = ring_attention_op(q, k, v, sm_scale=scale,
                                        causal=True)
            elif sp == "ulysses":
                ctx = ulysses_attention_op(q, k, v, sm_scale=scale,
                                           causal=True)
            else:
                # composed path (XLA-fused batch_matmul + softmax with a
                # broadcast causal-mask constant) — the graph
                # BertConfig's same-named flag selects on the encoder
                # side
                from ..ops import batch_matmul_op, softmax_op
                cmask = Variable(
                    self.name + "_causal_mask",
                    value=np.where(
                        np.tril(np.ones((seq_len, seq_len), bool)),
                        0.0, -1e9)[None, None].astype(np.float32),
                    trainable=False)
                k = k * scale
                scores = batch_matmul_op(q, k, trans_B=True)
                scores = scores + broadcastto_op(cmask, scores)
                ctx = batch_matmul_op(softmax_op(scores), v)
            ctx = array_reshape_op(transpose_op(ctx, [0, 2, 1, 3]),
                                   [-1, seq_len, self.hidden_size])
        out = self.proj(ctx, [-1, seq_len, self.hidden_size])
        return self.dropout(out)


class GPTBlock:
    """Pre-LN decoder block: x += attn(ln1 x); x += mlp(ln2 x)."""

    def __init__(self, config, name="block"):
        self.ln1 = LayerNorm(config.hidden_size, name=name + "_ln1")
        self.attn = CausalSelfAttention(config, name=name + "_attn")
        self.ln2 = LayerNorm(config.hidden_size, name=name + "_ln2")
        self.fc = Linear(config.hidden_size, config.intermediate_size,
                         activation=_act(config.hidden_act),
                         name=name + "_mlp_fc")
        self.proj = Linear(config.intermediate_size, config.hidden_size,
                           name=name + "_mlp_proj")
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.hidden_size = config.hidden_size

    def __call__(self, x, seq_len):
        shape3 = [-1, seq_len, self.hidden_size]
        x = x + self.attn(self.ln1(x), seq_len)
        h = self.fc(self.ln2(x), shape3)
        h = self.proj(h, shape3)
        return x + self.dropout(h)


class GPTModel:
    """Token + position embeddings, N causal blocks, final LayerNorm."""

    def __init__(self, config):
        self.config = config
        self.seq_len = config.max_position_embeddings
        self.wte = Embedding(config.vocab_size, config.hidden_size,
                             "gpt_wte")
        self.wpe = Embedding(config.max_position_embeddings,
                             config.hidden_size, "gpt_wpe")
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.blocks = [GPTBlock(config, name=f"gpt_h{i}")
                       for i in range(config.num_hidden_layers)]
        self.ln_f = LayerNorm(config.hidden_size, name="gpt_ln_f")

    def __call__(self, input_ids, seq_len=None):
        seq_len = seq_len or self.seq_len
        # int32, not the Variable default float32: float-dtype ids trip
        # the HT803 exactness gate (embedding.check_id_dtype)
        position_ids = Variable(
            "gpt_position_ids",
            value=np.arange(seq_len).reshape(1, -1), trainable=False,
            dtype=np.int32)
        x = self.wte(input_ids)
        x = x + broadcastto_op(self.wpe(position_ids), x)
        x = self.dropout(x)
        for block in self.blocks:
            x = block(x, seq_len)
        return self.ln_f(x)


class GPTLMHeadModel:
    """GPTModel + untied LM head; returns (logits, per-position loss)
    when labels are given (labels pre-shifted by the caller)."""

    def __init__(self, config):
        self.config = config
        self.transformer = GPTModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias=False, name="gpt_lm_head")

    def __call__(self, input_ids, labels=None, seq_len=None):
        seq_len = seq_len or self.config.max_position_embeddings
        hidden = self.transformer(input_ids, seq_len)
        logits = self.lm_head(
            hidden, [-1, seq_len, self.config.vocab_size])
        if labels is None:
            return logits
        loss = softmaxcrossentropy_sparse_op(logits, labels)
        return logits, loss
