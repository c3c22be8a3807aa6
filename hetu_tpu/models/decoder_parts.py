"""What the serving decoders with routed experts share
(``models/latent_moe.py``, ``models/window_moe.py``): the RMS norm, the
rotation in halves, a layer's feed-forward (SwiGLU, or the shared expert
plus the held experts' part of the routed sum, ``ops/moe.py``) and its
pass a chunk of tokens at a time, the scatter of new rows into a paged
pool, and the record a program hands back beside a generated token.
"""
from __future__ import annotations

__all__ = ["rms", "rope", "token_chunks", "feed_forward", "pool_scatter",
           "records"]


def rms(x, weight, eps):
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, cos, sin):
    """Rotate ``x [..., rope]`` in halves: pair ``(i, i + rope / 2)``
    turns by ``position * inv_freq[i]`` (the source model stores the
    pairs interleaved; that is a fixed permutation of the projection's
    columns). ``cos`` / ``sin`` broadcast against ``x``'s halves."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def token_chunks(fn, x, valid):
    """``fn(x [T, H], valid [T]) -> (y [T, H], picks [T, k], counts)``
    over at most ``ops.moe.TOKEN_CHUNK`` tokens at a time, one pass
    after another, so that a feed-forward's temporaries are a chunk's
    whatever the prompt bucket; ``counts`` add up over the passes."""
    import jax
    import jax.numpy as jnp
    from ..ops.moe import TOKEN_CHUNK
    t = x.shape[0]
    if t <= TOKEN_CHUNK:
        return fn(x, valid)
    pad = -t % TOKEN_CHUNK

    def chunks(a):
        a = jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])
        return a.reshape(-1, TOKEN_CHUNK, *a.shape[1:])

    y, picks, counts = jax.lax.map(lambda c: fn(*c),
                                   (chunks(x), chunks(valid)))
    return (y.reshape(-1, y.shape[-1])[:t],
            picks.reshape(-1, picks.shape[-1])[:t],
            jax.tree_util.tree_map(lambda c: jnp.sum(c, axis=0), counts))


def feed_forward(config, blk, x, valid):
    """One layer's feed-forward on ``x [T, H]``: SwiGLU in a dense
    layer; in an expert layer the shared expert plus the held experts'
    part of the routed sum, the router group-limited where
    ``config.n_group`` is over 1. Returns ``(y [T, H], the router's picks
    [T, k] int32 (zeros in a dense layer), (rows by held expert [held]
    int32, held experts visited))``."""
    import jax.numpy as jnp
    from ..ops import moe
    held = config.experts_held[1]
    if "mlp_gate_up" in blk:
        return (moe.swiglu(x, blk["mlp_gate_up"], blk["mlp_down"]),
                jnp.zeros((x.shape[0], config.num_experts_per_tok),
                          jnp.int32),
                (jnp.zeros(held, jnp.int32), jnp.int32(0)))
    experts, weights, _ = moe.route(
        x, blk["router"], blk["router_bias"], config.num_experts_per_tok,
        config.routed_scaling_factor, config.n_group, config.topk_group)
    routed, rows = moe.held_experts(
        x, experts, weights, valid, blk["experts_gate_up"],
        blk["experts_down"], first=config.experts_held[0])
    shared = moe.swiglu(x, blk["shared_gate_up"], blk["shared_down"])
    y = (shared.astype(jnp.float32) + routed).astype(x.dtype)
    return y, experts, (rows, jnp.sum(rows > 0).astype(jnp.int32))


def pool_scatter(pool, slots, rows):
    """Write ``rows [..., W]`` into flat slots ``slots [...]`` of one
    layer's pool ``[num_blocks, block_size, W]`` (duplicates, the
    padded lanes on the scratch block, resolve to SOME row). The slot
    is split into (block, row in block) and the pool indexed as it
    lies: flattening a bfloat16 pool first costs two pool-sized copies
    a layer a step on the chip (the flat and the blocked layouts tile
    differently)."""
    block_size, width = pool.shape[-2:]
    flat = slots.reshape(-1)
    return pool.at[flat // block_size, flat % block_size].set(
        rows.reshape(-1, width).astype(pool.dtype), mode="drop")


def records(picks, logits):
    """One int32 record a row of the batch, of the token the row's
    float32 ``logits [B, V]`` decide: the experts each expert layer's
    router picked for it (``picks [B, expert layers, k]``), then the
    bits of the row's best logit. What the engine hands back beside a
    generated token (``Future.token_records``); a checker forces a
    reference onto the same routing with it
    (a serving model's ``read_records``)."""
    import jax
    import jax.numpy as jnp
    best = jax.lax.bitcast_convert_type(
        jnp.max(logits, axis=-1).astype(jnp.float32), jnp.int32)
    return jnp.concatenate(
        [picks.reshape(picks.shape[0], -1).astype(jnp.int32),
         best[:, None]], axis=1)
