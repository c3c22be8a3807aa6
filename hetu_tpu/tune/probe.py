"""Segment-timing probe: where does a long-sequence step's time go?

``bert_s2048`` runs at a fraction of roofline and the open question is
"kernel or XLA remainder". This harness answers it with data instead of
a guess: it times the flash forward(+lse) and fused backward in
isolation on the exact attention shape a model runs, at the tiles the
kernels' rule gives (``ops/pallas_attention.py:_block_sizes``) or at
given ones, and splits a measured full-step time into
attention-fwd / attention-bwd / XLA-remainder.

Results flow through the process-global telemetry — one ``attn_probe``
span per probed kernel with the chosen blocks and milliseconds in its
attrs, plus ``probe_attn_{fwd,bwd,remainder}_ms`` gauges — so the
attribution lands in the same trace as the step it explains.

CLI::

    python -m hetu_tpu.tune.probe --batch 8 --heads 8 --seq 2048 \
        --head-dim 64 --dtype bfloat16 [--causal] [--no-mask] \
        [--blocks 512 256] [--step-ms 58.3 --layers 4]

prints one JSON document; with ``--step-ms`` it includes the
full-step attribution.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

__all__ = ["probe_attention", "attribute_step", "main"]


def _telemetry():
    from .. import telemetry
    return telemetry.get_telemetry()


def probe_attention(batch, heads, seq, head_dim, dtype="bfloat16",
                    sm_scale=None, causal=False, has_mask=True,
                    interpret=None, reps=5, blocks=None):
    """Per-kernel milliseconds for the flash fwd(+lse)/bwd on one shape.

    Returns ``{"fwd_ms", "fwd_lse_ms", "bwd_ms", "blocks": {kind:
    (bq, bk)}, "fwd_walk": {...}, "bwd_walk": {...}}`` at the rule's
    tiles, or with ``blocks = (bq, bk)`` every kernel at those (how a
    candidate tile is measured against the rule's). Those are the
    head-major kernels over ``[B, H, S, D]``. ``flash_layout`` is what a caller
    that hands this shape's packed qkv rows gets
    (``ops/attention.py:flash_layout``: the operand form, the heads a
    program owns, the reason when head-major); where the heads fill
    whole lane blocks, ``token_major`` holds the same three kernels
    over the packed ``[B, S, 3H]`` rows at the same tiles, and
    ``layer_ms`` times ONE LAYER both ways from the same rows to the
    same rows (context ``[B, S, H]`` and d(qkv) ``[B, S, 3H]``):
    ``head_major`` with the split, the transposes and the merge XLA
    runs around the kernels, ``token_major`` with the one
    concatenation; and from three projections' ``[B, S, H]`` arrays
    to three gradients (an encoder's call): ``rows`` token-major in
    both directions, ``composed`` the head-major forward and
    ``jax.vjp`` of the reference with the transposes around them (what
    a head-major call below ``FUSED_BWD_MIN_SEQ`` runs;
    ``composed_vjp_ms`` is that vjp jitted alone over ``[B, H, S, D]``,
    its forward inside it). ``bwd_walk`` is the one-pass
    backward's tile walk at its tiles (``pallas_attention.
    tile_walk_counts``): with ``causal`` the share of the square it
    visits and the share of visited tiles that carry the mask;
    ``fwd_walk`` the forward-with-lse's (``fwd_walk_counts``: the same
    counts at ITS tiles, the heads a program takes and its independent
    chains; under ``token_major`` the lane-block form's)."""
    import jax
    import jax.numpy as jnp

    from ..ops import pallas_attention as pk
    from .autotune import timeit

    if interpret is None:
        # off-TPU the kernels only run in interpret mode; timings there
        # are emulation, not device truth, but the plumbing still works
        interpret = pk.INTERPRET or jax.default_backend() != "tpu"
    dtype = jnp.dtype(dtype)
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(head_dim))
    rng = np.random.RandomState(0)

    def mk():
        return jnp.asarray(
            rng.randn(batch, heads, seq, head_dim) * 0.3, dtype)

    q, k, v = mk(), mk(), mk()
    mask = (jnp.zeros((batch, 1, 1, seq), jnp.float32)
            if has_mask else None)

    def sync(out):
        first = out[0] if isinstance(out, tuple) else out
        return float(jnp.sum(first.astype(jnp.float32)))

    tel = _telemetry()
    out = {"shape": {"batch": batch, "heads": heads, "seq": seq,
                     "head_dim": head_dim, "dtype": dtype.name,
                     "causal": causal, "mask": has_mask},
           "blocks": {}}

    # one rule for both operand forms
    tuned = {kind: tuple(blocks) if blocks else pk._block_sizes(
        seq, head_dim, kind, causal, has_mask)
        for kind in ("fwd", "fwd_lse", "bwd")}
    out["blocks"] = {kind: list(b) for kind, b in tuned.items()}
    # how far the backward's tile walk engages at the tiles it runs
    # with: tiles visited / tiles of the square, masked / visited
    out["bwd_walk"] = pk.tile_walk_counts(seq, *tuned["bwd"], causal)
    out["fwd_walk"] = pk.fwd_walk_counts(heads, seq, *tuned["fwd_lse"],
                                         causal)

    def run_fwd(blocks, need_lse):
        bq, bk = blocks
        return lambda: pk._flash_attention_jit(
            q, k, v, mask, sm_scale, causal, interpret, bq, bk,
            need_lse)

    o, lse = pk._flash_attention_jit(q, k, v, mask, sm_scale, causal,
                                     interpret, *tuned["fwd_lse"], True)
    do = mk()

    def run_bwd(blocks):
        bq, bk = blocks
        return lambda: pk._flash_attention_bwd_jit(
            q, k, v, mask, o, lse, do, sm_scale, causal, interpret,
            bq, bk)

    from ..ops.attention import attention_reference, flash_layout

    def reference(q_, k_, v_):
        m = mask
        if causal:
            cm = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), 0.0,
                           -1e9)[None, None]
            m = cm if m is None else m + cm
        return attention_reference(q_, k_, v_, m, sm_scale)

    composed_vjp = jax.jit(
        lambda q_, k_, v_, dy: jax.vjp(reference, q_, k_, v_)[1](dy))
    plan = [("fwd_ms", run_fwd(tuned["fwd"], False), tuned["fwd"]),
            ("fwd_lse_ms", run_fwd(tuned["fwd_lse"], True),
             tuned["fwd_lse"]),
            ("bwd_ms", run_bwd(tuned["bwd"]), tuned["bwd"]),
            ("composed_vjp_ms", lambda: composed_vjp(q, k, v, do),
             tuned["bwd"])]
    form, reason = flash_layout(seq, head_dim, heads, True)
    packed = pk.TokenMajor.packed(heads, head_dim)
    out["flash_layout"] = {
        "layout": form, "reason": reason,
        "heads_per_block": packed.per_block if reason is None else 1}
    if packed.fits(seq):
        rows = jnp.asarray(
            rng.randn(batch, seq, 3 * heads * head_dim) * 0.3, dtype)
        d_ctx = jnp.asarray(
            rng.randn(batch, seq, heads * head_dim) * 0.3, dtype)
        out["token_major"] = {
            "blocks": {kind: list(b) for kind, b in tuned.items()},
            "fwd_walk": pk.fwd_walk_counts(heads, seq, *tuned["fwd_lse"],
                                           causal, packed),
            "bwd_heads_per_program": pk.heads_per_program(
                heads, seq, *tuned["bwd"], packed)}

        def tm_fwd(need_lse):
            bq, bk = tuned["fwd_lse" if need_lse else "fwd"]
            return lambda: pk._flash_attention_jit(
                rows, rows, rows, mask, sm_scale, causal, interpret, bq,
                bk, need_lse, packed)

        ctx, rows_lse = tm_fwd(True)()
        plan += [("token_major.fwd_ms", tm_fwd(False), tuned["fwd"]),
                 ("token_major.fwd_lse_ms", tm_fwd(True), tuned["fwd_lse"]),
                 ("token_major.bwd_ms",
                  lambda: pk._flash_attention_bwd_jit(
                      rows, rows, rows, mask, ctx, rows_lse, d_ctx,
                      sm_scale, causal, interpret, *tuned["bwd"], packed),
                  tuned["bwd"])]

        def layer(layout):
            """rows -> (context rows, d(qkv) rows), one layer's two
            flash calls with what XLA runs around them."""
            def run(x, dy):
                if layout is None:
                    q_, k_, v_ = x.reshape(
                        batch, seq, 3, heads, head_dim).transpose(
                            2, 0, 3, 1, 4)
                    dy = dy.reshape(batch, seq, heads,
                                    head_dim).transpose(0, 2, 1, 3)
                else:
                    q_ = k_ = v_ = x
                o_, l_ = pk._flash_attention_jit(
                    q_, k_, v_, mask, sm_scale, causal, interpret,
                    *tuned["fwd_lse"], True, layout)
                grads = pk._flash_attention_bwd_jit(
                    q_, k_, v_, mask, o_, l_, dy, sm_scale, causal,
                    interpret, *tuned["bwd"], layout)
                if layout is not None:
                    return o_, jnp.concatenate(grads, axis=-1)
                return (o_.transpose(0, 2, 1, 3).reshape(d_ctx.shape),
                        jnp.stack(grads).transpose(1, 3, 0, 2, 4)
                        .reshape(rows.shape))
            jitted = jax.jit(run)
            return lambda: jitted(rows, d_ctx)

        apart = pk.TokenMajor(heads, head_dim)
        three = jnp.split(rows, 3, axis=-1)

        def to_heads(x):
            return x.reshape(batch, seq, heads, head_dim).transpose(
                0, 2, 1, 3)

        def to_rows(x):
            return x.transpose(0, 2, 1, 3).reshape(d_ctx.shape)

        def encoder_layer(fused):
            """Three ``[B, S, H]`` arrays -> (context rows, dq, dk, dv
            rows)."""
            def run(q_, k_, v_, dy):
                if fused:
                    o_, l_ = pk._flash_attention_jit(
                        q_, k_, v_, mask, sm_scale, causal, interpret,
                        *tuned["fwd_lse"], True, apart)
                    return (o_,) + tuple(pk._flash_attention_bwd_jit(
                        q_, k_, v_, mask, o_, l_, dy, sm_scale, causal,
                        interpret, *tuned["bwd"], apart))
                heads_ = [to_heads(x) for x in (q_, k_, v_)]
                o_ = pk._flash_attention_jit(
                    *heads_, mask, sm_scale, causal, interpret,
                    *tuned["fwd"], False)
                grads = jax.vjp(reference, *heads_)[1](to_heads(dy))
                return (to_rows(o_),) + tuple(to_rows(g) for g in grads)
            jitted = jax.jit(run)
            return lambda: jitted(*three, d_ctx)

        plan += [("layer_ms.head_major", layer(None),
                  tuned["bwd"]),
                 ("layer_ms.token_major", layer(packed), tuned["bwd"]),
                 ("layer_ms.rows", encoder_layer(True), tuned["bwd"]),
                 ("layer_ms.composed", encoder_layer(False),
                  tuned["fwd"])]
    for name, run, blocks in plan:
        t0 = tel.clock()
        wall0 = time.perf_counter()
        ms = timeit(run, sync, reps=reps, windows=2) * 1000
        group, _, leaf = name.rpartition(".")
        (out.setdefault(group, {}) if group else out)[leaf] = round(ms, 4)
        if tel.enabled:
            tel.complete(
                "attn_probe", t0,
                t0 + int((time.perf_counter() - wall0) * 1e9),
                args={"kernel": name[:-3], "ms": round(ms, 4),
                      "blocks": str(tuple(blocks)), "seq": seq,
                      "head_dim": head_dim, "dtype": dtype.name})
    return out


def attribute_step(step_ms, layers, fwd_ms, bwd_ms):
    """Split a measured training-step time into attention-forward,
    attention-backward and everything-else ("XLA remainder": matmuls,
    LN, softmax head, optimizer, data movement). ``fwd_ms``/``bwd_ms``
    are per-layer kernel times from :func:`probe_attention` — pass the
    ``fwd_lse_ms`` twin for a training step, since that is the kernel
    the fused-backward forward actually runs."""
    attn_fwd = layers * float(fwd_ms)
    attn_bwd = layers * float(bwd_ms)
    remainder = max(0.0, float(step_ms) - attn_fwd - attn_bwd)
    tel = _telemetry()
    tel.set_gauge("probe_attn_fwd_ms", attn_fwd)
    tel.set_gauge("probe_attn_bwd_ms", attn_bwd)
    tel.set_gauge("probe_attn_remainder_ms", remainder)
    return {"step_ms": round(float(step_ms), 3),
            "attn_fwd_ms": round(attn_fwd, 3),
            "attn_bwd_ms": round(attn_bwd, 3),
            "xla_remainder_ms": round(remainder, 3),
            "attn_fraction": round((attn_fwd + attn_bwd)
                                   / max(float(step_ms), 1e-9), 4)}


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m hetu_tpu.tune.probe",
        description="time flash attention fwd/bwd kernels in isolation "
                    "and attribute a full step")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--causal", action="store_true")
    parser.add_argument("--no-mask", dest="mask", action="store_false")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--blocks", type=int, nargs=2, default=None,
                        metavar=("BQ", "BK"),
                        help="time every kernel at these tiles, not "
                             "the rule's")
    parser.add_argument("--step-ms", type=float, default=None,
                        help="measured full-step ms to attribute")
    parser.add_argument("--layers", type=int, default=4)
    args = parser.parse_args(argv)
    out = probe_attention(args.batch, args.heads, args.seq,
                          args.head_dim, dtype=args.dtype,
                          causal=args.causal, has_mask=args.mask,
                          reps=args.reps, blocks=args.blocks)
    if args.step_ms is not None:
        out["attribution"] = attribute_step(
            args.step_ms, args.layers, out["fwd_lse_ms"], out["bwd_ms"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
