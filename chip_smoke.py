"""Does the system still start on the chip?

    python chip_smoke.py             # one TPU chip: trainer, engine, PS
    python chip_smoke.py --chips 4   # four chips: dp / tp against twins
    python chip_smoke.py --rehearse  # tiny widths, any backend, never a pass

Drives the main paths once, in ONE process, through the entry points a
user calls, at the full width of models the repo supports, with weights
and data made from fixed seeds:

* ``bert``      — BERT-base MLM+NSP exactly as
  ``examples/nlp/bert/train_hetu_bert.py`` builds it with its defaults
  (12x768, vocab 30522, S=128, batch 64, bf16, flash attention);
* ``gpt_train`` — GPT-2 small (12x768, vocab 50257) at S=1024, batch 8,
  bf16, through the graph of ``examples/nlp/train_hetu_gpt.py``; then
  ``Executor.save``;
* ``gpt_serve`` — ``ContinuousBatchingEngine.from_checkpoint`` on those
  weights, KV pool sized from the device's own ``bytes_limit``, eight
  requests of mixed prompt lengths through ``submit()`` with the
  scheduler thread running;
* ``wdl_ps``    — Wide&Deep from ``examples/ctr/run_hetu.py`` in PS
  mode: one C++ server child, the HBM device cache, the example's own
  batch size and synthetic Criteo-shaped feed.

``bert`` and ``gpt_train`` also run one dropout op in a training step of
its own at their model's mask shape (``[256, 128, 768]``, ``[16384,
768]``): the mask kernel is in the step, the backward multiplies by the
forward's mask element for element, and the hardware generator keeps
0.9 of the elements within 1e-3.

Each phase prints one JSON line (``phase``, ``ok``, wall seconds split
into compile and steady, first/last loss or tokens generated,
``pallas_calls`` in the compiled program, ``jax``). A failed check
raises: the run ends non-zero and prints no result line. The last line
of a passing run is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it.

``--chips 4`` runs only what exists across chips: one BERT-base training
step on a ``("dp", 4)`` mesh and a dispatch-marked tensor-parallel MLP
at BERT-base's feed-forward widths, each against its single-chip twin on
the same batch.

``--rehearse`` drives the same control flow at tiny widths on whatever
backend is there, with the Pallas kernels in interpret mode. It ends
with ``"ok": false`` and a non-zero exit, so it can never be read as a
pass. Without it the script refuses to start unless ``jax.devices()``
are TPU chips.
"""
import argparse
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
import types

import numpy as np

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import cachedir
from __graft_entry__ import _assert_loss_close, _bert_graph, _feed_values

ROOT = os.path.dirname(os.path.abspath(__file__))
EXIT_REHEARSAL = 4      # a rehearsal ran to its end; never a pass


class SmokeFailure(AssertionError):
    """A phase produced something other than what it must."""


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def example(relpath):
    """Import one of the repo's example scripts as a module."""
    path = os.path.join(ROOT, "examples", relpath)
    name = "chip_smoke_" + os.path.basename(relpath)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_phase(name, fn, rehearse):
    """Run one phase and print its line. A failure prints ``ok: false``
    and re-raises — no phase failure lets the run end 0."""
    t0 = time.perf_counter()
    try:
        fields = fn(rehearse)
    except BaseException as e:
        print(json.dumps({"phase": name, "ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000],
                          "jax": jax.__version__}), flush=True)
        raise
    print(json.dumps({"phase": name, "ok": True,
                      "wall_s": round(time.perf_counter() - t0, 3),
                      **fields, "jax": jax.__version__}), flush=True)
    # drop the phase's graphs, executables and device buffers so the
    # next one starts with the whole chip
    gc.collect()
    jax.clear_caches()


def split_seconds(seconds):
    """Per-step wall seconds -> compile / steady: the first step
    carries the trace and the XLA compile."""
    steady = float(np.median(seconds[1:]))
    return {"compile_s": round(max(0.0, seconds[0] - steady), 3),
            "steady_s": round(float(np.sum(seconds[1:])), 3),
            "steady_step_s": round(steady, 4)}


def split_rounds(seconds):
    """(cold round, warm round) wall seconds -> compile / steady."""
    return {"compile_s": round(max(0.0, seconds[0] - seconds[1]), 3),
            "steady_s": round(seconds[1], 3)}


def two_steps(executor, feed):
    """Losses and wall seconds of two training steps on one feed."""
    losses, seconds = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        losses.append(float(np.asarray(
            executor.run(feed_dict=feed)[0].asnumpy())))
        seconds.append(time.perf_counter() - t0)
    return losses, seconds


def step_program(executor, feed_dict, name="default"):
    """(jitted step, its arguments) the executor compiled for this feed."""
    sub = executor.subexecutors[name]
    feed_map = {n: sub._ingest(v) for n, v in feed_dict.items()}
    return (sub.compiled[sub._shape_key(feed_map)],
            sub.trace_args(executor, feed_map))


def count_primitive(jaxpr, name):
    """Equations of one primitive in a jaxpr, nested jaxprs included
    (the printed form shares repeated sub-jaxprs, so text undercounts)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    n += count_primitive(sub, name)
    return n


def pallas_calls(jitted, args, rehearse):
    """Pallas kernel call sites in a jitted program, counted as
    ``pallas_call`` equations of its jaxpr (the lowered text shares one
    function among the layers, so text undercounts). Outside a rehearsal
    the lowering must also carry them as Mosaic custom calls — an
    interpret-mode kernel lowers to plain HLO and would not."""
    traced = jitted.trace(*args)
    n = count_primitive(traced.jaxpr.jaxpr, "pallas_call")
    if not rehearse:
        check(n == 0 or "tpu_custom_call" in traced.lower().as_text(),
              "the Pallas kernels did not lower to Mosaic custom calls")
    return n


def dropout_masks(shape, rehearse):
    """One dropout op over ones of ``shape`` in a training step of its
    own (an optimizer over a dummy parameter makes it one): the kernel
    is in the step, what the backward multiplied by is the forward's
    mask element for element, and the generator keeps its share."""
    keep_prob = 0.9
    axes = list(range(len(shape)))
    x = ht.Variable("smoke_dropout_x", trainable=False)
    y = ht.dropout_op(x, keep_prob)
    total = ht.reduce_sum_op(y, axes=axes)
    (dx,) = ht.gradients(total, [x])
    w = ht.Variable("smoke_dropout_w", value=np.ones((1,), np.float32))
    train_op = ht.optim.SGDOptimizer(learning_rate=0.0).minimize(
        total + ht.reduce_sum_op(w, axes=[0]))
    executor = ht.Executor([y, dx, train_op])
    feed = {x: np.ones(shape, np.float32)}
    shares = []
    for _ in range(2):
        out, grad = [np.asarray(r.asnumpy())
                     for r in executor.run(feed_dict=feed)[:2]]
        kept = out != 0
        check(np.allclose(out[kept], 1 / keep_prob, rtol=1e-6),
              "a kept element is not 1/keep_prob")
        check(np.array_equal(grad, out),
              "the backward's mask is not the forward's")
        shares.append(float(kept.mean()))
    n_pallas = pallas_calls(*step_program(executor, feed), rehearse)
    check(n_pallas >= 1, "no dropout-mask kernel in the step")
    # 1e-3 is 12 binomial standard deviations of a [16384, 768] mask
    check(rehearse or all(abs(s - keep_prob) < 1e-3 for s in shares),
          f"kept shares {shares} of {shape}, want {keep_prob} +- 1e-3")
    check(shares[0] != shares[1], "two steps drew the same mask")
    return {"dropout_kept_shares": shares,
            "dropout_mask_kernels": n_pallas}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_bert(rehearse):
    bert = example("nlp/bert/train_hetu_bert.py")
    # the synthetic feed starts AT the uniform-prediction floor; what
    # there is to learn (labels repeat the unmasked input) shows only
    # after a few hundred steps, so that many are run
    argv = ["--num-steps", "300", "--log-every", "1"]
    if rehearse:
        argv += ["--vocab-size", "512", "--hidden-size", "64",
                 "--num-layers", "2", "--num-heads", "4",
                 "--batch-size", "16", "--lr", "1e-3"]
    args = bert.parse_args(argv)
    session = bert.build(args)
    results = bert.run(args, session)
    losses = results["losses"]
    check(len(losses) >= 6, f"{len(losses)} steps ran, need >= 6")
    check(np.all(np.isfinite(losses)), f"non-finite loss in {losses}")
    # every step draws a fresh batch (15% of positions labelled, so the
    # per-step loss wanders by a few percent): the end is read as the
    # median of the last ten steps
    end_loss = float(np.median(losses[-10:]))
    check(end_loss < losses[0],
          f"loss did not fall: {losses[0]} -> {end_loss} "
          f"(median of the last ten)")
    executor, feed_nodes = session
    feed = dict(zip(feed_nodes, bert.synthetic_batch(
        np.random.RandomState(0), args.batch_size, args.seq_length,
        args.vocab_size)))
    n_pallas = pallas_calls(*step_program(executor, feed), rehearse)
    check(n_pallas >= args.num_layers,
          f"{n_pallas} Pallas kernels in the compiled step of a "
          f"{args.num_layers}-layer model: the flash path did not run")
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": end_loss, "pallas_calls": n_pallas,
            **dropout_masks((64, 128) if rehearse else (256, 128, 768),
                            rehearse),
            **split_seconds(results["window_seconds"])}


def phase_gpt_train(rehearse, run):
    gpt = example("nlp/train_hetu_gpt.py")
    if rehearse:
        # S=512 keeps the fused backward
        # (ops/attention.py FUSED_BWD_MIN_SEQ) on the rehearsed path
        argv = ["--vocab-size", "512", "--hidden-size", "32",
                "--num-layers", "2", "--num-heads", "2",
                "--seq-len", "512", "--batch-size", "2",
                "--nsamples", "8"]
    else:
        argv = ["--vocab-size", "50257", "--hidden-size", "768",
                "--num-layers", "12", "--num-heads", "12",
                "--seq-len", "1024", "--batch-size", "8",
                "--nsamples", "32"]
    args = gpt.parse_args(argv)
    run.corpus = gpt.load_corpus(args)
    run.config, ids, labels, lm_loss, train_op = gpt.build_graph(args)
    executor = ht.Executor([lm_loss, train_op], dtype=jnp.bfloat16)
    losses, seconds = [], []
    for x, y in gpt.batches(args, run.corpus):
        t0 = time.perf_counter()
        out = executor.run(feed_dict={ids: x, labels: y},
                           convert_to_numpy_ret_vals=True)
        losses.append(float(out[0]))
        seconds.append(time.perf_counter() - t0)
    check(len(losses) >= 3, f"{len(losses)} steps ran, need >= 3")
    check(np.all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    n_pallas = pallas_calls(
        *step_program(executor, {ids: x, labels: y}), rehearse)
    # forward(+lse) and the two backward kernels per layer
    check(n_pallas >= 3 * args.num_layers,
          f"{n_pallas} Pallas kernels in the compiled step of a "
          f"{args.num_layers}-layer model: the fused path did not run")
    executor.save(run.checkpoint)
    return {"steps": len(losses), "first_loss": losses[0],
            "last_loss": losses[-1], "losses": losses,
            "pallas_calls": n_pallas,
            **dropout_masks((64, 128) if rehearse else (16384, 768),
                            rehearse),
            **split_seconds(seconds)}


def latent_moe_check(rehearse):
    """The latent-attention expert block against the plain reference,
    in bfloat16: a prefill and 8 decode steps through the paged latent
    cache. On the chip the preset has the published head shapes (nope
    128 / rope 64 / value 128, latent 512) at a small hidden size, so
    the flash call at 192 / 128, the absorbed decode kernel and the
    grouped matmul are tried before a benchmark cell is."""
    import jax
    import jax.numpy as jnp
    from benchmark.families import sarvam_mla as family
    from benchmark.reference import sarvam_mla as reference
    from hetu_tpu.models import latent_moe as lm
    from hetu_tpu.serving.kvcache import PagedKVCache

    heads = dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32) if rehearse else dict(
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256)
    prompt_len, block = (24, 4) if rehearse else (256, 16)
    config = dict(
        heads, vocab_size=1024, num_hidden_layers=3, num_attention_heads=4,
        num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
        first_k_dense_replace=1, routed_scaling_factor=2.5,
        rms_norm_eps=1e-6, rope_theta=10000, max_position_embeddings=4096,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1, "type":
                      "deepseek_yarn",
                      "original_max_position_embeddings": 64},
        deployment={"num_routed_experts": 8, "experts_first": 0},
        assumed={"initializer_std": 0.05, "router_bias_std": 0.02},
        serve_dtype="bfloat16")
    weights = family.seeded_weights(config, 3)
    cfg = family.model_config(config)
    params = lm.latent_moe_serving_params(cfg, weights.__getitem__)
    steps = 8
    context = 2 * prompt_len
    cache = PagedKVCache(cfg, num_blocks=context // block, block_size=block)
    tokens = np.random.RandomState(0).randint(
        0, 1024, prompt_len + steps).astype(np.int32)
    cache.add_seq(0, context)
    prefill = jax.jit(lm.latent_moe_paged_prefill,
                      static_argnames="config")
    step = jax.jit(lm.latent_moe_paged_step, static_argnames="config")
    (logits, _), pools = prefill(
        params, cache.pools, jnp.asarray(tokens[None, :prompt_len]),
        jnp.asarray(cache.slot_mapping(0, 0, prompt_len)[None]),
        jnp.asarray([prompt_len - 1]), config=cfg)
    got = [np.asarray(logits[0])]
    grid = jnp.asarray(cache.gather_slots([0], context))
    for pos in range(prompt_len, prompt_len + steps):
        (logits, _), pools = step(
            params, pools, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos]), grid,
            jnp.asarray([cache.slot_of(0, pos)]), config=cfg)
        got.append(np.asarray(logits[0]))
    rows = np.arange(prompt_len - 1, prompt_len + steps)
    want, layers = reference.forward(weights, config, tokens, rows)
    # a row where an expert layer's call was closer than bfloat16
    # resolves may have swapped an expert: held to the reference where
    # the calls were clear
    clear = np.min([layer["margin"] for layer in layers], axis=0) > 0.01
    err = np.sqrt(np.mean(np.square(np.asarray(got) - want), axis=-1)) \
        / float(want.std())
    check(clear.sum() >= 3, f"only {clear.sum()} rows with clear calls")
    check(float(err[clear].max()) < 0.1,
          f"latent-MoE block is {err.tolist()} of the logits' spread "
          f"from the reference (clear rows {clear.tolist()})")
    return {"latent_moe_worst_row_error": float(err[clear].max()),
            "latent_moe_rows_checked": int(clear.sum())}


def phase_gpt_serve(rehearse, run):
    from hetu_tpu.models.gpt import gpt_forward
    from hetu_tpu.serving.scheduler import ContinuousBatchingEngine

    cfg = run.config
    if rehearse:
        # the CPU backend reports no bytes_limit to size the pool from
        lengths, new_tokens, kw = (17, 40, 100, 100, 128, 200, 300, 256), \
            8, {"num_blocks": 256}
    else:
        lengths, new_tokens, kw = (17, 40, 100, 100, 300, 512, 650, 700), \
            32, {}
    # prompts are corpus rows; 2 and 3 are the SAME prompt, and the
    # power-of-two one is compared with the plain forward below (its
    # prompt bucket is its own length, so both run one kernel shape)
    prompts = [run.corpus[i % len(run.corpus), :n].astype(np.int32)
               for i, n in enumerate(lengths)]
    prompts[3] = prompts[2].copy()
    probe = next(i for i, n in enumerate(lengths)
                 if n >= 128 and n & (n - 1) == 0)

    engine = ContinuousBatchingEngine.from_checkpoint(
        cfg, run.checkpoint, **kw)
    try:
        rounds, seconds = [], []
        for _ in range(2):      # cold (compiles), then steady
            t0 = time.perf_counter()
            futures = [engine.submit(p, new_tokens) for p in prompts]
            rounds.append([f.result(timeout=1000) for f in futures])
            seconds.append(time.perf_counter() - t0)
        stats = engine.stats()
        for outs in rounds:
            check(all(o.shape == (new_tokens,) for o in outs),
                  f"wrong output shapes {[o.shape for o in outs]}")
            check(all(0 <= int(t) < cfg.vocab_size
                      for o in outs for t in o), "token out of vocabulary")
            check(np.array_equal(outs[2], outs[3]),
                  f"identical prompts, different greedy outputs: "
                  f"{outs[2]} vs {outs[3]}")
        check(all(np.array_equal(a, b) for a, b in zip(*rounds)),
              "greedy outputs changed between the two rounds")

        p = prompts[probe]
        nh = cfg.num_attention_heads
        logits = jax.jit(gpt_forward, static_argnames=("num_heads",))(
            engine.params, jnp.asarray(p)[None], num_heads=nh)
        want = int(jnp.argmax(logits[0, -1]))
        got = int(rounds[0][probe][0])
        check(got == want,
              f"first token of the {len(p)}-token request is {got}; the "
              f"plain forward says {want}")
        slots = jnp.zeros((1, len(p)), jnp.int32)
        n_pallas = pallas_calls(
            engine._prefill_fn,
            (engine.params, engine.cache.pools, jnp.asarray(p)[None],
             slots), rehearse)
        check(n_pallas >= cfg.num_hidden_layers,
              f"{n_pallas} Pallas kernels in the prefill program")
    finally:
        engine.close()
    return {"requests": 2 * len(prompts),
            "tokens_generated": 2 * len(prompts) * new_tokens,
            "first_token_matches_plain_forward": True,
            "kv_blocks": stats["kv_blocks"],
            "kv_pool_bytes": engine.cache.hbm_bytes(),
            "jit_compiles": stats["jit_compiles"],
            "pallas_calls": n_pallas, **latent_moe_check(rehearse),
            **split_rounds(seconds)}


def phase_wdl_ps(rehearse):
    from hetu_tpu.ps import client as ps_client
    from hetu_tpu.ps import server as ps_server

    ctr = example("ctr/run_hetu.py")
    argv = ["--model", "wdl_criteo", "--comm-mode", "PS", "--all",
            "--nepoch", "2"]
    if rehearse:
        argv += ["--dim", "20000", "--nsamples", str(128 * 40)]
    args = ctr.parse_args(argv)
    try:
        results = ctr.worker(args)      # ends with executor.close()
        servers = list(ps_server._server_procs)
    finally:
        ps_client.get_default_client().shutdown_servers()
        ps_client.close_default_client()
        ps_server.shutdown_server()
    losses, seconds = results["epoch_losses"], results["epoch_times"]
    check(len(servers) == 1, f"{len(servers)} PS server children")
    check(all(p.poll() is not None for p in servers),
          "a PS server process survived shutdown")
    check(np.all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    steps_per_epoch = int(args.nsamples * 0.9) // args.batch_size
    return {"steps": steps_per_epoch * args.nepoch,
            "first_loss": losses[0], "last_loss": losses[-1],
            "pallas_calls": 0,
            "server_exit_codes": [p.returncode for p in servers],
            **split_rounds(seconds)}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def distinct_devices(arr):
    return len({s.device for s in arr.addressable_shards})


def phase_dp4(rehearse):
    """Two BERT training steps with the batch sharded over a ("dp", 4)
    mesh, against the single-chip twin (parameter init is seeded by
    name, so the twin materializes bit-identical weights). The second
    loss is what checks the reduced gradients, so the optimizer is SGD:
    Adam's first step is sign(g) — blind to a wrong 1/N on the
    all-reduce, and it turns rounding noise on near-zero gradients into
    full-size updates."""
    from jax.sharding import Mesh
    from hetu_tpu.executor import Executor, HetuConfig

    n = 4
    shape = dict(vocab=64, hidden=32, layers=2, heads=4, intermediate=64,
                 seq_len=16) if rehearse else {}
    vocab, seq_len, batch = (64, 16, 8) if rehearse else (30522, 128, 64)

    def build(mesh):
        loss, feed_nodes = _bert_graph(dropout=0.0, **shape)
        train_op = ht.optim.SGDOptimizer(
            learning_rate=0.01).minimize(loss)
        if mesh is None:
            return Executor([loss, train_op]), feed_nodes
        config = HetuConfig(eval_node_list=[loss, train_op], mesh=mesh)
        config.nrank = n
        return Executor({"default": [loss, train_op]},
                        config=config), feed_nodes

    mesh = Mesh(np.asarray(jax.devices()[:n]), axis_names=("dp",))
    exe, feed_nodes = build(mesh)
    feed = _feed_values(feed_nodes, batch, seq_len, vocab)
    got, seconds = two_steps(exe, feed)
    spread = {distinct_devices(a) for a in exe.params.values()}
    check(spread == {n}, f"parameters live on {spread} devices, not {n}")
    sub = exe.subexecutors["default"]
    ids = sub._ingest(feed[feed_nodes[0]])
    check(distinct_devices(ids) == n
          and ids.addressable_shards[0].data.shape[0] == batch // n,
          f"batch not split {n} ways: {ids.sharding}")
    jitted, jitted_args = step_program(exe, feed)
    text = jitted.lower(*jitted_args).compile().as_text()
    check("all-reduce" in text, "no all-reduce in the compiled dp step")
    twin, feed_nodes_r = build(None)
    want, _ = two_steps(
        twin, _feed_values(feed_nodes_r, batch, seq_len, vocab))
    for step, (g, w) in enumerate(zip(got, want)):
        _assert_loss_close(f"dp step {step}", g, w)
    return {"mesh": {"dp": n}, "losses": got, "twin_losses": want,
            "all_reduce": True, "pallas_calls": 0,
            **split_rounds(seconds)}


def phase_tp4(rehearse):
    """A dispatch-marked MLP at BERT-base's feed-forward widths, second
    weight column-split four ways by the partition-state planner,
    against its unsplit twin."""
    from hetu_tpu.executor import Executor

    tp = 4
    hidden, inner, rows = (32, 64, 16) if rehearse else (768, 3072, 1024)
    rng = np.random.RandomState(0)
    w1_v = rng.randn(hidden, inner).astype("f") * 0.05
    w2_v = rng.randn(inner, hidden).astype("f") * 0.05
    x_v = rng.randn(rows, hidden).astype("f")
    y_v = np.eye(hidden, dtype="f")[rng.randint(0, hidden, rows)]

    def build(split):
        x = ht.Variable("x", trainable=False)
        y_ = ht.Variable("y_", trainable=False)
        w1 = ht.Variable("w1", value=w1_v)
        w2 = ht.Variable("w2", value=w2_v)
        act = ht.relu_op(ht.matmul_op(x, w1))
        if split:
            act = ht.dispatch(act, (1, 1))
            w2 = ht.dispatch(w2, (1, tp))       # Megatron column split
        act = ht.matmul_op(act, w2)
        if split:
            act = ht.dispatch(act, (1, 1))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(act, y_), [0])
        train_op = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
        return Executor([loss, train_op]), {x: x_v, y_: y_v}

    exe, feed = build(split=True)
    check(exe.config.mesh is not None and exe.config.model_axes,
          "the TP planner built no mesh")
    got, seconds = two_steps(exe, feed)
    split_shapes = sorted(
        {tuple(a.addressable_shards[0].data.shape)
         for a in exe.params.values()
         if distinct_devices(a) == tp
         and a.addressable_shards[0].data.shape != a.shape})
    check(split_shapes == [(inner, hidden // tp)],
          f"expected w2 column-split over {tp} devices, found shard "
          f"shapes {split_shapes}")
    twin, feed_r = build(split=False)
    want, _ = two_steps(twin, feed_r)
    for step, (g, w) in enumerate(zip(got, want)):
        _assert_loss_close(f"tp step {step}", g, w)
    return {"mesh": dict(exe.config.model_axes), "losses": got,
            "twin_losses": want, "w2_shard": list(split_shapes[0]),
            "pallas_calls": 0, **split_rounds(seconds)}


# ---------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths, interpret-mode kernels, any "
                             "backend; always ends ok=false, non-zero")
    args = parser.parse_args(argv)

    dev = jax.devices()[0]
    count = len(jax.devices())
    if args.rehearse:
        from hetu_tpu.ops import (attention, pallas_attention,
                                  pallas_dropout, pallas_norm,
                                  pallas_sparse_update)
        # steer the platform-decided kernel dispatch from here, as the
        # tests do: the program itself has no such option
        pallas_attention.INTERPRET = True
        pallas_norm.INTERPRET = True
        pallas_dropout.INTERPRET = True
        pallas_sparse_update.INTERPRET = True
        attention._use_pallas = lambda: True
        check(count >= args.chips,
              f"rehearsal of --chips {args.chips} needs that many "
              f"devices (XLA_FLAGS=--xla_force_host_platform_device_"
              f"count={args.chips}), have {count}")
    else:
        check(dev.platform == "tpu",
              f"chip_smoke needs a TPU; JAX found {dev.platform!r} "
              f"({dev.device_kind})")
        check(count == args.chips,
              f"--chips {args.chips} but JAX found {count} device(s)")
        cachedir.enable_compile_cache()
    os.makedirs(cachedir.STATE_ROOT, exist_ok=True)

    if args.chips == 4:
        run_phase("dp4_bert", phase_dp4, args.rehearse)
        run_phase("tp4_mlp", phase_tp4, args.rehearse)
    else:
        run_phase("bert", phase_bert, args.rehearse)
        with tempfile.TemporaryDirectory(
                prefix="chip_smoke_ckpt_", dir=cachedir.STATE_ROOT) as ckpt:
            # what the training phase hands the serving phase
            gpt_run = types.SimpleNamespace(checkpoint=ckpt, config=None,
                                            corpus=None)
            run_phase("gpt_train",
                      lambda r: phase_gpt_train(r, gpt_run), args.rehearse)
            run_phase("gpt_serve",
                      lambda r: phase_gpt_serve(r, gpt_run), args.rehearse)
        run_phase("wdl_ps", phase_wdl_ps, args.rehearse)

    print(json.dumps({"ok": not args.rehearse,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": count}}), flush=True)
    return EXIT_REHEARSAL if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
