"""GPT's serving parameters stacked over layers (PR 44): the block's
eight vectors as ``[L, n]`` arrays, its four matrices an array a layer
(``models/gpt.py:gpt_serving_params`` says why), so a jitted call walks
5 + 8 + 4 L parameter arrays where it walked 5 + 12 L. The paged pools
stay a ``{"k", "v"}`` entry a layer, as every model's are.

The reference here is ``per_name_forward``: the decoder written over
the checkpoint's per-NAME weights with a Python list of blocks, the form
the serving block had before the stacks. It shares no stacking or
indexing code with the engine's trees, so a wrong layer's matrix or
vector shows as another token. CPU, tiny float32 GPT (3 layers).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu.models as M
from hetu_tpu import telemetry
from hetu_tpu.models.gpt import gpt_forward, gpt_param_names
from hetu_tpu.ops.attention import attention_reference
from hetu_tpu.serving import ContinuousBatchingEngine, PagedKVCache

from gpt_reference import VOCAB

SEQ = 64
LAYERS = 3


def _cfg():
    return M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                       num_hidden_layers=LAYERS, num_attention_heads=4,
                       max_position_embeddings=SEQ,
                       hidden_dropout_prob=0.0)


def _weights(cfg, seed):
    """name -> float32 array for every name of ``gpt_param_names``:
    what a checkpoint holds."""
    rng = np.random.RandomState(seed)
    h, i = cfg.hidden_size, cfg.intermediate_size
    shapes = {"ln1": ((h,), (h,)), "ln2": ((h,), (h,)),
              "qkv": ((h, 3 * h), (3 * h,)), "proj": ((h, h), (h,)),
              "fc": ((h, i), (i,)), "mlp_proj": ((i, h), (h,))}
    names = gpt_param_names(cfg)
    out = {names["wte"]: rng.randn(cfg.vocab_size, h) * 0.3,
           names["wpe"]: rng.randn(SEQ, h) * 0.3,
           names["ln_f"][0]: 1.0 + 0.1 * rng.randn(h),
           names["ln_f"][1]: 0.1 * rng.randn(h),
           names["lm_head"]: rng.randn(h, cfg.vocab_size) * 0.3}
    for blk in names["blocks"]:
        for role, pair in blk.items():
            for name, shape in zip(pair, shapes[role]):
                out[name] = (1.0 if name.endswith("_scale") else 0.0) \
                    + rng.randn(*shape) * 0.2
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


def _ln(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jnp.reciprocal(jnp.sqrt(var + 1e-12)) * scale + bias


def per_name_forward(cfg, weights, ids):
    """Logits ``[B, S, V]`` of the pre-LN decoder over per-name
    weights, a block at a time by NAME."""
    names = gpt_param_names(cfg)
    w = weights.__getitem__
    nh = cfg.num_attention_heads
    b, s = ids.shape
    x = w(names["wte"])[ids] + w(names["wpe"])[:s][None]
    hidden = x.shape[-1]
    mask = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0, -1e9)[None, None]
    for blk in names["blocks"]:
        qkv = _ln(x, *map(w, blk["ln1"])) @ w(blk["qkv"][0]) + w(blk["qkv"][1])
        q, k, v = (qkv[..., j * hidden:(j + 1) * hidden]
                   .reshape(b, s, nh, hidden // nh).transpose(0, 2, 1, 3)
                   for j in range(3))
        ctx = attention_reference(q, k, v, mask,
                                  1.0 / float(np.sqrt(hidden // nh)))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(x.shape)
        x = x + (ctx @ w(blk["proj"][0]) + w(blk["proj"][1]))
        h = jax.nn.gelu(_ln(x, *map(w, blk["ln2"])) @ w(blk["fc"][0])
                        + w(blk["fc"][1]), approximate=True)
        x = x + (h @ w(blk["mlp_proj"][0]) + w(blk["mlp_proj"][1]))
    return _ln(x, *map(w, names["ln_f"])) @ w(names["lm_head"])


def per_name_chain(cfg, weights, prompt, n):
    """The ``n`` greedy tokens after ``prompt``: a full per-name forward
    a token."""
    cur = np.asarray(prompt, np.int32)[None]
    for _ in range(n):
        nxt = np.argmax(np.asarray(
            per_name_forward(cfg, weights, jnp.asarray(cur)))[:, -1], -1)
        cur = np.concatenate([cur, nxt[:, None].astype(np.int32)], axis=1)
    return cur[0, len(prompt):].tolist()


def _drive(engine, futures, limit=800):
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, _weights(cfg, 11)


@pytest.fixture(scope="module")
def trace(model):
    """Requests that share a 12-token prefix (something for the prefix
    cache and the copy-on-write to do) and the tokens the per-name
    reference gives each."""
    cfg, weights = model
    rng = np.random.RandomState(5)
    shared = rng.randint(0, VOCAB, 12)
    prompts = [np.concatenate([shared, rng.randint(0, VOCAB, n)])
               .astype(np.int32) for n in (3, 5, 2)]
    prompts += [rng.randint(0, VOCAB, n).astype(np.int32) for n in (7, 18)]
    new = [5, 4, 6, 5, 4]
    want = [per_name_chain(cfg, weights, p, n)
            for p, n in zip(prompts, new)]
    return prompts, new, want


def test_the_stacked_forward_is_the_per_name_forward_bit_for_bit(model):
    """Same blocks, same order, same float32 arithmetic: a static index
    into a stack is the matrix that went in."""
    cfg, weights = model
    ids = jnp.asarray(np.random.RandomState(3).randint(0, VOCAB, (2, 9)))
    params = cfg.serving_model().params(weights.__getitem__)
    got = gpt_forward(params, ids, num_heads=cfg.num_attention_heads)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(per_name_forward(cfg, weights, ids)))
    names = gpt_param_names(cfg)
    for i, blk in enumerate(names["blocks"]):
        for role, (w_name, b_name) in blk.items():
            w, b = params["blocks"][role]
            np.testing.assert_array_equal(w[i], weights[w_name])
            np.testing.assert_array_equal(b[i], weights[b_name])
    # vectors are stacks, matrices an array a layer
    for role, (w, b) in params["blocks"].items():
        assert b.shape[0] == LAYERS
        if role.startswith("ln"):
            assert w.shape == (LAYERS, cfg.hidden_size)
        else:
            assert isinstance(w, tuple) and len(w) == LAYERS


ENGINES = {
    "plain": dict(num_blocks=64),
    "chunked_prefix": dict(num_blocks=64, prefix_cache=True,
                           prefill_chunk=8),
    # a pool too small for everyone to grow: preempt, requeue, replay
    "preempt_replay": dict(num_blocks=7, reserve="lazy",
                           prefix_cache=True, prefill_chunk=8),
}


@pytest.mark.parametrize("mode", list(ENGINES))
def test_engine_tokens_are_the_per_name_reference_tokens(model, trace, mode):
    cfg, weights = model
    prompts, new, want = trace
    tel = telemetry.Telemetry(enabled=True)
    engine = ContinuousBatchingEngine(
        cfg, weights.__getitem__, block_size=4, max_batch_size=4,
        start=False, telemetry=tel, **ENGINES[mode])
    futures = [engine.submit(p, n) for p, n in zip(prompts, new)]
    _drive(engine, futures)
    assert [f.result(1).tolist() for f in futures] == want
    if mode == "preempt_replay":
        assert tel.counter_value("engine_preemptions") > 0, \
            "the small lazy pool never preempted: the case lost its point"
    if mode != "plain":
        assert engine.cache.cow_copies > 0, "nothing was copied on write"
    engine.cache.assert_consistent()
    engine.close()


def test_the_decode_call_walks_tens_of_arrays(model):
    """5 + 8 + 4 L parameter arrays and 2 L pools (149 + 24 at GPT-2
    small's depth before, 61 + 24 now), and ``stats()["program_leaves"]``
    counts what the first greedy decode call was handed (those and its
    four inputs) and handed back (the ids and the pools); the
    ``jit_compile`` span carries both."""
    cfg, weights = model
    tel = telemetry.Telemetry(enabled=True)
    engine = ContinuousBatchingEngine(
        cfg, weights.__getitem__, num_blocks=16, block_size=4,
        max_batch_size=2, start=False, telemetry=tel)
    trees = jax.tree_util.tree_leaves((engine.params, engine.cache.pools))
    assert len(jax.tree_util.tree_leaves(engine.params)) \
        == 5 + 8 + 4 * LAYERS < 5 + 12 * LAYERS
    assert len(jax.tree_util.tree_leaves(engine.cache.pools)) == 2 * LAYERS
    assert engine.stats()["program_leaves"] is None     # nothing ran yet
    futures = [engine.submit(np.arange(5, dtype=np.int32), 3)]
    _drive(engine, futures)
    assert engine.stats()["program_leaves"] == {"in": len(trees) + 4,
                                                "out": 1 + 2 * LAYERS}
    spans = [e["args"] for e in tel.tracer.drain()
             if e.get("name") == "jit_compile"]
    decode = [a for a in spans if a["shape_key"].startswith("('decode'")]
    assert decode and all(a["leaves_in"] == len(trees) + 4
                          and a["leaves_out"] == 1 + 2 * LAYERS
                          for a in decode)
    assert all("leaves_in" in a and "leaves_out" in a for a in spans)
    engine.close()


def test_copy_on_write_copies_the_block_in_every_layer_and_no_other(model):
    cfg, _ = model
    cache = PagedKVCache(cfg, num_blocks=6, block_size=4, prefix_cache=True)
    assert len(cache.pools) == LAYERS
    assert cache.pools[0]["k"].shape == (7, 4, cfg.hidden_size)
    # every row of every layer its own number
    filled = [{name: (jnp.arange(pool.size, dtype=jnp.float32)
                      .reshape(pool.shape) + 1000.0 * layer
                      + (0.5 if name == "v" else 0.0))
               for name, pool in entry.items()}
              for layer, entry in enumerate(cache.pools)]
    before = [{name: np.asarray(pool) for name, pool in entry.items()}
              for entry in filled]
    cache.pools = filled
    prompt = np.arange(6, dtype=np.int32)           # 1 full block + 2 tail
    cache.add_seq_prefix(0, 6 + 4, prompt)
    cache.insert_prefix(0, prompt)
    src = cache.tables[0][1]
    assert cache.ensure_writable(0, 6, 7) == 1      # the frozen tail
    dst = cache.tables[0][1]
    assert dst != src
    untouched = [b for b in range(7) if b != dst]
    for layer, entry in enumerate(cache.pools):
        for name, pool in entry.items():
            after = np.asarray(pool)
            np.testing.assert_array_equal(after[dst],
                                          before[layer][name][src])
            np.testing.assert_array_equal(after[untouched],
                                          before[layer][name][untouched])
    cache.assert_consistent()


def test_the_pools_cost_a_k_and_a_v_row_a_layer(model):
    from hetu_tpu.serving.kvcache import kv_block_bytes
    cfg, _ = model
    row = 2 * cfg.hidden_size * 4                   # k and v, float32
    assert kv_block_bytes(cfg, 4) == LAYERS * 4 * row
    cache = PagedKVCache(cfg, num_blocks=6, block_size=4)
    assert cache.kv_bytes() == sum(
        pool.nbytes for pool in jax.tree_util.tree_leaves(cache.pools))


def test_the_older_drivers_warm_up_call_dispatches_and_warms(model):
    """``benchmark/drivers/serve_openloop.py:warm`` reaches into the
    engine: ``_dispatch`` with the keys ``("prefill", bb, pb)`` /
    ``("decode", bb, cb)``, ``_prefill_fn`` / ``_step_fn``,
    ``engine.params`` and ``engine.cache.pools`` as opaque trees,
    device arrays for the rest, the returned pools stored back. The
    call is repeated here argument for argument; afterwards requests
    of those buckets compile nothing (by the engine's count and by the
    backend's own)."""
    cfg, weights = model
    engine = ContinuousBatchingEngine(
        cfg, weights.__getitem__, num_blocks=32, block_size=4,
        max_batch_size=2, start=False)
    for bb in engine.batch_buckets:
        for pb in (4, 8):
            zeros = jnp.zeros((bb, pb), jnp.int32)
            logits, engine.cache.pools = engine._dispatch(
                ("prefill", bb, pb), engine._prefill_fn, engine.params,
                engine.cache.pools, zeros, zeros)
            assert logits.shape == (bb, pb, VOCAB)
            for n in range(bb // 2 + 1, bb + 1):
                np.asarray(logits[jnp.arange(n),
                                  jnp.asarray([pb - 1] * n)])
        for cb in (4, 8, 16):
            row = jnp.zeros(bb, jnp.int32)
            ids, engine.cache.pools = engine._dispatch(
                ("decode", bb, cb), engine._step_fn, engine.params,
                engine.cache.pools, row, row,
                jnp.zeros((bb, cb), jnp.int32), row)
            assert ids.shape == (bb,) and ids.dtype == jnp.int32
    warmed = engine.jit_compiles
    assert warmed == len(engine.batch_buckets) * 5
    want = per_name_chain(cfg, weights, np.arange(3, 9), 4)
    compiled = []

    def listen(event, *_, **__):
        if event.endswith("backend_compile_duration"):
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        futures = [engine.submit(np.arange(3, 9, dtype=np.int32), 4),
                   engine.submit(np.arange(3, 9, dtype=np.int32), 4)]
        _drive(engine, futures)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [f.result(1).tolist() for f in futures] == [want, want]
    assert engine.jit_compiles == warmed, "a warmed bucket compiled again"
    assert not compiled, "the backend compiled after the warm-up"
    engine.close()


def test_from_checkpoint_round_trip(model, trace, tmp_path):
    """One ``.npy`` a name, as ``Executor.save`` writes them, stacked at
    the engine's build: the tokens of the engine over the live arrays."""
    cfg, weights = model
    prompts, new, want = trace
    for name, value in weights.items():
        np.save(tmp_path / f"{name}.npy", np.asarray(value))
    engine = ContinuousBatchingEngine.from_checkpoint(
        cfg, str(tmp_path), num_blocks=64, block_size=4,
        max_batch_size=4, start=False)
    assert engine.params["blocks"]["qkv"][1].shape == (
        LAYERS, 3 * cfg.hidden_size)
    futures = [engine.submit(p, n) for p, n in zip(prompts, new)]
    _drive(engine, futures)
    assert [f.result(1).tolist() for f in futures] == want
    engine.close()
