"""The decode step's data path (ISSUE 25): an all-greedy step picks its
tokens inside ``hetu_paged_decode`` and brings ``bb`` int32 ids back; a
step that holds a ``temperature > 0`` sequence runs the logits-returning
twin and picks on the host, token for token as before. Everything here
is counted or compared on the CPU, nothing is timed.
"""
import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.models.gpt import gpt_paged_prefill, gpt_paged_step
from hetu_tpu.serving import ContinuousBatchingEngine, PagedKVCache
from hetu_tpu.serving.scheduler import _choose_token

from gpt_reference import VOCAB, gpt_session

# engine keywords of the three ways a decode step's build phase can go:
# nothing special, copy-on-write out of the prefix cache with chunked
# prefill, and a lazy pool small enough that sequences are preempted
VARIANTS = {
    "plain": dict(num_blocks=40),
    "prefix_chunked": dict(num_blocks=40, prefix_cache=True,
                           prefill_chunk=8),
    "lazy_preempting": dict(num_blocks=7, reserve="lazy"),
}


@pytest.fixture(scope="module")
def model():
    return gpt_session(seed=11)


def _engine(model, **kw):
    cfg, sess = model
    kw.setdefault("telemetry", False)
    return ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, start=False, **kw)


def _prompts(n=4, shared=8):
    """``n`` prompts of 10-13 tokens whose first ``shared`` (two blocks)
    agree, so the prefix cache has something to hit."""
    rng = np.random.RandomState(5)
    head = rng.randint(0, VOCAB, shared)
    return [np.concatenate([head, rng.randint(0, VOCAB, 2 + i)])
            for i in range(n)]


def _serve(engine, requests):
    """Submit one request (a tuple of ``submit``'s arguments) a step, so
    that later ones are admitted beside running ones and find the
    earlier prompts' blocks in the prefix cache; then drive to the
    end."""
    futures = []
    for request in requests:
        futures.append(engine.submit(*request))
        engine.step()
    return _drive(engine, futures)


def _drive(engine, futures, limit=500):
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"
    return [f.result(1) for f in futures]


def _teacher_logits(engine, ids):
    """``[len(ids), V]`` float32 logits of one causal forward over
    ``ids`` through ``gpt_paged_prefill``, its K/V rows thrown into the
    scratch block of a pool of its own."""
    import jax.numpy as jnp
    pools = PagedKVCache(engine.config, num_blocks=1, block_size=4).pools
    logits, _ = gpt_paged_prefill(
        engine.params, pools, jnp.asarray(ids[None], jnp.int32),
        jnp.zeros((1, len(ids)), jnp.int32),
        num_heads=engine.config.num_attention_heads)
    return np.asarray(logits[0])


def _reference_tokens(engine, prompt, out, temperature=0.0, seed=0):
    """What ``_choose_token`` picks at each index from the
    teacher-forced logits over ``prompt + out``."""
    p = len(prompt)
    logits = _teacher_logits(engine, np.concatenate([prompt, out[:-1]]))
    return [_choose_token(logits[p - 1 + k], temperature, seed, k)
            for k in range(len(out))]


# ---------------------------------------------------------------------------
# (a) the device's pick is the host's pick
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_greedy_outputs_equal_teacher_forced_argmax(model, variant):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(model, telemetry=tel, **VARIANTS[variant])
    prompts = _prompts()
    outs = _serve(engine, [(p, 6) for p in prompts])
    for prompt, out in zip(prompts, outs):
        assert out.dtype == np.int32 and out.shape == (6,)
        assert out.tolist() == _reference_tokens(engine, prompt, out)
    # every decode step of an all-greedy run picked on the device
    assert engine.decode_steps > 0
    assert engine.decode_device_pick_steps == engine.decode_steps
    assert engine.cache.referenced_blocks == 0
    if variant == "lazy_preempting":
        assert tel.counter_value("engine_preemptions") > 0, \
            "the 7-block pool never preempted: the case lost its point"
    if variant == "prefix_chunked":
        assert engine.cache.prefix.hit_rate() > 0
    engine.close()


@pytest.mark.parametrize("twin_offset", [5, -5])
def test_pick_greedy_is_argmax_of_the_logits_with_a_planted_tie(
        model, twin_offset):
    """Two equal ``lm_head`` columns tie at the top of row 0: the
    program's pick is ``np.argmax``'s, the first of the two, whichever
    side the twin lies on."""
    import jax
    import jax.numpy as jnp
    engine = _engine(model, num_blocks=16)
    cfg = engine.config
    rng = np.random.RandomState(2)
    b, ctx = 3, 8
    tokens = jnp.asarray(rng.randint(0, VOCAB, b), jnp.int32)
    positions = jnp.asarray([0, 0, 0], jnp.int32)
    grid = jnp.zeros((b, ctx), jnp.int32)
    slots = jnp.zeros(b, jnp.int32)

    def step(params, **kw):
        pools = jax.tree_util.tree_map(jnp.copy, engine.cache.pools)
        return gpt_paged_step(params, pools, tokens, positions, grid,
                              slots, num_heads=cfg.num_attention_heads,
                              **kw)[0]

    top = int(np.argmax(np.asarray(step(engine.params))[0]))
    twin = (top + twin_offset) % VOCAB
    head = np.array(engine.params["lm_head"])
    head[:, twin] = head[:, top]
    params = dict(engine.params, lm_head=jnp.asarray(head))
    logits = np.asarray(step(params))
    assert logits.shape == (b, VOCAB) and logits.dtype == np.float32
    assert logits[0, twin] == logits[0, top] == logits[0].max()
    picked = np.asarray(step(params, pick="greedy"))
    assert picked.dtype == np.int32 and picked.shape == (b,)
    assert picked.tolist() == np.argmax(logits, -1).tolist()
    assert picked[0] == min(top, twin)
    with pytest.raises(ValueError, match="pick"):
        step(params, pick="sample")
    engine.close()


# ---------------------------------------------------------------------------
# (b) a sampled sequence takes the logits route, and replays itself
# ---------------------------------------------------------------------------

def _serve_mixed(model, **kw):
    """Three greedy requests and one sampled (row 1)."""
    engine = _engine(model, **kw)
    prompts = _prompts()
    outs = _serve(engine, [(p, 6, 0.8 if i == 1 else 0.0, 40 + i)
                           for i, p in enumerate(prompts)])
    return engine, prompts, outs


def test_sampled_row_takes_the_logits_route_and_survives_preemption(
        model):
    full, prompts, want = _serve_mixed(model, num_blocks=40)
    tel = telemetry.Telemetry(enabled=True)
    lazy, _, got = _serve_mixed(model, num_blocks=7, reserve="lazy",
                                telemetry=tel)
    assert tel.counter_value("engine_preemptions") > 0
    for i, (prompt, w, g) in enumerate(zip(prompts, want, got)):
        np.testing.assert_array_equal(w, g)
        # the (seed, index)-keyed draw over the teacher-forced logits
        assert w.tolist() == _reference_tokens(
            full, prompt, w, temperature=0.8 if i == 1 else 0.0,
            seed=40 + i)
    # what the commit before ISSUE 25 answered to this (seed, prompt),
    # with every pick on the host (recorded from it on the CPU)
    assert want[1].tolist() == [63, 42, 35, 48, 38, 59]
    for engine in (full, lazy):
        assert 0 <= engine.decode_device_pick_steps < engine.decode_steps
        assert any(k[0] == "decode_logits" for k in engine._signatures)
        assert engine.jit_compiles <= engine.compile_bound
        engine.close()
    # the sampled row really sampled: greedy gives another answer
    greedy = _engine(model, num_blocks=40)
    (plain,) = _drive(greedy, [greedy.submit(prompts[1], 6)])
    assert plain.tolist() != want[1].tolist()
    greedy.close()


def test_route_is_chosen_step_by_step(model):
    """Once the sampled sequence has left, the steps that remain pick on
    the device again."""
    engine = _engine(model, num_blocks=40)
    prompts = _prompts(2)
    long = engine.submit(prompts[0], 8)
    short = engine.submit(prompts[1], 3, temperature=0.7, seed=9)
    _drive(engine, [short])
    mixed_steps = engine.decode_steps
    assert engine.decode_device_pick_steps == 0 and mixed_steps > 0
    _drive(engine, [long])
    assert engine.decode_steps > mixed_steps
    assert engine.decode_device_pick_steps == \
        engine.decode_steps - mixed_steps
    engine.close()


# ---------------------------------------------------------------------------
# (c) what leaves the program
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bb,cb", [(1, 4), (4, 16)])
def test_greedy_program_returns_ids_not_logits(model, bb, cb):
    import jax
    import jax.numpy as jnp
    engine = _engine(model, num_blocks=16)
    row = jnp.zeros(bb, jnp.int32)
    args = (engine.params, engine.cache.pools, row, row,
            jnp.zeros((bb, cb), jnp.int32), row)
    out, pools = engine._step_fn.lower(*args).out_info
    assert out.shape == (bb,) and out.dtype == np.int32
    leaves = jax.tree_util.tree_leaves(pools)
    assert len(leaves) == len(jax.tree_util.tree_leaves(
        engine.cache.pools))
    assert not any(leaf.shape[-1] == VOCAB for leaf in [out] + leaves)
    logits, _ = engine._logits_step_fn.lower(*args).out_info
    assert logits.shape == (bb, VOCAB) and logits.dtype == np.float32
    engine.close()


# ---------------------------------------------------------------------------
# (d) the counters
# ---------------------------------------------------------------------------

def test_stats_and_telemetry_carry_both_counts(model):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(model, num_blocks=40, telemetry=tel, name="eng")
    fresh = engine.stats()
    assert fresh["decode_steps"] == fresh["decode_device_pick_steps"] == 0
    prompts = _prompts(2)
    _drive(engine, [engine.submit(prompts[0], 4)])
    stats = engine.stats()
    assert stats["decode_steps"] == stats["decode_device_pick_steps"] == 3
    _drive(engine, [engine.submit(prompts[1], 4, temperature=1.0)])
    stats = engine.stats()
    assert stats["decode_steps"] == 6
    assert stats["decode_device_pick_steps"] == 3
    assert tel.counter_value("eng_decode_steps") == 6
    assert tel.counter_value("eng_decode_device_pick_steps") == 3
    assert tel.counter_value("eng_tokens") == 8
    engine.close()


def test_compile_bound_counts_the_second_decode_ladder(model):
    engine = _engine(model, num_blocks=40)
    assert engine.compile_bound == len(engine.batch_buckets) * (
        len(engine.prompt_buckets) + 2 * len(engine.ctx_buckets))
    chunked = _engine(model, num_blocks=40, prefill_chunk=8)
    assert chunked.compile_bound == engine.compile_bound + (
        len(chunked.batch_buckets) * len(chunked.chunk_buckets)
        * len(chunked.ctx_buckets))
    engine.close()
    chunked.close()


# ---------------------------------------------------------------------------
# (e) greedy traffic never compiles the logits program
# ---------------------------------------------------------------------------

def test_greedy_traffic_compiles_no_logits_program(model):
    tel = telemetry.Telemetry(enabled=True)
    engine = _engine(model, num_blocks=40, telemetry=tel)
    assert engine.jit_compiles == 0
    _serve(engine, [(p, 6) for p in _prompts()])
    keys = [e["args"]["shape_key"] for e in tel.tracer.drain()
            if e["ph"] == "X" and e["name"] == "jit_compile"]
    kinds = sorted({k.split(",")[0] for k in keys})
    assert kinds == ["('decode'", "('prefill'"], kinds
    assert engine.jit_compiles == len(keys) == len(engine._signatures)
    assert engine._logits_step_fn._cache_size() == 0
    # one compile a key; a bucket's fast path holds a second entry where
    # its tokens also came as the device array of the step before
    decodes = sum(k.startswith("('decode'") for k in keys)
    assert decodes <= engine._step_fn._cache_size() <= 2 * decodes
    # the first sampled step is what compiles it
    _drive(engine, [engine.submit(_prompts()[0], 2, temperature=0.5)])
    assert engine._logits_step_fn._cache_size() == 1
    assert [k[0] for k in engine._signatures].count("decode_logits") == 1
    engine.close()
