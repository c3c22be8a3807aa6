"""The state-space / attention hybrid on the serving path
(``models/ssm_hybrid.py``, ``ops/ssm.py``, ``ops/attention.py:grouped_*``,
the state slots of ``serving/kvcache.py``) against the plain reference
(``benchmark/reference/jamba_ssm.py``) at a tiny preset: hidden 64, 4
query heads on 1 key/value head, 16 state values, 4 layers of which
layer 1 is attention. CPU, seeded weights; the kernels run in interpret
mode here, compile for the described chip at the bottom, and run on the
chip in the benchmark's cell.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import jamba_ssm as family  # noqa: E402
from benchmark.reference import jamba_ssm as reference  # noqa: E402
from hetu_tpu.ops import ssm  # noqa: E402
from hetu_tpu.serving.kvcache import (KVCacheExhausted, PagedKVCache,  # noqa: E402
                                      blocks_for_budget, kv_block_bytes,
                                      state_slot_bytes)
from hetu_tpu.serving.scheduler import ContinuousBatchingEngine  # noqa: E402

VOCAB = 96


def tiny(dtype="float32", hidden=64):
    """A configuration file's content, as ``configs/jamba2-3b.json``
    holds it."""
    return {
        "family": "jamba_ssm", "vocab_size": VOCAB, "hidden_size": hidden,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 128,
        "attn_layer_period": 3, "attn_layer_offset": 1,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 8,
        "mamba_expand": 2, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 512, "serve_dtype": dtype,
        "assumed": {"head_dim": hidden // 4, "initializer_std": 0.2,
                    "dt_min": 1e-3, "dt_max": 1e-1}}


@pytest.fixture(scope="module")
def f32():
    config = tiny()
    return config, family.seeded_weights(config, 7)


def engine_for(config, weights, **kw):
    kw = dict(dict(num_blocks=48, block_size=4, max_len=64,
                   max_batch_size=4, start=False, telemetry=False), **kw)
    return ContinuousBatchingEngine(family.model_config(config),
                                    weights.__getitem__, **kw)


def run_all(engine, prompts, new=6):
    futures = [engine.submit(p, new) for p in prompts]
    while not all(f.done() for f in futures):
        engine.step()
    return futures


def prompts_of(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in lengths]


# -- the recurrence ----------------------------------------------------------

def recurrence_inputs(rows, t, d, n, dtype, seed=0):
    r = np.random.RandomState(seed)
    return dict(
        x=jnp.asarray(r.randn(rows, t, d), dtype),
        delta=jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                           (rows, t, d))), jnp.float32),
        a_t=-jnp.asarray(np.tile(np.arange(1.0, n + 1)[:, None], (1, d)),
                         jnp.float32),
        b=jnp.asarray(r.randn(rows, t, n), jnp.float32),
        c=jnp.asarray(r.randn(rows, t, n), jnp.float32),
        d=jnp.asarray(r.randn(d), jnp.float32),
        s0=jnp.asarray(r.randn(rows, n, d), jnp.float32))


def sequential(x, delta, a_t, b, c, d, s0, length):
    """The recurrence of one row in numpy float64, token by token."""
    s = np.asarray(s0, np.float64)
    x, delta, b, c = (np.asarray(a.astype(jnp.float32), np.float64)
                      for a in (x, delta, b, c))
    ys = []
    for t in range(length):
        s = np.exp(delta[t][None, :] * np.asarray(a_t)) * s \
            + (delta[t] * x[t])[None, :] * b[t][:, None]
        ys.append(s.T @ c[t] + np.asarray(d) * x[t])
    return np.asarray(ys), s


@pytest.mark.parametrize("form", ["composed", "kernel"])
@pytest.mark.parametrize("dtype,limit", [("float32", 2e-5),
                                         ("bfloat16", 2e-2)])
def test_scan_matches_the_sequential_recurrence(form, dtype, limit):
    """Ragged lengths in one padded batch (a full row, a part-filled
    one, an empty one) and a non-zero initial state; two chunks of
    tokens and two tiles of channels, so the kernel carries its state
    both ways. float32 agrees to the order of additions; bfloat16 ``y``
    to its 8 bits."""
    ssm.INTERPRET = form == "kernel"
    try:
        a = recurrence_inputs(3, 256, 256, 16, dtype)
        lengths = np.asarray([256, 100, 0])
        y, s = ssm.ssm_scan(a["x"], a["delta"], a["a_t"], a["b"], a["c"],
                            a["d"], a["s0"], jnp.asarray(lengths))
    finally:
        ssm.INTERPRET = False
    assert y.dtype == a["x"].dtype and s.dtype == jnp.float32
    for row, n in enumerate(lengths):
        want_y, want_s = sequential(
            a["x"][row], a["delta"][row], a["a_t"], a["b"][row],
            a["c"][row], a["d"], a["s0"][row], n)
        if n:
            got = np.asarray(y[row, :n].astype(jnp.float32))
            assert np.abs(got - want_y).max() \
                <= limit * np.abs(want_y).max()
        # the state is the one at the last REAL token, in float32
        np.testing.assert_allclose(np.asarray(s[row]), want_s,
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(s[2]), np.asarray(a["s0"][2]))


@pytest.mark.parametrize("form", ["composed", "kernel"])
def test_step_updates_the_rows_slots_and_no_other(form):
    ssm.INTERPRET = form == "kernel"
    try:
        a = recurrence_inputs(1, 4, 256, 16, "float32", seed=3)
        whole = jnp.asarray(np.random.RandomState(4).randn(6, 3, 16, 256),
                            jnp.float32)
        slots = jnp.asarray([3, 1, 0, 0], jnp.int32)   # two padded lanes
        # layer 1 of three, the index traced as a loop's is
        y, after = jax.jit(ssm.ssm_step)(
            whole, slots, jnp.int32(1), a["x"][0], a["delta"][0],
            a["a_t"], a["b"][0], a["c"][0], a["d"])
    finally:
        ssm.INTERPRET = False
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(after[:, other]),
                                      np.asarray(whole[:, other]))
    pool, new = whole[:, 1], after[:, 1]
    for lane, slot in enumerate([3, 1]):
        want_y, want_s = sequential(
            a["x"][0, lane:lane + 1], a["delta"][0, lane:lane + 1],
            a["a_t"], a["b"][0, lane:lane + 1], a["c"][0, lane:lane + 1],
            a["d"], pool[slot], 1)
        np.testing.assert_allclose(np.asarray(y[lane]), want_y[0],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(new[slot]), want_s,
                                   rtol=2e-5, atol=2e-5)
    for untouched in (2, 4, 5):
        np.testing.assert_array_equal(np.asarray(new[untouched]),
                                      np.asarray(pool[untouched]))


def test_a_traced_call_says_which_form_it_runs_in():
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry.check import check_args
    old = telemetry._default
    tel = telemetry.configure(enabled=True, service="test-ssm-plan")
    try:
        a = recurrence_inputs(1, 16, 128, 16, "float32")
        args = (a["x"], a["delta"], a["a_t"], a["b"], a["c"], a["d"],
                a["s0"], jnp.asarray([12]))
        ssm.ssm_scan(*args)
        ssm.INTERPRET = True
        ssm.ssm_scan(*args)
        b = recurrence_inputs(1, 12, 128, 16, "float32")
        ssm.ssm_scan(b["x"], b["delta"], b["a_t"], b["b"], b["c"], b["d"],
                     b["s0"], jnp.asarray([12]))
    finally:
        ssm.INTERPRET = False
        telemetry._default = old
    plans = [e["args"] for e in tel.tracer.drain(clear=True)
             if e.get("name") == "ssm_plan"]
    assert [p["form"] for p in plans] == ["composed", "kernel", "composed"]
    assert plans[0]["reason"] == "platform" and "reason" not in plans[1]
    assert "sublane tiles" in plans[2]["reason"]
    assert all(check_args("ssm_plan", p) == [] for p in plans)
    assert check_args("ssm_plan", {"form": "kernel"}) != []
    assert ssm.supported(4096, 5120, 16, "bfloat16") is None
    assert ssm.supported(12, 5120, 16, "bfloat16")
    assert ssm.supported(64, 100, 16, "bfloat16")


# -- the engine against the reference ---------------------------------------

# float32: engine and reference are the same arithmetic in another
# order (channels-minor state, fused projections), so what is left is
# the order of additions: 1e-4 of logits whose spread is about 1.
# bfloat16: activations carry 8 bits through 4 layers and the weights'
# spread here is ten times the published one (logits of spread 2 and
# more): 0.2, two to three times the readings.
@pytest.mark.parametrize("form", ["composed", "kernel"])
@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.2)])
def test_prefill_then_decode_through_slots_and_pool(dtype, limit, form):
    """Ragged prompts in padded buckets, then six decode steps through
    the state slots and the paged pool, held to the reference's full
    forward on LOGITS: the engine's best logit (its record) against the
    reference's logit at the engine's token, and the reference's best
    against that."""
    config = tiny(dtype, hidden=128 if form == "kernel" else 64)
    ssm.INTERPRET = form == "kernel"
    try:
        weights = family.seeded_weights(config, 7)
        engine = engine_for(config, weights)
        prompts = prompts_of(0, [21, 37, 16, 50, 9])
        futures = run_all(engine, prompts)
    finally:
        ssm.INTERPRET = False
    model = engine.model
    for prompt, f in zip(prompts, futures):
        readings = family.logit_readings(
            config, weights, prompt, f.result(),
            model.read_records(f.token_records))
        assert readings["gap"].max() <= limit
        assert readings["value"].max() <= limit
    stats = engine.stats()
    real = sum(len(p) for p in prompts)
    assert stats["prefill_ssm_rows"] == real * 3     # 3 Mamba layers
    assert stats["decode_ssm_rows"] == 5 * 5 * 3
    assert stats["state_slots"] == 4 and stats["state_slots_used"] == 0
    engine.cache.assert_consistent()


def test_every_mutant_and_the_control_fail_the_comparison(f32):
    """What ``correct`` runs on the chip, at the CPU's float32: the
    sound engine passes both parts; each fault of the mechanism, played
    by the reference, fails one."""
    config, weights = f32
    engine = engine_for(config, weights)
    prompt = prompts_of(1, [21])[0]
    (f,) = run_all(engine, [prompt])
    out, record = f.result(), engine.model.read_records(f.token_records)
    sound = family.logit_readings(config, weights, prompt, out, record)
    assert sound["value"].max() <= 1e-4 and sound["gap"].max() <= 1e-4
    _, u = reference.forward(weights, config,
                             np.concatenate([prompt, out[:-1]]), [20],
                             want_layer=0)
    reading, program = family.mixer_readings(config, weights, 0,
                                             np.asarray(u), 21)
    assert reading["mixer_error"].max() <= 1e-5
    for fault in reference.MUTANTS:
        by_logits = family.logit_readings(config, weights, prompt, out,
                                          record, fault)
        assert by_logits["value"].max() > 1e-3, fault
        by_mixer, _ = family.mixer_readings(config, weights, 0,
                                            np.asarray(u), 21, fault,
                                            program)
        assert by_mixer["mixer_error"].max() > 0.1, fault
    low, _ = family.mixer_readings(config, weights, 0, np.asarray(u), 21,
                                   "state_bf16", program)
    assert family.MIXER_TOLERANCE < low["mixer_error"].max() < 0.05


@pytest.mark.parametrize("chunk", [8, 5])
def test_chunked_suffix_prefill_equals_one_prefill(f32, chunk):
    """A prompt prefilled a chunk a step continues the scan from its
    slot's state and tail: the same tokens and the same best logits as
    one prefill."""
    config, weights = f32
    prompts = prompts_of(2, [23, 9, 40])
    whole = run_all(engine_for(config, weights), prompts)
    chunked = run_all(engine_for(config, weights, prefill_chunk=chunk),
                      prompts)
    for a, b in zip(whole, chunked):
        np.testing.assert_array_equal(a.result(), b.result())
        np.testing.assert_allclose(
            a.token_records.view(np.float32),
            b.token_records.view(np.float32), atol=1e-4)


def test_a_preempted_request_replays_to_the_same_tokens(f32):
    """``reserve="lazy"`` in a pool too small for all: the youngest
    sequence loses blocks AND slot, and its replay rebuilds the state
    from its tokens."""
    config, weights = f32
    prompts = prompts_of(3, [14, 15, 13, 12])
    calm = run_all(engine_for(config, weights), prompts, new=10)
    tight = engine_for(config, weights, num_blocks=18, reserve="lazy")
    pressed = run_all(tight, prompts, new=10)
    assert sum(f.account["replay"] > 0 for f in pressed) >= 1
    for a, b in zip(calm, pressed):
        np.testing.assert_array_equal(a.result(), b.result())
    tight.cache.assert_consistent()
    assert tight.cache.state_slots_used == 0


def test_a_reused_slot_gives_what_a_fresh_engine_gives(f32):
    """One slot, three requests one after another: each finds the slot
    as the last left it, and answers as a fresh engine does (the
    prefill writes the slot and never reads it)."""
    config, weights = f32
    prompts = prompts_of(4, [30, 11, 19])
    one = engine_for(config, weights, max_batch_size=1)
    reused = [run_all(one, [p])[0] for p in prompts]
    ssm_pool = one.cache.pools[0]["ssm"]
    assert ssm_pool.shape[:2] == (2, 3) and np.asarray(ssm_pool[1]).all(
        axis=(1, 2)).all()
    for p, f in zip(prompts, reused):
        (fresh,) = run_all(engine_for(config, weights, max_batch_size=1),
                           [p])
        np.testing.assert_array_equal(f.result(), fresh.result())
        np.testing.assert_array_equal(f.token_records,
                                      fresh.token_records)


def test_continuous_batching_equals_one_request_at_a_time(f32):
    config, weights = f32
    prompts = prompts_of(5, [12, 33, 7, 20, 25, 5])
    together = run_all(engine_for(config, weights), prompts)
    one = engine_for(config, weights)   # its programs compile once
    for p, f in zip(prompts, together):
        (alone,) = run_all(one, [p])
        np.testing.assert_array_equal(f.result(), alone.result())


def test_warm_up_leaves_nothing_to_compile(f32):
    config, weights = f32
    engine = engine_for(config, weights)
    ran = engine.warm_up((3, 20), 6)
    warmed = engine.jit_compiles
    assert ran["prefill"] and ran["decode"] and ran["decode_ids"]
    run_all(engine, prompts_of(6, [3, 20, 11, 7, 16, 4, 9]))
    assert engine.jit_compiles == warmed <= engine.compile_bound


# -- the cache manager: two kinds of per-request memory ---------------------

def test_pools_by_layer_kind_and_their_bytes():
    config = tiny("bfloat16")
    cfg = family.model_config(config)
    cache = PagedKVCache(cfg, num_blocks=10, block_size=4, state_slots=3)
    assert cfg.serving_model().pool_kinds == ("state", "rows")
    # ONE entry of slots for the three Mamba layers, then layer 1's rows
    assert [sorted(entry) for entry in cache.pools] == [
        ["conv", "ssm"], ["k", "v"]]
    # one k and one v row of the ONE key/value head: no copy a query head
    assert cache.pools[1]["k"].shape == (11, 4, 16)
    assert cache.pools[1]["k"].dtype == jnp.bfloat16
    assert cache.pools[0]["ssm"].shape == (4, 3, 16, 128)
    assert cache.pools[0]["ssm"].dtype == jnp.float32
    assert cache.pools[0]["conv"].shape == (4, 3 * 3, 128)
    assert kv_block_bytes(cfg, 4) == 1 * 4 * 2 * 16 * 2
    slot = 3 * (16 * 128 * 4 + 3 * 128 * 2)
    assert state_slot_bytes(cfg) == slot
    assert cache.kv_bytes() == 11 * 4 * 2 * 16 * 2
    assert cache.state_bytes() == 4 * slot
    assert cache.hbm_bytes() == cache.kv_bytes() + cache.state_bytes()
    budget = cfg.serving_model().param_bytes() + 10 ** 6
    assert blocks_for_budget(cfg, 4, budget, headroom=0.0, state_slots=3) \
        == (10 ** 6 - 4 * slot) // kv_block_bytes(cfg, 4)


def test_slots_are_taken_with_blocks_and_given_back_with_them():
    cfg = family.model_config(tiny())
    cache = PagedKVCache(cfg, num_blocks=10, block_size=4, state_slots=2)
    cache.add_seq("a", 5)
    cache.add_seq("b", 5)
    assert (cache.slot_of_seq("a"), cache.slot_of_seq("b")) == (1, 2)
    assert not cache.can_admit(4)               # blocks, but no slot
    with pytest.raises(KVCacheExhausted, match="state slots"):
        cache.add_seq("c", 4)
    assert "c" not in cache.tables and cache.allocator.used == 4
    cache.free_seq("a")
    assert cache.can_admit(4) and cache.state_slots_used == 1
    cache.add_seq("c", 4)
    assert cache.slot_of_seq("c") == 1          # lowest first, as blocks
    cache.assert_consistent()
    with pytest.raises(ValueError, match="prefix_cache"):
        PagedKVCache(cfg, num_blocks=10, block_size=4, state_slots=2,
                     prefix_cache=True)


@pytest.mark.parametrize("model", ["gpt", "latent"])
def test_models_of_rows_alone_are_sized_as_they_were(model):
    """GPT's and the latent model's layouts and sizes do not know of
    state: every layer has rows, no slot is made or counted."""
    if model == "gpt":
        from hetu_tpu.models import GPTConfig
        cfg = GPTConfig(vocab_size=64, hidden_size=32,
                        num_hidden_layers=3, num_attention_heads=4,
                        max_position_embeddings=64)
        row = 2 * 32 * 4
    else:
        from test_latent_moe_serving import family as latent, tiny as lt
        cfg = latent.model_config(lt("bfloat16"))
        row = 128 * 2
    cache = PagedKVCache(cfg, num_blocks=10, block_size=4, state_slots=8)
    assert cache.state_slots == 0 and cache.state_bytes() == 0
    assert state_slot_bytes(cfg) == 0
    assert kv_block_bytes(cfg, 4) == 3 * 4 * row
    assert cache.hbm_bytes() == cache.kv_bytes() == 11 * 4 * 3 * row
    budget = cfg.serving_model().param_bytes() + 10 ** 6
    assert blocks_for_budget(cfg, 4, budget, headroom=0.0,
                             state_slots=8) == 10 ** 6 // (3 * 4 * row)
    cache.add_seq(0, 5)
    assert cache.slot_of_seq(0) == 0 and cache.can_admit(5)
    cache.assert_consistent()


def test_the_engine_refuses_a_prefix_cache_over_state(f32):
    config, weights = f32
    with pytest.raises(ValueError, match="state snapshots"):
        engine_for(config, weights, prefix_cache=True)
    engine = engine_for(config, weights)
    stats = engine.stats()
    assert stats["state_hbm_bytes"] == engine.cache.state_bytes() > 0
    assert stats["kv_hbm_bytes"] == engine.cache.kv_bytes()
    import inspect
    from hetu_tpu.serving import kvcache, scheduler
    for module in (kvcache, scheduler):
        source = inspect.getsource(module)
        assert "ssm_hybrid" not in source and "jamba" not in source


def test_the_reference_owes_the_program_nothing():
    import inspect
    source = inspect.getsource(reference)
    assert "hetu_tpu" not in source.split('"""', 2)[2]


# -- the kernels' tiles, compiled for the described chip --------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


def _custom_calls(compiled):
    """The names of the program's Mosaic kernels."""
    import re
    return [re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)", line).group(1)
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("rows,tokens,dtype", [
    (1, 4096, "bfloat16"), (8, 64, "bfloat16"), (1, 1024, "float32")])
def test_the_scan_kernel_compiles_under_its_name(one_chip, rows, tokens,
                                                 dtype):
    """At the published widths (5,120 channels, 16 state values): the
    longest prompt bucket, the widest batch of the shortest, and the
    float32 rows the cell's mixer check runs."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    n, d = 16, 5120
    compiled = ssm._jitted_scan(False).lower(
        s((rows, tokens, d), dtype), s((rows, tokens, d), "float32"),
        s((rows, tokens, n), "float32"), s((rows, tokens, n), "float32"),
        s((n, d), "float32"), s((d,), "float32"),
        s((rows, n, d), "float32")).compile()
    assert _custom_calls(compiled) == [ssm.SCAN_NAME]


@pytest.mark.parametrize("rows", [1, 64])
def test_the_step_kernel_compiles_in_place_under_its_name(one_chip, rows):
    """The pool is aliased to the result: no copy of it is made."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    n, d = 16, 5120

    def step(pool, *args):
        return ssm._jitted_step(False)(pool, *args)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        s((65, 26, n, d), "float32"), s((rows,), "int32"), s((), "int32"),
        s((rows, d), "bfloat16"), s((rows, d), "float32"),
        s((rows, n), "float32"), s((rows, n), "float32"),
        s((n, d), "float32"), s((d,), "float32")).compile()
    assert _custom_calls(compiled) == [ssm.STEP_NAME]
    assert "f32[65,26,16,5120]" in compiled.as_text()
    assert not [line for line in compiled.as_text().splitlines()
                if "f32[65,26,16,5120]" in line.split("=")[0]
                and " copy(" in line]
