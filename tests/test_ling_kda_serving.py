"""Delta-rule (KDA) layers beside a latent-attention layer on the serving
path (``models/latent_moe.py`` with ``layer_types``, ``ops/kda.py``, the
group-limited ``ops/moe.py:route``) against the plain reference
(``benchmark/reference/ling_kda.py``), at a tiny preset: hidden 64, 2
heads of 16, latent 32, 16 routed experts in 4 groups (the best 2 kept),
2 a token, 1 shared, layers ``kda, kda, mla`` with the first dense. CPU,
seeded weights; the kernels' own tiles are compiled for the described
chip at the bottom.
"""
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import ling_kda as family  # noqa: E402
from benchmark.reference import ling_kda as reference  # noqa: E402
from hetu_tpu.models import decoder_parts  # noqa: E402
from hetu_tpu.models import latent_moe as lm  # noqa: E402
from hetu_tpu.ops import kda, moe  # noqa: E402
from hetu_tpu.serving.kvcache import (PagedKVCache, kv_block_bytes,  # noqa: E402
                                      state_slot_bytes)
from hetu_tpu.serving.scheduler import ContinuousBatchingEngine  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "ling-3.0-flash-vl-ep4.json")


def tiny(dtype="float32", held=(0, 16), layer_types=("kda", "kda", "mla")):
    """A configuration file's content, as ``configs/*.json`` holds it."""
    return {
        "family": "ling_kda", "vocab_size": 96, "hidden_size": 64,
        "num_hidden_layers": len(layer_types), "num_attention_heads": 2,
        "head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
        "use_qk_norm": True, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": held[1],
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "n_group": 4, "topk_group": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "max_position_embeddings": 4096,
        "layer_types": list(layer_types), "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "serve_dtype": dtype,
        "deployment": {"num_routed_experts": 16, "experts_first": held[0]},
        "assumed": {"initializer_std": 0.2, "router_bias_std": 0.05,
                    "attn_output_gate": True}}


def engine_for(config, weights, **kw):
    kw = dict(dict(num_blocks=48, block_size=4, max_len=64,
                   max_batch_size=4, start=False, telemetry=False), **kw)
    return ContinuousBatchingEngine(family.model_config(config),
                                    weights.__getitem__, **kw)


def run_all(engine, prompts, new=6):
    futures = [engine.submit(p, new) for p in prompts]
    while not all(f.done() for f in futures):
        engine.step()
    return [f.result(timeout=0) for f in futures], futures


def prompts_of(rng, lengths, vocab=96):
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lengths]


# -- the router ------------------------------------------------------------

def test_group_limited_route_is_the_numpy_spelling():
    rng = np.random.RandomState(0)
    x = rng.normal(size=(64, 32)).astype(np.float32)
    w = rng.normal(size=(32, 64)).astype(np.float32)
    bias = (0.05 * rng.normal(size=64)).astype(np.float32)
    experts, weights, scores = moe.route(x, w, bias, 4, 2.5, n_group=8,
                                         topk_group=3)
    scores = np.asarray(scores)
    picks, kept = family.limited_top_k(scores + bias, 4, 8, 3)
    assert (np.sort(np.asarray(experts), 1) == np.sort(picks, 1)).all()
    # every pick lies in a kept group, and the weights leave the bias out
    assert all(set(e // 8) <= set(k) for e, k in zip(picks, kept))
    got = np.take_along_axis(scores, np.asarray(experts), 1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * got / got.sum(1, keepdims=True),
        rtol=1e-6)
    # the limit binds: without it some row picks outside its groups
    free, _, _ = moe.route(x, w, bias, 4, 2.5)
    assert (np.sort(np.asarray(free), 1) != np.sort(picks, 1)).any()
    assert (family.pick_readings(scores + bias, np.asarray(experts),
                                 {"n_group": 8, "topk_group": 3}) == 0).all()
    assert (family.pick_readings(scores + bias, np.asarray(free),
                                 {"n_group": 8, "topk_group": 3}) > 0).any()


def test_one_group_is_todays_router_bit_for_bit():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.normal(size=24), jnp.float32)

    def before(x, w_router, bias, top_k, scale, pick=None):
        # the text before the groups; ``pick=None``: PR 54's parent's,
        # which gathered the chosen scores (the values to this day)
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = pick(scores, experts) if pick \
            else jnp.take_along_axis(scores, experts, axis=-1)
        weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
        return experts.astype(jnp.int32), weights, scores

    for got, want in zip(moe.route(x, w, bias, 3, 2.0),
                         before(x, w, bias, 3, 2.0)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    text = [jax.jit(fn, static_argnums=(3, 4)).lower(
        x, w, bias, 3, 2.0).as_text().split("\n", 1)[1]
        for fn in (lambda *a: moe.route(*a),
                   lambda *a: before(*a, pick=moe._picked))]
    assert text[0] == text[1]


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,limit", [("float32", 2e-4),
                                         ("bfloat16", 0.3)])
def test_prefill_then_decode_through_slots_and_latent_rows(dtype, limit):
    """LOGITS of a padded prefill (the state left at the last real
    token) and of every decode step against the reference's full
    forward, the reference forced onto the program's picks."""
    config = tiny(dtype)
    weights = family.seeded_weights(config, 11)
    cfg = family.model_config(config)
    model = cfg.serving_model()
    params = model.params(weights.__getitem__)
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4, state_slots=2)
    assert model.pool_kinds == ("state", "rows")
    assert cache.pools[0]["kda"].shape == (3, 2, 2, 16, 16)
    assert cache.pools[0]["conv"].shape == (3, 6, 96)
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, 96, 21).astype(np.int32)
    p, bucket = 13, 16
    cache.add_seq(0, len(tokens))
    slot = cache.slot_of_seq(0)
    ids = np.zeros((2, bucket), np.int32)
    ids[0, :p] = tokens[:p]
    slots = np.zeros((2, bucket), np.int32)
    slots[0, :p] = cache.slot_mapping(0, 0, p)
    (logits, counted), pools = jax.jit(
        lm.latent_moe_paged_prefill, static_argnames="config")(
        params, cache.pools, jnp.asarray(ids), jnp.asarray(slots),
        jnp.asarray([p - 1, 0]), jnp.asarray([slot, 0], jnp.int32),
        config=cfg)
    width = len(model.counter_names) + model.vector_counter[1]
    names = dict(zip(model.counter_names, np.asarray(counted)))
    assert names["kda_rows"] == p * 2 and names["moe_tokens"] == p * 2
    assert names["mla_context_rows"] == p       # one latent layer
    assert names["kda_chunks"] == 2 * 2 * -(-bucket // kda.CHUNK)
    assert 0 < names["moe_group_kept"] <= p * 2 * 4
    record = model.read_records(
        np.asarray(counted)[width:].reshape(2, -1)[:1])
    got, picks = [np.asarray(logits[0])], [record["router_picks"][0]]
    step = jax.jit(lm.latent_moe_paged_step, static_argnames="config")
    for pos in range(p, len(tokens)):
        grid = cache.gather_slots([0], 24)
        (logits, counted), pools = step(
            params, pools, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos]), jnp.asarray(grid),
            jnp.asarray([cache.slot_of(0, pos)]),
            jnp.asarray([slot], jnp.int32), config=cfg)
        got.append(np.asarray(logits[0]))
        picks.append(model.read_records(
            np.asarray(counted)[width:].reshape(1, -1))["router_picks"][0])
    rows = np.arange(p - 1, len(tokens))
    want, layers = reference.forward(weights, config, tokens, rows,
                                     forced=np.stack(picks))
    assert np.abs(np.stack(got) - want).max() <= limit
    if dtype == "float32":
        for i, layer in enumerate(layers):
            assert (family.pick_readings(
                layer["scores"], np.stack(picks)[:, i], config) < 1e-5).all()


def test_chunked_prefill_continues_state_and_tails():
    """The suffix program from a slot: a prompt in chunks of 5 gives the
    logits of the whole-prompt prefill."""
    config = tiny()
    weights = family.seeded_weights(config, 5)
    prompt = prompts_of(np.random.RandomState(8), [14])
    whole, _ = run_all(engine_for(config, weights), prompt)
    chunked, _ = run_all(engine_for(config, weights, prefill_chunk=5),
                         prompt)
    np.testing.assert_array_equal(whole[0], chunked[0])


def test_engine_tokens_and_a_preempted_request_replayed():
    """Four requests through the engine are the reference's greedy
    tokens, one by one; with a pool too small for all of them a request
    is preempted, replayed (state and latent rows rebuilt from its
    tokens) and still gives the same tokens."""
    config = tiny()
    weights = family.seeded_weights(config, 7)
    prompts = prompts_of(np.random.RandomState(5), [9, 17, 5, 12])
    roomy = engine_for(config, weights)
    outs, futures = run_all(roomy, prompts, new=8)
    for prompt, out, f in zip(prompts, outs, futures):
        record = roomy.model.read_records(f.token_records)
        tokens = np.concatenate([prompt, out[:-1]])
        rows = np.arange(len(prompt) - 1, len(tokens))
        logits, _ = reference.forward(weights, config, tokens, rows,
                                      forced=record["router_picks"])
        chosen = logits[np.arange(len(out)), out]
        assert (logits.max(-1) - chosen).max() < 1e-3
        assert np.abs(record["best_logit"] - chosen).max() < 1e-3
    stats = roomy.stats()
    assert stats["state_slots"] == 4 and stats["state_slots_used"] == 0
    roomy.close()
    tight = engine_for(config, weights, num_blocks=12, reserve="lazy")
    again, pressed = run_all(tight, prompts, new=8)
    assert sum(f.account["replay"] > 0 for f in pressed) >= 1
    for a, b in zip(outs, again):
        np.testing.assert_array_equal(a, b)
    tight.cache.assert_consistent()
    assert tight.cache.state_slots_used == 0
    tight.close()


def test_a_reused_slot_gives_what_a_fresh_engine_gives():
    """One slot, three requests one after another: each finds the slot
    as the last left it, and answers as a fresh engine does (the prefill
    writes the slot from a zero state and never reads it)."""
    config = tiny()
    weights = family.seeded_weights(config, 7)
    prompts = prompts_of(np.random.RandomState(4), [30, 11, 19])
    one = engine_for(config, weights, max_batch_size=1)
    reused = [run_all(one, [p])[0][0] for p in prompts]
    assert np.asarray(one.cache.pools[0]["kda"][1]).any()
    for p, got in zip(prompts, reused):
        fresh, _ = run_all(engine_for(config, weights, max_batch_size=1),
                           [p])
        np.testing.assert_array_equal(got, fresh[0])


def test_prefix_cache_is_refused_over_state():
    config = tiny()
    with pytest.raises(ValueError, match="recurrent state"):
        engine_for(config, family.seeded_weights(config, 1),
                   prefix_cache=True)


# -- one chip's share -------------------------------------------------------

def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The program's feed-forward of each EP4 share (its four experts,
    the shared expert whole) on the same rows: the routed parts add up,
    with the shared expert counted once, to the reference's layer with
    every expert held."""
    config = tiny()
    weights = family.seeded_weights(config, 3)
    w = reference.layer_weights(weights, 1)
    x = jnp.asarray(np.random.RandomState(2).normal(size=(24, 64)),
                    jnp.float32)
    want = reference.uncut_expert_layer(x, w, config)
    shared = np.asarray(moe.swiglu(x, w["shared_gate_up"],
                                   w["shared_down"]))
    total = np.zeros_like(want)
    for first in range(0, 16, 4):
        cfg = family.model_config(tiny(held=(first, 4)))
        blk = dict(w, experts_gate_up=w["experts_gate_up"][first:first + 4],
                   experts_down=w["experts_down"][first:first + 4])
        y, _, (rows, _) = decoder_parts.feed_forward(
            cfg, blk, x, jnp.ones(24, bool))
        total += np.asarray(y) - shared
        assert int(rows.sum()) > 0
    np.testing.assert_allclose(total + shared, want, atol=2e-5)


# -- sizes ------------------------------------------------------------------

def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_param_bytes_is_the_hand_count():
    """ISSUE 54's arithmetic, in parameters: bfloat16 matrices 2 bytes,
    the float32 vectors (norms, router, bias, taps, decay) 4."""
    config = published()
    model = family.model_config(config).serving_model()
    h, d, heads = 2560, 4096, 32
    kda_matrices = h * 3 * d + h * d + d * h + 2 * h * heads
    kda_vectors = 4 * 3 * d + d + heads + 128 + 2 * h
    mla_matrices = h * heads * 192 + h * 576 + 512 * heads * 256 + d * h \
        + h * heads
    mla_vectors = 192 + 512 + 2 * h
    experts = (128 + 1) * 3 * h * 768
    router = h * 512 + 512
    dense = 3 * h * 6144
    embed_head = 2 * 39296 * h
    assert round(kda_matrices + 4 * 3 * d, -4) == 52_640_000
    matrices = 6 * kda_matrices + mla_matrices + 6 * experts + dense \
        + embed_head
    vectors = 6 * kda_vectors + mla_vectors + 6 * router + h
    assert model.param_bytes() == 2 * matrices + 4 * vectors
    assert abs(matrices + vectors - 5_169e6) < 10e6
    assert abs(model.param_bytes() - 10.34e9) < 0.03e9
    cfg = family.model_config(config)
    assert state_slot_bytes(cfg) == 6 * 32 * 128 * 128 * 4 \
        + 6 * 3 * 3 * d * 2 == 13_025_280
    assert kv_block_bytes(cfg, 16) == 16 * 640 * 2
    assert model.state_layout()[0] == ("kda", (6, 32, 128, 128), "float32")


def test_configuration_file_holds_the_catalog_rows_keys():
    config = published()
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash-VL")
        assert config["published"] == row["config"]
        assert config["source"] == row["source_url"]
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "num_experts", "vocab_size", "layer_types"}
    for key, value in config["published"].items():
        if key in reduced:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == ["kda"] * 6 + ["mla"]
    assert config["vocab_size"] % 128 == 0
    assert config["vocab_size"] <= min(
        config["published"][k] for k in (
            "image_patch_token", "video_patch_token", "image_start_token",
            "video_start_token"))
    for group in ("deployment", "assumed", "not_served", "sizing"):
        assert group in config
    # the pattern the held layers are cut from
    size = config["layer_group_size"]
    pattern = ["mla" if (i + 1) % size == 0 else "kda"
               for i in range(config["published"]["num_hidden_layers"])]
    assert pattern.count("mla") == 7
    assert [pattern[1]] + pattern[6:12] == config["layer_types"]


def test_the_reference_owes_the_program_nothing():
    import inspect
    source = inspect.getsource(reference)
    assert "hetu_tpu" not in source.split('"""', 2)[2]
    assert "lax.scan(token" in source      # a scan a token, no chunks


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


@pytest.mark.parametrize("rows,tokens", [(1, 16384), (32, 512)])
def test_the_chunk_kernel_compiles_under_its_name(one_chip, rows, tokens):
    """At the published widths (32 heads of 128): the longest prompt
    bucket and the widest batch of the shortest."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    a = s((rows, tokens, 32 * 128), "float32")
    compiled = kda._jitted_chunk(
        kda.CHUNK, kda._sub_block(kda.CHUNK), False).lower(
        a, a, a, a, a, s((rows, 32, 128, 128), "float32")).compile()
    assert _custom_calls(compiled) == [kda.CHUNK_NAME]


@pytest.mark.parametrize("rows", [1, 32])
def test_the_step_kernel_compiles_in_place_under_its_name(one_chip, rows):
    """The pool is aliased to the result: no copy of it is made."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    r = s((rows, 32, 128), "float32")

    def step(pool, *args):
        return kda._jitted_step(False)(pool, *args)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        s((33, 6, 32, 128, 128), "float32"), s((rows,), "int32"),
        s((), "int32"), r, r, r, r, r).compile()
    assert _custom_calls(compiled) == [kda.STEP_NAME]
    assert "f32[33,6,32,128,128]" in compiled.as_text()
    assert not [line for line in compiled.as_text().splitlines()
                if "f32[33,6,32,128,128]" in line.split("=")[0]
                and " copy(" in line]
