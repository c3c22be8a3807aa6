"""The latent-attention mixture-of-experts block on the serving path
(``models/latent_moe.py``, ``ops/moe.py``, ``ops/attention.py:mla_*``)
against the plain reference (``benchmark/reference/sarvam_mla.py``), at
a tiny preset: hidden 64, 4 heads, latent 32, nope 16 / rope 8 / value
16, 8 routed experts, 2 a token, 1 shared, 1 dense + 2 expert layers.
CPU, seeded weights; the kernels' own tiles are tried by the compile
tests at the bottom and on the chip by ``chip_smoke.py``.

The block's two query forms and two residual forms
(``LatentMoEConfig.q_lora_rank`` / ``qk_norm`` /
``hyper_connections``) are held to their references side by side:
``SHAPES`` is {sarvam-shaped, query LoRA + four residual streams}, the
second against ``benchmark/reference/xing_mhc.py``.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import sarvam_mla as family  # noqa: E402
from benchmark.families import xing_mhc as xing_family  # noqa: E402
from benchmark.reference import sarvam_mla as reference  # noqa: E402
from hetu_tpu.models import latent_moe as lm  # noqa: E402
from hetu_tpu.ops import moe  # noqa: E402
from hetu_tpu.serving.kvcache import PagedKVCache, kv_block_bytes  # noqa: E402
from hetu_tpu.serving.scheduler import ContinuousBatchingEngine  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "deepseek_yarn"}


def tiny(dtype="float32", held=(0, 8), layers=3):
    """A configuration file's content, as ``configs/*.json`` holds it."""
    return {
        "family": "sarvam_mla", "vocab_size": 96, "hidden_size": 64,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": held[1],
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
        "max_position_embeddings": 4096, "serve_dtype": dtype,
        "deployment": {"num_routed_experts": 8, "experts_first": held[0]},
        "assumed": {"initializer_std": 0.2, "router_bias_std": 0.05}}


def tiny_xing(dtype="float32", layers=4, hidden=64):
    """The other shape of the block: a query LoRA with no norm a head,
    four residual streams, 2 dense + 2 expert layers, every expert
    held (``configs/xing4.0-29b-a4b-stage.json``'s keys)."""
    return {
        "family": "xing_mhc", "vocab_size": 96, "hidden_size": hidden,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "kv_lora_rank": 32, "q_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts": 8, "num_shared_experts": 1,
        "num_experts_per_tok": 2, "first_k_dense_replace": 2,
        "routed_scaling_factor": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "rope_scaling": dict(YARN, factor=64,
                                                  type="yarn"),
        "max_position_embeddings": 4096, "serve_dtype": dtype,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "deployment": {"num_routed_experts": 8, "experts_first": 0,
                       "experts_held": 8},
        "assumed": {"initializer_std": 0.2, "router_bias_std": 0.05,
                    "hc_phi_std": 0.05, "hc_scale_init": 0.5,
                    "hc_bias_std": 0.5, "hc_res_diag": 2.0}}


# shape -> (a configuration file's content, its family)
SHAPES = {"sarvam": (tiny, family), "lora_4_streams": (tiny_xing,
                                                       xing_family)}


@pytest.fixture(scope="module")
def f32():
    config = tiny()
    return config, family.seeded_weights(config, 7)


def engine_for(config, weights, **kw):
    kw = dict(dict(num_blocks=48, block_size=4, max_len=64,
                   max_batch_size=4, start=False, telemetry=False), **kw)
    fam = xing_family if config["family"] == "xing_mhc" else family
    return ContinuousBatchingEngine(fam.model_config(config),
                                    weights.__getitem__, **kw)


def run_all(engine, prompts, new=6):
    futures = [engine.submit(p, new) for p in prompts]
    while not all(f.done() for f in futures):
        engine.step()
    return [f.result(timeout=0) for f in futures]


def prompts_of(rng, lengths, vocab=96):
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lengths]


# -- the block against the reference ---------------------------------------

# float32: the two forwards are one function, and what is left is the
# order of additions. bfloat16: activations carry 8 bits; the logits'
# spread at this preset is about 2, and a closer router call than the
# rounding swaps an expert, so the stated limit is for rows where the
# reference's calls are clear.
@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.15)])
def test_prefill_then_decode_through_the_latent_cache(dtype, limit):
    config = tiny(dtype)
    weights = family.seeded_weights(config, 11)
    cfg = family.model_config(config)
    params = lm.latent_moe_serving_params(cfg, weights.__getitem__)
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, 96, 21).astype(np.int32)
    p = 13
    cache.add_seq(0, len(tokens))
    slots = cache.slot_mapping(0, 0, p)[None]
    (logits, counted), pools = jax.jit(
        lm.latent_moe_paged_prefill, static_argnames="config")(
        params, cache.pools, jnp.asarray(tokens[None, :p]),
        jnp.asarray(slots), jnp.asarray([p - 1]), config=cfg)
    got = [np.asarray(logits[0])]
    step = jax.jit(lm.latent_moe_paged_step, static_argnames="config")
    for pos in range(p, len(tokens)):
        grid = cache.gather_slots([0], 24)
        (logits, _), pools = step(
            params, pools, jnp.asarray(tokens[pos:pos + 1]),
            jnp.asarray([pos]), jnp.asarray(grid),
            jnp.asarray([cache.slot_of(0, pos)]), config=cfg)
        got.append(np.asarray(logits[0]))
    rows = np.arange(p - 1, len(tokens))
    want, layers = reference.forward(weights, config, tokens, rows)
    clear = np.min([layer["margin"] for layer in layers], axis=0) > 0.03
    # per row: RMS of the difference over the vocabulary, as a share of
    # the logits' spread (benchmark/harness/stats.py:row_errors)
    err = np.sqrt(np.mean(np.square(np.asarray(got) - want), axis=-1)) \
        / want.std()
    assert clear.sum() >= 4
    assert err[clear].max() < limit, (err, want.std())
    # what the prefill counted: 13 real tokens, 2 expert layers, every
    # pick on a held expert; 3 layers of 13 context rows
    counted = np.asarray(counted)
    assert counted[:2].tolist() == [26, 52]
    assert counted[3] == 39 and counted[4] == 3 * 13 * 14 // 2
    assert counted[5:13].sum() == 52
    # behind them the one row's record: the picks its two expert layers
    # made for the last prompt token, then the bits of its best logit
    record = cfg.serving_model().read_records(counted[None, 13:])
    assert record["best_logit"][0] == got[0].max()
    if clear[0]:
        for i, layer in enumerate(layers):
            assert set(record["router_picks"][0, i]) == \
                set(layer["experts"][0])


def _reference_logits(fam, weights, config, tokens, rows):
    """``(logits [len(rows), V], clear [len(rows)])`` of the family's
    plain reference: ``clear`` where every router call of the row is
    further from a tie than rounding moves it."""
    out = fam.reference.forward(weights, config, tokens, rows)
    clear = np.min([layer["margin"] for layer in out[1]], axis=0) > 1e-4
    return out[0], clear


@pytest.mark.parametrize("path", ["paged_decode", "suffix_prefill",
                                  "preempted_and_replayed"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_both_shapes_agree_with_their_reference_on_logits(shape, path):
    """Prefill then decode through the paged cache, a suffix prefill
    behind it, and a request preempted and replayed by the engine: each
    against the reference's FULL forward, on logits, in float32 (the
    two are then one function up to the order of additions). The
    residual streams live inside a program: nothing of them is cached,
    and the cache, the scheduler and the replay are the same code for
    both shapes."""
    make, fam = SHAPES[shape]
    config = make()
    weights = fam.seeded_weights(config, 11)
    cfg = fam.model_config(config)
    rng = np.random.RandomState(3)
    if path == "preempted_and_replayed":
        from hetu_tpu.telemetry import Telemetry
        tel = Telemetry(enabled=True)
        engine = engine_for(config, weights, num_blocks=7, reserve="lazy",
                            telemetry=tel)
        prompts = prompts_of(rng, [6, 9, 4, 7])
        futures = [engine.submit(p, 8) for p in prompts]
        while not all(f.done() for f in futures):
            engine.step()
        assert tel.counter_value("engine_preemptions") > 0, \
            "the 7-block pool never preempted: the case lost its point"
        replayed = [f for f in futures if f.account["replay"] > 0]
        assert replayed
        model = engine.model
        for prompt, f in zip(prompts, futures):
            out = f.result(timeout=0)
            readings = fam.forced_readings(
                config, weights, prompt, out,
                model.read_records(f.token_records))[0]
            assert readings["gap"].max() == 0.0
            assert readings["value"].max() < 1e-4
            assert readings["pick_distance"].max() < 1e-5
        assert engine.cache.referenced_blocks == 0
        if shape == "lora_4_streams":
            # replayed tokens went through the streams a second time
            s = engine.stats()
            assert s["prefill_mhc_rows"] > 8 * sum(len(p) for p in prompts)
            # the counter rides in stats, in every program's own row
            # and, telemetry being on, in the ring's counters
            log = list(engine.program_log)
            assert sum(r["decode_mhc_rows"] for r in log
                       if r["kind"] == "decode") == s["decode_mhc_rows"] \
                == tel.counter_value("engine_decode_mhc_rows") > 0
            assert sum(r["prefill_mhc_rows"] for r in log
                       if r["kind"] == "prefill") == s["prefill_mhc_rows"]
            # in float32 even the exit's near-hidden fault is told (the
            # first stream alone for the sum of all: the final norm
            # hides most of it, reference/xing_mhc.py BEHIND_THE_NORM),
            # while the mean for the sum is gone entirely
            record = model.read_records(futures[0].token_records)
            out = futures[0].result(timeout=0)
            one = fam.forced_readings(config, weights, prompts[0], out,
                                      record, "exit_first_stream")[0]
            assert one["value"].max() > 1e-2
            mean = fam.forced_readings(config, weights, prompts[0], out,
                                       record, "exit_mean")[0]
            assert mean["value"].max() < 1e-4
        return
    params = lm.latent_moe_serving_params(cfg, weights.__getitem__)
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
    tokens = rng.randint(0, 96, 21).astype(np.int32)
    p = 13
    cache.add_seq(0, len(tokens))
    (logits, counted), pools = jax.jit(
        lm.latent_moe_paged_prefill, static_argnames="config")(
        params, cache.pools, jnp.asarray(tokens[None, :p]),
        jnp.asarray(cache.slot_mapping(0, 0, p)[None]),
        jnp.asarray([p - 1]), config=cfg)
    grid = jnp.asarray(cache.gather_slots([0], 24))
    if path == "suffix_prefill":
        (chunk, _), pools = lm.latent_moe_paged_suffix_prefill(
            params, pools, jnp.asarray(tokens[None, p:p + 5]),
            jnp.asarray([p]), grid,
            jnp.asarray(cache.slot_mapping(0, p, p + 5)[None]), config=cfg)
        got, rows = np.asarray(chunk)[0], np.arange(p, p + 5)
    else:
        got = [np.asarray(logits[0])]
        step = jax.jit(lm.latent_moe_paged_step, static_argnames="config")
        for pos in range(p, len(tokens)):
            (logits, _), pools = step(
                params, pools, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]), grid,
                jnp.asarray([cache.slot_of(0, pos)]), config=cfg)
            got.append(np.asarray(logits[0]))
        got, rows = np.asarray(got), np.arange(p - 1, len(tokens))
    want, clear = _reference_logits(fam, weights, config, tokens, rows)
    assert clear.sum() >= 3
    err = np.sqrt(np.mean(np.square(got - want), axis=-1)) / want.std()
    assert err[clear].max() < 1e-4, (err, want.std())
    counted = np.asarray(counted)
    names = cfg.serving_model().counter_names
    assert ("mhc_rows" in names) == (shape == "lora_4_streams")
    if shape == "lora_4_streams":
        # 13 real tokens x 2 sublayers x 4 layers
        assert counted[names.index("mhc_rows")] == 13 * 2 * 4


def test_one_stream_with_identity_maps_is_not_the_plain_residual():
    """``n = 1`` is NOT asked to equal ``x + F(norm(x))``, and does
    not: with ``b_res`` anything a 1 x 1 ``Hres`` is 1 after the first
    Sinkhorn row, but ``Hpre = sigmoid(.)`` and ``Hpost = 2
    sigmoid(.)`` scale what the sublayer reads and writes."""
    from hetu_tpu.ops import mhc
    x = jnp.asarray(np.random.RandomState(0).randn(6, 1, 64), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randn(6, 64), jnp.float32)
    maps = {"phi": jnp.zeros((64, 3)), "scale": jnp.ones(3),
            "bias": jnp.zeros(3)}
    u, carry = mhc.mhc_pre(x, maps, 20, 1e-6, (-30.0, 30.0))
    out = mhc.mhc_post(x, y, carry)
    np.testing.assert_allclose(np.asarray(carry[1]), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(u), 0.5 * np.asarray(x[:, 0]),
                               rtol=1e-6)        # sigmoid(0) x
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(x[:, 0] + y), rtol=1e-5)
    assert np.abs(np.asarray(u) - np.asarray(x[:, 0])).max() > 0.1


def test_shapes_bytes_and_the_token_cap_follow_the_streams():
    config = tiny_xing("bfloat16")
    cfg = xing_family.model_config(config)
    shapes = lm.latent_moe_param_shapes(cfg)
    assert shapes["lm_h0_q_a"] == ((64, 24), "matrix")
    assert shapes["lm_h0_q_a_norm"] == ((24,), "norm")
    assert shapes["lm_h0_q_b"] == ((24, 4 * 24), "matrix")
    assert "lm_h0_q" not in shapes and "lm_h0_q_norm" not in shapes
    assert shapes["lm_h3_hc_ffn_phi"] == ((4 * 64, 24), "hc_phi")
    assert shapes["lm_h3_hc_attn_bias"] == ((24,), "hc_bias")
    plain = family.model_config(tiny("bfloat16"))
    assert "lm_h0_q_norm" in lm.latent_moe_param_shapes(plain)
    assert not any("hc_" in k for k in lm.latent_moe_param_shapes(plain))
    model, one = cfg.serving_model(), plain.serving_model()
    assert model.param_bytes() == sum(
        int(np.prod(s)) * (2 if kind == "matrix" else 4)
        for s, kind in shapes.values())
    # the state as it enters and leaves a sublayer: 2 x 4 x 64 x 2
    # bytes beside what one stream costs at the same widths
    assert cfg.streams == 4 and plain.streams == 1
    assert model.prefill_bytes_per_token() \
        == one.prefill_bytes_per_token() + 2 * 4 * 64 * 2
    assert model.counter_names == lm.COUNTERS + lm.MHC_COUNTERS
    assert one.counter_names == lm.COUNTERS
    with pytest.raises(ValueError):
        lm.LatentMoEConfig(
            96, 64, 2, 4, 32, 16, 8, 16, 128, 32, 8, 2,
            hyper_connections={"streams": 4})


def test_absorbed_attention_equals_expanded_for_one_layer(f32):
    """A layer's last token attended ABSORBED (decode, and a suffix
    chunk) reads the same as EXPANDED (whole prefill)."""
    config = tiny(layers=1)
    weights = family.seeded_weights(config, 5)
    cfg = family.model_config(config)
    params = lm.latent_moe_serving_params(cfg, weights.__getitem__)
    tokens = np.random.RandomState(1).randint(0, 96, 12).astype(np.int32)
    whole = np.asarray(lm.latent_moe_forward(
        params, jnp.asarray(tokens[None]), cfg))[0]
    cache = PagedKVCache(cfg, num_blocks=8, block_size=4)
    cache.add_seq(0, 12)
    (_, _), pools = lm.latent_moe_paged_prefill(
        params, cache.pools, jnp.asarray(tokens[None, :8]),
        jnp.asarray(cache.slot_mapping(0, 0, 8)[None]),
        jnp.asarray([7]), config=cfg)
    grid = jnp.asarray(cache.gather_slots([0], 12))
    (chunk, _), pools = lm.latent_moe_paged_suffix_prefill(
        params, pools, jnp.asarray(tokens[None, 8:11]), jnp.asarray([8]),
        grid, jnp.asarray(cache.slot_mapping(0, 8, 11)[None]), config=cfg)
    np.testing.assert_allclose(np.asarray(chunk)[0], whole[8:11],
                               atol=2e-5)
    (last, _), _ = lm.latent_moe_paged_step(
        params, pools, jnp.asarray(tokens[11:]), jnp.asarray([11]), grid,
        jnp.asarray([cache.slot_of(0, 11)]), config=cfg)
    np.testing.assert_allclose(np.asarray(last)[0], whole[11], atol=2e-5)


@pytest.mark.parametrize("how", ["chunks", "prefix_hit_copy_on_write"])
def test_suffix_prefill_equals_whole_prefill(f32, how):
    config, weights = f32
    rng = np.random.RandomState(2)
    base = rng.randint(0, 96, 22).astype(np.int32)
    prompts = [base[:19], base, rng.randint(0, 96, 9).astype(np.int32)]
    plain = run_all(engine_for(config, weights), prompts)
    if how == "chunks":
        engine = engine_for(config, weights, prefill_chunk=4)
        got = run_all(engine, prompts)
    else:
        # the second prompt extends the first: its prefix is resident,
        # and writing past the shared tail block copies it first
        engine = engine_for(config, weights, prefix_cache=True)
        got = run_all(engine, prompts[:1]) + run_all(engine, prompts[1:])
        assert engine.stats()["serve_prefix_hit_rate"] > 0
        assert engine.cache.cow_copies >= 1
        engine.cache.assert_consistent()
    for a, b in zip(plain, got):
        assert a.tolist() == b.tolist()
    assert engine.jit_compiles <= engine.compile_bound


def test_continuous_batching_equals_one_request_at_a_time(f32):
    config, weights = f32
    prompts = prompts_of(np.random.RandomState(4), [5, 17, 9, 30, 3, 12])
    together = run_all(engine_for(config, weights), prompts, new=8)
    for p, want in zip(prompts, together):
        alone = run_all(engine_for(config, weights), [p], new=8)[0]
        assert alone.tolist() == want.tolist()


# -- routing ----------------------------------------------------------------

def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer(f32):
    """Each of four chips holds two of the eight experts: their routed
    parts, with the shared expert added ONCE, are the uncut layer."""
    config, weights = f32
    cfg = family.model_config(config)
    w = reference.layer_weights(weights, 1)
    x = jnp.asarray(np.random.RandomState(0).randn(40, 64), jnp.float32)
    experts, wts, _ = moe.route(x, w["router"], w["router_bias"], 2, 2.5)
    valid = jnp.ones(40, bool)
    parts, rows = [], []
    for first in range(0, 8, 2):
        part, n = moe.held_experts(
            x, experts, wts, valid, w["experts_gate_up"][first:first + 2],
            w["experts_down"][first:first + 2], first=first)
        parts.append(part)
        rows.append(np.asarray(n))
    assert np.concatenate(rows).sum() == 80      # every pick landed once
    shared = moe.swiglu(x, w["shared_gate_up"], w["shared_down"])
    # the uncut reference layer, as the block computes it on one chip
    r_experts, r_wts, _, _ = reference.router(x, w["router"],
                                              w["router_bias"], config)
    uncut = reference.swiglu(x, w["shared_gate_up"], w["shared_down"]) \
        + reference.held_experts(x, r_experts, r_wts, w["experts_gate_up"],
                                 w["experts_down"], 0)
    np.testing.assert_allclose(np.asarray(shared + sum(parts)),
                               np.asarray(uncut), atol=1e-4)
    y, _, (all_rows, visits) = lm.feed_forward(
        cfg, {k: v for k, v in w.items()}, x, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(uncut), atol=1e-4)
    assert np.asarray(all_rows).tolist() == np.concatenate(rows).tolist()
    assert int(visits) == int((np.concatenate(rows) > 0).sum())


def test_the_bias_moves_the_selection_and_never_a_weight():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 8) * 0.3, jnp.float32)
    none = jnp.zeros(8)
    e0, w0, p = moe.route(x, w, none, 2, 2.5)
    bias = none.at[5].set(10.0)                 # expert 5 now always wins
    e1, w1, _ = moe.route(x, w, bias, 2, 2.5)
    assert (np.asarray(e1) == 5).any(axis=1).all()
    assert not (np.asarray(e0) == 5).any(axis=1).all()
    picked = np.take_along_axis(np.asarray(p), np.asarray(e1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w1).sum(axis=1), 2.5, rtol=1e-6)


def test_all_tokens_on_one_expert_lose_no_row(f32):
    config, weights = f32
    w = reference.layer_weights(weights, 2)
    x = jnp.asarray(np.random.RandomState(1).randn(24, 64), jnp.float32)
    experts = jnp.tile(jnp.asarray([[3, 6]], jnp.int32), (24, 1))
    wts = jnp.full((24, 2), 1.25)
    # this chip holds experts 0..3: every token's first pick lands on 3,
    # the second elsewhere
    out, rows = moe.held_experts(
        x, experts, wts, jnp.ones(24, bool), w["experts_gate_up"][:4],
        w["experts_down"][:4], first=0)
    assert np.asarray(rows).tolist() == [0, 0, 0, 24]
    want = 1.25 * reference.swiglu(
        x, w["experts_gate_up"][3], w["experts_down"][3])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5)
    # a padded token is routed nowhere
    valid = jnp.arange(24) < 10
    out, rows = moe.held_experts(
        x, experts, wts, valid, w["experts_gate_up"][:4],
        w["experts_down"][:4], first=0)
    assert np.asarray(rows).tolist() == [0, 0, 0, 10]
    assert not np.asarray(out)[10:].any()


def test_yarn_tables_against_the_closed_form():
    plain = lm.yarn_inv_freq(64, 10000.0, None)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(0, 64, 2) / 64))
    s = dict(YARN, original_max_position_embeddings=4096)
    inv = lm.yarn_inv_freq(64, 10000.0, s)
    # the two ends of the ramp, from beta_fast / beta_slow turns over
    # the original context
    dim = lambda turns: 64 * np.log(4096 / (turns * 2 * np.pi)) \
        / (2 * np.log(10000.0))                         # noqa: E731
    low, high = int(np.floor(dim(32))), int(np.ceil(dim(1)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:low + 1], plain[:low + 1])
    np.testing.assert_allclose(inv[high:], plain[high:] / 40)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    np.testing.assert_allclose(
        inv[mid], plain[mid] / 40 * ramp + plain[mid] * (1 - ramp))
    m = 0.1 * np.log(40) + 1
    assert lm.yarn_mscale(40, 1) == pytest.approx(m)
    cfg = family.model_config(dict(tiny(), qk_nope_head_dim=128,
                                   qk_rope_head_dim=64, rope_scaling=s))
    assert lm.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = lm._rope_tables(cfg, jnp.asarray([0, 7]))
    np.testing.assert_allclose(np.asarray(cos)[1], np.cos(7 * inv),
                               atol=1e-6)      # the tables' own factor is 1


# -- the engine around it ---------------------------------------------------

def test_pool_bytes_are_one_latent_row_a_token_a_layer():
    """One pool a layer, a row ``[c ; k_r]`` in whole 128-lane tiles:
    640 x 2 bytes a token a layer at the published widths (576 of them
    the model's: the chip tiles a row to 128 lanes either way), against
    64 heads x (192 + 128) x 2 = 40,960 for expanded keys and values."""
    config = tiny("bfloat16")
    cfg = family.model_config(config)
    cache = PagedKVCache(cfg, num_blocks=10, block_size=4)
    assert len(cache.pools) == 3 and list(cache.pools[0]) == ["c"]
    assert cache.pools[0]["c"].shape == (11, 4, 128)
    assert cache.pools[0]["c"].dtype == jnp.bfloat16
    assert kv_block_bytes(cfg, 4) == 3 * 128 * 2 * 4
    assert cache.hbm_bytes() == 11 * 4 * 3 * 128 * 2
    wide = family.model_config(dict(config, kv_lora_rank=512,
                                    qk_rope_head_dim=64,
                                    num_hidden_layers=6))
    assert wide.cache_row_width == 640
    assert kv_block_bytes(wide, 16) == 6 * 640 * 2 * 16
    model = cfg.serving_model()
    assert model.param_bytes() == sum(
        int(np.prod(s)) * (2 if kind == "matrix" else 4)
        for s, kind in lm.latent_moe_param_shapes(cfg).values())
    # what the cache holds of a row past latent + rope is zeros
    weights = family.seeded_weights(config, 1)
    params = lm.latent_moe_serving_params(cfg, weights.__getitem__)
    cache.add_seq(0, 8)
    (_, _), pools = lm.latent_moe_paged_prefill(
        params, cache.pools, jnp.arange(8)[None],
        jnp.asarray(cache.slot_mapping(0, 0, 8)[None]), jnp.asarray([7]),
        config=cfg)
    rows = np.asarray(pools[1]["c"].astype(jnp.float32))[1:3]
    assert rows[..., :40].any() and not rows[..., 40:].any()


@pytest.mark.parametrize("mode", ["whole", "chunked", "gpt"])
def test_warm_up_leaves_nothing_to_compile_for_its_range(f32, mode):
    if mode == "gpt":
        from gpt_reference import VOCAB, gpt_session
        cfg, sess = gpt_session(seq=64)
        engine = ContinuousBatchingEngine.from_session(
            sess, cfg, num_blocks=48, block_size=4, max_batch_size=4,
            start=False, telemetry=False)
        vocab = VOCAB
    else:
        config, weights = f32
        engine = engine_for(config, weights, **(
            {"prefill_chunk": 8} if mode == "chunked" else {}))
        vocab = 96
    ran = engine.warm_up((3, 20), 6)
    assert ran["decode"] and (ran["suffix_prefill"] if mode == "chunked"
                              else ran["prefill"])
    warmed = engine.jit_compiles
    assert warmed == sum(len(v) for v in ran.values())
    prompts = prompts_of(np.random.RandomState(6),
                         [3, 20, 11, 7, 16, 4, 9], vocab)
    outs = run_all(engine, prompts, new=6)
    assert all(len(o) == 6 for o in outs)
    assert engine.jit_compiles == warmed <= engine.compile_bound
    with pytest.raises(ValueError):
        engine.warm_up((3, 60), 6)              # past max_len


def test_counters_ride_with_the_tokens_and_the_cap_splits_a_group(f32):
    config, weights = f32
    engine = engine_for(config, weights, max_batch_size=4)
    engine.prefill_token_cap = 40        # as a budget would set it
    prompts = prompts_of(np.random.RandomState(8), [14, 15, 13, 16])
    run_all(engine, prompts, new=5)
    s = engine.stats()
    # four prompts of bucket 16: two to a program under a cap of 40
    assert {k for k in engine._signatures if k[0] == "prefill"} == \
        {("prefill", 2, 16)}
    real = sum(len(p) for p in prompts)
    assert s["prefill_moe_tokens"] == 2 * real
    assert s["prefill_moe_routed_rows"] == 2 * 2 * real   # all eight held
    assert sum(s["prefill_moe_rows_by_expert"]) == 4 * real
    assert s["prefill_mla_context_rows"] == 3 * real
    assert s["decode_moe_tokens"] == 2 * 4 * 4      # 4 steps x 4 lanes
    assert s["decode_mla_context_rows"] == 3 * sum(
        len(p) + 1 + i for p in prompts for i in range(4))
    assert 0 < s["decode_moe_expert_visits"] <= 2 * 4 * 8
    assert not engine.program_log            # telemetry off: no log
    # with telemetry on every program leaves its own counts and times
    from hetu_tpu.telemetry import Telemetry
    logged = engine_for(config, weights, telemetry=Telemetry(enabled=True))
    run_all(logged, prompts[:2], new=3)
    rows = list(logged.program_log)
    assert [r["kind"] for r in rows] == ["prefill", "decode", "decode"]
    assert all(r["t0_ns"] < r["t1_ns"] for r in rows)
    assert rows[0]["prefill_moe_tokens"] == 2 * (14 + 15)
    assert sum(r["decode_moe_tokens"] for r in rows[1:]) == \
        logged.stats()["decode_moe_tokens"] == 2 * 2 * 2
    # a share that holds a quarter of the experts sees about a quarter
    quarter = tiny(held=(2, 2))
    share = engine_for(quarter, family.seeded_weights(quarter, 7))
    run_all(share, prompts_of(np.random.RandomState(8), [40] * 4), new=2)
    s = share.stats()
    ratio = s["prefill_moe_routed_rows"] / (2 * s["prefill_moe_tokens"])
    assert 0.1 < ratio < 0.4 and len(s["prefill_moe_rows_by_expert"]) == 2


def test_every_token_comes_back_with_the_record_of_its_row(f32):
    """``Future.token_records``: the picks and the best logit of the
    row that decided each token, from the prefill and from every decode
    step; the reference FORCED onto those picks reads the engine's own
    logit, and forced onto other picks it does not."""
    config, weights = f32
    engine = engine_for(config, weights)
    prompts = prompts_of(np.random.RandomState(21), [9, 14, 11])
    futures = [engine.submit(p, 6) for p in prompts]
    assert all(f.token_records is None for f in futures)
    while not all(f.done() for f in futures):
        engine.step()
    model = engine.model
    assert model.row_record_width == 2 * 2 + 1
    for prompt, f in zip(prompts, futures):
        out = f.result(timeout=0)
        assert f.token_records.shape == (6, 5)
        record = model.read_records(f.token_records)
        readings, layers = family.forced_readings(
            config, weights, prompt, out, record)
        assert readings["gap"].max() == 0.0
        assert readings["value"].max() < 1e-4
        assert readings["pick_distance"].max() < 1e-5
        # forcing the reference onto picks nobody made moves its logits
        other = dict(record, router_picks=(record["router_picks"] + 3) % 8)
        moved, _ = family.forced_readings(config, weights, prompt, out,
                                          other)
        assert moved["value"].max() > 1e-2
        assert np.isinf(family.pick_distance(
            layers[0]["scores"], np.zeros((6, 2), np.int32))).all()
    # a GPT engine's futures carry none
    from hetu_tpu.models import GPTConfig
    assert GPTConfig(vocab_size=8).serving_model().row_record_width == 0


def test_the_reference_owes_the_program_nothing():
    """Its rotary tables and softmax scale are its own: the module
    imports nothing of the package under test."""
    import inspect
    source = inspect.getsource(reference)
    assert "hetu_tpu" not in source.split('"""', 2)[2]
    s = dict(YARN, original_max_position_embeddings=4096)
    np.testing.assert_allclose(reference.yarn_inv_freq(64, 10000.0, s),
                               lm.yarn_inv_freq(64, 10000.0, s), rtol=1e-12)
    assert reference.yarn_mscale(40, 1) == lm.yarn_mscale(40, 1)


def test_the_engine_takes_its_model_from_the_configs_type(f32):
    from hetu_tpu.models import GPTConfig
    from hetu_tpu.models.gpt import GPTServingModel
    config, weights = f32
    engine = engine_for(config, weights)
    assert isinstance(engine.model, lm.LatentMoEServingModel)
    assert isinstance(GPTConfig(vocab_size=8).serving_model(),
                      GPTServingModel)
    # rotary positions: no learned table bounds max_len
    assert engine.max_len == 64 and engine.model.max_positions == 4096
    import inspect
    from hetu_tpu.serving import kvcache, scheduler
    for module in (kvcache, scheduler):
        source = inspect.getsource(module)
        assert "latent_moe" not in source and "sarvam" not in source


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


def _names_of_custom_calls(compiled):
    import re
    return set(re.findall(r"%([A-Za-z_]+)[.\d]* = [^\n]*custom-call",
                          compiled.as_text()))


@pytest.mark.parametrize("rows", [128, 32768])
def test_the_grouped_matmul_compiles_under_its_name(one_chip, rows,
                                                    monkeypatch):
    monkeypatch.setattr(moe, "_use_pallas", lambda: True)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(moe.grouped_matmul).lower(
        struct((rows, 4096), jnp.bfloat16),
        struct((32, 4096, 4096), jnp.bfloat16),
        struct((33,), jnp.int32)).compile()
    assert moe.KERNEL_NAME in _names_of_custom_calls(compiled)


@pytest.mark.parametrize("batch,context", [(1, 2048), (16, 16384)])
def test_the_absorbed_decode_kernel_compiles_under_its_name(
        one_chip, batch, context):
    from hetu_tpu.ops import pallas_mla

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pallas_mla.supported(512, 128, context)
    compiled = jax.jit(
        lambda q, rows, pos: pallas_mla.mla_decode(q, rows, pos, 0.1, 512)
    ).lower(struct((batch, 64, 640), jnp.bfloat16),
            struct((batch, context, 640), jnp.bfloat16),
            struct((batch,), jnp.int32)).compile()
    assert pallas_mla.KERNEL_NAME in _names_of_custom_calls(compiled)


@pytest.mark.parametrize("rows", [1, 8, 32, 4096])
def test_the_residual_kernels_compile_under_their_names(one_chip, rows):
    """``hetu_mhc_pre`` / ``hetu_mhc_post`` at the published stream (4
    x 3584, bfloat16): every decode batch bucket's shape class and a
    prefill's row tiles."""
    from hetu_tpu.ops import mhc

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, c = 4, 3584
    assert mhc.supported(n, c, jnp.bfloat16) is None
    pre = mhc._jitted_pre(n, 20, 1e-6, (-30.0, 30.0), False).lower(
        struct((rows, n * c), jnp.bfloat16),
        struct((n * c, 128), jnp.bfloat16),
        struct((256, 128), jnp.float32)).compile()
    assert mhc.PRE_NAME in _names_of_custom_calls(pre)
    post = mhc._jitted_post(n, False).lower(
        struct((rows, n * c), jnp.bfloat16), struct((rows, c), jnp.bfloat16),
        struct((rows, 128), jnp.float32)).compile()
    assert mhc.POST_NAME in _names_of_custom_calls(post)


def test_the_absorbed_decode_kernel_matches_the_composed_form():
    from hetu_tpu.ops import attention, pallas_mla
    rng = np.random.RandomState(0)
    b, heads, latent, rope, bs, blocks = 3, 4, 128, 128, 4, 8
    q_abs = jnp.asarray(rng.randn(b, heads, latent) * 0.3, jnp.float32)
    q_rope = jnp.asarray(rng.randn(b, heads, rope) * 0.3, jnp.float32)
    pool = jnp.asarray(rng.randn(blocks * b + 1, bs, latent + rope),
                       jnp.float32)
    grid = np.zeros((b, 32), np.int32)
    for i in range(b):
        table = 1 + i * blocks + np.arange(blocks)
        grid[i] = (table[:, None] * bs + np.arange(bs)).reshape(-1)
    positions = jnp.asarray([0, 13, 31])
    want = attention.mla_decode_attention(q_abs, q_rope, pool,
                                          jnp.asarray(grid), positions, 0.2)
    pallas_mla.INTERPRET = True
    try:
        rows = attention._gather_latent_rows(pool, jnp.asarray(grid))
        got = pallas_mla.mla_decode(
            jnp.concatenate([q_abs, q_rope], -1), rows, positions, 0.2,
            latent)
    finally:
        pallas_mla.INTERPRET = False
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
