"""Window / full grouped-query attention with routed experts on the
serving path (``models/window_moe.py``, the band in
``ops/pallas_attention.py``, ``ops/attention.py:grouped_*``,
the window rings of ``serving/kvcache.py``) against the plain reference
(``benchmark/reference/trinity_afmoe.py``) at a tiny preset: hidden 64,
4 query heads on 2 key/value heads of 16, window 8, blocks of 4, 8
routed experts of which this share holds 4, layers ``sliding, sliding,
full, sliding`` with one dense. CPU, seeded float32 weights; the flash
kernel runs in interpret mode here and on the chip in the benchmark's
cell.
"""
import hashlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):     # run as a script too
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.families import trinity_afmoe as family  # noqa: E402
from benchmark.reference import trinity_afmoe as reference  # noqa: E402
from hetu_tpu.models import window_moe  # noqa: E402
from hetu_tpu.ops import moe  # noqa: E402
from hetu_tpu.ops import pallas_attention as pk  # noqa: E402
from hetu_tpu.ops.attention import attention_reference  # noqa: E402
from hetu_tpu.serving.kvcache import (KVCacheExhausted, PagedKVCache,  # noqa: E402
                                      blocks_for_budget, kv_block_bytes)
from hetu_tpu.serving.scheduler import ContinuousBatchingEngine  # noqa: E402
from hetu_tpu import telemetry as tmod  # noqa: E402
from hetu_tpu.telemetry.check import check_args  # noqa: E402

VOCAB = 96
WINDOW, BLOCK = 8, 4
RING = WINDOW // BLOCK + 1
STORE = os.path.join(HERE, "data", "flash_window_none_text.json")


def tiny(dtype="float32", held=(2, 4), routed=8):
    """A configuration file's content, as
    ``configs/trinity-large-ep8.json`` holds it."""
    layers = ["sliding_attention", "sliding_attention", "full_attention",
              "sliding_attention"]
    return {
        "family": "trinity_afmoe", "vocab_size": VOCAB, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "layer_types": layers, "num_hidden_layers": len(layers),
        "sliding_window": WINDOW, "num_dense_layers": 1,
        "num_experts": held[1], "num_experts_per_tok": 2,
        "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
        "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "max_position_embeddings": 512, "serve_dtype": dtype,
        "deployment": {"num_routed_experts": routed,
                       "experts_first": held[0]},
        "assumed": {"initializer_std": 0.2, "router_bias_std": 0.01}}


@pytest.fixture(scope="module")
def f32():
    config = tiny()
    return config, family.seeded_weights(config, 7)


@pytest.fixture
def tel():
    """Enabled telemetry; restores the process-global default."""
    old_tel = tmod._default
    yield tmod.configure(enabled=True, service="test-window-moe")
    tmod._default = old_tel


def engine_for(config, weights, **kw):
    kw = dict(dict(num_blocks=64, block_size=BLOCK, max_len=64,
                   max_batch_size=4, start=False, telemetry=False), **kw)
    return ContinuousBatchingEngine(family.model_config(config),
                                    weights.__getitem__, **kw)


def run_all(engine, prompts, new=12):
    futures = [engine.submit(p, new) for p in prompts]
    while not all(f.done() for f in futures):
        engine.step()
    return futures


def prompts_of(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in lengths]


def readings(config, weights, prompt, future, **kw):
    model = family.model_config(config).serving_model()
    record = model.read_records(future.token_records)
    return family.forced_readings(config, weights, prompt,
                                  np.asarray(future.result()), record,
                                  **kw)[0]


def close(r, limit=2e-4):
    return bool(np.max(r["gap"]) <= limit and np.max(r["value"]) <= limit
                and np.max(r["pick_distance"]) == 0.0)


# -- (a) the engine against the reference's full forward ---------------------

@pytest.mark.parametrize("lengths", [
    (5,), (WINDOW,), (WINDOW + 1,), (RING * BLOCK + 1,), (30,),
    (3, 30, 9, 17)], ids=lambda l: "x".join(map(str, l)))
def test_engine_tokens_match_the_reference(f32, lengths):
    """Prompts shorter than, as long as and longer than the window and
    the ring, alone and short beside long in one batch; 12 new tokens
    carry every one of them past the ring's wrap (12 slots)."""
    config, weights = f32
    engine = engine_for(config, weights)
    prompts = prompts_of(sum(lengths), lengths)
    for prompt, future in zip(prompts, run_all(engine, prompts)):
        assert close(readings(config, weights, prompt, future))
    engine.cache.assert_consistent()
    assert engine.cache.window_blocks_used == 0 \
        and engine.cache.used_blocks == 0


def test_a_preempted_request_replays_through_both_tables(f32):
    """Lazy reservation over a pool too small for three long requests:
    the youngest is preempted, gives both tables back, and its replay
    rebuilds ring and table from its tokens to the same answer."""
    config, weights = f32
    engine = engine_for(config, weights, num_blocks=20, reserve="lazy",
                        max_batch_size=3)
    prompts = prompts_of(5, (22, 25, 19))
    futures = run_all(engine, prompts, new=14)
    assert any(f.account["replay"] > 0 for f in futures)
    for prompt, future in zip(prompts, futures):
        assert close(readings(config, weights, prompt, future))
    engine.cache.assert_consistent()
    assert engine.cache.window_blocks_used == 0


def test_counters_and_stats(f32, tel):
    config, weights = f32
    engine = engine_for(config, weights, telemetry=tel)
    (prompt,) = prompts_of(2, (13,))
    run_all(engine, [prompt], new=3)
    stats = engine.stats()
    sliding, full = 3, 1
    inside = sum(min(i + 1, WINDOW) for i in range(13))
    # a prefill's rows are its (query, key) pairs: inside the band ...
    assert stats["prefill_attn_window_rows"] == inside * sliding
    # ... and under the diagonal
    assert stats["prefill_attn_full_rows"] == 13 * 14 // 2 * full
    assert "prefill_swa_score_pairs" not in stats
    # two decode steps, at positions 13 and 14
    assert stats["decode_attn_window_rows"] == 2 * WINDOW * sliding
    assert stats["decode_attn_full_rows"] == (14 + 15) * full
    assert stats["prefill_moe_tokens"] == 13 * 3
    assert len(stats["decode_moe_rows_by_expert"]) == 4
    assert stats["window_blocks"] == 4 * RING \
        and stats["window_blocks_used"] == 0
    assert stats["window_hbm_bytes"] == engine.cache.window_bytes() > 0
    rows = list(engine.program_log)
    assert rows and all(r["window_blocks"] == 4 * RING for r in rows)
    assert max(r["window_blocks_used"] for r in rows) == RING
    plans = [e["args"] for e in tel.tracer.drain(clear=True)
             if e.get("name") == "attn_window_plan"]
    assert {p["op"] for p in plans} == {"prefill", "decode"}
    assert all(check_args("attn_window_plan", p) == [] for p in plans)
    assert check_args("attn_window_plan", {"op": "decode"}) != []


# -- (b) the eight shares sum to the uncut layer -----------------------------

def test_the_shares_sum_to_the_uncut_expert_layer():
    """Each share's routed part (``ops/moe.py`` told which experts it
    holds) summed over the shares, plus the shared expert ONCE, is the
    reference's uncut expert layer."""
    whole = tiny(held=(0, 8))
    weights = family.seeded_weights(whole, 11)
    layer = whole["num_dense_layers"]
    w = reference.layer_weights(weights, layer)
    x = jnp.asarray(np.random.RandomState(1).randn(24, 64), jnp.float32)
    experts, wts, _ = moe.route(x, w["router"], w["router_bias"], 2, 2.448)
    total = moe.swiglu(x, w["shared_gate_up"], w["shared_down"])
    rows = 0
    for share in range(8):
        part, held = moe.held_experts(
            x, experts, wts, jnp.ones(24, bool),
            w["experts_gate_up"][share:share + 1],
            w["experts_down"][share:share + 1], first=share)
        total, rows = total + part, rows + int(held.sum())
    assert rows == 24 * 2
    want = reference.uncut_expert_layer(weights, whole, layer, x)
    np.testing.assert_allclose(np.asarray(total), want, rtol=2e-4,
                               atol=2e-5)


# -- (c) the allocator -------------------------------------------------------

def cache_for(**kw):
    cfg = family.model_config(tiny())
    return PagedKVCache(cfg, **dict(dict(num_blocks=32, block_size=BLOCK,
                                         state_slots=2), **kw))


def test_window_blocks_never_pass_the_ring():
    cache = cache_for()
    assert cache.ring == RING and cache.window_blocks == 2 * RING
    cache.add_seq("short", 6)
    cache.add_seq("long", 100)
    assert len(cache.window_tables["short"]) == 2
    assert len(cache.window_tables["long"]) == RING
    assert len(cache.tables["long"]) == 25
    # every position of a long sequence lies in its ring, and a row is
    # overwritten only by one a whole ring later
    slots = cache.window_slot_mapping("long", 0, 100)
    assert len(set(slots.tolist())) == RING * BLOCK
    assert (slots[:-RING * BLOCK] == slots[RING * BLOCK:]).all()
    ring = cache.ring_slots(["long", "short"])
    assert ring.shape == (2, RING * BLOCK)
    assert (ring[0] == slots[:RING * BLOCK]).all()
    assert (ring[1, 2 * BLOCK:] == 0).all()       # never taken: scratch
    assert cache.window_slot_of("long", 57) == slots[57]
    # the window layers' bytes a sequence: window + one block of rows
    row = 2 * 2 * 16 * 4
    assert len(cache.window_tables["long"]) * BLOCK * row \
        == (WINDOW + BLOCK) * row
    cache.assert_consistent()
    cache.free_seq("long")
    cache.free_seq("short")
    assert cache.window_blocks_used == 0 and cache.used_blocks == 0
    cache.assert_consistent()


def test_both_tables_are_charged_all_or_nothing():
    cache = cache_for(window_blocks=RING + 1)
    assert cache.can_admit(40)
    cache.add_seq(0, 40)
    # the full pool has room, the window pool one block: not two
    assert cache.allocator.available >= 10
    assert not cache.can_admit(8) and cache.can_admit(4)
    with pytest.raises(KVCacheExhausted):
        cache.add_seq(1, 8)
    assert 1 not in cache.tables and 1 not in cache.window_tables
    assert cache.window_blocks_used == RING and cache.used_blocks == 10
    # the window pool has room, the full pool has not
    tight = cache_for(num_blocks=3)
    with pytest.raises(KVCacheExhausted):
        tight.add_seq(0, 16)
    assert tight.window_blocks_used == 0 and tight.used_blocks == 0
    assert not tight.fits_at_all(16) and tight.fits_at_all(12)
    # lazy growth takes both or neither
    cache.free_seq(0)
    cache.add_seq(2, 4)
    cache.add_seq(3, 4)
    cache.extend_seq(2, 12)
    assert len(cache.window_tables[2]) == RING
    with pytest.raises(KVCacheExhausted):
        cache.extend_seq(3, 8)
    assert len(cache.tables[3]) == 1 and len(cache.window_tables[3]) == 1
    cache.assert_consistent()


def test_bytes_count_the_window_pools_at_their_size():
    config = tiny()
    cfg = family.model_config(config)
    cache = cache_for()
    row = 2 * (2 * 16) * 4                  # k + v, float32
    assert kv_block_bytes(cfg, BLOCK) == 1 * BLOCK * row
    assert kv_block_bytes(cfg, BLOCK, "window") == 3 * BLOCK * row
    assert cache.window_bytes() == 3 * (2 * RING + 1) * BLOCK * row
    assert cache.kv_bytes() == 33 * BLOCK * row + cache.window_bytes() \
        == cache.hbm_bytes()
    assert sum(p["k"].nbytes + p["v"].nbytes for p in cache.pools) \
        == cache.hbm_bytes()
    budget = cfg.serving_model().param_bytes() + 10 ** 6
    assert blocks_for_budget(cfg, BLOCK, budget, headroom=0.0,
                             window_blocks=2 * RING) \
        == (10 ** 6 - cache.window_bytes()) // (BLOCK * row)
    assert cache.num_blocks == 32 and cache.utilization == 0.0


def test_full_reservation_charges_both_tables(f32):
    """``reserve="full"``: a request waits while EITHER pool lacks its
    blocks, here the window pool (room for one ring)."""
    config, weights = f32
    cfg = family.model_config(config)
    engine = engine_for(config, weights)
    engine.cache = PagedKVCache(cfg, num_blocks=64, block_size=BLOCK,
                                state_slots=4, window_blocks=RING,
                                telemetry=False)
    prompts = prompts_of(9, (20, 21))
    futures = [engine.submit(p, 4) for p in prompts]
    engine.step()
    assert engine.stats()["running"] == 1 and engine.stats()["waiting"] == 1
    while not all(f.done() for f in futures):
        engine.step()
    for prompt, future in zip(prompts, futures):
        assert close(readings(config, weights, prompt, future))


# -- (d) the band in the flash forward ---------------------------------------

def band_tiles(s, bq, bk, window):
    """Tile pairs the band ``0 <= i - j < window`` meets, by hand."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    band = (i - j >= 0) & (i - j < window)
    return int(band.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3)).sum())


def kernel_tiles(s, bq, bk, window):
    """Tile pairs a head of the forward KERNEL runs with the band: each
    region row's diagonal region, the edge regions it reaches, and the
    whole regions between."""
    span = pk._region_span(s, bq, bk)
    if span == s:
        return len(pk.tile_walk(s, bq, bk, True, window)[0])
    whole, edges = pk._band_regions(s, span, window)
    per_region = (span // bq) * (span // bk)
    total = 0
    for qi in range(s // span):
        total += len(pk.tile_walk(span, bq, bk, True, window)[0])
        total += min(qi, whole) * per_region
        total += sum(len(pk.tile_walk(span, bq, bk, True, window,
                                      e * span)[0])
                     for e in edges if qi >= e)
    return total


@pytest.mark.parametrize("s,window", [
    (256, 64), (256, 256), (256, 300), (512, 100), (2048, 1000),
    (4096, 1024), (4096, 1500), (4096, 2048), (4096, 5000)],
    ids=lambda v: str(v))
def test_flash_band_matches_a_masked_reference(s, window):
    """(S, window) cells: a window under a tile, no multiple of a tile,
    a multiple of a region, ``window >= S``; the walk visits the band's
    tiles and no other."""
    rng = np.random.RandomState(s + window)
    q, k, v = (jnp.asarray(rng.randn(1, 2, s, 128), jnp.float32)
               for _ in range(3))
    out = pk.flash_attention(q, k, v, None, sm_scale=0.09, causal=True,
                             interpret=True, window=window)
    mask = jnp.where(pk._band(s, window), 0.0, -1e30)[None, None]
    want = attention_reference(q, k, v, mask, 0.09)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    blocks = pk._block_sizes(s, 128, "fwd", True, False)
    counts = pk.fwd_walk_counts(2, s, *blocks, True, None, window)
    assert counts["tiles_visited"] == band_tiles(s, *blocks, window) \
        == kernel_tiles(s, *blocks, window)
    if window >= s:
        assert counts == pk.fwd_walk_counts(2, s, *blocks, True)


def test_window_wants_causal_and_no_mask():
    x = jnp.zeros((1, 1, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(x, x, x, None, causal=False, interpret=True,
                           window=64)
    with pytest.raises(ValueError, match="mask"):
        pk.flash_attention(x, x, x, jnp.zeros((1, 1, 1, 256)), causal=True,
                           interpret=True, window=64)


def test_walk_instant_carries_the_window(tel):
    x = jnp.zeros((1, 1, 512, 128), jnp.float32)
    pk.flash_attention(x, x, x, None, causal=True, interpret=True,
                       window=100)
    (walk,) = [e["args"] for e in tel.tracer.drain(clear=True)
               if e.get("name") == "flash_fwd_walk"]
    assert walk["window"] == 100 and check_args("flash_fwd_walk", walk) == []
    assert walk["tiles_visited"] == band_tiles(512, 256, 256, 100)


def flash_texts():
    """{call: the jaxpr's text (it carries no location)} of the flash
    calls the accepted cells trace with ``window=None``: the GPT-2 train
    step's (S = 1024, packed rows, with logsumexp, and its backward),
    the BERT train step's (S = 128, three arrays, a padding mask), and
    the serving prefills' (head-major; D = 192 at 8,192, D = 128 at
    4,096, float32 D = 64 at 512)."""
    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def text(fn, *args):
        return str(jax.make_jaxpr(fn)(*args))

    out = {}
    lay = pk.TokenMajor.packed(12, 64)
    x = sds(16, 1024, 2304)
    out["gpt2_train_fwd"] = text(
        lambda q: pk.flash_attention_with_lse(
            q, q, q, None, 0.125, True, False, lay), x)
    o, lse = sds(16, 1024, 768), sds(16, 12, 1, 1024, dtype=jnp.float32)
    out["gpt2_train_bwd"] = text(
        lambda q, o, lse: pk.flash_attention_bwd(
            q, q, q, None, o, lse, o, 0.125, True, False, lay), x, o, lse)
    lay = pk.TokenMajor(12, 64)
    x, m = sds(256, 128, 768), sds(256, 1, 1, 128, dtype=jnp.float32)
    out["bert_train_fwd"] = text(
        lambda q, m: pk.flash_attention_with_lse(
            q, q, q, m, 0.125, False, False, lay), x, m)
    for name, shape, dtype in (
            ("sarvam_prefill", (1, 64, 8192, 192), jnp.bfloat16),
            ("jamba_prefill", (1, 32, 4096, 128), jnp.bfloat16),
            ("gpt2_prefill", (4, 12, 512, 64), jnp.float32)):
        x = sds(*shape, dtype=dtype)
        out[name] = text(lambda q: pk.flash_attention(
            q, q, q, None, 0.1, True, False), x)
    return out


def test_window_none_is_the_parents_program():
    """``window=None``: every flash call of the accepted cells traces
    to the text it traced to on the parent (taken there with ``python
    tests/test_window_moe_serving.py --write`` on the parent's
    ``ops/pallas_attention.py``)."""
    with open(STORE) as f:
        stored = json.load(f)
    # the harness's "highest" is written into every dot of the text
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        texts = flash_texts()
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    got = {k: hashlib.sha256(v.encode()).hexdigest()
           for k, v in texts.items()}
    assert got == stored["sha256"]


# -- (e) the refusals --------------------------------------------------------

def test_prefix_cache_and_chunked_prefill_are_refused(f32):
    config, weights = f32
    with pytest.raises(ValueError, match="prefix_cache.*window"):
        engine_for(config, weights, prefix_cache=True)
    with pytest.raises(ValueError, match="prefill_chunk.*window"):
        engine_for(config, weights, prefill_chunk=8)
    with pytest.raises(ValueError, match="prefix_cache.*window"):
        cache_for(prefix_cache=True)
    with pytest.raises(ValueError, match="route_norm"):
        family.model_config(dict(config, route_norm=False))


# -- (f) each mutant fails ---------------------------------------------------

@pytest.fixture(scope="module")
def checked(f32):
    config, weights = f32
    engine = engine_for(config, weights)
    (prompt,) = prompts_of(21, (29,))
    (future,) = run_all(engine, [prompt])
    return prompt, future


@pytest.mark.parametrize("mutant",
                         reference.WHOLE_MUTANTS + (reference.CONTROL,))
def test_whole_forward_mutants_fail_the_logits(f32, checked, mutant):
    config, weights = f32
    prompt, future = checked
    assert close(readings(config, weights, prompt, future))
    r = readings(config, weights, prompt, future, mutant=mutant)
    assert max(np.max(r["gap"]), np.max(r["value"])) > 1e-2


@pytest.mark.parametrize("mutant", reference.ATTENTION_MUTANTS
                         + ("rope_on_full",))
def test_attention_mutants_fail_the_layers_rows(f32, checked,
                                                       mutant):
    config, weights = f32
    prompt, future = checked
    out = np.asarray(future.result())
    tokens = np.concatenate([prompt, out[:-1]])
    sliding = mutant != "rope_on_full"
    layer = 0 if sliding else 2
    _, _, att = reference.forward(weights, config, tokens, [len(prompt)],
                                  want_layers=(layer,))
    p = len(prompt)
    program, ring = family.program_attention(
        config, weights, layer, att[layer]["input"], p, BLOCK)
    assert ring == RING * BLOCK
    sound = family.attention_readings(config, att[layer], p, ring, program,
                                      sliding=sliding)
    assert np.max(sound["attention_error"]) < 1e-5
    r = family.attention_readings(config, att[layer], p, ring, program,
                                  mutant, sliding)
    assert np.max(r["attention_error"]) > 1e-3


@pytest.mark.parametrize("fault", ["rotates_its_full_layer",
                                   "rotates_no_sliding_layer"])
def test_the_attention_part_sees_the_programs_own_rotation(
        f32, checked, monkeypatch, fault):
    """The part runs ``window_moe.attention_inputs`` on the engine's
    parameters: a PROGRAM that rotates the wrong layers fails it against
    the sound reference."""
    config, weights = f32
    prompt, future = checked
    tokens = np.concatenate([prompt, np.asarray(future.result())[:-1]])
    layer = 2 if fault == "rotates_its_full_layer" else 0
    _, _, att = reference.forward(weights, config, tokens, [len(prompt)],
                                  want_layers=(layer,))
    sound = window_moe.attention_inputs
    monkeypatch.setattr(
        window_moe, "attention_inputs",
        lambda c, blk, h, cos, sin, sliding: sound(
            c, blk, h, cos, sin, fault == "rotates_its_full_layer"))
    p = len(prompt)
    program, ring = family.program_attention(
        config, weights, layer, att[layer]["input"], p, BLOCK)
    r = family.attention_readings(config, att[layer], p, ring, program,
                                  sliding=layer == 0)
    assert np.max(r["attention_error"]) > 1e-2


def test_the_experts_control_fails_the_routed_sum(f32):
    config, weights = f32
    x = np.random.RandomState(5).randn(16, 64).astype(np.float32)
    _, sound = family.parts_against_reference(config, weights, 1, x)
    _, control = family.parts_against_reference(config, weights, 1, x,
                                                reference.CONTROL)
    assert control["weight_error"] == sound["weight_error"]
    assert control["routed_error"] > 10 * max(sound["routed_error"], 1e-4)


def test_route_scale_fails_the_router_part(f32, checked):
    config, weights = f32
    x = np.random.RandomState(3).randn(16, 64).astype(np.float32)
    ok, reading = family.parts_against_reference(config, weights, 1, x)
    assert ok, reading
    ok, reading = family.parts_against_reference(config, weights, 1, x,
                                                 "no_route_scale")
    assert not ok and reading["weight_error"] > 0.5


if __name__ == "__main__":
    if sys.argv[1:] == ["--write"]:
        with open(STORE, "w") as f:
            json.dump({
                "what": "sha256 of the jaxpr text of each window=None "
                        "flash call of tests/test_window_moe_serving.py"
                        ":flash_texts, taken on the PARENT of PR 47 "
                        "(commit 7cf0e1f)",
                "sha256": {k: hashlib.sha256(v.encode()).hexdigest()
                           for k, v in flash_texts().items()}}, f,
                      indent=1)
            f.write("\n")
