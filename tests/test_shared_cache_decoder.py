"""The decoder-hybrid-decoder on the serving path
(``models/shared_cache_decoder.py``, the differential attention of
``ops/attention.py``, ``ops/ssm.py``, and all three kinds of pool of
``serving/kvcache.py`` in one cache) against the plain reference
(``benchmark/reference/phi4flash.py``) at tiny presets: 8 layers (3
Mamba, 2 window, 1 full, 1 gate, 1 cross), 8 query heads on 4
key/value heads, a window of 8 rows in blocks of 4. CPU, seeded
weights; the kernels run interpreted here and on the chip in the
benchmark's cell.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import phi4flash as family  # noqa: E402
from benchmark.reference import phi4flash as reference  # noqa: E402
from hetu_tpu.models import shared_cache_decoder as model  # noqa: E402
from hetu_tpu.ops import attention as ops  # noqa: E402
from hetu_tpu.ops import (pallas_attention, pallas_block_gather,  # noqa: E402
                          pallas_diff_attention, ssm)
from hetu_tpu.serving.kvcache import (KVCacheExhausted, PagedKVCache,  # noqa: E402
                                      kv_block_bytes, ring_blocks,
                                      state_slot_bytes)
from hetu_tpu.serving.scheduler import ContinuousBatchingEngine  # noqa: E402

# the reference walks tokens in blocks sized for the published widths
# beside a resident engine; tiny sequences want tiny blocks
reference.BLOCK = reference.PAD = 64
reference.QUERY_BLOCK = 32

VOCAB = 96
WINDOW = 8
BLOCK = 4
RING = 3            # ceil(8 / 4) + 1 blocks: 12 ring slots


def tiny(dtype="float32", layers=8, head_dim=8, window=WINDOW):
    """A configuration file's content, as
    ``configs/phi-4-mini-flash-reasoning.json`` holds it."""
    return {
        "family": "phi4flash", "vocab_size": VOCAB, "hidden_size": 64,
        "num_hidden_layers": layers, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "sliding_window": window, "mb_per_layer": 2,
        "layer_norm_eps": 1e-5, "max_position_embeddings": 1024,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 8,
        "mamba_expand": 2, "serve_dtype": dtype,
        "assumed": {"head_dim": head_dim, "initializer_std": 0.2,
                    "lambda_std": 0.1, "dt_min": 1e-3, "dt_max": 1e-1}}


@pytest.fixture(scope="module")
def f32():
    config = tiny()
    return config, family.seeded_weights(config, 7)


def engine_for(config, weights, **kw):
    kw = dict(dict(num_blocks=64, block_size=BLOCK, max_len=64,
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


def worst(config, weights, engine, prompts, futures):
    """The largest logit gap and value error of the requests against
    the reference's full forward (every layer at every position)."""
    readings = [family.logit_readings(
        config, weights, p, f.result(),
        engine.model.read_records(f.token_records))
        for p, f in zip(prompts, futures)]
    return max(float(r[k].max()) for r in readings
               for k in ("gap", "value"))


# -- (a) the engine against the reference ------------------------------------

@pytest.fixture(scope="module")
def served(f32):
    """One engine, three waves: mixed lengths in one batch; then the
    slots, rings and blocks they left, reused."""
    config, weights = f32
    engine = engine_for(config, weights)
    waves = {"mixed": [5, 8, 13, 23], "reused": [30, 3, 9], "long": [50]}
    out = {}
    for name, lengths in waves.items():
        prompts = prompts_of(len(name), lengths)
        out[name] = (prompts, run_all(engine, prompts, new=12))
    return engine, out


@pytest.mark.parametrize("wave,case", [
    ("mixed", 0), ("mixed", 1), ("mixed", 2), ("mixed", 3),
    ("reused", 0), ("reused", 1), ("reused", 2), ("long", 0)])
def test_engine_logits_match_the_reference(f32, served, wave, case):
    """Prefill (the cross-decoder on the last row alone), then twelve
    decode steps through state slots, rings and the one shared pool,
    held to the reference on LOGITS: prompts under the window (3, 5),
    at it (8), across it (9, 13) and past the ring (23, 30, 50: the ring
    of 12 slots wraps in prefill and again in decode); four lengths in
    one batch; a second wave in the first one's slots and blocks."""
    config, weights = f32
    engine, waves = served
    prompts, futures = waves[wave]
    assert worst(config, weights, engine, prompts[case:case + 1],
                 futures[case:case + 1]) <= 1e-4


def test_counters_say_what_a_prefill_ran(f32, served):
    """``cross_rows`` is PROMPTS in a prefill program, not tokens;
    ``attn_full_rows`` counts a row's context once a reading layer."""
    engine, waves = served
    lengths = [len(p) for prompts, _ in waves.values() for p in prompts]
    stats = engine.stats()
    tokens = sum(lengths)
    assert stats["prefill_cross_rows"] == len(lengths)
    assert stats["prefill_self_rows"] == tokens
    assert stats["prefill_ssm_rows"] == 3 * tokens
    assert stats["prefill_attn_full_rows"] == 2 * tokens
    assert stats["prefill_attn_window_rows"] == 2 * sum(
        min(i + 1, WINDOW) for n in lengths for i in range(n))
    steps = 11 * len(lengths)       # the first token is the prefill's
    assert stats["decode_cross_rows"] == stats["decode_self_rows"] == steps
    assert stats["decode_attn_full_rows"] == 2 * sum(
        n + i + 1 for n in lengths for i in range(11))
    assert stats["state_slots_used"] == 0
    assert stats["window_blocks_used"] == 0
    engine.cache.assert_consistent()


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.35)])
def test_the_kernels_path_matches_the_reference(monkeypatch, dtype, limit):
    """Heads of 64 (a pair's value a whole lane block) and prompts in
    buckets of 128 and 256: the banded two-map flash call and the scan
    and step kernels, interpreted, where the chip runs them."""
    config = tiny(dtype, head_dim=64, window=48)
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssm, "_use_pallas", lambda: True)
    monkeypatch.setattr(pallas_attention, "INTERPRET", True)
    monkeypatch.setattr(ssm, "INTERPRET", True)
    weights = family.seeded_weights(config, 11)
    engine = engine_for(config, weights, block_size=16, num_blocks=48,
                        max_len=512)
    prompts = prompts_of(5, [100, 130])
    futures = run_all(engine, prompts, new=4)
    assert worst(config, weights, engine, prompts, futures) <= limit


def test_a_preempted_request_replays_to_the_same_tokens(f32):
    config, weights = f32
    prompts = prompts_of(3, [20, 21, 22])
    calm = run_all(engine_for(config, weights), prompts, new=10)
    # a pool that cannot hold all three to their end: someone is
    # preempted and replayed through fresh slots, rings and blocks
    tight = engine_for(config, weights, num_blocks=20, reserve="lazy")
    futures = run_all(tight, prompts, new=10)
    assert sum(f.account["replay"] > 0 for f in futures) >= 1
    for a, b in zip(calm, futures):
        np.testing.assert_array_equal(a.result(), b.result())
    tight.cache.assert_consistent()
    assert tight.cache.state_slots_used == 0
    assert tight.cache.window_blocks_used == 0


def test_warm_up_leaves_nothing_to_compile(f32):
    config, weights = f32
    engine = engine_for(config, weights, max_batch_size=2, max_len=32)
    engine.warm_up((3, 14), 4)
    before = engine.jit_compiles
    run_all(engine, prompts_of(4, [3, 9, 14]), new=4)
    assert engine.jit_compiles == before


def test_prefix_cache_and_chunked_prefill_are_refused(f32):
    config, weights = f32
    with pytest.raises(ValueError, match="window layers"):
        engine_for(config, weights, prefix_cache=True)
    with pytest.raises(ValueError, match="window layers"):
        engine_for(config, weights, prefill_chunk=8)


# -- (b) the cache: three kinds of pool in one -------------------------------

def cache_for(**kw):
    cfg = family.model_config(tiny())
    return PagedKVCache(cfg, **dict(dict(num_blocks=32, block_size=BLOCK,
                                         state_slots=2), **kw))


def test_pools_by_kind_and_the_rows_entry_is_one_layers():
    cfg = family.model_config(tiny())
    served = cfg.serving_model()
    assert served.pool_kinds == ("state", "window", "window", "rows")
    row = 2 * (4 * 8) * 4                   # k + v of 4 heads of 8, f32
    # ONE layer's bytes a token, though two layers read the rows
    assert kv_block_bytes(cfg, BLOCK) == BLOCK * row
    assert kv_block_bytes(cfg, BLOCK, "window") == 2 * BLOCK * row
    assert ring_blocks(cfg, BLOCK) == RING
    assert state_slot_bytes(cfg) == 3 * (16 * 128 * 4 + 3 * 128 * 4)
    cache = cache_for()
    assert [sorted(p) for p in cache.pools] == [["conv", "ssm"]] \
        + [["k", "v"]] * 3
    assert cache.pools[0]["ssm"].shape == (3, 3, 16, 128)
    assert cache.pools[1]["k"].shape == (2 * RING + 1, BLOCK, 32)
    assert cache.pools[3]["k"].shape == (33, BLOCK, 32)
    assert cache.hbm_bytes() == sum(
        a.nbytes for p in cache.pools for a in p.values())
    # the published widths: 5,120 bytes a token in the ONE rows pool
    big = model.SharedCacheConfig(200064, 2560, 32, 40, 20, 10240, 512)
    assert kv_block_bytes(big, 16) == 16 * 5120
    assert kv_block_bytes(big, 16, "window") == 8 * 16 * 5120
    assert state_slot_bytes(big) == 3225600
    assert big.serving_model().pool_kinds.count("rows") == 1
    assert big.readers == 8


@pytest.mark.parametrize("event", ["admit", "grow", "finish", "refused"])
def test_the_three_tables_stay_consistent_through(event):
    """A sequence takes a slot, a ring and blocks together, grows the
    two tables together, and gives all three back together; one that
    fits no slot takes nothing."""
    cache = cache_for()
    cache.add_seq("a", 6)
    cache.add_seq("b", 30)
    assert cache.state_slots_used == 2
    assert len(cache.window_tables["a"]) == 2
    assert len(cache.window_tables["b"]) == RING
    assert len(cache.tables["b"]) == 8
    if event == "grow":
        cache.extend_seq("a", 40)
        assert len(cache.window_tables["a"]) == RING
        assert len(cache.tables["a"]) == 10
    if event == "finish":
        cache.free_seq("b")
        assert cache.state_slots_used == 1
        assert cache.window_blocks_used == 2 and cache.used_blocks == 2
        cache.add_seq("c", 9)       # the slot and the ring come back
        assert cache.slot_of_seq("c") != cache.slot_of_seq("a")
    if event == "refused":
        assert not cache.can_admit(4)       # no slot, though blocks
        with pytest.raises(KVCacheExhausted):
            cache.add_seq("c", 4)
        assert "c" not in cache.tables and "c" not in cache.window_tables
    cache.assert_consistent()
    for seq in list(cache.tables):
        cache.free_seq(seq)
    assert cache.used_blocks == cache.window_blocks_used \
        == cache.state_slots_used == 0
    cache.assert_consistent()


# -- (c) the differential op alone -------------------------------------------

HEADS, KV_HEADS, D = 8, 4, 8


def layer_of_random_weights(seed=0):
    """A reference attention layer whose ``W_o`` is the identity: the
    layer's output is the pairs' ``o`` itself."""
    r = np.random.RandomState(seed)
    wide = HEADS * D
    return {"o": jnp.eye(wide), "o_bias": jnp.zeros(wide),
            "pair_norm": jnp.asarray(1 + 0.1 * r.randn(2 * D), jnp.float32),
            **{f"lambda_{n}": jnp.asarray(0.3 * r.randn(D), jnp.float32)
               for n in ("q1", "k1", "q2", "k2")}}


def qkv_rows(t, seed=1):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(t, HEADS * D), jnp.float32),
            jnp.asarray(r.randn(t, KV_HEADS * D), jnp.float32),
            jnp.asarray(r.randn(t, KV_HEADS * D), jnp.float32))


def reference_rows(w, q, k, v, layer, window, mutant=None):
    t = q.shape[0]
    return np.asarray(reference.attend_block(
        w, q, k, v, jnp.arange(t), jnp.ones(t, bool),
        jnp.float32(reference.lambda_init(layer, mutant)), HEADS, KV_HEADS,
        window, 1e-5, mutant))


def program_rows(w, maps, layer):
    init = model.lambda_init(layer)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + init
    return np.asarray(ops.diff_combine(maps, lam, w["pair_norm"],
                                       1.0 - init, 1e-5))


@pytest.mark.parametrize("window", [None, 5, 16])
def test_prefill_form_matches_the_reference(window):
    """The two maps as heads of one causal call, banded or not: pair
    ``p`` reads key pair ``p // 2``, the value is the pair's two heads
    side by side."""
    w, (q, k, v) = layer_of_random_weights(), qkv_rows(16)
    maps = ops.diff_prefill_attention(
        q.reshape(1, 16, HEADS, D), k.reshape(1, 16, KV_HEADS, D),
        v.reshape(1, 16, KV_HEADS, D), D ** -0.5, window=window)
    np.testing.assert_allclose(
        program_rows(w, maps[0], 3), reference_rows(w, q, k, v, 3, window),
        rtol=2e-5, atol=2e-5)
    # and it is NOT the pairing by halves
    assert np.abs(program_rows(w, maps[0], 3) - reference_rows(
        w, q, k, v, 3, window, "pairs_by_halves")).max() > 0.1


@pytest.mark.parametrize("form", ["paged", "ring"])
def test_rows_form_matches_the_reference(monkeypatch, form):
    """One query a sequence against rows as a pool holds them: through
    a block table (every ``j <= t``) and through a ring that has
    wrapped (``t - window < j <= t``), two sequences of different
    lengths in one call."""
    w = layer_of_random_weights(2)
    window = None if form == "paged" else WINDOW
    lengths, got, want = (9, 30), [], []
    cache = cache_for(num_blocks=16)
    pools = {n: jnp.zeros((17, BLOCK, KV_HEADS * D)) for n in "kv"}
    ring_pools = {n: jnp.zeros((2 * RING + 1, BLOCK, KV_HEADS * D))
                  for n in "kv"}
    rows = []
    for seq, t in enumerate(lengths):
        q, k, v = qkv_rows(t, seed=10 + seq)
        cache.add_seq(seq, t)
        slots = jnp.asarray(cache.slot_mapping(seq, 0, t))
        pools = model._write_kv(pools, slots, k, v)
        # a ring keeps the newest row of each slot: write in order
        for at in range(t):
            ring_pools = model._write_kv(
                ring_pools, jnp.asarray([cache.window_slot_of(seq, at)]),
                k[at:at + 1], v[at:at + 1])
        rows.append(q[-1].reshape(HEADS, D))
        want.append(reference_rows(w, q, k, v, 5, window)[-1])
    at = jnp.asarray([t - 1 for t in lengths], jnp.int32)
    q = jnp.stack(rows)
    if form == "paged":
        idx = jnp.asarray(cache.gather_slots([0, 1], 32))
        k_rows, v_rows = (ops._gather_latent_rows(pools[n], idx)
                          for n in "kv")
        valid = jnp.arange(32)[None, :] <= at[:, None]
    else:
        idx = jnp.asarray(cache.ring_slots([0, 1]))
        k_rows, v_rows = (ops._gather_latent_rows(ring_pools[n], idx)
                          for n in "kv")
        valid = ops.ring_valid(RING * BLOCK, at, WINDOW)
    maps = ops.diff_rows_attention(q, k_rows, v_rows, valid, D ** -0.5)
    np.testing.assert_allclose(program_rows(w, maps, 5), np.stack(want),
                               rtol=2e-5, atol=2e-5)
    if form == "paged":
        # the kernel, interpreted: each row read to its own position
        monkeypatch.setattr(pallas_diff_attention, "INTERPRET", True)
        maps = ops.diff_rows_attention(q, k_rows, v_rows, None, D ** -0.5,
                                       positions=at)
        np.testing.assert_allclose(program_rows(w, maps, 5),
                                   np.stack(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-5),
                                         ("bfloat16", 2e-2)])
def test_the_decode_kernel_reads_each_row_to_its_own_position(
        monkeypatch, dtype, limit):
    """Three blocks of 512 rows, sequences that end in the second, the
    first and the third: the kernel (interpreted) against the composed
    form; the blocks past a sequence's own are NaN, which a read of them
    would spread."""
    r = np.random.RandomState(8)
    at = jnp.asarray([700, 100, 1535], jnp.int32)
    q = jnp.asarray(r.randn(3, 8, 32), dtype)
    k, v = (np.asarray(r.randn(3, 1536, 128), np.float32) for _ in "kv")
    composed = ops.diff_rows_attention(
        q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), None, 32 ** -0.5,
        positions=at)
    for row, last in enumerate(np.asarray(at)):
        behind = (last // 512 + 1) * 512    # the blocks past its own
        k[row, behind:] = v[row, behind:] = np.nan
    assert pallas_diff_attention.supported(8, 128, 1536)
    monkeypatch.setattr(pallas_diff_attention, "INTERPRET", True)
    kernel = ops.diff_rows_attention(
        q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), None, 32 ** -0.5,
        positions=at)
    assert kernel.shape == (3, 4, 2, 64) and kernel.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(composed),
                               rtol=limit, atol=limit)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", [True, False])
def test_one_gather_brings_each_sequence_to_its_extent(monkeypatch, dtype,
                                                       kernel):
    """Two groups of pools of one block table (three layers' ``k``, one
    ``v``): inside a sequence's extent the rows are the pools' rows at
    its slots, stacked a group; the kernel (interpreted) copies WHOLE
    blocks up to the extent and writes nothing behind them, the composed
    form the whole bucket."""
    r = np.random.RandomState(12)
    pools = [jnp.asarray(r.randn(40, 16, 128), dtype) for _ in range(4)]
    blocks = r.permutation(39)[:24].reshape(3, 8) + 1
    slot_idx = jnp.asarray(
        (blocks[:, :, None] * 16 + np.arange(16)).reshape(3, 128), jnp.int32)
    extent = jnp.asarray([128, 0, 40], jnp.int32)
    monkeypatch.setattr(pallas_block_gather, "INTERPRET", kernel)
    k, v = ops.gather_rows_once([pools[:3], pools[3:]], slot_idx, extent)
    assert k.shape == (3, 3, 128, 128) and v.shape == (1, 3, 128, 128)
    assert k.dtype == v.dtype == jnp.dtype(dtype)
    flat = [np.asarray(p, np.float32).reshape(-1, 128) for p in pools]
    got = [np.asarray(x, np.float32) for x in (*k, v[0])]
    for row, n in enumerate(np.asarray(extent)):
        for have, pool in zip(got, flat):
            np.testing.assert_array_equal(
                have[row, :n], pool[np.asarray(slot_idx[row, :n])])
    # 40 rows are three blocks of 16: the kernel copied 48 and no more
    behind = pools[0].reshape(-1, 128)[slot_idx[2, 48:]]
    assert bool(jnp.array_equal(k[0, 2, 40:48],
                                pools[0].reshape(-1, 128)[slot_idx[2, 40:48]]))
    assert bool(jnp.array_equal(k[0, 2, 48:], behind)) is not kernel
    # without an extent: every block of the table
    whole = ops.gather_rows_once([pools[3:]], slot_idx)[0]
    np.testing.assert_array_equal(
        np.asarray(whole[0], np.float32),
        flat[3][np.asarray(slot_idx)])


def test_the_extent_is_the_decode_kernels_whole_blocks(monkeypatch):
    """What the gather must bring for ``diff_rows_attention`` by
    positions: the kernel's blocks of 512 up to each position, the whole
    bucket for the composed form (its products take every row)."""
    at = jnp.asarray([0, 511, 512, 1535], jnp.int32)
    assert ops.diff_rows_extent(8, 128, 1536, at).tolist() == [1536] * 4
    monkeypatch.setattr(pallas_diff_attention, "INTERPRET", True)
    assert ops.diff_rows_extent(8, 128, 1536, at).tolist() == [
        512, 512, 1024, 1536]
    # a context of one short block, and shapes the kernel does not take
    assert ops.diff_rows_extent(8, 128, 64, at[:1]).tolist() == [64]
    assert ops.diff_rows_extent(6, 128, 1536, at[:1]).tolist() == [1536]


@pytest.mark.parametrize("form", ["prefill", "ring"])
def test_the_bands_edge_exactly(form):
    """Query ``t`` with a window of ``W``: key ``t - W`` is unseen
    (whatever its value holds, the output does not move), key ``t - W +
    1`` is seen."""
    t, w = 13, layer_of_random_weights(4)
    q, k, v = qkv_rows(t + 1, seed=5)

    def last_row(v):
        if form == "prefill":
            maps = ops.diff_prefill_attention(
                q.reshape(1, t + 1, HEADS, D),
                k.reshape(1, t + 1, KV_HEADS, D),
                v.reshape(1, t + 1, KV_HEADS, D), D ** -0.5,
                window=WINDOW)[0, -1:]
        else:
            ring = RING * BLOCK
            at = np.arange(t + 1)
            kept = at[at > t - ring]        # what a ring still holds
            k_ring, v_ring = (jnp.zeros((1, ring, KV_HEADS * D))
                              .at[0, kept % ring].set(x[kept])
                              for x in (k, v))
            maps = ops.diff_rows_attention(
                q[-1].reshape(1, HEADS, D), k_ring, v_ring,
                ops.ring_valid(ring, jnp.asarray([t]), WINDOW), D ** -0.5)
        return program_rows(w, maps, 1)

    sound = last_row(v)
    np.testing.assert_allclose(
        sound[0], reference_rows(w, q, k, v, 1, WINDOW)[-1], rtol=2e-5,
        atol=2e-5)
    unseen = last_row(v.at[t - WINDOW].set(1e3))
    np.testing.assert_array_equal(unseen, sound)
    seen = last_row(v.at[t - WINDOW + 1].set(1e3))
    assert np.abs(seen - sound).max() > 1.0


# -- (d) what ``correct`` runs ------------------------------------------------

@pytest.fixture(scope="module")
def checked(f32):
    config, weights = f32
    engine = engine_for(config, weights, max_len=128)
    prompts = prompts_of(6, [5, 40, 13, 23])
    futures = run_all(engine, prompts, new=12)
    lines = []
    ok = family.check_generated(
        config, weights, prompts, [f.result() for f in futures],
        [f.token_records for f in futures], lines.append)
    return ok, lines


def test_the_sound_engine_passes_every_part(checked):
    ok, lines = checked
    assert ok
    parts = {x["check"]: x for x in lines if x["check"].startswith(
        "program_")}
    assert set(parts) == {"program_mixer", "program_gate",
                          "program_attention", "program_cross"}
    assert all(x["ok"] and x["prompt_len"] == 40 for x in parts.values())


@pytest.mark.parametrize("fault", reference.CONTROLS + reference.MUTANTS)
def test_every_mutant_and_control_is_caught(checked, fault):
    _, lines = checked
    (line,) = [x for x in lines if x.get("fault") == fault]
    assert line["caught"], line


def test_the_reference_owes_the_program_nothing():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(reference))
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names} | {
        n.module.split(".")[0] for n in ast.walk(tree)
        if isinstance(n, ast.ImportFrom)}
    assert imported <= {"functools", "math", "jax", "numpy"}


def test_a_run_of_equal_pairs_is_one_loop(f32):
    """A program walks each run of equal pairs with ``lax.scan``: two
    loops a program, whatever the depth."""
    for layers in (8, 16):
        config = tiny(layers=layers)
        cfg = family.model_config(config)
        shapes = model.shared_cache_param_shapes(cfg)
        params = jax.eval_shape(lambda: model.shared_cache_serving_params(
            cfg, lambda n: jnp.zeros(shapes[n][0])))
        cache = PagedKVCache(cfg, num_blocks=8, block_size=BLOCK,
                             state_slots=2, telemetry=False)
        z = lambda *s: jnp.zeros(s, jnp.int32)      # noqa: E731
        text = jax.jit(
            lambda p, pools: model.shared_cache_paged_step(
                p, pools, z(2), z(2), z(2, 8), z(2), z(2),
                z(2, RING * BLOCK), z(2), config=cfg, pick="greedy")
        ).lower(params, cache.pools).as_text()
        assert text.count("stablehlo.while") == 2, layers
