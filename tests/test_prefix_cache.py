"""Prefix-cached paged KV (hetu_tpu/serving/kvcache.py PrefixCache +
scheduler.py suffix-prefill path): rolling-hash chunk keying, shared
blocks with per-block refcounts, copy-on-write isolation, LRU eviction
of cached-unreferenced blocks under pressure, chunked prefill
interleaving with decode, and the engine-level guarantee that prefix
sharing and chunking change NOTHING about outputs (byte-identical
tokens, logits within 1e-5 of the graph's full forward)."""
import numpy as np
import pytest

from hetu_tpu import telemetry
import hetu_tpu.models as M
from hetu_tpu.models.gpt import (gpt_paged_prefill,
                                 gpt_paged_suffix_prefill,
                                 gpt_serving_params)
from hetu_tpu.serving import (ContinuousBatchingEngine, PagedKVCache,
                              PrefixCache)

from gpt_reference import VOCAB, full_forward, gpt_session

SEQ = 64


def _tel():
    return telemetry.Telemetry(enabled=True)


def _cfg(layers=2):
    return M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                       num_hidden_layers=layers, num_attention_heads=4,
                       max_position_embeddings=SEQ,
                       hidden_dropout_prob=0.0)


def _gpt_session(seed=0):
    return gpt_session(seed=seed, seq=SEQ)


def _drive(engine, futures, limit=800):
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"
    return steps


# ---------------------------------------------------------------------------
# PrefixCache: rolling-hash keying, tails, LRU
# ---------------------------------------------------------------------------

def test_prefix_cache_match_full_blocks_and_tail():
    pc = PrefixCache(block_size=4)
    prompt = np.arange(10, dtype=np.int32)          # 2 full blocks + 2
    assert pc.insert_full(prompt[:4], 7)
    assert pc.insert_full(prompt[:8], 8)
    assert pc.insert_tail(prompt[:8], prompt[8:], 9)
    # exact prompt: both full blocks + the tail
    blocks, cached = pc.match(prompt)
    assert blocks == [7, 8, 9] and cached == 10
    # longer prompt with the same prefix: same blocks, same coverage
    longer = np.concatenate([prompt, [50, 51]]).astype(np.int32)
    blocks, cached = pc.match(longer)
    assert blocks == [7, 8, 9] and cached == 10
    # diverging after one block: only the first block matches (the
    # divergent second block must NOT, and the tail is keyed off the
    # full-block chain so it can't leak in either)
    div = prompt.copy()
    div[5] += 1
    blocks, cached = pc.match(div)
    assert blocks == [7] and cached == 4
    # tail shorter than stored: conservative miss on the tail
    blocks, cached = pc.match(prompt[:9])
    assert blocks == [7, 8] and cached == 8


def test_prefix_cache_keys_are_position_sensitive():
    """The rolling hash chains every preceding token into a block's
    key: identical token CONTENT at a different offset must not match
    (its K/V rows encode different positions and history)."""
    pc = PrefixCache(block_size=4)
    a = np.array([1, 2, 3, 4, 1, 2, 3, 4], np.int32)
    assert pc.insert_full(a[:4], 5)
    assert pc.insert_full(a[:8], 6)     # same tokens, second position
    assert 5 != 6
    blocks, cached = pc.match(a)
    assert blocks == [5, 6] and cached == 8
    # a prompt STARTING with the second block's tokens hits the
    # first-position entry (same content AND same position) — not the
    # second-position one
    blocks, _ = pc.match(np.array([1, 2, 3, 4], np.int32))
    assert blocks == [5]


def test_prefix_cache_lru_eviction_order():
    pc = PrefixCache(block_size=4)
    for i in range(3):
        assert pc.insert_full(np.arange(i * 100, i * 100 + 4), 10 + i)
    for b in (10, 11, 12):
        pc.mark_unreferenced(b)
    pc.mark_referenced(11)              # 11 is in use: not evictable
    assert pc.evictable == 2
    assert pc.pop_lru() == 10           # oldest unreferenced first
    assert pc.pop_lru() == 12
    assert pc.pop_lru() is None         # 11 still referenced
    assert pc.cached_blocks == 1        # 11's entry survives
    # evicted entries really left the map
    blocks, cached = pc.match(np.arange(4))
    assert blocks == [] and cached == 0


# ---------------------------------------------------------------------------
# PagedKVCache: sharing, CoW, eviction, consistency
# ---------------------------------------------------------------------------

def test_cache_prefix_hit_shares_blocks_and_caps_at_last_token():
    cfg = _cfg()
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4,
                         prefix_cache=True)
    prompt = np.arange(10, dtype=np.int32)
    blocks, cached = cache.add_seq_prefix(0, 10, prompt)
    assert cached == 0 and len(blocks) == 3
    cache.insert_prefix(0, prompt)
    used_after_insert = cache.used_blocks
    # identical prompt: every block shared, zero new allocations; the
    # cap leaves the LAST prompt token to recompute (its logits seed
    # the first sampled token)
    blocks2, cached2 = cache.add_seq_prefix(1, 10, prompt)
    assert cached2 == 9
    assert blocks2 == blocks            # same physical blocks
    assert cache.used_blocks == used_after_insert, \
        "a full prefix hit allocated fresh blocks"
    # both sequences + the cache reference the shared blocks
    assert cache.allocator.refcount(blocks[0]) == 3
    cache.free_seq(0)
    cache.free_seq(1)
    # blocks stay resident (the cache's reference), now evictable
    assert cache.referenced_blocks == 0
    assert cache.cached_blocks == 3
    cache.assert_consistent()


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_free_seq_hands_its_blocks_back_in_one_allocator_call(prefix_cache):
    """A finished sequence's table goes back in ONE ``allocator.free``
    (a call re-sorts the whole free list: a block at a time that was
    hundreds of milliseconds for a long sequence in a large pool), and
    the pool is left as a block at a time left it: the free list sorted,
    cached blocks evictable in the table's order."""
    cache = PagedKVCache(_cfg(), num_blocks=16, block_size=4,
                         prefix_cache=prefix_cache)
    prompt = np.arange(13, dtype=np.int32)
    if prefix_cache:
        blocks, _ = cache.add_seq_prefix(0, 22, prompt)
        cache.insert_prefix(0, prompt)
    else:
        blocks = list(cache.add_seq(0, 22))
    assert len(blocks) == 6
    cache.add_seq(1, 5)                 # another sequence's stay put
    calls, free = [], cache.allocator.free
    cache.allocator.free = lambda b: calls.append(list(b)) or free(b)
    cache.free_seq(0)
    assert calls == [blocks]
    cache.assert_consistent()
    # 13 tokens: 3 full blocks and the frozen tail stay the cache's
    kept = blocks[:4] if prefix_cache else []
    assert [b for b in blocks if b in cache.allocator._ref] == kept
    assert list(cache.allocator._free) == sorted(cache.allocator._free)
    assert set(blocks) - set(kept) <= set(cache.allocator._free)
    if prefix_cache:
        assert list(cache.prefix._lru) == kept
        assert cache.cached_blocks == 4 and cache.referenced_blocks == 2
    with pytest.raises(ValueError, match="double free"):
        free(blocks)                    # nothing is released twice
    cache.free_seq(1)
    cache.assert_consistent()


def test_cache_cow_isolates_sharers():
    """A sequence extending into a shared tail block copies it first:
    the sharer's rows and the cache's frozen entry never see the
    write."""
    cfg = _cfg()
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4,
                         prefix_cache=True)
    prompt = np.arange(6, dtype=np.int32)       # 1 full block + 2 tail
    cache.add_seq_prefix(0, 6 + 4, prompt)
    cache.insert_prefix(0, prompt)
    tail = cache.tables[0][1]
    # seq 0's first write past the prompt (position 6) lands in the
    # cache-frozen tail block -> CoW
    copies = cache.ensure_writable(0, 6, 7)
    assert copies == 1 and cache.cow_copies == 1
    assert cache.tables[0][1] != tail, "table still points at the "\
        "shared block after CoW"
    # the cache entry survives on the ORIGINAL block and still matches
    blocks, cached = cache.match_prefix(prompt)
    assert tail in blocks
    # the copied block's pool rows equal the source rows (history moved)
    k_src = np.asarray(cache.pools[0]["k"][tail])
    k_dst = np.asarray(cache.pools[0]["k"][cache.tables[0][1]])
    np.testing.assert_array_equal(k_src, k_dst)
    # a second writer into its own private copy: no further CoW
    assert cache.ensure_writable(0, 7, 8) == 0
    cache.assert_consistent()


def test_cache_cow_exhaustion_drops_cache_entry_in_place():
    """When the pool can't fund the copy and the ONLY other referent is
    the cache, the entry is dropped and the sequence writes in place —
    the cache relinquishes rather than kill the request."""
    cfg = _cfg()
    cache = PagedKVCache(cfg, num_blocks=2, block_size=4,
                         prefix_cache=True)
    prompt = np.arange(6, dtype=np.int32)
    cache.add_seq_prefix(0, 6, prompt)          # both blocks used
    cache.insert_prefix(0, prompt)
    tail = cache.tables[0][1]
    assert cache.allocator.available == 0
    copies = cache.ensure_writable(0, 6, 7)
    assert copies == 0                          # wrote in place
    assert cache.tables[0][1] == tail
    assert cache.allocator.refcount(tail) == 1  # cache ref dropped
    blocks, cached = cache.match_prefix(prompt)
    assert tail not in blocks, "dropped tail entry still matches"
    cache.assert_consistent()


def test_cache_evicts_lru_cached_blocks_under_pressure():
    """Cached-unreferenced blocks are reclaimable: allocation pressure
    evicts them LRU-first instead of failing admission."""
    cfg = _cfg()
    cache = PagedKVCache(cfg, num_blocks=4, block_size=4,
                         prefix_cache=True)
    a = np.arange(8, dtype=np.int32)
    cache.add_seq_prefix(0, 8, a)
    cache.insert_prefix(0, a)
    cache.free_seq(0)
    assert cache.cached_blocks == 2 and cache.allocator.available == 2
    # a 4-block allocation must evict both cached blocks
    cache.add_seq(1, 16)
    assert cache.cached_blocks == 0
    assert cache.prefix.evictions == 2
    assert cache.match_prefix(a) == ([], 0)
    cache.free_seq(1)
    cache.assert_consistent()


# ---------------------------------------------------------------------------
# suffix prefill numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("split", [1, 4, 7])
def test_suffix_prefill_logits_match_full_forward(split):
    """Prefill split at an offset (the prefix-hit shape): rows 0..k-1
    via the batch prefill, rows k.. via gpt_paged_suffix_prefill — every
    suffix position's logits equal the graph's full-prompt forward
    within 1e-5. The split falls after one token, on a block boundary
    (block_size 4) and three rows into a block."""
    import jax.numpy as jnp

    cfg, sess = _gpt_session()
    params = gpt_serving_params(cfg, sess.params_by_name().__getitem__)
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
    rng = np.random.RandomState(0)
    x = rng.randint(0, VOCAB, (1, 14))
    full = full_forward(sess, x)

    cache.add_seq(0, 14)
    slots = cache.slot_mapping(0, 0, split)[None, :]
    _, pools = gpt_paged_prefill(
        params, cache.pools, jnp.asarray(x[:, :split], jnp.int32),
        jnp.asarray(slots), num_heads=cfg.num_attention_heads)
    suffix = 14 - split
    grid = cache.gather_slots([0], 16)
    write = cache.slot_mapping(0, split, 14)[None, :]
    slogits, pools = gpt_paged_suffix_prefill(
        params, pools, jnp.asarray(x[:, split:], jnp.int32),
        jnp.asarray([split], jnp.int32), jnp.asarray(grid),
        jnp.asarray(write), num_heads=cfg.num_attention_heads)
    assert slogits.shape == (1, suffix, VOCAB)
    np.testing.assert_allclose(np.asarray(slogits), full[:, split:],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine: prefix sharing + chunked prefill change nothing about outputs
# ---------------------------------------------------------------------------

def _shared_prompt_trace(rng, n=8):
    sys_prompt = rng.randint(0, VOCAB, (12,))
    trace = []
    for k in range(n):
        if k % 3 == 2:
            p = rng.randint(0, VOCAB, (int(rng.randint(4, 16)),))
        else:
            p = np.concatenate(
                [sys_prompt, rng.randint(0, VOCAB,
                                         (int(rng.randint(2, 6)),))])
        trace.append((p.astype(np.int32), int(rng.randint(2, 6))))
    return trace


def _serve(sess, cfg, trace, *, sequential=True, **kw):
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, start=False, **kw)
    futs = []
    for p, g in trace:
        futs.append(eng.submit(p, g))
        if sequential:
            _drive(eng, futs[-1:])
    _drive(eng, futs)
    outs = [f.result(1).tolist() for f in futs]
    return eng, outs


def test_engine_prefix_cache_outputs_identical_and_hits():
    """Same trace through a no-cache engine and a prefix-cache engine:
    byte-identical greedy tokens, a real hit rate on the shared-prompt
    traffic, zero sequence-referenced blocks after retirement (cached
    blocks stay resident), and the refcount invariant sweep passes."""
    tel = _tel()
    cfg, sess = _gpt_session(seed=1)
    trace = _shared_prompt_trace(np.random.RandomState(2))
    _, want = _serve(sess, cfg, trace, num_blocks=64)
    eng, got = _serve(sess, cfg, trace, num_blocks=64,
                      prefix_cache=True, telemetry=tel)
    assert got == want, "prefix cache changed generated tokens"
    assert eng.cache.prefix.hit_rate() > 0.3, \
        f"shared-prompt trace only hit {eng.cache.prefix.hit_rate():.2f}"
    assert tel.counter_value("engine_prefill_cached_tokens") > 0
    # computed-vs-cached split: computed prefill tokens + cached tokens
    # cover every prompt token exactly
    total_prompt = sum(len(p) for p, _ in trace)
    assert tel.counter_value("engine_prefill_tokens") \
        + tel.counter_value("engine_prefill_cached_tokens") \
        == total_prompt
    assert eng.cache.referenced_blocks == 0, "retired seqs leaked refs"
    assert eng.cache.cached_blocks > 0, "cache evicted without pressure"
    eng.cache.assert_consistent()
    assert eng.stats()["serve_prefix_hit_rate"] > 0.3
    eng.close()


def test_engine_chunked_prefill_outputs_identical_and_interleaves():
    """A long cold prompt prefilling in pow2 chunks: outputs identical
    to the unchunked engine, the prompt spans multiple engine steps
    (serve_prefill_chunk spans), a concurrently running sequence keeps
    decoding between those chunks, and HT901 holds."""
    tel = _tel()
    cfg, sess = _gpt_session(seed=3)
    rng = np.random.RandomState(4)
    long_prompt = rng.randint(0, VOCAB, (40,)).astype(np.int32)
    short = rng.randint(0, VOCAB, (4,)).astype(np.int32)
    trace = [(short, 20), (long_prompt, 4)]
    _, want = _serve(sess, cfg, trace, sequential=False, num_blocks=64)

    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, start=False,
        num_blocks=64, prefill_chunk=8, telemetry=tel)
    f_short = eng.submit(short, 20)
    eng.step()                      # short admits and starts decoding
    f_long = eng.submit(long_prompt, 4)
    done_before = 0
    interleaved = False
    for _ in range(200):
        eng.step()
        # while the long prompt is still prefilling, the short request
        # must keep earning tokens — chunking's whole point
        still_prefilling = any(
            s.prompt.shape[0] == 40 and s.prefilling()
            for s in eng._running)
        if still_prefilling and len(eng._running) > 1:
            now_done = next(len(s.generated) for s in eng._running
                            if s.prompt.shape[0] != 40)
            if now_done > done_before > 0:
                interleaved = True
            done_before = max(done_before, now_done)
        if f_short.done() and f_long.done():
            break
    assert [f_short.result(1).tolist(), f_long.result(1).tolist()] \
        == want, "chunked prefill changed generated tokens"
    assert interleaved, "decode made no progress during chunked prefill"
    chunks = [e for e in tel.tracer.drain()
              if e.get("name") == "serve_prefill_chunk"]
    assert len(chunks) >= 5, \
        f"40-token prompt at chunk=8 dispatched {len(chunks)} chunks"
    assert all(c["args"]["tokens"] <= 8 for c in chunks)
    assert eng.jit_compiles <= eng.compile_bound
    eng.close()


def test_engine_prefix_plus_chunked_with_preemption_reproduces():
    """The works: prefix cache + chunked prefill + lazy reserve on a
    pool small enough to preempt. Outputs still byte-identical to the
    plain full-reserve engine, and after the churn the allocator passes
    the zero-leak / zero-dangling-refcount sweep."""
    tel = _tel()
    cfg, sess = _gpt_session(seed=5)
    trace = _shared_prompt_trace(np.random.RandomState(6), n=8)
    _, want = _serve(sess, cfg, trace, sequential=False, num_blocks=64)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=4, start=False,
        num_blocks=14, reserve="lazy", prefix_cache=True,
        prefill_chunk=8, telemetry=tel)
    futs = [eng.submit(p, g) for p, g in trace]
    _drive(eng, futs)
    assert [f.result(1).tolist() for f in futs] == want, \
        "prefix+chunk+preemption changed generated tokens"
    assert eng.cache.referenced_blocks == 0
    eng.cache.assert_consistent()
    eng.close()


def test_engine_prefix_cache_eviction_keeps_serving():
    """Distinct prompts fill the cache; admission pressure evicts LRU
    cached blocks instead of deadlocking the queue."""
    tel = _tel()
    cfg, sess = _gpt_session(seed=7)
    rng = np.random.RandomState(8)
    trace = [(rng.randint(0, VOCAB, (10,)).astype(np.int32), 3)
             for _ in range(8)]
    eng, _ = _serve(sess, cfg, trace, num_blocks=10,
                    prefix_cache=True, telemetry=tel)
    assert eng.cache.prefix.evictions > 0, \
        "10-block pool never evicted across 8 distinct 10-token prompts"
    assert tel.counter_value("serve_prefix_evictions") \
        == eng.cache.prefix.evictions
    eng.cache.assert_consistent()
    eng.close()


def test_engine_inflight_and_stats_report_prefix_fields():
    cfg, sess = _gpt_session(seed=9)
    rng = np.random.RandomState(10)
    sys_p = rng.randint(0, VOCAB, (8,)).astype(np.int32)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, block_size=4, max_batch_size=2, start=False,
        num_blocks=32, prefix_cache=True)
    f0 = eng.submit(sys_p, 2)
    _drive(eng, [f0])
    p1 = np.concatenate([sys_p, [1, 2, 3]]).astype(np.int32)
    f1 = eng.submit(p1, 8)
    eng.step()
    rows = {r["request_id"]: r for r in eng.inflight_requests()}
    (row,) = rows.values()
    assert row["cached_tokens"] > 0, \
        "in-flight table missing the cache-resolved prompt tokens"
    st = eng.stats()
    assert st["prefix_cache"] is True
    assert st["kv_blocks_cached"] >= 1
    assert 0.0 <= st["kv_hbm_utilization_cached"] <= 1.0
    assert st["serve_prefix_hit_rate"] > 0.0
    assert "serve_cow_copies" in st
    _drive(eng, [f1])
    eng.close()
