"""Continuous-batching serving plane (hetu_tpu/serving/kvcache.py,
scheduler.py, router.py): block allocator invariants, paged decode
numerics pinned to the graph's full forward (rtol/atol 1e-5),
iteration-level scheduling with the HT901 compile bound measured under
churn, KV-block admission control, lazy-reserve preemption determinism,
and SLO-probed replica routing."""
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
import hetu_tpu.models as M
from hetu_tpu.models.gpt import (gpt_paged_prefill, gpt_paged_step,
                                 gpt_serving_params)
from hetu_tpu.serving import (BlockAllocator, ContinuousBatchingEngine,
                              EngineOverloaded, KVCacheExhausted,
                              PagedKVCache, ReplicaRouter,
                              RouterOverloaded, SLOWindow)

from gpt_reference import VOCAB, full_forward, gpt_session, greedy_chain

SEQ = 32


def _tel():
    return telemetry.Telemetry(enabled=True)


def _drive(engine, futures, limit=500):
    """Drive a start=False engine until every future resolves."""
    steps = 0
    while any(not f.done() for f in futures):
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to converge"
    return steps


# ---------------------------------------------------------------------------
# block allocator / paged cache invariants
# ---------------------------------------------------------------------------

def test_block_allocator_stress_no_leaks():
    """Alloc/share/release/free cycles (the refcounted prefix-sharing
    shape) leak no blocks and leave no dangling refcounts: a shadow
    refcount model tracks every operation and the allocator must agree
    with it at every step; exhaustion raises the documented error
    WITHOUT allocating anything (all-or-nothing) and without touching
    live allocations; reuse is deterministic."""
    a = BlockAllocator(8, 4, first_id=1)
    rng = np.random.RandomState(0)
    refs = []       # one entry per outstanding reference: a block list
    for _ in range(400):
        r = rng.rand()
        if refs and r < 0.35:
            a.free(refs.pop(rng.randint(len(refs))))
        elif refs and r < 0.55:
            # share an existing allocation (a prefix hit / CoW source
            # taking its own reference to the same physical blocks)
            blocks = refs[rng.randint(len(refs))]
            a.share(blocks)
            refs.append(list(blocks))
        else:
            n = int(rng.randint(1, 4))
            if n <= a.available:
                got = a.alloc(n)
                assert len(got) == n
                refs.append(got)
            else:
                used_before = a.used
                with pytest.raises(KVCacheExhausted):
                    a.alloc(n)
                # all-or-nothing: the failed alloc took nothing and
                # corrupted no neighbor
                assert a.used == used_before
        # zero drift between the shadow model and the allocator: every
        # live block's refcount equals its outstanding references, no
        # block is live without a reference (leak) or referenced while
        # free (dangling)
        want = {}
        for blocks in refs:
            for b in blocks:
                want[b] = want.get(b, 0) + 1
        assert want == {b: a.refcount(b) for b in want}
        assert a.used == len(want)
        assert a.available == 8 - len(want)
    for blocks in refs:
        a.free(blocks)
    assert a.used == 0 and a.available == 8
    # deterministic reuse: freed-in-any-order blocks come back sorted
    assert a.alloc(8) == list(range(1, 9))
    with pytest.raises(ValueError):
        a.free([3, 3])          # double free within one call


def test_block_allocator_refcount_underflow_raises():
    """free() validates BEFORE mutating: releasing more references than
    a block holds (double free of a shared block, refcount underflow)
    raises and changes nothing; share() of a dead block raises."""
    a = BlockAllocator(4, 4, first_id=1)
    blocks = a.alloc(2)
    a.share(blocks)                     # refcount 2 each
    with pytest.raises(ValueError, match="double free"):
        a.free(blocks + blocks + blocks)    # 3 releases vs 2 held
    assert all(a.refcount(b) == 2 for b in blocks), \
        "failed free mutated refcounts"
    assert a.free(blocks) == []         # refcount 2 -> 1: none freed
    freed = a.free(blocks)              # refcount 1 -> 0: both freed
    assert sorted(freed) == sorted(blocks)
    with pytest.raises(ValueError, match="double free"):
        a.free([blocks[0]])             # dead block
    with pytest.raises(ValueError, match="non-live"):
        a.share([blocks[0]])            # can't share a free block
    assert a.used == 0 and a.available == 4


def test_paged_cache_tables_disjoint_and_scratch_reserved():
    cfg = M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=SEQ,
                      hidden_dropout_prob=0.0)
    cache = PagedKVCache(cfg, num_blocks=10, block_size=4)
    rng = np.random.RandomState(1)
    for sid in range(6):
        cache.add_seq(sid, int(rng.randint(1, 9)))
    tables = list(cache.tables.values())
    flat = [b for t in tables for b in t]
    assert len(flat) == len(set(flat)), "sequences share a block"
    assert 0 not in flat, "scratch block handed to a real sequence"
    # slot math: position j of a sequence lands inside its own blocks
    for sid, table in cache.tables.items():
        cap = cache.capacity_tokens(sid)
        slots = cache.slot_mapping(sid, 0, cap)
        assert set(s // 4 for s in slots) == set(table)
    before = {sid: list(t) for sid, t in cache.tables.items()}
    with pytest.raises(KVCacheExhausted):
        cache.add_seq(99, 10 * 4)
    assert {sid: list(t) for sid, t in cache.tables.items()} == before
    for sid in list(cache.tables):
        cache.free_seq(sid)
    assert cache.used_blocks == 0 and cache.utilization == 0.0


def test_cache_requires_num_blocks_without_budget(monkeypatch):
    monkeypatch.delenv("HETU_HBM_BUDGET", raising=False)
    cfg = M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=SEQ)
    with pytest.raises(ValueError, match="num_blocks"):
        PagedKVCache(cfg)       # CPU harness: no budget resolvable


def test_cache_sizes_from_hbm_budget(monkeypatch):
    """The HT4xx budget plumbing sizes the pool: blocks fit in (budget
    - params - headroom), and the pool's own byte accounting stays
    inside the budget."""
    from hetu_tpu.serving.kvcache import gpt_param_bytes, kv_block_bytes
    monkeypatch.setenv("HETU_HBM_BUDGET", "64MiB")
    cfg = M.GPTConfig(vocab_size=VOCAB, hidden_size=32,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=SEQ)
    cache = PagedKVCache(cfg, block_size=8)
    budget = 64 << 20
    want = (int(budget * 0.9) - gpt_param_bytes(cfg)) \
        // kv_block_bytes(cfg, 8)
    assert cache.num_blocks == want
    assert cache.hbm_bytes() + gpt_param_bytes(cfg) <= budget


# ---------------------------------------------------------------------------
# paged numerics pinned to the graph's full forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hidden_act", ["gelu", "relu"])
def test_paged_prefill_and_step_logits_match_full_forward(hidden_act):
    """Teacher-forced paged decode: prefill logits and every step's
    logits equal the graph's full-sequence forward at that position
    (rtol/atol 1e-5 fp32) — with a relu MLP too, so the serving block
    cannot silently hard-code gelu."""
    import jax.numpy as jnp

    cfg, sess = gpt_session(hidden_act=hidden_act)
    params = gpt_serving_params(cfg, sess.params_by_name().__getitem__)
    kw = dict(num_heads=cfg.num_attention_heads, hidden_act=hidden_act)
    cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
    rng = np.random.RandomState(0)
    x = rng.randint(0, VOCAB, (2, 14))
    prefix = 6
    full = full_forward(sess, x)

    for sid in (0, 1):
        cache.add_seq(sid, 14)
    slots = np.stack([cache.slot_mapping(0, 0, prefix),
                      cache.slot_mapping(1, 0, prefix)])
    plogits, pools = gpt_paged_prefill(
        params, cache.pools, jnp.asarray(x[:, :prefix], jnp.int32),
        jnp.asarray(slots), **kw)
    np.testing.assert_allclose(np.asarray(plogits), full[:, :prefix],
                               rtol=1e-5, atol=1e-5)
    for pos in range(prefix, 14):
        pstep, pools = gpt_paged_step(
            params, pools, jnp.asarray(x[:, pos], jnp.int32),
            jnp.asarray([pos, pos], jnp.int32),
            jnp.asarray(cache.gather_slots([0, 1], pos + 1)),
            jnp.asarray([cache.slot_of(0, pos), cache.slot_of(1, pos)],
                        jnp.int32), **kw)
        np.testing.assert_allclose(np.asarray(pstep), full[:, pos],
                                   rtol=1e-5, atol=1e-5)


def test_engine_greedy_matches_full_forward_chain():
    """The engine's continuous-batched ragged decode produces EXACTLY
    the argmax chain of repeated full-sequence forwards for every
    request — neighbors in the running batch never perturb a sequence
    (isolation through the block tables)."""
    cfg, sess = gpt_session(seed=1)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, VOCAB, (int(rng.randint(2, 10)),))
               for _ in range(6)]
    gens = [int(g) for g in rng.randint(1, 7, 6)]
    want = [greedy_chain(sess, p, g) for p, g in zip(prompts, gens)]

    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        start=False)
    futs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    _drive(eng, futs)
    for w, f in zip(want, futs):
        np.testing.assert_array_equal(w, f.result(1))
    assert eng.cache.used_blocks == 0, "finished sequences leaked blocks"
    eng.close()


def test_engine_ragged_prompts_match_exact_and_count_padding():
    """Prompts of lengths 5, 7, 12 — none a prompt bucket — prefill
    padded to 8, 8, 16; the padded K/V rows land in scratch or are
    overwritten before they become attendable, so every output equals
    the exact-length argmax chain. ``engine_prefill_tokens`` counts the
    24 REAL prompt tokens and ``engine_prefill_pad_tokens`` the bucket
    padding apart, so prefill throughput is not stamped from padding."""
    tel = _tel()
    cfg, sess = gpt_session(seed=4)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, (p,)) for p in (5, 7, 12)]
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        telemetry=tel, start=False)
    futs = [eng.submit(p, 4) for p in prompts]
    _drive(eng, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(greedy_chain(sess, p, 4),
                                      f.result(1))
    assert tel.counter_value("engine_prefill_tokens") == 5 + 7 + 12
    # one [2, 8] program for the 5 and the 7, one [1, 16] for the 12
    assert tel.counter_value("engine_prefill_pad_tokens") == \
        (2 * 8 - 12) + (16 - 12)
    eng.close()


def test_engine_temperature_sampling_in_vocab_and_seeded():
    """A ``temperature=1.0, seed=3`` request samples inside the
    vocabulary, and the same seed repeats it token for token."""
    cfg, sess = gpt_session(seed=2)
    x = np.random.RandomState(2).randint(0, VOCAB, (4,))
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=30, block_size=4, max_batch_size=4,
        start=False)
    futs = [eng.submit(x, 8, temperature=1.0, seed=3) for _ in range(2)]
    _drive(eng, futs)
    out = futs[0].result(1)
    assert out.shape == (8,)
    assert (out >= 0).all() and (out < VOCAB).all()
    np.testing.assert_array_equal(out, futs[1].result(1))
    eng.close()


def test_engine_from_checkpoint_matches_from_session(tmp_path):
    """``Executor.save`` -> ``from_checkpoint`` serves the same tokens
    as ``from_session`` over the live parameters."""
    cfg, sess = gpt_session(seed=3)
    sess.executor.save(str(tmp_path))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, (n,)) for n in (5, 9)]
    kw = dict(num_blocks=30, block_size=4, max_batch_size=4, start=False)
    outs = []
    for eng in (ContinuousBatchingEngine.from_session(sess, cfg, **kw),
                ContinuousBatchingEngine.from_checkpoint(
                    cfg, str(tmp_path), **kw)):
        futs = [eng.submit(p, 5) for p in prompts]
        _drive(eng, futs)
        outs.append([f.result(1).tolist() for f in futs])
        eng.close()
    assert outs[0] == outs[1]


def test_engine_from_checkpoint_names_missing_parameter(tmp_path):
    cfg, sess = gpt_session(seed=3)
    sess.executor.save(str(tmp_path))
    (tmp_path / "gpt_h1_mlp_fc_bias.npy").unlink()
    with pytest.raises(FileNotFoundError, match="gpt_h1_mlp_fc_bias"):
        ContinuousBatchingEngine.from_checkpoint(
            cfg, str(tmp_path), num_blocks=8, block_size=4, start=False)


# ---------------------------------------------------------------------------
# HT901: bounded compiles under churn
# ---------------------------------------------------------------------------

def test_engine_compile_bound_under_churny_trace():
    """Sequences join and leave every step (the iteration-level whole
    point) yet jit_compiles stays within the ladder-product bound — and
    a SECOND churn wave adds ZERO compiles (steady state)."""
    tel = _tel()
    cfg, sess = gpt_session(seed=2)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=40, block_size=4, max_batch_size=4,
        telemetry=tel, start=False)
    rng = np.random.RandomState(3)
    trace = [(rng.randint(0, VOCAB, (int(rng.randint(1, 12)),)),
              int(rng.randint(1, 8))) for _ in range(10)]

    def churn_wave():
        futs = []
        for p, g in trace:      # staggered arrivals: admit mid-flight
            futs.append(eng.submit(p, g))
            eng.step()
        _drive(eng, futs)
        return futs

    c0 = tel.counter_value("jit_compiles")
    churn_wave()
    warm = eng.jit_compiles
    assert warm <= eng.compile_bound, \
        f"{warm} compiles past the HT901 bound {eng.compile_bound}"
    # the engine's signature accounting and the telemetry counter agree
    assert tel.counter_value("jit_compiles") - c0 == warm
    # manual stepping makes the trace deterministic: replaying it must
    # reuse every compiled program
    churn_wave()
    assert eng.jit_compiles == warm, \
        "steady-state churn is still compiling new programs"
    eng.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_queue_policy_serves_everything():
    """A pool far smaller than the offered load: queue admission holds
    the FIFO head until blocks free, and every request completes."""
    cfg, sess = gpt_session(seed=3)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=6, block_size=4, max_batch_size=4,
        start=False)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, (5,)) for _ in range(6)]
    futs = [eng.submit(p, 4) for p in prompts]
    _drive(eng, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(greedy_chain(sess, p, 4),
                                      f.result(1))
    eng.close()


def test_admission_reject_policy_sheds_load():
    cfg, sess = gpt_session(seed=4)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=6, block_size=4, max_batch_size=4,
        admission="reject", start=False)
    rng = np.random.RandomState(5)
    futs = [eng.submit(rng.randint(0, VOCAB, (5,)), 4)
            for _ in range(6)]
    _drive(eng, futs)
    outcomes = []
    for f in futs:
        try:
            out = f.result(1)
            assert out.shape == (4,)
            outcomes.append("ok")
        except EngineOverloaded:
            outcomes.append("shed")
    assert "ok" in outcomes, "reject mode served nothing"
    assert "shed" in outcomes, \
        "reject mode never shed despite a 6-block pool"
    eng.close()


def test_submit_rejects_request_that_can_never_fit():
    cfg, sess = gpt_session(seed=5)
    eng = ContinuousBatchingEngine.from_session(
        sess, cfg, num_blocks=2, block_size=4, max_batch_size=2,
        start=False)
    with pytest.raises(KVCacheExhausted):
        eng.submit(np.zeros(5, np.int32), 10)   # 15 tokens > 8 slots
    with pytest.raises(EngineOverloaded):
        eng2 = ContinuousBatchingEngine.from_session(
            sess, cfg, num_blocks=8, block_size=4, max_batch_size=2,
            max_queue=1, start=False)
        eng2.submit(np.zeros(2, np.int32), 2)
        eng2.submit(np.zeros(2, np.int32), 2)   # queue full
    eng.close()
    eng2.close()


def test_lazy_reserve_preempts_and_still_reproduces():
    """reserve='lazy' under a pool too small for everyone to grow:
    preemption requeues the youngest sequence, and (seed, index)-keyed
    sampling makes its recompute reproduce the same tokens — outputs
    equal the full-reserve engine's exactly."""
    cfg, sess = gpt_session(seed=6)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, (5,)) for _ in range(4)]

    def serve(**kw):
        eng = ContinuousBatchingEngine.from_session(
            sess, cfg, block_size=4, max_batch_size=4, start=False, **kw)
        futs = [eng.submit(p, 6, temperature=0.8, seed=40 + i)
                for i, p in enumerate(prompts)]
        _drive(eng, futs)
        outs = [f.result(1) for f in futs]
        assert eng.cache.used_blocks == 0
        eng.close()
        return outs, eng

    want, _ = serve(num_blocks=40, reserve="full")
    tel = _tel()
    got, eng = serve(num_blocks=7, reserve="lazy", telemetry=tel)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert tel.counter_value("engine_preemptions") > 0, \
        "7-block lazy pool never preempted — the test lost its point"


# ---------------------------------------------------------------------------
# replica router
# ---------------------------------------------------------------------------

class _FakeReplica:
    def __init__(self, fail=False):
        self.fail = fail
        self.calls = 0

    def submit(self, prompt, max_new):
        from concurrent.futures import Future
        self.calls += 1
        f = Future()
        if self.fail:
            f.set_exception(RuntimeError("replica down"))
        else:
            f.set_result(np.zeros(max_new, np.int32))
        return f


def test_router_least_inflight_and_load_shedding():
    r1, r2 = _FakeReplica(), _FakeReplica(fail=True)
    router = ReplicaRouter([r1, r2], slo_error_rate=0.2, slo_window=8)
    # errors from the failing replica drive its window over the SLO;
    # afterwards every request routes to the healthy one
    for _ in range(10):
        try:
            router.submit(np.zeros(2, np.int32), 2).result(1)
        except RuntimeError:
            pass
    before = r2.calls
    for _ in range(6):
        router.submit(np.zeros(2, np.int32), 2).result(1)
    assert r2.calls == before, "router kept routing to a breached replica"
    assert router.health()[0]           # one healthy replica: healthy
    # every replica breached -> load shedding, not queueing
    router2 = ReplicaRouter([_FakeReplica(fail=True)],
                            slo_error_rate=0.1, slo_window=4)
    for _ in range(6):
        try:
            router2.submit(np.zeros(2, np.int32), 2).result(1)
        except RuntimeError:
            pass
    with pytest.raises(RouterOverloaded):
        router2.submit(np.zeros(2, np.int32), 2)
    ok, reason = router2.health()
    assert not ok and "error rate" in reason


def test_router_prefers_replica_own_health_probe():
    """A replica exposing health() (the engine, an HTTP frontend) is
    consulted directly — the router sees queue pressure it couldn't
    infer from its own outside window."""
    class _Unhealthy(_FakeReplica):
        def health(self):
            return False, "draining"

    good, draining = _FakeReplica(), _Unhealthy()
    router = ReplicaRouter([draining, good])
    for _ in range(4):
        router.submit(np.zeros(2, np.int32), 2).result(1)
    assert draining.calls == 0 and good.calls == 4


def test_slo_window_semantics_shared_with_http():
    """SLOWindow is the same breach logic ServingHTTPServer.health()
    rides (extracted, not duplicated): no SLO -> always ok; windowed
    p99 past the bound -> breached with the /healthz reason string."""
    w = SLOWindow()
    assert w.health() == (True, "ok")
    w = SLOWindow(p99_ms=10.0)
    assert w.health() == (True, "ok (no traffic)")
    for _ in range(20):
        w.note(True, 50.0)
    ok, reason = w.health()
    assert not ok and "serve_latency_ms p99" in reason
    # the HTTP server now delegates to the same class
    from hetu_tpu.serving.http import ServingHTTPServer
    srv = ServingHTTPServer(object(), slo_p99_ms=10.0)
    assert isinstance(srv._slo, SLOWindow)
    assert srv.health() == (True, "ok (no traffic)")


# ---------------------------------------------------------------------------
# engine smoke (tier-1: background thread end to end, tiny config)
# ---------------------------------------------------------------------------

def test_engine_smoke_background_thread():
    """Fast serving-engine smoke: threaded scheduler, concurrent
    submits, SLO health probe, metrics, clean close (the thread-leak
    gate in conftest watches the join)."""
    tel = _tel()
    cfg, sess = gpt_session(seed=8)
    with ContinuousBatchingEngine.from_session(
            sess, cfg, num_blocks=24, block_size=4, max_batch_size=4,
            telemetry=tel, slo_p99_ms=60_000.0) as eng:
        rng = np.random.RandomState(9)
        futs = [eng.submit(rng.randint(0, VOCAB, (int(rng.randint(2, 8)),)),
                           int(rng.randint(1, 5)))
                for _ in range(6)]
        outs = [f.result(60) for f in futs]
        assert all(o.dtype == np.int32 for o in outs)
        assert eng.health()[0]
        assert tel.counter_value("engine_tokens") == sum(len(o)
                                                        for o in outs)
        assert eng.cache.peak_utilization > 0.0
    # close() failed nothing that had already resolved, and a submit
    # after close refuses instead of hanging
    with pytest.raises(RuntimeError):
        eng.submit(np.zeros(2, np.int32), 1)
