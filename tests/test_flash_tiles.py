"""The flash kernels' tiles: one static rule
(``ops/pallas_attention.py:_block_sizes``), held to what was read on the
chip for every call the benchmark's six cells trace (``flash_cells.py``),
the same in every process, writing no file; and tile sizes must not
change the math.

The flash kernels run in Pallas interpret mode (no TPU on the test
harness)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import cachedir, telemetry as tmod
from hetu_tpu.telemetry.check import check_args
from hetu_tpu import tune
from hetu_tpu.ops import pallas_attention as pk
from hetu_tpu.ops.attention import attention_reference

from flash_cells import CELL_CALLS, call_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tel():
    """Enabled telemetry; restores the process-global default."""
    old_tel = tmod._default
    yield tmod.configure(enabled=True, service="test-flash-tiles")
    tmod._default = old_tel


def _at_tiles(monkeypatch, tiles):
    """Every call of this test resolves to ``tiles``."""
    monkeypatch.setattr(pk, "_block_sizes", lambda *a, **k: tiles)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("call", CELL_CALLS, ids=call_id)
def test_cell_call_resolves_to_the_tiles_read_on_the_chip(call):
    """Every flash call the six cells trace: the rule's tiles are the
    recorded pick (PERF.md section 6, PR 46), divide S and pass the
    grid's guard. That the kernel fits VMEM at them is the compiler's
    to say: ``test_chip_compile.py`` compiles the same calls."""
    bq, bk = pk._block_sizes(call.seq, call.head_dim, call.kind,
                             call.causal, call.has_mask)
    assert (bq, bk) == call.tiles
    assert call.seq % bq == 0 and call.seq % bk == 0
    assert pk._supported(call.seq, call.head_dim, bq, bk)


@pytest.mark.parametrize("s,d,kind,causal,has_mask", [
    (2048, 64, "fwd_lse", False, True),     # BERT-small at S = 2048
    (2048, 64, "bwd", False, True),
    (512, 64, "fwd_lse", False, True),      # BERT at S = 512
    (1024, 64, "bwd", True, True),          # causal beside padding
    (384, 64, "fwd", False, False),         # no power of two
], ids=str)
def test_shapes_no_cell_measured_keep_the_static_default(
        s, d, kind, causal, has_mask):
    """What no cell traces was never read on the chip: it keeps the
    tiles it always had (bq <= 256, bk <= 512)."""
    bq, bk = pk._block_sizes(s, d, kind, causal, has_mask)
    assert (bq, bk) == (pk._largest_tile(s, 256), pk._largest_tile(s, 512))
    assert s % bq == 0 and s % bk == 0


_RESOLVE = """
import json, os, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import numpy as np, jax.numpy as jnp
from hetu_tpu.ops import pallas_attention as pk
from hetu_tpu.tune.autotune import get_table
from flash_cells import CELL_CALLS
rule = [pk._block_sizes(c.seq, c.head_dim, c.kind, c.causal, c.has_mask)
        for c in CELL_CALLS]
q = jnp.asarray(np.random.RandomState(0).randn(1, 1, 512, 8), jnp.float32)
pk.flash_attention(q, q, q, None, 0.25, True, interpret=True)
print(json.dumps({{"rule": rule, "chosen": get_table().chosen("flash"),
                   "cwd": os.listdir(".")}}))
"""


def test_two_fresh_interpreters_resolve_identical_tiles_and_write_no_file(
        tmp_path):
    """Two processes of one tree run the same tiles, and leave nothing
    behind: neither where they ran nor in the checkout's state root."""
    def state():
        return sorted(
            os.path.join(d, f) for d, _, fs in os.walk(cachedir.STATE_ROOT)
            for f in fs)

    before = state()
    script = _RESOLVE.format(repo=REPO, tests=os.path.join(REPO, "tests"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    outs = []
    for name in ("one", "two"):
        cwd = tmp_path / name
        cwd.mkdir()
        run = subprocess.run([sys.executable, "-c", script], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        outs.append(json.loads(run.stdout.splitlines()[-1]))
    assert outs[0] == outs[1]
    assert outs[0]["cwd"] == []
    assert outs[0]["rule"] == [list(c.tiles) for c in CELL_CALLS]
    assert list(outs[0]["chosen"]) == [
        "cpu|flash_fwd_regions|S512|D8|float32|causal|nomask"]
    assert state() == before


# ---------------------------------------------------------------------------
# flash kernel wiring
# ---------------------------------------------------------------------------

def _qkv(s, d=16, b=1, h=1, seed=0):
    rng = np.random.RandomState(seed)

    def mk():
        return jnp.asarray(rng.randn(b, h, s, d) * 0.3, jnp.float32)

    return mk(), mk(), mk()


def _spy_blocks(monkeypatch):
    """Record the (block_q, block_k) every forward jit call used."""
    seen = []
    orig = pk._flash_attention_jit

    def spy(q, k, v, mask, sm_scale, causal, interpret, bq, bk,
            need_lse):
        seen.append((bq, bk))
        return orig(q, k, v, mask, sm_scale, causal, interpret, bq, bk,
                    need_lse)

    monkeypatch.setattr(pk, "_flash_attention_jit", spy)
    return seen


@pytest.mark.parametrize("s", [64, 128])
def test_short_seq_runs_one_tile_pair(monkeypatch, s):
    """Up to S = 128 a head is one (S, S) pair, whatever the call."""
    seen = _spy_blocks(monkeypatch)
    q, k, v = _qkv(s)
    pk.flash_attention(q, k, v, None, sm_scale=0.25, interpret=True)
    pk.flash_attention(q, k, v, None, sm_scale=0.25, causal=True,
                       interpret=True)
    assert seen == [(s, s), (s, s)]


@pytest.mark.parametrize("causal", [False, True])
def test_tuned_vs_static_numerics_s2048(causal):
    """Block sizes must not change the math: (1024, 128) tiles, given
    at the jit layer, vs the rule's through the public entries,
    forward + lse + fused backward, at a long sequence."""
    s, d = 2048, 8
    q, k, v = _qkv(s, d, seed=3)
    rng = np.random.RandomState(5)
    dy = jnp.asarray(rng.randn(*q.shape) * 0.3, jnp.float32)

    o_t, lse_t = pk._flash_attention_jit(q, k, v, None, 0.25, causal,
                                         True, 1024, 128, True)
    g_t = pk._flash_attention_bwd_jit(q, k, v, None, o_t, lse_t, dy, 0.25,
                                      causal, True, 1024, 128)
    assert pk._block_sizes(s, d, "bwd", causal) != (1024, 128)
    o_s, lse_s = pk.flash_attention_with_lse(q, k, v, None,
                                             sm_scale=0.25,
                                             causal=causal,
                                             interpret=True)
    g_s = pk.flash_attention_bwd(q, k, v, None, o_s, lse_s, dy,
                                 sm_scale=0.25, causal=causal,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(o_t), np.asarray(o_s),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_t), np.asarray(lse_s),
                               rtol=2e-5, atol=2e-5)
    for gt, gs, nm in zip(g_t, g_s, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gt), np.asarray(gs), rtol=2e-4, atol=2e-4,
            err_msg=f"d{nm} tile-dependent (causal={causal})")


@pytest.mark.parametrize("causal", [False, True])
def test_block_independence_s128(causal):
    """At S=128 the rule has one answer, so pin block-size
    independence directly at the jit layer: ODD (64, 32) tiles — which
    the rule never picks — against the composed reference, forward and
    backward (the (128, 128) default is covered against the same
    reference by tests/test_attention.py)."""
    s, d = 128, 16
    q, k, v = _qkv(s, d, b=1, h=2, seed=7)
    rng = np.random.RandomState(9)
    dy = jnp.asarray(rng.randn(*q.shape) * 0.3, jnp.float32)
    o, lse = pk._flash_attention_jit(q, k, v, None, 0.25, causal,
                                     True, 64, 32, True)
    grads = pk._flash_attention_bwd_jit(
        q, k, v, None, o, lse, dy, 0.25, causal, True, 64, 32)
    cm = None
    if causal:
        cm = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                       -1e30)[None, None]

    def f(q_, k_, v_):
        return attention_reference(q_, k_, v_, cm, 0.25)

    ref, vjp = jax.vjp(f, q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for g, w in zip(grads, vjp(dy)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def _backward_at(s, d, causal=True):
    q, k, v = _qkv(s, d, seed=3)
    dy = jnp.asarray(np.random.RandomState(5).randn(*q.shape) * 0.3,
                     jnp.float32)
    o, lse = pk._flash_attention_jit(q, k, v, None, 0.25, causal, True,
                                     128, 128, True)
    return pk.flash_attention_bwd(q, k, v, None, o, lse, dy,
                                  sm_scale=0.25, causal=causal,
                                  interpret=True)


@pytest.mark.parametrize("kind", ["fwd", "fwd_lse"])
def test_forward_records_its_walk_at_trace_time(tel, monkeypatch, kind):
    """A traced forward call records the walk at the tiles it runs
    with as one ``flash_fwd_walk`` instant (schema-checked)."""
    s, d = 512, 8
    _at_tiles(monkeypatch, (128, 256))
    entry = pk.flash_attention if kind == "fwd" \
        else pk.flash_attention_with_lse
    q, k, v = _qkv(s, d, seed=3)
    entry(q, k, v, None, sm_scale=0.25, causal=True, interpret=True)
    events = [e["args"] for e in tel.tracer.drain(clear=True)
              if e.get("name") == "flash_fwd_walk"]
    assert len(events) == 1 and check_args("flash_fwd_walk",
                                           events[0]) == []
    got = events[0]
    assert got == {"seq": s, "head_dim": d, "block_q": 128,
                   "block_k": 256, "causal": True,
                   "heads_per_program": 1, "chains": 4,
                   **pk.tile_walk_counts(s, 128, 256, True)}
    assert (got["tiles_visited"], got["tiles_masked"]) == (6, 4)


@pytest.mark.parametrize("tiles,visited,masked", [
    ((128, 128), 10 / 16, 4 / 10), ((256, 256), 3 / 4, 2 / 3),
    ((512, 128), 1.0, 1.0)], ids=["128x128", "256x256", "512x128"])
def test_backward_records_its_walk_at_trace_time(tel, monkeypatch, tiles,
                                                 visited, masked):
    """Beside the tiles, the share of the square the walk visits and
    the share of visited tiles that carry the mask, as one
    ``flash_bwd_walk`` instant a traced call (schema-checked)."""
    s, d = 512, 8
    _at_tiles(monkeypatch, tiles)
    _backward_at(s, d)
    events = [e for e in tel.tracer.drain()
              if e.get("name") == "flash_bwd_walk"]
    assert len(events) == 1
    args = events[0]["args"]
    assert (args["block_q"], args["block_k"]) == tiles
    assert args["visited_share"] == pytest.approx(visited, abs=1e-4)
    assert args["masked_share"] == pytest.approx(masked, abs=1e-4)
    assert args["tiles_square"] == (s // tiles[0]) * (s // tiles[1])
    assert check_args("flash_bwd_walk", args) == []
    _backward_at(s, d, causal=False)
    full = [e for e in tel.tracer.drain()
            if e.get("name") == "flash_bwd_walk"][-1]["args"]
    assert full["visited_share"] == 1.0 and full["tiles_masked"] == 0


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("blocks", [None, (128, 128)],
                         ids=["rule", "given"])
def test_probe_and_attribution(tel, blocks):
    pr = tune.probe_attention(1, 2, 256, 8, dtype="float32",
                              causal=False, has_mask=True,
                              interpret=True, reps=1, blocks=blocks)
    for f in ("fwd_ms", "fwd_lse_ms", "bwd_ms"):
        assert pr[f] > 0.0
    want = {kind: list(blocks or pk._block_sizes(256, 8, kind, False,
                                                 True))
            for kind in ("fwd", "fwd_lse", "bwd")}
    assert pr["blocks"] == want
    # the backward's walk at its tiles: the full square without causal
    assert pr["bwd_walk"]["visited_share"] == 1.0
    assert pr["bwd_walk"]["tiles_square"] == \
        (256 // pr["blocks"]["bwd"][0]) * (256 // pr["blocks"]["bwd"][1])
    # the forward's at ITS tiles, and what a program holds
    bq, bk = pr["blocks"]["fwd_lse"]
    assert pr["fwd_walk"] == pk.fwd_walk_counts(2, 256, bq, bk, False)
    att = tune.attribute_step(100.0, 4, pr["fwd_lse_ms"], pr["bwd_ms"])
    # fields are independently rounded to 3 decimals — compare at 2x
    # that granularity
    assert att["attn_fwd_ms"] == pytest.approx(4 * pr["fwd_lse_ms"],
                                               abs=2e-3)
    assert att["xla_remainder_ms"] == pytest.approx(
        100.0 - att["attn_fwd_ms"] - att["attn_bwd_ms"], abs=2e-3)
    # the probe's kernel timings land in the trace as attn_probe spans
    names = [e.get("name") for e in tel.tracer.drain()]
    assert "attn_probe" in names
