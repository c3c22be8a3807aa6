"""Training health monitor (hetu_tpu/telemetry/health.py): device-side
sentinels fused into the jitted step, cadence sampling, the trip ladder
(warn/dump/raise), staleness + hot-key + table telemetry, the
divergence-doctor CLI, the blackbox integration, the
overhead contract, and the 2-rank injected-NaN acceptance run."""
import gc
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.telemetry import Telemetry, check, health
from hetu_tpu.telemetry.health import (HealthError, HealthMonitor,
                                       HealthOptions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_global_telemetry():
    import hetu_tpu.telemetry as tmod
    yield
    tmod._default = None
    health._LAST = None


def _mlp(prefix):
    x = ht.Variable(f"{prefix}_x", trainable=False)
    y_ = ht.Variable(f"{prefix}_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name=f"{prefix}_w1")
    w2 = ht.init.xavier_normal((12, 4), name=f"{prefix}_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return x, y_, loss, train


def _feeds(rng, n=8):
    xs = rng.randn(n, 16).astype("f")
    ys = np.eye(4, dtype="f")[rng.randint(0, 4, n)]
    return xs, ys


# ---------------------------------------------------------------------------
# options resolution
# ---------------------------------------------------------------------------

def test_options_resolve_forms(monkeypatch):
    monkeypatch.delenv("HETU_HEALTH", raising=False)
    assert not HealthOptions.resolve(None).enabled
    assert not HealthOptions.resolve(False).enabled
    assert HealthOptions.resolve(True).enabled
    o = HealthOptions.resolve({"every_n": 3, "action": "raise"})
    assert o.enabled and o.every_n == 3 and o.action == "raise"
    o = HealthOptions.resolve("every_n=5,action=dump,spike_factor=8.5")
    assert o.enabled and o.every_n == 5 and o.action == "dump"
    assert o.spike_factor == 8.5
    monkeypatch.setenv("HETU_HEALTH", "every_n=7")
    assert HealthOptions.resolve(None).every_n == 7
    monkeypatch.setenv("HETU_HEALTH", "0")
    assert not HealthOptions.resolve(None).enabled
    with pytest.raises(ValueError):
        HealthOptions.resolve({"action": "explode"})
    with pytest.raises(ValueError):
        HealthOptions.resolve({"bogus_knob": 1})


# ---------------------------------------------------------------------------
# sentinels + cadence (plain run path)
# ---------------------------------------------------------------------------

def test_sentinels_sampled_at_cadence(tmp_path):
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hc")
    exe = Executor([loss, train], health_options={
        "every_n": 5, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    assert hm is not None
    xs, ys = _feeds(rng)
    for _ in range(12):
        exe.run(feed_dict={x: xs, y_: ys})
    assert [r["step"] for r in hm.records] == [5, 10]
    rec = hm.records[0]
    assert set(rec["layers"]) == {"hc_w1", "hc_w2"}
    for m in rec["layers"].values():
        assert m["grad_norm"] > 0 and m["nonfinite"] == 0
        assert m["update_ratio"] > 0
    assert rec["loss_finite"] and rec["loss"] > 0
    assert rec["loss_name"]          # the scalar eval output's name
    assert rec["lr"] == pytest.approx(0.1)
    assert rec["grad_norm_total"] == pytest.approx(
        float(np.sqrt(sum(m["grad_norm"] ** 2
                          for m in rec["layers"].values()))), rel=1e-5)
    assert not rec["trips"]
    # the JSONL landed, one line per sampled record
    lines = [json.loads(ln) for ln in
             (tmp_path / "health_rank0.jsonl").read_text().splitlines()]
    assert [r["step"] for r in lines] == [5, 10]
    exe.close()


def test_nan_trip_names_step_and_layer_and_dumps(tmp_path):
    """NaN injected at step 3 trips at the next sampled step (4, with
    every_n=2), names a layer, dumps the flight ring + last-good
    record, and the doctor reproduces first-bad-step from the JSONL."""
    tel = Telemetry(enabled=True, out_dir=str(tmp_path), rank=0)
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hn")
    exe = Executor([loss, train], telemetry=tel, health_options={
        "every_n": 2, "action": "dump"})
    hm = exe.config.health_monitor
    xs, ys = _feeds(rng)
    for step in range(1, 7):
        xv = xs.copy()
        if step == 3:
            xv[0, 0] = np.nan       # poisons params from step 3 on
        exe.run(feed_dict={x: xv, y_: ys})
    trip_recs = [r for r in hm.records if r["trips"]]
    assert trip_recs and trip_recs[0]["step"] == 4   # within every_n
    kinds = {t["kind"] for t in trip_recs[0]["trips"]}
    assert kinds == {"nonfinite"}
    named = [t["layer"] for t in trip_recs[0]["trips"] if t["layer"]]
    assert named and named[0] in ("hn_w1", "hn_w2")
    assert not trip_recs[0]["loss_finite"]
    # dump rung artifacts: flight ring with the health reason + the
    # last-good record (step 2, the sample before the poison)
    dump = json.loads((tmp_path / "flight_rank0.json").read_text())
    assert dump["reason"].startswith("health trip: nonfinite")
    lastgood = json.loads(
        (tmp_path / "health_lastgood_rank0.json").read_text())
    assert lastgood["step"] == 2 and not lastgood["trips"]
    exe.close()
    # doctor: same first-bad-step from the merged JSONL
    rep = health.diagnose(str(tmp_path))
    assert rep["first_bad_step"] == 4
    assert rep["layer"] == named[0]
    assert not rep["healthy"] and not rep["loss_finite"]
    assert any(c["cause"] == "data_anomaly"
               for c in rep["probable_causes"])


def test_action_raise_raises_health_error(tmp_path):
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hr")
    exe = Executor([loss, train], health_options={
        "every_n": 1, "action": "raise", "out_dir": str(tmp_path)})
    xs, ys = _feeds(rng)
    xs[0, 0] = np.inf
    with pytest.raises(HealthError, match="nonfinite"):
        exe.run(feed_dict={x: xs, y_: ys})
    # the record (with its trips) still reached the JSONL before raise
    lines = (tmp_path / "health_rank0.jsonl").read_text().splitlines()
    assert json.loads(lines[-1])["trips"]


def test_grad_spike_trip_vs_baseline(tmp_path):
    """A sudden grad explosion (loss scale jump) trips grad_spike
    against the running EMA baseline and names the worst layer."""
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hs")
    exe = Executor([loss, train], health_options={
        "every_n": 1, "spike_factor": 50.0, "warmup": 3,
        "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    xs, ys = _feeds(rng)
    for _ in range(5):
        exe.run(feed_dict={x: xs, y_: ys})
    assert not hm.trips
    exe.run(feed_dict={x: xs * 1e4, y_: ys})    # grads blow up, finite
    spikes = [t for t in hm.trips if t["kind"] == "grad_spike"]
    assert spikes, hm.records[-1]
    assert spikes[0]["layer"] in ("hs_w1", "hs_w2")
    assert spikes[0]["value"] > spikes[0]["limit"]


# ---------------------------------------------------------------------------
# block (lax.scan) path
# ---------------------------------------------------------------------------

def test_block_path_samples_inside_block(tmp_path):
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hb")
    exe = Executor([loss, train], health_options={
        "every_n": 3, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    blocks = []
    for _ in range(8):
        xs, ys = _feeds(rng)
        blocks.append({x: xs, y_: ys})
    exe.run_batches(blocks)
    assert [r["step"] for r in hm.records] == [3, 6]
    for rec in hm.records:
        assert rec["loss_finite"] and rec["layers"]["hb_w1"][
            "grad_norm"] > 0


def test_block_nan_trip(tmp_path):
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hbn")
    exe = Executor([loss, train], health_options={
        "every_n": 2, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    blocks = []
    for k in range(6):
        xs, ys = _feeds(rng)
        if k == 2:                  # step 3 of the block
            xs[0, 0] = np.nan
        blocks.append({x: xs, y_: ys})
    exe.run_batches(blocks)
    trip_recs = [r for r in hm.records if r["trips"]]
    assert trip_recs and trip_recs[0]["step"] == 4


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------

def test_disabled_path_zero_allocations():
    """No live monitor: the sparse-side hooks (the only health code on
    the disabled hot path beyond `health_monitor is None` checks) are
    one falsy check — zero net allocations."""
    gc.collect()                    # drop any dead monitors first
    assert not health.active()
    upds = np.array([1, 2, 3], np.int64)
    for _ in range(200):            # warm caches
        health.observe_staleness("push", 1, upds, 4)
        health.active()
    gc.collect()
    gc.disable()
    try:
        before = sys.getallocatedblocks()
        for _ in range(5000):
            health.observe_staleness("push", 1, upds, 4)
            health.active()
        after = sys.getallocatedblocks()
    finally:
        gc.enable()
    assert after - before <= 8, \
        f"disabled health hooks leaked {after - before} blocks"


def test_disabled_executor_has_no_monitor(monkeypatch):
    monkeypatch.delenv("HETU_HEALTH", raising=False)
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hd")
    exe = Executor([loss, train])
    assert exe.config.health_monitor is None
    xs, ys = _feeds(rng)
    exe.run(feed_dict={x: xs, y_: ys})
    sub = exe.subexecutors["default"]
    assert getattr(sub, "_last_health", None) is None


def test_overhead_guard_under_2pct_at_every_n_10(tmp_path):
    """The monitor's host cost at every_n=10, amortized per step, stays
    under 2% of the step. Bounded by counts and best-of-repeats, not by
    one micro-timing over one loaded step: the monitor samples exactly
    at its cadence (2 records in 23 steps), and one sample's
    fetch+check, timed by the monitor itself on sentinels that are
    already computed (inside the loop its ``device_get`` also waits for
    the step it rides on, which is the step's time, not the
    monitor's), best of several repeats, over the cadence, against the
    best of the step timings. The device-side sentinel reductions ride
    inside the compiled step (a handful of scalar reductions against a
    3072x1024 matmul)."""
    import jax
    rng = np.random.RandomState(0)
    x = ht.Variable("ho_x", trainable=False)
    y_ = ht.Variable("ho_y", trainable=False)
    w1 = ht.init.xavier_normal((3072, 1024), name="ho_w1")
    w2 = ht.init.xavier_normal((1024, 10), name="ho_w2")
    hid = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(hid, w2), y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    exe = Executor([loss, train], health_options={
        "every_n": 10, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    feeds = {x: rng.randn(128, 3072).astype("f"),
             y_: np.eye(10, dtype="f")[rng.randint(0, 10, 128)]}
    for _ in range(3):
        exe.run(feed_dict=feeds)
    times = []
    for _ in range(20):
        t0 = time.perf_counter()
        out = exe.run(feed_dict=feeds)
        out[0].asnumpy()
        times.append(time.perf_counter() - t0)
    step_ms = min(times) * 1000
    assert len(hm.records) == 2, "23 steps at every_n=10 sample twice"

    sub = exe.subexecutors["default"]
    jax.block_until_ready(sub._last_health)
    sample_ms = []
    for _ in range(5):
        sub.step_count = 30             # a cadence step: after_step samples
        before = hm.sample_wall_ms
        hm.after_step(sub)
        sample_ms.append(hm.sample_wall_ms - before)
    sub.step_count = 23
    assert len(hm.records) == 7
    per_step_ms = min(sample_ms) / 10.0
    assert per_step_ms < 0.02 * step_ms, (sample_ms, step_ms)


# ---------------------------------------------------------------------------
# staleness / hot-key / table telemetry
# ---------------------------------------------------------------------------

def test_staleness_observation_and_push_trip(tmp_path):
    """Push-side staleness past the bound (a drain that claimed more
    per-row updates than push_bound) is a violation and trips; pull-
    side refresh deltas are histogram-only (the protocol enforcing the
    bound is not a violation)."""
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hst")
    exe = Executor([loss, train], health_options={
        "every_n": 1, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    health.observe_staleness("pull", 7, np.array([6, 9]), 4)
    health.observe_staleness("push", 7, np.array([3, 9]), 4)
    xs, ys = _feeds(rng)
    exe.run(feed_dict={x: xs, y_: ys})
    rec = hm.records[-1]
    st = rec["staleness"]
    assert st["pull:7"]["max"] == 9 and st["pull:7"]["violations"] == 0
    assert st["push:7"]["violations"] == 1
    assert st["push:7"]["bound"] == 4.0
    trips = [t for t in rec["trips"] if t["kind"] == "staleness"]
    assert trips and trips[0]["table"] == "7"
    assert trips[0]["value"] == 9 and trips[0]["limit"] == 4.0
    exe.close()


def test_device_cache_take_dirty_feeds_staleness(tmp_path):
    """DeviceCacheTable.take_dirty routes per-row update counts into
    the live monitor (kind=push, bound=push_bound)."""
    from hetu_tpu.ps.device_cache import DeviceCacheTable

    class _Tbl:
        id = 42
        name = "t42"

    class _Cache:
        id = 43

    hm = HealthMonitor(HealthOptions(enabled=True,
                                     out_dir=str(tmp_path)))
    try:
        rt = DeviceCacheTable(_Tbl(), _Cache(), client=None, capacity=8,
                              width=4, rows=16, push_bound=2,
                              pull_bound=2, nworkers=1)
        slots, miss_ids, new_slots, uniq = rt.assign(
            np.array([1, 2, 3]), lambda: None)
        for _ in range(3):                       # 3 updates > bound 2
            rt.note_update(uniq)
        rt.take_dirty()
        key = ("push", 42)
        assert key in hm._stale
        assert hm._stale[key]["max"] == 3
        assert hm._stale[key]["violations"] == 3  # all rows past bound
    finally:
        hm.close()


def test_scoped_staleness_does_not_cross_attribute(tmp_path):
    """An observation carrying its owning monitor (the PS runtime
    stamps it onto registered cache objects) lands ONLY there — two
    executors in one process never cross-attribute staleness."""
    hm_a = HealthMonitor(HealthOptions(enabled=True,
                                       out_dir=str(tmp_path / "a")))
    hm_b = HealthMonitor(HealthOptions(enabled=True,
                                       out_dir=str(tmp_path / "b")))
    try:
        health.observe_staleness("push", 11, np.array([9]), 4,
                                 monitor=hm_a)
        assert ("push", 11) in hm_a._stale
        assert ("push", 11) not in hm_b._stale
        # unscoped fallback (bare cache objects) still broadcasts
        health.observe_staleness("push", 12, np.array([1]), 4)
        assert ("push", 12) in hm_a._stale and ("push", 12) in hm_b._stale
    finally:
        hm_a.close()
        hm_b.close()


def test_jsonl_truncates_across_processes_appends_within(tmp_path):
    """First open of health_rank<r>.jsonl in a process truncates (a
    rerun reusing a telemetry dir must not merge two runs in the
    doctor); later monitors in the SAME process append."""
    stale = tmp_path / "health_rank0.jsonl"
    stale.write_text(json.dumps(_rec(99, 0)) + "\n")   # "previous run"
    health._OPENED_PATHS.discard(str(stale))           # fresh process
    hm = HealthMonitor(HealthOptions(enabled=True,
                                     out_dir=str(tmp_path)))
    hm._write(_rec(5, 0))
    hm.close()
    hm2 = HealthMonitor(HealthOptions(enabled=True,
                                      out_dir=str(tmp_path)))
    hm2._write(_rec(10, 0))
    hm2.close()
    steps = [json.loads(ln)["step"]
             for ln in stale.read_text().splitlines()]
    assert steps == [5, 10]        # stale run gone, same-process kept


def test_hot_key_skew_in_record(tmp_path):
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hk")
    exe = Executor([loss, train], health_options={
        "every_n": 1, "out_dir": str(tmp_path)})
    hm = exe.config.health_monitor
    ids = np.concatenate([np.zeros(90, np.int64),
                          np.arange(1, 11, dtype=np.int64)])
    hm.observe_ids(5, ids)
    xs, ys = _feeds(rng)
    exe.run(feed_dict={x: xs, y_: ys})
    hot = hm.records[-1]["hot_keys"]["5"]
    assert hot["n"] == 100 and hot["unique"] == 11
    assert hot["top1_share"] == pytest.approx(0.9)
    # drained per sample: the next record starts a fresh window
    exe.run(feed_dict={x: xs, y_: ys})
    assert "hot_keys" not in hm.records[-1]
    exe.close()


def test_table_sampling_with_stub_runtime(tmp_path):
    """Row-norm / dead-row stats from a (stubbed) server sample: half
    the sampled rows are zero -> dead_frac 0.5."""

    class _Client:
        def sparse_pull(self, tid, ids, width):
            rows = np.ones((len(ids), width), np.float32)
            rows[::2] = 0.0
            return rows

    class _RT:
        tid, rows, width = 9, 128, 8

    class _Config:
        ps_nodes = ()

    class _Runtime:
        device_tables = {9: _RT()}
        client = _Client()
        config = _Config()

    hm = HealthMonitor(HealthOptions(enabled=True, table_sample=32,
                                     out_dir=str(tmp_path)))
    try:
        out = hm.sample_tables(_Runtime(), step=10)
        assert out["9"]["rows_sampled"] == 32
        assert out["9"]["dead_frac"] == 0.5
        assert out["9"]["row_norm_max"] == pytest.approx(np.sqrt(8),
                                                         abs=1e-3)
    finally:
        hm.close()


def test_cstable_shadow_staleness(tmp_path):
    """The host-cache shadow counts pending updates per key and reports
    them (kind=cstable, histogram-only) at lookup."""
    from hetu_tpu.ps import client as ps_client
    from hetu_tpu.ps import server as ps_server
    try:
        from hetu_tpu.cstable import CacheSparseTable
        port = ps_server.pick_free_port()
        ps_server.ensure_server(port=port, nworkers=1)
        client = ps_client.PSClient(hosts="127.0.0.1", ports=str(port),
                                    rank=0, nworkers=1)
    except Exception as e:          # noqa: BLE001 — native lib missing
        pytest.skip(f"native PS unavailable: {e}")
    hm = HealthMonitor(HealthOptions(enabled=True,
                                     out_dir=str(tmp_path)))
    try:
        client.init_tensor(990, (64, 4), kind=2, opt="SGD", lrs=[1.0])
        client.set_param(990, np.zeros((64, 4), np.float32))
        tbl = CacheSparseTable(990, 64, 4, limit=16, policy="LRU",
                               pull_bound=100, push_bound=100)
        tbl.embedding_lookup(np.array([1, 2], np.int64))  # fill rows
        keys = np.array([1, 2, 1], np.int64)
        tbl.embedding_update(keys, np.ones((3, 4), np.float32))
        assert tbl._upd_pending == {1: 2, 2: 1}
        tbl.embedding_lookup(np.array([1, 2], np.int64))
        key = ("cstable", 990)
        assert key in hm._stale and hm._stale[key]["max"] == 2
        assert hm._stale[key]["violations"] == 0    # never a trip
        tbl.flush()
        assert not tbl._upd_pending
        del tbl
    finally:
        hm.close()
        client.shutdown_servers()
        client.close()
        ps_server.shutdown_server()


# ---------------------------------------------------------------------------
# divergence doctor
# ---------------------------------------------------------------------------

def _write_jsonl(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _rec(step, rank, loss=1.0, loss_finite=True, lr=0.1, gn=1.0,
         trips=(), layers=None):
    return {"step": step, "rank": rank, "t": 0.0, "loss": loss,
            "loss_finite": loss_finite, "grad_norm_total": gn,
            "lr": lr, "layers": layers or
            {"w": {"grad_norm": gn, "nonfinite": 0,
                   "update_ratio": 0.01}},
            "trips": list(trips)}


def test_doctor_rank_divergence_cause(tmp_path):
    """Only rank 1 trips at step 10 -> first_bad_step 10, cause
    rank_divergence ranked."""
    _write_jsonl(tmp_path / "health_rank0.jsonl",
                 [_rec(5, 0), _rec(10, 0, gn=1.1)])
    bad = _rec(10, 1, loss=None, loss_finite=False, gn=None,
               trips=[{"kind": "nonfinite", "layer": "w",
                       "value": 3.0, "limit": 0}],
               layers={"w": {"grad_norm": None, "nonfinite": 3,
                             "update_ratio": None}})
    _write_jsonl(tmp_path / "health_rank1.jsonl", [_rec(5, 1), bad])
    rep = health.diagnose(str(tmp_path))
    assert rep["first_bad_step"] == 10 and rep["bad_rank"] == 1
    assert rep["bad_ranks"] == [1]
    assert rep["layer"] == "w" and not rep["loss_finite"]
    causes = {c["cause"]: c for c in rep["probable_causes"]}
    assert "rank_divergence" in causes
    assert rep["trip_kinds"] == ["nonfinite"]


def test_doctor_staleness_cause_ranked_first(tmp_path):
    stale_trip = {"kind": "staleness", "table": "7", "value": 9,
                  "limit": 4}
    recs = [_rec(5, 0),
            _rec(10, 0, trips=[stale_trip]),
            _rec(15, 0, loss=None, loss_finite=False, gn=None,
                 trips=[{"kind": "nonfinite", "layer": "w",
                         "value": 1, "limit": 0}])]
    _write_jsonl(tmp_path / "health_rank0.jsonl", recs)
    rep = health.diagnose(str(tmp_path))
    assert rep["first_bad_step"] == 10
    causes = rep["probable_causes"]
    assert causes and causes[0]["cause"] == "staleness_violation"


def test_doctor_lr_spike_cause(tmp_path):
    recs = [_rec(2, 0, lr=0.1), _rec(4, 0, lr=0.1),
            _rec(6, 0, lr=0.1),
            _rec(8, 0, lr=5.0, loss=None, loss_finite=False, gn=None,
                 trips=[{"kind": "nonfinite", "layer": "w",
                         "value": 1, "limit": 0}])]
    _write_jsonl(tmp_path / "health_rank0.jsonl", recs)
    rep = health.diagnose(str(tmp_path))
    causes = {c["cause"] for c in rep["probable_causes"]}
    assert "lr_spike" in causes


def test_doctor_healthy_run_and_cli(tmp_path):
    _write_jsonl(tmp_path / "health_rank0.jsonl",
                 [_rec(5, 0), _rec(10, 0)])
    rep = health.diagnose(str(tmp_path))
    assert rep["healthy"] and rep["loss_finite"]
    assert rep["first_bad_step"] is None
    assert health.main([str(tmp_path)]) == 0
    assert health.main([str(tmp_path), "--json"]) == 0
    assert health.main([str(tmp_path / "empty")]) == 2
    text = health.format_report(rep)
    assert "HEALTHY" in text


# ---------------------------------------------------------------------------
# span-attr schema (check.py satellite): producer fixture + drift case
# ---------------------------------------------------------------------------

def test_health_spans_validate_against_schema(tmp_path):
    """The monitor's real trace output — the producer fixture for the
    health/health_trip schema entries — passes the drift gate."""
    tel = Telemetry(enabled=True, out_dir=str(tmp_path / "tel"), rank=0)
    rng = np.random.RandomState(0)
    x, y_, loss, train = _mlp("hv")
    exe = Executor([loss, train], telemetry=tel, health_options={
        "every_n": 2})
    xs, ys = _feeds(rng)
    xs[0, 0] = np.nan
    for _ in range(2):
        exe.run(feed_dict={x: xs, y_: ys})
    paths = tel.flush()
    trace = paths[0]
    doc = json.load(open(trace))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "health" in names and "health_trip" in names
    n, errors = check.validate(trace)
    assert not errors, errors
    exe.close()


@pytest.mark.parametrize("name,args,match", [
    ("health", {"layers": 2}, "missing"),            # step required
    ("health", {"step": 2, "novel": 1}, "unknown attr"),
    ("health_trip", {"step": 2}, "kind"),            # kind required
    ("health_trip", {"step": 2, "kind": "nonfinite", "layer": 3},
     "layer"),                                       # wrong type
])
def test_health_schema_drift_rejected(tmp_path, name, args, match):
    from hetu_tpu.telemetry import Tracer
    tr = Tracer(pid=0)
    t = tr.clock()
    tr.complete(name, t, t + 1000, args)
    path = tr.export(str(tmp_path / "trace_rank0.json"))
    _, errors = check.validate(path)
    assert errors and any(match in e for e in errors), (errors, match)


# ---------------------------------------------------------------------------
# blackbox integration
# ---------------------------------------------------------------------------

def test_blackbox_ingests_health_records(tmp_path):
    from hetu_tpu.telemetry import blackbox
    (tmp_path / "flight_rank0.json").write_text(json.dumps(
        {"rank": 0, "pid": 1, "nprocs": 1, "reason": "flush",
         "last_step": 12, "steps": [], "events": []}))
    _write_jsonl(tmp_path / "health_rank0.jsonl",
                 [_rec(5, 0),
                  _rec(10, 0, loss=None, loss_finite=False, gn=None,
                       trips=[{"kind": "nonfinite", "layer": "w",
                               "value": 2, "limit": 0}])])
    rep = blackbox.analyze(str(tmp_path))
    assert rep["health"]["first_bad_step"] == 10
    assert rep["health"]["layer"] == "w"
    # no dead/diverged ranks -> the health-tripped rank is the suspect
    assert rep["suspect_ranks"] == [0]
    text = blackbox.format_report(rep)
    assert "HEALTH: first bad step 10" in text


# ---------------------------------------------------------------------------
# acceptance: 2-rank dryrun, NaN injected at a known step
# ---------------------------------------------------------------------------

HEALTH_CONFIG = """
nodes:
  - host: localhost
    workers: 2
    chief: true
"""

HEALTH_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import hetu_tpu as ht
from hetu_tpu.executor import Executor

rank = int(os.environ.get("HETU_PS_RANK", "0"))
rng = np.random.RandomState(0)
x = ht.Variable("x", trainable=False)
y_ = ht.Variable("y_", trainable=False)
w1 = ht.init.xavier_normal((12, 16), name="acc_w1")
w2 = ht.init.xavier_normal((16, 4), name="acc_w2")
h = ht.relu_op(ht.matmul_op(x, w1))
loss = ht.reduce_mean_op(
    ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
train = ht.optim.SGDOptimizer(0.1).minimize(loss)
exe = Executor([loss, train])
assert exe.config.health_monitor is not None, "HETU_HEALTH must arm it"
frng = np.random.RandomState(3 + rank)
for step in range(1, 14):
    xs = frng.randn(8, 12).astype("f")
    ys = np.eye(4, dtype="f")[frng.randint(0, 4, 8)]
    if step == 7:
        xs[0, 0] = np.nan          # the known injection step
    exe.run(feed_dict={x: xs, y_: ys})
exe.close()
print("health dryrun rank", rank, "done", flush=True)
"""


def test_acceptance_2rank_nan_injection(tmp_path):
    """Acceptance (ISSUE 9): NaN injected at step 7 of a 2-rank dryrun
    trips within every_n=5 steps (at the step-10 sample), names the
    step and a layer, dumps artifacts, and the doctor CLI reproduces
    first-bad-step from the merged JSONL."""
    from launcher_util import clean_launcher_env
    cfg = tmp_path / "health.yml"
    cfg.write_text(HEALTH_CONFIG)
    script = tmp_path / "worker.py"
    script.write_text(HEALTH_WORKER)
    tdir = tmp_path / "teldir"
    env = clean_launcher_env()
    env.pop("HETU_TELEMETRY", None)
    env.pop("HETU_HEALTH", None)
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg),
         "--telemetry", str(tdir), "--health", "every_n=5,action=dump",
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("health dryrun rank") == 2, proc.stdout
    # per-rank health records exist and both ranks tripped at step 10
    for r in (0, 1):
        lines = [json.loads(ln) for ln in
                 (tdir / f"health_rank{r}.jsonl").read_text()
                 .splitlines()]
        assert [rec["step"] for rec in lines] == [5, 10]
        assert lines[0]["loss_finite"] and not lines[1]["loss_finite"]
        trips = lines[1]["trips"]
        assert any(t["kind"] == "nonfinite" for t in trips)
        assert any(t.get("layer") in ("acc_w1", "acc_w2")
                   for t in trips)
        # dump-rung artifacts via the crash-dump machinery
        assert (tdir / f"flight_rank{r}.json").exists()
        assert (tdir / f"health_lastgood_rank{r}.json").exists()
        lastgood = json.loads(
            (tdir / f"health_lastgood_rank{r}.json").read_text())
        assert lastgood["step"] == 5
    # the doctor CLI reproduces first-bad-step from the merged JSONL
    out = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.health", str(tdir),
         "--json"],
        capture_output=True, text=True, env=clean_launcher_env())
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.loads(out.stdout)
    assert rep["first_bad_step"] == 10
    assert rep["bad_ranks"] == [0, 1]
    assert rep["layer"] in ("acc_w1", "acc_w2")
    assert rep["loss_finite"] is False and rep["healthy"] is False
    assert rep["probable_causes"], rep
    # and the blackbox post-mortem names the same first bad step
    bb = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.telemetry.blackbox",
         str(tdir), "--json"],
        capture_output=True, text=True, env=clean_launcher_env())
    assert bb.returncode == 0, bb.stdout + bb.stderr
    bb_rep = json.loads(bb.stdout)
    assert bb_rep["health"]["first_bad_step"] == 10
