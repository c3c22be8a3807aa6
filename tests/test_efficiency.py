"""Efficiency verifier (analysis/efficiency.py, HT9xx) + the
doctor-validated soundness twin (analysis/perfcheck.py, HT910).

Acceptance pins (ISSUE 15): every injected-bug fixture trips its HT9xx
code with the right severity, user file:line provenance and a
CostDB-priced ``estimated_ms_per_step``, and is silenced by an
``# ht-ok: HT9xx`` waiver on the construction line; every fixture has
a clean twin; the whole zoo is clean under the efficiency CLI gate;
the perfcheck round-trip on mlp + wdl_adult leaves every surviving
priced claim consistent with the measured doctor buckets (no HT910),
with an escape fixture proving the gate bites; and the HT904
fragmented-collective pricing is confirmed by a measured
bucketed-vs-unbucketed A/B within the documented tolerance.
"""
import json
import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import initializers as init
from hetu_tpu.analysis import Report, analyze
from hetu_tpu.analysis.efficiency import (
    DEFAULT_MS_THRESHOLD, DOCTOR_BUCKET, EfficiencyResult, check_zoo,
    check_host_sync_source, efficiency_pass, predict, recompile_pass,
    sorted_by_savings)
from hetu_tpu.analysis.findings import Finding
from hetu_tpu.analysis.perfcheck import (
    AB_TOLERANCE, SOUND_FACTOR, ab_bucketed_allreduce, perfcheck_model,
    serving_claim_check, soundness_pass, _constant_feeds)
from hetu_tpu.analysis.shapes import shape_pass
from hetu_tpu.graph.autodiff import find_topo_sort
from hetu_tpu.telemetry.costdb import (CostDB, latency_crossover_bytes,
                                       recommend_bucket_bytes)

THIS_FILE = os.path.abspath(__file__)


@pytest.fixture(autouse=True)
def _isolated_dbs(tmp_path, monkeypatch):
    """Deterministic cold-start pricing: the developer's real cost
    database must not leak measured entries into fixture
    expectations."""
    monkeypatch.setenv("HETU_COSTDB", str(tmp_path / "costdb.json"))
    monkeypatch.delenv("HETU_EFF_THRESHOLD_MS", raising=False)


def run_pass(eval_nodes, feed_shapes=None, config=None, extra_roots=(),
             costdb=None, steps=None):
    topo = find_topo_sort(list(eval_nodes))
    dtypes = {}
    shapes = shape_pass(topo, Report(), feed_shapes=feed_shapes,
                        dtypes_out=dtypes)
    report = Report()
    efficiency_pass(topo, report, shapes=shapes, dtypes=dtypes,
                    config=config, costdb=costdb,
                    eval_nodes=eval_nodes, extra_roots=extra_roots,
                    steps=steps)
    return report, topo


def codes(report):
    return {f.code for f in report.findings}


def assert_priced(finding):
    """Every fixture finding carries the priced field + provenance at
    THIS file."""
    assert finding.data.get("estimated_ms_per_step") is not None, finding
    assert finding.data["estimated_ms_per_step"] > 0, finding
    assert finding.where is not None, finding
    path, _, line = finding.where.rpartition(":")
    assert os.path.abspath(path) == THIS_FILE, finding.where
    assert int(line) > 0


# ---------------------------------------------------------------------------
# HT901 — recompile hazard
# ---------------------------------------------------------------------------

def test_ht901_recompile_fixture():
    anchor = ht.Variable("feed901", trainable=False)
    keys = [((b, 64), "float32") for b in (3, 5, 6, 7, 9, 11)]
    report = Report()
    f = recompile_pass(keys, report, steps=10, node=anchor)
    assert f is not None and f.code == "HT901"
    assert f.severity == "warn"          # 2 excess compiles / 10 steps
    assert_priced(f)
    assert f.data["bucket"] == "jit"
    # clean twin: the serving pow2-bucketing contract
    assert recompile_pass(
        [((b, 64), "float32") for b in (1, 2, 4, 8, 16, 32)],
        Report(), steps=10) is None
    # under budget is clean too
    assert recompile_pass(keys[:3], Report(), steps=10) is None


def test_ht901_suppressed():
    anchor = ht.Variable("feed901s", trainable=False)  # ht-ok: HT901 test waiver: fixture pins the suppression path
    keys = [((b, 64), "float32") for b in (3, 5, 6, 7, 9, 11)]
    assert recompile_pass(keys, Report(), steps=10, node=anchor) is None


def test_ht901_runtime_advisor():
    """The executor's compile-churn hook: 8 distinct non-pow2 feed
    shapes fire HT901 once into the session's analysis report."""
    from hetu_tpu.executor import Executor
    x = ht.Variable("x901rt", trainable=False)
    w = init.random_normal((4, 3), name="w901rt")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    exe = Executor([loss], validate="warn")
    rng = np.random.RandomState(0)
    try:
        for b in (3, 5, 6, 7, 9, 11, 13, 17):
            exe.run(feed_dict={x: rng.randn(b, 4).astype("f")})
    finally:
        exe.close()
    hits = [f for f in exe.config.analysis_report.findings
            if f.code == "HT901"]
    assert len(hits) == 1                # fires once, not per compile
    assert hits[0].data["signatures"] >= 8


# ---------------------------------------------------------------------------
# HT902 — tiling/padding waste
# ---------------------------------------------------------------------------

def _ht902_matmul(n_out=72, waived=False):
    a = init.random_normal((256, 4096), name="a902")
    if waived:
        b = init.random_normal((4096, n_out), name="b902w")
        y = ht.matmul_op(a, b)  # ht-ok: HT902 test waiver: fixture pins the suppression path
    else:
        b = init.random_normal((4096, n_out), name="b902")
        y = ht.matmul_op(a, b)
    return [ht.reduce_mean_op(y, [0, 1])]


def test_ht902_matmul_fixture():
    report, _ = run_pass(_ht902_matmul())
    hits = [f for f in report.findings if f.code == "HT902"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "warn"
    assert_priced(f)
    assert f.data["bucket"] == "compute"
    assert 0.3 <= f.data["waste_frac"] <= 0.5       # 72 -> 128 lanes
    # clean twin: lane-aligned output dim
    clean, _ = run_pass(_ht902_matmul(n_out=128))
    assert "HT902" not in codes(clean)
    # waived twin
    waived, _ = run_pass(_ht902_matmul(waived=True))
    assert "HT902" not in codes(waived)


def test_ht902_embedding_fixture():
    table = init.random_normal((300000, 8), name="e902")
    ids = ht.Variable("ids902", trainable=False)
    y = ht.embedding_lookup_op(table, ids)
    report, _ = run_pass([ht.reduce_mean_op(y, [0, 1, 2])],
                         feed_shapes={ids: ((16, 8), np.int32)})
    hits = [f for f in report.findings if f.code == "HT902"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "info"          # gather waste prices tiny
    assert_priced(f)
    assert f.data["padded_mib"] > 16


# ---------------------------------------------------------------------------
# HT903 — host sync on the hot path
# ---------------------------------------------------------------------------

def test_ht903_scalar_fetch_fixture():
    x = ht.Variable("x903", trainable=False)
    w = init.random_normal((16, 8), name="w903")
    y = ht.matmul_op(x, w)
    loss = ht.reduce_mean_op(y, [0, 1])
    scalars = [ht.reduce_mean_op(y * float(i + 1), [0, 1])
               for i in range(8)]
    report, _ = run_pass([loss] + scalars,
                         feed_shapes={x: ((4, 16), np.float32)})
    hits = [f for f in report.findings if f.code == "HT903"]
    assert len(hits) == 1
    assert hits[0].severity == "warn"    # cold d2h latency 0.1 ms each
    assert_priced(hits[0])
    assert hits[0].data["scalar_fetches"] == 9
    # clean twin: loss + a couple of metrics is normal
    clean, _ = run_pass([loss] + scalars[:2],
                        feed_shapes={x: ((4, 16), np.float32)})
    assert "HT903" not in codes(clean)


_HT903_SRC = """
def train(exe, feeds):
    for step in range(100):
        out = exe.run(feed_dict=feeds)
        print(out[0].item())
"""

_HT903_SRC_CADENCE = """
def train(exe, feeds):
    for step in range(100):
        out = exe.run(feed_dict=feeds)
        if step % 10 == 0:
            print(out[0].item())
"""

_HT903_SRC_WAIVED = """
def train(exe, feeds):
    for step in range(100):
        out = exe.run(feed_dict=feeds)
        print(out[0].item())  # ht-ok: HT903 debugging run
"""

# np.array/np.asarray building HOST feeds is not a device sync —
# only application to (a subscript of) the run result counts
_HT903_SRC_HOST_FEED = """
import numpy as np
def train(exe, x, data):
    for step in range(100):
        feeds = {x: np.array(data[step])}
        out = exe.run(feed_dict=feeds)
"""

_HT903_SRC_RESULT_ASARRAY = """
import numpy as np
def train(exe, feeds, log):
    for step in range(100):
        out = exe.run(feed_dict=feeds)
        log.append(np.asarray(out[0]))
"""


def test_ht903_ast_fixture():
    report = check_host_sync_source(_HT903_SRC, path="train.py")
    hits = [f for f in report.findings if f.code == "HT903"]
    assert len(hits) == 1
    assert hits[0].where == "train.py:5"
    assert hits[0].data["estimated_ms_per_step"] > 0
    # cadence-guarded twin is the clean pattern
    assert len(check_host_sync_source(_HT903_SRC_CADENCE)) == 0
    # ht-ok waiver on the sync line
    assert len(check_host_sync_source(_HT903_SRC_WAIVED)) == 0
    # host-side feed construction with np.array is NOT a sync
    assert len(check_host_sync_source(_HT903_SRC_HOST_FEED)) == 0
    # ...but asarray over the run result is
    res = check_host_sync_source(_HT903_SRC_RESULT_ASARRAY)
    assert [f.code for f in res.findings] == ["HT903"]


# ---------------------------------------------------------------------------
# HT904 — fragmented collectives
# ---------------------------------------------------------------------------

def _ht904_graph(waived=False):
    from hetu_tpu.ops.comm import allreduceCommunicate_op
    from hetu_tpu.optimizer import OptimizerOp

    x = ht.Variable("x904", trainable=False)
    ws = [init.random_normal((64, 64), name=f"w904_{i}")
          for i in range(5)]
    act = x
    for w in ws:
        act = ht.matmul_op(act, w)
    loss = ht.reduce_mean_op(act, [0, 1])
    opt = ht.optim.SGDOptimizer(0.01)
    opt.params = ws
    grads = ht.gradients(loss, ws)
    if waived:
        ars = [allreduceCommunicate_op(g) for g in grads]  # ht-ok: HT904 test waiver: fixture pins the suppression path
    else:
        ars = [allreduceCommunicate_op(g) for g in grads]
    train = OptimizerOp(ars, opt)
    return [loss, train], {x: ((32, 64), np.float32)}


def test_ht904_fragmented_fixture():
    eval_nodes, feeds = _ht904_graph()
    report, _ = run_pass(eval_nodes, feed_shapes=feeds)
    hits = [f for f in report.findings if f.code == "HT904"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "warn"
    assert_priced(f)
    assert f.data["bucket"] == "collective"
    assert f.data["collectives"] == 5
    assert f.data["buckets"] < 5
    assert f.data["recommended_bucket_bytes"] >= (1 << 20)


def test_ht904_clean_when_bucketed():
    from hetu_tpu.ingest import OverlapOptions

    class Cfg:
        overlap = OverlapOptions(bucket_bytes=4 << 20)

    eval_nodes, feeds = _ht904_graph()
    report, _ = run_pass(eval_nodes, feed_shapes=feeds, config=Cfg())
    assert "HT904" not in codes(report)


def test_ht904_suppressed():
    eval_nodes, feeds = _ht904_graph(waived=True)
    report, _ = run_pass(eval_nodes, feed_shapes=feeds)
    assert "HT904" not in codes(report)


# ---------------------------------------------------------------------------
# HT905 — redundant reshard
# ---------------------------------------------------------------------------

def _ht905_graph(resplit=True, waived=False):
    from hetu_tpu.ops.comm import dispatch

    x = ht.Variable("x905", trainable=False)
    w = init.random_normal((1024, 1024), name="w905")
    s = dispatch(w, (2, 1))
    g = dispatch(s, (1, 1))
    if resplit:
        if waived:
            r = dispatch(g, (2, 1))  # ht-ok: HT905 test waiver: fixture pins the suppression path
        else:
            r = dispatch(g, (2, 1))
    else:
        r = g
    y = ht.matmul_op(x, r)
    return [ht.reduce_mean_op(y, [0, 1])], {x: ((8, 1024), np.float32)}


def test_ht905_reshard_fixture():
    eval_nodes, feeds = _ht905_graph()
    report, _ = run_pass(eval_nodes, feed_shapes=feeds)
    hits = [f for f in report.findings if f.code == "HT905"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "warn"          # 4 MB x 2 hops off the curve
    assert_priced(f)
    assert f.data["bucket"] == "h2d_ingest"
    assert f.data["bytes"] == 1024 * 1024 * 4
    # clean twin: gather without the identical resplit
    clean, _ = run_pass(*_ht905_graph(resplit=False))
    assert "HT905" not in codes(clean)
    waived, _ = run_pass(*_ht905_graph(waived=True))
    assert "HT905" not in codes(waived)


def test_ht905_constant_feed_dynamic():
    """perfcheck's dynamic half: byte-identical large feeds across
    measured steps fire HT905; varying feeds stay clean."""
    x = ht.Variable("x905c", trainable=False)
    const = np.ones((256, 256), np.float32)
    report = Report()
    _constant_feeds([{x: const}, {x: const.copy()}, {x: const.copy()}],
                    report)
    hits = [f for f in report.findings if f.code == "HT905"]
    assert len(hits) == 1
    assert hits[0].data["estimated_ms_per_step"] > 0
    clean = Report()
    rng = np.random.RandomState(0)
    _constant_feeds([{x: rng.randn(256, 256).astype("f")}
                     for _ in range(3)], clean)
    assert len(clean) == 0


# ---------------------------------------------------------------------------
# HT906 — cost-weighted dead compute
# ---------------------------------------------------------------------------

def _ht906_graphs(waived=False):
    x = ht.Variable("x906", trainable=False)
    w = init.random_normal((16, 8), name="w906")
    loss = ht.reduce_mean_op(ht.matmul_op(x, w), [0, 1])
    da = init.random_normal((512, 4096), name="dead_a906")
    db_ = init.random_normal((4096, 512), name="dead_b906")
    if waived:
        dead = ht.matmul_op(da, db_)  # ht-ok: HT906 test waiver: fixture pins the suppression path
    else:
        dead = ht.matmul_op(da, db_)
    return [loss], {x: ((4, 16), np.float32)}, [dead]


def test_ht906_dead_compute_fixture():
    eval_nodes, feeds, roots = _ht906_graphs()
    report, _ = run_pass(eval_nodes, feed_shapes=feeds,
                         extra_roots=roots)
    hits = [f for f in report.findings if f.code == "HT906"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "warn"          # ~2.1 GFLOP of dead matmul
    assert_priced(f)
    assert f.data["dead_ops"] == 1
    # clean twin: no extra construction roots -> nothing dead
    clean, _ = run_pass(eval_nodes, feed_shapes=feeds)
    assert "HT906" not in codes(clean)
    waived_nodes, feeds, roots = _ht906_graphs(waived=True)
    waived, _ = run_pass(waived_nodes, feed_shapes=feeds,
                         extra_roots=roots)
    assert "HT906" not in codes(waived)


# ---------------------------------------------------------------------------
# HT908 — coverage-gap advisory
# ---------------------------------------------------------------------------

def test_ht908_coverage_advisory(tmp_path):
    db = CostDB(str(tmp_path / "cov.json"))
    db.record("SomeOtherOp", (1, 1), "float32", 0.5)
    eval_nodes = _ht902_matmul(n_out=128)       # hot but tile-clean
    report, _ = run_pass(eval_nodes, costdb=db)
    hits = [f for f in report.findings if f.code == "HT908"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "info"          # advisory, never gates
    assert_priced(f)
    assert f.data["guessed_ops"] >= 1
    # clean twin: a fully cold DB is vacuous (the doctor owns the
    # global "run costdb --sweep" hint)
    cold, _ = run_pass(eval_nodes,
                       costdb=CostDB(str(tmp_path / "cold.json")))
    assert "HT908" not in codes(cold)


# ---------------------------------------------------------------------------
# report shape, CLI, zoo gate, analyze() wiring
# ---------------------------------------------------------------------------

def test_sorted_by_savings_and_result_shape():
    eval_nodes, feeds, roots = _ht906_graphs()
    res = predict(eval_nodes, feed_shapes=feeds, extra_roots=roots)
    assert isinstance(res, EfficiencyResult)
    assert res.total_ms > 0
    assert res.predicted_waste_ms() > 0
    ms = [f.data["estimated_ms_per_step"] for f in res.findings]
    assert ms == sorted(ms, reverse=True)
    doc = res.to_dict()
    assert doc["findings"] and "estimated_ms_per_step" in \
        doc["findings"][0]


def test_zoo_clean_gate():
    """Acceptance: every zoo model carries zero unsuppressed HT9xx
    findings (the wdl/ncf/cnn waivers hold)."""
    results = check_zoo()
    bad = [(name, str(f)) for name, res in results.items()
           for f in res.report.findings]
    assert not bad, bad


def test_efficiency_cli_zoo_subset(tmp_path, capsys):
    from hetu_tpu.analysis.efficiency import main
    out = tmp_path / "efficiency_report.json"
    assert main(["mlp", "wdl_adult", "--json", "--out",
                 str(out)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"mlp", "wdl_adult"}
    assert os.path.exists(out)
    assert json.loads(out.read_text())["mlp"]["findings"] == []


def test_analyze_includes_efficiency_pass_never_errors():
    """HT9xx findings surface through analyze() (Executor validate /
    preflight) at warn severity — they advise, never block a launch."""
    report = analyze(_ht902_matmul())
    hits = [f for f in report.findings if f.code == "HT902"]
    assert hits and hits[0].severity == "warn"
    assert report.ok                     # no errors: launch proceeds


def test_graphboard_waste_overlay(tmp_path):
    from hetu_tpu.executor import Executor
    from hetu_tpu import graphboard

    eval_nodes = _ht902_matmul()
    res = predict(eval_nodes)
    exe = Executor(list(eval_nodes))
    try:
        path = graphboard.render(exe, str(tmp_path / "waste.html"),
                                 waste=res)
    finally:
        exe.close()
    html = open(path).read()
    assert "HT902" in html
    assert "ms/step predicted" in html
    dot = open(str(tmp_path / "waste.dot")).read()
    assert "HT902" in dot


# ---------------------------------------------------------------------------
# satellites: doctor cross-link, autoplan bucket default
# ---------------------------------------------------------------------------

def test_doctor_remediation_cites_ht_codes():
    from hetu_tpu.telemetry import doctor

    a = {"steps": 4, "windows": 4, "wall_ms": 4.0,
         "buckets": {"collective": 2.0, "compute": 2.0},
         "per_step_ms": {"collective": 0.5, "compute": 0.5},
         "step_wall_ms": 1.0, "hidden_ms": {}, "segments": [],
         "conserved": True, "conservation_error": 0.0}
    diag = doctor.diagnose({"rank0": a})
    top = diag["top_exposed_bucket"]
    assert top["bucket"] == "collective"
    assert top["ht_code"] == "HT904"
    assert "HT904" in top["remedy"]
    assert "analysis.efficiency" in top["remedy"]
    ranked = {r["bucket"]: r for r in diag["ranked_exposed"]}
    assert ranked["collective"]["ht_code"] == "HT904"


def test_recommend_bucket_bytes():
    assert recommend_bucket_bytes(None) == 4 << 20    # cold default
    db = CostDB("/nonexistent/never_written.json")
    assert recommend_bucket_bytes(db) == 4 << 20      # no curve
    db = CostDB("/nonexistent/never_written2.json")
    db.record("allreduce", 1 << 14, "float32", 5.0, nbytes=1 << 14)
    db.record("allreduce", 1 << 24, "float32", 30.0, nbytes=1 << 24)
    rec = recommend_bucket_bytes(db)
    cross = latency_crossover_bytes(db)
    assert rec == int(min(64 << 20, max(1 << 20, 4 * cross)))
    assert (1 << 20) <= rec <= (64 << 20)


def test_autoplan_dp_plan_sets_bucket_bytes():
    from hetu_tpu.parallel.autoplan import Plan, apply_plan

    eval_nodes = _ht902_matmul(n_out=128)
    plan = Plan(dp=2, tp=1, pp=1, schedule="spmd")
    overrides = apply_plan(list(eval_nodes), plan)
    assert overrides["overlap_options"]["bucket_bytes"] == 4 << 20
    # single-device plans add no knob
    assert "overlap_options" not in apply_plan(
        list(_ht902_matmul(n_out=128)), Plan(dp=1, tp=1, pp=1,
                                             schedule="spmd"))


# ---------------------------------------------------------------------------
# perfcheck: the doctor-validated soundness twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["mlp", "wdl_adult"])
def test_perfcheck_roundtrip(model):
    """Acceptance: a dense and a sparse zoo model run under the trace;
    every surviving priced claim is consistent with the measured
    doctor buckets — no HT910."""
    report, checked, buckets, static = perfcheck_model(model, steps=6)
    viol = [f for f in report.findings if f.code == "HT910"]
    assert not viol, [str(f) for f in viol]
    assert buckets, "doctor produced no measured buckets"
    assert buckets.get("compute", 0) >= 0


def test_ht910_escape_fixture():
    """The gate bites: a priced claim bigger than its measured bucket
    allows is an HT910 error naming both numbers."""
    big_claim = Finding("HT904", "warn", "synthetic fragmented claim",
                        node="AllReduce_x", where="model.py:7",
                        estimated_ms_per_step=100.0,
                        bucket="collective", source="cold_start")
    fine_claim = Finding("HT902", "warn", "synthetic tile claim",
                         node="MatMul_y", where="model.py:9",
                         estimated_ms_per_step=0.2,
                         bucket="compute", source="cold_start")
    measured = {"collective": 0.01, "compute": 1.5}
    report, checked = soundness_pass([big_claim, fine_claim], measured)
    assert checked == 2
    viol = [f for f in report.findings if f.code == "HT910"]
    assert len(viol) == 1
    v = viol[0]
    assert v.severity == "error"
    assert v.data["claim_code"] == "HT904"
    assert v.data["claimed_ms"] == 100.0
    assert v.data["measured_ms"] == 0.01
    # unmeasured buckets and unpriced advisories are vacuous
    report2, checked2 = soundness_pass([big_claim], {"compute": 1.0})
    assert checked2 == 0 and not report2.findings


@pytest.mark.parametrize("claimed,counted,wall_s,ok,measured", [
    (120.0, 240, 2.0, True, 120.0),         # the counters' own rate
    (120.0 * SOUND_FACTOR, 240, 2.0, True, 120.0),      # at the bound
    (120.0 * SOUND_FACTOR + 1.0, 240, 2.0, False, 120.0),   # over-claim
    (120.0 / SOUND_FACTOR - 1.0, 240, 2.0, False, 120.0),   # under-claim
    (120.0, 0, 2.0, False, 0.0),            # the engine counted nothing
    (120.0, 240, 0.0, False, 0.0),          # no window
    (0.0, 240, 2.0, False, 120.0),          # nothing claimed
])
def test_serving_claim_check_holds_a_claim_to_the_counters(
        claimed, counted, wall_s, ok, measured):
    """A claimed tokens/s is attributed only within SOUND_FACTOR of
    what the engine's token counter supports over the same window;
    a window or a counter that measured nothing attributes nothing."""
    assert serving_claim_check(claimed, counted, wall_s) == (ok, measured)


def test_ht904_ab_measured_confirms_prediction():
    """Acceptance: the HT904 pricing's predicted bucketed-vs-per-grad
    savings is confirmed by a measured A/B within the documented
    AB_TOLERANCE (the prediction uses a curve fitted on this
    machine's own measured collective points)."""
    r = ab_bucketed_allreduce(reps=4)
    if r is None:
        pytest.skip("single-device backend: no collective to measure")

    def consistent(r):
        return (r["predicted_ms"] > 0 and r["measured_ms"] > 0
                and 1.0 / AB_TOLERANCE
                <= r["measured_ms"] / r["predicted_ms"]
                <= AB_TOLERANCE)

    if not consistent(r):
        # one refinement pass: a loaded CI box can smear the first
        # measurement window; more reps tighten both sides
        r = ab_bucketed_allreduce(reps=12)
    assert r["predicted_ms"] > 0, r
    assert r["measured_ms"] > 0, r
    assert consistent(r), r
