"""Cost-model auto-parallelism planner (parallel/autoplan.py) + the
interleaved (virtual-stage) 1F1B schedule.

Coverage per ISSUE 10: candidate enumeration prunes invalid
factorizations with reasons; the cost model ranks plans by measured
comm costs from a synthetic CostDB; rules→Dispatch compilation equals
hand-written specs (and conflicts are HT205 findings); interleaved
schedules are loss-equivalent to the staged runners (in-process
collective V∈{2,4} and a 2-process round-robin 1F1B dryrun); the
interleaved rank event programs carry HT3xx coverage including a
mutated lost-send fixture; auto-picked plans preflight clean across
the zoo; and planning is deterministic against the committed fixture
CostDB (the CI autoplan job's snapshot gate)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.executor import Executor
from hetu_tpu.parallel import autoplan
from hetu_tpu.parallel.pipeline import (analytic_bubble_fraction,
                                        virtual_stage_program)
from hetu_tpu.telemetry.costdb import CostDB

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _chain(layers=4, h=32, seed=1, ctx_of=None):
    """Uniform matmul chain; ``ctx_of(k)`` supplies a context string
    per layer (None = single context)."""
    r = np.random.RandomState(seed)
    act = x = None
    loss = train = y_ = None
    for k in range(layers):
        ctx = ht.context(ctx_of(k)) if ctx_of else ht.context(ht.cpu(0))
        with ctx:
            if k == 0:
                x = ht.Variable("x", trainable=False)
                act = x
            w = ht.Variable(f"w{k}", value=r.randn(h, h).astype("f")*.05)
            act = ht.matmul_op(act, w)
            if k < layers - 1:
                act = ht.relu_op(act)
            else:
                y_ = ht.Variable("y_", trainable=False)
                loss = ht.reduce_mean_op(
                    ht.softmaxcrossentropy_op(act, y_), [0])
                train = ht.optim.SGDOptimizer(0.3).minimize(loss)
    feeds = {x: ((16, h), np.float32), y_: ((16, h), np.float32)}
    return x, y_, loss, train, feeds


def _run(exe, x, y_, xv, yv, steps=4):
    out = []
    for _ in range(steps):
        res = exe.run(feed_dict={x: xv, y_: yv})
        out.append(float(np.asarray(res[0].asnumpy()).reshape(())))
    return np.asarray(out)


# ---------------------------------------------------------------------------
# 1. candidate enumeration
# ---------------------------------------------------------------------------

def test_enumeration_prunes_invalid_factorizations():
    x, y_, loss, train, feeds = _chain(layers=3, h=6)
    info = autoplan.graph_costs([loss, train], feed_shapes=feeds)
    valid, rejected = autoplan.enumerate_candidates(8, info=info)
    # h=6 param dims divide by 2,3,6 — never 4 or 8
    assert all(tp in (1, 2, 3, 6) for _, tp, _ in valid)
    reasons = {c: r for c, r in rejected}
    assert any("divisible by tp=4" in r for r in reasons.values())
    # the single-device baseline is always a candidate
    assert (1, 1, 1) in valid
    # rules that bind nothing to tp prune every tp>1 candidate
    valid2, rejected2 = autoplan.enumerate_candidates(
        8, info=info, rules={"out": None})
    assert all(tp == 1 for _, tp, _ in valid2)
    assert any("rules bind no axis to tp" in r for _, r in rejected2)
    # pp deeper than the op chain is pruned with a reason
    assert any("deeper than" in r for _, r in rejected)


def test_balance_stages_by_measured_cost():
    costs = {f"op{i}": ms for i, ms in
             enumerate([1.0, 1.0, 1.0, 1.0, 4.0, 4.0])}
    order = list(costs)
    cuts, stage_ms = autoplan.balance_stages(costs, order, 2)
    assert len(cuts) == 1 and len(stage_ms) == 2
    # a balanced-by-cost cut puts the two 4.0 ops alone on stage 1
    assert abs(stage_ms[0] - stage_ms[1]) <= 4.0
    assert sum(stage_ms) == pytest.approx(12.0)


# ---------------------------------------------------------------------------
# 2. cost model vs a synthetic CostDB
# ---------------------------------------------------------------------------

def _synthetic_db(tmp_path, allreduce_ms):
    db = CostDB(str(tmp_path / "db.json"))
    for nbytes in (1 << 14, 1 << 20):
        db.record("allreduce", nbytes, "float32", allreduce_ms,
                  nbytes=nbytes)
        db.record("p2p", nbytes, "float32", 0.01, nbytes=nbytes)
        db.record("h2d", nbytes, "float32", 0.05, nbytes=nbytes)
    return db


def test_cost_model_ranks_slow_axis_tp_below_good_plan(tmp_path):
    """tp across a slow interconnect (synthetic DB: allreduce costs
    seconds) must rank below the no-comm single-device plan; on a fast
    interconnect the same tp plan wins for the same compute-heavy
    graph — the ranking follows the MEASURED comm curve, not a
    constant."""
    x, y_, loss, train, feeds = _chain(layers=4, h=64)
    nodes = [loss, train]
    slow = _synthetic_db(tmp_path / "slow", allreduce_ms=5000.0)
    info = autoplan.graph_costs(nodes, db=slow, feed_shapes=feeds)
    info["bindings"], _ = autoplan.compile_rules(nodes, None, 8,
                                                 topo=info["topo"])
    bad = autoplan.score_plan(1, 8, 1, info, db=slow)
    good = autoplan.score_plan(1, 1, 1, info, db=slow)
    assert bad.predicted_ms > good.predicted_ms

    fast = _synthetic_db(tmp_path / "fast", allreduce_ms=0.001)
    info_f = autoplan.graph_costs(nodes, db=fast, feed_shapes=feeds)
    info_f["bindings"], _ = autoplan.compile_rules(nodes, None, 8,
                                                   topo=info_f["topo"])
    bad_f = autoplan.score_plan(1, 8, 1, info_f, db=fast)
    good_f = autoplan.score_plan(1, 1, 1, info_f, db=fast)
    assert bad_f.predicted_ms < good_f.predicted_ms


def test_measured_refinement_overrides_prediction(tmp_path):
    """The top-k finalists are measured; the measured argmin wins even
    when the prediction preferred another plan."""
    x, y_, loss, train, feeds = _chain(layers=4, h=64)
    db = CostDB(str(tmp_path / "db.json"))
    measured = {}

    def measure(plan):
        # synthetic ground truth: the LAST finalist is the fastest
        dt = 0.1 - 0.01 * len(measured)
        measured[autoplan.plan_key(plan)] = dt
        return dt

    res = autoplan.choose_plan([loss, train], nworld=8, db=db,
                               feed_shapes=feeds, model="refine",
                               measure=measure, topk=4)
    assert len(measured) > 1, "the finalists were not measured"
    assert autoplan.plan_key(res.plan) == min(measured, key=measured.get)
    assert res.plan.measured_ms == pytest.approx(
        1000 * min(measured.values()))
    assert [p.measured_ms is not None for p in res.candidates] == \
        [True] * len(measured) + [False] * (len(res.candidates) - len(measured))


# ---------------------------------------------------------------------------
# 3. rules -> Dispatch compilation vs hand specs
# ---------------------------------------------------------------------------

def test_rules_compile_equals_hand_mlp_spec():
    """The compiled parts tuple for an MLP weight equals the
    hand-written ``ht.dispatch(w, (1, 2))`` spec, and the planner's
    propagated statuses agree between the two graphs."""
    from hetu_tpu.graph.autodiff import find_topo_sort
    from hetu_tpu.parallel.planner import propagate_statuses

    # hand spec (the test_parallel idiom)
    r = np.random.RandomState(1)
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("w1", value=r.randn(8, 4).astype("f"))
    act = ht.matmul_op(x, ht.dispatch(w1, (1, 2)))
    y_ = ht.Variable("y_", trainable=False)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(act, y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    hand_status = propagate_statuses(find_topo_sort([loss, train]))
    hand_w1 = hand_status[w1]

    # rules compile on the same model WITHOUT the hand spec
    r = np.random.RandomState(1)
    x2 = ht.Variable("x", trainable=False)
    w1b = ht.Variable("w1", value=r.randn(8, 4).astype("f"))
    act2 = ht.matmul_op(x2, w1b)
    y2 = ht.Variable("y_", trainable=False)
    loss2 = ht.reduce_mean_op(ht.softmaxcrossentropy_op(act2, y2), [0])
    train2 = ht.optim.SGDOptimizer(0.1).minimize(loss2)
    bindings, conflicts = autoplan.compile_rules([loss2, train2],
                                                 None, tp=2)
    assert not conflicts
    assert [b.param.name for b in bindings] == ["w1"]
    assert bindings[0].parts == (1, 2)      # == the hand spec
    autoplan.apply_rules([loss2, train2], bindings)
    auto_status = propagate_statuses(find_topo_sort([loss2, train2]))
    assert auto_status[w1b] == hand_w1


def test_rules_compile_equals_hand_embedding_spec():
    """Embedding tables bind their row (vocab) axis: the compiled spec
    equals a hand ``ht.dispatch(table, (2, 1))`` row split."""
    ids = ht.Variable("ids", trainable=False, dtype=np.int32)
    tbl = ht.Variable("tbl", value=np.random.RandomState(0)
                      .randn(16, 4).astype("f"))
    emb = ht.embedding_lookup_op(tbl, ids)
    loss = ht.reduce_mean_op(ht.reduce_sum_op(emb, [1]), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    bindings, conflicts = autoplan.compile_rules([loss, train],
                                                 None, tp=2)
    assert not conflicts
    tb = [b for b in bindings if b.param is tbl]
    assert tb and tb[0].parts == (2, 1)     # row (vocab) split
    assert tb[0].axes == ("vocab", "embed")


def test_hand_spec_conflict_is_ht205():
    from hetu_tpu.analysis.findings import Report, collecting

    r = np.random.RandomState(1)
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("w1", value=r.randn(8, 4).astype("f"))
    # hand spec splits the ROW axis; the rules say column (1, 2)
    act = ht.matmul_op(x, ht.dispatch(w1, (2, 1)))
    y_ = ht.Variable("y_", trainable=False)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(act, y_), [0])
    train = ht.optim.SGDOptimizer(0.1).minimize(loss)
    report = Report()
    with collecting(report):
        bindings, conflicts = autoplan.compile_rules([loss, train],
                                                     None, tp=2)
    assert conflicts and conflicts[0][0] is w1
    assert not any(b.param is w1 for b in bindings)  # hand spec wins
    assert any(f.code == "HT205" for f in report.findings)


# ---------------------------------------------------------------------------
# 4. interleaved schedule: loss equivalence
# ---------------------------------------------------------------------------

_STAGED_REF = {}    # staged-gpipe reference losses, shared across Vs


def _staged_ref(M, S_total, xv, yv):
    key = (M, S_total)
    if key not in _STAGED_REF:
        x, y_, loss, train, _ = _chain(
            layers=S_total, h=32,
            ctx_of=lambda k: f"v0:cpu:{k}")
        _STAGED_REF[key] = _run(
            Executor([loss, train], gpipe=True, num_microbatches=M),
            x, y_, xv, yv)
    return _STAGED_REF[key]


@pytest.mark.parametrize("V", [2,
                               pytest.param(4, marks=pytest.mark.slow)])
def test_interleaved_collective_matches_staged_gpipe(V):
    """The V-way interleaved collective schedule computes the exact
    GPipe math on the same 8-stage graph: losses match the staged
    runner step for step (the schedule reorders work, never changes
    it). V=4 is slow-marked (one more whole-schedule XLA compile);
    the CI autoplan job and a full `pytest tests/` still run it."""
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 16)]
    M, S_total = 8, 8
    s_dev = S_total // V
    ref = _staged_ref(M, S_total, xv, yv)

    x, y_, loss, train, _ = _chain(
        layers=S_total, h=32,
        ctx_of=lambda k: f"v{k // s_dev}:cpu:{k % s_dev}")
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=M,
                   pp_options={"virtual_stages": V})
    got = _run(exe, x, y_, xv, yv)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5)
    assert exe.subexecutors["default"]._cpp.V == V
    assert exe.subexecutors["default"]._cpp.S_dev == s_dev


def test_interleaved_requires_m_ge_devices():
    x, y_, loss, train, _ = _chain(
        layers=8, h=32, ctx_of=lambda k: f"v{k // 4}:cpu:{k % 4}")
    exe = Executor([loss, train], pipeline_mode="collective",
                   num_microbatches=2,         # < 4 devices
                   pp_options={"virtual_stages": 2})
    rng = np.random.RandomState(0)
    with pytest.raises(ValueError, match="M >= device count"):
        exe.run(feed_dict={x: rng.randn(16, 32).astype("f"),
                           y_: np.eye(32, dtype="f")[:16]})


def test_interleaved_bubble_fraction_drops():
    for M in (4, 8):
        b1 = analytic_bubble_fraction(4, M, 1)
        b2 = analytic_bubble_fraction(8, M, 2)
        b4 = analytic_bubble_fraction(16, M, 4)
        assert b2 < b1 and b4 < b2


# ---------------------------------------------------------------------------
# 5. interleaved event programs (HT3xx coverage)
# ---------------------------------------------------------------------------

def test_virtual_stage_program_round_robin():
    progs = virtual_stage_program(2, 4, M=4)
    # each rank owns V=2 chunks; every microbatch visits both
    for r in (0, 1):
        stages = {s for _, _, s in progs[r]}
        assert stages == {r, r + 2}
    # 1F1B order: rank 0's first events are the warmup forwards
    kinds = [k for k, _, _ in progs[0]]
    assert kinds[0] == "fwd"
    assert "bwd" in kinds


def _interleaved_plan_2rank():
    """4 stages placed round-robin over worker0/worker1 (V=2)."""
    ctxs = ["worker0:cpu:0", "worker1:cpu:0",
            "worker0:cpu:1", "worker1:cpu:1"]
    x, y_, loss, train, _ = _chain(layers=4, h=16,
                                   ctx_of=lambda k: ctxs[k])
    from hetu_tpu.analysis.deadlock import build_plan
    plan = build_plan([loss, train], nprocs=2)
    return plan


def test_interleaved_rank_programs_drain_clean():
    from hetu_tpu.analysis.deadlock import rank_programs, simulate
    from hetu_tpu.analysis.findings import Report

    plan = _interleaved_plan_2rank()
    assert [s.owner for s in plan.stages] == [0, 1, 0, 1]
    report = Report()
    progs = rank_programs(plan, schedule="1f1b", num_microbatches=4,
                          report=report)
    assert simulate(progs, report)
    assert not report.errors


def test_interleaved_lost_send_is_ht301():
    """Mutated fixture: drop one of rank 0's sends from the interleaved
    program — the symbolic run must name the blocked recv (HT301)."""
    from hetu_tpu.analysis.deadlock import rank_programs, simulate
    from hetu_tpu.analysis.findings import Report

    plan = _interleaved_plan_2rank()
    report = Report()
    progs = rank_programs(plan, schedule="1f1b", num_microbatches=4,
                          report=report)
    sends = [i for i, ev in enumerate(progs[0]) if ev.kind == "send"]
    del progs[0][sends[0]]
    bad = Report()
    assert not simulate(progs, bad)
    assert any(f.code in ("HT301", "HT302") for f in bad.findings)


def test_blocked_collective_placement_is_ht308_in_preflight():
    """The collective form of HT308: virtual_stages folded onto
    non-round-robin device contexts must FAIL preflight — the
    collective builder refuses the same configuration with a
    ValueError at first dispatch, and a static pass that passed it
    would approve a launch that dies on every rank."""
    from hetu_tpu import analysis

    # blocked: stages 0,1 on device 0, stages 2,3 on device 1, ...
    x, y_, loss, train, _ = _chain(
        layers=8, h=32, ctx_of=lambda k: f"v0:cpu:{k // 2}")
    report = analysis.analyze([loss, train], schedule="collective",
                              virtual_stages=2)
    assert any(f.code == "HT308" for f in report.errors)

    # round-robin placement: clean
    x, y_, loss, train, _ = _chain(
        layers=8, h=32, ctx_of=lambda k: f"v{k // 4}:cpu:{k % 4}")
    report = analysis.analyze([loss, train], schedule="collective",
                              virtual_stages=2)
    assert not any(f.code == "HT308" for f in report.findings)


def test_nonuniform_collective_plan_downgrades_without_resplice():
    """A collective-schedule plan over a NON-uniform chain downgrades
    to staged gpipe at apply time (the collective builder would raise
    on heterogeneous per-stage params), and the downgrade recursion
    must not re-splice the tp dispatches (a chained dispatch-over-
    dispatch would gather the split away)."""
    from hetu_tpu.graph.autodiff import find_topo_sort
    from hetu_tpu.ops.comm import DispatchOp

    r = np.random.RandomState(1)
    widths = [(32, 16), (16, 32), (32, 16), (16, 32)]
    act = x = None
    for k, (win, wout) in enumerate(widths):
        with ht.context(ht.cpu(0)):
            if k == 0:
                x = ht.Variable("x", trainable=False)
                act = x
            w = ht.Variable(f"w{k}",
                            value=r.randn(win, wout).astype("f")*.05)
            act = ht.matmul_op(act, w)
            if k < 3:
                act = ht.relu_op(act)
            else:
                y_ = ht.Variable("y_", trainable=False)
                loss = ht.reduce_mean_op(
                    ht.softmaxcrossentropy_op(act, y_), [0])
                train = ht.optim.SGDOptimizer(0.3).minimize(loss)
    nodes = [loss, train]
    info = autoplan.graph_costs(
        nodes, feed_shapes={x: ((16, 32), np.float32),
                            y_: ((16, 32), np.float32)})
    bindings, _ = autoplan.compile_rules(nodes, None, 2,
                                         topo=info["topo"])
    plan = autoplan.Plan(dp=1, tp=2, pp=2, M=4, V=2,
                         schedule="collective", bindings=bindings)
    ov = autoplan.apply_plan(nodes, plan, info=info)
    assert "pipeline_mode" not in ov and ov.get("gpipe")
    disp = [n for n in find_topo_sort(nodes)
            if isinstance(n, DispatchOp)]
    assert disp, "tp splits were not applied at all"
    assert not any(isinstance(d.inputs[0], DispatchOp) for d in disp)


def test_blocked_placement_is_ht308():
    from hetu_tpu.analysis.deadlock import (build_plan,
                                            interleaved_placement_pass)
    from hetu_tpu.analysis.findings import Report

    # blocked ownership: worker0 owns stages 0+1, worker1 owns 2+3
    ctxs = ["worker0:cpu:0", "worker0:cpu:1",
            "worker1:cpu:0", "worker1:cpu:1"]
    x, y_, loss, train, _ = _chain(layers=4, h=16,
                                   ctx_of=lambda k: ctxs[k])
    plan = build_plan([loss, train], nprocs=2)
    report = Report()
    ok = interleaved_placement_pass(plan, report, virtual_stages=2)
    assert not ok
    assert any(f.code == "HT308" for f in report.findings)


# ---------------------------------------------------------------------------
# 6. costdb cold start + coverage
# ---------------------------------------------------------------------------

def test_costdb_cold_start_fallback(tmp_path):
    db = CostDB(str(tmp_path / "empty.json"))
    ms = db.estimate_ms("allreduce", 1 << 20, cold_start=True)
    assert ms is not None and 0 < ms < 1e4
    val, src = db.estimate_info("allreduce", 1 << 20)
    assert src == "cold_start" and val == ms
    # without cold start the old None contract holds
    assert db.estimate_ms("allreduce", 1 << 20) is None
    # measured entries upgrade the source
    db.record("allreduce", 1 << 20, "bytes", 2.5, nbytes=1 << 20)
    val, src = db.estimate_info("allreduce", 1 << 20)
    assert src == "measured" and val == pytest.approx(2.5)


def test_costdb_coverage_measured_vs_guessed(tmp_path):
    db = CostDB(str(tmp_path / "db.json"))
    db.record("h2d", 1 << 14, "float32", 0.5, nbytes=1 << 14)
    measured, guessed = db.coverage(("h2d", "allreduce"))
    assert measured == ["h2d"] and guessed == ["allreduce"]
    # tuple keys demand an exact entry
    measured, guessed = db.coverage(
        (("h2d", 1 << 14, "float32"), ("h2d", 1 << 20, "float32")))
    assert len(measured) == 1 and len(guessed) == 1


# ---------------------------------------------------------------------------
# 7. end-to-end: Executor(parallel="auto")
# ---------------------------------------------------------------------------

def test_apply_plan_to_rebuilt_graph_resplices():
    """A plan applied to a REBUILT graph (a per-candidate
    measurement loop) must recompile its rules against that graph —
    stored bindings reference the scored graph's nodes, and silently
    splicing nothing would report a tp plan while running unsplit."""
    from hetu_tpu.graph.autodiff import find_topo_sort
    from hetu_tpu.ops.comm import DispatchOp

    def build():
        x, y_, loss, train, feeds = _chain(layers=2, h=32)
        return [loss, train], feeds

    nodes, feeds = build()
    bindings, _ = autoplan.compile_rules(nodes, None, tp=2)
    plan = autoplan.Plan(dp=1, tp=2, pp=1, schedule="spmd",
                         bindings=bindings, rules=None)
    nodes2, _ = build()
    autoplan.apply_plan(nodes2, plan)
    n_disp = sum(isinstance(n, DispatchOp)
                 for n in find_topo_sort(nodes2))
    assert n_disp >= 2, "rebuilt-graph application spliced nothing"


def test_executor_parallel_auto_matches_baseline():
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 32).astype("f")
    yv = np.eye(32, dtype="f")[rng.randint(0, 32, 16)]
    x, y_, loss, train, _ = _chain(layers=3, h=32)
    base = _run(Executor([loss, train]), x, y_, xv, yv)
    x, y_, loss, train, _ = _chain(layers=3, h=32)
    exe = Executor([loss, train], parallel="auto")
    assert exe.config.autoplan is not None
    assert exe.config.autoplan.plan.nworld >= 1
    got = _run(exe, x, y_, xv, yv)
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)


def test_auto_plans_preflight_clean_across_zoo():
    """The auto-picked plan for every zoo model passes the full static
    preflight (shapes, sharding, deadlock, memory) with zero errors
    after application."""
    from hetu_tpu import analysis
    from hetu_tpu.analysis import zoo

    failures = {}
    for name in sorted(zoo.ZOO):
        nodes, feeds = zoo.build(name)
        res = autoplan.choose_plan(nodes, nworld=8, feed_shapes=feeds,
                                   db=CostDB("/nonexistent/db.json"),
                                   model=name)
        overrides = autoplan.apply_plan(nodes, res.plan, info=res.info)
        schedule = ("collective" if overrides.get("pipeline_mode")
                    else "1f1b" if overrides.get("pipedream")
                    else "gpipe")
        report = analysis.analyze(
            nodes, feed_shapes=feeds, schedule=schedule,
            num_microbatches=overrides.get("num_microbatches"))
        if report.errors:
            failures[name] = [str(f) for f in report.errors]
    assert not failures, failures


def test_autoplan_report_env_exits_before_fleet(tmp_path):
    """HETU_AUTOPLAN_REPORT (the `heturun --autoplan` contract): the
    config prints the plan table, writes the JSON report, and exits 0
    before any executor machinery."""
    script = tmp_path / "train.py"
    script.write_text(
        "import numpy as np\n"
        "import hetu_tpu as ht\n"
        "from hetu_tpu.executor import Executor\n"
        "x = ht.Variable('x', trainable=False)\n"
        "w = ht.Variable('w', value=np.ones((8, 8), 'f'))\n"
        "y_ = ht.Variable('y_', trainable=False)\n"
        "loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(\n"
        "    ht.matmul_op(x, w), y_), [0])\n"
        "train = ht.optim.SGDOptimizer(0.1).minimize(loss)\n"
        "exe = Executor([loss, train])\n"
        "raise SystemExit('executor machinery ran past the report')\n")
    report_path = tmp_path / "autoplan.json"
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(DATA, "..", "..") + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "HETU_AUTOPLAN_REPORT": str(report_path)}
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "autoplan: OK" in proc.stdout
    assert "chosen:" in proc.stderr
    doc = json.loads(report_path.read_text())
    assert "chosen" in doc and "candidates" in doc


# ---------------------------------------------------------------------------
# 8. deterministic plan snapshot (the CI autoplan job)
# ---------------------------------------------------------------------------

def test_autoplan_deterministic_against_fixture(monkeypatch):
    """With the committed fixture CostDB, the planner's choice for each
    snapshot model is deterministic — CI compares against the
    committed snapshot and a diff fails the job (a cost-model change
    must update the snapshot deliberately)."""
    from hetu_tpu.analysis import zoo

    fixture = os.path.join(DATA, "costdb_fixture.json")
    snap_path = os.path.join(DATA, "autoplan_snapshot.json")
    snapshot = json.loads(open(snap_path).read())
    got = {}
    for name in snapshot:
        nodes, feeds = zoo.build(name)
        res = autoplan.choose_plan(nodes, nworld=8,
                                   db=CostDB(fixture),
                                   feed_shapes=feeds, model=name)
        got[name] = autoplan.plan_key(res.plan)
    assert got == snapshot, (
        f"autoplan snapshot drift: {got} != {snapshot} — if the cost "
        f"model changed intentionally, regenerate "
        f"tests/data/autoplan_snapshot.json")


# ---------------------------------------------------------------------------
# 9. 2-process interleaved 1F1B dryrun (the launcher-matrix entry)
# ---------------------------------------------------------------------------

_SPMD_CONFIG = """\
spmd: true
nodes:
  - host: localhost
    workers: 2
    chief: true
"""

_INTERLEAVED_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from hetu_tpu.executor import Executor, maybe_init_distributed
maybe_init_distributed()
import jax
jax.config.update("jax_default_matmul_precision", "highest")
import hetu_tpu as ht

rank = int(os.environ["HETU_PROC_ID"])
r = np.random.RandomState(0)
H = 16
# 4 stages placed ROUND-ROBIN over 2 worker ranks (V=2 chunks each):
# the interleaved 1F1B layout — stage i owned by rank i % 2
ctxs = ["worker0:cpu:0", "worker1:cpu:0",
        "worker0:cpu:1", "worker1:cpu:1"]
act = x = None
for k in range(4):
    with ht.context(ctxs[k]):
        if k == 0:
            x = ht.Variable("x", trainable=False)
            act = x
        w = ht.Variable(f"w{k}", value=r.randn(H, H).astype("f") * 0.3)
        act = ht.matmul_op(act, w)
        if k < 3:
            act = ht.relu_op(act)
        else:
            y_ = ht.Variable("y_", trainable=False)
            loss = ht.reduce_mean_op(
                ht.softmaxcrossentropy_op(act, y_), [0])
            train_op = ht.optim.SGDOptimizer(0.3).minimize(loss)
exe = Executor([loss, train_op], pipedream=True, num_microbatches=4,
               pp_options={"virtual_stages": 2})
sub = exe.subexecutors["default"]
assert sub.multiproc and sub.virtual_stages == 2
assert [s.owner for s in sub.stages] == [0, 1, 0, 1]
frng = np.random.RandomState(3)
xs = frng.randn(16, H).astype("f")
ys = np.eye(H, dtype="f")[frng.randint(0, H, 16)]
losses = []
for _ in range(5):
    out = exe.run(feed_dict={x: xs, y_: ys})
    if out[0] is not None:
        losses.append(float(np.asarray(out[0].asnumpy()).reshape(())))
with open(os.path.join(os.environ["HETU_TEST_OUT"],
                       f"il_{rank}.txt"), "w") as f:
    f.write(" ".join(str(v) for v in losses))
"""


def test_two_process_interleaved_1f1b_matches_plain(tmp_path):
    """Interleaved 1F1B (V=2 chunks per rank, round-robin placement)
    across 2 worker processes: losses and params are the exact plain
    1F1B math — the interleaving is a placement/overlap property, the
    per-microbatch weight-stash semantics are untouched (ground truth:
    the same 4-stage model under the in-process 1F1B runner)."""
    from launcher_util import clean_launcher_env

    cfg_path = tmp_path / "spmd.yml"
    cfg_path.write_text(_SPMD_CONFIG)
    script = tmp_path / "il_worker.py"
    script.write_text(_INTERLEAVED_WORKER)
    env = clean_launcher_env(HETU_TEST_OUT=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # in-process plain 1F1B twin (same weights, same feeds)
    r = np.random.RandomState(0)
    H = 16
    act = x = None
    for k in range(4):
        with ht.context(f"tw{k}:cpu:{k}"):
            if k == 0:
                x = ht.Variable("x", trainable=False)
                act = x
            w = ht.Variable(f"w{k}",
                            value=r.randn(H, H).astype("f") * 0.3)
            act = ht.matmul_op(act, w)
            if k < 3:
                act = ht.relu_op(act)
            else:
                y_ = ht.Variable("y_", trainable=False)
                loss = ht.reduce_mean_op(
                    ht.softmaxcrossentropy_op(act, y_), [0])
                train = ht.optim.SGDOptimizer(0.3).minimize(loss)
    exe = Executor([loss, train], pipedream=True, num_microbatches=4)
    frng = np.random.RandomState(3)
    xs = frng.randn(16, H).astype("f")
    ys = np.eye(H, dtype="f")[frng.randint(0, H, 16)]
    base = _run(exe, x, y_, xs, ys, steps=5)

    # rank 1 owns the loss stage (stage 3 -> worker1)
    got = [float(v) for v in
           (tmp_path / "il_1.txt").read_text().split()]
    assert len(got) == 5
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-5)
    assert (tmp_path / "il_0.txt").read_text().strip() == ""
