"""Preflight graph verifier (hetu_tpu/analysis): static shape/sharding/
deadlock/memory passes, op provenance localization, the jit-purity
codebase lint, and the ``heturun --preflight`` gate.

Acceptance pins (ISSUE 6): a mis-paired 2-stage pipeline schedule is
rejected statically with an HT3xx finding naming both ranks, in under
5 seconds, without a single worker process spawning; every zoo model
preflights error-free; ``Executor(validate=...)`` defaults to "off" and
leaves runtime behavior untouched.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import analysis
from hetu_tpu.analysis import (GraphValidationError, Report, analyze,
                               collecting, emit)
from hetu_tpu.analysis.deadlock import (build_plan, deadlock_pass, Event,
                                        rank_programs, simulate,
                                        collective_order_pass)
from hetu_tpu.analysis.jit_purity import check_source
from hetu_tpu.analysis.memory import parse_bytes
from hetu_tpu.executor import Executor, HetuConfig
from tests.launcher_util import REPO, clean_launcher_env


# ---------------------------------------------------------------------------
# graph builders
# ---------------------------------------------------------------------------

def _mlp_nodes(w2_rows=256):
    """Tiny MLP; ``w2_rows != 256`` plants a matmul contraction
    mismatch. Returns (eval_nodes, feeds, the mismatching line no)."""
    x = ht.Variable("x", trainable=False)
    y_ = ht.Variable("y_", trainable=False)
    w1 = ht.Variable("w1", value=np.zeros((784, 256), "f"))
    w2 = ht.Variable("w2", value=np.zeros((w2_rows, 10), "f"))
    h = ht.relu_op(ht.matmul_op(x, w1))
    logits = ht.matmul_op(h, w2)   # <- provenance must point HERE
    bad_line = logits.defined_at[1] if logits.defined_at else None
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    feeds = {x: ((8, 784), np.float32), y_: ((8, 10), np.float32)}
    return [loss, train_op], feeds, bad_line


def _staged_2rank(back_edge=False):
    """2-stage MLP across worker0/worker1 hostname contexts. With
    ``back_edge`` the last block returns to worker0 — a stage-0 node
    consuming a stage-1 boundary, i.e. a cross-rank cyclic wait."""
    with ht.context("worker0:cpu:0"):
        x = ht.Variable("x", trainable=False)
        w1 = ht.Variable("w1", value=np.zeros((20, 32), "f"))
        a = ht.relu_op(ht.matmul_op(x, w1))
    with ht.context("worker1:cpu:0"):
        w2 = ht.Variable("w2", value=np.zeros((32, 32), "f"))
        b = ht.relu_op(ht.matmul_op(a, w2))
    tail_ctx = "worker0:cpu:0" if back_edge else "worker1:cpu:0"
    with ht.context(tail_ctx):
        w3 = ht.Variable("w3", value=np.zeros((32, 10), "f"))
        y_ = ht.Variable("y_", trainable=False)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(b, w3), y_), [0])
        train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    return [loss, train_op]


# ---------------------------------------------------------------------------
# pass 1: shapes + provenance localization
# ---------------------------------------------------------------------------

def test_shape_mismatch_reports_user_line():
    nodes, feeds, bad_line = _mlp_nodes(w2_rows=128)
    report = analyze(nodes, feed_shapes=feeds)
    errs = [f for f in report.errors if f.code == "HT101"]
    assert len(errs) == 1
    f = errs[0]
    assert "matmul" in f.message.lower()
    # provenance: THIS test file and the logits = matmul_op(...) line
    assert f.where is not None and "test_analysis.py" in f.where
    assert f.where.endswith(f":{bad_line}")


def test_clean_graph_no_errors_and_side_effect_free():
    nodes, feeds, _ = _mlp_nodes()
    topo = ht.graph.autodiff.find_topo_sort(nodes)
    assert analyze(nodes, feed_shapes=feeds).ok
    # the pass must not leave inferred_shape droppings on the graph
    assert not any(hasattr(n, "inferred_shape") for n in topo)


def test_unknown_feeds_stop_propagation_without_false_positives():
    nodes, _, _ = _mlp_nodes(w2_rows=128)   # mismatch NOT reachable
    report = analyze(nodes)                 # ...without feed shapes
    assert not [f for f in report.errors if f.code == "HT101"]
    assert [f for f in report.infos if f.code == "HT100"]


def test_validate_error_raises_at_first_dispatch():
    nodes, _, bad_line = _mlp_nodes(w2_rows=128)
    x = next(n for n in ht.graph.autodiff.find_topo_sort(nodes)
             if getattr(n, "name", "") == "x")
    y_ = next(n for n in ht.graph.autodiff.find_topo_sort(nodes)
              if getattr(n, "name", "") == "y_")
    exe = Executor({"default": nodes}, ctx=ht.cpu(0), validate="error")
    with pytest.raises(GraphValidationError) as ei:
        exe.run(feed_dict={x: np.zeros((8, 784), "f"),
                           y_: np.zeros((8, 10), "f")})
    f = ei.value.report.errors[0]
    assert f.code == "HT101" and f.where.endswith(f":{bad_line}")


def test_validate_default_off_and_env_override(monkeypatch):
    nodes, _, _ = _mlp_nodes()
    config = HetuConfig(eval_node_list=nodes, ctx=ht.cpu(0))
    assert config.validate == "off" and config.analysis_report is None
    monkeypatch.setenv("HETU_VALIDATE", "warn")
    nodes2, _, _ = _mlp_nodes()
    config2 = HetuConfig(eval_node_list=nodes2, ctx=ht.cpu(0))
    assert config2.validate == "warn"
    assert config2.analysis_report is not None
    with pytest.raises(ValueError, match="unknown validate"):
        nodes3, _, _ = _mlp_nodes()
        HetuConfig(eval_node_list=nodes3, ctx=ht.cpu(0),
                   validate="loud")


def test_validate_warn_clean_graph_runs():
    nodes, _, _ = _mlp_nodes()
    topo = ht.graph.autodiff.find_topo_sort(nodes)
    x = next(n for n in topo if getattr(n, "name", "") == "x")
    y_ = next(n for n in topo if getattr(n, "name", "") == "y_")
    exe = Executor({"default": nodes}, ctx=ht.cpu(0), validate="warn")
    out = exe.run(feed_dict={x: np.random.randn(8, 784).astype("f"),
                             y_: np.eye(10, dtype="f")[
                                 np.random.randint(0, 10, 8)]})
    assert np.isfinite(float(np.asarray(out[0].asnumpy()).item()))
    assert exe.config.analysis_report is not None


def test_lint_duplicate_param_and_unused_variable():
    x = ht.Variable("x", trainable=False)
    w = ht.Variable("dup_w", value=np.zeros((4, 4), "f"))
    w2 = ht.Variable("dup_w", value=np.zeros((4, 4), "f"))
    frozen = ht.Variable("frozen_w", value=np.zeros((4, 4), "f"))
    y = ht.matmul_op(ht.matmul_op(ht.matmul_op(x, w), w2), frozen)
    loss = ht.reduce_mean_op(y, [0])
    # optimizer only covers w — w2/frozen train as constants
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss, var_list=[w])
    report = analyze([loss, train_op],
                     feed_shapes={x: ((2, 4), np.float32)})
    codes = {f.code for f in report.findings}
    assert "HT112" in codes      # duplicate trainable name
    assert "HT111" in codes      # trainable but never updated


# ---------------------------------------------------------------------------
# pass 2: sharding
# ---------------------------------------------------------------------------

def test_unmappable_status_becomes_ht201_with_collector():
    from hetu_tpu.context import NodeStatus
    from hetu_tpu.parallel.planner import spec_for_status
    st = NodeStatus(state=(1, 3), duplicate=1)    # 3-way split...
    axes = {"tp0": 2}                             # ...on a 2-axis mesh
    report = Report()
    with collecting(report):
        assert spec_for_status(st, axes, node="w_tp") is None
    assert [f for f in report.errors if f.code == "HT201"]
    assert "w_tp" in report.errors[0].message


def test_unmappable_status_warns_without_collector(caplog):
    import logging
    from hetu_tpu.context import NodeStatus
    from hetu_tpu.parallel.planner import spec_for_status
    st = NodeStatus(state=(1, 3), duplicate=1)
    with caplog.at_level(logging.WARNING,
                         logger="hetu_tpu.parallel.planner"):
        assert spec_for_status(st, {"tp0": 2}, node="w_tp") is None
    assert any("unmappable" in r.message for r in caplog.records)


def test_emit_returns_false_without_collector():
    assert emit("HT999", "error", "nobody listening") is False
    report = Report()
    with collecting(report):
        assert emit("HT999", "error", "captured", node="n0") is True
    assert len(report) == 1 and report.errors[0].node == "n0"


def test_tp_plan_over_device_budget_is_ht204():
    with ht.context((ht.cpu(0), ht.cpu(1))):
        x = ht.Variable("x", trainable=False)
        w = ht.Variable("w_big", value=np.zeros((8, 64), "f"))
        wd = ht.dispatch(w, (1, 2))
        y = ht.matmul_op(x, wd)
        loss = ht.reduce_mean_op(y, [0])
    from hetu_tpu.analysis.sharding import sharding_pass
    from hetu_tpu.graph.autodiff import find_topo_sort
    report = Report()
    sharding_pass(find_topo_sort([loss]), report, ndevices=1)
    assert [f for f in report.errors if f.code == "HT204"]


# ---------------------------------------------------------------------------
# pass 3: deadlock
# ---------------------------------------------------------------------------

def test_clean_gpipe_and_1f1b_schedules_have_zero_errors():
    nodes = _staged_2rank()
    for schedule, kw in (("gpipe", {}),
                        ("1f1b", {"num_microbatches": 4})):
        report = Report()
        deadlock_pass(nodes, report, schedule=schedule, nprocs=2, **kw)
        assert not report.errors, (schedule, report.to_text())


def test_collective_chain_contract_clean():
    nodes = _staged_2rank()
    report = Report()
    deadlock_pass(nodes, report, schedule="collective", nprocs=2)
    assert not report.errors, report.to_text()


def test_cross_rank_cycle_is_ht302_naming_both_ranks():
    nodes = _staged_2rank(back_edge=True)
    t0 = time.monotonic()
    report = Report()
    deadlock_pass(nodes, report, schedule="gpipe", nprocs=2)
    elapsed = time.monotonic() - t0
    errs = [f for f in report.errors if f.code == "HT302"]
    assert errs, report.to_text()
    text = " ".join(f.message for f in errs)
    assert "rank 0" in text and "rank 1" in text
    assert elapsed < 5.0


def test_mutated_schedule_lost_send_is_ht301():
    """Mis-pair the schedule the way a mutated splice_send_recv output
    would: rank 0's boundary send never happens — rank 1 must be
    reported as blocking forever on a transfer nobody makes."""
    plan = build_plan(_staged_2rank(), nprocs=2)
    assert plan is not None and plan.nranks == 2
    programs = rank_programs(plan, schedule="gpipe")
    programs[0] = [ev for ev in programs[0] if ev.kind != "send"]
    report = Report()
    assert not simulate(programs, report)
    errs = [f for f in report.errors if f.code == "HT301"]
    assert errs, report.to_text()
    assert "rank 1" in errs[0].message and "rank 0" in errs[0].message


def test_unpaired_markers_are_ht304():
    from hetu_tpu.ops.comm import PipelineSendOp
    pending_before = PipelineSendOp.pending()
    try:
        recv = ht.pipeline_receive_op(source=0, ctx=ht.cpu(0))
        y = ht.relu_op(recv)
        report = Report()
        deadlock_pass([y], report, schedule="gpipe", nprocs=2)
        assert [f for f in report.errors if f.code == "HT304"]
    finally:
        stale = [s for s in PipelineSendOp.pending()
                 if s not in pending_before]
        PipelineSendOp.consume(stale)


def test_collective_order_divergence_is_ht303():
    programs = {
        0: [Event("collective", tag="AllReduceOp", label="g1"),
            Event("collective", tag="AllGatherOp", label="g2")],
        1: [Event("collective", tag="AllGatherOp", label="g2"),
            Event("collective", tag="AllReduceOp", label="g1")],
    }
    report = Report()
    collective_order_pass(programs, report)
    errs = [f for f in report.errors if f.code == "HT303"]
    assert errs and "#0" in errs[0].message


# ---------------------------------------------------------------------------
# pass 4: memory
# ---------------------------------------------------------------------------

def test_parse_bytes_units():
    assert parse_bytes("8G") == 8 * 2 ** 30
    assert parse_bytes("512MiB") == 512 * 2 ** 20
    assert parse_bytes("1024") == 1024
    assert parse_bytes(2048) == 2048
    with pytest.raises(ValueError):
        parse_bytes("eight gigs")


def test_memory_budget_ht401_and_breakdown():
    nodes, feeds, _ = _mlp_nodes()
    report = analyze(nodes, feed_shapes=feeds, hbm_budget="64K")
    errs = [f for f in report.errors if f.code == "HT401"]
    assert errs and "64.0KiB" in errs[0].message
    info = next(f for f in report.infos if f.code == "HT402")
    # params: 784*256 + 128*10... w2=256x10: (784*256 + 256*10) * 4B
    assert info.data["param_bytes"] == (784 * 256 + 256 * 10) * 4
    assert info.data["grad_bytes"] == info.data["param_bytes"]  # SGD
    assert info.data["opt_slot_bytes"] == 0
    # a generous budget stays clean
    assert analyze(nodes, feed_shapes=feeds, hbm_budget="8G").ok


# ---------------------------------------------------------------------------
# zoo: every model preflights error-free (the CI gate's in-proc twin)
# ---------------------------------------------------------------------------

def test_all_zoo_models_preflight_clean():
    from hetu_tpu.analysis import zoo
    failed = {}
    for name in sorted(zoo.ZOO):
        nodes, feeds = zoo.build(name)
        report = analyze(nodes, feed_shapes=feeds)
        if report.errors:
            failed[name] = report.to_text()
    assert not failed, failed


# ---------------------------------------------------------------------------
# frozen-graph pass (serving contract)
# ---------------------------------------------------------------------------

def test_frozen_graph_pass_flags_training_ops():
    nodes, _, _ = _mlp_nodes()
    report = analyze(nodes, frozen=True)
    assert [f for f in report.errors if f.code == "HT150"]
    # eval-only closure is clean
    loss = nodes[0]
    assert not [f for f in analyze([loss], frozen=True).errors
                if f.code in ("HT150", "HT151", "HT152")]


def test_inference_session_raises_via_analysis():
    from hetu_tpu.serving import InferenceSession
    nodes, _, _ = _mlp_nodes()
    with pytest.raises(ValueError, match="OptimizerOp"):
        InferenceSession(nodes, ctx=ht.cpu(0))


# ---------------------------------------------------------------------------
# jit-purity self-lint
# ---------------------------------------------------------------------------

def test_jit_purity_flags_clock_rng_io():
    src = """
import time, os
import numpy as np
import jax

@jax.jit
def step(x):
    t = time.time()
    r = np.random.randn(4)
    os.getenv("HOME")
    return x * t + r.sum()
"""
    report = check_source(src)
    codes = [f.code for f in report.errors]
    assert "HTP01" in codes and "HTP02" in codes and "HTP03" in codes


def test_jit_purity_traced_local_def_and_branches():
    src = """
import jax

def outer(xs):
    def body(carry, x):
        if x > 0:
            carry = carry + x
        return carry, x
    return jax.lax.scan(body, 0.0, xs)
"""
    report = check_source(src)
    assert [f for f in report.findings
            if f.code == "HTP20" and f.node == "body"]


def test_jit_purity_jit_ok_suppression_and_host_code_ignored():
    src = """
import time
import numpy as np
import jax

@jax.jit
def step(x):
    t = time.time()  # jit-ok: static trace-time stamp, never reread
    return x + t

def host_loop():
    return time.time(), np.random.randn(3)
"""
    report = check_source(src)
    assert not report.findings     # suppressed + untraced host code


def test_jit_purity_cli_clean_on_this_repo():
    from hetu_tpu.analysis.jit_purity import check_paths
    report = check_paths([os.path.join(REPO, "hetu_tpu")])
    assert not report.errors, report.to_text()


# ---------------------------------------------------------------------------
# graphboard findings overlay
# ---------------------------------------------------------------------------

def test_graphboard_findings_overlay(tmp_path):
    from hetu_tpu import graphboard
    nodes, _, _ = _mlp_nodes()
    exe = Executor({"default": nodes}, ctx=ht.cpu(0))
    report = Report()
    topo = exe.subexecutors["default"].topo_order
    target = next(n for n in topo if n.op_type == "MatMulOp")
    report.add("HT101", "error", "planted finding", node=target)
    out = tmp_path / "board.html"
    graphboard.render(exe, str(out), findings=report)
    html = out.read_text()
    assert "HT101" in html and "#cc1f1f" in html
    dot = (tmp_path / "board.dot").read_text()
    assert "HT101" in dot and "penwidth" in dot
    # report.by_node: the overlay index keeps the worst severity
    report.add("HT402", "info", "also planted", node=target)
    assert report.by_node()[target.name] == "error"


# ---------------------------------------------------------------------------
# heturun --preflight: the fleet gate
# ---------------------------------------------------------------------------

_CLUSTER_YML = """
nodes:
  - host: localhost
    chief: true
    servers: 0
    workers: 2
"""

_DEADLOCK_SCRIPT = """
import os
import numpy as np
import hetu_tpu as ht
from hetu_tpu.executor import Executor

with ht.context("worker0:cpu:0"):
    x = ht.Variable("x", trainable=False)
    w1 = ht.Variable("w1", value=np.zeros((20, 32), "f"))
    a = ht.relu_op(ht.matmul_op(x, w1))
with ht.context("worker1:cpu:0"):
    w2 = ht.Variable("w2", value=np.zeros((32, 32), "f"))
    b = ht.relu_op(ht.matmul_op(a, w2))
with ht.context("worker0:cpu:0"):
    w3 = ht.Variable("w3", value=np.zeros((32, 10), "f"))
    y_ = ht.Variable("y_", trainable=False)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(b, w3), y_), [0])
    train_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
exe = Executor([loss, train_op], gpipe=True, num_microbatches=4)
# preflight exits inside HetuConfig: this sentinel must never appear
open(os.environ["HETU_TEST_OUT"] + "/WORKER_RAN", "w").write("x")
"""

_CLEAN_SCRIPT = _DEADLOCK_SCRIPT.replace(
    'with ht.context("worker0:cpu:0"):\n    w3',
    'with ht.context("worker1:cpu:0"):\n    w3')


def test_heturun_preflight_rejects_deadlock_fast(tmp_path, capfd):
    """Acceptance: mis-paired 2-stage schedule -> HT3xx naming both
    ranks, refused before any worker process starts."""
    from hetu_tpu.launcher import parse_config, run_preflight
    from hetu_tpu.analysis import EXIT_PREFLIGHT
    cfg_path = tmp_path / "cluster.yml"
    cfg_path.write_text(_CLUSTER_YML)
    script = tmp_path / "train.py"
    script.write_text(_DEADLOCK_SCRIPT)
    cfg = parse_config(str(cfg_path))
    os.environ["HETU_TEST_OUT"] = str(tmp_path)
    try:
        rc = run_preflight(cfg, [sys.executable, str(script)])
    finally:
        os.environ.pop("HETU_TEST_OUT", None)
    assert rc == EXIT_PREFLIGHT == 121
    assert not (tmp_path / "WORKER_RAN").exists(), \
        "preflight spawned a worker"
    out = capfd.readouterr()
    text = out.out + out.err
    assert "HT302" in text and "rank 0" in text and "rank 1" in text


def test_heturun_preflight_cli_clean_graph(tmp_path):
    """Full CLI pass-through: a clean graph preflights OK (rc 0) and
    still does not run the worker body."""
    cfg_path = tmp_path / "cluster.yml"
    cfg_path.write_text(_CLUSTER_YML)
    script = tmp_path / "train.py"
    script.write_text(_CLEAN_SCRIPT)
    env = clean_launcher_env(HETU_TEST_OUT=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.launcher", "-c", str(cfg_path),
         "--preflight", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "preflight: OK" in proc.stdout + proc.stderr
    assert "graph verified clean" in proc.stdout + proc.stderr
    assert not (tmp_path / "WORKER_RAN").exists()


def test_analysis_cli_zoo_subset():
    proc = subprocess.run(
        [sys.executable, "-m", "hetu_tpu.analysis", "mlp", "logreg"],
        env=clean_launcher_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "== mlp: ok" in proc.stdout
    assert "== logreg: ok" in proc.stdout


def test_preflight_report_json_written(tmp_path):
    """The HETU_PREFLIGHT env contract writes a machine-readable
    report at the given path."""
    import json
    nodes = _staged_2rank(back_edge=True)
    report = analyze(nodes, schedule="gpipe", nprocs=2)
    path = tmp_path / "preflight.json"
    with pytest.raises(SystemExit) as ei:
        analysis.finish_preflight(report, str(path))
    assert ei.value.code == analysis.EXIT_PREFLIGHT
    data = json.loads(path.read_text())
    assert data["errors"] >= 1
    assert any(f["code"] == "HT302" for f in data["findings"])
