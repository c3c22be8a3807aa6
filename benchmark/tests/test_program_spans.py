"""The readers of the program's own ``hetu.*`` spans and stable program
names (``layer_metrics/*`` added with them, ``trace/program_spans.py``),
each against a hand-made trace whose answer can be worked out on paper:
``data/program_spans_trace.json`` (its comment gives the arithmetic)."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import spec
from benchmark.trace import program_spans, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
SERVE = ["device_idle_pct.serve_engine", "engine.decode_host_ms",
         "engine.decode_build_ms", "engine.decode_sample_ms",
         "model.prefill_device_ms", "engine.queue_wait_ms"]
TRAIN = ["executor.ingest_ms_per_step", "executor.dispatch_ms_per_step"]


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def serve_trace():
    return spec.read_json(os.path.join(DATA, "program_spans_trace.json"))


@pytest.fixture(scope="module")
def old_trace():
    """A profile of a program from before the spans."""
    return spec.read_json(os.path.join(DATA, "recorded_trace.json"))


def train_trace():
    """Two steps in a window 0..1000 us; a third after it."""
    us = 1000
    host = [["bench.window", 0, 1000 * us]]
    for t0 in (100, 500, 1200):
        host += [["hetu.step", t0 * us, 300 * us],
                 ["hetu.executor.ingest", (t0 + 10) * us, 40 * us],
                 ["hetu.device_dispatch", (t0 + 60) * us, 200 * us],
                 ["hetu.executor.outputs", (t0 + 270) * us, 20 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 0, 900 * us]]},
            {"name": "XLA Modules",
             "events": [["jit_hetu_step_default(9)", 0, 900 * us]]}]},
        {"name": "/host:CPU",
         "lines": [{"name": "MainThread", "events": host}]}]}


# -- the motivation's straddling gap ----------------------------------------

def test_winner_take_all_gives_the_engines_gap_to_the_load_generator(
        serve_trace):
    """What ``breakdown.idle_gaps`` does with this trace: of 1020 us of
    idle, 730 go to the load generator's sleep on another thread,
    though the scheduler waited for work during 190 only."""
    assert xplane.idle_percent(serve_trace) == pytest.approx(51.0)
    gaps = xplane.idle_gaps(serve_trace)
    assert max(gaps, key=gaps.get) == "bench.wait_arrival"
    assert gaps["bench.wait_arrival"] == pytest.approx(730e-6)
    assert gaps["bench.engine.step"] == pytest.approx(280e-6)
    assert gaps["bench.engine.decode"] == pytest.approx(10e-6)
    # the gap between the first two decode programs is among them
    busy = xplane.busy(serve_trace)[2][0]
    assert (1520000, 1700000) in xplane.subtract(
        [xplane.window(serve_trace)], busy)


def test_idle_is_split_at_the_wait_spans_edges(serve_trace, capsys):
    outside, inside, window = program_spans.idle_split(serve_trace)
    assert (outside, inside, window) == (830e3, 190e3, 2000e3)
    value = reader("device_idle_pct.serve_engine").reduce(serve_trace, {})
    assert value == pytest.approx(41.5)
    # the two parts are device_idle_pct.serve, and the reader says how
    # much of the window the scheduler's leaf spans cover
    log = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert value + log["idle_inside_serve_wait_pct"] == pytest.approx(
        reader("device_idle_pct.serve").reduce(serve_trace, {}))
    assert log["serve_leaf_coverage_pct"] == pytest.approx(99.5)


def test_a_wait_that_crosses_the_windows_edge_counts_its_part_inside():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 1000, 200]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.window", 1000, 1000],
            ["hetu.serve.decode.device", 990, 220],
            ["hetu.serve.wait", 1700, 900]]}]}]}
    # idle 1200-2000 = 800, of which the wait covers 1700-2000
    assert program_spans.idle_split(trace) == (500, 300, 1000)


# -- the serving engine's readers --------------------------------------------

def test_decode_host_time_is_the_gap_between_two_decode_programs(
        serve_trace):
    # 1500->1700 and 1900->2000 count; 2200->2800 holds a wait and a
    # prefill and does not
    assert reader("engine.decode_host_ms").reduce(serve_trace, {}) == \
        pytest.approx(0.150)


def test_decode_build_and_sample_medians(serve_trace):
    # builds 40, 80, 25, 25 us
    assert reader("engine.decode_build_ms").reduce(serve_trace, {}) == \
        pytest.approx(0.0325)
    # sample + the finish after it: 60, 40, 90, 35 us; the two finishes
    # that follow a prefill belong to no decode step
    assert reader("engine.decode_sample_ms").reduce(serve_trace, {}) == \
        pytest.approx(0.050)


def test_prefill_program_is_found_by_its_name(serve_trace):
    # 100 and 120 us inside the window; the suffix-prefill program
    # after it has another name
    assert reader("model.prefill_device_ms").reduce(serve_trace, {}) == \
        pytest.approx(0.110)
    names = program_spans.names()
    for key, hit in (("decode_module", "jit_hetu_paged_decode"),
                     ("prefill_module", "jit_hetu_paged_prefill")):
        pat = re.compile(names[key])
        assert pat.search(hit) and pat.search(hit + "(1234567890)")
        assert not pat.search(hit + "_2(1)")
        assert not pat.search("jit_hetu_paged_suffix_prefill(1)")
        assert not pat.search("jit__unknown(7)")


def test_queue_wait_is_the_engines_histogram():
    r = reader("engine.queue_wait_ms")
    assert r.reduce(None, {"serve_queue_wait_ms_p50": 1.25}) == 1.25
    assert r.reduce(None, {}) is None


# -- the executor's readers ---------------------------------------------------

def test_ingest_and_dispatch_per_step_count_the_windows_steps():
    trace, facts = train_trace(), {"steps": 2}
    assert reader("executor.ingest_ms_per_step").reduce(trace, facts) == \
        pytest.approx(0.040)
    assert reader("executor.dispatch_ms_per_step").reduce(trace, facts) \
        == pytest.approx(0.200)
    for name in TRAIN:
        assert reader(name).reduce(trace, {}) is None


# -- a program without the spans ---------------------------------------------

@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_without_program_spans_a_reader_says_nothing(name, old_trace):
    facts = {"steps": 3}
    assert reader(name).reduce(old_trace, facts) is None
    assert reader(name).reduce(None, facts) is None


def test_a_serving_trace_has_no_executor_numbers_and_the_reverse(
        serve_trace):
    # (the engine's dispatches are hetu.device_dispatch too: that
    # reader is kept to the train cells by its "workloads", and by the
    # serve driver counting no "steps")
    assert reader(TRAIN[0]).reduce(serve_trace, {"steps": 3}) is None
    for name in TRAIN:
        assert reader(name).reduce(serve_trace, {}) is None
    for name in SERVE[:-1]:
        assert reader(name).reduce(train_trace(), {"steps": 2}) is None


# -- the benchmark file ---------------------------------------------------------

def test_the_eight_metrics_are_listed_in_their_cells():
    mine = [m for m in BENCH["per_layer"] if m["name"] in SERVE + TRAIN]
    assert [m["name"] for m in mine] == SERVE + TRAIN
    cells = {w["name"]: spec.resolve(w["name"]) for w in BENCH["workloads"]}
    for m in mine:
        assert m["workloads"]
        for cell in m["workloads"]:
            assert m["moves"] in [e["name"] for e in cells[cell].end_to_end]
            assert callable(cells[cell].reader(m["name"]).reduce)


# -- a real profile -------------------------------------------------------------

REHEARSE = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
from benchmark.harness import spec
bench = spec.read_json(run.REHEARSAL_FILE)
for m in spec.read_json({root!r} + "/BENCHMARK.json")["per_layer"]:
    if m["name"] not in {mine!r}:
        continue
    cell = "tiny-gpt2-serve" if "serve" in m["moves"] else "tiny-gpt2-train"
    bench["per_layer"].append(dict(m, workloads=[cell]))
run.REHEARSAL_FILE = {out!r}
json.dump(bench, open(run.REHEARSAL_FILE, "w"))
sys.exit(run.main(["--workload", {cell!r}, "--seed", "3000000019",
                   "--seconds", "1", "--trace", "1", "--rehearse"]))
"""


@pytest.mark.parametrize("cell,expected", [
    ("tiny-gpt2-serve", ["engine.decode_build_ms",
                         "engine.decode_sample_ms",
                         "engine.queue_wait_ms"]),
    ("tiny-gpt2-train", TRAIN)])
def test_a_traced_rehearsal_reads_the_programs_spans(tmp_path, cell,
                                                     expected):
    """The tiny cells under ``jax.profiler`` on the CPU: the spans the
    program writes are found by the names in ``program_names.json``. (No
    device plane here, so the readers of device time say nothing.)"""
    script = REHEARSE.format(root=spec.REPO_ROOT, cell=cell,
                             mine=SERVE + TRAIN,
                             out=str(tmp_path / "BENCHMARK.json"))
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 4, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    for name in expected:
        assert last["metrics"][name]["value"] >= 0, name
        assert last["metrics"][name]["unit"] == "ms"
    absent = set(SERVE + TRAIN) - set(expected)
    assert not absent & set(last["metrics"])
