import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark.flops import flash, transformer
from benchmark.harness import arrivals, contract, spec, stats
from benchmark.harness.session import executor_seed
from benchmark.trace import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic is a pure function of the seed --------------------------------

def _traffic():
    return spec.read_json(os.path.join(DATA, "traffic", "chat-tiny.json"))


def test_schedule_is_a_pure_function_of_the_seed():
    a = arrivals.schedule(_traffic(), 3000000019, 30.0, 512)
    b = arrivals.schedule(_traffic(), 3000000019, 30.0, 512)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]


def test_every_seed_offers_the_same_work_at_the_same_moments():
    a = arrivals.schedule(_traffic(), 1, 30.0, 512)
    b = arrivals.schedule(_traffic(), 2, 30.0, 512)
    assert len(a) == len(b) == 180          # 6 requests/s x 30 s
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert a[-1].due_s == pytest.approx(30.0)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))
    faster = arrivals.schedule(_traffic(), 1, 30.0, 512, rate_per_s=12.0)
    assert len(faster) == 360


def test_lengths_respect_their_clips():
    spec_ = {"dist": "lognormal", "median": 160, "sigma": 0.7,
             "min": 64, "max": 512}
    got = arrivals.lengths(spec_, 5000, np.random.RandomState(0))
    assert got.min() == 64 and got.max() == 512
    assert 140 < np.median(got) < 180
    assert (arrivals.lengths({"dist": "fixed", "value": 7}, 3,
                             np.random.RandomState(0)) == 7).all()


def test_executor_seed_keeps_numpy_seeds_in_range():
    for seed in (0, 1, 2 ** 31 + 12345, 2 ** 32 + 7):
        assert 0 <= executor_seed(seed) < 9973
        assert executor_seed(seed) + 0xFFFFFFFF - 9973 < 2 ** 32


# -- percentiles and due-time latency --------------------------------------

def test_percentile_matches_numpy_and_refuses_nothing():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_latency_is_timed_from_the_due_time():
    from benchmark.drivers import serve_openloop as drv
    reqs = [arrivals.Request(0.5, np.zeros(4, np.int32), 3),   # pre-window
            arrivals.Request(1.2, np.zeros(4, np.int32), 3),
            arrivals.Request(1.8, np.zeros(4, np.int32), 2),
            arrivals.Request(1.9, np.zeros(4, np.int32), 2)]
    t0 = 100.0
    out = lambda n: np.zeros(n, np.int32)
    # request 1 was submitted 0.3 s late and done at 1.9: latency is
    # 0.7 s from its due time, not 0.4 s from its submission
    submit = [100.5, 101.5, 101.8, 101.9]
    done = [101.1, 101.9, 102.4, None]
    res = [out(3), out(3), out(2), None]
    s = drv.summarize(reqs, t0, submit, done, res, 1.0, 2.0, 10)
    assert s["attempted"] == 3 and s["failed"] == 1
    assert sorted(s["latencies_ms"]) == pytest.approx([600.0, 700.0])
    # completed INSIDE [1, 2): request 0 (due before it) and request 1
    assert s["out_tokens"] == 6 and s["completed_in_window"] == 2
    assert s["generator_late_ms_max"] == pytest.approx(300.0)
    assert s["well_formed"]
    bad = drv.summarize(reqs, t0, submit, done,
                        [out(3), out(2), out(2), None], 1.0, 2.0, 10)
    assert not bad["well_formed"]


def test_the_drain_ends_when_the_last_request_does():
    import threading
    import time
    from benchmark.drivers import serve_openloop as drv
    head, tail = [1.0, None], [None]

    def finish():
        time.sleep(0.2)
        head[1] = tail[0] = 2.0
    t = threading.Thread(target=finish)
    t0 = time.perf_counter()
    t.start()
    drv.drain([head, tail], 20.0)
    waited = time.perf_counter() - t0
    t.join(timeout=5)
    assert 0.15 < waited < 5.0 and not t.is_alive()
    t0 = time.perf_counter()
    drv.drain([[None]], 0.1)            # never done: the limit holds
    assert 0.1 <= time.perf_counter() - t0 < 2.0


# -- the trace reduction on the recorded trace -----------------------------

@pytest.fixture(scope="module")
def trace():
    return spec.read_json(os.path.join(DATA, "recorded_trace.json"))


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [(1, 4), (5, 8)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6)]) == \
        [(0, 1), (2, 4), (6, 10)]
    assert xplane.total(xplane.clip([(0, 5), (8, 12)], 3, 10)) == 4


def test_busy_union_and_window(trace):
    assert xplane.window(trace) == (1000, 2000)
    assert [p["name"] for p in xplane.device_planes(trace)] == \
        ["/device:TPU:0", "/device:TPU:1"]
    busy_s, window_s, unions = xplane.busy(trace)
    # device 0: 1000-1500, 1700-1950 = 750; device 1: 600 + 100 = 700
    assert xplane.total(unions[0]) == 750
    assert xplane.total(unions[1]) == 700
    assert busy_s == pytest.approx(725e-9)
    assert window_s == pytest.approx(1000e-9)


def test_op_seconds_clip_to_the_window(trace):
    ops = xplane.op_seconds(trace)
    assert "fusion.9" not in ops                   # after the window
    assert ops["fusion.1"] == pytest.approx((200 + 600) / 2 * 1e-9)
    assert ops["custom-call.7"] == pytest.approx(100e-9)
    assert xplane.top(ops, 1)[0][0] == "fusion.1"
    assert xplane.idle_percent(trace) == pytest.approx(27.5)
    assert xplane.idle_percent(None) is None


def test_short_names_survive_renumbering():
    raw = ("%_flash_attention_bwd_jit.21 = (bf16[192,1024,64]{2,1,0:T(8,"
           "128)(2,1)}, bf16[192,1024,64]{2,1,0}) custom-call(bf16[192,"
           "1024,64]{2,1,0} %bitcast.1134)")
    assert xplane.short_name(raw) == \
        "_flash_attention_bwd_jit:bf16[192,1024,64]"
    assert xplane.short_name(
        "%fusion.1499 = bf16[16,1024,768]{2,1,0} fusion(...)") == \
        xplane.short_name(
            "%fusion.7 = bf16[16,1024,768]{2,1,0} fusion(...)") == \
        "fusion:bf16[16,1024,768]"
    assert xplane.short_name("%divide_subtract_fusion = (f32[768,50257]"
                             "{0,1}, f32[768,50257]{0,1}) fusion(") == \
        "divide_subtract_fusion:f32[768,50257]"
    assert xplane.short_name("%all-reduce-start.3 = f32[8]{0} all-"
                             "reduce-start(") == "all-reduce-start:f32[8]"
    assert xplane.short_name("jit_step_fn(123)") == "jit_step_fn(123)"
    names = spec.read_json(os.path.join(spec.BENCH_DIR, "trace",
                                        "names.json"))
    flash_pat = re.compile(names["flash_kernels"])
    assert flash_pat.search(xplane.short_name(raw))
    assert flash_pat.search("_flash_attention_jit:bf16[3072,128,64]")
    assert not flash_pat.search("fusion:bf16[16,1024,768]")


def test_decode_program_is_found_by_its_host_span(trace):
    reader = spec.load_module("layer_metrics", "model.decode_device_ms")
    # inside bench.engine.decode (1600-1700): a 60 ns program and a
    # 10 ns gather; the step is the longer
    assert reader.reduce(trace, {}) == pytest.approx(60e-6)
    assert reader.reduce(None, {}) is None


def test_gap_attribution(trace):
    gaps = xplane.idle_gaps(trace)
    # device 0 gaps: 1500-1700 and 1950-2000; device 1: 1600-1800 and
    # 1900-2000. The innermost span with the largest overlap wins.
    # 1500-1700: feed 120, executor_run 80, engine.step 110, decode 100
    #   -> bench.feed (200 ns / 2 devices)
    # 1950-2000: read_loss 50 = engine.step 50, read_loss is shorter
    # 1600-1800: engine.step 200 -> bench.engine.step
    # 1900-2000: engine.step 100 -> bench.engine.step
    assert gaps["bench.feed"] == pytest.approx(100e-9)
    assert gaps["bench.read_loss"] == pytest.approx(25e-9)
    assert gaps["bench.engine.step"] == pytest.approx(150e-9)
    assert sum(gaps.values()) == pytest.approx((1000 - 725) * 1e-9)
    assert "bench.window" not in gaps


def test_gap_attribution_sweep_agrees_with_every_gap_against_every_span():
    rng = np.random.RandomState(5)
    ops, t = [], 1000
    for _ in range(400):
        d = int(rng.randint(5, 60))
        ops.append(["op", t, d])
        t += d + int(rng.randint(1, 40))
    # distinct lengths, so that no two spans tie on (overlap, length)
    spans = [[f"bench.s{i % 7}", int(rng.randint(900, t)), 50 + 3 * i]
             for i in range(120)]
    spans.append(["bench.window", 1000, t - 1000])
    trace = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": spans}]}]}
    lo, hi = xplane.window(trace)
    expected = {}
    for gap in xplane.subtract([(lo, hi)], xplane.busy(trace)[2][0]):
        best, best_key = "host:other", (0, 0)
        for n, s, d in spans[:-1]:
            key = (xplane.overlap(gap, (s, s + d)), -d)
            if key[0] and key > best_key:
                best, best_key = n, key
        expected[best] = expected.get(best, 0.0) + (gap[1] - gap[0]) / 1e9
    got = xplane.idle_gaps(trace)
    assert got.keys() == expected.keys()
    for name, seconds in expected.items():
        assert got[name] == pytest.approx(seconds)


# -- operations and bytes against hand-worked values -----------------------

def test_training_flops_per_token():
    # GPT-2 small, S=1024: 12 * (8*768^2 + 2*1024*768 + 4*768*3072)
    #   + 2*768*50257 = 12 * 15728640 + 77194752 = 265938432; x3
    assert transformer.gpt_train_flops_per_token(
        1024, 768, 12, 3072, 50257) == 3.0 * 265938432
    # BERT-base, S=128: 12 * (4718592 + 4*128*768 + 9437184)
    #   + 2*768*30522 = 12 * 14548992 + 46881792 = 221469696; x3
    assert transformer.bert_train_flops_per_token(
        128, 768, 12, 3072, 30522) == 3.0 * 221469696


def test_flash_calls():
    # b=2 h=3 s=8 d=4, bf16: pairs = 2*3*64 = 384
    f, n = flash.forward(2, 3, 8, 4, 2, causal=False, with_lse=False)
    assert f == 4 * 384 * 4 and n == 4 * 2 * 3 * 8 * 4 * 2
    f, n = flash.forward(2, 3, 8, 4, 2, causal=True, with_lse=True)
    assert f == 4 * 192 * 4 and n == 4 * 192 * 2 + 4 * 48
    f, n = flash.backward(2, 3, 8, 4, 2, causal=True)
    assert f == 10 * 192 * 4 and n == 8 * 192 * 2 + 4 * 48
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flash.least_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flash.least_seconds(100.0, 50.0, peaks) == (5.0, "memory")


def _flash_trace(names):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            [n, 100 * i, 50] for i, n in enumerate(names)]}]}]}


def test_flash_least_time_is_the_familys_calls_the_trace_shows():
    from benchmark.families import bert, gpt2
    from benchmark.trace import flash_calls
    config = {"num_attention_heads": 3, "hidden_size": 12,
              "num_hidden_layers": 5}
    calls = gpt2.flash_calls_per_step(config, {"seq_len": 8}, 2)
    assert [c["kind"] for c in calls] == ["forward", "backward"]
    assert all(c["causal"] and c["calls"] == 5 and
               (c["b"], c["h"], c["s"], c["d"]) == (2, 3, 8, 4)
               for c in calls)
    assert not any(c["causal"] for c in
                   bert.flash_calls_per_step(config, {"seq_len": 8}, 2))
    facts = {"steps": 2, "device_kind": "TPU v5 lite",
             "flash_calls": calls}
    peaks = spec.read_json(os.path.join(
        spec.BENCH_DIR, "peaks.json"))["devices"]["TPU v5 lite"]
    fwd = "%_flash_attention_jit.3 = bf16[6,8,4]{2,1,0} custom-call("
    bwd = "%_flash_attention_bwd_jit.9 = (bf16[6,8,4]{2,1,0}) custom-call("
    other = "%fusion.1 = bf16[6,8,4]{2,1,0} fusion("
    forward_only = _flash_trace([xplane.short_name(n)
                                 for n in (fwd, other, fwd)])
    assert flash_calls.seconds_per_step(forward_only, facts) == \
        pytest.approx(100e-9 / 2)
    least, _ = flash_calls.least_seconds_per_step(forward_only, facts)
    assert least == pytest.approx(5 * flash.least_seconds(
        *flash.forward(2, 3, 8, 4, 2, True, with_lse=False), peaks)[0])
    both = _flash_trace([xplane.short_name(n) for n in (fwd, bwd, bwd)])
    least, _ = flash_calls.least_seconds_per_step(both, facts)
    assert least == pytest.approx(5 * (
        flash.least_seconds(*flash.forward(2, 3, 8, 4, 2, True,
                                           with_lse=True), peaks)[0]
        + flash.least_seconds(*flash.backward(2, 3, 8, 4, 2, True),
                              peaks)[0]))
    assert flash_calls.seconds_per_step(_flash_trace([other]), facts) \
        is None


# -- what `correct` can see -------------------------------------------------

def test_row_errors():
    want = np.asarray([[1.0, -1.0], [1.0, -1.0]])       # std 1
    got = np.asarray([[1.0, -1.0], [1.3, -1.4]])
    assert stats.row_errors(got, want) == pytest.approx(
        [0.0, np.sqrt((0.09 + 0.16) / 2)])
    with pytest.raises(ValueError):
        stats.row_errors(got[:1], want)


class _Array:
    def __init__(self, a):
        self.a = np.asarray(a)

    def asnumpy(self):
        return self.a


def test_the_train_check_holds_every_position_not_just_the_loss():
    from benchmark.drivers import train_executor as drv
    from benchmark.harness.session import TrainSession
    session = TrainSession(None, (), None, 8, None, loss_tolerance=1e-2,
                           output_tolerance=0.05)
    rng = np.random.RandomState(0)
    logits = rng.normal(0, 0.17, (2, 8, 64)).astype(np.float32)
    want = (4.16, [logits])
    seen = []
    near = logits + rng.normal(0, 0.005, logits.shape)
    assert drv.agrees(session, [_Array(4.15), _Array(near)], want,
                      seen.append)
    assert [r["ok"] for r in seen] == [True, True]
    # all-zero logits score the uniform loss, which a bfloat16 loss
    # cannot tell from the reference's: the positions can
    assert not drv.agrees(session, [_Array(4.15), _Array(0 * logits)],
                          want, seen.append)
    assert [r["ok"] for r in seen[2:]] == [True, False]
    # one wrong position among sixteen (a bad mask, a bad kernel block)
    one_bad = near.copy()
    one_bad[1, 5] = rng.normal(0, 0.17, 64)
    assert not drv.agrees(session, [_Array(4.15), _Array(one_bad)], want,
                          seen.append)
    assert seen[-1]["worst_row"] == 13
    assert not drv.agrees(session, [_Array(4.5), _Array(near)], want,
                          seen.append)


def test_every_generated_token_is_held_to_the_reference():
    from concurrent.futures import Future
    from benchmark.drivers import serve_openloop as drv

    vocab, new = 16, 4

    def ref_logits(tokens, positions):
        # the "model": the best next token is (last token + 1) % vocab
        rows = np.zeros((len(positions), vocab), np.float32)
        for k, pos in enumerate(positions):
            rows[k, (int(tokens[pos]) + 1) % vocab] = 1.0
        return rows

    class Family:
        LOGIT_TOLERANCE = 0.05

        @staticmethod
        def engine_reference_logits(config, weights, tokens, positions,
                                    pad_to):
            assert len(tokens) == positions[-1] + 1 <= pad_to
            return ref_logits(tokens, positions)

    class Cell:
        config = {}

        @staticmethod
        def family():
            return Family

    class Engine:
        def __init__(self, wrong_at=None):
            self.wrong_at = wrong_at

        def submit(self, prompt, max_new):
            out = [(int(prompt[-1]) + 1 + k) % vocab
                   for k in range(max_new)]
            if self.wrong_at is not None:
                out[self.wrong_at] = (out[self.wrong_at] + 5) % vocab
            f = Future()
            f.set_result(np.asarray(out, np.int32))
            return f

    traffic = {"check_prompts": 2, "check_new_tokens": new,
               "prompt_len": {"max": 6}}
    reqs = [arrivals.Request(0.0, np.asarray([3, 9, 4], np.int32), 8),
            arrivals.Request(0.1, np.asarray([1, 15], np.int32), 8)]
    seen = []
    assert drv.check_tokens(Cell, Engine(), None, reqs, traffic,
                            seen.append)
    assert all(r["logit_gaps"] == [0.0] * new for r in seen)
    # a first token that is right and a LATER one that is wrong (the
    # decode path) fails; the tokens after it are judged on their own
    seen.clear()
    assert not drv.check_tokens(Cell, Engine(wrong_at=2), None, reqs,
                                traffic, seen.append)
    assert seen[0]["logit_gaps"] == [0.0, 0.0, 1.0, 1.0]


# -- BENCHMARK.json obeys the contract's character rules -------------------

def test_names_units_and_shapes_of_the_benchmark_file():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads"):
        got = [x["name"] for x in BENCH[key]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert c["name"] in {w["config"] for w in BENCH["workloads"]}
        body = spec.read_json(os.path.join(spec.REPO_ROOT, c["file"]))
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]


def test_every_cell_resolves_and_reports_what_it_must():
    for w in BENCH["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == w["chips"]
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert hasattr(cell.family(), "train_flops_per_token")
        assert hasattr(cell.driver(), "run")
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).reduce)


# -- the last line ----------------------------------------------------------

def test_result_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    line = json.loads(contract.result_line(
        True, 400, 0, {"setup_s": (95.3127, "s")}, device))
    assert set(line) == set(contract.KEYS)
    assert line["metrics"] == {"setup_s": {"value": 95.3127, "unit": "s"}}
    traced = json.loads(contract.result_line(
        True, 4, 0, {"x": (1, "ms")}, dict(device, busy_s=1.0,
                                            window_s=2.0),
        {"device_ops": [["a", 1.0]] * 12, "idle_gaps": []}))
    assert set(traced) == set(contract.KEYS) | {"breakdown"}
    assert len(traced["breakdown"]["device_ops"]) == 10
    with pytest.raises(ValueError):
        contract.result_line(True, 1, 0, {"x": (float("nan"), "ms")},
                             device)
    with pytest.raises(KeyError):
        contract.result_line(True, 1, 0, {}, {"platform": "tpu"})


# -- a later PR adds files and entries, and edits nothing -------------------

DUMMY_DRIVER = '''
from benchmark.harness.outcome import Outcome
def run(cell, opts):
    fam = cell.family()
    return Outcome(correct=True, attempted=cell.traffic["n"], failed=0,
                   setup_s=1.5, end_to_end={"dummy_rate": fam.RATE},
                   facts={"seen": cell.config["width"]})
'''


def test_a_new_config_mix_driver_family_and_metric_are_found(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    (b / "configs" / "dummy.json").write_text(json.dumps(
        {"family": "dummy", "source": "none", "width": 7, "reduced": []}))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"driver": "dummy_driver", "n": 3}))
    (b / "families" / "dummy.py").write_text("RATE = 12.5\n")
    (b / "drivers" / "dummy_driver.py").write_text(DUMMY_DRIVER)
    (b / "layer_metrics" / "dummy.width.py").write_text(
        "def reduce(trace, facts):\n    return facts['seen'] * 2\n")
    (b / "layer_metrics" / "dummy.absent.py").write_text(
        "def reduce(trace, facts):\n    return None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy", "source": "none",
                             "file": "benchmark/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append(
        {"name": "dummy_rate", "unit": "ops/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["dummy-cell"]})
    for name in ("dummy.width", "dummy.absent"):
        bench["per_layer"].append(
            {"name": name, "unit": "n", "better": "higher",
             "source": "program_counter", "layer": "dummy",
             "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve("dummy-cell", root=str(root))
    assert sorted(m["name"] for m in cell.end_to_end) == \
        ["dummy_rate", "setup_s"]
    out = cell.driver().run(cell, None)
    assert out.end_to_end == {"dummy_rate": 12.5}
    values = {m["name"]: cell.reader(m["name"]).reduce(None, out.facts)
              for m in cell.per_layer}
    assert values == {"dummy.width": 14, "dummy.absent": None}
    # an old cell still resolves from the copy, and no file that was
    # there has changed
    assert spec.resolve(BENCH["workloads"][0]["name"],
                        root=str(root)).per_layer
    assert all(p.read_bytes() == body for p, body in before.items())


# -- no chip, no number ------------------------------------------------------

def _run(*args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, **kw)


def test_without_a_tpu_it_exits_non_zero_and_prints_no_metric():
    cell = BENCH["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_a_rehearsal_runs_to_its_end_and_never_passes():
    p = _run("--workload", "tiny-bert-train", "--seed", "3000000019",
             "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 4, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == set(contract.KEYS)
    assert last["correct"] is False and last["failed"] == 0
    assert set(last["metrics"]) == {"train_tokens_per_s_per_chip",
                                    "setup_s"}
    assert last["device"]["platform"] == "cpu"


def test_in_a_directory_without_the_program_it_fails(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=env, timeout=600)
    assert p.returncode != 0 and '"metrics"' not in p.stdout
