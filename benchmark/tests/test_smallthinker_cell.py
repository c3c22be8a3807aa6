"""The cell PR 50 adds (``smallthinker21b-train-s8192``): the
configuration against the catalog row's published numbers and a
parameter count by hand, the cell on exactly its metrics, the train
driver and the family rehearsed to the end at a tiny size (in a copy of
``benchmark/`` whose rehearsal file has the tiny cell appended: that
file is not this PR's to edit), every new reader on a small recorded
trace and without one, and the rooflines' counts at a worked example.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import gqa_train, moe_train
from benchmark.harness import contract, device, spec

CELL = "smallthinker21b-train-s8192"
CONFIG = "smallthinker-21b-ep4"
NEW_METRICS = ("kernel.flash_gqa_train_ms_per_step",
               "kernel.flash_gqa_train_roofline",
               "kernel.moe_experts_train_ms_per_step",
               "kernel.moe_experts_train_roofline",
               "moe.train_load_imbalance")
JOINED = ("executor.host_ms_per_step", "executor.ingest_ms_per_step",
          "executor.dispatch_ms_per_step", "device_idle_pct.train")
REDUCED = {"num_hidden_layers": 4, "moe_num_primary_experts": 16,
           "vocab_size": 37984, "sliding_window_layout": [0, 1, 1, 1],
           "rope_layout": [0, 1, 1, 1]}


@pytest.fixture(scope="module")
def cell():
    return spec.resolve(CELL)


def test_every_published_number_is_in_the_file_and_reduced_names_the_rest(
        cell):
    config = cell.config
    published = config["published"]
    assert published["num_hidden_layers"] == 52
    assert published["moe_num_primary_experts"] == 64
    assert published["vocab_size"] == 151936
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert config[key] == (REDUCED[key] if key in REDUCED else value), key
    entry = next(c for c in spec.read_json(os.path.join(
        spec.REPO_ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the share: EP4 of 13 stages, the router as wide as published
    d = config["deployment"]
    assert d["chips_per_layer"] == 4 and d["pipeline_stages"] == 13
    assert config["num_routed_experts"] == 64
    assert config["first_expert"] == 0 and config["not_served"] is True
    assert config["sizing"]["per_chip_batch"] == 1
    # one whole period, as the published layouts begin
    for key in ("sliding_window_layout", "rope_layout"):
        assert config[key] == published[key][:4]


def test_the_parameter_count_by_hand(cell):
    attention = 2560 * (3584 + 512 + 512) + 3584 * 2560
    assert attention == 20_971_520
    expert = 3 * 2560 * 768
    layer = attention + 2560 * 64 + 2 * 2560 + 16 * expert
    assert layer == 115_512_320
    total = 4 * layer + 2 * 37984 * 2560 + 2560
    assert total == 656_529_920
    assert cell.family().param_count(cell.config) == total


def test_the_cell_reports_exactly_its_metrics(cell):
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_per_chip", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        NEW_METRICS + JOINED)
    for m in cell.per_layer:
        assert m["moves"] == "train_tokens_per_s_per_chip"
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    assert cell.traffic["driver"] == "train_executor"
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["check_sequences"] == 1


# -- the rehearsal -----------------------------------------------------------

def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-smallthinker", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-smallthinker.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-smallthinker-train", "config": "tiny-smallthinker",
        "traffic": "lm-s64-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("tiny-smallthinker-train")
    for name in NEW_METRICS:
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["tiny-smallthinker-train"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_train_driver_and_the_family_rehearse_to_their_end(tmp_path,
                                                              trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-smallthinker-train", "--seed", "3000000019", "--seconds",
         "1", "--trace", trace, "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900, cwd=root)
    assert p.returncode == 4, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert set(last) >= set(contract.KEYS)
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    checks = {x["check"]: x for x in lines if "check" in x}
    assert checks["picks_vs_reference"]["ok"]
    assert len(checks["picks_vs_reference"]["rows_differing_by_layer"]) == 4
    assert checks["validate_loss_vs_reference"]["ok"]
    # the scores, then each layer's picks: outputs of the one program,
    # which the reference was forced onto and hands back as its own
    outputs = [x for x in lines
               if x.get("check") == "validate_outputs_vs_reference"]
    assert [x["shape"] for x in outputs] == [[1, 64, 96]] + 4 * [[1, 64, 3]]
    assert all(x["ok"] for x in outputs)
    assert [x["row_error_max"] for x in outputs[1:]] == 4 * [0.0]
    run = next(x for x in lines if "steps" in x and "mfu" in x)
    assert run["jit_compiles_before"] == run["jit_compiles_after"]
    assert run["last_loss"] < run["first_loss"]
    counted = next(x["moe_counters"] for x in lines if "moe_counters" in x)
    # warm-up + window, every step counted; half the experts held
    assert counted["steps"] == last["attempted"] + 2
    picks = counted["steps"] * 4 * 2 * 64 * 3
    assert 0.35 < counted["moe_routed_rows"] / picks < 0.65
    if trace == "0":
        assert set(last["metrics"]) == {"train_tokens_per_s_per_chip",
                                        "setup_s"}
    else:
        # on the CPU there is no device plane: the kernel readers find
        # nothing and are left out; the counter's metric is there
        assert set(last["metrics"]) == {"moe.train_load_imbalance"}
        assert last["metrics"]["moe.train_load_imbalance"]["value"] >= 1.0


# -- the readers on a recorded trace -----------------------------------------

def _facts(with_calls=True):
    config = spec.read_json(os.path.join(
        spec.BENCH_DIR, "configs", CONFIG + ".json"))
    traffic = spec.read_json(os.path.join(
        spec.BENCH_DIR, "traffic", "lm-s8192.json"))
    family = spec.load_module("families", "smallthinker_moe")
    calls = family.attention_calls(config, traffic, 1)
    calls.append({"kind": "moe_counters", "steps": 5,
                  "moe_routed_rows": 5 * 4 * 12288,
                  "moe_expert_visits": 5 * 4 * 16,
                  "layers": [{"moe_rows_by_expert": [900] + [700] * 15}
                             for _ in range(4)]})
    return {"steps": 2, "device_kind": "TPU v5 lite", "config": config,
            "traffic": traffic, "flash_calls": calls if with_calls else []}


def _trace(ms):
    """Two steps' events on one device plane, ``ms`` by short name."""
    events, at = [], 0
    for _ in range(2):
        for name, length in ms.items():
            events.append([name, at, int(length * 1e6)])
            at += int(length * 1e6) + 1000
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.window", 0, at]]}]}]}


RECORDED = {
    "hetu_flash_gqa_fwd:bf16[1,8192,3584]": 3.0,
    "hetu_flash_gqa_window_fwd:bf16[1,8192,3584]": 7.0,
    "hetu_flash_gqa_bwd:bf16[1,8192,3584]": 8.0,
    "hetu_flash_gqa_window_bwd:bf16[1,8192,3584]": 18.0,
    "hetu_moe_experts:bf16[49152,1536]": 4.0,
    "hetu_moe_experts_dx:bf16[49152,768]": 5.0,
    "hetu_moe_experts_dw:f32[16,2560,1536]": 6.0,
    "_flash_attention_jit:bf16[16,1024,768]": 50.0,     # not read here
    "fusion:bf16[8192,2560]": 11.0}


def test_each_new_reader_returns_a_number_on_a_recorded_trace():
    trace, facts = _trace(RECORDED), _facts()
    got = {name: spec.load_module("layer_metrics", name).reduce(trace, facts)
           for name in NEW_METRICS}
    assert got["kernel.flash_gqa_train_ms_per_step"] == pytest.approx(36.0)
    assert got["kernel.moe_experts_train_ms_per_step"] == pytest.approx(15.0)
    assert got["moe.train_load_imbalance"] == pytest.approx(
        900 / ((900 + 15 * 700) / 16))
    peaks = device.peaks("TPU v5 lite")
    flops = 14.0 * 28 * 128 * (33_558_528 + 3 * 25_167_872)
    assert got["kernel.flash_gqa_train_roofline"] == pytest.approx(
        100 * flops / peaks["bf16_flops_per_s"] / 36e-3, rel=1e-6)
    rows = 4 * 12288
    assert got["kernel.moe_experts_train_roofline"] == pytest.approx(
        100 * 18.0 * rows * 2560 * 768 / peaks["bf16_flops_per_s"] / 15e-3,
        rel=1e-6)
    assert all(0 < got[n] <= 100 for n in NEW_METRICS if "roofline" in n)


def test_each_new_reader_returns_none_without_something_to_read():
    facts = _facts()
    others = _trace({"fusion:bf16[8192,2560]": 11.0,
                     "_flash_attention_bwd_jit:bf16[16,1024,768]": 9.0})
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert reader.reduce(None, _facts(with_calls=False)) is None
        if name.startswith("kernel."):
            assert reader.reduce(None, facts) is None
            assert reader.reduce(others, facts) is None      # the parent
            assert reader.reduce(_trace(RECORDED),
                                 _facts(with_calls=False)) is None \
                or name.endswith("ms_per_step")
    # a family that hands plain flash calls (GPT-2's) carries no counter
    gpt2 = dict(facts, flash_calls=[dict(
        kind="forward", b=16, h=12, s=1024, d=64, itemsize=2, causal=True,
        calls=12)])
    assert spec.load_module(
        "layer_metrics", "moe.train_load_imbalance").reduce(None, gpt2) \
        is None
    assert spec.load_module(
        "layer_metrics", "kernel.flash_gqa_train_roofline").reduce(
            _trace(RECORDED), gpt2) is None


# -- the counts at a worked example ------------------------------------------

def test_pairs_inside_the_band_and_under_the_diagonal():
    assert gqa_train.pairs(8192) == 33_558_528                  # 33.6M
    assert gqa_train.pairs(8192, 4096) == 25_167_872            # 25.2M
    assert gqa_train.pairs(8, 3) == 1 + 2 + 3 * 6
    assert gqa_train.pairs(4, 8) == gqa_train.pairs(4) == 10
    by_hand = sum(min(i + 1, 4096) for i in range(8192))
    assert by_hand == gqa_train.pairs(8192, 4096)


def test_attention_operations_and_bytes_of_one_call():
    flops, nbytes = gqa_train.forward(1, 28, 4, 8192, 128, 2, 4096)
    assert flops == 4.0 * 28 * 25_167_872 * 128
    # q and the context a query head, k and v ONCE a group, the lse
    assert nbytes == (2 * 28 + 2 * 4) * 8192 * 128 * 2 + 4 * 28 * 8192
    flops, nbytes = gqa_train.backward(1, 28, 4, 8192, 128, 2, None)
    assert flops == 10.0 * 28 * 33_558_528 * 128
    assert nbytes == (4 * 28 + 4 * 4) * 8192 * 128 * 2 + 4 * 28 * 8192


def test_expert_operations_and_bytes_from_counted_work():
    assert moe_train.flops(768, 2560, 768) == 18.0 * 768 * 2560 * 768
    assert moe_train.flops(1, 2560, 768, moe_train.FORWARD) \
        == 6.0 * 2560 * 768
    # three matrices read twice in bfloat16, three gradients in float32
    assert moe_train.weight_bytes(16, 2560, 768, 2) \
        == 16 * 3 * 2560 * 768 * (2 + 2 + 4)


def test_train_flops_per_token_of_the_share(cell):
    per_token = cell.family().train_flops_per_token(cell.config, 8192)
    assert 1.9e9 < per_token < 2.05e9
