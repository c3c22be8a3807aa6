"""The cell PR 56 adds (``lfm2-8b-a1b-train-s8192``): the configuration
against the catalog row's published numbers and a parameter count by
hand, the cut's floors, the cell on exactly its metrics, the train
driver and the family rehearsed to the end at a tiny size (in a copy of
``benchmark/`` whose rehearsal file has the tiny cell appended: that
file is not this PR's to edit), every new reader on a small recorded
account and without one, and the roofline's count at a worked example.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import short_conv
from benchmark.harness import contract, device, spec
from benchmark.trace import short_conv_events, step_account

CELL = "lfm2-8b-a1b-train-s8192"
CONFIG = "lfm2-8b-a1b-ep4"
NEW_METRICS = ("kernel.short_conv_train_ms_per_step",
               "kernel.short_conv_train_roofline", "moe.bias_flipped_pct")
JOINED = ("executor.host_ms_per_step", "executor.ingest_ms_per_step",
          "executor.dispatch_ms_per_step", "device_idle_pct.train",
          "step.forward_ms_per_step", "step.backward_ms_per_step",
          "step.optimizer_ms_per_step", "step.mixed_ms_per_step",
          "step.unscoped_pct", "kernel.moe_experts_train_ms_per_step",
          "kernel.moe_experts_train_roofline", "moe.train_load_imbalance",
          "kernel.flash_ms_per_step")
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}


@pytest.fixture(scope="module")
def cell():
    return spec.resolve(CELL)


def test_every_published_number_is_in_the_file_and_reduced_names_the_rest(
        cell):
    config = cell.config
    published = config["published"]
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"LFM2-8B-A1B"' in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is not None:
        assert published == row["config"]
        assert config["source"] == row["source_url"]
    assert published["num_hidden_layers"] == 24
    assert published["num_experts"] == 32 and published["vocab_size"] == 65536
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert config[key] == (REDUCED[key] if key in REDUCED else value), key
    entry = next(c for c in spec.read_json(os.path.join(
        spec.REPO_ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert config["num_routed_experts"] == 32 and config["first_expert"] == 0
    assert config["not_served"] is True
    # the alias the accepted experts' roofline reads the width by
    assert config["moe_ffn_hidden_size"] == config["moe_intermediate_size"]


def test_the_cut_stays_inside_the_floors(cell):
    config, published = cell.config, cell.config["published"]
    # no width cut: a width is any key that is not a count or a list
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert config[key] == published[key]
    # a whole period after the dense layer: 1 attention to 3 convolution
    # layers, the published 6 to 18
    kept = config["layer_types"][config["num_dense_layers"]:]
    assert kept == published["layer_types"][2:6]
    assert published["layer_types"].count("conv") == 3 * \
        published["layer_types"].count("full_attention")
    assert config["layer_types"][0] == published["layer_types"][1] == "conv"
    assert config["num_experts"] * 4 == published["num_experts"]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["deployment"]["chips_per_layer"] == 4
    sizing = config["sizing"]
    assert sizing["per_chip_batch"] == 1
    by_batch = sizing["analysis"]["bytes_by_per_chip_batch"]
    limit = 16909336064
    assert by_batch["1"] <= 0.9 * limit < by_batch["2"]
    assert by_batch["1"] >= 0.25 * limit


def test_the_parameter_count_by_hand(cell):
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert conv == 16_783_360
    attention = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 2 * 64
    assert attention == 10_485_888
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    assert (dense, expert) == (44_040_192, 11_010_048)
    routed = 2048 * 32 + 8 * expert         # the 32 bias values: a buffer
    dense_layer = conv + dense + 4096
    assert dense_layer == 60_827_648
    total = dense_layer + (attention + routed + 4096) \
        + 3 * (conv + routed + 4096) + 16384 * 2048 + 2048
    assert total == 507_820_160
    assert cell.family().param_count(cell.config) == total
    assert cell.config["sizing"]["analysis"]["parameters"] == total
    # ISSUE 56 counted 507,820,288: with the four layers' 32 bias values
    assert total + 4 * 32 == 507_820_288


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
    per_token = cell.family().train_flops_per_token(cell.config, 8192)
    assert 1.30e9 < per_token < 1.33e9


# -- the rehearsal -----------------------------------------------------------

def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-lfm2", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-lfm2.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-lfm2-train", "config": "tiny-lfm2",
        "traffic": "lm-s64-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("tiny-lfm2-train")
    for name in NEW_METRICS + ("moe.train_load_imbalance",):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["tiny-lfm2-train"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_train_driver_and_the_family_rehearse_to_their_end(tmp_path,
                                                              trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-lfm2-train", "--seed", "3000000019", "--seconds", "1",
         "--trace", trace, "--rehearse"],
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
    assert len(checks["picks_vs_reference"]["rows_differing_by_layer"]) == 3
    assert checks["validate_loss_vs_reference"]["ok"]
    outputs = [x for x in lines
               if x.get("check") == "validate_outputs_vs_reference"]
    assert [x["shape"] for x in outputs] == [[1, 64, 96]] + 3 * [[1, 64, 3]]
    assert all(x["ok"] for x in outputs)
    run = next(x for x in lines if "steps" in x and "mfu" in x)
    assert run["jit_compiles_before"] == run["jit_compiles_after"]
    counted = next(x["moe_counters"] for x in lines if "moe_counters" in x)
    # warm-up + window, every step counted; half the experts held (at
    # these widths the scores hardly differ, so the seeded bias decides
    # most picks and the share is loose)
    assert counted["steps"] == last["attempted"] + 2
    assert counted["moe_picks"] == counted["steps"] * 3 * 2 * 64 * 3
    assert 0.2 < counted["moe_routed_rows"] / counted["moe_picks"] < 0.8
    assert 0 < counted["moe_bias_flipped_picks"] < counted["moe_picks"]
    if trace == "0":
        assert set(last["metrics"]) == {"train_tokens_per_s_per_chip",
                                        "setup_s"}
    else:
        # on the CPU there is no device plane: the convolution's readers
        # find nothing and are left out; the counters' metrics are there
        assert set(last["metrics"]) == {"moe.train_load_imbalance",
                                        "moe.bias_flipped_pct"}
        assert 0 < last["metrics"]["moe.bias_flipped_pct"]["value"] < 100


# -- the readers -------------------------------------------------------------

def _facts(with_calls=True):
    config = spec.read_json(os.path.join(
        spec.BENCH_DIR, "configs", CONFIG + ".json"))
    traffic = spec.read_json(os.path.join(
        spec.BENCH_DIR, "traffic", "lm-s8192.json"))
    family = spec.load_module("families", "lfm2_moe")
    calls = family.attention_calls(config, traffic, 1)
    calls.append(family.short_conv_calls(config, traffic, 1))
    calls.append({"kind": "moe_counters", "steps": 5,
                  "moe_routed_rows": 5 * 4 * 8192,
                  "moe_expert_visits": 5 * 4 * 8,
                  "moe_bias_flipped_picks": 5 * 4 * 1600,
                  "moe_picks": 5 * 4 * 8192 * 4,
                  "layers": [{"moe_rows_by_expert": [1100] + [1000] * 7}
                             for _ in range(4)]})
    return {"steps": 2, "device_kind": "TPU v5 lite", "config": config,
            "traffic": traffic, "flash_calls": calls if with_calls else []}


_TRACE = {"planes": [{"name": "/device:TPU:0", "lines": []}]}


def _account(ms_by_op_type, monkeypatch):
    """An account of two steps whose rows are ``ms_by_op_type``."""
    rows = [(f"fusion.{i}", step_account.Attribution(
        "forward" if op_type == "ShortConvOp" else "backward",
        "fwd" if op_type == "ShortConvOp" else "bwd", op_type, "n1"),
        2, int(ms * 2e6)) for i, (op_type, ms) in
        enumerate(ms_by_op_type.items())]
    account = step_account.Account(
        steps=2, total_ns=sum(r[3] for r in rows), by_kind={}, rows=rows,
        program="jit_hetu_step_default")
    monkeypatch.setattr(step_account, "find_profile", lambda: "a.pb")
    monkeypatch.setattr(step_account, "account", lambda path, window: account)
    monkeypatch.setattr(short_conv_events.xplane, "window",
                        lambda trace: (0, 1))


def test_each_new_reader_returns_a_number_on_a_recorded_account(
        monkeypatch):
    _account({"ShortConvOp": 1.5, "_ShortConvGradientOp": 4.5,
              "MatMulOp": 40.0}, monkeypatch)
    facts = _facts()
    got = {name: spec.load_module("layer_metrics", name).reduce(_TRACE, facts)
           for name in NEW_METRICS}
    assert got["kernel.short_conv_train_ms_per_step"] == pytest.approx(6.0)
    peaks = device.peaks("TPU v5 lite")
    least = 4 * 11 * 8192 * 2048 * 2 / peaks["hbm_bytes_per_s"]
    assert got["kernel.short_conv_train_roofline"] == pytest.approx(
        100 * least / 6e-3, rel=1e-6)
    assert 0 < got["kernel.short_conv_train_roofline"] <= 100
    assert got["moe.bias_flipped_pct"] == pytest.approx(100 * 1600 / 32768)


def test_each_new_reader_returns_none_without_something_to_read(
        monkeypatch):
    facts = _facts()
    for name in NEW_METRICS:
        reader = spec.load_module("layer_metrics", name)
        assert reader.reduce(None, _facts(with_calls=False)) is None
        if name.startswith("kernel."):
            assert reader.reduce(None, facts) is None
    # the parent's program: an account with no such op
    _account({"MatMulOp": 40.0}, monkeypatch)
    for name in NEW_METRICS[:2]:
        assert spec.load_module("layer_metrics", name).reduce(
            _TRACE, facts) is None
    # no profile under the trace directory
    monkeypatch.setattr(step_account, "find_profile", lambda: None)
    assert spec.load_module("layer_metrics", NEW_METRICS[0]).reduce(
        _TRACE, facts) is None
    # a family whose counters do not count the bias (smallthinker's)
    plain = dict(facts, flash_calls=[{
        "kind": "moe_counters", "steps": 5, "moe_routed_rows": 10,
        "moe_expert_visits": 5, "layers": []}])
    assert spec.load_module("layer_metrics", "moe.bias_flipped_pct").reduce(
        None, plain) is None


def test_short_conv_bytes_of_one_layer():
    flops, nbytes = short_conv.forward(8192, 2048, 3, 2)
    assert nbytes == (3 + 1) * 8192 * 2048 * 2            # 134 MB
    assert flops == 8192 * 2048 * 8
    flops, nbytes = short_conv.backward(8192, 2048, 3, 2)
    assert nbytes == (3 + 1 + 3) * 8192 * 2048 * 2        # 235 MB
    peaks = device.peaks("TPU v5 lite")
    # bound by memory on this chip: a few operations a byte
    assert flops / peaks["bf16_flops_per_s"] \
        < nbytes / peaks["hbm_bytes_per_s"]
