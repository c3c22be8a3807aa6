"""The cell PR 32 adds (``sarvam105b-serve-docqa-r50``): its driver and
family rehearsed to the end at a tiny size, its readers on a small
recorded fixture, its operation counts against hand-worked values.

The rehearsal's benchmark file (``tests/data/BENCHMARK.json``) is not
this PR's to edit, so the rehearsal runs in a copy of ``benchmark/``
whose rehearsal file has the tiny cell appended: files and entries
only, as a cell is added.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import mla, moe
from benchmark.harness import contract, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sarvam105b-serve-docqa-r50"
NEW_METRICS = ("kernel.moe_experts_decode_ms", "kernel.mla_decode_ms",
               "kernel.moe_experts_prefill_roofline",
               "kernel.moe_experts_decode_roofline",
               "kernel.mla_decode_roofline", "kernel.mla_prefill_roofline",
               "moe.load_imbalance", "kernel.mla_decode_with_gather_ms")


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-sarvam", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-sarvam.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-sarvam-serve", "config": "tiny-sarvam",
        "traffic": "docqa-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-sarvam-serve")
    for name in NEW_METRICS:
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-sarvam-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-sarvam-serve", "--seed", "3000000019", "--seconds", "1",
         "--trace", trace, "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900, cwd=root)
    assert p.returncode == 4, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert set(last) >= set(contract.KEYS)
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    by_check = {}
    for line in lines:
        if "check" in line:
            by_check.setdefault(line["check"], []).append(line)
    # the family's account of `correct`, all three parts, passed
    assert len(by_check["generated_tokens_vs_reference"]) == 2
    assert all(x["ok"] for x in by_check["generated_tokens_vs_reference"])
    assert all(x["ok"] for x in by_check["program_router_and_experts"])
    # and every mutant of the reference was told from the engine
    assert {x["mutant"]: x["caught"] for x in by_check["mutant"]} == \
        dict.fromkeys(("expert_dropped", "top_k_minus_1", "bias_in_weights",
                       "no_rope", "experts_8bit", "bf16_routing"), True)
    # the checked rows were held to the reference FORCED onto the picks
    # the engine reported; the 8-bit control failed the same comparison
    assert all(len(x["pick_distances"]) == len(x["tokens"]) > 0
               for x in by_check["generated_tokens_vs_reference"])
    assert [x["caught"] for x in by_check["control"]] == [True]
    assert len(by_check["free_run"]) == 1
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    # this share holds 2 of 8 experts, 2 picks a token: about a quarter
    assert 0.1 < counted["moe_routed_rows_per_pick"]["decode"] < 0.45
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernel readers find
        # nothing and are left out; the counter's metric is there
        assert set(last["metrics"]) == {"moe.load_imbalance"}
        assert last["metrics"]["moe.load_imbalance"]["value"] >= 1.0


# -- the readers on a small recorded fixture --------------------------------

def _fixture():
    """Two decode programs and one prefill program inside the window
    (1000-9000 ns), one decode program across its end; the kernels'
    events inside them; the engine's records on a host clock that reads
    500 where the window starts."""
    moe_ev, mla_ev, flash = ("hetu_moe_experts:bf16[128,4096]",
                             "hetu_mla_decode:bf16[4,64,512]",
                             "_flash_attention_jit:bf16[64,4096,192]")
    gather, pool = "fusion:bf16[64,16,640]", "fusion:bf16[129,16,640]"
    ops = [[moe_ev, 1100, 100], [gather, 1210, 30], [mla_ev, 1250, 50],
           [moe_ev, 1400, 100], [pool, 1510, 30],
           ["fusion:bf16[4,4096]", 1550, 20], [gather, 6450, 40],
           [flash, 3100, 400], [moe_ev, 3600, 200],
           [moe_ev, 6100, 300], [mla_ev, 6500, 100],
           [moe_ev, 8900, 50]]
    modules = [["jit_hetu_paged_decode(1)", 1050, 600],
               ["jit_hetu_paged_prefill(2)", 3000, 1000],
               ["jit_hetu_paged_decode(1)", 6000, 700],
               ["jit_hetu_paged_decode(1)", 8800, 600]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 1000, 8000]]}]}]}

    def record(kind, t0, t1, **counters):
        return dict({f"{kind}_{k}": v for k, v in counters.items()},
                    kind=kind, t0_ns=t0, t1_ns=t1)

    facts = {
        "device_kind": "TPU v5 lite", "window_perf_ns": 500,
        "clock_slack_ns": 10, "kv_blocks": 128,
        "traffic": {"engine": {"block_size": 16}},
        "config": {"hidden_size": 4096, "moe_intermediate_size": 2048,
                   "num_attention_heads": 64, "kv_lora_rank": 512,
                   "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                   "v_head_dim": 128, "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 520, 1200, moe_expert_visits=20,
                   mla_context_rows=60000),
            record("prefill", 2450, 3600, moe_routed_rows=8000,
                   mla_score_pairs=30000000),
            record("decode", 5480, 6300, moe_expert_visits=30,
                   mla_context_rows=90000),
            record("decode", 8250, 9100, moe_expert_visits=99,
                   mla_context_rows=99)],
        "model_counters": {
            "prefill_moe_rows_by_expert": [10, 30, 20, 20],
            "decode_moe_rows_by_expert": [0, 10, 0, 10]}}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def test_the_ms_readers_take_the_median_over_decode_programs():
    trace, facts = _fixture()
    # the two whole decode programs hold 200 and 300 ns of the experts'
    # kernel, 50 and 100 of the attention's
    assert _read("kernel.moe_experts_decode_ms", trace, facts) == \
        pytest.approx(250e-6)
    assert _read("kernel.mla_decode_ms", trace, facts) == \
        pytest.approx(75e-6)
    # with the gathers that feed it (30 and 40 ns; the pool-sized
    # result of the scatter is not one)
    assert _read("kernel.mla_decode_with_gather_ms", trace, facts) == \
        pytest.approx(110e-6)
    assert _read("moe.load_imbalance", trace, facts) == \
        pytest.approx(40 / 25)


def test_the_rooflines_divide_counted_work_by_the_same_programs_time():
    trace, facts = _fixture()
    peaks = spec.read_json(os.path.join(
        spec.BENCH_DIR, "peaks.json"))["devices"]["TPU v5 lite"]
    # decode: 50 visits x 3 x 4096 x 2048 x 2 bytes over 500 ns
    want = moe.weight_bytes(50, 4096, 2048, 2) / 500e-9 \
        / peaks["hbm_bytes_per_s"] * 100
    assert _read("kernel.moe_experts_decode_roofline", trace, facts) == \
        pytest.approx(want)
    # prefill: 8000 rows x 6 x 4096 x 2048 over the 200 ns inside it
    want = moe.flops(8000, 4096, 2048) / 200e-9 \
        / peaks["bf16_flops_per_s"] * 100
    assert _read("kernel.moe_experts_prefill_roofline", trace, facts) == \
        pytest.approx(want)
    rows = 150000
    least = max(mla.absorbed_bytes(rows, 512, 64, 2)
                / peaks["hbm_bytes_per_s"],
                mla.absorbed_flops(rows, 64, 512, 64)
                / peaks["bf16_flops_per_s"])
    assert _read("kernel.mla_decode_roofline", trace, facts) == \
        pytest.approx(100 * least / 150e-9)
    want = mla.expanded_flops(30000000, 64, 128, 64, 128) / 400e-9 \
        / peaks["bf16_flops_per_s"] * 100
    assert _read("kernel.mla_prefill_roofline", trace, facts) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_its_events(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # a program from before the kernels: the programs are there, the
    # named events are not; and before the counters
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1550, 20]]
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_the_two_percent_rule_drops_a_roofline_and_keeps_the_ms():
    trace, facts = _fixture()
    # the engine's record of the second decode program is lost: one of
    # two programs is unmatched, far over 2%
    facts["programs"] = [r for r in facts["programs"]
                         if r["t0_ns"] != 5480]
    assert _read("kernel.moe_experts_decode_roofline", trace, facts) is None
    assert _read("kernel.mla_decode_roofline", trace, facts) is None
    assert _read("kernel.moe_experts_decode_ms", trace, facts) == \
        pytest.approx(250e-6)
    # the prefill's is still there
    assert _read("kernel.moe_experts_prefill_roofline", trace,
                 facts) is not None


def test_operation_counts_against_hand_worked_values():
    # a routed row: gate, up and down of 4096 x 2048, 2 a multiply-add
    assert moe.flops(1, 4096, 2048) == 6 * 4096 * 2048 == 50331648
    # an expert's weights: 25.17M parameters x 2 bytes
    assert moe.weight_bytes(1, 4096, 2048, 2) == 3 * 4096 * 2048 * 2
    assert mla.absorbed_bytes(1, 512, 64, 2) == 1152
    assert mla.absorbed_flops(1, 64, 512, 64) == 64 * 1088 * 2
    assert mla.expanded_flops(1, 64, 128, 64, 128) == 2 * 64 * 320


def test_the_cell_reports_what_the_issue_names():
    cell = spec.resolve(CELL)
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_request_p95_ms", "setup_s"}
    # its own, and the accepted serve metrics it was appended to: the
    # same as the GPT serve cell reports
    accepted = {m["name"] for m in
                spec.resolve("gpt2s-serve-chat-r50").per_layer}
    assert len(accepted) == 11
    assert {m["name"] for m in cell.per_layer} == \
        set(NEW_METRICS) | accepted
    config, traffic = cell.config, cell.traffic
    assert traffic["driver"] == "serve_openloop_public"
    assert traffic["rate_per_s"] == pytest.approx(
        0.5 * traffic["knee_per_s"])
    engine = traffic["engine"]
    assert engine["num_blocks"] == engine["max_batch_size"] * (
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"]) \
        // engine["block_size"]
    # every published number under its own key, but for the three cuts
    changed = {k for k, v in config["published"].items()
               if config[k] != v}
    assert changed == set(config["reduced"]) == \
        {"num_hidden_layers", "num_experts", "vocab_size"}
    assert config["deployment"]["num_routed_experts"] == \
        config["published"]["num_experts"]
    with pytest.raises(NotImplementedError):
        cell.family().train_flops_per_token(config, 1024)
