"""The cell PR 39 adds (``xing29b-serve-reason-r50``): its family
rehearsed to the end at a tiny size through the public driver, its
three readers on a small recorded fixture, its byte and operation
counts against values worked by hand, and its configuration file
against the catalog row it was drawn from.

The rehearsal's benchmark file (``tests/data/BENCHMARK.json``) is not
this PR's to edit, so the rehearsal runs in a copy of ``benchmark/``
whose rehearsal file has the tiny cell appended, as
``test_latent_moe_cell.py`` does it.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import mhc as flops
from benchmark.harness import contract, spec

CELL = "xing29b-serve-reason-r50"
NEW_METRICS = ("kernel.mhc_decode_ms", "kernel.mhc_prefill_roofline",
               "kernel.mhc_decode_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHIP_BYTES = 16909336064        # bytes_limit of one TPU v5 lite


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-xing", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-xing.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-xing-serve", "config": "tiny-xing",
        "traffic": "docqa-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-xing-serve")
    for name in NEW_METRICS + ("moe.load_imbalance",):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-xing-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-xing-serve", "--seed", "3000000039", "--seconds", "1",
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
    assert len(by_check["generated_tokens_vs_reference"]) == 2
    assert all(x["ok"] for x in by_check["generated_tokens_vs_reference"])
    assert all(x["ok"] for x in by_check["program_router_and_experts"])
    # the residual path: the first and the last held layer, and the
    # last again where b_res is large enough for the clamp to decide
    residual = by_check["program_residual_path"]
    assert [x["layer"] for x in residual] == [0, 3, 3]
    assert all(x["ok"] for x in residual), residual
    assert all(x["hres_sum_error"] < 1e-4 for x in residual)
    # every mutant of the reference was told from the engine: the
    # sarvam reference's six and the residual path's five
    from benchmark.reference import xing_mhc as reference
    assert {x["mutant"]: x["caught"] for x in by_check["mutant"]} == \
        dict.fromkeys(reference.MUTANTS, True)
    assert len(reference.MUTANTS) == 11
    # the exit's two, which the final norm hides, are run and logged
    hidden = by_check["behind_the_final_norm"]
    assert [x["mutant"] for x in hidden] == ["exit_mean",
                                            "exit_first_stream"]
    assert hidden[0]["caught"] is False
    assert [x["caught"] for x in by_check["control"]] == [True]
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    model = counted["model_counters_in_window"]
    # real tokens x 2 sublayers x 4 layers; an expert layer sees them
    # once, and there are 2
    assert model["decode_mhc_rows"] == 4 * model["decode_moe_tokens"] > 0
    assert model["prefill_mhc_rows"] == 4 * model["prefill_moe_tokens"] > 0
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernels' readers
        # find nothing and are left out
        assert set(last["metrics"]) == {"moe.load_imbalance"}


# -- the readers on a small recorded fixture --------------------------------

def _fixture():
    """Two decode programs and one prefill program inside the window
    (1000-9000 ns), one decode program across its end; the two kernels'
    events inside them; the engine's records on a host clock that reads
    500 where the window starts."""
    pre, post = "hetu_mhc_pre:bf16[8,3584]", "hetu_mhc_post:bf16[8,14336]"
    big_pre, big_post = ("hetu_mhc_pre:bf16[2048,3584]",
                         "hetu_mhc_post:bf16[2048,14336]")
    ops = [[pre, 1100, 40], [post, 1200, 20], [pre, 1300, 40],
           ["hetu_moe_experts:bf16[128,3584]", 1400, 100],
           [big_pre, 3100, 300], [big_post, 3500, 500],
           [pre, 6100, 60], [post, 6300, 40],
           [pre, 8900, 50]]
    modules = [["jit_hetu_paged_decode(1)", 1050, 600],
               ["jit_hetu_paged_prefill(2)", 3000, 1200],
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
        "clock_slack_ns": 10,
        "config": {"hidden_size": 3584, "hc_mult": 4,
                   "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 520, 1200, mhc_rows=48, moe_tokens=18),
            record("prefill", 2450, 3800, mhc_rows=32000),
            record("decode", 5480, 6300, mhc_rows=64, moe_tokens=24),
            record("decode", 8250, 9100, mhc_rows=999)]}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def test_the_three_readers_on_a_recorded_trace():
    trace, facts = _fixture()
    # the two whole decode programs hold 100 and 100 ns of the kernels
    assert _read("kernel.mhc_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    peak = spec.read_json(os.path.join(spec.BENCH_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]["hbm_bytes_per_s"]
    # decode: 112 rows x 71,680 bytes over 200 ns; prefill: 32,000 rows
    # over the 800 ns of the two kernels inside the program
    assert _read("kernel.mhc_decode_roofline", trace, facts) == \
        pytest.approx(100 * 112 * 71680 / 200e-9 / peak)
    assert _read("kernel.mhc_prefill_roofline", trace, facts) == \
        pytest.approx(100 * 32000 * 71680 / 800e-9 / peak)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_its_events(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # the parent's program: the programs are there, the kernels are not
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1550, 20]]
    assert _read(name, trace, facts) is None
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_the_two_percent_rule_and_records_without_the_counter():
    trace, facts = _fixture()
    kept = facts["programs"]
    facts["programs"] = [r for r in kept if r["t0_ns"] != 5480]
    assert _read("kernel.mhc_decode_roofline", trace, facts) is None
    assert _read("kernel.mhc_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    assert _read("kernel.mhc_prefill_roofline", trace, facts) is not None
    # an engine whose programs count no rows (one residual stream)
    facts["programs"] = [{k: v for k, v in r.items()
                          if not k.endswith("mhc_rows")} for r in kept]
    assert _read("kernel.mhc_decode_roofline", trace, facts) is None
    assert _read("kernel.mhc_prefill_roofline", trace, facts) is None


def test_clocks_a_millisecond_apart_still_pair_where_the_counts_agree():
    trace, facts = _fixture()
    want = _read("kernel.mhc_prefill_roofline", trace, facts)
    # the record's host interval 1.4 ms off the program's device one
    prefill = next(r for r in facts["programs"] if r["kind"] == "prefill")
    prefill["t0_ns"] += 1_400_000
    prefill["t1_ns"] += 1_400_000
    facts["clock_slack_ns"] = 1_000_000
    assert _read("kernel.mhc_prefill_roofline", trace, facts) == want
    # a second record in the window: the counts differ, the strict
    # rule stands, and one of two programs' worth is under 98%
    facts["programs"].append(dict(prefill, t0_ns=prefill["t0_ns"] + 10**8,
                                  t1_ns=prefill["t1_ns"] + 10**8))
    assert _read("kernel.mhc_prefill_roofline", trace, facts) is None


def test_bytes_and_operations_against_a_count_by_hand():
    # the stream read once and written once, y read, u written, bf16
    assert flops.bytes_per_row(4, 3584, 2) \
        == (4 * 3584 + 4 * 3584 + 3584 + 3584) * 2 == 71680
    # x~ phi: 14336 x 24 multiply-adds; sum of squares and mix-in:
    # 14336 each; Hres X: 4 x 14336; Hpost y: 14336 — 2 a multiply-add
    assert flops.flops_per_row(4, 3584) \
        == 2 * (14336 * 24 + 14336 + 14336 + 4 * 14336 + 14336) == 888832
    assert flops.two_kernel_ceiling(4) == pytest.approx(10 / 14)
    assert flops.bytes_per_row(1, 128, 4) == 4 * 128 * 4


# -- the cell and its configuration ----------------------------------------

def test_the_cell_resolves_and_reports_what_the_issue_names():
    cell = spec.resolve(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_request_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    # whatever the sarvam cell reports of the accepted metrics, this
    # cell was appended to, or PERF.md section 7 names it
    sarvam = {m["name"] for m in
              spec.resolve("sarvam105b-serve-docqa-r50").per_layer}
    assert names - set(NEW_METRICS) <= sarvam
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "kernels"
            assert m["moves"] == "serve_request_p95_ms"
            assert m["source"] == "device_trace"
    traffic = cell.traffic
    assert traffic["driver"] == "serve_openloop_public"
    assert traffic["rate_per_s"] == pytest.approx(
        0.5 * traffic["knee_per_s"], rel=0.03)
    engine = traffic["engine"]
    assert engine["max_batch_size"] == 32 and engine["max_len"] == 4096
    assert engine["num_blocks"] == 32 * (2048 + 1536) // 16 == 7168
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.7, "min": 128, "max": 2048}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.6, "min": 128, "max": 1536}
    rows = traffic["sweep"]["rows"]
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    assert traffic["knee_per_s"] == max(sustained)
    assert any(not r["sustained"] for r in rows)
    with pytest.raises(NotImplementedError):
        cell.family().train_flops_per_token(cell.config, 1024)


def test_published_equals_the_catalog_row_key_for_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    config = spec.resolve(CELL).config
    assert config["source"] == row["source_url"]
    assert config["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    layers = config["num_hidden_layers"]
    assert layers - config["first_k_dense_replace"] >= 4
    assert layers == 2 + config["sizing"]["expert_layers"]
    d = config["deployment"]
    assert d["chips_per_layer"] == 1 and d["pipeline_stages"] == 8
    assert d["experts_held"] == config["n_routed_experts"] == 64
    assert d["vocab_rows"] == [0, config["vocab_size"]]
    assert "num_nextn_predict_layers" in config["not_served"]
    assert {"hc_entry_exit", "hc_sinkhorn_order", "hc_eps_use",
            "hc_precision", "hc_init", "query", "router_scoring"} \
        <= set(config["assumed"])


def test_the_reckoned_bytes_are_over_a_quarter_of_the_chip():
    from hetu_tpu.serving.kvcache import kv_block_bytes
    cell = spec.resolve(CELL)
    cfg = cell.family().model_config(cell.config)
    model = cfg.serving_model()
    held = model.param_bytes()
    c = cell.config
    # by hand, bf16: attention 28.41M, a dense feed-forward 99.09M, the
    # shared expert and each of 64 routed experts 11.01M, the router
    # and the maps float32, embedding + head 939.5M
    attention = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 \
        + 4096 * 3584
    expert = 3 * 3584 * 1024
    maps = 2 * (14336 * 24 + 3 + 24) * 4
    norms = (2 * 3584 + 768 + 512) * 4
    dense = (attention + 3 * 3584 * 9216) * 2 + maps + norms
    moe = (attention + 65 * expert) * 2 + (3584 * 64 + 64) * 4 + maps \
        + norms
    want = 2 * dense + (c["num_hidden_layers"] - 2) * moe \
        + 2 * 131072 * 3584 * 2 + 3584 * 4
    assert held == want
    pool = (cell.traffic["engine"]["num_blocks"] + 1) \
        * kv_block_bytes(cfg, 16)
    assert (held + pool) / CHIP_BYTES > 0.25
    analysis = c["sizing"]["analysis"]
    chosen = analysis[f"L{c['sizing']['expert_layers']}"]
    assert all(v["spare_share_of_bytes_limit"] >= 0.10
               for v in chosen.values() if isinstance(v, dict))
    # four streams: the state a prefill token holds is counted
    assert model.prefill_bytes_per_token() == 32 * (7 * 192 + 256) * 2 \
        + 6 * 3584 * 4 + 2 * 4 * 3584 * 2
