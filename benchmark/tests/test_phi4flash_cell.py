"""The cell PR 60 adds (``phi4-mini-flash-serve-reasondeep-r50``): its
family rehearsed to the end at a tiny size through the public driver,
its five readers on a small recorded fixture, its byte and operation
counts against values worked by hand, and its configuration file against
the catalog row it was drawn from.

The rehearsal's benchmark file (``tests/data/BENCHMARK.json``) is not
this PR's to edit, so the rehearsal runs in a copy of ``benchmark/``
whose rehearsal file has the tiny cell appended, as
``test_jamba_cell.py`` does it.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import diff_attn as flops
from benchmark.harness import contract, spec

CELL = "phi4-mini-flash-serve-reasondeep-r50"
NEW_METRICS = ("kernel.diff_attn_decode_ms",
               "kernel.diff_attn_decode_roofline",
               "kernel.diff_attn_prefill_roofline",
               "model.cross_decoder_decode_ms",
               "model.prefill_cross_rows_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHIP_BYTES = 16909336064        # bytes_limit of one TPU v5 lite


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-phi4flash", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-phi4flash.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-phi4flash-serve", "config": "tiny-phi4flash",
        "traffic": "reasondeep-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-phi4flash-serve")
    for name in NEW_METRICS + ("statecache.used_pct",
                               "kvcache.window_used_pct"):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-phi4flash-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    """The family, the mix, the readers and the configuration are found
    by name; every mutant and control is caught at the tiny size."""
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-phi4flash-serve", "--seed", "3000000060", "--seconds", "1",
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
    for part in ("mixer", "gate", "attention", "cross"):
        (reading,) = by_check["program_" + part]
        assert reading["ok"], reading
    from benchmark.reference import phi4flash as reference
    assert {x["fault"]: x["caught"] for x in by_check["mutant"]} == \
        dict.fromkeys(reference.MUTANTS, True)
    assert len(reference.MUTANTS) == 10
    controls = {x["fault"]: x for x in by_check["control"]}
    assert set(controls) == set(reference.CONTROLS)
    assert controls["state_bf16"]["caught"] and \
        controls["state_bf16"]["mixer"]
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    model = counted["model_counters_in_window"]
    # 3 Mamba layers of the toy's 8; a prefill's cross-decoder rows are
    # prompts, far fewer than its tokens
    assert model["prefill_ssm_rows"] == 3 * model["prefill_self_rows"] > 0
    assert 0 < model["prefill_cross_rows"] < model["prefill_self_rows"] / 8
    assert model["decode_cross_rows"] == model["decode_self_rows"] > 0
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernels' readers find
        # nothing and are left out; the counters are the engine's own
        assert set(last["metrics"]) == {
            "model.prefill_cross_rows_pct", "statecache.used_pct",
            "kvcache.window_used_pct"}
        assert 0 < last["metrics"]["model.prefill_cross_rows_pct"][
            "value"] < 100 / 8


# -- the readers on a small recorded fixture --------------------------------

def _fixture():
    """Two decode programs and one prefill program inside the window
    (1000-9000 ns), one decode program across its end; the events inside
    them; the engine's records on a host clock that reads 500 where the
    window starts."""
    a_in, a_out = "hetu_diff_attn_decode_in:bf16[8,2560]", \
        "hetu_diff_attn_decode_out:f32[8,5120]"
    c_in, c_out = "hetu_cross_decoder_in:bf16[8,2560]", \
        "hetu_cross_decoder_out:bf16[8,2560]"
    flash = "hetu_flash_window:bf16[40,2048,128]"
    ops = [[a_in, 1100, 10], ["fusion:gather", 1110, 30],
           [a_out, 1150, 10],                       # 1100..1160: 60
           [c_in, 1200, 10], [a_in, 1220, 10], [a_out, 1250, 10],
           [c_out, 1400, 10],        # attention 40 more; cross 210
           [flash, 3100, 300], [flash, 3500, 500],
           [a_in, 6100, 10], [a_out, 6150, 10],     # 60
           [c_in, 6200, 10], [a_in, 6220, 10], [a_out, 6250, 10],
           [c_out, 6500, 10],        # attention 40 more; cross 310
           [a_in, 8900, 10], [a_out, 8950, 10]]
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

    def record(kind, t0, t1, window, full, cross, own):
        return {f"{kind}_attn_window_rows": window,
                f"{kind}_attn_full_rows": full, f"{kind}_cross_rows": cross,
                f"{kind}_self_rows": own, "kind": kind, "t0_ns": t0,
                "t1_ns": t1}

    facts = {
        "device_kind": "TPU v5 lite", "window_perf_ns": 500,
        "clock_slack_ns": 10,
        "config": {"num_attention_heads": 40, "num_key_value_heads": 20,
                   "assumed": {"head_dim": 64}, "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 520, 1200, 8 * 3 * 512, 8 * 3 * 5000, 3, 3),
            record("prefill", 2450, 3800, 8 * 900000, 8 * 2000, 1, 2000),
            record("decode", 5480, 6300, 8 * 4 * 512, 8 * 4 * 6000, 4, 4),
            record("decode", 8250, 9100, 999, 999, 1, 1)]}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def test_the_five_readers_on_a_recorded_trace():
    trace, facts = _fixture()
    # each whole decode program holds 60 + 40 ns of bracketed attention
    assert _read("kernel.diff_attn_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    # and 210 / 310 ns of cross-decoder: the median of the two
    assert _read("model.cross_decoder_decode_ms", trace, facts) == \
        pytest.approx(260e-6)
    peaks = spec.read_json(os.path.join(spec.BENCH_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]
    # decode: 8 x (3 x 5000 + 4 x 6000) rows x 5,120 bytes over 200 ns
    assert _read("kernel.diff_attn_decode_roofline", trace, facts) == \
        pytest.approx(100 * 8 * 39000 * 5120 / 200e-9
                      / peaks["hbm_bytes_per_s"])
    # prefill: 7,200,000 pairs x 15,360 operations over 800 ns
    assert _read("kernel.diff_attn_prefill_roofline", trace, facts) == \
        pytest.approx(100 * 7200000 * 15360 / 800e-9
                      / peaks["bf16_flops_per_s"])
    # one prompt of 2,000 tokens: its last row alone
    assert _read("model.prefill_cross_rows_pct", trace, facts) == \
        pytest.approx(100 / 2000)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_what_it_reads(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # the parent's program: the programs are there, the events and the
    # counters are not
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1550, 20]]
    facts["programs"] = [
        {k: v for k, v in r.items() if "rows" not in k}
        for r in facts["programs"]]
    assert _read(name, trace, facts) is None
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_the_two_percent_rule():
    trace, facts = _fixture()
    facts["programs"] = [r for r in facts["programs"]
                         if r["t0_ns"] != 5480]
    assert _read("kernel.diff_attn_decode_roofline", trace, facts) is None
    assert _read("kernel.diff_attn_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    assert _read("kernel.diff_attn_prefill_roofline", trace, facts) \
        is not None


def test_counts_worked_by_hand():
    # two maps of a pair: 2 x 64 + 2 x 128 multiply-adds, 20 pairs
    assert flops.score_pair_flops(40, 64) == 2 * 20 * 2 * (64 + 128) \
        == 15360
    assert flops.shared_row_bytes(20, 64, 2) == 5120


# -- the configuration, the traffic and the entries --------------------------

def _cell():
    return spec.resolve(CELL)


def test_the_configuration_is_the_catalog_rows_whole():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    config = _cell().config
    assert config["source"] == row["source_url"]
    assert config["reduced"] == []
    for key, value in row["config"].items():
        assert config[key] == value, key
    # what the row lacks is under ``assumed``, each with its reason
    assumed = config["assumed"]
    for key in ("head_dim", "mamba", "biases", "layer_rule", "pairing",
                "lambda", "band", "swiglu", "positions", "memory",
                "precision", "weights"):
        assert key in assumed, key
    assert "the catalog's config has no such key" in assumed["why"]
    bench = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "phi-4-mini-flash-reasoning"]
    assert entry["source"] == row["source_url"] and entry["reduced"] == []


def test_the_memory_account():
    from benchmark.families import phi4flash as family
    from hetu_tpu.serving import kvcache
    cell = _cell()
    cfg = family.model_config(cell.config)
    served = cfg.serving_model()
    assert served.param_bytes() == 7708133376
    assert str(served.param_bytes()).replace(",", "") in \
        cell.config["sizing"]["parameter_bytes"].replace(",", "")
    engine = cell.traffic["engine"]
    assert engine["num_blocks"] == engine["max_batch_size"] \
        * engine["max_len"] // engine["block_size"] == 34816
    rows = (engine["num_blocks"] + 1) * kvcache.kv_block_bytes(cfg, 16)
    ring = kvcache.ring_blocks(cfg, 16)
    windows = (engine["max_batch_size"] * ring + 1) \
        * kvcache.kv_block_bytes(cfg, 16, "window")
    state = (engine["max_batch_size"] + 1) * kvcache.state_slot_bytes(cfg)
    assert (ring, rows, windows, state) == (
        33, 2852208640, 692715520, 106444800)
    held = served.param_bytes() + rows + windows + state
    assert 0.65 < held / CHIP_BYTES < 0.70


def test_the_traffic_file_is_the_issues_table():
    t = _cell().traffic
    assert t["driver"] == "serve_openloop_public"
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.8, "min": 512, "max": 16384}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "min": 128, "max": 1024}
    assert t["engine"]["max_batch_size"] == 32
    assert t["engine"]["max_len"] == 17408
    assert t["engine"]["prefix_cache"] is False
    assert t["engine"]["prefill_chunk"] is None
    assert (t["pre_seconds"], t["drain_seconds"], t["trace_seconds"],
            t["check_prompts"], t["check_new_tokens"]) == (5, 30, 4, 4, 32)
    assert t["rate_per_s"] == pytest.approx(0.5 * t["knee_per_s"])
    sustained = [r["rate_per_s"] for r in t["sweep"]["rows"]
                 if r["sustained"]]
    assert t["knee_per_s"] in sustained
    for run in ("measured", "traced"):
        first = t["population_check"][run]["first_four_prompts"]
        assert len(first) == t["check_prompts"]
        assert min(first) < 4096 < max(first)
        assert max(first) <= t["population_check"]["longest_checked"]
    # the longest prefill program and the 16,384 context bucket are
    # held to the reference in every measured run
    assert max(t["population_check"]["measured"]["first_four_prompts"]) \
        > 8192
    # the profiled window holds prefills beside a running request
    due = t["population_check"]["traced"]["due_s"]
    assert sum(5.2 < d < 8.0 for d in due) >= 2 and due[0] < 5.0


def test_the_cell_reports_what_the_issue_lists():
    cell = _cell()
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "serve_request_p95_ms"] or {
        m["name"] for m in cell.end_to_end} == {
            "setup_s", "serve_request_p95_ms"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"kernel.ssm_scan_prefill_roofline", "kernel.ssm_step_decode_ms",
            "kernel.ssm_step_decode_roofline", "statecache.used_pct",
            "kvcache.window_used_pct", "kvcache.used_pct",
            "model.decode_program_ms", "model.prefill_device_ms"} <= names
    assert not {"kernel.swa_prefill_roofline", "kernel.gqa_decode_ms",
                "kernel.gqa_decode_roofline"} & names
    for m in cell.per_layer:
        assert m["moves"] == "serve_request_p95_ms"
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
