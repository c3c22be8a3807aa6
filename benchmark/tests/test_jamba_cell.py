"""The cell PR 43 adds (``jamba2-3b-serve-chat-r50``): its family
rehearsed to the end at a tiny size through the public driver, its four
readers on a small recorded fixture, its byte counts against values
worked by hand, and its configuration file against the catalog row it
was drawn from.

The rehearsal's benchmark file (``tests/data/BENCHMARK.json``) is not
this PR's to edit, so the rehearsal runs in a copy of ``benchmark/``
whose rehearsal file has the tiny cell appended, as
``test_xing_cell.py`` does it.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.flops import ssm as flops
from benchmark.harness import contract, spec

CELL = "jamba2-3b-serve-chat-r50"
NEW_METRICS = ("kernel.ssm_scan_prefill_roofline",
               "kernel.ssm_step_decode_ms",
               "kernel.ssm_step_decode_roofline", "statecache.used_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHIP_BYTES = 16909336064        # bytes_limit of one TPU v5 lite


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-jamba", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-jamba.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-jamba-serve", "config": "tiny-jamba",
        "traffic": "docqa-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-jamba-serve")
    for name in NEW_METRICS:
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-jamba-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-jamba-serve", "--seed", "3000000043", "--seconds", "1",
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
    (mixer,) = by_check["program_mixer"]
    assert mixer["ok"] and mixer["layer"] == 0
    assert mixer["worst_mixer_error"] < 1e-4
    # every fault of the reference was told from the engine by one of
    # the two parts; at these toy widths (logits of spread 0.2) the
    # 8-bit control is not: the chip's run at the published widths is
    # where it has to fail, and does (PERF.md section 6, PR 43)
    from benchmark.reference import jamba_ssm as reference
    assert {x["fault"]: x["caught"] for x in by_check["mutant"]} == \
        dict.fromkeys(reference.MUTANTS, True)
    assert len(reference.MUTANTS) == 5
    controls = {x["fault"]: x for x in by_check["control"]}
    assert set(controls) == set(reference.CONTROLS)
    assert controls["state_bf16"]["caught"] and \
        controls["state_bf16"]["mixer"]
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    model = counted["model_counters_in_window"]
    # real tokens x the 3 Mamba layers of the toy's 4
    assert model["prefill_ssm_rows"] > 0 and model["decode_ssm_rows"] > 0
    assert model["prefill_ssm_rows"] % 3 == model["decode_ssm_rows"] % 3 == 0
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernels' readers
        # find nothing and are left out; the slots are the engine's own
        assert set(last["metrics"]) == {"statecache.used_pct"}
        assert 0 < last["metrics"]["statecache.used_pct"]["value"] <= 100


# -- the readers on a small recorded fixture --------------------------------

def _fixture():
    """Two decode programs and one prefill program inside the window
    (1000-9000 ns), one decode program across its end; the kernels'
    events inside them; the engine's records on a host clock that reads
    500 where the window starts."""
    step = "hetu_ssm_step:f32[8,1,5120]"
    scan = "hetu_ssm_scan:bf16[1,2048,5120]"
    ops = [[step, 1100, 40], [step, 1200, 20], [step, 1300, 40],
           ["fusion:bf16[8,2560]", 1400, 100],
           [scan, 3100, 300], [scan, 3500, 500],
           [step, 6100, 60], [step, 6300, 40],
           [step, 8900, 50]]
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

    def record(kind, t0, t1, rows, used):
        return {f"{kind}_ssm_rows": rows, "kind": kind, "t0_ns": t0,
                "t1_ns": t1, "state_slots": 64, "state_slots_used": used}

    facts = {
        "device_kind": "TPU v5 lite", "window_perf_ns": 500,
        "clock_slack_ns": 10,
        "config": {"hidden_size": 2560, "mamba_expand": 2,
                   "mamba_d_state": 16, "mamba_dt_rank": 160,
                   "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 520, 1200, 6 * 26, 6),
            record("prefill", 2450, 3800, 1500 * 26, 7),
            record("decode", 5480, 6300, 7 * 26, 16),
            record("decode", 8250, 9100, 999, 3)]}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def test_the_four_readers_on_a_recorded_trace():
    trace, facts = _fixture()
    # the two whole decode programs hold 100 and 100 ns of the kernel
    assert _read("kernel.ssm_step_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    peak = spec.read_json(os.path.join(spec.BENCH_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]["hbm_bytes_per_s"]
    # decode: 13 x 26 rows x 655,360 bytes over 200 ns; prefill: 39,000
    # rows x 21,248 bytes over the 800 ns of the scans in the program
    assert _read("kernel.ssm_step_decode_roofline", trace, facts) == \
        pytest.approx(100 * 13 * 26 * 655360 / 200e-9 / peak)
    assert _read("kernel.ssm_scan_prefill_roofline", trace, facts) == \
        pytest.approx(100 * 39000 * 21248 / 800e-9 / peak)
    # the most slots any of the window's programs saw held: 16 of 64
    assert _read("statecache.used_pct", trace, facts) == 25.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_what_it_reads(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # the parent's program: the programs are there, the kernels, the
    # counter and the slots are not
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1550, 20]]
    facts["programs"] = [
        {k: v for k, v in r.items() if "ssm" not in k and "slots" not in k}
        for r in facts["programs"]]
    assert _read(name, trace, facts) is None
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_the_two_percent_rule_and_records_without_the_counter():
    trace, facts = _fixture()
    kept = facts["programs"]
    facts["programs"] = [r for r in kept if r["t0_ns"] != 5480]
    assert _read("kernel.ssm_step_decode_roofline", trace, facts) is None
    assert _read("kernel.ssm_step_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    assert _read("kernel.ssm_scan_prefill_roofline", trace, facts) \
        is not None
    facts["programs"] = [{k: v for k, v in r.items()
                          if not k.endswith("ssm_rows")} for r in kept]
    assert _read("kernel.ssm_step_decode_roofline", trace, facts) is None
    assert _read("kernel.ssm_scan_prefill_roofline", trace, facts) is None
    assert _read("statecache.used_pct", trace, facts) == 25.0


def test_bytes_against_a_count_by_hand():
    # x in and y out in bfloat16, dt_rank + 2 d_state float32 in
    assert flops.scan_bytes_per_row(5120, 16, 160, 2) \
        == 5120 * 2 + 5120 * 2 + (160 + 16 + 16) * 4 == 21248
    # the state read once and written once, float32
    assert flops.step_bytes_per_row(5120, 16) == 2 * 5120 * 16 * 4 == 655360
    assert flops.scan_vector_ops_per_row(5120, 16) == 7 * 81920
    # so the scan's share of the bandwidth cannot reach 100: 27 vector
    # operations a byte of the count
    assert flops.scan_vector_ops_per_row(5120, 16) \
        / flops.scan_bytes_per_row(5120, 16, 160, 2) > 25


# -- the cell and its configuration ----------------------------------------

def test_the_cell_resolves_and_reports_what_the_issue_names():
    cell = spec.resolve(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_request_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    # every accepted metric the GPT serve cell reports, this cell was
    # appended to, but the one that has been blind since PR 33
    gpt = {m["name"] for m in
           spec.resolve("gpt2s-serve-chat-r50").per_layer}
    assert names - set(NEW_METRICS) == gpt - {"model.decode_device_ms"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_request_p95_ms"
    traffic = cell.traffic
    assert traffic["driver"] == "serve_openloop_public"
    assert traffic["rate_per_s"] == pytest.approx(
        0.5 * traffic["knee_per_s"], rel=0.03)
    engine = traffic["engine"]
    assert engine["max_batch_size"] == 64 and engine["max_len"] == 4608
    assert engine["num_blocks"] == 64 * (4096 + 512) // 16 == 18432
    assert engine["prefix_cache"] is False
    assert engine["prefill_chunk"] is None and engine["reserve"] == "full"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 0.9, "min": 64, "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 128,
                                     "sigma": 0.6, "min": 32, "max": 512}
    assert traffic["population_seed"] == 43
    rows = traffic["sweep"]["rows"]
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    assert traffic["knee_per_s"] == max(sustained)
    assert any(not r["sustained"] for r in rows)
    with pytest.raises(NotImplementedError):
        cell.family().train_flops_per_token(cell.config, 1024)


def test_the_file_holds_the_catalog_row_key_for_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    config = spec.resolve(CELL).config
    assert config["source"] == row["source_url"]
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["reduced"] == []
    bench = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    assert {"feed_forward", "layer_order", "head_dim", "positions",
            "mamba", "norms", "precision", "weights"} \
        <= set(config["assumed"])
    cfg = spec.resolve(CELL).family().model_config(config)
    assert [i for i in range(28) if cfg.is_attention(i)] == [7, 21]
    assert cfg.d_inner == 5120 and cfg.head_dim == 128
    assert cfg.ssm_layers == 26


def test_the_reckoned_bytes_are_over_a_quarter_of_the_chip():
    from hetu_tpu.models.ssm_hybrid import ssm_hybrid_param_shapes
    from hetu_tpu.serving.kvcache import kv_block_bytes, state_slot_bytes
    cell = spec.resolve(CELL)
    cfg = cell.family().model_config(cell.config)
    model = cfg.serving_model()
    # by hand: a Mamba layer 104,161,472 parameters, an attention layer
    # 76,682,240, the tied embedding 167,772,160, the final norm 2,560
    mamba = 2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 + 16 + 16 \
        + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560 \
        + 2 * 2560 + 2560 * 16384 + 8192 * 2560
    attention = 2560 * (2560 + 2 * 128) + 2560 * 2560 + 2 * 2560 \
        + 2560 * 16384 + 8192 * 2560
    assert (mamba, attention) == (104161472, 76682240)
    total = 26 * mamba + 2 * attention + 65536 * 2560 + 2560
    assert total == 3029337472 == sum(
        int.__mul__(*(shape + (1,))[:2]) for shape, _
        in ssm_hybrid_param_shapes(cfg).values())
    # matrices bfloat16, the rest float32
    small = 26 * (4 * 5120 + 5120 + 192 + 5120 + 5120 * 16 + 5120
                  + 2 * 2560) + 2 * 2 * 2560 + 2560
    assert model.param_bytes() == 2 * (total - small) + 4 * small \
        == 6065100288
    assert model.param_bytes() / CHIP_BYTES > 0.25
    # a sequence's state, whatever its length; a token's rows
    assert state_slot_bytes(cfg) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) \
        == 9318400
    assert kv_block_bytes(cfg, 16) == 16 * 2 * 2 * 128 * 2 == 16 * 1024
    engine = cell.traffic["engine"]
    assert (engine["num_blocks"] + 1) * kv_block_bytes(cfg, 16) \
        == 302006272
    assert (engine["max_batch_size"] + 1) * state_slot_bytes(cfg) \
        == 605696000
