"""The cell PR 47 adds (``trinity-large-serve-longchat-r50``): its
family rehearsed to the end at a tiny size through the public driver,
its four readers on a small recorded fixture, its byte counts against
values worked by hand, its configuration file against the catalog row
it was drawn from, and its traffic file's first four prompts against
the rule that puts them on both sides of the window.

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

from benchmark.flops import gqa_window as flops
from benchmark.harness import arrivals, contract, spec

CELL = "trinity-large-serve-longchat-r50"
NEW_METRICS = ("kernel.swa_prefill_roofline", "kernel.gqa_decode_ms",
               "kernel.gqa_decode_roofline", "kvcache.window_used_pct")
MOE_METRICS = ("kernel.moe_experts_decode_ms",
               "kernel.moe_experts_prefill_roofline",
               "kernel.moe_experts_decode_roofline", "moe.load_imbalance")
# the accepted prefill roofline pairs a program with its record within a
# millisecond (``trace/latent_moe_events.py``) and the cell's three or so
# prefill programs a traced window must ALL pair: the driver's traced run
# of this cell read nothing, so the cell is off that metric's list
# (PERF.md section 7); the rehearsal still runs the reader
LISTED_MOE = tuple(m for m in MOE_METRICS
                   if m != "kernel.moe_experts_prefill_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CHIP_BYTES = 16909336064        # bytes_limit of one TPU v5 lite


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-trinity", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-trinity.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-trinity-serve", "config": "tiny-trinity",
        "traffic": "longchat-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-trinity-serve")
    for name in NEW_METRICS + MOE_METRICS:
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-trinity-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-trinity-serve", "--seed", "3000000047", "--seconds", "1",
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
    checked = by_check["generated_tokens_vs_reference"]
    # the first four prompts lie on both sides of the window (8) and of
    # the engine's ring (12 slots; the attention part builds its own of blocks of 16, 32 slots, and takes the prompt of 33)
    assert [x["prompt_len"] for x in checked] == [15, 10, 33, 4]
    assert all(x["ok"] for x in checked)
    window, full = by_check["program_attention"]
    assert (window["kind"], full["kind"]) == ("sliding_attention",
                                              "full_attention")
    for attention in (window, full):
        assert attention["ok"] and attention["prompt_len"] == 33
        assert attention["ring"] == 32
        assert attention["worst_attention_error"] < 1e-4
    assert all(x["ok"] for x in by_check["program_router_and_experts"])
    from benchmark.reference import trinity_afmoe as reference
    caught = {}
    for x in by_check["mutant"]:
        caught[x["mutant"]] = caught.get(x["mutant"], False) or x["caught"]
    # every fault of the reference was told from the engine by the part
    # that answers for it; at these toy widths (float32, logits of
    # spread 0.3) the 8-bit control moves no logit by the chip's limits:
    # the chip's run at the published widths is where it has to fail
    assert caught == dict.fromkeys(reference.MUTANTS, True)
    assert len(reference.MUTANTS) == 9
    assert by_check["control"][0]["control"] == reference.CONTROL
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    model = counted["model_counters_in_window"]
    # 3 sliding layers and 1 full one; 3 expert layers
    assert model["prefill_attn_window_rows"] > 0
    assert model["prefill_attn_window_rows"] % 3 == 0
    assert model["decode_attn_window_rows"] % 3 == 0
    assert model["decode_attn_full_rows"] > 0
    assert model["prefill_moe_tokens"] % 3 == 0
    assert len(model["decode_moe_rows_by_expert"]) == 4
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernels' readers
        # find nothing and are left out; the blocks and the routed rows
        # are the engine's own
        assert set(last["metrics"]) == {"kvcache.window_used_pct",
                                        "moe.load_imbalance"}
        assert 0 < last["metrics"]["kvcache.window_used_pct"]["value"] \
            <= 100


# -- the readers on a small recorded fixture --------------------------------

def _fixture():
    """Two decode programs and one prefill program inside the window
    (1000-9000 ns), one decode program across its end; the attention
    events inside them; the engine's records on a host clock that reads
    500 where the window starts."""
    ring = "hetu_gqa_decode_window_%s:bf16[8,6144]"
    full = "hetu_gqa_decode_full_%s:bf16[8,6144]"
    band = "hetu_flash_window:bf16[48,8192,128]"
    # a decode attention lies between its _in and its _out event: 60
    # and 40 ns in the first program, 60 and 40 in the third
    ops = [[ring % "in", 1100, 2], ["fusion:bf16[257,16,1024]", 1110, 40],
           [ring % "out", 1158, 2],
           [full % "in", 1300, 2], [full % "out", 1338, 2],
           ["fusion:bf16[8,3072]", 1400, 100],
           [band, 3100, 300], [band, 3500, 500],
           ["_flash_attention_jit:bf16[48,8192,128]", 4050, 100],
           [ring % "in", 6100, 2], [ring % "out", 6158, 2],
           [full % "in", 6300, 2], [full % "out", 6338, 2],
           [ring % "in", 8900, 2], [ring % "out", 8948, 2]]
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

    def record(kind, t0, t1, used, **counts):
        return dict({f"{kind}_{k}": v for k, v in counts.items()},
                    kind=kind, t0_ns=t0, t1_ns=t1, window_blocks=4112,
                    window_blocks_used=used, window_hbm_bytes=1078198272)

    facts = {
        "device_kind": "TPU v5 lite", "window_perf_ns": 500,
        "clock_slack_ns": 10,
        "config": {"num_attention_heads": 48, "num_key_value_heads": 8,
                   "head_dim": 128, "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 520, 1200, 514, attn_window_rows=6000,
                   attn_full_rows=4000),
            record("prefill", 2450, 3800, 771, attn_window_rows=9000000,
                   attn_full_rows=5000000),
            record("decode", 5480, 6300, 1028, attn_window_rows=7000,
                   attn_full_rows=5000),
            record("decode", 8250, 9100, 257, attn_window_rows=99,
                   attn_full_rows=99)]}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def test_the_four_readers_on_a_recorded_trace():
    trace, facts = _fixture()
    # the two whole decode programs hold 100 and 100 ns of the two
    # decode attentions
    assert _read("kernel.gqa_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    peaks = spec.read_json(os.path.join(spec.BENCH_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]
    # decode: 22,000 rows x 4,096 bytes over 200 ns; prefill: 9,000,000
    # pairs x 24,576 operations over the 800 ns of the windowed calls
    # (the full layer's flash call is not theirs)
    assert _read("kernel.gqa_decode_roofline", trace, facts) == \
        pytest.approx(100 * 22000 * 4096 / 200e-9
                      / peaks["hbm_bytes_per_s"])
    assert _read("kernel.swa_prefill_roofline", trace, facts) == \
        pytest.approx(100 * 9e6 * 24576 / 800e-9
                      / peaks["bf16_flops_per_s"])
    # the most blocks any of the window's programs saw held
    assert _read("kvcache.window_used_pct", trace, facts) == 25.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_what_it_reads(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # the parent's program: the programs are there, the events, the
    # counters and the window blocks are not
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1550, 20]]
    facts["programs"] = [
        {k: v for k, v in r.items()
         if "attn" not in k and "score" not in k and "window" not in k}
        for r in facts["programs"]]
    assert _read(name, trace, facts) is None
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_the_two_percent_rule_and_records_without_the_counters():
    trace, facts = _fixture()
    kept = facts["programs"]
    facts["programs"] = [r for r in kept if r["t0_ns"] != 5480]
    assert _read("kernel.gqa_decode_roofline", trace, facts) is None
    assert _read("kernel.gqa_decode_ms", trace, facts) == \
        pytest.approx(100e-6)
    assert _read("kernel.swa_prefill_roofline", trace, facts) is not None
    facts["programs"] = [{k: v for k, v in r.items()
                          if "rows" not in k and "pairs" not in k}
                         for r in kept]
    assert _read("kernel.gqa_decode_roofline", trace, facts) is None
    assert _read("kernel.swa_prefill_roofline", trace, facts) is None
    assert _read("kvcache.window_used_pct", trace, facts) == 25.0


def test_operations_and_bytes_against_a_count_by_hand():
    # q k and p v over 128, a multiply-add 2 operations, 48 heads
    assert flops.score_pair_flops(48, 128) == 2 * 2 * 128 * 48 == 24576
    # one k and one v row of 8 key/value heads of 128, bfloat16
    assert flops.cached_row_bytes(8, 128, 2) == 2 * 8 * 128 * 2 == 4096
    # a 16,384-token prompt: 58.7M of a head's 134.2M causal pairs lie
    # inside the band
    inside = sum(min(i + 1, 4096) for i in range(16384))
    assert inside == 58722304 and 16384 * 16385 // 2 == 134225920


# -- the cell, its configuration and its traffic ----------------------------

def test_the_cell_resolves_and_reports_what_the_issue_names():
    cell = spec.resolve(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_request_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) | set(LISTED_MOE) <= names
    assert "kernel.moe_experts_prefill_roofline" not in names
    # every accepted metric the GPT serve cell reports, this cell was
    # appended to, but the one that has been blind since PR 33
    gpt = {m["name"] for m in
           spec.resolve("gpt2s-serve-chat-r50").per_layer}
    assert names - set(NEW_METRICS) - set(LISTED_MOE) \
        == gpt - {"model.decode_device_ms"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_request_p95_ms"
    traffic = cell.traffic
    assert traffic["driver"] == "serve_openloop_public"
    assert traffic["rate_per_s"] == pytest.approx(
        0.5 * traffic["knee_per_s"], rel=0.03)
    engine = traffic["engine"]
    assert engine["max_batch_size"] == 16 and engine["max_len"] == 16896
    assert engine["num_blocks"] == 16 * (16384 + 512) // 16 == 16896
    assert engine["prefix_cache"] is False and engine["block_size"] == 16
    assert engine["prefill_chunk"] is None and engine["reserve"] == "full"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                     "sigma": 0.7, "min": 1024,
                                     "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 160,
                                     "sigma": 0.6, "min": 32, "max": 512}
    rows = traffic["sweep"]["rows"]
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    assert traffic["knee_per_s"] == max(sustained)
    assert any(not r["sustained"] for r in rows)
    with pytest.raises(NotImplementedError):
        cell.family().train_flops_per_token(cell.config, 1024)


@pytest.mark.parametrize("seconds", [30, 4])
def test_the_checked_prompts_lie_on_both_sides_of_the_window(seconds):
    """The driver checks ``requests[:4]``; the population (a function of
    the traffic file and the schedule's length: a measured run's 5 + 30
    s, a traced one's 5 + 4 s) puts at least two of them past the ring
    (4,112 slots: the band's edge and the ring's wrap) and at least one
    inside the window (the short path)."""
    traffic = spec.resolve(CELL).traffic
    _, prompts, _ = arrivals.population(
        traffic, traffic["pre_seconds"] + seconds)
    first = prompts[:traffic["check_prompts"]]
    assert len(first) == 4
    assert sum(p > 4112 for p in first) >= 2
    assert sum(p <= 4096 for p in first) >= 1


def test_the_file_holds_the_catalog_row_key_for_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    config = spec.resolve(CELL).config
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size", "layer_types"]
    assert config["source"] == row["source_url"]
    assert config["reduced"] == reduced
    assert {k: config[k] for k in row["config"] if k not in reduced} \
        == {k: v for k, v in row["config"].items() if k not in reduced}
    assert config["published"] == row["config"]
    bench = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-large-ep8")
    assert entry["reduced"] == reduced
    assert entry["source"] == row["source_url"]
    # one dense layer and one whole period in the published order
    assert config["layer_types"] == ["sliding_attention"] \
        + row["config"]["layer_types"][8:12]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) \
        == (5, 1, 32, 200192 // 8)
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["deployment"]["num_routed_experts"] == 256
    assert {"attention_gate", "qk_norm", "rope_pairs", "norms", "swiglu",
            "window_edge", "router", "embedding", "precision",
            "weights"} <= set(config["assumed"])
    sizing = config["sizing"]
    assert sizing["expert_layers"] == 4
    assert sizing["analysis"]["L4"]["prefill_1x16384"][
        "spare_share_of_bytes_limit"] >= 0.10
    assert sizing["analysis"]["L5"]["prefill_1x16384"][
        "spare_share_of_bytes_limit"] < 0.10
    cfg = spec.resolve(CELL).family().model_config(config)
    assert [cfg.is_sliding(i) for i in range(5)] == [True] * 4 + [False]
    assert cfg.experts_held == (0, 32) and cfg.num_experts == 256


def test_the_reckoned_bytes_are_over_a_quarter_of_the_chip():
    from hetu_tpu.models.window_moe import window_moe_param_shapes
    from hetu_tpu.serving.kvcache import PagedKVCache, kv_block_bytes
    cell = spec.resolve(CELL)
    cfg = cell.family().model_config(cell.config)
    model = cfg.serving_model()
    # by hand (ISSUE 47): q, gate and o 18,874,368 each, k and v
    # 3,145,728 each; the dense SwiGLU 113,246,208; an expert
    # 28,311,552; the router 786,432
    attention = 3 * 3072 * 6144 + 2 * 3072 * 1024
    norms = 4 * 3072 + 2 * 128
    expert = 3 * 3072 * 3072
    assert (attention, expert) == (62914560, 28311552)
    dense = attention + 3 * 3072 * 12288 + norms
    moe = attention + expert + 3072 * 256 + 256 + 32 * expert + norms
    total = dense + 4 * moe + 2 * 25024 * 3072 + 3072
    assert total == sum(
        __import__("math").prod(shape)
        for shape, _ in window_moe_param_shapes(cfg).values())
    small = 5 * norms + 3072 + 4 * (3072 * 256 + 256)   # float32
    assert model.param_bytes() == 2 * (total - small) + 4 * small \
        == cell.config["sizing"]["analysis"]["L4"]["parameter_bytes"]
    assert 0.50 < model.param_bytes() / CHIP_BYTES < 0.52
    # a token's rows, a layer: k and v of 8 heads of 128, bfloat16
    assert kv_block_bytes(cfg, 16) == 16 * 4096 * 1
    assert kv_block_bytes(cfg, 16, "window") == 16 * 4096 * 4
    engine = cell.traffic["engine"]
    full = (engine["num_blocks"] + 1) * 65536
    ring = (16 * 257 + 1) * 65536 * 4
    assert (full, ring) == (1107361792, 1078198272)
    # 2.19 GB with the window allocator, 5.54 GB as five full pools
    assert full + ring == 2185560064 \
        == cell.config["sizing"]["analysis"]["L4"]["pool_bytes"]
    assert 5 * full == 5536808960
    assert model.prefill_bytes_per_token() == 212992
