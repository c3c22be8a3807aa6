"""The cell PR 54 adds (``ling3-flash-vl-serve-longgen-r50``): its family
rehearsed to the end at a tiny size through the public driver, its three
readers on a small recorded fixture (the profile's planes 1.4 ms apart),
its operation and byte counts against values worked by hand, and the
cell's files against what ISSUE 54 names.

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

from benchmark.flops import kda as flops
from benchmark.harness import contract, spec

CELL = "ling3-flash-vl-serve-longgen-r50"
NEW_METRICS = ("kernel.kda_chunk_prefill_roofline",
               "kernel.kda_step_decode_ms",
               "kernel.kda_step_decode_roofline")
CHIP_BYTES = 16909336064        # bytes_limit of one TPU v5 lite


def _copy_with_the_tiny_cell(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "benchmark" / "tests" / "data" / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "tiny-ling", "source": "none",
        "file": "benchmark/tests/data/configs/tiny-ling.json",
        "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-ling-serve", "config": "tiny-ling",
        "traffic": "docqa-tiny", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_request_p95_ms":
            m["workloads"].append("tiny-ling-serve")
    for name in NEW_METRICS + ("statecache.used_pct",):
        bench["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "serve_request_p95_ms",
            "workloads": ["tiny-ling-serve"]})
    path.write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_public_driver_and_the_family_rehearse_to_their_end(
        tmp_path, trace):
    root = _copy_with_the_tiny_cell(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_ROOT)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "tiny-ling-serve", "--seed", "3000000054", "--seconds", "1",
         "--trace", trace, "--rehearse"],
        capture_output=True, text=True, env=env, timeout=1200, cwd=root)
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
    # the timed engine's own slots: what its state_layout() says, the
    # recurrent state in the dtype the configuration states
    (held,) = by_check["engine_state_entry"]
    assert held["ok"] and held["stated"] == "float32"
    assert held["held"] == held["state_layout"]
    assert held["held"]["kda"] == [[5, 2, 1, 128, 128], "float32"]
    (mixer,) = by_check["program_mixer"]
    assert mixer["ok"] and mixer["layer"] == 0
    assert mixer["worst_mixer_error"] < 1e-4
    # every fault of the delta-rule mixer was told from the program's (at
    # these toy widths the logits' part is noise: the chip's run at the
    # published widths is where the whole-forward faults and the 8-bit
    # control have to fail, and do: PERF.md section 6, PR 54)
    from benchmark.reference import ling_kda as reference
    mixer_faults = {x["fault"]: x["caught"] for x in by_check["mutant"]
                    if x.get("part") == "mixer"}
    assert mixer_faults == dict.fromkeys(reference.MIXER_MUTANTS, True)
    assert len(reference.MUTANTS) == 9
    controls = {x["fault"]: x for x in by_check["control"]}
    assert set(controls) == set(reference.CONTROLS) | {"experts_8bit"}
    assert controls["state_bf16"]["caught"]
    # (four checked rows over 16 experts may well pick alike with and
    # without the group limit: the reading is there, the chip decides)
    (router,) = [x for x in by_check["mutant"] if x.get("part") == "router"]
    assert router["fault"] == "group_limit_ignored" and router["rows"] == 4
    window = next(x["window"] for x in lines if "window" in x)
    assert window["jit_compiles"]["at_window_end"] == \
        window["jit_compiles"]["at_window_start"]
    counted = next(x for x in lines if "model_counters_in_window" in x)
    model = counted["model_counters_in_window"]
    # real tokens x the 2 delta-rule layers of the toy's 3
    assert model["prefill_kda_rows"] > 0 and model["decode_kda_rows"] > 0
    assert model["prefill_kda_rows"] % 2 == model["decode_kda_rows"] % 2 == 0
    assert model["prefill_kda_chunks"] > 0 == model["decode_kda_chunks"]
    assert model["decode_moe_group_kept"] > 0
    if trace == "0":
        assert set(last["metrics"]) == {"serve_request_p95_ms", "setup_s"}
    else:
        # on the CPU there is no device plane: the kernels' readers find
        # nothing and are left out; the slots are the engine's own
        assert set(last["metrics"]) == {"statecache.used_pct"}
        assert 0 < last["metrics"]["statecache.used_pct"]["value"] <= 100


# -- the readers on a small recorded fixture --------------------------------

def _fixture(apart_ns=1_400_000):
    """Two decode programs and two prefill programs inside the window
    (device clock 1,000,000-9,000,000 ns), one decode program across its
    end; the kernels' events inside them; the engine's records on a host
    clock whose reading at the window's start is off by ``apart_ns``
    from where the planes would agree."""
    step = "hetu_kda_step:f32[8,32,128]"
    chunk = "hetu_kda_chunk:f32[1,4096,4096]"
    ms = 1_000_000
    ops = [[step, 1.10 * ms, 40_000], [step, 1.20 * ms, 60_000],
           ["fusion:bf16[8,2560]", 1.4 * ms, 100_000],
           [chunk, 3.1 * ms, 300_000], [chunk, 3.5 * ms, 500_000],
           [chunk, 5.1 * ms, 200_000],
           [step, 6.1 * ms, 60_000], [step, 6.3 * ms, 40_000],
           [step, 8.9 * ms, 50_000]]
    modules = [["jit_hetu_paged_decode(1)", 1.05 * ms, 0.6 * ms],
               ["jit_hetu_paged_prefill(2)", 3.0 * ms, 1.2 * ms],
               ["jit_hetu_paged_prefill(2)", 5.0 * ms, 0.4 * ms],
               ["jit_hetu_paged_decode(1)", 6.0 * ms, 0.7 * ms],
               ["jit_hetu_paged_decode(1)", 8.8 * ms, 0.6 * ms]]
    as_int = lambda rows: [[n, int(s), int(d)] for n, s, d in rows]  # noqa: E731
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": as_int(ops)},
            {"name": "XLA Modules", "events": as_int(modules)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 1 * ms, 8 * ms]]}]}]}

    def record(kind, t0, t1, rows):
        return {f"{kind}_kda_rows": rows, "kind": kind,
                "t0_ns": int(t0 * ms) + apart_ns,
                "t1_ns": int(t1 * ms) + apart_ns,
                "state_slots": 32, "state_slots_used": 5}

    facts = {
        "device_kind": "TPU v5 lite", "window_perf_ns": 1 * ms,
        "config": {"num_attention_heads": 32, "head_dim": 128,
                   "serve_dtype": "bfloat16"},
        "programs": [
            record("decode", 1.0, 1.7, 5 * 6),
            record("prefill", 2.9, 4.3, 3000 * 6),
            record("prefill", 4.9, 5.5, 700 * 6),
            record("decode", 5.9, 6.8, 7 * 6)]}
    return trace, facts


def _read(name, trace, facts):
    return spec.load_module("layer_metrics", name).reduce(trace, facts)


def _peaks():
    return spec.read_json(os.path.join(spec.BENCH_DIR, "peaks.json"))[
        "devices"]["TPU v5 lite"]


@pytest.mark.parametrize("apart_ns", [0, 1_400_000, -1_400_000])
def test_the_three_readers_pair_by_order_on_planes_apart(apart_ns):
    """As many programs as records inside the window: they are paired
    by order, wherever the two clocks put them (1.4 ms apart dropped the
    one-millisecond rule's prefill rooflines: PR 47)."""
    trace, facts = _fixture(apart_ns)
    assert _read("kernel.kda_step_decode_ms", trace, facts) == \
        pytest.approx(0.1)
    peaks = _peaks()
    assert _read("kernel.kda_step_decode_roofline", trace, facts) == \
        pytest.approx(100 * 12 * 6 * 4194304 / 200e-6
                      / peaks["hbm_bytes_per_s"])
    # 3,700 x 6 (token, layer) pairs x 41,088 bytes over the 1.0 ms of
    # the chunk kernel's events in the two prefill programs
    assert _read("kernel.kda_chunk_prefill_roofline", trace, facts) == \
        pytest.approx(100 * 3700 * 6 * 41088 / 1000e-6
                      / peaks["hbm_bytes_per_s"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_what_it_reads(name):
    trace, facts = _fixture()
    assert _read(name, None, {}) is None
    # the parent's program: the programs are there, the kernels and the
    # counter are not
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                line["events"] = [["fusion:bf16[4,4096]", 1_550_000, 20]]
    facts["programs"] = [
        {k: v for k, v in r.items() if "kda" not in k}
        for r in facts["programs"]]
    assert _read(name, trace, facts) is None
    assert _read(name, trace, {"device_kind": "TPU v5 lite"}) is None


def test_unequal_counts_fall_back_on_the_clocks():
    """A record more than programs: the clocks decide, within the usual
    slack; with the planes 1.4 ms apart nothing pairs and the roofline
    is left out (the milliseconds stay: they need no record)."""
    trace, facts = _fixture(0)
    extra = dict(facts["programs"][0], t0_ns=8_200_000, t1_ns=8_700_000)
    facts["programs"].append(extra)
    assert _read("kernel.kda_step_decode_roofline", trace, facts) \
        is not None
    trace, facts = _fixture(1_400_000)
    facts["programs"].append(extra)
    assert _read("kernel.kda_step_decode_roofline", trace, facts) is None
    assert _read("kernel.kda_step_decode_ms", trace, facts) == \
        pytest.approx(0.1)


def test_operations_and_bytes_against_a_count_by_hand():
    # a (token, layer) of the chunked form at d = C = 128, 32 heads
    assert flops.chunk_flops_per_row(32, 128, 128) \
        == 32 * (6 * 128 * 128 + 5 * 128 * 128) == 5767168
    # q, k, v, the decay's projection and the output in bfloat16, the
    # write strength float32
    assert flops.chunk_bytes_per_row(32, 128, 2) \
        == 5 * 4096 * 2 + 32 * 4 == 40960 + 128 == 41088
    seconds, bound = flops.chunk_least_seconds_per_row(32, 128, 128, 2,
                                                       _peaks())
    assert bound == "memory" and seconds == pytest.approx(41088 / 819e9)
    assert 5767168 / 197e12 < seconds
    # the state read once and written once, float32
    assert flops.step_bytes_per_row(32, 128) == 2 * 4 * 32 * 128 * 128 \
        == 4194304
    assert 6 * flops.step_bytes_per_row(32, 128) / 2 == 12582912
    names = spec.read_json(os.path.join(
        spec.BENCH_DIR, "layer_metrics", "kda_names.json"))
    from hetu_tpu.ops import kda
    assert "chunk" not in names     # the reader takes kda.CHUNK itself
    assert names["kda_chunk_kernel"] == f"^{kda.CHUNK_NAME}:"
    assert names["kda_step_kernel"] == f"^{kda.STEP_NAME}:"


# -- the cell and its configuration ----------------------------------------

def test_the_cell_resolves_and_reports_what_the_issue_names():
    cell = spec.resolve(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_request_p95_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    # every accepted metric the sarvam cell reports, but the three whose
    # pairing falls silent in a short window of prefills (PR 47) or has
    # been blind since PR 33, and a stall's length: the traced window's
    # two prompts (the schedule is the traffic file's, the same at every
    # seed) may both meet an empty engine, and then there is no stall to
    # time (``engine.decode_stalled_pct`` reads 0 there and stays); and
    # the slots' share, as jamba's
    sarvam = {m["name"] for m in
              spec.resolve("sarvam105b-serve-docqa-r50").per_layer}
    assert names - set(NEW_METRICS) == (sarvam - {
        "model.decode_device_ms", "kernel.moe_experts_prefill_roofline",
        "kernel.mla_prefill_roofline", "engine.stall_ms"}) \
        | {"statecache.used_pct"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_request_p95_ms"
    traffic = cell.traffic
    assert traffic["driver"] == "serve_openloop_public"
    assert traffic["rate_per_s"] == pytest.approx(
        0.5 * traffic["knee_per_s"], rel=0.03)
    engine = traffic["engine"]
    assert engine["max_batch_size"] == 32 and engine["max_len"] == 17408
    assert engine["num_blocks"] == 32 * (16384 + 1024) // 16 == 34816
    assert engine["prefix_cache"] is False
    assert engine["prefill_chunk"] is None and engine["reserve"] == "full"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                     "sigma": 0.8, "min": 512, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 0.6, "min": 64, "max": 1024}
    rows = traffic["sweep"]["rows"]
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    assert traffic["knee_per_s"] == max(sustained)
    assert any(not r["sustained"] for r in rows)
    with pytest.raises(NotImplementedError):
        cell.family().train_flops_per_token(cell.config, 1024)


def test_the_checked_prompts_lie_on_both_sides_of_4096():
    from benchmark.harness import arrivals
    traffic = spec.resolve(CELL).traffic
    said = traffic["population_check"]
    for name, seconds in (("measured", 30),
                          ("traced", traffic["trace_seconds"])):
        _, prompts, _ = arrivals.population(
            traffic, traffic["pre_seconds"] + seconds)
        checked = prompts[:traffic["check_prompts"]]
        assert checked.min() < 4096 < checked.max()
        assert checked.max() <= 6200    # the reference beside the engine
        assert said[name]["first_four_prompts"] == checked.tolist()
        assert said[name]["requests"] == len(prompts)
    # the file's rule: no light or heavy draw of the mix
    assert 5000 <= said["measured"]["mean_prompt"] <= 6300


def test_the_reckoned_bytes_are_over_a_quarter_of_the_chip():
    from hetu_tpu.serving.kvcache import kv_block_bytes, state_slot_bytes
    cell = spec.resolve(CELL)
    cfg = cell.family().model_config(cell.config)
    model = cfg.serving_model()
    assert model.param_bytes() == cell.config["sizing"]["parameter_bytes"]
    assert model.param_bytes() / CHIP_BYTES > 0.25
    engine = cell.traffic["engine"]
    held = model.param_bytes() \
        + (engine["num_blocks"] + 1) * kv_block_bytes(cfg, 16) \
        + (engine["max_batch_size"] + 1) * state_slot_bytes(cfg)
    assert (engine["num_blocks"] + 1) * kv_block_bytes(cfg, 16) == 713052160
    assert (engine["max_batch_size"] + 1) * state_slot_bytes(cfg) \
        == 429834240
    assert 0.65 < held / CHIP_BYTES < 0.9
