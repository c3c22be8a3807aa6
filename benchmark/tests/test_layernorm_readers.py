"""The reader of the LayerNorm-backward kernel's events
(``layer_metrics/kernel.layernorm_bwd_ms_per_step``,
``trace/layernorm_calls.py``) against a
hand-made trace whose answers can be worked out on paper."""
import json
import os

import pytest

from benchmark.harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
MINE = "kernel.layernorm_bwd_ms_per_step"
KERNEL = "hetu_layer_norm_bwd:bf16[16384,768]"
FACTS = {"steps": 2}


def reader(name):
    return spec.load_module("layer_metrics", name)


def trace():
    """Two steps in a window 0..1000 us on one chip, three kernel calls
    a step (200, 250 and 150 us; 184.375, 250 and 150), other
    operations between them; a seventh call after the window's end and
    one that straddles it, which no reader may count."""
    us = 1000
    ops = [["fusion:bf16[768]", 0, 50 * us]]
    for t0, first in ((60, 200), (520, 184.375)):
        ops += [[KERNEL, t0 * us, int(first * us)],
                ["fusion:bf16[16,1024,768]", (t0 + 201) * us, 9 * us],
                [KERNEL, (t0 + 210) * us, 250 * us],
                ["_flash_attention_bwd_jit:bf16[192,1024,64]",
                 (t0 + 300) * us, 9 * us],
                [KERNEL, (t0 + 310) * us, 150 * us]]
    ops += [[KERNEL, 990 * us, 100 * us], [KERNEL, 1200 * us, 100 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules",
             "events": [["jit_hetu_step_default(9)", 0, 980 * us]]}]},
        {"name": "/host:CPU", "lines": [{"name": "MainThread", "events": [
            ["bench.window", 0, 1000 * us]]}]}]}


def test_ms_and_calls_per_step(capsys):
    value = reader(MINE).reduce(trace(), FACTS)
    assert value == pytest.approx((200 + 184.375 + 2 * 250 + 2 * 150)
                                  / 1e3 / 2)
    log = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert log["layernorm_bwd_calls_per_step"] == 3
    assert log["layernorm_bwd_ms_per_step"] == pytest.approx(value)
    # per shape: the least and the median call
    assert log["layernorm_bwd_us_per_call"] == {KERNEL: {
        "least": 150.0, "median": 192.1875}}


def test_without_the_kernels_events_the_reader_says_nothing():
    """The parent's program, the serve cell, a CPU rehearsal."""
    old = spec.read_json(os.path.join(DATA, "recorded_trace.json"))
    serve = spec.read_json(os.path.join(DATA, "program_spans_trace.json"))
    no_device = {"planes": [{"name": "/host:CPU", "lines": []}]}
    for t in (old, serve, no_device, None):
        assert reader(MINE).reduce(t, FACTS) is None
    assert reader(MINE).reduce(trace(), {}) is None


def test_the_metric_is_appended_for_the_train_cells():
    m = BENCH["per_layer"][-1]
    train = ["gpt2s-train-s1024", "bert-base-train-s128"]
    assert m["name"] == MINE and m["workloads"] == train
    assert m["layer"] == "kernels" and m["source"] == "device_trace"
    for name in train:
        cell = spec.resolve(name)
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
        assert callable(cell.reader(MINE).reduce)
    serve = spec.resolve("gpt2s-serve-chat-r50")
    assert MINE not in {m["name"] for m in serve.per_layer}
    # no share of a roofline: a call whose operands XLA keeps on chip
    # runs under the HBM floor (PERF.md §6, PR 28)
    assert not [m for m in BENCH["per_layer"]
                if m["name"].startswith("kernel.layernorm")
                and "roofline" in m["name"]]
