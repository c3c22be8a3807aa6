"""The reader of the dropout-mask kernel's events
(``layer_metrics/kernel.dropout_mask_ms_per_step``,
``trace/dropout_calls.py``) against a hand-made trace whose answers can
be worked out on paper."""
import json
import os

import pytest

from benchmark.harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
MINE = "kernel.dropout_mask_ms_per_step"
MASK = "hetu_dropout_mask:s8[16384,768]"
SMALL = "hetu_dropout_mask:s8[512,768]"
FACTS = {"steps": 2}


def reader(name):
    return spec.load_module("layer_metrics", name)


def trace():
    """Two steps in a window 0..1000 us on one chip. A step makes four
    calls: a forward and a backward mask of 20 and 22 us (20 and 30 in
    the second step) around the fusions that read them, and two of a
    smaller shape, 2 us each. One more call straddles the window's end
    and one lies after it: no reader may count them. LayerNorm's
    kernel and a fusion that carries the mask's name inside its own are
    other events."""
    us = 1000
    ops = [["fusion:bf16[768]", 0, 50 * us]]
    for t0, backward in ((60, 22), (520, 30)):
        ops += [[MASK, t0 * us, 20 * us],
                ["convert_reduce_fusion:f32[16,1024]", (t0 + 21) * us,
                 90 * us],
                [SMALL, (t0 + 120) * us, 2 * us],
                ["hetu_layer_norm_bwd:bf16[16384,768]", (t0 + 130) * us,
                 88 * us],
                [SMALL, (t0 + 220) * us, 2 * us],
                [MASK, (t0 + 230) * us, backward * us],
                ["fusion_hetu_dropout_mask:bf16[768]", (t0 + 260) * us,
                 90 * us]]
    ops += [[MASK, 990 * us, 20 * us], [MASK, 1200 * us, 20 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules",
             "events": [["jit_hetu_step_default(9)", 0, 980 * us]]}]},
        {"name": "/host:CPU", "lines": [{"name": "MainThread", "events": [
            ["bench.window", 0, 1000 * us]]}]}]}


def test_ms_and_calls_per_step(capsys):
    value = reader(MINE).reduce(trace(), FACTS)
    assert value == pytest.approx((20 + 22 + 20 + 30 + 4 * 2) / 1e3 / 2)
    log = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert log["dropout_mask_calls_per_step"] == 4
    assert log["dropout_mask_ms_per_step"] == pytest.approx(value)
    # per shape: the least and the median call
    assert log["dropout_mask_us_per_call"] == {
        MASK: {"least": 20.0, "median": 21.0},
        SMALL: {"least": 2.0, "median": 2.0}}


def test_two_chips_share_the_steps():
    """Per step AND chip, as the other kernel readers count."""
    two = trace()
    second = json.loads(json.dumps(two["planes"][0]))
    second["name"] = "/device:TPU:1"
    two["planes"].insert(1, second)
    assert reader(MINE).reduce(two, FACTS) == pytest.approx(
        reader(MINE).reduce(trace(), FACTS))


def test_without_the_kernels_events_the_reader_says_nothing():
    """The parent's program, the serve cell, a CPU rehearsal."""
    old = spec.read_json(os.path.join(DATA, "recorded_trace.json"))
    serve = spec.read_json(os.path.join(DATA, "program_spans_trace.json"))
    no_device = {"planes": [{"name": "/host:CPU", "lines": []}]}
    for t in (old, serve, no_device, None):
        assert reader(MINE).reduce(t, FACTS) is None
    assert reader(MINE).reduce(trace(), {}) is None


def test_the_metric_is_listed_for_the_train_cells():
    """Looked up by name: a later PR appends after it."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == MINE]
    train = ["gpt2s-train-s1024", "bert-base-train-s128"]
    assert m["workloads"] == train
    assert m["layer"] == "kernels" and m["source"] == "device_trace"
    for name in train:
        cell = spec.resolve(name)
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
        assert callable(cell.reader(MINE).reduce)
    serve = spec.resolve("gpt2s-serve-chat-r50")
    assert MINE not in {m["name"] for m in serve.per_layer}
    # no share of a roofline: the kernel's time is drawing bits, which
    # peaks.json has no peak for
    assert not [m for m in BENCH["per_layer"]
                if "dropout" in m["name"] and "roofline" in m["name"]]
