"""The readers added with the decode loop's program in flight
(``layer_metrics/engine.decode_ahead_pct``,
``layer_metrics/model.decode_program_ms``) against a hand-made trace
whose answers can be worked out on paper."""
import os

import pytest

from benchmark.harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
AHEAD, PROGRAM = "engine.decode_ahead_pct", "model.decode_program_ms"
SERVE = ["gpt2s-serve-chat-r50", "sarvam105b-serve-docqa-r50"]


def reader(name):
    return spec.load_module("layer_metrics", name)


def trace(ahead=True):
    """A window 0..1000 us on one chip: a prefill, then five decode
    programs back to back, of 100, 100, 120, 140 and 100 us (the first
    built from host values in a ``decode.device`` span, the next three
    dispatched ahead of the read of the one before, the fifth built
    from host values after an admission), with the slice of a step's
    ids (another program) between two of them. A sixth decode program
    straddles the window's end, dispatched ahead by a span inside the
    window; a seventh program and its span lie after it. With
    ``ahead=False`` the same programs run and no step is dispatched
    ahead (the parent's loop)."""
    us = 1000
    modules = [["jit_hetu_paged_prefill(11)", 20 * us, 60 * us]]
    starts = ((100, 100), (200, 100), (305, 120), (425, 140), (700, 100),
              (950, 100), (1100, 100))
    for t0, dur in starts:
        modules.append(["jit_hetu_paged_decode(22)", t0 * us, dur * us])
    modules.append(["jit_hetu_decode_ids(33)", 300 * us, 4 * us])
    host = [["bench.window", 0, 1000 * us],
            ["hetu.serve.prefill.device", 10 * us, 75 * us],
            ["hetu.serve.decode.build", 86 * us, 6 * us],
            ["hetu.serve.decode.device", 92 * us, 20 * us]]
    for t0 in (150, 250, 350, 900, 1050):
        host += [["hetu.serve.decode.build", (t0 - 10) * us, 8 * us],
                 ["hetu.serve.decode.ahead" if ahead
                  else "hetu.serve.decode.device", t0 * us, 30 * us],
                 ["hetu.serve.decode.device", (t0 + 32) * us, 20 * us],
                 ["hetu.serve.decode.sample", (t0 + 53) * us, 5 * us]]
    host += [["hetu.serve.decode.build", 680 * us, 8 * us],
             ["hetu.serve.decode.device", 690 * us, 115 * us]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [["fusion.1", s, d] for _, s, d in modules]},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU",
         "lines": [{"name": "scheduler", "events": host}]}]}


def test_ahead_share_is_spans_over_decode_programs():
    # four spans inside the window (the fifth lies after it) over the
    # five decode programs wholly inside it: the one that straddles the
    # window's end has its span counted and is not itself
    assert reader(AHEAD).reduce(trace(), {}) == pytest.approx(80.0)


def test_without_the_span_the_ahead_reader_says_nothing():
    """The parent's loop, a trace from before the program's spans, a
    train cell, a CPU rehearsal."""
    old = spec.read_json(os.path.join(DATA, "recorded_trace.json"))
    parent = spec.read_json(os.path.join(DATA, "program_spans_trace.json"))
    no_device = {"planes": [{"name": "/host:CPU", "lines": []}]}
    for t in (trace(ahead=False), old, parent, no_device, None):
        assert reader(AHEAD).reduce(t, {}) is None
    # the span without one decode program inside the window
    spans_only = trace()
    spans_only["planes"][0]["lines"][1]["events"] = [
        ["jit_hetu_paged_prefill(11)", 20000, 60000]]
    assert reader(AHEAD).reduce(spans_only, {}) is None


@pytest.mark.parametrize("ahead", [True, False])
def test_decode_program_is_found_by_its_name(ahead):
    # 100, 100, 120, 140, 100 us inside the window, wherever the host
    # spans lie; the prefill, the ids' slice and the programs that
    # cross or follow the window's end are not among them
    assert reader(PROGRAM).reduce(trace(ahead), {}) == pytest.approx(0.100)
    longer = trace(ahead)
    longer["planes"][0]["lines"][1]["events"][1][2] = 130000
    assert reader(PROGRAM).reduce(longer, {}) == pytest.approx(0.120)


def test_decode_program_on_the_recorded_serving_trace():
    # the trace of test_program_spans.py: decode programs of 200, 200,
    # 200 and 150 us
    parent = spec.read_json(os.path.join(DATA, "program_spans_trace.json"))
    assert reader(PROGRAM).reduce(parent, {}) == pytest.approx(0.200)
    old = spec.read_json(os.path.join(DATA, "recorded_trace.json"))
    no_device = {"planes": [{"name": "/host:CPU", "lines": []}]}
    for t in (old, no_device, None):
        assert reader(PROGRAM).reduce(t, {}) is None


@pytest.mark.parametrize("name,layer,source", [
    (AHEAD, "serving engine", "program_span"),
    (PROGRAM, "model step", "device_trace")])
def test_the_metrics_are_listed_for_the_serve_cells(name, layer, source):
    """Looked up by name: a later PR appends after them."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m["workloads"] == SERVE
    assert (m["layer"], m["source"]) == (layer, source)
    assert m["moves"] == "serve_request_p95_ms"
    assert layer in {e["layer"] for e in BENCH["per_layer"]
                     if e["name"] not in (AHEAD, PROGRAM)}
    for cell_name in SERVE:
        cell = spec.resolve(cell_name)
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
        assert callable(cell.reader(name).reduce)
    train = spec.resolve("gpt2s-train-s1024")
    assert name not in {e["name"] for e in train.per_layer}
