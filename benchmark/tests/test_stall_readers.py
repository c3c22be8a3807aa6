"""The readers of the serving engine's phase clock
(``layer_metrics/engine.decode_stalled_pct``, ``engine.stall_ms``,
``engine.loop_host_pct``; arithmetic in ``trace/stall_spans.py``, names
in ``layer_metrics/stall_names.json``) against a hand-made trace whose
answers can be worked out on paper — and the seven accepted readers of
``hetu.serve.*`` names on the same trace with and without the new
parent and child spans: they must not move."""
import copy
import os

import pytest

from benchmark.harness import spec
from benchmark.trace import program_spans, stall_spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
STALLED, STALL, HOST = ("engine.decode_stalled_pct", "engine.stall_ms",
                        "engine.loop_host_pct")
SERVE = ["gpt2s-serve-chat-r50", "sarvam105b-serve-docqa-r50"]
NEW_SPANS = ("hetu.serve.stall", "hetu.serve.prefill.sync")
ACCEPTED = ["device_idle_pct.serve_engine", "engine.decode_host_ms",
            "engine.decode_build_ms", "engine.decode_sample_ms",
            "engine.decode_ahead_pct"]


def reader(name):
    return spec.load_module("layer_metrics", name)


def trace(phase_clock=True):
    """A window 0..2000 us on one chip, the scheduler thread tiled by
    its leaves (us):

    * 0-135 request A's prefill into an empty engine: NO stall; the
      host waits 40-120 inside ``prefill.device`` 25-125;
    * 135-210 a decode step read at once (``decode.device`` 145-200
      holds its dispatch 146-156), then two steps dispatched ahead
      (``decode.ahead`` 220-240 and 320-340, syncs 240-300, 340-400);
    * 410 request B is admitted beside A: the program in flight is read
      (``decode.device`` 415-450), then the STALL 455-700: build, a
      ``prefill.device`` 480-680 whose sync is 495-675, sample, finish;
    * 700-810 a decode step read at once (sync 710-800 less its
      dispatch 711-721); then the scheduler waits, in slices, 810-1100
      and 1105-1890;
    * 1900-2100 a second stall CUT by the window's end at 2000 (its
      sync 1935-2075).

    With ``phase_clock=False`` the same leaves without the parent
    ``serve.stall`` and the child ``serve.prefill.sync``: the parent
    commit's profile."""
    us = 1000
    host = [("bench.window", 0, 2000)]

    def leaf(name, t0, t1):
        host.append(("hetu.serve." + name, t0, t1))

    def prefill(t0, build, device, sync, done):
        leaf("prefill.build", t0, build)
        leaf("prefill.device", build, device)
        host.append(("hetu.device_dispatch", build + 2, build + 12))
        leaf("prefill.sync", *sync)
        leaf("prefill.sample", device, device + 10)
        leaf("finish", device + 10, done)

    leaf("admit", 0, 5)
    prefill(5, 25, 125, (40, 120), 135)
    leaf("decode.build", 135, 145)
    leaf("decode.device", 145, 200)
    host.append(("hetu.device_dispatch", 146, 156))
    leaf("decode.sample", 200, 205)
    leaf("finish", 205, 210)
    for t0 in (210, 310):
        leaf("admit", t0, t0 + 2)
        leaf("decode.build", t0 + 2, t0 + 10)
        leaf("decode.ahead", t0 + 10, t0 + 30)
        host.append(("hetu.device_dispatch", t0 + 11, t0 + 29))
        leaf("decode.device", t0 + 30, t0 + 90)
        leaf("decode.sample", t0 + 90, t0 + 95)
        leaf("finish", t0 + 95, t0 + 100)
    leaf("admit", 410, 415)
    leaf("decode.device", 415, 450)
    leaf("decode.sample", 450, 455)
    leaf("stall", 455, 700)
    prefill(455, 480, 680, (495, 675), 700)
    leaf("decode.build", 700, 710)
    leaf("decode.device", 710, 800)
    host.append(("hetu.device_dispatch", 711, 721))
    leaf("decode.sample", 800, 805)
    leaf("finish", 805, 810)
    for t0 in (810, 910, 1010):
        leaf("wait", t0, min(t0 + 100, 1100))
    leaf("admit", 1100, 1105)
    for t0 in range(1105, 1890, 100):
        leaf("wait", t0, min(t0 + 100, 1890))
    leaf("admit", 1890, 1900)
    leaf("stall", 1900, 2100)
    prefill(1900, 1920, 2080, (1935, 2075), 2100)
    if not phase_clock:
        host = [e for e in host if e[0] not in NEW_SPANS]
    modules = [("jit_hetu_paged_prefill(11)", 45, 115),
               ("jit_hetu_paged_decode(22)", 160, 195),
               ("jit_hetu_paged_decode(22)", 245, 295),
               ("jit_hetu_paged_decode(22)", 345, 395),
               ("jit_hetu_paged_decode(22)", 420, 445),
               ("jit_hetu_paged_prefill(11)", 500, 670),
               ("jit_hetu_paged_decode(22)", 725, 795),
               ("jit_hetu_paged_prefill(11)", 1940, 2070)]

    def events(rows):
        return [[n, t0 * us, (t1 - t0) * us] for n, t0, t1 in rows]

    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events(
                [("fusion.1", t0, t1) for _, t0, t1 in modules])},
            {"name": "XLA Modules", "events": events(modules)}]},
        {"name": "/host:CPU",
         "lines": [{"name": "engine-scheduler", "events": events(host)}]}]}


# -- the three metrics' arithmetic -------------------------------------------

def test_stalled_share_is_the_union_clipped_to_the_window():
    # 455-700 whole and 1900-2000 of the stall the window's end cuts:
    # 345 of 2000 us
    assert reader(STALLED).reduce(trace(), {}) == pytest.approx(17.25)


def test_stall_length_counts_whole_stalls_only():
    # the one wholly inside the window lasts 245 us; the cut one is no
    # gap any caller saw the end of
    assert reader(STALL).reduce(trace(), {}) == pytest.approx(0.245)
    two = trace()
    two["planes"][1]["lines"][0]["events"].append(
        ["hetu.serve.stall", 1200000, 101000])
    assert reader(STALL).reduce(two, {}) == pytest.approx(0.173)


def test_a_window_without_a_stall_reads_zero_and_no_length():
    """The program has the clock (a prefill sync leaf is there) and
    every prefill met an empty engine: 0%, and no length to report."""
    quiet = trace()
    host = quiet["planes"][1]["lines"][0]["events"]
    host[:] = [e for e in host if e[0] != "hetu.serve.stall"]
    assert reader(STALLED).reduce(quiet, {}) == 0.0
    assert reader(STALL).reduce(quiet, {}) is None
    assert reader(HOST).reduce(quiet, {}) == pytest.approx(
        reader(HOST).reduce(trace(), {}))


def test_loop_host_share_is_what_is_not_a_sync():
    # the thread waits 290 + 785 of the 2000 us: 925 us of serving.
    # Decode syncs: 145-200 less its dispatch 146-156 (45), 240-300,
    # 340-400, 415-450 (35), 710-800 less 711-721 (80): 280. Prefill
    # syncs: 40-120, 495-675 and 1935-2000 of the cut one: 325.
    sync, serving = stall_spans.loop_split(trace())
    assert (sync, serving) == (605e3, 925e3)
    assert reader(HOST).reduce(trace(), {}) == pytest.approx(
        100.0 * (1 - 605 / 925))


def test_a_thread_that_only_waited_has_no_host_share():
    idle = trace()
    idle["planes"][1]["lines"][0]["events"] = [
        ["bench.window", 0, 2000000], ["hetu.serve.wait", 0, 2000000],
        ["hetu.serve.prefill.sync", 2100000, 5000]]
    assert reader(HOST).reduce(idle, {}) is None
    assert reader(STALLED).reduce(idle, {}) == 0.0


@pytest.mark.parametrize("name", [STALLED, STALL, HOST])
def test_without_the_phase_clock_the_readers_say_nothing(name):
    """The parent's profile (every leaf, no stall, no prefill sync), a
    profile from before the program's spans, the recorded serving trace
    of ``test_program_spans.py``, a CPU rehearsal without a device, no
    profile: ``None``, never 0."""
    old = spec.read_json(os.path.join(DATA, "recorded_trace.json"))
    parent = spec.read_json(os.path.join(DATA, "program_spans_trace.json"))
    no_device = {"planes": [{"name": "/host:CPU", "lines": []}]}
    for t in (trace(phase_clock=False), old, parent, no_device, None):
        assert reader(name).reduce(t, {}) is None


# -- the accepted readers do not move -----------------------------------------

@pytest.mark.parametrize("name", ACCEPTED)
def test_accepted_readers_read_the_same_with_the_new_spans(name, capsys):
    """``serve_span_prefix`` is ``hetu.serve.``: the new parent and
    child fall under it, inside leaves that were there."""
    with_spans = reader(name).reduce(trace(), {})
    without = reader(name).reduce(trace(phase_clock=False), {})
    assert with_spans is not None
    assert with_spans == without


def test_idle_split_and_leaf_coverage_read_the_same():
    new, old = trace(), trace(phase_clock=False)
    assert program_spans.idle_split(new) == program_spans.idle_split(old)
    assert program_spans.idle_split(new) is not None
    assert program_spans.leaf_coverage(new) == \
        program_spans.leaf_coverage(old)
    # the leaves tile 0-2000 but for 1100-1105's neighbours: all of it
    assert program_spans.leaf_coverage(new) == pytest.approx(100.0)


def test_the_fixture_without_the_clock_is_the_fixture_less_two_names():
    new, old = trace(), trace(phase_clock=False)
    kept = copy.deepcopy(new)
    host = kept["planes"][1]["lines"][0]["events"]
    host[:] = [e for e in host if e[0] not in NEW_SPANS]
    assert kept == old
    dropped = [e[0] for e in new["planes"][1]["lines"][0]["events"]
               if e[0] in NEW_SPANS]
    assert sorted(dropped) == ["hetu.serve.prefill.sync"] * 3 \
        + ["hetu.serve.stall"] * 2


# -- the entries ---------------------------------------------------------------

@pytest.mark.parametrize("name,unit", [(STALLED, "%"), (STALL, "ms"),
                                       (HOST, "%")])
def test_the_metrics_are_listed_for_the_serve_cells(name, unit):
    """Looked up by name: a later PR appends after them."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m["workloads"] == SERVE
    assert (m["layer"], m["source"]) == ("serving engine", "program_span")
    assert (m["unit"], m["better"]) == (unit, "lower")
    assert m["moves"] == "serve_request_p95_ms"
    for cell_name in SERVE:
        cell = spec.resolve(cell_name)
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
        assert callable(cell.reader(name).reduce)
    train = spec.resolve("gpt2s-train-s1024")
    assert name not in {e["name"] for e in train.per_layer}
