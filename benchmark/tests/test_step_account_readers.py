"""The five ``step.*`` metrics (``trace/step_account.py``): where they
are listed, and the whole reader on a profile of the tiny train files'
step made here on the CPU.

A CPU profile carries the program (its ``/host:metadata`` plane, as the
chip's does) and the step's events on host threads, named by their
``hlo_op`` stat. The chip's form of those events — one ``XLA Ops`` line
of a ``/device:TPU:0`` plane, events named by their whole instruction,
inside ``XLA Modules`` events — is written here around the real
metadata plane: the CPU's events of each run laid end to end.
"""
import collections
import glob
import json
import os

import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.trace import step_account, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.read_json(os.path.join(spec.REPO_ROOT, "BENCHMARK.json"))
MINE = ("step.forward_ms_per_step", "step.backward_ms_per_step",
        "step.optimizer_ms_per_step", "step.mixed_ms_per_step",
        "step.unscoped_pct")
MOVES = "train_tokens_per_s_per_chip"


def test_the_entries_are_listed_for_the_cells_that_train():
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    trains = set(end_to_end[MOVES]["workloads"])
    assert len(trains) >= 3
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in MINE:
        entry = entries[name]
        assert set(entry["workloads"]) == trains
        assert entry["moves"] == MOVES
        assert entry["source"] == "device_trace"
        assert entry["better"] == "lower"
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py"))
    assert entries["step.optimizer_ms_per_step"]["layer"] == "optimizer"
    assert entries["step.unscoped_pct"]["unit"] == "%"
    # a serve cell's line holds none of them
    serves = {w["name"] for w in BENCH["workloads"]} - trains
    for cell_name in serves:
        listed = {m["name"] for m in spec.resolve(cell_name).per_layer}
        assert not listed & set(MINE)
    for cell_name in trains:
        listed = {m["name"] for m in spec.resolve(cell_name).per_layer}
        assert set(MINE) <= listed


def test_the_names_file_gives_every_field_its_source():
    for name, (number, source) in step_account.names()["fields"].items():
        assert isinstance(number, int) and number > 0, name
        assert ":" in source, name


# ---------------------------------------------------------------------------
# a profile of the tiny train step, in the chip's form
# ---------------------------------------------------------------------------

def _varint(value):
    out = bytearray()
    while True:
        out.append(value & 0x7F | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, lines):
    """A serialized XPlane: ``lines`` = ``[(line name, [(event name,
    start ns, duration ns)])]``."""
    ids, body = {}, _field(2, name.encode())
    for key, (line_name, events) in enumerate(lines, 1):
        line = _field(1, key) + _field(2, line_name.encode())
        for event, start, duration in events:
            ident = ids.setdefault(event, len(ids) + 1)
            line += _field(4, _field(1, ident) + _field(2, start * 1000)
                           + _field(3, duration * 1000))
        body += _field(3, line)
    for event, ident in ids.items():
        body += _field(4, _field(1, ident) + _field(2, _field(
            1, ident) + _field(2, event.encode())))
    return body


def _cpu_runs(path, module_name):
    """``[[(hlo_op, duration ns)]]``: the CPU backend's events of each
    run of ``module_name``, in start order, and the program id."""
    from jax.profiler import ProfileData
    runs, ids = collections.defaultdict(list), set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                stats = dict(event.stats)
                if stats.get("hlo_module") == module_name:
                    ids.add(stats["program_id"])
                    runs[stats["run_id"]].append(
                        (int(event.start_ns), stats["hlo_op"],
                         max(1, int(event.duration_ns))))
    (program_id,) = ids
    return [[(op, ns) for _, op, ns in sorted(events)]
            for _, events in sorted(runs.items())], program_id


@pytest.fixture(scope="module")
def chip_form(tmp_path_factory):
    """``(path, steps)``: a profile file under a stand-in for the
    benchmark's trace directory."""
    import hetu_tpu.profiler
    cell = spec.resolve("tiny-gpt2-train",
                        os.path.join(DATA, "BENCHMARK.json"))
    session = cell.family().build_train(cell.config, cell.traffic, 3)
    rng = np.random.RandomState(3)
    batch = cell.config["sizing"]["per_chip_batch"]
    feed = dict(zip(session.feed_nodes, session.make_batch(rng, batch)))
    run = session.executor.run
    for _ in range(2):          # compile outside, and finish there
        run(feed_dict=feed)[0].asnumpy()
    root = tmp_path_factory.mktemp("state")
    cpu_dir = root / "cpu"
    with hetu_tpu.profiler.trace(str(cpu_dir)):
        for _ in range(3):
            out = run(feed_dict=feed)
        out[0].asnumpy()
    session.executor.close()
    cpu_path = xplane.find_xplane(str(cpu_dir))
    runs, program_id = _cpu_runs(cpu_path, "jit_hetu_step_default")
    printed = f"jit_hetu_step_default({program_id})"
    ops, modules, at = [], [], 1000
    for events in runs:
        start = at
        for op, ns in events:
            ops.append((f"%{op} = f32[8]{{0}} fusion()", at, ns))
            at += ns
        modules.append((printed, start, at - start))
        at += 500
    # one more execution that the window cuts: it must not count
    modules.append((printed, at, 4000))
    ops.append((f"%{runs[0][0][0]} = f32[8]{{0}} fusion()", at, 4000))
    window = ("bench.window", 0, at + 2000)
    metadata = dict(step_account.planes(cpu_path))["/host:metadata"]
    out_dir = root / "bench_trace" / "tiny-gpt2-train" / "plugins" \
        / "profile" / "2026_01_01"
    out_dir.mkdir(parents=True)
    path = out_dir / "host.xplane.pb"
    path.write_bytes(
        _field(1, _plane("/device:TPU:0", [("XLA Modules", modules),
                                           ("XLA Ops", ops)]))
        + _field(1, bytes(metadata))
        + _field(1, _plane("/host:CPU", [("main", [window])])))
    return str(root), str(path), len(runs)


def test_the_five_readers_on_the_tiny_train_step(chip_form, monkeypatch,
                                                 capsys):
    from hetu_tpu import cachedir
    root, path, steps = chip_form
    monkeypatch.setattr(cachedir, "STATE_ROOT", root)
    assert step_account.find_profile() == path
    trace = xplane.load(path)
    assert xplane.device_planes(trace)
    lo, hi = xplane.window(trace)
    assert lo == 0
    values = {name: spec.load_module("layer_metrics", name).reduce(
        trace, {"steps": 99}) for name in MINE}
    assert all(v is not None for v in values.values()), values
    account = step_account.account(path, (lo, hi))
    assert account.steps == steps == 3       # not the cut one, not facts'
    assert values["step.forward_ms_per_step"] > 0
    assert values["step.backward_ms_per_step"] > 0
    assert values["step.optimizer_ms_per_step"] \
        + values["step.mixed_ms_per_step"] > 0
    assert 0 <= values["step.unscoped_pct"] < 50
    # conservation, against what xplane.py says the device was busy for
    in_steps = sum(account.ms_per_step(k) for k in step_account.KINDS)
    assert in_steps == pytest.approx(
        account.total_ns / 1e6 / account.steps, rel=1e-3)
    # (the cut execution's one event lies half inside the window)
    busy_s, _, _ = xplane.busy(trace)
    assert account.total_ns / 1e9 == pytest.approx(
        busy_s - 2000e-9, rel=1e-6)
    logged = [json.loads(line)["step_account"]
              for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"step_account"')]
    assert logged[0]["steps"] == 3          # memoised: logged once
    assert sum("steps" in row for row in logged) == 1
    assert any("by_role_and_op_type" in row for row in logged)
    (table,) = [row["ms_per_step_by_parameter"] for row in logged
                if "ms_per_step_by_parameter" in row]
    assert table["columns"] == ["parameter", "optimizer", "mixed"]
    assert table["rows"] and all(r[1] + r[2] > 0 for r in table["rows"])


def test_the_tool_prints_the_account(chip_form, capsys):
    from benchmark.tools import step_account as tool
    root, path, _ = chip_form
    trace_dir = path
    for _ in range(4):          # <dir>/plugins/profile/<time>/<file>
        trace_dir = os.path.dirname(trace_dir)
    assert glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.pb"))
    assert tool.main([trace_dir, "--by", "role"]) == 0
    by_role = capsys.readouterr().out
    assert " fwd" in by_role and " bwd" in by_role
    assert tool.main([trace_dir, "--top", "5"]) == 0
    rows = [line for line in capsys.readouterr().out.splitlines()
            if " ms " in line and " x " in line]
    assert len(rows) == 5
    assert tool.main([trace_dir, "--by", "parameter"]) == 0
    assert "gpt_wte" in capsys.readouterr().out


def test_a_profile_without_a_training_step_says_nothing(tmp_path, capsys):
    path = tmp_path / "serve.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_hetu_paged_decode(7)", 10, 100),
                         ("jit_hetu_step_validate(8)", 200, 100)]),
        ("XLA Ops", [("%fusion.1 = f32[8]{0} fusion()", 10, 100)])])))
    assert step_account.account(str(path), (0, 1000)) is None
    assert "no whole execution of a training step" in capsys.readouterr().out
