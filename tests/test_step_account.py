"""A compiled step names what it traces, and a profile's own copy of the
program says whose every device event is (ISSUE 52).

``SubExecutor._build_step`` runs each ``node.compute`` under
``hetu.<role>/<op_type>/<node>`` (``Op.scope``; ``OptimizerOp`` one more
level a parameter), which ends up in the ``op_name`` of every
instruction of the optimised module. These tests hold the program's
side — every traced operation is scoped, the roles are decided where a
node is made, the scopes change no instruction — and the reader's
(``benchmark/trace/step_account.py``): the wire walk of a profile file,
the join of events to instructions, conservation, and ``None`` with a
logged reason where the join does not hold. All on the CPU backend,
whose events carry the instruction's name in their ``hlo_op`` stat.
"""
import collections
import contextlib
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import profiler
from hetu_tpu.ops import (attention, pallas_attention, pallas_dropout,
                          pallas_norm, pallas_sparse_update)

from benchmark.trace import step_account

OPTIMIZERS = {"sgd": ht.optim.SGDOptimizer, "adam": ht.optim.AdamOptimizer}
WIDTH = 128         # whole lanes: the sparse kernel's rows
ROWS = 16


def _dense(prefix):
    """A two-layer MLP: ``(feeds, parameters, loss, a feed dict)``."""
    x = ht.Variable(f"{prefix}_x", trainable=False)
    y_ = ht.Variable(f"{prefix}_y", trainable=False)
    w1 = ht.init.xavier_normal((16, 12), name=f"{prefix}_w1")
    w2 = ht.init.xavier_normal((12, 4), name=f"{prefix}_w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    rng = np.random.RandomState(0)
    feed = {x: rng.rand(8, 16).astype(np.float32),
            y_: np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]}
    return (w1, w2), loss, feed


def _sparse(prefix):
    """An embedding table (an ``IndexedSlices`` gradient) under a dense
    head."""
    ids = ht.Variable(f"{prefix}_ids", trainable=False)
    y_ = ht.Variable(f"{prefix}_y", trainable=False)
    rng = np.random.RandomState(1)
    table = ht.Variable(f"{prefix}_table", value=rng.randn(
        ROWS, WIDTH).astype(np.float32))
    head = ht.Variable(f"{prefix}_head", value=(
        rng.randn(WIDTH, 4) * 0.1).astype(np.float32))
    h = ht.embedding_lookup_op(table, ids)
    logits = ht.matmul_op(ht.array_reshape_op(h, (-1, WIDTH)), head)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
    feed = {ids: rng.randint(0, ROWS, (2, 4)).astype(np.int32),
            y_: np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]}
    return (table, head), loss, feed


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The kernels' paths without a TPU: the platform rule answers yes
    and every kernel a whole graph may meet is interpreted."""
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    for module in (pallas_attention, pallas_norm, pallas_dropout,
                   pallas_sparse_update):
        monkeypatch.setattr(module, "INTERPRET", True)


def _session(model, optimizer, prefix, **kw):
    params, loss, feed = model(prefix)
    train = OPTIMIZERS[optimizer](0.05).minimize(loss)
    exe = ht.Executor({"default": [loss, train], "validate": [loss]}, **kw)
    return exe, params, loss, train, feed


def _step_text(exe, feed, group="default"):
    """The optimised text of ``group``'s step as the executor builds
    it."""
    sub = exe.subexecutors[group]
    feed_map = {node: sub._ingest(value) for node, value in feed.items()}
    step = sub.prepare(exe, feed_map)
    return jax.jit(step).lower(
        *sub.trace_args(exe, feed_map)).compile().as_text()


def _op_names(text):
    """The ``op_name`` of every instruction that the step traced
    (arguments are named after the argument, reductions' scalar
    computations after the primitive: neither is under the jit)."""
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(hetu_step")]


# ---------------------------------------------------------------------------
# (a) the program names what it traces
# ---------------------------------------------------------------------------

UPDATES = {"dense": (_dense, False), "sparse_composed": (_sparse, False),
           "sparse_kernel": (_sparse, True)}


@pytest.mark.parametrize("update", sorted(UPDATES))
@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_every_traced_operation_is_scoped(optimizer, update, request):
    model, kernel = UPDATES[update]
    if kernel:
        request.getfixturevalue("on_a_tpu")
    exe, params, loss, train, feed = _session(
        model, optimizer, f"a_{optimizer}_{update}")
    text = _step_text(exe, feed)
    traced = _op_names(text)
    assert len(traced) > 10
    assert [n for n in traced if "hetu." not in n] == []
    ops = [op for n in traced for op in step_account.graph_ops(n)]
    assert {op[0] for op in ops} == {"fwd", "bwd", "opt"}
    # a gradient node's scope says bwd, the loss's fwd (the last
    # parameter's is a matmul; a table's is IndexedSlices, no operation)
    gradient = train.inputs[-1]
    assert gradient.scope().startswith("hetu.bwd/")
    assert ("bwd", gradient.op_type, gradient.name, "") in ops
    assert ("fwd", loss.op_type, loss.name, "") in ops
    # the optimizer's time divides by parameter, in the dense branch and
    # in both branches of _update_rows
    levels = {op[3] for op in ops if op[0] == "opt"}
    assert {p.name for p in params} <= levels
    took_the_kernel = any(
        f"/{params[0].name}/jit(hetu_sparse_rows_update)" in n
        for n in traced)
    assert took_the_kernel == kernel
    exe.close()


def test_roles_are_decided_where_a_node_is_made():
    params, loss, _ = _dense("roles")
    before = {n.id for n in ht.graph.autodiff.find_topo_sort([loss])}
    train = ht.optim.AdamOptimizer(0.1).minimize(loss)
    topo = ht.graph.autodiff.find_topo_sort([train])
    assert train.role == "opt"
    assert train.scope() == "hetu.opt/OptimizerOp/Optimizer_Adam"
    minted = [n for n in topo if n.id not in before and n is not train]
    assert minted and {n.role for n in minted} == {"bwd"}
    assert {n.role for n in topo if n.id in before} == {"fwd"}
    # a node's name may hold the separator; its scope does not
    odd = ht.Variable("tower/w", trainable=False)
    assert odd.scope() == "hetu.fwd/PlaceholderOp/tower.w"


def test_an_inference_subexecutor_is_all_forward():
    exe, _, _, _, feed = _session(_dense, "adam", "infer")
    ops = [op for n in _op_names(_step_text(exe, feed, "validate"))
           for op in step_account.graph_ops(n)]
    assert ops and {op[0] for op in ops} == {"fwd"}
    exe.close()


@pytest.fixture
def as_a_chip_entry_point(monkeypatch, tmp_path):
    """The jax config of ``cachedir.enable_compile_cache()`` (what
    ``benchmark/run.py`` and ``chip_smoke.py`` call),
    restored afterwards; the cache itself stays where it was."""
    from hetu_tpu import cachedir
    names = ("jax_include_full_tracebacks_in_locations",
             "jax_traceback_in_locations_limit")
    was = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cachedir.enable_compile_cache() == str(tmp_path)
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def test_the_chip_entry_points_keep_the_scopes(as_a_chip_entry_point):
    """With ``jax_include_full_tracebacks_in_locations`` off (PR 21 to
    PR 51) an operation lowered outside a nested jit was named by its
    primitive alone and two thirds of GPT-2's step on the chip carried
    no scope (PR 52's first traced run); a one-frame traceback keeps
    the call stack out of a kernel's bytes and the scopes in."""
    exe, _, _, _, feed = _session(_dense, "adam", "entry")
    traced = _op_names(_step_text(exe, feed))
    assert len(traced) > 10
    assert [n for n in traced if "hetu." not in n] == []
    exe.close()


# ---------------------------------------------------------------------------
# (b) a scope is metadata: no instruction changes
# ---------------------------------------------------------------------------

def _instructions(text):
    """The module's text without metadata and without the tables of
    source locations that metadata points into."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n(.+\n)*", "", text)


@pytest.mark.parametrize("model", ["dense", "sparse_composed"])
def test_the_scopes_change_no_instruction(model, monkeypatch):
    exe, _, _, _, feed = _session(UPDATES[model][0], "adam",
                                  f"b_{model}", dtype=jnp.bfloat16)
    scoped = _step_text(exe, feed)
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        bare = _step_text(exe, feed)
    assert "hetu." in scoped and "hetu." not in bare
    assert _instructions(scoped) == _instructions(bare)
    # mixed precision: the convert of a feed is the step's own
    assert "hetu.step/feeds" in scoped
    exe.close()


# ---------------------------------------------------------------------------
# (d) the wire walker
# ---------------------------------------------------------------------------

def _key(number, wire):
    return _uvarint(number << 3 | wire)


def _uvarint(value):
    out = bytearray()
    while True:
        out.append(value & 0x7F | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _key(number, 0) + _uvarint(payload)
    return _key(number, 2) + _uvarint(len(payload)) + payload


def test_the_wire_walker_passes_over_what_it_does_not_know():
    message = (_field(1, b"fusion.7") + _field(999, 300)
               + _key(12, 1) + (2 ** 40 + 5).to_bytes(8, "little")
               + _key(13, 5) + (77).to_bytes(4, "little")
               + _field(7, _field(2, b"jit(f)/hetu.fwd/A/A1/mul"))
               + _field(38, _uvarint(3) + _uvarint(300))
               + _field(38, 9))
    fields = [(n, w, v if isinstance(v, int) else bytes(v))
              for n, w, v in step_account.walk(message)]
    assert fields[0] == (1, 2, b"fusion.7")
    assert fields[1] == (999, 0, 300)
    assert fields[2] == (12, 1, 2 ** 40 + 5)
    assert fields[3] == (13, 5, 77)
    got = step_account._instruction(message)
    assert (got.name, got.op_name) == ("fusion.7",
                                       "jit(f)/hetu.fwd/A/A1/mul")
    assert got.calls == (3, 300, 9)      # packed, then one a field


@pytest.mark.parametrize("message, complaint", [
    (_key(3, 3), "groups"),
    (_field(1, b"abc")[:-1], "runs past"),
])
def test_the_wire_walker_refuses_what_it_cannot_read(message, complaint):
    with pytest.raises(ValueError, match=complaint):
        list(step_account.walk(message))


def _profile_bytes(programs):
    """A profile file with a metadata plane for ``{printed name:
    [(computation id, name, [(instruction, op_name, calls)])]}``."""
    def computation(ident, name, instructions):
        body = _field(1, name.encode()) + _field(5, ident)
        for instr, op_name, calls in instructions:
            body += _field(2, _field(1, instr.encode())
                           + _field(2, b"fusion")
                           + _field(7, _field(2, op_name.encode()))
                           + b"".join(_field(38, c) for c in calls))
        return _field(3, body)
    plane = _field(2, b"/host:metadata") \
        + _field(5, _field(1, 1) + _field(2, _field(2, b"Hlo Proto")))
    for key, (printed, computations) in enumerate(programs.items(), 1):
        module = _field(1, printed.split("(")[0].encode()) \
            + _field(2, computations[0][1].encode()) \
            + b"".join(computation(*c) for c in computations)
        stat = _field(1, 1) + _field(6, _field(1, module))
        plane += _field(4, _field(1, key) + _field(2, _field(
            2, printed.encode()) + _field(5, stat)))
    other = _field(2, b"/host:CPU") + _field(7, 123)
    return _field(1, other) + _field(1, plane)


def test_programs_of_a_hand_built_profile(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_profile_bytes({
        "jit_hetu_step_default(5)": [
            (1, "main.9", [("fusion.1", "jit(x)/hetu.bwd/B/B2/dot", [2]),
                           ("copy.3", "", [])]),
            (2, "fused.1", [("mul.4", "jit(x)/hetu.opt/OptimizerOp/"
                             "Optimizer_Adam/w/mul", [])])],
        "jit_other(6)": [(1, "main.1", [("add.1", "", [])])]}))
    assert [n for n, _ in step_account.planes(str(path))] == [
        "/host:CPU", "/host:metadata"]
    found = step_account.programs(str(path), re.compile("hetu_step"))
    assert [(m.name, m.program_id, m.entry) for m in found] == [
        ("jit_hetu_step_default", "5", "main.9")]
    whose = step_account.attribute(found[0])
    assert whose["fusion.1"].kind == "mixed"
    assert whose["fusion.1"].roles == "bwd+opt"
    assert whose["mul.4"] == step_account.Attribution(
        "optimizer", "opt", "OptimizerOp", "Optimizer_Adam", "w")
    assert whose["copy.3"].kind == "unscoped"


# ---------------------------------------------------------------------------
# the grammar and the arithmetic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(hetu_step_default)/hetu.fwd/MatMulOp/MatMulOp4/dot_general",
     [("fwd", "MatMulOp", "MatMulOp4", "")]),
    ("jit(hetu_step_default)/transpose(jvp(hetu.bwd/FlashOp/Flash9))/"
     "jit(_flash_bwd)/pallas_call",
     [("bwd", "FlashOp", "Flash9))", "")]),
    ("jit(hetu_step_default)/hetu.opt/OptimizerOp/Optimizer_Adam/wte/mul",
     [("opt", "OptimizerOp", "Optimizer_Adam", "wte")]),
    ("jit(hetu_step_default)/hetu.opt/OptimizerOp/Optimizer_Adam/"
     "broadcast_in_dim", [("opt", "OptimizerOp", "Optimizer_Adam", "")]),
    ("jit(hetu_step_default)/hetu.opt/OptimizerOp/Optimizer_SGD/"
     "jit(settle)/add", [("opt", "OptimizerOp", "Optimizer_SGD", "")]),
    ("jit(hetu_step_default)/hetu.step/feeds/convert_element_type", []),
    ("params['3']", []),
])
def test_the_scope_grammar(op_name, want):
    assert step_account.graph_ops(op_name) == want


def test_an_event_that_contains_others_is_booked_at_its_self_time():
    events = [("%while.1 = (s32[]) while(...)", 0, 100),
              ("%fusion.2 = f32[4] fusion(...)", 10, 40),
              ("%fusion.3 = f32[4] fusion(...)", 40, 90),
              ("%copy.4 = f32[4] copy(...)", 100, 130)]
    assert step_account.self_times(events) == [
        (events[0][0], 20), (events[1][0], 30), (events[2][0], 50),
        (events[3][0], 30)]
    assert step_account.instruction_name(events[0][0]) == "while.1"
    assert step_account.instruction_name("fusion.2") == "fusion.2"


# ---------------------------------------------------------------------------
# (c) end to end on the CPU, and (e) None where the join does not hold
# ---------------------------------------------------------------------------

def _profiled_steps(exe, feed, trace_dir, steps=3):
    for _ in range(2):      # compile outside, and let it finish there
        exe.run("default", feed_dict=feed)[0].asnumpy()
    with profiler.trace(str(trace_dir)):
        for _ in range(steps):
            out = exe.run("default", feed_dict=feed)
        out[0].asnumpy()
    (path,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    return path


def _cpu_join(path, module_name="jit_hetu_step_default"):
    """``(module, lists of (hlo_op, start, end), runs)``: the CPU
    backend's events of the program of that name that ran in the
    profile, one list a run and thread (a thread's events follow one
    another as a device line's do), and that program's module out of
    the file (the file carries every live program, other tests' steps
    of the same name too: the events say which id ran)."""
    from jax.profiler import ProfileData
    by, runs, ids = collections.defaultdict(list), set(), set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                stats = dict(event.stats)
                if stats.get("hlo_module") != module_name:
                    continue
                runs.add(stats["run_id"])
                ids.add(str(stats["program_id"]))
                start = int(event.start_ns)
                by[line.name, stats["run_id"]].append(
                    (stats["hlo_op"], start,
                     start + int(event.duration_ns)))
    (program_id,) = ids
    (module,) = [m for m in step_account.programs(
        path, re.compile(re.escape(module_name)))
        if (m.name, m.program_id) == (module_name, program_id)]
    return module, list(by.values()), len(runs)


def _logged(capsys):
    return [json.loads(line)["step_account"]
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"step_account"')]


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_a_cpu_profile_end_to_end(optimizer, tmp_path, capsys):
    exe, params, _, _, feed = _session(_dense, optimizer,
                                       f"c_{optimizer}")
    path = _profiled_steps(exe, feed, tmp_path)
    module, executions, runs = _cpu_join(path)
    assert module.entry and module.program_id.isdigit()
    assert step_account.choose([module], executions) is module
    scoped = [i for i in module.instructions.values()
              if "hetu." in i.op_name]
    assert len(scoped) > 10
    assert runs == 3
    account = step_account.book(module, executions, steps=runs)
    assert account is not None and account.steps == 3
    # conservation: the kinds sum to what the events cover
    assert sum(account.by_kind.values()) == pytest.approx(
        account.total_ns, rel=1e-3)
    assert account.by_kind["unjoined"] == 0
    assert account.by_kind["optimizer"] + account.by_kind["mixed"] > 0
    assert account.by_kind["forward"] + account.by_kind["backward"] > 0
    assert account.unscoped_pct() < 50
    by_parameter = {k for k, _, _ in account.grouped(lambda a: a.parameter)}
    assert by_parameter & {p.name for p in params}
    step_account.report(account)
    first = _logged(capsys)[0]
    assert first["steps"] == 3 and first["program"].startswith(
        "jit_hetu_step_default(")
    assert first["sum_ms_per_step"] == pytest.approx(
        first["events_ms_per_step"], rel=1e-3)
    exe.close()


def test_a_program_compiled_without_scopes_gives_no_number(
        tmp_path, capsys, monkeypatch):
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        exe, _, _, _, feed = _session(_dense, "adam", "e_bare")
        path = _profiled_steps(exe, feed, tmp_path)
    module, executions, runs = _cpu_join(path)
    assert step_account.book(module, executions, steps=runs) is None
    (said,) = _logged(capsys)
    assert "carries no hetu. scope" in said["none"]
    assert said["unscoped_share"] > 0.5
    exe.close()


def test_events_the_module_does_not_hold_give_no_number(capsys):
    module = step_account.Module("jit_hetu_step_default", "5", "main", {
        1: ("main", [step_account.Instruction(
            "fusion.1", "fusion", "jit(x)/hetu.fwd/A/A1/mul")])})
    events = [("%fusion.1 = f32[4] fusion(...)", 0, 90),
              ("%fusion.77 = f32[4] fusion(...)", 90, 100)]
    assert step_account.book(module, [events]) is None
    (said,) = _logged(capsys)
    assert said["first"] == ["fusion.77"] and said["instructions"] == 1
    assert said["share"] == pytest.approx(0.1)
    # inside the limit the event is counted, as the account's blind share
    events[1] = ("%fusion.77 = f32[4] fusion(...)", 90, 90.5)
    account = step_account.book(module, [events])
    assert account.by_kind["unjoined"] == 0.5
    assert account.unscoped_pct() == pytest.approx(100 * 0.5 / 90.5)
    assert account.ms_per_step("forward") == pytest.approx(90 / 1e6)


def test_no_profile_and_no_device_plane_give_no_number():
    assert step_account.metric(None, {}, "forward") is None
    host_only = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert step_account.metric(host_only, {}, "unscoped_pct") is None
