"""A compiled training step's device time by graph op and by role, from
nothing but the profile FILE (read by the five ``step.*`` metrics and by
``tools/step_account.py``).

The program runs every ``node.compute`` of a step under a scope
``hetu.<role>/<op_type>/<node>[/<parameter>]`` (``<role>``: ``fwd``,
``bwd``, ``opt``) and what the step does outside any node under
``hetu.step/<what>`` (docs/tools.md). A scope is compiled metadata: it
ends up in the ``op_name`` of every instruction of the OPTIMISED module,
the ones inside fused computations too. The profile carries that module:
its ``/host:metadata`` plane holds one event-metadata entry a program,
named ``jit_<function>(<program id>)``, whose ``Hlo Proto`` stat is the
serialized ``HloProto``. ``jax.profiler.ProfileData`` does not expose
event metadata, so :func:`programs` walks the protobuf wire format
itself (varints and length-delimited fields; no generated class, no
package). Field numbers and every name matched live in
``layer_metrics/step_account_names.json``.

The join. A device event on the ``XLA Ops`` line is named by its whole
instruction (``%fusion.1499 = bf16[...] fusion(...)``): the text before
`` = `` without the ``%`` is the instruction's name in the module.
``xplane.load`` has already shortened those names in the ``trace`` a
reader is handed, so the events are read from the file again, raw,
through ``ProfileData``. Kept are the events wholly inside an
``XLA Modules`` event of the training step (``step_module``) that
itself lies wholly inside ``bench.window``: only whole executions
count, and ``steps`` is their number. An instruction's roles are those
of its own ``op_name`` and of every instruction of the computations it
calls, recursively (a fusion's, a ``while``'s body). One role: that
role, and the graph op most of its scoped instructions name. More:
``mixed``, booked under the pair (``bwd+opt`` is a weight's gradient
matmul with Adam fused in). None: ``step`` where a ``hetu.step`` scope
is all it has, else ``unscoped`` (XLA's own copies and bitcasts). An
event that contains other events of its line (a ``while`` and its
body's) is booked at its SELF time. An event whose instruction the
module does not hold is ``unjoined``.

What is NOT read: the ``Async XLA Ops`` line (copies that overlap the
compute; their time is on nobody's critical path by construction, and
adding it would break the conservation below).

Conservation, checked on every account: forward + backward + optimizer
+ mixed + step + unscoped + unjoined (self times) = the time the step's
events cover (the union of their intervals), to 0.1%.
Every metric is ``None``, with one logged line saying why, where more
than 1% of the time is unjoined, or where more than half is unscoped:
then the executable was compiled before the scopes existed (a
compile-cache entry written by an older commit at the same path — the
cache key leaves metadata out) and the cure is a fresh copy directory.
"""
import collections
import dataclasses
import functools
import glob
import json
import os
import re

from benchmark.harness.spec import BENCH_DIR, read_json
from benchmark.trace import xplane


@functools.lru_cache(maxsize=None)
def names():
    return read_json(BENCH_DIR + "/layer_metrics/step_account_names.json")


def field(name):
    return names()["fields"][name][0]


def log(fields):
    print(json.dumps({"step_account": fields}), flush=True)


# ---------------------------------------------------------------------------
# the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, pos):
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def walk(buf):
    """``(field number, wire type, value)`` of every field of one
    serialized message: an int for a varint (0) and for the fixed-width
    types (1: 64 bits, 5: 32 bits, little-endian), a memoryview for a
    length-delimited field (2: a string, bytes, a message, a packed
    list). A reader picks the numbers it knows and passes over the
    rest."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[pos:pos + size], "little")
            pos += size
        else:
            raise ValueError(f"wire type {wire} of field {number}: "
                             "groups are not read")
        if pos > end:
            raise ValueError(f"field {number} runs past its message")
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _varints(wire, value):
    """A repeated integer field: packed, or one varint a field."""
    if wire == 0:
        return [value]
    out, pos = [], 0
    while pos < len(value):
        v, pos = _varint(value, pos)
        out.append(v)
    return out


# ---------------------------------------------------------------------------
# the programs a profile carries
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str = ""
    op_name: str = ""
    calls: tuple = ()           # ids of the computations it calls


@dataclasses.dataclass
class Module:
    name: str                   # as the profile prints it, without the id
    program_id: str
    entry: str
    computations: dict          # id -> (name, [Instruction])

    @functools.cached_property
    def instructions(self):
        """Every instruction of every computation, by name (names are
        unique in a module)."""
        return {i.name: i for _, instrs in self.computations.values()
                for i in instrs}


def _instruction(buf):
    out, calls = Instruction(""), []
    f_name, f_opcode = field("HloInstructionProto.name"), \
        field("HloInstructionProto.opcode")
    f_meta, f_calls = field("HloInstructionProto.metadata"), \
        field("HloInstructionProto.called_computation_ids")
    f_op_name = field("OpMetadata.op_name")
    for number, wire, value in walk(buf):
        if number == f_name:
            out.name = _text(value)
        elif number == f_opcode:
            out.opcode = _text(value)
        elif number == f_meta:
            for n, _, v in walk(value):
                if n == f_op_name:
                    out.op_name = _text(v)
        elif number == f_calls:
            calls += _varints(wire, value)
    out.calls = tuple(calls)
    return out


def _module(hlo_proto, printed_name):
    found = re.match(names()["module_name"], printed_name)
    computations, entry = {}, ""
    for number, _, module in walk(hlo_proto):
        if number != field("HloProto.hlo_module"):
            continue
        for n, _, value in walk(module):
            if n == field("HloModuleProto.entry_computation_name"):
                entry = _text(value)
            elif n == field("HloModuleProto.computations"):
                name, ident, instrs = "", None, []
                for m, _, v in walk(value):
                    if m == field("HloComputationProto.name"):
                        name = _text(v)
                    elif m == field("HloComputationProto.id"):
                        ident = v
                    elif m == field("HloComputationProto.instructions"):
                        instrs.append(_instruction(v))
                computations[ident] = (name, instrs)
    return Module(found.group("name"), found.group("id") or "", entry,
                  computations)


def _map_values(plane, map_field):
    for number, _, entry in walk(plane):
        if number == map_field:
            key = value = None
            for n, _, v in walk(entry):
                if n == field("map.key"):
                    key = v
                elif n == field("map.value"):
                    value = v
            if value is not None:
                yield key, value


def planes(path):
    """``[(plane name, serialized XPlane)]`` of a profile file."""
    with open(path, "rb") as f:
        space = f.read()
    out = []
    for number, _, plane in walk(space):
        if number == field("XSpace.planes"):
            name = next((_text(v) for n, _, v in walk(plane)
                         if n == field("XPlane.name")), "")
            out.append((name, plane))
    return out


def programs(path, wanted=None):
    """``[Module]``: the programs whose optimised HLO the profile's
    metadata plane carries; ``wanted`` (a compiled pattern searched in
    the printed name) leaves the others unparsed."""
    out = []
    for plane_name, plane in planes(path):
        if plane_name != names()["metadata_plane"]:
            continue
        stat_ids = {key for key, meta in _map_values(
            plane, field("XPlane.stat_metadata"))
            if any(n == field("XStatMetadata.name")
                   and _text(v) == names()["hlo_proto_stat"]
                   for n, _, v in walk(meta))}
        for _, meta in _map_values(plane, field("XPlane.event_metadata")):
            printed, protos = "", []
            for n, _, v in walk(meta):
                if n == field("XEventMetadata.name"):
                    printed = _text(v)
                elif n == field("XEventMetadata.stats"):
                    stat = {m: w for m, _, w in walk(v)}
                    if stat.get(field("XStat.metadata_id")) in stat_ids \
                            and field("XStat.bytes_value") in stat:
                        protos.append(stat[field("XStat.bytes_value")])
            if wanted is not None and not wanted.search(printed):
                continue
            out += [_module(proto, printed) for proto in protos]
    return out


# ---------------------------------------------------------------------------
# whose time an instruction's is
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Attribution:
    kind: str           # forward | backward | optimizer | mixed | step |
    #                     unscoped | unjoined
    roles: str          # "fwd", "bwd+opt", "" where it has none
    op_type: str = ""   # the graph op most of its operations name
    node: str = ""
    parameter: str = ""     # the parameter most of its updates name

    @property
    def whose(self):
        """``fwd``, ``bwd+opt``, ... or, with no role, the kind."""
        return self.roles or self.kind


def graph_ops(op_name):
    """``[(role, op_type, node, parameter)]`` of the ``hetu.`` scopes in
    one ``op_name`` (jax nests them only where one node's compute
    traces another's, which no op does)."""
    spec, out = names(), []
    for found in re.finditer(spec["scope"], op_name):
        parameter = ""
        if found.group("role") == "opt":
            level = re.match(spec["parameter"], op_name[found.end():])
            parameter = level.group("parameter") if level else ""
        out.append((found.group("role"), found.group("op_type"),
                    found.group("node"), parameter))
    return out


def attribute(module):
    """``{instruction name: Attribution}`` for every instruction of
    ``module``."""
    spec = names()
    step_scope = re.compile(spec["step_scope"])
    # (Counter of graph ops, has a hetu.step scope): an instruction's own
    # with its callees' folded in, by name; a computation's, by id
    whole_of, folded = {}, {}

    def fold(ident):
        if ident not in folded:
            ops, step = collections.Counter(), False
            for instr in module.computations.get(ident, ("", ()))[1]:
                more, has = whole(instr)
                ops.update(more)
                step = step or has
            folded[ident] = (ops, step)
        return folded[ident]

    def whole(instr):
        if instr.name not in whole_of:
            ops = collections.Counter(graph_ops(instr.op_name))
            step = bool(step_scope.search(instr.op_name))
            for ident in instr.calls:
                more, has = fold(ident)
                ops.update(more)
                step = step or has
            whole_of[instr.name] = (ops, step)
        return whole_of[instr.name]

    out = {}
    for name, instr in module.instructions.items():
        ops, step = whole(instr)
        roles = sorted({op[0] for op in ops})
        if not roles:
            out[name] = Attribution("step" if step else "unscoped", "")
            continue
        _, op_type, node, _ = ops.most_common(1)[0][0]
        updated = collections.Counter()
        for op, count in ops.items():
            if op[3]:
                updated[op[3]] += count
        kind = spec["roles"][roles[0]] if len(roles) == 1 else "mixed"
        out[name] = Attribution(
            kind, "+".join(roles), op_type, node,
            updated.most_common(1)[0][0] if updated else "")
    return out


KINDS = ("forward", "backward", "optimizer", "mixed", "step", "unscoped",
         "unjoined")


def instruction_name(raw):
    """``%fusion.1499 = bf16[...] fusion(...)`` -> ``fusion.1499``; a
    bare name (the CPU's ``hlo_op``) as it is."""
    return raw.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """``[(name, self ns)]`` of one line's ``[(name, start, end)]``: an
    event's duration less that of the events directly inside it."""
    out, open_ = [], []      # open_: [index into out, end]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][1] <= start:
            open_.pop()
        if open_ and end <= open_[-1][1]:
            out[open_[-1][0]][1] -= end - start
        out.append([name, end - start])
        open_.append([len(out) - 1, end])
    return [(name, ns) for name, ns in out]


@dataclasses.dataclass
class Account:
    steps: int
    total_ns: int
    by_kind: dict           # kind -> ns
    rows: list              # [(instruction, Attribution, calls, ns)]
    program: str

    def ms_per_step(self, kind):
        return self.by_kind[kind] / 1e6 / self.steps

    def unscoped_pct(self):
        return 100.0 * (self.by_kind["unscoped"]
                        + self.by_kind["unjoined"]) / self.total_ns

    def grouped(self, key):
        """``[(key(Attribution), calls a step, ms a step)]``, most time
        first."""
        by = collections.defaultdict(lambda: [0, 0])
        for _, att, calls, ns in self.rows:
            cell = by[key(att)]
            cell[0] += calls
            cell[1] += ns
        return sorted(((k, c / self.steps, ns / 1e6 / self.steps)
                       for k, (c, ns) in by.items()), key=lambda r: -r[2])


def book(module, executions, steps=None):
    """The :class:`Account` of ``executions`` — one list of
    ``[(raw event name, start, end)]`` a whole run of the step, the
    events of ONE line — against ``module``; None (and the logged
    reason) where the join does not hold. ``steps`` where it is not the
    number of lists (the CPU runs a step on several threads, a line
    each)."""
    spec = names()
    attribution = attribute(module)
    by_name = collections.defaultdict(lambda: [0, 0])
    total = 0       # what the line was busy for: the events' union
    for events in executions:
        total += xplane.total(xplane.union(
            (start, end) for _, start, end in events))
        for raw, ns in self_times(events):
            cell = by_name[instruction_name(raw)]
            cell[0] += 1
            cell[1] += ns
    if not total:
        log({"none": "no whole execution of the step in the window"})
        return None
    unjoined = Attribution("unjoined", "")
    rows = sorted(((name, attribution.get(name, unjoined), calls, ns)
                   for name, (calls, ns) in by_name.items()),
                  key=lambda r: -r[3])
    by_kind = dict.fromkeys(KINDS, 0)
    for _, att, _, ns in rows:
        by_kind[att.kind] += ns
    account = Account(steps or len(executions), total, by_kind, rows,
                      f"{module.name}({module.program_id})")
    booked = sum(by_kind.values())
    if abs(booked - total) > spec["conservation"] * total:
        log({"none": "the roles do not sum to the time the events cover "
             "(events of one line that overlap without nesting?)",
             "booked_ns": booked, "covered_ns": total})
        return None
    if by_kind["unjoined"] > spec["unjoined_share"] * total:
        missing = [r for r in rows if r[1].kind == "unjoined"]
        log({"none": "events whose instruction the module does not hold",
             "program": account.program, "instructions": len(missing),
             "share": by_kind["unjoined"] / total,
             "first": [r[0] for r in missing[:3]]})
        return None
    if by_kind["unscoped"] > spec["unscoped_share"] * total:
        log({"none": "over half the step's time carries no hetu. scope: "
             "a program of a commit without them, or an executable "
             "compiled before they existed (a compile-cache entry of an "
             "older commit at the same path; the key leaves metadata "
             "out: measure from a fresh copy directory).",
             "program": account.program,
             "unscoped_share": by_kind["unscoped"] / total})
        return None
    return account


# ---------------------------------------------------------------------------
# the device's events
# ---------------------------------------------------------------------------

def step_executions(path, window):
    """``{printed module name: [[(raw name, start, end)]]}``: the
    ``XLA Ops`` events of every whole execution of a training step
    inside ``window`` (ns), by the step's printed name, over every
    device plane."""
    from jax.profiler import ProfileData
    spec = names()
    is_step = re.compile(spec["step_module"])
    lo, hi = window
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        runs, ops = [], []
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                runs = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in line.events
                    if is_step.search(e.name))
            elif line.name == xplane.OPS_LINE:
                ops = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in line.events)
        at = 0
        for start, end, printed in runs:
            if start < lo or end > hi:
                continue
            while at < len(ops) and ops[at][0] < start:
                at += 1
            inside = []
            while at < len(ops) and ops[at][0] < end:
                if ops[at][1] <= end:
                    inside.append((ops[at][2], ops[at][0], ops[at][1]))
                at += 1
            out[printed].append(inside)
    return out


def choose(modules, executions):
    """Of the programs of one printed name, the one that holds most of
    the events' instructions (a second compile of one function is a
    second program id)."""
    seen = {instruction_name(raw) for events in executions
            for raw, _, _ in events}
    return max(modules, key=lambda m: len(seen & m.instructions.keys()),
               default=None)


@functools.lru_cache(maxsize=4)
def _account(path, mtime, window):
    spec = names()
    by_program = step_executions(path, window)
    if not by_program:
        log({"none": "no whole execution of a training step "
             f"({spec['step_module']}) in the window"})
        return None
    # one training program a cell: the one that ran most
    printed = max(by_program, key=lambda p: len(by_program[p]))
    found = re.match(spec["module_name"], printed)
    candidates = [m for m in programs(
        path, re.compile(re.escape(found.group("name"))))
        if m.name == found.group("name")]
    exact = [m for m in candidates if m.program_id == found.group("id")]
    module = choose(exact or candidates, by_program[printed])
    if module is None:
        log({"none": "the profile carries no module of that name",
             "program": printed, "planes": [n for n, _ in planes(path)]})
        return None
    account = book(module, by_program[printed])
    if account is not None:
        report(account)
    return account


def find_profile():
    """The newest profile under the benchmark's trace directory
    (``run.py`` clears a cell's before its run, and the readers run
    before it is cleared again), or None."""
    from hetu_tpu import cachedir
    paths = glob.glob(os.path.join(
        cachedir.STATE_ROOT, "bench_trace", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def account(path, window):
    """The step's :class:`Account` from the profile at ``path`` within
    ``window`` (ns, the profile's clock), memoised: five metrics ask."""
    return _account(path, os.path.getmtime(path), tuple(window))


TOP_OPS, TOP_PARAMETERS = 15, 10     # rows of the two logged tables


def report(account):
    """The account as the other readers log theirs: the roles, the
    pairs that are mixed, the (role, op_type) rows with most time and
    the parameters with most update time."""
    ms = {k: account.ms_per_step(k) for k in KINDS}
    log({"program": account.program, "steps": account.steps,
         "ms_per_step": ms, "sum_ms_per_step": sum(ms.values()),
         "events_ms_per_step": account.total_ns / 1e6 / account.steps,
         "unscoped_pct": account.unscoped_pct()})
    log({"mixed_ms_per_step_by_pair": {
        k: v for k, _, v in account.grouped(
            lambda a: a.roles if a.kind == "mixed" else None) if k}})
    log({"by_role_and_op_type": [
        [f"{k[0]}/{k[1]}", calls, v] for k, calls, v in account.grouped(
            lambda a: (a.whose, a.op_type))[:TOP_OPS]]})
    # a parameter's update alone, and inside instructions of other roles
    # too (a weight gradient's matmul with the update fused in)
    alone = {k: v for k, _, v in account.grouped(
        lambda a: a.parameter if a.kind == "optimizer" else None)}
    fused = {k: v for k, _, v in account.grouped(
        lambda a: a.parameter if a.kind == "mixed" else None)}
    table = sorted(((k, alone.get(k, 0.0), fused.get(k, 0.0))
                    for k in (alone.keys() | fused.keys()) - {None, ""}),
                   key=lambda r: -(r[1] + r[2]))
    log({"ms_per_step_by_parameter": {
        "columns": ["parameter", "optimizer", "mixed"],
        "rows": [list(r) for r in table[:TOP_PARAMETERS]]}})


def metric(trace, facts, what):
    """What the ``step.*`` readers return: ``what`` is a kind of
    ``KINDS`` (ms a step) or ``"unscoped_pct"``; None where there is no
    profile, no device plane, or no account."""
    if trace is None or not xplane.device_planes(trace):
        return None
    path = find_profile()
    if path is None:
        return None
    found = account(path, xplane.window(trace))
    if found is None:
        return None
    if what == "unscoped_pct":
        return found.unscoped_pct()
    return found.ms_per_step(what)
