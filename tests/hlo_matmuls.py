"""Where the operands of a compiled step's matmuls come from.

``matmul_fusions(text)`` reads the optimized HLO of a step
(``compiled.as_text()``) and, for every fusion of the entry computation
whose called computation holds a ``convolution`` (XLA's name for a
matmul on the TPU), follows each operand of that convolution back
through the instructions that move or convert and compute nothing
(``convert``, ``bitcast``, ``copy``, ``transpose``, ``reshape`` and a
nested fusion of only those) to the fused computation's parameter, and
from there to the instruction of the entry computation that the fusion
takes in that place. An operand that is computed inside the fusion (a
GELU, a LayerNorm's scaling) has no such source and is left out.

Used by ``tests/test_chip_compile.py`` (no float32 weight reaches a
matmul) and by the builder's chip scripts, which join the rows with a
profile's events by the fusion's name.
"""
import re

_MOVES = ("convert", "bitcast", "copy", "transpose", "reshape")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*?\{\n(.*?)^\}",
                          re.MULTILINE | re.DOTALL)
_LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) "
                   r"([\w\-]+)\((.*?)\)(?:, |$)", re.MULTILINE)
_TYPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^{}]*\})?")


def _computations(text):
    """{name: {instruction: (type, opcode, [operand names], its line)}},
    and the entry computation's name."""
    found, entry = {}, None
    for m in _COMPUTATION.finditer(text):
        body = {}
        for ln in _LINE.finditer(m.group(2)):
            name, result, opcode, operands = ln.groups()
            line = m.group(2)[ln.start():m.group(2).find("\n", ln.end())]
            body[name] = (result, opcode,
                          re.findall(r"%[\w.\-]+", operands), line)
        found[m.group(1)] = body
        if m.group(0).startswith("ENTRY"):
            entry = m.group(1)
    return found, entry


def _called(line):
    m = re.search(r"calls=(%[\w.\-]+)", line)
    return m.group(1) if m else None


def _root(body):
    for name, (_, _, _, line) in body.items():
        if line.lstrip().startswith("ROOT "):
            return name
    return None


def _source(comps, comp, name, seen_convert=False):
    """(parameter number of ``comp`` that ``name`` is a moved or
    converted copy of, whether a convert lay on the way), or None where
    ``name`` is computed."""
    result, opcode, operands, line = comps[comp][name]
    if opcode == "parameter":
        return int(re.search(r"parameter\((\d+)\)", line).group(1)), \
            seen_convert
    if opcode in _MOVES and len(operands) == 1:
        return _source(comps, comp, operands[0],
                       seen_convert or opcode == "convert")
    if opcode == "fusion":
        inner = _called(line)
        got = _source(comps, inner, _root(comps[inner])) \
            if inner in comps else None
        if got is None:
            return None
        number, converted = got
        return _source(comps, comp, operands[number],
                       seen_convert or converted)
    return None


def describe(type_text):
    """``f32[3072,768]{1,0:T(8,128)S(1)}`` -> (``f32``, (3072, 768),
    whether the layout places it in on-chip memory ``S(1)``)."""
    m = _TYPE.search(type_text)
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims, "S(1)" in (m.group(3) or "")


def result_types(row):
    """``describe`` of every array a fusion of ``matmul_fusions``
    returns."""
    return [describe(m.group(0)) for m in _TYPE.finditer(row["result"])]


def matmul_fusions(text):
    """One row a fusion of the entry computation that holds a
    convolution: ``{"name", "result", "operands": [{"side", "dtype",
    "shape", "on_chip", "converted", "from": the opcode (and, of a
    custom call, its target) of the entry instruction that feeds it,
    "from_name"}]}``, an entry a convolution operand that is a moved or
    converted copy of one of the fusion's own operands."""
    comps, entry = _computations(text)
    rows = []
    for name, (result, opcode, operands, line) in comps[entry].items():
        inner = _called(line) if opcode == "fusion" else None
        if inner not in comps:
            continue
        convs = [(n, v) for n, v in comps[inner].items()
                 if v[1] == "convolution"]
        if not convs:
            continue
        row = {"name": name, "result": result, "operands": []}
        for _, (_, _, conv_operands, _) in convs:
            for side, operand in zip(("lhs", "rhs"), conv_operands):
                got = _source(comps, inner, operand)
                if got is None:
                    continue
                number, converted = got
                param = next(v for v in comps[inner].values()
                             if v[1] == "parameter" and
                             f"parameter({number})" in v[3])
                dtype, shape, on_chip = describe(param[0])
                fed_by = comps[entry].get(operands[number])
                origin = fed_by[1] if fed_by else "?"
                if fed_by and origin == "custom-call":
                    target = re.search(r'custom_call_target="([^"]+)"',
                                       fed_by[3])
                    origin += f":{target.group(1)}" if target else ""
                row["operands"].append({
                    "side": side, "dtype": dtype, "shape": shape,
                    "on_chip": on_chip, "converted": converted,
                    "from": origin, "from_name": operands[number]})
        rows.append(row)
    return rows
