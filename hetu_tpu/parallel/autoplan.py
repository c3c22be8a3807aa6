"""Cost-model auto-parallelism planner (ROADMAP item 4's endpoint).

Hand-written parallel configs — per-node ``ht.dispatch`` specs, stage
contexts, microbatch counts — become one declarative call::

    exe = Executor([loss, train_op], parallel="auto",
                   rules={"out": "tp", "vocab": "tp", "embed": None})

The planner:

1. **enumerates candidates** — factorizations of the world size into
   ``(dp, tp, pp)`` mesh shapes, pruned against the graph (a tp that
   divides no rule-splittable parameter dim is invalid; a pp deeper
   than the graph's cuttable layer chain is invalid),
2. **compiles the rules table** down to the existing partition-state
   machinery: ``rules={logical_axis: mesh_axis|None}`` (the
   Alpa/GSPMD ``DEFAULT_RULES`` idiom, SNIPPETS.md [2]/[3]) maps each
   parameter's inferred logical axes onto per-dim split counts — i.e.
   exactly the ``Dispatch`` specs ``parallel/planner.py`` already
   lowers through ``propagate_statuses`` / ``spec_for_status``; a
   hand-written Dispatch that contradicts the compiled rule is an
   HT205 finding (plan-vs-rules conflict),
3. **scores each candidate** with a closed-form cost model built on
   PR 8's measured :class:`~hetu_tpu.telemetry.costdb.CostDB`:

   * compute from per-op DB entries (``profile_ops`` populated),
     FLOPs-proportional fallback on a miss — calibrated against the
     ops the DB *did* measure, cold-start ``cold_start_flops_ms``
     when it measured none;
   * comm from the DB's latency+bandwidth curves applied to the dp
     gradient-allreduce bytes, the implicit-reshard byte volumes the
     HT203 sharding pass computes, and the pipeline boundary bytes;
   * pipeline bubble from the schedule's analytic fill/drain fraction
     (``pipeline.analytic_bubble_fraction`` — the interleaved V>1
     form included), with per-tick overhead from the p2p latency
     curve, which is what auto-picks M, V and fuse_ticks;

4. **optionally refines** the top-k finalists by measurement (the
   caller's ``measure``, each finalist once, the least wins), and
5. **applies** the winner: Dispatch markers spliced for tp, stage
   contexts assigned over the balanced per-op-cost cut for pp, and
   the executor kwargs (schedule, M, ``pp_options``) returned.

Estimates are labeled ``measured`` / ``curve`` / ``cold_start`` per
input (``CostDB.coverage``), and the report — printed by
``heturun --autoplan`` — carries the split, so a ranking that rests on
guesses says so on its face.
"""
from __future__ import annotations

import logging

import numpy as np

__all__ = ["DEFAULT_RULES", "Plan", "AutoPlanResult", "logical_axes_of",
           "compile_rules", "apply_rules", "enumerate_candidates",
           "balance_stages", "graph_costs", "score_plan", "choose_plan",
           "apply_plan", "plan_key"]

logger = logging.getLogger(__name__)

# the exemplar rules shape (SNIPPETS.md [2]/[3]): logical axis -> mesh
# axis or None (replicated). "dp"/"tp" are the planner's mesh axes; a
# model can extend the vocabulary via Variable(..).logical_axes.
DEFAULT_RULES = {
    "batch": "dp",      # feed batch dim (data parallelism)
    "in": None,         # matmul contraction dim: replicated
    "out": "tp",        # matmul output features: column-split
    "vocab": "tp",      # embedding rows / output vocab
    "embed": None,      # embedding width / bias dims
    "cout": "tp",       # conv output channels
}

_M_CANDIDATES = (2, 4, 8, 16, 32)
_V_CANDIDATES = (1, 2, 4)
_TRAIN_FLOP_FACTOR = 3.0    # fwd + ~2x fwd for the backward
# at Executor construction feeds are unshaped, so activation shapes are
# unknown; weight-touching ops then assume this batch for their FLOPs
# (pass feed_shapes / autoplan_options={"feed_shapes": ...} for exact
# numbers — ranking only needs relative mass, which params dominate)
_DEFAULT_BATCH = 32


class Plan:
    """One candidate parallel configuration plus its predicted cost."""

    __slots__ = ("dp", "tp", "pp", "M", "V", "fuse_ticks", "schedule",
                 "stage_cut", "predicted_ms", "measured_ms",
                 "breakdown", "bindings", "rules", "notes")

    def __init__(self, dp=1, tp=1, pp=1, M=1, V=1, fuse_ticks=1,
                 schedule="spmd", stage_cut=(), predicted_ms=None,
                 breakdown=None, bindings=(), rules=None, notes=()):
        self.dp, self.tp, self.pp = int(dp), int(tp), int(pp)
        self.M, self.V = int(M), int(V)
        self.fuse_ticks = int(fuse_ticks)
        self.schedule = schedule
        self.stage_cut = tuple(stage_cut)
        self.predicted_ms = predicted_ms
        self.measured_ms = None
        self.breakdown = dict(breakdown or {})
        self.bindings = tuple(bindings)
        self.rules = dict(rules) if rules is not None else None
        self.notes = tuple(notes)

    @property
    def nworld(self):
        return self.dp * self.tp * self.pp

    def key(self):
        return (self.dp, self.tp, self.pp, self.M, self.V,
                self.fuse_ticks)

    def describe(self):
        s = f"dp{self.dp}·tp{self.tp}·pp{self.pp}"
        if self.pp > 1:
            s += f" {self.schedule} M={self.M}"
            if self.V > 1:
                s += f" V={self.V}"
            if self.fuse_ticks > 1:
                s += f" fuse={self.fuse_ticks}"
        return s

    def to_dict(self):
        return {"dp": self.dp, "tp": self.tp, "pp": self.pp,
                "M": self.M, "V": self.V, "fuse_ticks": self.fuse_ticks,
                "schedule": self.schedule, "stage_cut": list(self.stage_cut),
                "predicted_ms": self.predicted_ms,
                "measured_ms": self.measured_ms,
                "breakdown": self.breakdown, "notes": list(self.notes)}

    def __repr__(self):
        return f"Plan({self.describe()}, predicted={self.predicted_ms})"


def plan_key(plan):
    """Stable string form of a plan's knobs — the CI snapshot unit."""
    return "dp{}-tp{}-pp{}-M{}-V{}-f{}".format(*plan.key())


# ---------------------------------------------------------------------------
# rules -> Dispatch specs
# ---------------------------------------------------------------------------

def logical_axes_of(param, topo):
    """Per-dim logical axis names of a trainable parameter: an explicit
    ``param.logical_axes`` wins; otherwise inferred from the consuming
    op (the same classification the TP examples hand-annotate): matmul
    weights are ("in", "out"), embedding tables ("vocab", "embed"),
    conv filters ("cout", "cin", "kh", "kw"), 1-D params ("embed",)."""
    explicit = getattr(param, "logical_axes", None)
    if explicit:
        return tuple(explicit)
    from ..ops.comm import DispatchOp
    from ..ops.embedding import EmbeddingLookUp
    from ..ops.linalg import MatMulOp, BatchMatMulOp
    try:
        from ..ops.conv import Conv2dOp
    except ImportError:         # pragma: no cover - conv always present
        Conv2dOp = ()
    ndim = len(getattr(param, "shape", ()) or ())
    # see through hand Dispatch wrappers: the classifying consumer of
    # dispatch(param, ...) is the param's consumer
    alias = {param}
    for node in topo:
        if isinstance(node, DispatchOp) and node.inputs \
                and node.inputs[0] in alias:
            alias.add(node)

    def feeds(node, pos=None):
        ins = getattr(node, "inputs", ())
        if pos is not None:
            return len(ins) > pos and ins[pos] in alias
        return any(i in alias for i in ins)

    for node in topo:
        if not feeds(node):
            continue
        if isinstance(node, EmbeddingLookUp) and feeds(node, 0):
            return ("vocab", "embed")
        if isinstance(node, (MatMulOp, BatchMatMulOp)) \
                and feeds(node, 1) and ndim == 2:
            return ("in", "out")
        if Conv2dOp and isinstance(node, Conv2dOp) \
                and feeds(node, 1) and ndim == 4:
            return ("cout", "cin", "kh", "kw")
    if ndim == 1:
        return ("embed",)
    return None


class RuleBinding:
    """One parameter's compiled split: the Dispatch spec the rules table
    implies (``parts`` is the DispatchOp constructor tuple)."""

    __slots__ = ("param", "axes", "parts", "dim", "axis_name")

    def __init__(self, param, axes, parts, dim, axis_name):
        self.param = param
        self.axes = axes
        self.parts = parts
        self.dim = dim
        self.axis_name = axis_name

    def __repr__(self):
        return (f"RuleBinding({self.param.name}: {self.axes} -> "
                f"parts {self.parts})")


def compile_rules(eval_nodes, rules=None, tp=1, topo=None):
    """Compile a ``{logical_axis: mesh_axis|None}`` table down to
    per-parameter Dispatch ``parts`` tuples (the hand-spec equivalent).

    Returns ``(bindings, conflicts)``: one :class:`RuleBinding` per
    parameter the rules split ``tp`` ways, and an HT205 conflict entry
    per parameter whose graph ALREADY carries a hand Dispatch that
    disagrees with the compiled rule (the hand spec wins at apply
    time — silent double-splitting would corrupt the plan the user
    asked for, so it is a structured finding)."""
    from ..graph.autodiff import find_topo_sort
    from ..ops.comm import DispatchOp
    from ..ops.variable import PlaceholderOp
    from ..analysis.findings import emit

    rules = dict(DEFAULT_RULES) if rules is None else dict(rules)
    if topo is None:
        topo = find_topo_sort(list(eval_nodes))
    hand = {}       # param -> existing DispatchOp parts
    for node in topo:
        if isinstance(node, DispatchOp) and node.inputs \
                and isinstance(node.inputs[0], PlaceholderOp):
            hand[node.inputs[0]] = node.parts
    bindings, conflicts = [], []
    if tp <= 1:
        return bindings, conflicts
    for node in topo:
        if not (isinstance(node, PlaceholderOp) and node.trainable):
            continue
        axes = logical_axes_of(node, topo)
        if not axes:
            continue
        shape = tuple(getattr(node, "shape", ()) or ())
        for dim, axis in enumerate(axes):
            if rules.get(axis) != "tp":
                continue
            if dim >= len(shape) or shape[dim] % tp != 0:
                continue
            parts = tuple(tp if d == dim else 1
                          for d in range(len(shape)))
            if node in hand:
                if tuple(hand[node]) != parts:
                    msg = (f"plan-vs-rules conflict on {node.name}: "
                           f"hand-written dispatch {tuple(hand[node])} "
                           f"vs rules-compiled {parts} (axis "
                           f"{axis!r} -> tp={tp}) — the hand spec "
                           f"wins; drop it or fix the rules table")
                    conflicts.append((node, tuple(hand[node]), parts))
                    if not emit("HT205", "warn", msg, node=node):
                        logger.warning("%s", msg)
                break       # hand spec present: never double-split
            bindings.append(RuleBinding(node, axes, parts, dim, axis))
            break           # one split dim per param
    return bindings, conflicts


def apply_rules(eval_nodes, bindings, shapes=None):
    """Splice the compiled Dispatch markers into the graph: for each
    binding, consumers of the parameter are rewired through a fresh
    ``DispatchOp(param, parts)``, and each split op's OUTPUT is rewired
    through an all-ones gather dispatch — the hand-TP idiom's
    ``act = ht.dispatch(act, (1, 1))`` between layers, without which
    consecutive splits compound through ``deduce_states`` into a
    tp^depth-way plan. From here the existing planner
    (``propagate_statuses`` -> ``spec_for_status``) owns everything,
    exactly as if the user had written the specs by hand."""
    from ..graph.autodiff import find_topo_sort
    from ..ops.comm import dispatch

    if not bindings:
        return []
    topo = find_topo_sort(list(eval_nodes))
    shapes = shapes or {}
    spliced = []
    consumers_of = {}
    for node in topo:
        for i in getattr(node, "inputs", ()):
            consumers_of.setdefault(id(i), []).append(node)
    for b in bindings:
        d = dispatch(b.param, b.parts, ctx=b.param.raw_ctx)
        split_ops = []
        for node in consumers_of.get(id(b.param), ()):
            if node is d:
                continue
            node.inputs = [d if i is b.param else i
                           for i in node.inputs]
            split_ops.append(node)
        spliced.append(d)
        for op in split_ops:
            out_shape = shapes.get(op)
            ndim = len(out_shape) if out_shape else 2
            g = dispatch(op, (1,) * ndim, ctx=op.raw_ctx)
            for cons in consumers_of.get(id(op), ()):
                if cons is g:
                    continue
                cons.inputs = [g if i is op else i
                               for i in cons.inputs]
            spliced.append(g)
    return spliced


# ---------------------------------------------------------------------------
# per-op cost extraction
# ---------------------------------------------------------------------------

def flops_of(node, shapes):
    """Analytic forward FLOPs of one op (the fallback scale when the
    CostDB has no measured entry): matmul/conv count multiply-adds,
    everything else counts one op per output element."""
    out = shapes.get(node) or ()
    ins = [shapes.get(i) for i in getattr(node, "inputs", ())]
    kind = type(node).__name__

    def prod(s):
        try:
            return int(np.prod([int(d) for d in s])) if s else 0
        except (TypeError, ValueError):
            return 0

    if kind == "MatMulOp" and len(ins) == 2 and ins[1]:
        if ins[0] and out:
            # contraction dim honors the transpose flag: a gradient
            # matmul (trans_A=True) contracts over ins[0][-2], and
            # reading [-1] there inflates its FLOPs by the weight dim
            k = int(ins[0][-2]
                    if getattr(node, "matmul_attr_trans_A", False)
                    else ins[0][-1])
            return 2.0 * prod(out) * k
        # activation shape unknown (construction-time planning):
        # assume the default batch over the known weight
        return 2.0 * _DEFAULT_BATCH * prod(ins[1])
    if kind == "BatchMatMulOp" and len(ins) == 2 and ins[0] and out:
        return 2.0 * prod(out) * int(ins[0][-1])
    if kind == "Conv2dOp" and len(ins) == 2 and ins[1] and len(
            ins[1]) == 4:
        cin, kh, kw = int(ins[1][1]), int(ins[1][2]), int(ins[1][3])
        base = prod(out) if out else \
            _DEFAULT_BATCH * int(ins[1][0])
        return 2.0 * base * cin * kh * kw
    if kind in ("EmbeddingLookUp", "EmbeddingLookUpGradient"):
        return float(prod(out)) if out else \
            float(_DEFAULT_BATCH * (ins[0][-1] if ins[0] else 1))
    return float(prod(out))


def _bytes_of(shape, itemsize=4):
    try:
        return int(np.prod([int(d) for d in shape])) * itemsize \
            if shape else 0
    except (TypeError, ValueError):
        return 0


def graph_costs(eval_nodes, db=None, feed_shapes=None, topo=None):
    """Per-op compute costs + the volumes the comm model needs.

    Returns a dict:

    * ``op_ms``      — {node: ms} per forward op (training factor
      applied), measured entries preferred, FLOPs-scaled otherwise;
    * ``sources``    — {node: "measured"|"flops_scaled"|"cold_start"};
    * ``fwd_order``  — the forward (non-placeholder, non-optimizer)
      ops in topo order (the stage-cut axis);
    * ``shapes``     — the shape map (for comm-byte estimates);
    * ``param_bytes``— total trainable parameter bytes;
    * ``splittable`` — {tp candidate divisor -> True} probe source:
      per-param dim sizes the rules could split.
    """
    from ..graph.autodiff import find_topo_sort
    from ..optimizer import OptimizerOp
    from ..ops.variable import PlaceholderOp
    from ..analysis.findings import Report
    from ..analysis.shapes import shape_pass
    from ..telemetry import costdb as _costdb

    if topo is None:
        topo = find_topo_sort(list(eval_nodes))
    shapes = shape_pass(topo, Report(), feed_shapes=feed_shapes) or {}

    # the stage-cut axis is the FORWARD graph only (pipeline stages
    # place forward ops; each stage's backward is its own vjp) — the
    # _TRAIN_FLOP_FACTOR on forward op costs accounts for the backward,
    # so costing grad ops separately would double-count it
    fwd_roots = [n for n in eval_nodes
                 if not isinstance(n, OptimizerOp)]
    fwd_topo = find_topo_sort(fwd_roots) if fwd_roots else []
    fwd = [n for n in fwd_topo if not isinstance(n, PlaceholderOp)]
    params = [n for n in topo
              if isinstance(n, PlaceholderOp) and n.trainable]

    # measured-vs-flops calibration: ops the DB measured anchor the
    # FLOPs scale for the ones it did not
    op_ms, sources = {}, {}
    cal_fl, cal_ms = 0.0, 0.0
    measured = {}
    if db is not None:
        for node in fwd:
            ent = db.get(type(node).__name__, shapes.get(node))
            if ent is not None:
                measured[node] = float(ent["ms"])
                fl = flops_of(node, shapes)
                if fl > 0 and ent["ms"] > 0:
                    cal_fl += fl
                    cal_ms += float(ent["ms"])
    flops_per_ms = (cal_fl / cal_ms) if cal_ms > 0 else None
    for node in fwd:
        if node in measured:
            op_ms[node] = measured[node] * _TRAIN_FLOP_FACTOR
            sources[node] = "measured"
            continue
        fl = flops_of(node, shapes) * _TRAIN_FLOP_FACTOR
        if flops_per_ms:
            op_ms[node] = fl / flops_per_ms
            sources[node] = "flops_scaled"
        else:
            op_ms[node] = _costdb.cold_start_flops_ms(fl)
            sources[node] = "cold_start"

    splittable = set()
    for p in params:
        for d in tuple(getattr(p, "shape", ()) or ()):
            # divisors up to a practical mesh width — a million-row
            # embedding table must not cost a million-iteration scan
            for q in range(2, min(int(d), 512) + 1):
                if d % q == 0:
                    splittable.add(q)
    return {
        "op_ms": op_ms,
        "sources": sources,
        "fwd_order": fwd,
        "shapes": shapes,
        "params": params,
        "param_bytes": sum(_bytes_of(p.shape) for p in params),
        "splittable": splittable,
        "topo": topo,
    }


def balance_stages(costs, fwd_order, pp):
    """Contiguous pp-way cut of the forward op chain minimizing the max
    stage cost (greedy over prefix sums — the per-op measured costs are
    what makes "balanced" mean milliseconds, not op counts). Returns
    (cut_indices, stage_ms): ``cut_indices`` are the pp-1 topo
    positions where a new stage starts."""
    ms = [max(0.0, costs.get(n, 0.0)) for n in fwd_order]
    total = sum(ms)
    if pp <= 1 or not ms:
        return (), [total]
    target = total / pp
    cuts, acc, stage_ms = [], 0.0, []
    for i, v in enumerate(ms):
        remaining_stages = pp - len(cuts)
        if len(cuts) < pp - 1 and acc >= target and \
                len(ms) - i >= remaining_stages - 1:
            cuts.append(i)
            stage_ms.append(acc)
            acc = 0.0
        acc += v
    stage_ms.append(acc)
    while len(stage_ms) < pp:       # degenerate: not enough mass
        stage_ms.append(0.0)
    return tuple(cuts), stage_ms


# ---------------------------------------------------------------------------
# candidate enumeration + scoring
# ---------------------------------------------------------------------------

def enumerate_candidates(nworld, info=None, rules=None, max_pp=None):
    """``(dp, tp, pp)`` factorizations of every device count up to
    ``nworld`` (a tiny model's best plan is often to use FEWER devices
    than the world — the single-device (1,1,1) baseline is always a
    candidate), pruned against the graph. Returns (valid, rejected)
    where rejected pairs each pruned tuple with its reason — the
    enumeration must be auditable, not just correct."""
    valid, rejected = [], []
    rules = dict(DEFAULT_RULES) if rules is None else dict(rules)
    tp_on = any(v == "tp" for v in rules.values())
    splittable = (info or {}).get("splittable", set())
    n_ops = len((info or {}).get("fwd_order", ()))
    seen = set()
    for world in _divisors(nworld):
        for dp in _divisors(world):
            for tp in _divisors(world // dp):
                pp = world // dp // tp
                cand = (dp, tp, pp)
                if cand in seen:
                    continue
                seen.add(cand)
                if tp > 1 and not tp_on:
                    rejected.append(
                        (cand, "rules bind no axis to tp"))
                    continue
                if tp > 1 and info is not None \
                        and tp not in splittable:
                    rejected.append(
                        (cand,
                         f"no parameter dim divisible by tp={tp}"))
                    continue
                if max_pp is not None and pp > max_pp:
                    rejected.append(
                        (cand, f"pp={pp} > max_pp={max_pp}"))
                    continue
                if pp > 1 and info is not None and pp > max(n_ops, 1):
                    rejected.append(
                        (cand,
                         f"pp={pp} deeper than the {n_ops}-op chain"))
                    continue
                valid.append(cand)
    return valid, rejected


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def _comm_est(db, kind, nbytes):
    """(ms, source) from the CostDB with the cold-start floor."""
    from ..telemetry import costdb as _costdb
    if db is None:
        return _costdb.cold_start_ms(kind, nbytes), "cold_start"
    return db.estimate_info(kind, nbytes, cold_start=True)


def score_plan(dp, tp, pp, info, db=None, schedule=None,
               num_microbatches=None):
    """Closed-form cost of one mesh factorization; picks the best
    (M, V, fuse_ticks, stage cut) for the pipeline dimension and
    returns the resulting :class:`Plan` with its breakdown.

    The model (docs/parallelism.md "Cost-model inputs"):

    * compute: sum of per-op ms / dp (batch split), split ops
      additionally / tp;
    * dp comm: one gradient allreduce of the (tp-reduced) parameter
      bytes;
    * tp comm: implicit-reshard volume — for each split parameter, its
      consumer's activation row is partially reduced across tp (the
      HT203 edge set), costed on the allreduce curve;
    * pp: per-stage compute from the balanced cut, wall multiplied by
      the analytic fill/drain factor ``(V·M + S - 1)/(V·M)``, plus
      boundary p2p bytes and a per-tick latency term that penalizes
      large M·V when the p2p latency curve says ticks are expensive —
      the argmin over (M, V) IS the auto-pick.
    """
    from .pipeline import analytic_bubble_fraction

    op_ms = info["op_ms"]
    shapes = info["shapes"]
    fwd = info["fwd_order"]
    notes = []

    # tp: which ops the rules-compiled split accelerates
    split_ops = set()
    if tp > 1:
        bindings = info.get("bindings") or ()
        split_params = {b.param for b in bindings}
        for node in fwd:
            if any(i in split_params for i in
                   getattr(node, "inputs", ())):
                split_ops.add(node)
    eff_ms = {n: (v / tp if n in split_ops else v)
              for n, v in op_ms.items()}
    compute_ms = sum(eff_ms.values()) / max(1, dp)

    comm_ms = 0.0
    srcs = set(info["sources"].values())
    if dp > 1:
        grad_bytes = info["param_bytes"]
        if tp > 1:
            grad_bytes = int(grad_bytes / tp)
        ms, src = _comm_est(db, "allreduce", grad_bytes)
        comm_ms += ms
        srcs.add(src)
    if tp > 1:
        # partial-sum reduction per split matmul's output row (the
        # HT203 implicit-reshard edges the sharding pass reports)
        reshard = sum(_bytes_of(shapes.get(n)) for n in split_ops)
        ms, src = _comm_est(db, "allreduce", max(1, reshard))
        comm_ms += ms
        srcs.add(src)

    if pp <= 1:
        plan = Plan(dp, tp, pp, M=1, V=1, schedule="spmd",
                    predicted_ms=compute_ms + comm_ms,
                    breakdown={"compute_ms": round(compute_ms, 4),
                               "comm_ms": round(comm_ms, 4),
                               "bubble_fraction": 0.0,
                               "sources": sorted(srcs)},
                    notes=notes)
        return plan

    cut, stage_ms = balance_stages(eff_ms, fwd, pp)
    stage_max = max(stage_ms) if stage_ms else 0.0
    # boundary tensor: the activation crossing the first cut (uniform
    # chains have one size; fall back to the largest activation)
    if cut:
        bnode = fwd[cut[0] - 1]
        bbytes = _bytes_of(shapes.get(bnode)) or 4
    else:
        bbytes = max((_bytes_of(shapes.get(n)) for n in fwd),
                     default=4)
    bbytes = max(1, bbytes // max(1, dp))

    best = None
    m_fixed = [num_microbatches] if num_microbatches else _M_CANDIDATES
    for M in m_fixed:
        for V in _V_CANDIDATES:
            if V > 1 and (M < pp or schedule == "gpipe"):
                continue        # interleaving requires M >= S devices
            bubble = analytic_bubble_fraction(pp * V, M, V)
            wall = stage_max / max(1e-9, (1.0 - bubble))
            # per-microbatch boundary transfer (fwd + cotangent) and a
            # per-tick latency term: more ticks cost more dispatch
            per_mb, src = _comm_est(db, "p2p", max(1, bbytes // M) * 2)
            ticks = V * M + pp - 1
            lat_ms, lsrc = _comm_est(db, "p2p", 1)
            pipe_comm = per_mb * M * max(1, pp - 1) / max(1, pp) \
                + ticks * lat_ms
            total = wall + comm_ms + pipe_comm
            cand = (total, M, V, bubble, pipe_comm,
                    {src, lsrc})
            if best is None or total < best[0]:
                best = cand
    total, M, V, bubble, pipe_comm, psrc = best
    srcs |= psrc
    sched = schedule or ("collective" if V > 1 else "gpipe")
    fuse = 2 if M * V >= 8 and sched == "collective" else 1
    plan = Plan(dp, tp, pp, M=M, V=V, fuse_ticks=fuse, schedule=sched,
                stage_cut=cut,
                predicted_ms=total,
                breakdown={"compute_ms": round(compute_ms, 4),
                           "stage_max_ms": round(stage_max, 4),
                           "comm_ms": round(comm_ms + pipe_comm, 4),
                           "bubble_fraction": round(bubble, 4),
                           "sources": sorted(srcs)},
                notes=notes)
    return plan


# ---------------------------------------------------------------------------
# the planner front door
# ---------------------------------------------------------------------------

class AutoPlanResult:
    """Chosen plan + the full scored candidate table + the DB coverage
    split — everything the ``--autoplan`` report prints."""

    def __init__(self, plan, candidates, rejected, coverage, model,
                 nworld, info=None):
        self.plan = plan
        self.candidates = candidates
        self.rejected = rejected
        self.coverage = coverage        # (measured kinds, guessed kinds)
        self.model = model
        self.nworld = nworld
        self.info = info                # graph_costs() output (apply reuse)

    def to_dict(self):
        measured, guessed = self.coverage
        return {"model": self.model, "nworld": self.nworld,
                "chosen": self.plan.to_dict(),
                "candidates": [p.to_dict() for p in self.candidates],
                "rejected": [{"mesh": list(c), "reason": r}
                             for c, r in self.rejected],
                "coverage": {"measured": [str(k) for k in measured],
                             "guessed": [str(k) for k in guessed]}}

    def render(self):
        """The predicted-vs-measured cost table (text)."""
        lines = [f"autoplan: {self.model} over {self.nworld} device(s)"]
        lines.append(f"{'candidate':<30} {'predicted':>12} "
                     f"{'measured':>12}  breakdown")
        for p in self.candidates:
            mark = " *" if p is self.plan else "  "
            meas = (f"{p.measured_ms:.2f} ms"
                    if p.measured_ms is not None else "-")
            bd = p.breakdown
            det = (f"compute {bd.get('compute_ms', 0):.2f} / comm "
                   f"{bd.get('comm_ms', 0):.2f} / bubble "
                   f"{bd.get('bubble_fraction', 0):.3f}")
            lines.append(f"{mark}{p.describe():<28} "
                         f"{p.predicted_ms:>9.2f} ms {meas:>12}  {det}")
        for cand, reason in self.rejected:
            lines.append(f"  pruned dp{cand[0]}·tp{cand[1]}"
                         f"·pp{cand[2]}: {reason}")
        measured, guessed = self.coverage
        lines.append(f"cost inputs measured: "
                     f"{[str(k) for k in measured] or '-'}")
        lines.append(f"cost inputs guessed (cold start): "
                     f"{[str(k) for k in guessed] or 'none'} — run "
                     f"`python -m hetu_tpu.telemetry.costdb --sweep` "
                     f"to measure")
        lines.append(f"chosen: {self.plan.describe()} "
                     f"(predicted {self.plan.predicted_ms:.2f} ms)")
        return "\n".join(lines)


def choose_plan(eval_nodes, nworld=None, rules=None, db=None,
                feed_shapes=None, num_microbatches=None, model="model",
                measure=None, topk=3, max_pp=None):
    """Enumerate, score, and (optionally) measure candidates; returns
    an :class:`AutoPlanResult` with the argmin plan.

    ``measure(plan) -> seconds`` activates the top-``topk`` refinement:
    each finalist is measured in this call and the least wins (nothing
    is kept between processes)."""
    import jax

    from ..telemetry.costdb import CostDB, COMM_KINDS

    if nworld is None:
        try:
            nworld = len(jax.devices())
        except RuntimeError:
            nworld = 1
    if db is None:
        db = CostDB()
    info = graph_costs(eval_nodes, db=db, feed_shapes=feed_shapes)
    info["db"] = db             # apply_plan derives dp knob defaults
    # (bucket_bytes) from the same DB the plan was scored on
    rules = dict(DEFAULT_RULES) if rules is None else dict(rules)

    cands, rejected = enumerate_candidates(nworld, info=info,
                                           rules=rules, max_pp=max_pp)
    plans = []
    compiled_by_tp = {}     # rules compilation depends only on tp
    for dp, tp, pp in cands:
        if tp not in compiled_by_tp:
            compiled_by_tp[tp] = compile_rules(eval_nodes, rules, tp,
                                               topo=info["topo"])
        bindings, _conf = compiled_by_tp[tp]
        if tp > 1 and not bindings:
            rejected.append(((dp, tp, pp),
                             "rules compile to no split at this tp"))
            continue
        info["bindings"] = bindings
        plan = score_plan(dp, tp, pp, info, db=db,
                          num_microbatches=num_microbatches)
        plan.bindings = tuple(bindings)
        plan.rules = dict(rules)
        plans.append(plan)
    if not plans:
        plans = [Plan(predicted_ms=sum(info["op_ms"].values()),
                      rules=rules)]
    plans.sort(key=lambda p: p.predicted_ms)

    if measure is not None and len(plans) > 1:
        k = max(1, topk)
        plans[:k] = _refine_measured(plans[:k], measure)

    comm_cov = db.coverage(COMM_KINDS)
    # fold the per-op compute coverage into the same report the doctor
    # prints: how many op costs were measured vs guessed
    n_meas = sum(1 for s in info["sources"].values()
                 if s == "measured")
    n_all = max(1, len(info["sources"]))
    measured_k, guessed_k = list(comm_cov[0]), list(comm_cov[1])
    if n_meas:
        measured_k.append(f"op-compute:{n_meas}/{n_all}")
    else:
        guessed_k.append("op-compute (FLOPs cold start)")
    return AutoPlanResult(plans[0], plans, rejected,
                          (measured_k, guessed_k), model, nworld,
                          info=info)


def _refine_measured(finalists, measure):
    """The finalists, each measured (``measured_ms``), the least
    first."""
    for plan in finalists:
        plan.measured_ms = float(measure(plan)) * 1000.0
    return sorted(finalists, key=lambda p: p.measured_ms)


# ---------------------------------------------------------------------------
# plan application (the Executor(parallel="auto") path)
# ---------------------------------------------------------------------------

def apply_plan(eval_nodes, plan, info=None, _splice_rules=True):
    """Mutate the graph per the chosen plan and return the executor
    kwargs overrides ``HetuConfig`` merges in:

    * tp: the compiled Dispatch markers splice in (``apply_rules``) —
      the existing planner lowers them from here;
    * pp: forward ops get stage device contexts over the balanced cut
      (``v<chunk>:...:<device>`` keys, so V>1 chunks fold round-robin
      onto pp devices exactly like hand-written interleaved contexts);
    * dp: rides the existing executor machinery (worker contexts /
      launcher fleet) — the plan reports it, application is a no-op in
      a single-process session.

    Returns ``{"gpipe"/"pipedream": ..., "pipeline_mode": ...,
    "num_microbatches": ..., "pp_options": ...}`` (empty for pure
    dp/tp plans)."""
    from ..graph.autodiff import find_topo_sort
    from ..context import DeviceGroup
    from ..ndarray import rcpu, rtpu
    import jax

    overrides = {}
    if info is None:
        info = graph_costs(eval_nodes)
    if plan.dp > 1:
        # dp plans bucket their gradient allreduce by default: the
        # CostDB-derived bucket_bytes (4x the measured latency-
        # bandwidth crossover, costdb.recommend_bucket_bytes) keeps
        # `parallel="auto"` off the per-grad latency-regime pattern
        # the HT904 lint prices — a user-supplied overlap_options
        # value still wins in the executor's merge
        from ..telemetry.costdb import recommend_bucket_bytes
        overrides["overlap_options"] = {
            "bucket_bytes": recommend_bucket_bytes(info.get("db"))}
    bindings = plan.bindings
    if plan.tp > 1 and _splice_rules:
        # a plan is often applied to a REBUILT graph (a
        # measure-per-candidate loop, a fresh training process reusing
        # a cached plan): stored bindings reference the scored graph's
        # nodes, so recompile the rules against THIS graph whenever
        # the stored params aren't its nodes — silently splicing
        # nothing would report a tp plan while running unsplit
        here = set(info["topo"])
        if not bindings or not all(b.param in here for b in bindings):
            bindings, _conf = compile_rules(eval_nodes, plan.rules,
                                            plan.tp,
                                            topo=info["topo"])
            plan.bindings = tuple(bindings)
    if bindings and _splice_rules:
        apply_rules(eval_nodes, bindings, shapes=info.get("shapes"))
    if plan.pp <= 1:
        return overrides

    topo = find_topo_sort(list(eval_nodes))
    fwd = info["fwd_order"]
    n_chunks = plan.pp * plan.V
    cuts = plan.stage_cut
    if len(cuts) != n_chunks - 1:
        # the score pass cut pp ways; V>1 application needs pp*V chunks
        cuts = balance_stages(info["op_ms"], fwd, n_chunks)[0]
    try:
        on_cpu = all(d.platform == "cpu" for d in jax.local_devices())
    except RuntimeError:
        on_cpu = True
    mk = rcpu if on_cpu else rtpu

    def ctx_for(chunk):
        v, dev = chunk // plan.pp, chunk % plan.pp
        host = "localhost" if plan.V == 1 else f"v{v}"
        return DeviceGroup(mk(host, dev))

    chunk = 0
    bounds = set(cuts)
    chunk_of = {}
    for i, node in enumerate(fwd):
        if i in bounds and chunk < n_chunks - 1:
            chunk += 1
        node.raw_ctx = ctx_for(chunk)
        chunk_of[node] = chunk
    if plan.schedule == "collective":
        # the collective builder's contract (linear chain, homogeneous
        # per-stage params) raises at trace time; downgrade to the
        # staged runner when the auto cut can't satisfy the cheap half
        # of it (equal per-chunk param-shape lists), rather than ship
        # a plan that dies on first dispatch
        from ..ops.comm import DispatchOp
        from ..ops.variable import PlaceholderOp

        def _param_of(inp):
            # the tp splice above rewired params behind DispatchOps:
            # resolve through them, or every chunk list is vacuously
            # empty and the guard never fires
            while isinstance(inp, DispatchOp) and inp.inputs:
                inp = inp.inputs[0]
            return inp if (isinstance(inp, PlaceholderOp)
                           and inp.trainable) else None

        per_chunk = [[] for _ in range(n_chunks)]
        for node in fwd:
            for inp in getattr(node, "inputs", ()):
                p = _param_of(inp)
                if p is not None:
                    per_chunk[chunk_of[node]].append(
                        tuple(p.shape or ()))
        uniform = all(sorted(c) == sorted(per_chunk[0])
                      for c in per_chunk)
        if not uniform:
            plan.schedule = "gpipe"
            if plan.V > 1:
                # re-place with V folded out (staged gpipe has no
                # virtual stages; contexts must be one per device);
                # the rules were already spliced above, so the
                # recursion only redoes stage placement
                plan.V = 1
                return apply_plan(eval_nodes, plan, info=info,
                                  _splice_rules=False)
    if plan.schedule == "collective":
        overrides["pipeline_mode"] = "collective"
    elif plan.schedule == "1f1b":
        overrides["pipedream"] = True
    else:
        overrides["gpipe"] = True
    overrides["num_microbatches"] = plan.M
    pp_opts = {"virtual_stages": plan.V}
    if plan.schedule == "collective":
        pp_opts["fuse_ticks"] = plan.fuse_ticks
    overrides["pp_options"] = pp_opts
    return overrides
