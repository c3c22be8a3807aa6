"""Pass 7 — CostDB-priced static performance lint (HT9xx).

The verifier stack covers crash (HT3xx/HT6xx), wire/consistency
(HT7xx) and silently-wrong (HT8xx); this pass covers **slow**: the
inefficiency patterns the perf doctor keeps diagnosing *after* a fleet
burned a day — recompile storms, tile-padding waste, hot-path host
syncs, fragmented collectives, redundant reshards, dead compute,
untuned kernels — detected statically over the topo order + parallel
plan and **priced** through the measured CostDB
(``telemetry/costdb.py`` ``estimate_ms``/``estimate_info``/``curve``),
so every finding carries an ``estimated_ms_per_step`` a reviewer can
rank by instead of a vibe.

Codes (severity: ``warn`` when the priced cost clears the ms
threshold, ``info`` below it — an HT9xx finding is never an ``error``
and never blocks a launch; HT908 is always advisory)::

  HT901  recompile hazard: per-step-varying jit signature keys
         (unbucketed dynamic feed shapes reaching the executor's
         dispatch keys — the serving bucketing contract is the clean
         model; runtime half fires from SubExecutor._note_compile)
  HT902  TPU tiling/padding waste: matmul/conv/embedding hot-path
         dims misaligned to the per-dtype (sublane, lane) tile,
         priced as padded-FLOP fraction x op ms / padded HBM bytes
  HT903  host sync on the hot path: per-step device fetches beyond
         sampling cadence (scalar fetch lists; AST detection of
         .item()/device_get inside step loops — composing with
         jit_purity, which owns syncs inside *traced* bodies)
  HT904  fragmented collectives: optimizer-bound per-grad allreduces
         whose sizes sit in the CostDB latency regime while
         overlap_options.bucket_bytes is unset, priced as the
         latency-vs-bandwidth delta against bucketed emission
  HT905  redundant reshard/transfer: gather-then-resplit Dispatch
         chains (and, dynamically via perfcheck, per-step h2d of
         constant feeds), priced from the comm curves
  HT906  cost-weighted dead compute: the HT110 dead-subgraph lint
         with predicted ms attached
  HT908  CostDB coverage gap (advisory): the plan's hot ops priced
         from guesses, not measurements

Every finding carries ``estimated_ms_per_step`` (CostDB-priced),
``estimated_pct`` (share of the predicted step), ``bucket`` (the perf
doctor bucket the claim charges — ``analysis/perfcheck.py`` holds each
priced claim against the *measured* bucket, HT910) and ``source``
(``measured``/``curve``/``cold_start``). ``# ht-ok: HT9xx <reason>``
on the construction line waives a finding (``findings.suppressed_at``).

CLI::

    python -m hetu_tpu.analysis.efficiency [models...] [--json]
        [--out efficiency_report.json] [--costdb PATH]
        [--scripts PATH...]     # HT903 AST lint over host step loops

runs every zoo model, prints findings sorted by predicted savings, and
exits 1 when any unsuppressed warn-or-error finding survives — the CI
``analysis`` job's efficiency gate.
"""
from __future__ import annotations

import ast
import json
import os

import numpy as np

from .findings import Report, suppressed, suppressed_at

__all__ = ["efficiency_pass", "predict", "EfficiencyResult", "op_costs",
           "recompile_pass", "check_host_sync_source", "check_zoo",
           "advise_recompiles", "sorted_by_savings", "DOCTOR_BUCKET",
           "DEFAULT_MS_THRESHOLD", "main"]

# warn-vs-info pricing threshold (ms/step); HETU_EFF_THRESHOLD_MS
# overrides per process
DEFAULT_MS_THRESHOLD = 0.05

# one-time costs (recompiles) amortize over this many
# steps for the per-step price when the caller knows no step count
_AMORTIZE_STEPS = 1000

# the perf-doctor bucket each code's claimed savings would come out of
# (telemetry/doctor.py BUCKETS) — perfcheck's soundness gate joins the
# static claim to the measured bucket through this map
DOCTOR_BUCKET = {"HT901": "jit", "HT902": "compute",
                 "HT903": "unaccounted", "HT904": "collective",
                 "HT905": "h2d_ingest", "HT906": "compute"}

# distinct compiled signatures a session may accumulate before HT901
# calls it churn (train + eval + a couple of block variants)
RECOMPILE_BUDGET = 4

# HT902 floors: below these, padding is real but not worth a finding
_FLOPS_FLOOR = 1e7              # 10 MFLOP/step on the op
_WASTE_FRAC_FLOOR = 0.3         # >=30% of the padded tile is padding
_EMBED_WASTE_FLOOR = 16 << 20   # >=16 MiB of padded table residency
_EMBED_WASTE_FRAC = 0.5
# assumed HBM sustained bandwidth for pricing padded gather traffic
# (GB/s; same conservative class as costdb._COLD_GBPS)
_HBM_GBPS = 100.0

# HT903: scalar fetches in the per-step eval list beyond this are
# host syncs the sampling cadence should own
_SCALAR_FETCH_BUDGET = 4

# NOTE on pricing: unlike autoplan (fwd-only topo, x3 training
# factor), this pass prices the FULL step topo — gradient ops are
# their own nodes and price individually, so no factor applies.


def _db(costdb):
    if costdb is None:
        from ..telemetry.costdb import CostDB
        return CostDB()
    return costdb


def _threshold(ms_threshold):
    if ms_threshold is not None:
        return float(ms_threshold)
    env = os.environ.get("HETU_EFF_THRESHOLD_MS")
    return float(env) if env else DEFAULT_MS_THRESHOLD


def _suppressed_node(node, code):
    # a waiver anchors on the user construction line (defined_at) OR
    # the in-package line that composed the op (composed_at — the
    # models/ctr.py line for zoo-built graphs, whatever script called
    # the builder notwithstanding), and — because the fix for a
    # width/shape finding usually lives on the *parameter* line — on
    # either site of a trainable input too
    for n in (node, *(i for i in getattr(node, "inputs", ())
                      if getattr(i, "trainable", False))):
        for site in (getattr(n, "defined_at", None),
                     getattr(n, "composed_at", None)):
            if site and suppressed_at(site[0], site[1], code):
                return True
    return False


def _prod(shape):
    try:
        return int(np.prod([int(d) for d in shape])) if shape else 0
    except (TypeError, ValueError):
        return 0


def _itemsize(dt):
    try:
        return int(np.dtype(dt).itemsize) if dt is not None else 4
    except TypeError:
        return 4


def _nbytes(shape, dt=None):
    return _prod(shape) * _itemsize(dt)


def tile_for(dt):
    """(sublane, lane) tile for a dtype — the (8, 128)-per-dtype TPU
    layout unit the padding model prices against."""
    try:
        d = np.dtype(dt) if dt is not None else np.dtype(np.float32)
    except TypeError:
        d = np.dtype(np.float32)
    if d.itemsize == 2:
        return (16, 128)
    if d.itemsize == 1:
        return (32, 128)
    return (8, 128)


def _pad(d, m):
    d = max(1, int(d))
    return ((d + m - 1) // m) * m


def _flops(node, shapes):
    """Analytic per-op FLOPs: autoplan's model plus the attention
    family (4*B*H*S^2*D — QK^T and PV)."""
    if "Attention" in node.op_type:
        q = shapes.get(node.inputs[0]) if node.inputs else None
        dims = node.attention_shape(q) if q else None
        if dims:
            b, h, s, d = dims
            return 4.0 * b * h * s * s * d
    from ..parallel.autoplan import flops_of
    return flops_of(node, shapes)


_SKIP_COST_TYPES = ("OptimizerOp", "DataloaderOp", "GNNDataLoaderOp",
                    "DispatchOp", "PipelineSendOp", "PipelineReceiveOp")


def _is_compute(node):
    if node.op_type in _SKIP_COST_TYPES or "Communicate" in node.op_type \
            or "SparsePull" in node.op_type:
        return False
    from ..ops.variable import PlaceholderOp
    return not isinstance(node, PlaceholderOp)


def op_costs(topo, shapes, db):
    """({node: predicted ms}, {node: source}, total_ms) over the
    compute ops — measured CostDB entries preferred, FLOPs scaled
    against the measured anchors otherwise, the documented cold-start
    rate as the last resort (autoplan's calibration, applied to the
    full step graph so gradient ops price too)."""
    from ..telemetry import costdb as _costdb

    op_ms, sources = {}, {}
    measured = {}
    cal_fl = cal_ms = 0.0
    compute = [n for n in topo if _is_compute(n)]
    for node in compute:
        ent = db.get(node.op_type, shapes.get(node))
        if ent is not None:
            measured[node] = float(ent["ms"])
            fl = _flops(node, shapes)
            if fl > 0 and ent["ms"] > 0:
                cal_fl += fl
                cal_ms += float(ent["ms"])
    flops_per_ms = (cal_fl / cal_ms) if cal_ms > 0 else None
    for node in compute:
        if node in measured:
            op_ms[node] = measured[node]
            sources[node] = "measured"
            continue
        fl = _flops(node, shapes)
        if flops_per_ms:
            op_ms[node] = fl / flops_per_ms
            sources[node] = "flops_scaled"
        else:
            op_ms[node] = _costdb.cold_start_flops_ms(fl)
            sources[node] = "cold_start"
    return op_ms, sources, sum(op_ms.values())


class EfficiencyResult:
    """One graph's priced lint: the findings ``Report``, the per-node
    predicted ms map (graphboard's ``waste=`` overlay input), cost
    sources, and the predicted compute floor of a step."""

    __slots__ = ("report", "op_ms", "sources", "total_ms", "topo")

    def __init__(self, report, op_ms, sources, total_ms, topo):
        self.report = report
        self.op_ms = op_ms
        self.sources = sources
        self.total_ms = total_ms
        self.topo = topo

    @property
    def findings(self):
        return sorted_by_savings(self.report)

    def predicted_waste_ms(self):
        """Total priced ms/step across the findings — what the graph
        throws away per step if every finding is real. HT908 is
        excluded: its price is the ms *resting on guesses* (pricing
        uncertainty), not waste, and counting it would double-bill ops
        that also carry a real HT902/HT906 price."""
        return round(sum(f.data.get("estimated_ms_per_step", 0.0)
                         for f in self.report.findings
                         if f.code != "HT908"), 6)

    def to_dict(self):
        return {
            "total_predicted_ms": round(self.total_ms, 6),
            "predicted_waste_ms": self.predicted_waste_ms(),
            "findings": [f.to_dict() for f in self.findings],
        }


def sorted_by_savings(report):
    """Findings sorted by predicted savings, biggest first — the
    reading order of a priced report."""
    return sorted(report.findings,
                  key=lambda f: -float(
                      f.data.get("estimated_ms_per_step", 0.0)))


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def efficiency_pass(topo, report, shapes=None, dtypes=None, config=None,
                    costdb=None, eval_nodes=None, extra_roots=(),
                    shape_keys=None, steps=None, ms_threshold=None,
                    feed_shapes=None, op_ms_out=None, sources_out=None):
    """Run every HT90x check over a topo-sorted graph; returns the
    per-node predicted-ms map. ``shape_keys`` (observed dispatch
    signatures) enables HT901; ``extra_roots`` enables HT906;
    ``config`` (a HetuConfig) supplies the plan knobs HT904 reads.
    Findings land in ``report`` with ``estimated_ms_per_step`` /
    ``estimated_pct`` / ``bucket`` / ``source`` attached;
    ``op_ms_out``/``sources_out`` (dicts) receive the per-node pricing
    so callers never pay the cost sweep twice."""
    if shapes is None or dtypes is None:
        from .shapes import shape_pass
        dtypes = {} if dtypes is None else dtypes
        shapes = shape_pass(topo, Report(), feed_shapes=feed_shapes,
                            dtypes_out=dtypes)
    db = _db(costdb)
    threshold = _threshold(ms_threshold)
    op_ms, sources, total_ms = op_costs(topo, shapes, db)
    if op_ms_out is not None:
        op_ms_out.update({n: round(v, 6) for n, v in op_ms.items()})
    if sources_out is not None:
        sources_out.update(sources)

    def add(code, message, node, ms, source, extra_sev=None, **data):
        if node is not None and _suppressed_node(node, code):
            return None
        sev = extra_sev or ("warn" if ms >= threshold else "info")
        pct = round(ms / total_ms, 4) if total_ms > 0 else None
        return report.add(
            code, sev, message, node=node,
            estimated_ms_per_step=round(float(ms), 6),
            estimated_pct=pct, bucket=DOCTOR_BUCKET.get(code),
            source=source, **data)

    if shape_keys is not None:
        recompile_pass(shape_keys, report, costdb=db, steps=steps,
                       ms_threshold=threshold)
    _tiling_pass(topo, shapes, dtypes, op_ms, db, add)
    if eval_nodes is not None:
        _fetch_pass(topo, eval_nodes, shapes, db, add)
        _collective_pass(topo, eval_nodes, shapes, dtypes, config, db,
                         add)
        if extra_roots:
            _dead_compute_pass(topo, eval_nodes, extra_roots, db, add)
    _reshard_pass(topo, shapes, dtypes, db, add)
    _coverage_pass(topo, shapes, op_ms, sources, db, add, threshold)
    return op_ms


def predict(eval_nodes, feed_shapes=None, config=None, costdb=None,
            extra_roots=(), shape_keys=None, steps=None,
            ms_threshold=None):
    """Priced lint over a graph in one call: shape-propagate, run
    :func:`efficiency_pass`, return an :class:`EfficiencyResult` —
    the CLI's and graphboard's entry point."""
    from .shapes import shape_pass
    from ..graph.autodiff import find_topo_sort

    topo = find_topo_sort(list(eval_nodes))
    dtypes = {}
    shapes = shape_pass(topo, Report(), feed_shapes=feed_shapes,
                        dtypes_out=dtypes)
    report = Report()
    sources = {}
    op_ms = efficiency_pass(
        topo, report, shapes=shapes, dtypes=dtypes, config=config,
        costdb=costdb, eval_nodes=eval_nodes, extra_roots=extra_roots,
        shape_keys=shape_keys, steps=steps, ms_threshold=ms_threshold,
        sources_out=sources)
    named = {n.name: round(v, 6) for n, v in op_ms.items()}
    return EfficiencyResult(report, named,
                            {n.name: s for n, s in sources.items()},
                            sum(op_ms.values()), topo)


# ---------------------------------------------------------------------------
# HT901 — recompile hazard
# ---------------------------------------------------------------------------

def _leaf_ints(key, out):
    if isinstance(key, (tuple, list)):
        for k in key:
            _leaf_ints(k, out)
    elif isinstance(key, (int, np.integer)):
        out.append(int(key))


def _bucketed(keys):
    """True when every dim that varies across the observed signatures
    only takes power-of-two values — the serving bucketing contract
    (serving/session.py): pow2 buckets bound distinct signatures by
    log2(range), which is the clean model for dynamic shapes."""
    flat = []
    for k in keys:
        ints = []
        _leaf_ints(k, ints)
        flat.append(tuple(ints))
    if len({len(f) for f in flat}) != 1:
        return False            # structurally different keys: not a
        # bucket ladder at all (e.g. feeds appearing and vanishing)
    for pos in range(len(flat[0])):
        vals = {f[pos] for f in flat}
        if len(vals) <= 1:
            continue
        if not all(v > 0 and (v & (v - 1)) == 0 for v in vals):
            return False
    return True


def recompile_pass(shape_keys, report, costdb=None, steps=None,
                   node=None, budget=RECOMPILE_BUDGET,
                   ms_threshold=None):
    """HT901 over a set of observed jit dispatch signatures (the
    executor's ``SubExecutor.compiled`` keys, or any recorded shape
    history): more than ``budget`` distinct signatures whose varying
    dims do *not* follow the pow2 bucketing contract is a recompile
    storm — every new signature pays a full XLA compile. Priced from
    the CostDB's measured ``jit_compile`` entries (cold-start: the
    documented 200 ms floor), amortized over ``steps``."""
    keys = list(dict.fromkeys(tuple(k) if isinstance(k, list) else k
                              for k in shape_keys))
    n = len(keys)
    if n <= budget or _bucketed(keys):
        return None
    if node is not None and _suppressed_node(node, "HT901"):
        return None
    db = _db(costdb)
    threshold = _threshold(ms_threshold)
    compile_ms, source = db.estimate_info("jit_compile", 0)
    excess = n - budget
    total = excess * compile_ms
    horizon = max(1, int(steps)) if steps else _AMORTIZE_STEPS
    ms = total / horizon
    sev = "warn" if ms >= threshold else "info"
    return report.add(
        "HT901", sev,
        f"recompile hazard: {n} distinct jit signatures observed "
        f"(budget {budget}) and the varying dims are not pow2-bucketed "
        f"— every new feed shape pays a full XLA compile "
        f"(~{compile_ms:.0f} ms each, {source}). Bucket dynamic dims "
        f"like serving does (pad up to pow2, trim outputs) or pin the "
        f"feed shapes", node=node,
        estimated_ms_per_step=round(ms, 6),
        estimated_ms_total=round(total, 3),
        bucket=DOCTOR_BUCKET["HT901"], source=source,
        signatures=n)


def advise_recompiles(sub):
    """Runtime half, called once from ``SubExecutor._note_compile``
    when a session crosses the compiled-signature threshold: run
    :func:`recompile_pass` over the real dispatch keys, log the
    finding, and append it to the session's analysis report when
    ``Executor(validate=...)`` keeps one."""
    import logging
    report = Report()
    f = recompile_pass(sub.compiled.keys(), report,
                       steps=max(1, sub.step_count))
    if f is None:
        return None
    logging.getLogger(__name__).warning("%s", f)
    session_report = getattr(sub.config, "analysis_report", None)
    if session_report is not None:
        session_report.findings.append(f)
    tel = getattr(sub.config, "telemetry", None)
    if tel is not None and tel.enabled:
        tel.inc("recompile_hazard_advisories")
    return f


# ---------------------------------------------------------------------------
# HT902 — tiling/padding waste
# ---------------------------------------------------------------------------

def _lane_waste(k, n, dt, count_k=True):
    """Padded-issue fraction over the ARCHITECTURAL matmul dims: the
    contraction (K) and output-feature (N) lane dims. The sublane/M
    dim scales with batch — a bench harness artifact, not a model
    property — so it never fires the lint on its own; ``count_k=False``
    additionally excludes K for weight-gradient matmuls, whose
    contraction rides the batch dim too."""
    _, lane = tile_for(dt)
    true, padded = n, _pad(n, lane)
    if count_k:
        true *= k
        padded *= _pad(k, lane)
    return 1.0 - true / padded if padded else 0.0


def _matmul_mkn(node, ins, out):
    """Effective (M, K, N) honoring the transpose flags (a gradient
    matmul is trans_A/trans_B; reading raw operand dims would price
    the wrong contraction)."""
    m, n = int(out[-2]), int(out[-1])
    k = int(ins[0][-2] if getattr(node, "matmul_attr_trans_A", False)
            else ins[0][-1])
    return m, k, n


def _tiling_pass(topo, shapes, dtypes, op_ms, db, add):
    for node in topo:
        kind = node.op_type
        out = shapes.get(node)
        ins = [shapes.get(i) for i in node.inputs]
        dt = dtypes.get(node)
        if kind in ("MatMulOp", "BatchMatMulOp") and len(ins) >= 2 \
                and ins[0] and ins[1] and out and len(out) >= 2:
            m, k, n = _matmul_mkn(node, ins, out)
            fl = 2.0 * m * k * n
            # trans_A = a weight-gradient matmul: K is the batch dim
            waste = _lane_waste(
                k, n, dt,
                count_k=not getattr(node, "matmul_attr_trans_A", False))
            if fl >= _FLOPS_FLOOR and waste >= _WASTE_FRAC_FLOOR:
                ms = op_ms.get(node, 0.0) * waste
                sub, lane = tile_for(dt)
                add("HT902",
                    f"{kind} {node.name}: dims [{m}x{k}]x[{k}x{n}] pad "
                    f"to the ({sub},{lane}) tile with {waste:.0%} of "
                    f"the MXU issue wasted on padding — align the "
                    f"lane dims (K={k}, N={n}) to {lane} or waive "
                    f"with a measured justification", node, ms,
                    "measured" if db.get(kind, out) else "cold_start",
                    waste_frac=round(waste, 4))
        elif kind == "Conv2dOp" and len(ins) >= 2 and ins[1] \
                and len(ins[1]) == 4 and out:
            cout, cin, kh, kw = (int(x) for x in ins[1])
            m = _prod(out) // max(1, cout)      # N*H*W rows of im2col
            k = cin * kh * kw
            fl = _flops(node, shapes)
            waste = _lane_waste(k, cout, dt)
            if fl >= _FLOPS_FLOOR and waste >= _WASTE_FRAC_FLOOR:
                ms = op_ms.get(node, 0.0) * waste
                sub, lane = tile_for(dt)
                add("HT902",
                    f"Conv2d {node.name}: im2col [{m}x{k}]x[{k}x{cout}] "
                    f"pads to the ({sub},{lane}) tile with {waste:.0%} "
                    f"padding waste (cout={cout}, cin*kh*kw={k}) — "
                    f"align channel counts to {lane} lanes or waive "
                    f"with a measured justification", node, ms,
                    "measured" if db.get(kind, out) else "cold_start",
                    waste_frac=round(waste, 4))
        elif kind == "EmbeddingLookUp" and ins and ins[0] \
                and len(ins[0]) == 2:
            rows, width = int(ins[0][0]), int(ins[0][1])
            tdt = dtypes.get(node.inputs[0])
            isz = _itemsize(tdt)
            _, lane = tile_for(tdt)
            padw = _pad(width, lane)
            delta = rows * (padw - width) * isz
            frac = 1.0 - width / padw
            if delta >= _EMBED_WASTE_FLOOR and frac >= _EMBED_WASTE_FRAC:
                nlook = _prod(ins[1]) if len(ins) > 1 and ins[1] else 1
                waste_bytes = nlook * (padw - width) * isz
                ms = waste_bytes / (_HBM_GBPS * 1e6)
                add("HT902",
                    f"EmbeddingLookUp {node.name}: table rows are "
                    f"{width} wide but store {padw}-lane tiles — "
                    f"{frac:.0%} of {delta / (1 << 20):.0f} MiB of HBM "
                    f"residency (and every gathered row's traffic) is "
                    f"padding. Widen to a multiple of {lane}, pack "
                    f"rows, or waive with a measured justification",
                    node, ms, "cold_start",
                    waste_frac=round(frac, 4), padded_mib=round(
                        delta / (1 << 20), 1))


# ---------------------------------------------------------------------------
# HT903 — host sync on the hot path
# ---------------------------------------------------------------------------

def _fetch_pass(topo, eval_nodes, shapes, db, add):
    """Graph half: a per-step fetch list carrying many scalar outputs
    is a per-step host sync per scalar — the sentinel/health pattern
    (one fused aux pytree, fetched at cadence) is the clean model."""
    from ..optimizer import OptimizerOp

    scalars = [n for n in eval_nodes
               if not isinstance(n, OptimizerOp)
               and shapes.get(n) is not None
               and _prod(shapes.get(n)) <= 1]
    extra = len(scalars) - _SCALAR_FETCH_BUDGET
    if extra <= 0:
        return
    per, source = db.estimate_info("d2h", 8)
    ms = extra * per
    add("HT903",
        f"{len(scalars)} scalar outputs in the per-step fetch list — "
        f"each is a device round-trip every step (budget "
        f"{_SCALAR_FETCH_BUDGET}). Fuse them into one aux fetch (the "
        f"health-sentinel pattern) or sample at cadence",
        scalars[_SCALAR_FETCH_BUDGET], ms, source,
        scalar_fetches=len(scalars))


class _LoopWalker(ast.NodeVisitor):
    """Find host step loops (For/While whose body calls .run/.predict/
    run_step) and the device syncs inside them. ``.item()`` /
    ``.block_until_ready()`` / ``device_get`` always sync;
    ``np.asarray``/``np.array`` only count when applied to (a subscript
    of) a name assigned from the run call — host-side feed construction
    with the same spelling is not a device round-trip."""

    _RUN_NAMES = {"run", "run_step", "run_batches",
                  "run_batches_stream", "predict"}
    _SYNC_ATTRS = {"item", "block_until_ready"}
    _SYNC_ALWAYS = {"device_get"}
    _SYNC_ON_RESULT = {"asarray", "array"}

    def __init__(self):
        self.loops = []         # (loop node, [sync nodes])

    @staticmethod
    def _is_run_call(node):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _LoopWalker._RUN_NAMES)

    def _visit_loop(self, node):
        runs = False
        results = set()         # names bound to a run call's result
        for sub in ast.walk(node):
            if self._is_run_call(sub):
                runs = True
            elif isinstance(sub, ast.Assign) and \
                    self._is_run_call(sub.value):
                results.update(t.id for t in sub.targets
                               if isinstance(t, ast.Name))

        def on_result(arg):
            while isinstance(arg, ast.Subscript):
                arg = arg.value
            return isinstance(arg, ast.Name) and arg.id in results

        syncs = []
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if name in self._SYNC_ATTRS or name in self._SYNC_ALWAYS:
                syncs.append(sub)
            elif name in self._SYNC_ON_RESULT and sub.args \
                    and on_result(sub.args[0]):
                syncs.append(sub)
        if runs and syncs:
            self.loops.append((node, syncs))
        self.generic_visit(node)

    visit_For = _visit_loop
    visit_While = _visit_loop


def _cadence_guarded(tree, sync):
    """True when ``sync`` sits under an ``if ... % n`` guard — sampled
    at cadence, the clean pattern."""
    guarded = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.If) and any(
                isinstance(b, ast.BinOp) and isinstance(b.op, ast.Mod)
                for b in ast.walk(node.test)):
            for sub in ast.walk(node):
                guarded.add(id(sub))
    return id(sync) in guarded


def check_host_sync_source(src, path="<string>", costdb=None,
                           ms_threshold=None):
    """HT903 AST half over a host training script: ``.item()`` /
    ``device_get`` / ``np.asarray`` / ``block_until_ready`` inside a
    step loop (a For/While that drives ``run()``/``predict()``),
    unless cadence-guarded (``if step % n``). Composes with
    ``jit_purity`` — that lint owns syncs inside *traced* bodies, this
    one owns the host loop around them. Returns a Report."""
    report = Report()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        report.add("HT900", "warn", f"unparseable script: {e}",
                   where=path)
        return report
    db = _db(costdb)
    threshold = _threshold(ms_threshold)
    per, source = db.estimate_info("d2h", 8)
    lines = src.splitlines()
    walker = _LoopWalker()
    walker.visit(tree)
    for loop, syncs in walker.loops:
        for sync in syncs:
            if _cadence_guarded(loop, sync):
                continue
            if suppressed(lines, sync.lineno, "HT903"):
                continue
            fn = sync.func
            name = fn.attr if isinstance(fn, ast.Attribute) else fn.id
            sev = "warn" if per >= threshold else "info"
            report.add(
                "HT903", sev,
                f"{name}() inside the step loop at line {loop.lineno} "
                f"forces a device sync every step (~{per:.3f} ms, "
                f"{source}) — guard it with a cadence (if step % n) "
                f"or fuse the value into the step's aux fetch",
                where=f"{path}:{sync.lineno}",
                estimated_ms_per_step=round(per, 6),
                bucket=DOCTOR_BUCKET["HT903"], source=source)
    return report


# ---------------------------------------------------------------------------
# HT904 — fragmented collectives
# ---------------------------------------------------------------------------

def _collective_pass(topo, eval_nodes, shapes, dtypes, config, db, add):
    from ..optimizer import OptimizerOp
    from ..ops.comm import optimizer_allreduce_ops
    from ..telemetry.costdb import (latency_crossover_bytes,
                                    recommend_bucket_bytes)

    overlap = getattr(config, "overlap", None) if config is not None \
        else None
    if overlap is not None and overlap.bucket_bytes:
        return                  # bucketing on: the pattern is handled
    optimizer_ops = [n for n in topo if isinstance(n, OptimizerOp)]
    if not optimizer_ops:
        return
    ars = optimizer_allreduce_ops(topo, optimizer_ops, eval_nodes)
    if len(ars) < 2:
        return
    sizes = []
    for op in sorted(ars, key=lambda n: n.id):
        shape = shapes.get(op) or shapes.get(op.inputs[0])
        sizes.append((op, _nbytes(shape, dtypes.get(op))))
    crossover = latency_crossover_bytes(db, "allreduce")
    frag = [(op, s) for op, s in sizes if 0 < s < crossover]
    if len(frag) < 2:
        return
    per_grad = 0.0
    source = "cold_start"
    for _, s in sizes:
        ms, src = db.estimate_info("allreduce", s)
        per_grad += ms
        if src in ("measured", "curve"):
            source = src
    bucket_bytes = recommend_bucket_bytes(db)
    # greedy size-targeted packing, the settle_deferred_allreduce shape
    buckets, cur = [], 0
    for _, s in sizes:
        if cur and cur + s > bucket_bytes:
            buckets.append(cur)
            cur = 0
        cur += s
    if cur:
        buckets.append(cur)
    bucketed = sum(db.estimate_info("allreduce", b)[0] for b in buckets)
    delta = per_grad - bucketed
    if delta <= 0:
        return
    add("HT904",
        f"{len(sizes)} per-gradient allreduces ({len(frag)} below the "
        f"{crossover / 1e6:.2f} MB latency crossover) with "
        f"overlap_options.bucket_bytes unset — {len(sizes)} latency "
        f"payments per step where {len(buckets)} would do. Set "
        f"bucket_bytes={bucket_bytes} (CostDB-derived; "
        f"autoplan applies it to dp plans automatically)",
        frag[0][0], delta, source,
        collectives=len(sizes), buckets=len(buckets),
        recommended_bucket_bytes=bucket_bytes)


# ---------------------------------------------------------------------------
# HT905 — redundant reshard / transfer
# ---------------------------------------------------------------------------

def _reshard_pass(topo, shapes, dtypes, db, add):
    from ..ops.comm import DispatchOp

    def is_gather(n):
        return isinstance(n, DispatchOp) and all(
            p <= 1 for p in n.parts)

    def is_split(n):
        return isinstance(n, DispatchOp) and any(
            p > 1 for p in n.parts)

    consumers = {}
    for op in topo:
        for inp in op.inputs:
            consumers.setdefault(id(inp), []).append(op)

    for node in topo:
        if not (is_split(node) and node.inputs):
            continue
        g = node.inputs[0]
        if not (is_gather(g) and g.inputs):
            continue
        s = g.inputs[0]
        if not (is_split(s) and s.parts == node.parts):
            continue
        if len(consumers.get(id(g), ())) > 1:
            continue            # the gathered value is used elsewhere
        shape = shapes.get(s) or shapes.get(g)
        nb = _nbytes(shape, dtypes.get(s))
        # gather-then-identical-resplit: the bytes ride the links twice
        # for a no-op — price both hops off the collective curve
        ms, source = db.estimate_info("allreduce", nb)
        add("HT905",
            f"gather-then-resplit Dispatch chain {s.name} -> {g.name} "
            f"-> {node.name} re-creates the same {tuple(node.parts)} "
            f"partition it gathered — "
            f"{nb / 1e6:.2f} MB resharded round-trip per step for a "
            f"no-op; drop the pair and keep the split output",
            node, 2 * ms, source, bytes=nb)


# ---------------------------------------------------------------------------
# HT906 — cost-weighted dead compute
# ---------------------------------------------------------------------------

def _dead_compute_pass(topo, eval_nodes, extra_roots, db, add):
    from ..graph.autodiff import find_topo_sort
    from .shapes import shape_pass

    live = {id(n) for n in topo}
    dead_topo = [n for n in find_topo_sort(list(extra_roots))
                 if id(n) not in live]
    dead = [n for n in dead_topo if _is_compute(n)]
    if not dead:
        return
    dshapes = shape_pass(dead_topo, Report())
    dms, _src, _tot = op_costs(dead_topo, dshapes, db)
    ms = sum(dms.get(n, 0.0) for n in dead)
    names = ", ".join(n.name for n in dead[:5])
    add("HT906",
        f"{len(dead)} dead compute op(s) reachable from constructed "
        f"roots but not from the eval outputs ({names}"
        f"{'...' if len(dead) > 5 else ''}) — if a step function "
        f"evaluates them they burn ~{ms:.4f} ms/step for nothing; "
        f"delete the subgraph or fetch its outputs",
        dead[0], ms, "cold_start", dead_ops=len(dead))


# ---------------------------------------------------------------------------
# HT908 — CostDB coverage gap (advisory)
# ---------------------------------------------------------------------------

_COVERAGE_TOP = 5


def _coverage_pass(topo, shapes, op_ms, sources, db, add, threshold):
    if db is None or len(db) == 0:
        # a fully cold DB guesses everything; the doctor's global
        # "run costdb --sweep" hint owns that case — an advisory per
        # graph would be noise
        return
    guessed = [(n, m) for n, m in op_ms.items()
               if sources.get(n) != "measured" and m >= threshold]
    if not guessed:
        return
    guessed.sort(key=lambda nm: -nm[1])
    top = guessed[:_COVERAGE_TOP]
    at_stake = sum(m for _, m in guessed)
    keys = ", ".join(f"({n.op_type}, "
                     f"{'x'.join(str(d) for d in (shapes.get(n) or ()))})"
                     for n, _ in top)
    add("HT908",
        f"{len(guessed)} hot op(s) priced from guesses, not "
        f"measurements ({keys}"
        f"{'...' if len(guessed) > _COVERAGE_TOP else ''}) — "
        f"~{at_stake:.3f} ms/step of this report rests on the "
        f"cold-start model. profile_ops(costdb=...) one real run to "
        f"replace them", top[0][0], at_stake, "cold_start",
        extra_sev="info", guessed_ops=len(guessed))


# ---------------------------------------------------------------------------
# CLI: zoo sweep gating on unsuppressed warn/error findings
# ---------------------------------------------------------------------------

def check_zoo(names=None, costdb=None, ms_threshold=None):
    """{model: EfficiencyResult} over the zoo graphs."""
    from . import zoo

    out = {}
    for name in names or sorted(zoo.ZOO):
        eval_nodes, feed_shapes = zoo.build(name)
        out[name] = predict(eval_nodes, feed_shapes=feed_shapes,
                            costdb=costdb, ms_threshold=ms_threshold)
    return out


def main(argv=None):
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m hetu_tpu.analysis.efficiency",
        description="CostDB-priced static performance lint (HT9xx) "
                    "over the zoo graphs; exits 1 on any unsuppressed "
                    "warn-or-error finding")
    parser.add_argument("models", nargs="*",
                        help="zoo model names (default: all)")
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--out", default=None, metavar="FILE",
                        help="write the priced report JSON here (the "
                             "CI artifact)")
    parser.add_argument("--costdb", default=None, metavar="PATH",
                        help="cost DB (default: $HETU_COSTDB or the "
                             "standard path; cold-start pricing when "
                             "absent)")
    parser.add_argument("--threshold-ms", type=float, default=None,
                        help=f"warn-vs-info pricing threshold "
                             f"(default {DEFAULT_MS_THRESHOLD} or "
                             f"$HETU_EFF_THRESHOLD_MS)")
    parser.add_argument("--scripts", nargs="*", default=(),
                        metavar="PATH",
                        help="also run the HT903 host-sync AST lint "
                             "over these training scripts")
    args = parser.parse_args(argv)

    from . import zoo
    names = args.models or sorted(zoo.ZOO)
    unknown = [n for n in names if n not in zoo.ZOO]
    if unknown:
        parser.error(f"unknown zoo model(s) {unknown}")

    db = None
    if args.costdb:
        from ..telemetry.costdb import CostDB
        db = CostDB(args.costdb)
    results = check_zoo(names, costdb=db,
                        ms_threshold=args.threshold_ms)
    script_reports = {}
    for path in args.scripts:
        with open(path, encoding="utf-8") as f:
            script_reports[path] = check_host_sync_source(
                f.read(), path=path, costdb=db,
                ms_threshold=args.threshold_ms)

    gate = 0
    doc = {}
    for name, res in results.items():
        gating = [f for f in res.report.findings
                  if f.severity in ("warn", "error")]
        doc[name] = res.to_dict()
        if gating:
            gate = 1
        if not args.json:
            status = "FAIL" if gating else "ok"
            print(f"== {name}: {status} ({len(res.report)} finding(s), "
                  f"predicted waste {res.predicted_waste_ms():.4f} "
                  f"ms/step of {res.total_ms:.4f})")
            for f in res.findings:
                print(f"   {f}  "
                      f"[{f.data.get('estimated_ms_per_step', 0):.4f} "
                      f"ms/step]")
    for path, rep in script_reports.items():
        gating = [f for f in rep.findings
                  if f.severity in ("warn", "error")]
        doc[path] = {"findings": [f.to_dict()
                                  for f in sorted_by_savings(rep)]}
        if gating:
            gate = 1
        if not args.json:
            print(f"== {path}: "
                  f"{'FAIL' if gating else 'ok'} "
                  f"({len(rep)} finding(s))")
            for f in sorted_by_savings(rep):
                print("   " + str(f))
    if args.json:
        print(json.dumps(doc, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"priced report written to {args.out}", file=sys.stderr)
    total = sum(len(r.report) for r in results.values()) + \
        sum(len(r) for r in script_reports.values())
    if not args.json:
        print(f"efficiency: {total} finding(s) across {len(names)} "
              f"zoo model(s)"
              + (f" + {len(script_reports)} script(s)"
                 if script_reports else ""))
    if gate:
        print("efficiency: FAILED — fix the inefficiency, or waive "
              "with '# ht-ok: HT9xx <reason>' on the construction "
              "line", file=sys.stderr)
    return gate


if __name__ == "__main__":
    import sys
    sys.exit(main())
