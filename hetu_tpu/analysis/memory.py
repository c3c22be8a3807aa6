"""Pass 4 — memory preflight against an HBM budget (HT4xx).

Two tiers, sharing `telemetry/memory.py`'s accounting vocabulary:

* **Static estimate** (:func:`memory_pass`): from the shape pass's
  results alone — parameter bytes, gradient mirror, optimizer slots
  (per-optimizer-class multiplier), and a conservative forward
  activation sum — checked against the budget *before anything
  compiles*. Deliberately pessimistic about activations (no XLA fusion
  or rematerialization credit): a plan that fails HT401 statically is
  certain to OOM; one that passes may still need the compiled check.
* **Compiled check** (:func:`check_compiled`): when the executor's AOT
  path has real ``compiled.memory_analysis()`` numbers (the dict
  ``telemetry/memory.capture_compile`` builds), compare
  arg+out+temp bytes against the budget — exact, but only available
  once a step traced.

Budget resolution order: explicit argument > ``HETU_HBM_BUDGET`` env
(accepts ``8G`` / ``512MiB`` / plain bytes) > the device's advertised
``bytes_limit`` (TPU backends report it; CPU doesn't).

HT401  estimated footprint exceeds the HBM budget            error
HT402  footprint breakdown (always, when shapes are known)   info
HT403  estimate within 10% of the budget                     warn
HT404  compiled memory_analysis exceeds the budget           warn
"""
from __future__ import annotations

import os
import re

from ..telemetry.memory import fmt_bytes

__all__ = ["memory_pass", "check_compiled", "parse_bytes",
           "resolve_budget"]

_SLOTS_PER_PARAM = {
    "SGDOptimizer": 0,
    "MomentumOptimizer": 1,
    "NesterovOptimizer": 1,
    "AdaGradOptimizer": 1,
    "AdamOptimizer": 2,
    "AdamWOptimizer": 2,
}

_UNITS = {"": 1, "k": 2 ** 10, "m": 2 ** 20, "g": 2 ** 30, "t": 2 ** 40}


def parse_bytes(value):
    """'8G' / '512MiB' / '1073741824' -> bytes (int)."""
    if value is None:
        return None
    if isinstance(value, (int, float)):
        return int(value)
    m = re.fullmatch(r"\s*([0-9]*\.?[0-9]+)\s*([kKmMgGtT]?)i?[bB]?\s*",
                     str(value))
    if not m:
        raise ValueError(f"unparseable byte size {value!r}")
    return int(float(m.group(1)) * _UNITS[m.group(2).lower()])


def resolve_budget(budget=None):
    """Explicit budget > HETU_HBM_BUDGET > device bytes_limit. ``None``
    only on the CPU backend, which reports no device memory; an
    accelerator whose ``memory_stats()`` lacks ``bytes_limit`` raises —
    sizing a pool against a guess is worse than not starting."""
    if budget is not None:
        return parse_bytes(budget)
    env = os.environ.get("HETU_HBM_BUDGET")
    if env:
        return parse_bytes(env)
    import jax
    limits = []
    for d in jax.local_devices():
        if d.platform == "cpu":
            return None
        stats = d.memory_stats()
        limit = int(stats.get("bytes_limit", 0)) if stats else 0
        if limit <= 0:
            raise RuntimeError(
                f"{d}: memory_stats() reports no bytes_limit "
                f"({stats!r}); pass an explicit budget or set "
                f"HETU_HBM_BUDGET")
        limits.append(limit)
    return min(limits)


def _nbytes(shape, itemsize=4):
    if shape is None:
        return None
    n = itemsize
    for s in shape:
        n *= int(s)
    return n


def memory_pass(topo, shapes, report, budget=None):
    """Static footprint estimate vs budget; returns the breakdown dict."""
    from ..optimizer import OptimizerOp
    from ..ops.variable import PlaceholderOp

    param_bytes = 0
    for n in topo:
        if isinstance(n, PlaceholderOp) and n.trainable:
            b = _nbytes(shapes.get(n))
            if b:
                param_bytes += b

    opt_ops = [n for n in topo if isinstance(n, OptimizerOp)]
    slot_mult = 0
    for op in opt_ops:
        cls = type(op.optimizer).__name__
        slot_mult = max(slot_mult, _SLOTS_PER_PARAM.get(cls, 1))
    training = bool(opt_ops)
    grad_bytes = param_bytes if training else 0
    slot_bytes = param_bytes * slot_mult

    act_bytes = 0
    unknown_acts = 0
    for n in topo:
        if isinstance(n, (PlaceholderOp, OptimizerOp)):
            continue
        b = _nbytes(shapes.get(n))
        if b is None:
            unknown_acts += 1
        else:
            act_bytes += b

    total = param_bytes + grad_bytes + slot_bytes + act_bytes
    breakdown = {"param_bytes": param_bytes, "grad_bytes": grad_bytes,
                 "opt_slot_bytes": slot_bytes,
                 "activation_bytes": act_bytes, "total_bytes": total}
    if total:
        caveat = (f" ({unknown_acts} node(s) unshaped and uncounted)"
                  if unknown_acts else "")
        report.add(
            "HT402", "info",
            f"static footprint estimate: params {fmt_bytes(param_bytes)}"
            f" + grads {fmt_bytes(grad_bytes)} + optimizer slots "
            f"{fmt_bytes(slot_bytes)} + activations "
            f"{fmt_bytes(act_bytes)} = {fmt_bytes(total)}{caveat}",
            **breakdown)

    budget = resolve_budget(budget)
    if budget is None or not total:
        return breakdown
    if total > budget:
        report.add(
            "HT401", "error",
            f"estimated device footprint {fmt_bytes(total)} exceeds "
            f"the HBM budget {fmt_bytes(budget)} — the plan OOMs "
            f"before the first step completes; shard parameters "
            f"(dispatch/PS), shrink the batch, or raise the budget",
            budget_bytes=budget, **breakdown)
    elif total > 0.9 * budget:
        report.add(
            "HT403", "warn",
            f"estimated footprint {fmt_bytes(total)} is within 10% of "
            f"the HBM budget {fmt_bytes(budget)} — fragmentation or "
            f"temp buffers can tip this over",
            budget_bytes=budget, **breakdown)
    return breakdown


def check_compiled(mem, budget=None):
    """Compare a ``capture_compile`` dict (arg/out/temp bytes from
    ``compiled.memory_analysis()``) against the budget. Returns a list
    of :class:`~.findings.Finding` (empty when within budget or no
    budget resolves)."""
    from .findings import Finding
    budget = resolve_budget(budget)
    if not mem or budget is None:
        return []
    used = (mem.get("arg_bytes", 0) + mem.get("out_bytes", 0)
            + mem.get("temp_bytes", 0) - mem.get("alias_bytes", 0))
    if used <= budget:
        return []
    return [Finding(
        "HT404", "warn",
        f"compiled program needs {fmt_bytes(used)} "
        f"(args {fmt_bytes(mem.get('arg_bytes', 0))} + outputs "
        f"{fmt_bytes(mem.get('out_bytes', 0))} + temps "
        f"{fmt_bytes(mem.get('temp_bytes', 0))}, aliasing credited) "
        f"but the HBM budget is {fmt_bytes(budget)}",
        budget_bytes=budget, **mem)]
