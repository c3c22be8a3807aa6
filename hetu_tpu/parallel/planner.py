"""Tensor-parallel planner: NodeStatus propagation → PartitionSpec.

Reference parity: ``assign_context_by_traverse_nodes`` (context.py:256-726)
— there, a NodeStatus per node is realized by rewriting the graph with
split/concat/add ops and NCCL p2p send/recv (cross_send/cross_receive). On
TPU the planner only *annotates*: statuses propagate through the ops'
``deduce_states`` (same tables, e.g. the matmul row/col/k mapping,
MatrixMult.py:88-141), then lower to ``PartitionSpec`` constraints over a
named mesh; XLA's SPMD partitioner materializes every repartition as ICI
collectives. Sharding constraints never change numerics — a status the
planner cannot map is simply left unconstrained (XLA picks a layout), so
parallel runs stay loss-equivalent with single-device runs by
construction, which the reference has to *test* for
(examples/runner/parallel/validate_results.py).
"""
from __future__ import annotations

import logging

import numpy as np

from ..context import NodeStatus
from .mesh import mesh_for_statuses

__all__ = ["assign_states", "spec_for_status"]

logger = logging.getLogger(__name__)


def _prime_factors(n):
    out = []
    d = 2
    while n > 1:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1 if d == 2 else 2
    return out


def spec_for_status(status, model_axes, node=None):
    """Lower a NodeStatus to a PartitionSpec over prime-factored model
    axes; returns None when the status is unmappable (leave unconstrained).

    Each split dim claims unused axes whose sizes multiply to its split
    count; the duplicate (replica) axis stays unsharded. Dropping a
    *distributed* status is numerically safe (XLA picks a layout) but it
    silently forfeits the memory/compute split the user asked for — so
    it warns, naming the node and status (round-5 review #7).
    """
    from jax.sharding import PartitionSpec
    if status is None or status.state is None or not status.is_dist():
        return PartitionSpec() if status is not None else None
    avail = {name: size for name, size in model_axes.items()}
    spec = []
    for parts in status.state:
        if parts == 1:
            spec.append(None)
            continue
        take = []
        for p in _prime_factors(parts):
            cand = next((n for n, s in avail.items()
                         if s == p and n not in take), None)
            if cand is None:
                # under an active analysis pass this is a structured
                # HT201 finding with node provenance; the bare warning
                # stays as the fallback when analysis is off
                from ..analysis.findings import emit
                msg = (f"TP constraint unmappable: "
                       f"{node if node is not None else '<node>'} "
                       f"wants status {status} but the {parts}-way "
                       f"split has no free mesh axis of size {p} in "
                       f"{dict(model_axes)} — the node would run "
                       f"unconstrained (replicated layout, no "
                       f"memory/compute split)")
                if not emit("HT201", "error", msg, node=node):
                    logger.warning("%s", msg)
                return None
            take.append(cand)
        del_names = list(take)
        for n in del_names:
            avail.pop(n, None)
        spec.append(tuple(take) if len(take) > 1 else take[0])
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


def propagate_statuses(topo, sweeps=3):
    """Seed statuses from DispatchOp markers and propagate through
    ``deduce_states`` in topo order to a fixpoint.

    Returns the node -> NodeStatus dict (empty when no dispatch present).
    Mesh-independent: callers lower the statuses to specs over whatever
    mesh fits their device set (global for SPMD, per-stage for PP+TP).
    """
    from ..ops.comm import DispatchOp, DispatchGradientOp
    from ..ops.variable import PlaceholderOp

    dispatch_ops = [n for n in topo if isinstance(n, DispatchOp)]
    if not dispatch_ops:
        return {}

    status = {}
    for d in dispatch_ops:
        st = d.target_status()
        status[d] = st
        # a parameter feeding a dispatch is stored sharded (the TP memory
        # win — reference Variable.reshape_in_mp slices it per device,
        # Variable.py:82-108; here device_put with the spec shards it)
        if isinstance(d.inputs[0], PlaceholderOp):
            status[d.inputs[0]] = st

    # forward propagation to a fixpoint: ops without an explicit rule use
    # the elementwise default (Op.deduce_states)
    for _ in range(sweeps):
        changed = False
        for node in topo:
            if node in status and isinstance(
                    node, (DispatchOp, PlaceholderOp)):
                continue
            in_sts = [status.get(i) for i in node.inputs]
            if all(s is None for s in in_sts):
                continue
            st = NodeStatus()
            try:
                node.deduce_states(
                    [NodeStatus.from_other(s) if s is not None else None
                     for s in in_sts], st, False)
            except Exception as e:
                # the node stays unconstrained (numerics unaffected — XLA
                # picks a layout) but a broken rule must not be silent:
                # structured HT202 under an analysis pass, warning else
                from ..analysis.findings import emit
                msg = (f"deduce_states failed for {node} "
                       f"({type(e).__name__}: {e}) — conflicting or "
                       f"malformed input partition statuses; the node "
                       f"runs unconstrained")
                if not emit("HT202", "error", msg, node=node):
                    logger.warning("%s", msg)
                continue
            if st.state is None:
                continue
            if st.duplicate is None or st.order is None:
                st.get_default()
            if status.get(node) != st:
                status[node] = st
                changed = True
        if not changed:
            break

    # gradient side: DispatchGradientOp mirrors its forward input's status
    for node in topo:
        if isinstance(node, DispatchGradientOp) and \
                node.forward_input in status:
            status[node] = status[node.forward_input]
    return status


def assign_states(eval_node_list, config):
    """Whole-graph planning for the SPMD executor: propagate statuses,
    build the mesh, assign specs.

    Fills ``config.node_status`` (node -> NodeStatus) and
    ``config.node_spec`` (node -> PartitionSpec); sets ``config.mesh``
    and ``config.model_axes`` when TP is present.
    """
    from ..graph.autodiff import find_topo_sort

    topo = find_topo_sort(eval_node_list)
    status = propagate_statuses(topo)
    if not status or not any(
            st is not None and st.is_dist() for st in status.values()):
        # only degenerate (1,1) dispatches: nothing is actually split —
        # an empty mesh would poison every constraint site
        return False

    # mesh + specs
    dp = config.nrank if config.mesh is not None and \
        "dp" in getattr(config.mesh, "axis_names", ()) else 1
    mesh, model_axes = mesh_for_statuses(status.values(), dp=dp)
    config.mesh = mesh
    config.model_axes = model_axes
    config.node_status = status
    config.node_spec = {}
    for node, st in status.items():
        spec = spec_for_status(st, model_axes, node=node)
        if spec is not None:
            config.node_spec[node] = spec
    return True
