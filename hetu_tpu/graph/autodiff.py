"""Reverse-mode autodiff over the op graph.

Reference parity: ``gradients`` / ``find_topo_sort`` / ``sum_node_list``
(python/hetu/gpu_ops/executor.py:1867-2034). Walks the reverse topological
order, sums partial adjoints per node, and asks each op for the gradient
ops of its inputs.
"""
from __future__ import annotations

__all__ = ["gradients", "find_topo_sort", "find_topo_sort_inference",
           "sum_node_list", "topo_sort_with_hook"]


def find_topo_sort(node_list):
    """Post-order DFS topological sort (reference executor.py:1946)."""
    visited = set()
    topo_order = []

    def dfs(node):
        if id(node) in visited:
            return
        visited.add(id(node))
        for n in node.inputs:
            dfs(n)
        topo_order.append(node)

    for node in node_list:
        dfs(node)
    return topo_order


def sum_node_list(node_list, ctx=None):
    """Sum partial adjoints, avoiding creating redundant add nodes
    (reference executor.py:2026)."""
    from ..ops.basic import add_op
    node_list = [n for n in node_list if n is not None]
    if not node_list:
        return None
    result = node_list[0]
    for node in node_list[1:]:
        result = add_op(result, node, ctx=ctx)
    return result


def gradients(output_node, node_list, insert_grad=None):
    """Build gradient ops of output_node w.r.t. each node in node_list
    (reference executor.py:1867-1919).

    insert_grad: optional op to use as the seed adjoint of output_node
    (defaults to OnesLike, i.e. d(output)/d(output) = 1).
    """
    from . import node as graph_node
    from ..ops.shape import oneslike_op

    first_minted = graph_node.G_NODE_ID
    if insert_grad is None:
        insert_grad = oneslike_op(output_node, ctx=output_node.raw_ctx)
    node_to_grads = {output_node: [insert_grad]}
    node_to_grad = {}

    reverse_topo = reversed(find_topo_sort([output_node]))
    for node in reverse_topo:
        if node not in node_to_grads:
            continue
        grad = sum_node_list(node_to_grads[node], ctx=node.raw_ctx)
        if grad is None:
            continue
        node_to_grad[node] = grad
        if not node.inputs:
            continue
        input_grads = node.gradient(grad)
        if input_grads is None:
            continue
        for inp, ig in zip(node.inputs, input_grads):
            if ig is None:
                continue
            node_to_grads.setdefault(inp, []).append(ig)

    # every node minted here is the backward pass's (Op.role): the seed,
    # each node.gradient(...) result and what it is built from, the sums
    for node in find_topo_sort(list(node_to_grad.values())):
        if node.id >= first_minted:
            node.role = "bwd"
    results = []
    for node in node_list:
        assert node in node_to_grad, \
            f"no gradient path from output to {node.name}"
        results.append(node_to_grad[node])
    return results


def find_topo_sort_inference(node_list):
    """Topo sort for the inference graph: strips optimizer and gradient-only
    subtrees, keeping parameter reads (reference executor.py:1972-1998 swaps
    PS pushes for SparsePulls; here the executor handles that at partition
    time, so inference topo is a plain sort of the eval nodes)."""
    return find_topo_sort(node_list)


def topo_sort_with_hook(node_list, config):
    """Reverse-order backward hooks then forward-order forward hooks
    (reference executor.py:1926-1943)."""
    topo_order = find_topo_sort(node_list)
    for node in reversed(topo_order):
        node.backward_hook(config)
    for node in topo_order:
        node.forward_hook(config)
    return topo_order
