"""DistGCN-1.5D: distributed full-graph GCN SpMM over a 2D device mesh.

Reference parity: python/hetu/gpu_ops/DistGCN_15d.py:19-156 — A·(H·W)
with H blocks broadcast stage-by-stage inside column subgroups, each
process multiplying its CSR slice and accumulating, then an allreduce
over row subgroups combining the replicated partials.

TPU-native formulation: mesh axes ("gr", "gc") with gr = size/replication
graph-row shards and gc = replication. H shards over gr (replicated over
gc). Instead of NCCL broadcasts, H blocks rotate around the gr ring with
``lax.ppermute`` (neighbor ICI links, overlapping with the SpMM blocks —
the same schedule ring attention uses); each gc column multiplies only
the column blocks assigned to it (block b belongs to column b mod gc),
so SpMM flops divide by gc, and ``lax.psum`` over gc plays the
reference's row-group allreduce. Per-device adjacency travels as padded
COO stages so shapes stay static under jit.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["DistCSR15d", "partition_csr_15d", "dist_gcn_spmm"]


class DistCSR15d:
    """Padded per-(device, ring-step) COO stages of the adjacency.

    data:  [gr, gc, gr, nnz_max] float32
    rows:  [gr, gc, gr, nnz_max] int32   (row within the device's shard)
    cols:  [gr, gc, gr, nnz_max] int32   (row within the incoming block)
    ``n_per`` rows per shard (graph padded to gr * n_per)."""

    def __init__(self, data, rows, cols, n_per, n_nodes, gr, gc):
        self.data = data
        self.rows = rows
        self.cols = cols
        self.n_per = int(n_per)
        self.n_nodes = int(n_nodes)
        self.gr = int(gr)
        self.gc = int(gc)

    def tree_flatten(self):
        return ((self.data, self.rows, self.cols),
                (self.n_per, self.n_nodes, self.gr, self.gc))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


jax.tree_util.register_pytree_node(
    DistCSR15d, DistCSR15d.tree_flatten, DistCSR15d.tree_unflatten)


def partition_csr_15d(adj, gr, gc):
    """scipy CSR -> DistCSR15d for a (gr, gc) mesh.

    Device (r, c) at ring step k multiplies A[rows_r, block_b] where
    b = (r + k) mod gr, but only when b mod gc == c (its column
    assignment) — other steps carry zero padding."""
    import scipy.sparse as sp

    n = adj.shape[0]
    n_per = -(-n // gr)
    padded = n_per * gr
    if padded != n:
        adj = sp.csr_matrix((adj.data, adj.indices, adj.indptr),
                            shape=(n, n))
        adj.resize((padded, padded))

    stages = {}
    nnz_max = 1
    for r in range(gr):
        rows_lo, rows_hi = r * n_per, (r + 1) * n_per
        a_r = adj[rows_lo:rows_hi]
        for c in range(gc):
            for k in range(gr):
                b = (r + k) % gr
                if b % gc != c:
                    continue
                blk = a_r[:, b * n_per:(b + 1) * n_per].tocoo()
                stages[(r, c, k)] = (
                    blk.data.astype(np.float32),
                    blk.row.astype(np.int32),
                    blk.col.astype(np.int32))
                nnz_max = max(nnz_max, len(blk.data))

    data = np.zeros((gr, gc, gr, nnz_max), np.float32)
    rows = np.zeros((gr, gc, gr, nnz_max), np.int32)
    cols = np.zeros((gr, gc, gr, nnz_max), np.int32)
    for (r, c, k), (d, ri, ci) in stages.items():
        data[r, c, k, :len(d)] = d
        rows[r, c, k, :len(d)] = ri
        cols[r, c, k, :len(d)] = ci
    return DistCSR15d(data, rows, cols, n_per, n, gr, gc)


def dist_gcn_spmm(adj, h, mesh):
    """z = A @ h over the ("gr", "gc") mesh; h, z are [N, F] global
    (sharded over gr, replicated over gc)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    gr, gc, n_per, n = adj.gr, adj.gc, adj.n_per, adj.n_nodes
    padded = gr * n_per
    if h.shape[0] != padded:
        h = jnp.pad(h, ((0, padded - h.shape[0]), (0, 0)))

    def body(data, rows, cols, h_local):
        # h_local: [n_per, F] (gr dim consumed by the spec); adj stages
        # keep size-1 leading mesh dims: [1, 1, gr, nnz]
        perm = [(i, (i - 1) % gr) for i in range(gr)]

        def accum(z, k, h_cur):
            d = data[0, 0, k]
            return z + jax.ops.segment_sum(
                h_cur[cols[0, 0, k]] * d[:, None], rows[0, 0, k],
                num_segments=n_per)

        def step(k, carry):
            z, h_cur = carry
            return accum(z, k, h_cur), lax.ppermute(h_cur, "gr", perm)

        # z accumulates data-derived (gc-varying) terms; mark the zero
        # init as gc-varying too or the scan carry types disagree
        z0 = lax.pcast(jnp.zeros_like(h_local), ("gc",), to="varying")
        # gr-1 rotations in the loop; the last block accumulates outside
        # (a gr-th ppermute would rotate into a discarded carry)
        z, h_last = lax.fori_loop(0, gr - 1, step, (z0, h_local))
        z = accum(z, gr - 1, h_last)
        return lax.psum(z, "gc")  # reference row-group allreduce

    spec_adj = P("gr", "gc", None, None)
    spec_h = P("gr", None)
    z = shard_map(body, mesh=mesh,
                  in_specs=(spec_adj, spec_adj, spec_adj, spec_h),
                  out_specs=spec_h)(adj.data, adj.rows, adj.cols, h)
    return z[:n]
