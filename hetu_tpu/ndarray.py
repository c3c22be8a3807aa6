"""Array and device layer for hetu-tpu.

TPU-native equivalent of the reference's DLArray/NDArray runtime
(reference: python/hetu/ndarray.py, src/common/c_runtime_api.h). Instead of a
ctypes handle into a CUDA allocator, an :class:`NDArray` owns a ``jax.Array``
(device memory managed by XLA/PJRT) plus a :class:`DLContext` describing the
logical placement. Host<->device copies map to ``jax.device_put`` /
``np.asarray``; CUDA streams/events map to XLA async dispatch +
``block_until_ready`` (see stream.py).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "DLContext", "cpu", "gpu", "tpu", "rcpu", "rgpu", "rtpu",
    "is_gpu_ctx", "is_tpu_ctx", "device_backend", "cpu_pinned",
    "NDArray", "array", "empty", "sparse_array", "ND_Sparse_Array",
    "IndexedSlices",
]


# ---------------------------------------------------------------------------
# Device contexts
# ---------------------------------------------------------------------------

_DEVICE_KINDS = ("cpu", "tpu")


def cpu_pinned():
    """True when this process was explicitly pinned to the CPU platform
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms",
    "cpu")`` — the test harness and chip-free rehearsals). Only then may
    an accelerator context stand for a virtual CPU device."""
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


class DLContext:
    """A logical device: (hostname, kind, device_id).

    Mirrors the reference DLContext (python/hetu/ndarray.py:17) but device
    kinds are cpu/tpu. ``gpu(i)`` is kept as a compatibility alias that maps
    onto the i-th accelerator so reference example scripts run unchanged.
    """

    __slots__ = ("hostname", "kind", "device_id")

    def __init__(self, kind, device_id=0, hostname="localhost"):
        assert kind in _DEVICE_KINDS, f"unknown device kind {kind}"
        self.kind = kind
        self.device_id = int(device_id)
        self.hostname = hostname

    @property
    def local(self):
        return self.hostname == "localhost"

    def is_accelerator(self):
        return self.kind != "cpu"

    def jax_device(self):
        """The concrete local jax device this context names.

        ``tpu(i)`` is the i-th accelerator of this process. It stands for
        the i-th virtual CPU device only in a process pinned to the CPU
        platform (:func:`cpu_pinned`); anywhere else a missing
        accelerator is an error, never a quiet CPU placement. ``cpu(i)``
        is a logical host placement (PS-routed tables, dataloader
        buffers): its arrays live on the i-th device of the default
        backend so feeds need no second hop. An index beyond the devices
        present raises — it never wraps onto device 0."""
        devs = jax.local_devices()
        if self.kind == "tpu":
            accel = [d for d in devs if d.platform != "cpu"]
            if accel:
                devs = accel
            elif not cpu_pinned():
                raise RuntimeError(
                    f"{self!r}: no accelerator in this process (default "
                    f"backend {jax.default_backend()!r}); pin the CPU "
                    f"platform with JAX_PLATFORMS=cpu to run accelerator "
                    f"contexts on virtual CPU devices")
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: device index {self.device_id} out of range, "
                f"this process has {len(devs)} "
                f"{devs[0].platform} device(s)")
        return devs[self.device_id]

    def relocalize(self):
        self.hostname = "localhost"

    def __eq__(self, other):
        return (isinstance(other, DLContext)
                and self.hostname == other.hostname
                and self.kind == other.kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.hostname, self.kind, self.device_id))

    def __repr__(self):
        prefix = "" if self.local else self.hostname + ":"
        return f"{prefix}{self.kind}:{self.device_id}"


def cpu(dev_id=0):
    return DLContext("cpu", dev_id)


def tpu(dev_id=0):
    return DLContext("tpu", dev_id)


def gpu(dev_id=0):
    """Compatibility alias: reference scripts say ``ht.gpu(i)``; on this
    framework that means the i-th TPU chip."""
    return DLContext("tpu", dev_id)


def rcpu(hostname, dev_id=0):
    return DLContext("cpu", dev_id, hostname=hostname)


def rtpu(hostname, dev_id=0):
    return DLContext("tpu", dev_id, hostname=hostname)


def rgpu(hostname, dev_id=0):
    return DLContext("tpu", dev_id, hostname=hostname)


def is_gpu_ctx(ctx):
    """Reference-compat name (ndarray.py:84): true if ctx is an accelerator."""
    return ctx is not None and ctx.is_accelerator()


def is_tpu_ctx(ctx):
    return is_gpu_ctx(ctx)


def device_backend(ctx=None):
    if ctx is None or ctx.is_accelerator():
        return jax.default_backend()
    return "cpu"


# ---------------------------------------------------------------------------
# NDArray
# ---------------------------------------------------------------------------

class NDArray:
    """Device array handle: a jax.Array + logical context.

    The executor's boundary type. Feed values, fetched results and saved
    parameters travel as NDArray; inside a compiled step everything is raw
    jax values.
    """

    __slots__ = ("_value", "ctx")

    def __init__(self, value, ctx=None):
        self._value = value
        self.ctx = ctx if ctx is not None else cpu(0)

    # -- properties ---------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def jax_array(self):
        return self._value

    @property
    def lazy(self):
        return False

    # -- host/device movement ----------------------------------------------
    def asnumpy(self):
        return np.asarray(self._value)

    def copyto(self, target):
        if isinstance(target, DLContext):
            return NDArray(jax.device_put(self._value, target.jax_device()),
                           target)
        assert isinstance(target, NDArray)
        target._value = jax.device_put(self._value, target.ctx.jax_device())
        return target

    def async_h2d(self, source, stream_handle=None, event_handle=None):
        # jax.device_put is asynchronous already; completion is observed via
        # block_until_ready (stream.Event.sync).
        if isinstance(source, np.ndarray):
            self._value = jax.device_put(source, self.ctx.jax_device())
        else:
            self._value = jax.device_put(source._value, self.ctx.jax_device())

    def async_d2h(self, source, stream_handle=None, event_handle=None):
        self._value = np.asarray(source._value)

    def block_until_ready(self):
        if isinstance(self._value, jax.Array):
            self._value.block_until_ready()
        return self

    # -- numpy-ish sugar ----------------------------------------------------
    def __getitem__(self, idx):
        return NDArray(self._value[idx], self.ctx)

    def __repr__(self):
        return f"NDArray(shape={self.shape}, dtype={self.dtype}, ctx={self.ctx})"


def array(arr, ctx=None, dtype=np.float32):
    """Create an NDArray from array-like data on the given context
    (reference ndarray.py:407)."""
    ctx = ctx if ctx is not None else cpu(0)
    arr = np.asarray(arr, dtype=dtype)
    value = jax.device_put(arr, ctx.jax_device())
    return NDArray(value, ctx)


def empty(shape, ctx=None, dtype=np.float32):
    ctx = ctx if ctx is not None else cpu(0)
    value = jax.device_put(jnp.zeros(shape, dtype=dtype), ctx.jax_device())
    return NDArray(value, ctx)


# ---------------------------------------------------------------------------
# Sparse containers
# ---------------------------------------------------------------------------

class ND_Sparse_Array:
    """CSR sparse matrix (reference ndarray.py:435). Stored as three device
    arrays; consumed by csrmm/csrmv ops which lower to gather/segment-sum —
    XLA-friendly replacements for cuSPARSE."""

    __slots__ = ("data", "row", "col", "nrow", "ncol", "ctx")

    def __init__(self, data, row, col, nrow, ncol, ctx=None):
        self.data = data            # NDArray [nnz]
        self.row = row              # NDArray [nrow+1] indptr (int32)
        self.col = col              # NDArray [nnz]   indices (int32)
        self.nrow = nrow
        self.ncol = ncol
        self.ctx = ctx if ctx is not None else cpu(0)

    @property
    def shape(self):
        return (self.nrow, self.ncol)

    def asnumpy(self):
        import scipy.sparse as sp
        return sp.csr_matrix(
            (self.data.asnumpy(), self.col.asnumpy(), self.row.asnumpy()),
            shape=self.shape).toarray()


def sparse_array(values, indices, shape, ctx=None, dtype=np.float32):
    """Build CSR from COO (values, (rows, cols)) like reference
    ndarray.py:469."""
    import scipy.sparse as sp
    mat = sp.csr_matrix((values, indices), shape=shape, dtype=dtype)
    return ND_Sparse_Array(
        array(mat.data, ctx=ctx, dtype=dtype),
        array(mat.indptr, ctx=ctx, dtype=np.int32),
        array(mat.indices, ctx=ctx, dtype=np.int32),
        shape[0], shape[1], ctx=ctx)


class IndexedSlices:
    """Sparse gradient of an embedding lookup: (indices, values) pair
    (reference ndarray.py:482). ``dedup`` merges duplicate rows with a
    segment-sum so downstream optimizers apply each row once."""

    __slots__ = ("indices", "values", "dense_shape")

    def __init__(self, indices=None, values=None, dense_shape=None):
        self.indices = indices      # jnp int array, any shape
        self.values = values        # jnp float array, indices.shape + [dim]
        self.dense_shape = dense_shape

    def get_dense_rows(self):
        return self.values.reshape(-1, self.dense_shape[-1])

    def get_flat_indices(self):
        return self.indices.reshape(-1)

    def dedup(self):
        """Merge duplicate indices (reference: IndexedSlices.deduplicate,
        src/ops/IndexedSlices.cu). Returns (unique_indices, summed_values)
        with static shapes (padded with dense_shape[0] sentinel)."""
        flat_idx = self.get_flat_indices()
        rows = self.get_dense_rows()
        uniq, inv = jnp.unique(
            flat_idx, return_inverse=True, size=flat_idx.shape[0],
            fill_value=self.dense_shape[0])
        summed = jax.ops.segment_sum(rows, inv, num_segments=flat_idx.shape[0])
        return uniq, summed

    def sorted_rows(self):
        """``(ids, rows)`` in ascending order of the ids, duplicates
        kept, each beside its own row, in the order they came (a stable
        sort): what ``hetu_sparse_rows_update`` takes, which adds the
        rows of one id up itself. One sort of the ids and one gather of
        the rows, where ``dedup`` asks XLA for a scatter-add of every
        row as well (on a TPU a microsecond a row: PERF.md section 6,
        PR 51)."""
        flat_idx = self.get_flat_indices()
        ids, order = jax.lax.sort_key_val(
            flat_idx, jnp.arange(flat_idx.shape[0], dtype=jnp.int32))
        return ids, self.get_dense_rows()[order]

    def to_dense(self):
        out = jnp.zeros(self.dense_shape, dtype=self.values.dtype)
        return out.at[self.get_flat_indices()].add(self.get_dense_rows())


class CSRValue:
    """Traced CSR triple with static shape — the in-graph value form of
    ND_Sparse_Array (nrow/ncol stay static so segment_sum sizes are
    compile-time constants).

    ``row_ids`` (the per-nnz row index, i.e. the COO row array) is a pure
    function of ``indptr``; it is precomputed once at ingest so csrmm /
    csrmv never re-derive it with a searchsorted over nnz inside every
    forward and backward call (the reference's cuSPARSE kernels get it for
    free from the CSR walk, src/ops/CuSparseCsrmm.cu).

    ``t_data/t_indices/t_row_ids`` hold A^T in the same COO-sorted form
    (entries sorted by column). The transposed product in every csrmm
    backward then lowers to a gather + *sorted* segment-sum instead of a
    general scatter — the TPU analogue of cuSPARSE keeping a CSC copy for
    the transposed kernels."""

    __slots__ = ("data", "indptr", "indices", "nrow", "ncol", "row_ids",
                 "t_data", "t_indices", "t_row_ids")

    def __init__(self, data, indptr, indices, nrow, ncol, row_ids=None,
                 t_data=None, t_indices=None, t_row_ids=None):
        self.data = data
        self.indptr = indptr
        self.indices = indices
        self.nrow = nrow
        self.ncol = ncol
        self.row_ids = row_ids
        self.t_data = t_data          # data sorted by column
        self.t_indices = t_indices    # original row per entry (A^T's cols)
        self.t_row_ids = t_row_ids    # sorted columns (A^T's rows)

    @classmethod
    def from_sparse_array(cls, sp: "ND_Sparse_Array"):
        def as_jax(v):
            return v.jax_array if isinstance(v, NDArray) else jnp.asarray(v)
        def host(v):
            return np.asarray(v.asnumpy() if isinstance(v, NDArray) else v)
        indptr_host = host(sp.row)
        indices_host = host(sp.indices if hasattr(sp, "indices") else sp.col)
        data_host = host(sp.data)
        row_ids = np.repeat(
            np.arange(sp.nrow, dtype=np.int32), np.diff(indptr_host))
        perm = np.argsort(indices_host, kind="stable")
        return cls(as_jax(sp.data), as_jax(sp.row), as_jax(sp.col),
                   sp.nrow, sp.ncol, jnp.asarray(row_ids),
                   jnp.asarray(data_host[perm]),
                   jnp.asarray(row_ids[perm]),
                   jnp.asarray(indices_host[perm].astype(np.int32)))


jax.tree_util.register_pytree_node(
    CSRValue,
    lambda s: ((s.data, s.indptr, s.indices, s.row_ids,
                s.t_data, s.t_indices, s.t_row_ids), (s.nrow, s.ncol)),
    lambda aux, leaves: CSRValue(leaves[0], leaves[1], leaves[2],
                                 aux[0], aux[1], *leaves[3:]),
)


# IndexedSlices values flow through jitted step functions, so they must be
# a pytree (indices/values are leaves, dense_shape is static metadata).
jax.tree_util.register_pytree_node(
    IndexedSlices,
    lambda s: ((s.indices, s.values), s.dense_shape),
    lambda shape, leaves: IndexedSlices(leaves[0], leaves[1], shape),
)
