"""Frozen-graph inference sessions.

``InferenceSession`` wraps an eval-only :class:`~hetu_tpu.executor.Executor`
(no optimizer state, no dataloader machinery) restored from an
``Executor.save`` checkpoint (one ``.npy`` per parameter + sidecar), and
serves ``predict(feed_dict)`` with MANDATORY shape bucketing: the batch
dim pads up to the next power-of-two bucket and (optionally) a ragged
sequence dim pads to a fixed bucket, so the number of distinct compiled
programs — visible as the ``jit_compiles`` telemetry counter — is bounded
by the bucket count no matter how ragged the traffic is. TF-Serving's
frozen-graph session is the shape; the executor's per-feed-shape jit
cache is the mechanism.
"""
from __future__ import annotations

import time

import numpy as np

from .. import telemetry as _telemetry
from ..executor import Executor, HetuConfig
from ..graph.autodiff import find_topo_sort
from ..graph.node import Op

__all__ = ["InferenceSession", "next_bucket"]


def next_bucket(n, buckets=None):
    """Smallest bucket >= n. ``buckets=None`` means the power-of-two
    ladder {1, 2, 4, 8, ...}; an explicit sequence must be sorted."""
    n = int(n)
    if buckets is None:
        b = 1
        while b < n:
            b *= 2
        return b
    for b in buckets:
        if b >= n:
            return int(b)
    raise ValueError(f"batch/seq of {n} exceeds the largest configured "
                     f"bucket {max(buckets)}")


def _pad_axis(arr, target, axis):
    """Pad by repeating the trailing slice (edge padding keeps ids in
    vocabulary range and dense features finite — zeros could be an
    out-of-distribution input for either)."""
    n = arr.shape[axis]
    if n == target:
        return arr
    take = [slice(None)] * arr.ndim
    take[axis] = slice(n - 1, n)
    pad = np.repeat(arr[tuple(take)], target - n, axis=axis)
    return np.concatenate([arr, pad], axis=axis)


class InferenceSession:
    """Serve ``predict()`` over a frozen eval graph.

    Parameters
    ----------
    eval_node_list : list[Op]
        Output nodes (logits, probabilities, ...). The graph must be
        inference-only: an optimizer, dataloader, or PS push op in the
        closure raises at construction — freezing is a contract, not a
        convention.
    checkpoint : str, optional
        ``Executor.save`` directory to restore parameters from.
    batch_buckets : sequence[int], optional
        Batch-dim buckets (default: powers of two).
    seq_buckets : sequence[int], optional
        When set, dim ``seq_axis`` of every feed with more than
        ``seq_axis`` dims also pads up to a bucket (causal LMs: extra
        trailing positions never change real positions' outputs).
    ps_read_only : bool
        Wrap the session's PS client so any push raises (default True).
    executor_kwargs :
        Forwarded to :class:`HetuConfig` (``ctx``, ``comm_mode``,
        ``mesh``, ``dtype``, ``telemetry``, ...).
    """

    def __init__(self, eval_node_list, checkpoint=None, *,
                 batch_buckets=None, seq_buckets=None, seq_axis=1,
                 ps_read_only=True, embed_cache_rows=0, telemetry=None,
                 **executor_kwargs):
        eval_node_list = list(eval_node_list)
        self._check_frozen(eval_node_list)
        self.telemetry = _telemetry.resolve(telemetry)
        self.batch_buckets = (tuple(sorted(batch_buckets))
                              if batch_buckets else None)
        self.seq_buckets = (tuple(sorted(seq_buckets))
                            if seq_buckets else None)
        self.seq_axis = int(seq_axis)

        config = HetuConfig(eval_node_list=eval_node_list,
                            telemetry=self.telemetry, **executor_kwargs)
        self.ps_client = None
        if config.ps_comm is not None and ps_read_only:
            from .embedding import ReadOnlyPSClient
            if not isinstance(config.ps_comm, ReadOnlyPSClient):
                config.ps_comm = ReadOnlyPSClient(
                    config.ps_comm, cache_rows=embed_cache_rows,
                    telemetry=self.telemetry)
            self.ps_client = config.ps_comm
        self.executor = Executor({"default": eval_node_list},
                                 config=config)
        sub = self.executor.subexecutors["default"]
        assert not sub.training
        self.feed_nodes = list(sub.feed_nodes)
        # the PS sparse-pull path consumes raw id feeds that are also
        # plain graph inputs — names resolve either way
        self._by_name = {n.name: n for n in self.feed_nodes}
        if checkpoint is not None:
            self.load(checkpoint)

    @staticmethod
    def _check_frozen(eval_node_list):
        # the frozen-graph contract (no optimizer / PS push / dataloader
        # ops) is an analysis pass (HT15x findings); construction keeps
        # raising ValueError so the session API is unchanged
        from ..analysis import Report, frozen_graph_pass
        report = Report()
        frozen_graph_pass(find_topo_sort(eval_node_list), report)
        if report.errors:
            raise ValueError("\n".join(f.message for f in report.errors))

    # ------------------------------------------------------------------
    def load(self, checkpoint):
        """Restore parameters from an ``Executor.save`` directory."""
        self.executor.load(checkpoint)
        return self

    def params_by_name(self):
        """{param name: device array} — the bridge to weight-level
        serving paths (ContinuousBatchingEngine.from_session)."""
        return {node.name: self.executor.params[sid]
                for sid, node in self.executor._param_nodes.items()}

    def node_of(self, key):
        if isinstance(key, Op):
            return key
        try:
            return self._by_name[key]
        except KeyError:
            raise KeyError(
                f"unknown feed {key!r}; session feeds are "
                f"{sorted(self._by_name)}") from None

    # ------------------------------------------------------------------
    def predict(self, feed_dict, unpad=True):
        """Run the frozen forward on one (ragged) batch.

        Feeds pad up to the shape bucket, outputs slice back to the real
        batch (and sequence) before returning, as numpy arrays."""
        t0 = time.perf_counter()
        feeds = {self.node_of(k): np.asarray(v)
                 for k, v in feed_dict.items()}
        sizes = {v.shape[0] for v in feeds.values() if v.ndim}
        if len(sizes) != 1:
            raise ValueError(
                f"feeds disagree on batch size: {sorted(sizes)}")
        n = sizes.pop()
        b = next_bucket(n, self.batch_buckets)
        seq_pads = {}      # bucket -> set of real lengths padded to it
        padded = {}
        for node, v in feeds.items():
            v = _pad_axis(v, b, 0)
            if self.seq_buckets is not None and v.ndim > self.seq_axis:
                s = v.shape[self.seq_axis]
                sb = next_bucket(s, self.seq_buckets)
                seq_pads.setdefault(sb, set()).add(s)
                v = _pad_axis(v, sb, self.seq_axis)
            padded[node] = v
        # black box: a predict that never returns (wedged PS pull, hung
        # device) is a pending flight entry carrying the bucket size;
        # tag/byte-sum construction stays off the disabled hot path
        frec = None
        if self.telemetry.enabled:
            frec = self.telemetry.flight.start(
                "serve", "serve_predict", tag=f"bucket{b}",
                nbytes=sum(int(v.nbytes) for v in padded.values()))
        outs = self.executor.run("default", feed_dict=padded,
                                 convert_to_numpy_ret_vals=True)
        self.telemetry.flight_complete(frec)
        if unpad:
            outs = [self._trim(o, n, b, seq_pads) for o in outs]
        tel = self.telemetry
        if tel.enabled:
            tel.inc("serve_predictions")
            tel.observe("predict_ms", (time.perf_counter() - t0) * 1e3)
            tel.set_gauge("serve_batch_bucket", b)
        return outs

    def _trim(self, out, n, b, seq_pads):
        if out is None or not getattr(out, "ndim", 0):
            return out
        if out.shape[0] == b:
            out = out[:n]
        if seq_pads and out.ndim > self.seq_axis + 1:
            # ndim guard: only outputs with structure BEYOND
            # [batch, features] (e.g. logits [B, S, V]) are treated as
            # sequence-shaped — a [B, C] head whose class count happens
            # to equal a seq bucket must never be cut; per-position 2-D
            # outputs come back padded, callers slice themselves
            width = out.shape[self.seq_axis]
            reals = seq_pads.get(width)
            # trim ONLY when unambiguous: every feed padded to this
            # bucket had the same real length (two ragged feeds sharing
            # a bucket would make any cut a guess — return padded then)
            if reals is not None and len(reals) == 1:
                real = next(iter(reals))
                if real != width:
                    idx = [slice(None)] * out.ndim
                    idx[self.seq_axis] = slice(0, real)
                    out = out[tuple(idx)]
        return out

    # ------------------------------------------------------------------
    def close(self):
        self.executor.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
