"""Online inference: the subsystem that turns a trained checkpoint into
something that answers requests.

The training stack (executor/PS/telemetry) already owns compilation,
checkpoints and sparse tables; serving composes them into these pieces:

* :class:`~hetu_tpu.serving.session.InferenceSession` — frozen-graph
  sessions over eval nodes + an ``Executor.save`` checkpoint dir, with
  mandatory shape bucketing so ragged traffic cannot cause a retrace
  storm (``jit_compiles`` is bounded by the bucket count).
* :class:`~hetu_tpu.serving.batcher.MicroBatcher` — thread-safe dynamic
  micro-batching: concurrent ``submit()`` calls coalesce into one padded
  batch per tick (``max_batch_size`` / ``max_wait_ms``), results split
  back per request, queue-depth / latency / occupancy metrics exported
  through ``hetu_tpu/telemetry/metrics.py``.
* :mod:`~hetu_tpu.serving.embedding` — PS-backed sparse serving: eval
  graphs rewritten to pull embedding rows from the parameter server
  read-only (a push from a serving session raises), with a host row
  cache and hit-rate gauge.
* :class:`~hetu_tpu.serving.http.ServingHTTPServer` — minimal stdlib
  JSON frontend over a session or batcher (``/v1/predict``, ``/healthz``,
  ``/metrics``).
* autoregressive decode for the GPT family —
  :class:`~hetu_tpu.serving.kvcache.PagedKVCache` (block-paged pooled
  K/V + free-list allocator, HBM-budgeted via HT4xx),
  :class:`~hetu_tpu.serving.scheduler.ContinuousBatchingEngine`
  (iteration-level join/leave scheduling over the paged cache, HT901
  bucketed jit signatures, KV-block admission control, greedy +
  temperature sampling; numerically pinned against the full-sequence
  graph forward), and
  :class:`~hetu_tpu.serving.router.ReplicaRouter` (SLO-probed
  least-inflight routing + load shedding over N replicas).
* :mod:`~hetu_tpu.serving.lifecycle` — request-level observability:
  end-to-end request ids minted at ingress and propagated through
  router/engine/batcher, per-request phase timelines exported as
  ``serve_request``/``serve_phase`` trace spans (the serving doctor's
  input: ``python -m hetu_tpu.telemetry.doctor --serving``), live
  ``inflight_requests()``/``stats()`` introspection behind
  ``GET /v1/requests`` and ``GET /stats``, and crash-time
  ``requests_rank<r>.json`` dumps the black-box analyzer ingests.
"""
from .session import InferenceSession, next_bucket
from .batcher import MicroBatcher
from .embedding import ReadOnlyPSClient, serve_embeddings_from_ps
from .http import ServingHTTPServer
from .kvcache import (BlockAllocator, KVCacheExhausted, PagedKVCache,
                      PrefixCache)
from .lifecycle import RequestTimeline, mint_request_id
from .router import ReplicaRouter, RouterOverloaded, SLOWindow
from .scheduler import ContinuousBatchingEngine, EngineOverloaded

__all__ = ["InferenceSession", "MicroBatcher",
           "ReadOnlyPSClient", "serve_embeddings_from_ps",
           "ServingHTTPServer", "next_bucket",
           "BlockAllocator", "KVCacheExhausted", "PagedKVCache",
           "PrefixCache",
           "ContinuousBatchingEngine", "EngineOverloaded",
           "ReplicaRouter", "RouterOverloaded", "SLOWindow",
           "RequestTimeline", "mint_request_id"]
