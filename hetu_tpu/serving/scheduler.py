"""Iteration-level continuous batching over the paged KV cache.

*Request-level* batching fuses a tick's requests into one batch that
prefills together, decodes together, and finishes together — every
sequence pays the longest member's generation length, a late arrival
waits for the whole batch, and each batch allocates dense
``[B, H, S_max, D]`` cache buffers.

:class:`ContinuousBatchingEngine` schedules at *iteration* granularity
instead (Orca, OSDI '22), over the block-paged cache of
``serving/kvcache.py`` (vLLM's PagedAttention, SOSP '23). Every
scheduler step:

1. **finish** — sequences that produced their last token leave the
   batch immediately, resolve their Future, and free their KV blocks;
2. **admit** — waiting requests join while batch width and free KV
   blocks allow. Admission is the only gate on cache memory:
   ``admission="queue"`` (default) holds the FIFO head until blocks
   free up, ``admission="reject"`` fails its Future with
   :class:`EngineOverloaded` instead (load shedding at the engine). A
   request that could NEVER fit the pool raises
   :class:`~hetu_tpu.serving.kvcache.KVCacheExhausted` at submit;
3. **prefill** — newly admitted prompts run one causal forward
   (grouped per prompt bucket, and split so that one program holds at
   most ``prefill_token_cap`` prompt tokens) that scatters their cache
   rows into the pool via the model's prefill program
   (``models/gpt.py:gpt_paged_prefill`` for GPT);
4. **decode** — ALL running sequences take one token step in ONE jit
   program (``gpt_paged_step`` for GPT): per-sequence position vectors make
   the batch ragged-safe, block tables make it gather from the pool.
   Four small int32 numpy arrays go in with the call itself (no put of
   their own); when every active sequence is greedy the program picks
   the token itself (``pick="greedy"``) and ``bb`` int32 ids come back,
   one host sync a step, no logits leave the device. A step with a
   ``temperature > 0`` sequence in it runs the logits-returning twin
   (``hetu_paged_decode_logits``, compiled on first use) and picks on
   the host, because that draw is keyed on ``(seed, token_index)`` in
   float64 numpy and a replay has to repeat it. The loop keeps ONE
   greedy program in flight: when the next step holds the same rows,
   it is dispatched before this step's ids are read, with the ids —
   still on the device — as its ``tokens``, so the host's half of a
   step runs beside the device's (:meth:`_decode_once` has the rule;
   a request ends by count, so no step is ever dispatched for a row
   that has ended, and the programs run are the synchronous loop's).

**The HT901 contract is load-bearing here.** Sequences join/leave every
step, so naive shapes would retrace constantly. Instead every dispatch
snaps to precomputed ladders — batch width to the power-of-two ladder
(``session.py:next_bucket``), context length to a block-aligned ladder,
prompt length to the decoder's prompt ladder — so distinct jit
signatures are bounded by :attr:`compile_bound` =
``|batch| x (|prompt| + 2 |ctx|)`` ladder products (decode has a
greedy and a logits program per bucket; a model with counters adds a
slice of the ids per batch bucket) no matter how churny the trace (the
serving test measures exactly this).

``reserve="full"`` (default) allocates a request's whole
``prompt + max_new_tokens`` block budget at admission — no mid-decode
exhaustion, ever. ``reserve="lazy"`` allocates blocks as positions are
written (higher occupancy) and, on exhaustion, **preempts** the
youngest running sequence: its blocks free, it requeues at the waiting
head, and because sampling is keyed on ``(seed, token_index)`` the
recompute reproduces the exact tokens it lost.

**Prefix caching** (``prefix_cache=True``): admission resolves each
prompt against the pool's :class:`~hetu_tpu.serving.kvcache.PrefixCache`
— the cached prefix's blocks are *shared* (refcount bumped, zero new
blocks, zero prefill compute) and only the non-cached suffix is
allocated, charged, and prefilled (``gpt_paged_suffix_prefill`` starts
at the first non-cached position). Finished prefills publish their
blocks back to the cache; retired requests leave cached blocks resident
for the next hit (LRU-evicted only under allocation pressure). Shared
blocks are copy-on-write: a sequence extending into one (suffix prefill
into a shared tail, or the first decode write past a cache-frozen
prompt tail) copies it first, so sharers never see each other's writes
— with ``reserve="full"`` admission charges those copies up front and
the no-mid-decode-exhaustion guarantee stands; a genuine multi-sharer
shortfall preempts the youngest sequence exactly like lazy exhaustion.

**Chunked prefill** (``prefill_chunk=N``): a prompt longer than ``N``
non-cached tokens prefills one chunk per engine step, interleaved with
the running batch's decode, so one long cold prompt no longer stalls
TPOT for every running sequence. Chunk widths snap to their own pow2
ladder and the suffix-prefill program keys on (batch, chunk, ctx)
buckets, so :attr:`compile_bound` stays a finite ladder product —
HT901 holds with both features on.

**Models with recurrent state** (``model.pool_kinds`` holds a
``"state"`` entry: state-space layers): a running sequence holds one
state SLOT of the cache beside its blocks, from admission to retirement
or preemption, and every program takes each row's slot behind its other
arguments (``_state_slots``; padded lanes name the scratch slot 0). A
prefill writes the slot from a zero state, a decode step updates it in
place through the donated pools, a chunk of a chunked prefill continues
from it (the suffix program also takes each row's count of real
tokens, and returns each row's last real position's logits alone). A
preempted sequence's replay rebuilds its state from its tokens.
``prefix_cache=True`` is refused for such a model.

**Models with window layers** (``model.pool_kinds`` holds ``"window"``
entries: attention over the last ``model.window_size`` positions): a
running sequence holds a second, short table into those layers' own
pools, a ring (``serving/kvcache.py``), beside its table into the full
layers' pool. Every program takes the window layers' slots behind its
other arguments: a prefill each position's ring slot (``[B, P]``; only a
prompt's last ``window`` rows are written, the earlier ones and padded
lanes name the scratch block), a decode step each row's whole ring
(``[B, ring x block_size]``, a fixed shape whatever the context bucket)
and its write slot there. A preempted sequence's replay rebuilds both
tables from its tokens. ``prefix_cache=True`` and ``prefill_chunk`` are
refused for such a model.

**Program spans.** The scheduler thread's time is tiled by leaf spans
(``Telemetry.span``: the ring when telemetry is on, a ``hetu.<name>``
annotation in a ``jax.profiler`` trace always): ``serve.wait`` (nothing
waiting, nothing running; at most 100 ms each, so a profile that starts
inside a wait sees the next slice), ``serve.admit``,
``serve.prefill.build`` / ``.device`` / ``.sample``,
``serve.decode.build`` / ``.ahead`` / ``.device`` / ``.sample`` and
``serve.finish``; ``step`` is their parent. A ``.device`` span runs
from the dispatch of its program through the host sync of the rows the
scheduler reads, so what lies between two of them is the host's own
work; where a decode program stays in flight its dispatch and its sync
are a span each, and the dispatch of one made while the one before is
unread is ``serve.decode.ahead``. Inside ``serve.prefill.device`` the
host's wait for the result is the child ``serve.prefill.sync``. A
prefill that runs while other rows could have decoded lies, with the
finish after it, inside the parent ``serve.stall`` (``rows`` kept
waiting, ``admitted`` being prefilled; one a chunk under
``prefill_chunk``). The jitted programs are named
``hetu_paged_prefill`` / ``hetu_paged_decode`` /
``hetu_paged_decode_logits`` / ``hetu_paged_suffix_prefill``.

**The phase clock.** The helper that opens a leaf span (:meth:`_leaf`)
also closes a LAP of one clock (``perf_counter_ns``; plain ints, the
scheduler thread the only writer; telemetry on or off): the time since
the last lap goes to the phase the thread was in, so the phases
``wait`` / ``admit`` / ``prefill_host`` / ``prefill_device`` /
``decode_host`` / ``decode_device`` tile the thread's life exactly
(``*_device`` is the host blocked on a result, ``*_host`` everything
else, dispatch included), with ``stalled`` — the ``serve.stall`` spans
— as an overlay. ``stats()["phase_ms"]`` has the totals; a request's
account (``Future.account``, ``stats()["request_account"]``,
``serving/lifecycle.py``) is differences of readings of this clock.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from concurrent.futures import Future

import numpy as np

from .. import telemetry as _telemetry
from . import lifecycle as _lifecycle
from .kvcache import (_BUDGET_HEADROOM, DEFAULT_BLOCK_SIZE,
                      KVCacheExhausted, PagedKVCache)
from .lifecycle import RequestTimeline, mint_request_id
from .router import SLOWindow
from .session import next_bucket

__all__ = ["ContinuousBatchingEngine", "EngineOverloaded", "ENGINE_PHASES"]

_clock = time.perf_counter_ns

# the phase clock's phases (indices into ``_phase_ns``): they tile the
# scheduler thread; ``stalled`` is kept beside them as an overlay
ENGINE_PHASES = ("wait", "admit", "prefill_host", "prefill_device",
                 "decode_host", "decode_device")
_WAIT, _ADMIT, _PREFILL_HOST, _PREFILL_DEVICE, _DECODE_HOST, \
    _DECODE_DEVICE = range(len(ENGINE_PHASES))
# retired requests whose accounts ``stats()["request_account"]`` covers
_ACCOUNT_WINDOW = 256
# the longest one ``serve.wait`` span of an idle engine lasts
_WAIT_SLICE_S = 0.1


class EngineOverloaded(RuntimeError):
    """Admission control shed this request: the waiting queue is full,
    or ``admission="reject"`` and the KV pool can't hold it right now."""


def _pow2_ladder(start, cap):
    """Power-of-two ladder from ``start`` capped (and terminated) at
    ``cap`` — the finite bucket set one dispatch dimension snaps to."""
    ladder, b = [], max(1, int(start))
    while b < cap:
        ladder.append(b)
        b *= 2
    ladder.append(int(cap))
    return tuple(ladder)


def _named_program(fn, name, **static):
    """``jax.jit`` of ``fn`` with ``static`` keywords bound, under a
    stable name: a jitted ``functools.partial`` is ``jit__unknown`` in a
    profile, this is ``jit_<name>``. The pools (argument 1) are
    donated."""
    import jax

    def program(*args):
        return fn(*args, **static)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=(1,))


def _ids_program():
    """``jit_hetu_decode_ids``: the first ``n`` (static) entries of a
    decode program's result. A model with counters returns them behind
    its ``bb`` ids in one array; this takes the ids alone, on the
    device, as the next step's ``tokens``."""
    import jax

    def hetu_decode_ids(out, n):
        return out[:n]

    return jax.jit(hetu_decode_ids, static_argnums=1)


def _choose_token(logits_row, temperature, seed, idx):
    """Greedy or temperature sampling, host-side. Randomness is keyed
    on ``(seed, token_index)`` — NOT on any global stream — so a
    preempted sequence's recompute reproduces the tokens it already
    produced."""
    if temperature and temperature > 0.0:
        z = logits_row.astype(np.float64) / float(temperature)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(idx)])
        return int(rng.choice(len(p), p=p))
    return int(np.argmax(logits_row))


class _Seq:
    __slots__ = ("id", "prompt", "max_new", "temperature", "seed",
                 "future", "generated", "pending", "n_written",
                 "t_submit_ns", "t_first_token_ns", "preempts", "rid",
                 "tl", "tokens_lost", "cached_tokens", "prefill_pos",
                 "records", "unread")

    def __init__(self, sid, prompt, max_new, temperature, seed, rid):
        self.id = sid
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.future = Future()
        self.generated = []     # chosen tokens, pending included
        # beside each, the record the model's program returned for the
        # row that decided it (models with row_record_width only)
        self.records = []
        self.pending = None     # chosen but not yet written to the cache
        # cache rows written (prompt + decode), those of a decode
        # program that is dispatched and not read yet included
        self.n_written = 0
        # tokens picked on the device by dispatched decode programs that
        # the host has not read yet (they are not in ``generated``)
        self.unread = 0
        # both stamps (perf_counter_ns) are taken with telemetry on or
        # off and can be read on the Future, before and after it is done
        self.t_submit_ns = self.future.t_submit_ns = time.perf_counter_ns()
        self.t_first_token_ns = self.future.t_first_token_ns = None
        self.future.token_records = None
        # when it retired, and its latency by phase (ms;
        # lifecycle.PHASES): both set before the result is
        self.future.t_retire_ns = self.future.account = None
        self.preempts = 0
        self.rid = rid          # request id (caller-supplied or minted)
        # the marks its account is made of, telemetry on or off
        self.tl = RequestTimeline(rid, self.t_submit_ns)
        # tokens the last preemption threw away; while
        # len(generated) <= tokens_lost the sequence is re-earning them
        # (its time is "replay", and live introspection says so)
        self.tokens_lost = 0
        # prompt tokens the prefix cache resolved at admission (their
        # K/V was already resident — never recomputed)
        self.cached_tokens = 0
        # next prompt position to prefill; < len(prompt) means the
        # sequence is still in (possibly chunked) prefill
        self.prefill_pos = 0

    def prefilling(self):
        return self.prefill_pos < self.prompt.shape[0]

    def replaying(self):
        return self.tokens_lost > 0 and \
            len(self.generated) <= self.tokens_lost


class _DecodeProgram:
    """One dispatched decode program: its rows (the active sequences,
    in batch order), its spans' attributes, what it returned as a
    device array, and — once :meth:`ContinuousBatchingEngine._sync` has
    read that — the host's copy."""
    __slots__ = ("rows", "bb", "attrs", "device_pick", "out", "t0", "t1",
                 "last", "records")

    def __init__(self, rows, bb, attrs, device_pick, out, t0):
        self.rows, self.bb, self.attrs = rows, bb, attrs
        self.device_pick, self.out, self.t0 = device_pick, out, t0
        self.t1 = self.last = self.records = None



def _reachable(ladder, lo, hi):
    """Entries of a bucket ladder that values in [lo, hi] snap to."""
    first, last = next_bucket(lo, ladder), next_bucket(hi, ladder)
    return [b for b in ladder if first <= b <= last]


class ContinuousBatchingEngine:
    """See the module docstring. The model is ``config``'s serving
    model (``config.serving_model()``: parameters, the cache's row
    layout, the programs of the three cache backends —
    ``docs/serving.md``, "The serving-model interface"), so the TYPE of
    ``config`` picks it, and nothing here names a model.
    ``lookup(name) -> array`` resolves the model's checkpoint
    parameter names (for GPT ``models/gpt.py:gpt_param_names``) to
    arrays; use the classmethods for the common sources.

    With ``start=True`` (default) a daemon scheduler thread drives
    :meth:`step` whenever work exists; with ``start=False`` the caller
    drives ``step()`` directly (deterministic tests) — never both.

    ``submit()`` returns a Future resolving to the generated tokens as
    a 1-D int32 array of length ``max_new_tokens``; the Future carries
    ``t_submit_ns`` and ``t_first_token_ns`` (``perf_counter_ns``; the
    latter ``None`` until the prefill's host sync ended) and, once
    done, ``t_retire_ns``, ``account`` (its latency in ms by phase: ``queue``,
    ``prefill``, ``stalled`` behind other requests' prefills,
    ``decode_device``, ``decode_host``, ``replay``; they sum to retire
    - submit) and ``token_records``: for a model whose programs return a record
    a row (``row_record_width``), int32 ``[max_new_tokens, width]``,
    the record of the row that decided each token (``docs/serving.md``
    says what a model puts there), else ``None``."""

    def __init__(self, config, lookup, *, num_blocks=None,
                 block_size=DEFAULT_BLOCK_SIZE, budget=None, max_len=None,
                 max_batch_size=8, admission="queue", max_queue=256,
                 reserve="full", prefix_cache=False, prefill_chunk=None,
                 slo_p99_ms=None, slo_error_rate=None,
                 slo_window=128, slo_ttft_p99_ms=None, telemetry=None,
                 name="engine", start=True):
        if admission not in ("queue", "reject"):
            raise ValueError(f"admission must be 'queue' or 'reject', "
                             f"got {admission!r}")
        if reserve not in ("full", "lazy"):
            raise ValueError(f"reserve must be 'full' or 'lazy', "
                             f"got {reserve!r}")
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        self.config = config
        self.model = model = config.serving_model()
        self.max_len = int(max_len or model.max_positions)
        if self.max_len > model.max_positions:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's "
                f"positions ({model.max_positions})")
        self.max_batch_size = int(max_batch_size)
        self.admission = admission
        self.max_queue = int(max_queue)
        self.reserve = reserve
        self.name = name
        self.telemetry = _telemetry.resolve(telemetry)
        self.slo = SLOWindow(slo_p99_ms, slo_error_rate, slo_window,
                             ttft_p99_ms=slo_ttft_p99_ms)
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk = int(prefill_chunk) \
            if prefill_chunk is not None else None
        # prefix hits and chunking both mean "prefill from a token
        # offset into an existing table" — one suffix-prefill program
        # serves both, so either knob switches prefill onto it
        self._suffix_mode = self.prefix_cache \
            or self.prefill_chunk is not None
        self.params = model.params(lookup)
        # layers with recurrent state: a slot a running sequence, and
        # every program takes each row's slot behind its other arguments
        self._stateful = "state" in getattr(model, "pool_kinds", ())
        # layers that keep a window of rows: a ring a running sequence,
        # and every program takes the ring's slots behind the others
        self._windowed = "window" in getattr(model, "pool_kinds", ())
        if self._windowed and self.prefill_chunk is not None:
            raise ValueError(
                "prefill_chunk with a model that has window layers: a "
                "chunk would have to read the previous chunk's tail out "
                "of the ring, and no suffix-prefill program does (not "
                "implemented; ROADMAP.md Queue 2 A3)")
        self.cache = PagedKVCache(config, num_blocks=num_blocks,
                                  block_size=block_size, budget=budget,
                                  telemetry=self.telemetry,
                                  prefix_cache=self.prefix_cache,
                                  state_slots=self.max_batch_size)
        # HT901 ladders: every dispatch dimension snaps to one of these,
        # so signatures stay bounded under per-step churn
        self.batch_buckets = _pow2_ladder(1, self.max_batch_size)
        self.prompt_buckets = _pow2_ladder(1, self.max_len)
        self.ctx_buckets = _pow2_ladder(self.cache.block_size,
                                        self.max_len)
        self.chunk_buckets = _pow2_ladder(
            1, min(self.prefill_chunk or self.max_len, self.max_len))

        def program(kind, name):
            fn, static = model.program(kind)
            return _named_program(fn, name, **static)

        self._prefill_fn = program("prefill", "hetu_paged_prefill")
        # the greedy hot path: token ids leave the program, not logits
        self._step_fn = program("decode", "hetu_paged_decode")
        # its logits-returning twin, for a step that holds a sampled
        # sequence; nothing compiles it until such a step runs
        self._logits_step_fn = program("decode_logits",
                                       "hetu_paged_decode_logits")
        self._sprefill_fn = program("suffix_prefill",
                                    "hetu_paged_suffix_prefill")
        self._ids_fn = _ids_program()
        self.prefill_token_cap = self._prefill_token_cap(budget)
        # what the model's programs count on the device and return with
        # their tokens (none for GPT), summed by program kind
        width = len(model.counter_names) + (
            model.vector_counter[1] if model.vector_counter else 0)
        self._model_counters = {kind: np.zeros(width, np.int64)
                                for kind in ("prefill", "decode")}
        # with telemetry on, the last programs that returned counters:
        # {"kind", "t0_ns", "t1_ns" (perf_counter_ns, dispatch to the
        # end of the host sync), "<kind>_<counter>": its own counts;
        # for a model with state also "state_slots", "state_slots_used",
        # for one with window layers "window_blocks", "window_blocks_used"
        # and "window_hbm_bytes"}
        self.program_log = collections.deque(
            maxlen=16384 if width else 0)
        self._signatures = set()
        # arrays the greedy decode call walks on its way in (every leaf
        # of the parameters, the pools and the step's inputs) and wraps
        # on its way out, counted at its first dispatch: host work a
        # token that grows with the trees' SHAPE, not with their bytes
        self.program_leaves = None
        self.decode_steps = 0               # decode programs dispatched
        self.decode_device_pick_steps = 0   # ... that picked on the device
        # ... that were dispatched while the one before was still unread
        self.decode_ahead_steps = 0
        # the decode program that is dispatched and not read yet, if any
        # (scheduler thread only; see _decode_once)
        self._flight = None
        # the phase clock (scheduler thread only; see the module
        # docstring): ns by phase, the phase the thread is in and since
        # when; the stall overlay's total and, while one is open, its
        # start; with telemetry on the last stalls (t0, t1, request ids
        # prefilled) for the retiring requests' episodes
        self._phase_ns = [0] * len(ENGINE_PHASES)
        self._phase = _WAIT
        self._lap_ns = _clock()
        self._stalled_ns = 0
        self._stall_t0 = 0
        self._stalls = collections.deque(maxlen=1024)
        # the rows that took their first token inside the open stall
        self._born = []
        # the last retired requests' accounts (ns, in PHASES' order)
        self._accounts = collections.deque(maxlen=_ACCOUNT_WINDOW)
        self._ids = itertools.count()
        self._waiting = collections.deque()
        self._running = []
        self._cond = threading.Condition()
        self._closed = False
        self._thread = None
        _lifecycle.register(self)   # crash-time in-flight dumps
        if start:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name=f"{name}-scheduler")
            self._thread.start()

    # ------------------------------------------------------------------
    @classmethod
    def from_session(cls, session, config, **kw):
        """From a live :class:`InferenceSession` over the same model
        (shares the session's device-resident parameters)."""
        params = session.params_by_name()
        return cls(config, params.__getitem__, **kw)

    @classmethod
    def from_checkpoint(cls, config, path, **kw):
        """From an ``Executor.save`` checkpoint directory."""
        def lookup(name):
            f = os.path.join(path, name + ".npy")
            if not os.path.exists(f):
                raise FileNotFoundError(
                    f"checkpoint {path} has no parameter {name!r} "
                    f"(expected {f})")
            return np.load(f)
        return cls(config, lookup, **kw)

    # ------------------------------------------------------------------
    @property
    def compile_bound(self):
        """The HT901 ladder-product bound on distinct jit signatures:
        prefill keys on (batch, prompt) buckets, decode on (batch, ctx)
        buckets twice over (the greedy program and the logits one),
        suffix prefill (prefix cache / chunked prefill) on (batch,
        chunk, ctx) buckets, the slice of a step's ids for a model with
        counters on the batch bucket — churn can never compile more
        programs than this."""
        bound = len(self.batch_buckets) * (len(self.prompt_buckets)
                                           + 2 * len(self.ctx_buckets))
        if self.model.counter_names:
            bound += len(self.batch_buckets)    # hetu_decode_ids
        if self._suffix_mode:
            bound += (len(self.batch_buckets) * len(self.chunk_buckets)
                      * len(self.ctx_buckets))
        return bound

    @property
    def jit_compiles(self):
        """Distinct jit signatures dispatched so far (always <=
        :attr:`compile_bound`; the serving test asserts it)."""
        return len(self._signatures)

    def health(self):
        """(healthy, reason) under the configured SLOs — the same probe
        contract as ``ServingHTTPServer.health`` / ``/healthz``, so the
        replica router treats engines and HTTP replicas uniformly."""
        return self.slo.health()

    def inflight_requests(self):
        """Live in-flight table (``GET /v1/requests`` and the
        crash-dump ``requests_rank<r>.json``): one row per waiting or
        running request — id, phase (waiting / preempted / running /
        replay), tokens done vs budget, KV blocks held, preemption
        count, age. Works with telemetry disabled."""
        now = time.perf_counter_ns()
        with self._cond:
            snap = [(s, "waiting" if s.preempts == 0 else "preempted")
                    for s in self._waiting]
            snap += [(s, "replay" if s.replaying() else "running")
                     for s in self._running]
        tables = self.cache.tables
        return [{"request_id": s.rid,
                 "phase": phase,
                 "tokens_done": len(s.generated),
                 "tokens_budget": s.max_new,
                 "kv_blocks": len(tables.get(s.id, ())),
                 "cached_tokens": s.cached_tokens,
                 "preempts": s.preempts,
                 "age_ms": round((now - s.t_submit_ns) / 1e6, 3)}
                for s, phase in snap]

    def stats(self):
        """One engine snapshot for ``GET /stats``: queue depths, KV
        pressure, HT901 compile accounting, SLO verdict."""
        with self._cond:
            running, waiting = len(self._running), len(self._waiting)
        healthy, reason = self.health()
        out = {"name": self.name,
               "kind": "ContinuousBatchingEngine",
               "running": running,
               "waiting": waiting,
               "max_batch_size": self.max_batch_size,
               "admission": self.admission,
               "reserve": self.reserve,
               "kv_blocks": self.cache.num_blocks,
               "kv_blocks_used": self.cache.used_blocks,
               "kv_hbm_utilization": round(self.cache.utilization, 4),
               "kv_hbm_bytes": self.cache.kv_bytes(),
               "state_slots": self.cache.state_slots,
               "state_slots_used": self.cache.state_slots_used,
               "state_hbm_bytes": self.cache.state_bytes(),
               "window_blocks": self.cache.window_blocks,
               "window_blocks_used": self.cache.window_blocks_used,
               "window_hbm_bytes": self.cache.window_bytes(),
               "jit_compiles": self.jit_compiles,
               "compile_bound": self.compile_bound,
               "program_leaves": self.program_leaves,
               "decode_steps": self.decode_steps,
               "decode_device_pick_steps": self.decode_device_pick_steps,
               "decode_ahead_steps": self.decode_ahead_steps,
               "healthy": healthy,
               "health_reason": reason}
        out["prefix_cache"] = self.prefix_cache
        out["prefill_chunk"] = self.prefill_chunk
        # closed laps only (at most one leaf span behind), cumulative
        # since the engine was made: a scraper differences them
        out["phase_ms"] = dict(
            zip(ENGINE_PHASES, (ns / 1e6 for ns in self._phase_ns)),
            stalled=self._stalled_ns / 1e6)
        # list() first: the scheduler thread appends while this reads
        out["request_account"] = _lifecycle.summarize_accounts(
            list(self._accounts))
        for kind, vec in self._model_counters.items():
            out.update(self._named_counters(kind, vec))
        if self.prefix_cache:
            # utilization above counts only sequence-referenced blocks;
            # the cached-unreferenced remainder is reclaimable HBM
            out["kv_blocks_cached"] = self.cache.cached_blocks
            out["kv_hbm_utilization_cached"] = round(
                self.cache.cached_utilization, 4)
            out["serve_prefix_hit_rate"] = round(
                self.cache.prefix.hit_rate(), 4)
            out["serve_cow_copies"] = self.cache.cow_copies
            out["serve_prefix_evictions"] = self.cache.prefix.evictions
        return out

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens, temperature=0.0, seed=0,
               request_id=None):
        """Enqueue one request; returns a Future resolving to the
        generated tokens (1-D int32, length ``max_new_tokens``).

        ``request_id`` is the end-to-end tracing id (minted here when
        the caller — HTTP ingress, router — didn't supply one); every
        lifecycle span, in-flight table row, and flight-ring event for
        this request carries it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        if p < 1:
            raise ValueError("submit() needs at least one prompt token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if p + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt {p} + {max_new_tokens} new tokens exceeds the "
                f"engine's max_len {self.max_len}")
        if not self.cache.fits_at_all(p + int(max_new_tokens)):
            # no amount of queueing serves this: the pool is too small
            raise KVCacheExhausted(
                f"request of {p}+{max_new_tokens} tokens needs "
                f"{self.cache.allocator.blocks_for_tokens(p + int(max_new_tokens))} "
                f"blocks; the pool has {self.cache.num_blocks}")
        tel = self.telemetry
        rid = str(request_id) if request_id is not None \
            else mint_request_id()
        seq = _Seq(next(self._ids), prompt, max_new_tokens, temperature,
                   seed, rid)
        tel.flight_record("serve", "submit", tag=rid)
        with self._cond:
            if self._closed:
                raise RuntimeError("engine closed")
            if len(self._waiting) >= self.max_queue:
                raise EngineOverloaded(
                    f"waiting queue full ({self.max_queue} requests)")
            self._waiting.append(seq)
            self._cond.notify()
        return seq.future

    # ------------------------------------------------------------------
    # the phase clock
    def _lap(self, phase):
        """Close the lap: the time since the last one goes to the phase
        the thread was in, and it is in ``phase`` from here."""
        now = _clock()
        self._phase_ns[self._phase] += now - self._lap_ns
        self._lap_ns = now
        self._phase = phase

    def _leaf(self, name, phase, **attrs):
        """Open the leaf span ``name`` (``with self._leaf(...):``) and
        put the thread in ``phase``. The span's end closes no lap: what
        follows it up to the next leaf is the same phase."""
        self._lap(phase)
        return self.telemetry.span(name, **attrs)

    def _reading(self):
        """The clock now, as a request's mark wants it: ``(t_ns,
        stalled_ns, decode_device_ns)`` with an open stall counted up
        to now (no mark is taken inside a decode sync)."""
        now = _clock()
        stalled = self._stalled_ns
        if self._stall_t0:
            stalled += now - self._stall_t0
        return now, stalled, self._phase_ns[_DECODE_DEVICE]

    def _prefill(self, prefilled, admitted):
        """One step's prefill and the finish after it (a prefill gives
        token 0, which may be a request's last; without one nothing was
        produced since the finish that ended the previous step)."""
        if self._suffix_mode:
            self._prefill_suffix_step(prefilled)
        else:
            self._prefill_admitted(admitted)
        self._finish_done()

    # ------------------------------------------------------------------
    def step(self):
        """One scheduler iteration (admit -> prefill -> decode ->
        finish); returns the number of sequences still running. It may
        return with ONE decode program dispatched and not read (see
        :meth:`_decode_once`): its rows are still running and their
        last token is not in ``generated`` yet, so a caller that drives
        ``step()`` itself keeps calling it until its Futures are done;
        the next ``step()`` reads it, ``close()`` drops it with its
        rows."""
        tel = self.telemetry
        t0 = time.perf_counter()
        with self._leaf("serve.admit", _ADMIT), self._cond:
            admitted = self._admit_locked()
        if not admitted and not self._running:
            return 0
        width = len(self._running)
        with tel.span("step", subgraph="serving_engine"):
            # chunked/prefix prefill: EVERY still-prefilling sequence
            # (not just this step's admissions) computes one chunk, then
            # the running batch decodes — long cold prompts interleave
            # with decode instead of stalling it
            prefilled = [s for s in self._running if s.prefilling()] \
                if self._suffix_mode else admitted
            if prefilled:
                # the rows change: what is in flight is read first
                self._read_flight()
                # the rows that would decode now if no prompt had come:
                # every running row that is not prefilling has tokens
                # left (one that has none was retired by the finish
                # that followed its last step)
                rows = len(self._running) - len(prefilled)
                if not rows:
                    self._prefill(prefilled, admitted)
                else:
                    # the stall: the parent span, the overlay's total
                    # and, with telemetry on, who was prefilled
                    with tel.span("serve.stall", rows=rows,
                                  admitted=len(prefilled)):
                        self._stall_t0 = s0 = _clock()
                        try:
                            self._prefill(prefilled, admitted)
                        finally:
                            s1 = _clock()
                            self._stalled_ns += s1 - s0
                            self._stall_t0 = 0
                            # a row is not stalled by the stall its own
                            # prompt ran in: the rest of it was its own
                            # sample and finish
                            for s in self._born:
                                s.tl.skip_stall(self._stalled_ns)
                            self._born.clear()
                            if tel.enabled:
                                self._stalls.append((s0, s1, tuple(
                                    s.rid for s in prefilled)))
            if self._running:
                self._decode_once()
                self._finish_done()
        if tel.enabled:
            tel.observe(f"{self.name}_step_ms",
                        (time.perf_counter() - t0) * 1e3)
            tel.observe(f"{self.name}_batch_width", width)
        return len(self._running)

    def _can_admit_locked(self, seq, reserve_tokens):
        """Block check for one admission. Without a prefix cache this is
        plain free-list arithmetic; with one, the request is charged
        only its non-cached remainder plus the copy-on-write spares its
        writes into shared blocks will need, against free + evictable
        blocks (matched blocks excluded — sharing them un-LRUs them
        before any eviction could touch them)."""
        if not self.prefix_cache:
            return self.cache.can_admit(reserve_tokens)
        need = self.cache.admit_blocks_needed(seq.prompt, reserve_tokens)
        matched, _ = self.cache.match_prefix(seq.prompt)
        evictable = max(0, self.cache.cached_blocks - len(matched))
        return need <= self.cache.allocator.available + evictable

    def _admit_locked(self):
        admitted = []
        while self._waiting and \
                len(self._running) + len(admitted) < self.max_batch_size:
            seq = self._waiting[0]
            p = seq.prompt.shape[0]
            reserve_tokens = p + (seq.max_new
                                  if self.reserve == "full" else 0)
            if not self._can_admit_locked(seq, reserve_tokens):
                if self.admission == "reject":
                    self._waiting.popleft()
                    seq.future.set_exception(EngineOverloaded(
                        f"KV admission rejected request: "
                        f"{self.cache.allocator.blocks_for_tokens(reserve_tokens)} "
                        f"block(s) needed, "
                        f"{self.cache.allocator.available} free"))
                    continue
                # queue policy: the FIFO head waits for blocks — later
                # arrivals never jump it (no starvation)
                break
            self._waiting.popleft()
            if self.prefix_cache:
                _, cached = self.cache.add_seq_prefix(
                    seq.id, reserve_tokens, seq.prompt)
                seq.cached_tokens = cached
                seq.prefill_pos = cached
                seq.n_written = cached   # cached rows are resident
                if cached and self.telemetry.enabled:
                    self.telemetry.inc(
                        f"{self.name}_prefill_cached_tokens", cached)
            else:
                self.cache.add_seq(seq.id, reserve_tokens)
                seq.cached_tokens = 0
                seq.prefill_pos = 0
            admitted.append(seq)
        self._running.extend(admitted)
        if admitted:
            # the queue ends here; a request that comes back from a
            # preemption stays in replay unless it had no token to lose
            reading = self._reading()
            for s in admitted:
                if not s.tokens_lost:
                    s.tl.mark(reading, "prefill")
                    if not s.preempts:
                        s.tl.cached_tokens = s.cached_tokens
                    s.tl.computed_tokens += \
                        s.prompt.shape[0] - s.cached_tokens
                self.telemetry.flight_record("serve", "admit", tag=s.rid)
        return admitted

    # ------------------------------------------------------------------
    def _prefill_token_cap(self, budget):
        """Prompt tokens one prefill program may hold: what the HBM
        budget (resolved as the pool's is) leaves beside the parameters,
        the pool and the headroom, over the bytes of temporaries the
        model says a prompt token costs. ``None`` where no budget
        resolves (a CPU harness): no cap."""
        from ..analysis.memory import resolve_budget
        budget = resolve_budget(budget)
        if budget is None:
            return None
        spare = (int(budget * (1.0 - _BUDGET_HEADROOM))
                 - self.model.param_bytes() - self.cache.hbm_bytes())
        return max(0, spare) // self.model.prefill_bytes_per_token()

    def _prefill_width(self, prompt_bucket):
        """Sequences one prefill program of this prompt bucket holds:
        the widest batch bucket whose tokens stay under the cap (one
        sequence at the least)."""
        if self.prefill_token_cap is None:
            return self.max_batch_size
        fit = [b for b in self.batch_buckets
               if b * prompt_bucket <= self.prefill_token_cap]
        return fit[-1] if fit else self.batch_buckets[0]

    def _state_slots(self, seqs, bb):
        """What a stateful model's programs take behind their other
        arguments: ``(slots [bb] int32,)``, each row's state slot
        (padded lanes: the scratch slot 0); ``()`` for a model of rows
        alone."""
        if not self._stateful:
            return ()
        slots = np.zeros(bb, np.int32)
        slots[:len(seqs)] = [self.cache.slot_of_seq(s.id) for s in seqs]
        return (slots,)

    def _window_prefill_slots(self, group, bb, pb):
        """What a windowed model's prefill takes behind its other
        arguments: ``(slots [bb, pb] int32,)``, each position's slot in
        the window layers' ring — a prompt's last ``window`` rows; the
        rows before them, and padded lanes, name the scratch block.
        ``()`` for a model without a window layer."""
        if not self._windowed:
            return ()
        import jax.numpy as jnp
        slots = np.zeros((bb, pb), np.int32)
        for i, s in enumerate(group):
            p = s.prompt.shape[0]
            first = max(0, p - self.model.window_size)
            slots[i, first:p] = self.cache.window_slot_mapping(
                s.id, first, p)
        return (jnp.asarray(slots),)

    def _window_decode_slots(self, active, bb):
        """What a windowed model's decode step takes behind its other
        arguments: ``(ring [bb, ring x block_size], write [bb])``
        int32, each row's ring as it lies and the ring slot its token's
        row goes to (padded lanes: the scratch block)."""
        if not self._windowed:
            return ()
        ring = np.zeros((bb, self.cache.ring * self.cache.block_size),
                        np.int32)
        write = np.zeros(bb, np.int32)
        ring[:len(active)] = self.cache.ring_slots([s.id for s in active])
        write[:len(active)] = [self.cache.window_slot_of(s.id, s.n_written)
                               for s in active]
        return ring, write

    def _named_counters(self, kind, vec):
        """``{"<kind>_<counter>": value}`` of one counter vector."""
        names = self.model.counter_names
        out = {f"{kind}_{n}": int(v) for n, v in zip(names, vec)}
        if self.model.vector_counter:
            out[f"{kind}_{self.model.vector_counter[0]}"] = \
                [int(v) for v in vec[len(names):]]
        return out

    def _count(self, kind, counted, t0, t1):
        """Take apart what one program returned beside its tokens or
        logits (``None``: the model returns nothing): its device-side
        counters are added to the engine's and, with telemetry on, to
        its ``{name}_*`` counters, and the program itself, with its
        host times and its own counts, goes to :attr:`program_log`.
        Returns the batch rows' records ``[bb, row_record_width]``
        (``None`` for a model without)."""
        if counted is None:
            return None
        counted = np.asarray(counted)
        width = len(self._model_counters[kind])
        records = counted[width:].reshape(-1, self.model.row_record_width) \
            if self.model.row_record_width else None
        counted = counted[:width].astype(np.int64)
        self._model_counters[kind] += counted
        tel = self.telemetry
        if tel.enabled:
            named = self._named_counters(kind, counted)
            for name, value in named.items():
                if not isinstance(value, list):
                    tel.inc(f"{self.name}_{name}", value)
            row = dict(named, kind=kind, t0_ns=t0, t1_ns=t1)
            if self._stateful:
                row.update(state_slots=self.cache.state_slots,
                           state_slots_used=self.cache.state_slots_used)
            if self._windowed:
                row.update(
                    window_blocks=self.cache.window_blocks,
                    window_blocks_used=self.cache.window_blocks_used,
                    window_hbm_bytes=self.cache.window_bytes())
            self.program_log.append(row)
        return records

    def warm_up(self, prompt_len, max_new):
        """Compile and run once, on the scratch block, every program
        that requests with prompts of ``prompt_len = (shortest,
        longest)`` tokens and up to ``max_new`` new ones can reach:
        each (batch, prompt) prefill bucket the token cap admits, each
        (batch, context) greedy-decode bucket (for a model with
        counters also the slice that hands a step's ids to the next, a
        batch bucket) and, with a prefix cache or chunked prefill, each
        (batch, chunk, context) suffix-prefill bucket — with the small
        host reads the scheduler makes on their results. Call it before
        the first ``submit``: it uses the pools and must not run beside
        the scheduler's own steps. Afterwards
        ``jit_compiles`` does not rise for such traffic (the sampled
        route's ``hetu_paged_decode_logits`` aside, which compiles on
        first use). Returns the buckets it ran."""
        import jax.numpy as jnp
        lo, hi = (int(prompt_len), int(prompt_len)) \
            if np.isscalar(prompt_len) else map(int, prompt_len)
        if not 1 <= lo <= hi or hi + int(max_new) > self.max_len:
            raise ValueError(
                f"prompts of {lo}..{hi} tokens with {max_new} new ones "
                f"do not fit max_len {self.max_len}")
        prompt_buckets = _reachable(self.prompt_buckets, lo, hi)
        ctx_buckets = _reachable(self.ctx_buckets, lo + 1,
                                 hi + int(max_new))
        ran = {"prefill": [], "decode": [], "decode_ids": [],
               "suffix_prefill": []}

        def zeros(*shape):
            return np.zeros(shape, np.int32)

        for bb in self.batch_buckets:
            sizes = range(bb // 2 + 1, bb + 1)   # groups that snap to bb
            for pb in () if self._suffix_mode else prompt_buckets:
                if bb > self._prefill_width(pb):
                    continue
                if self.model.prefill_last_row:
                    (logits, _), self.cache.pools = self._dispatch(
                        ("prefill", bb, pb), self._prefill_fn,
                        self.params, self.cache.pools,
                        jnp.asarray(zeros(bb, pb)),
                        jnp.asarray(zeros(bb, pb)), zeros(bb),
                        *self._state_slots((), bb),
                        *self._window_prefill_slots((), bb, pb))
                    np.asarray(logits)
                else:
                    logits, self.cache.pools = self._dispatch(
                        ("prefill", bb, pb), self._prefill_fn,
                        self.params, self.cache.pools,
                        jnp.asarray(zeros(bb, pb)),
                        jnp.asarray(zeros(bb, pb)))
                    for n in sizes:     # the last-row gather of a group
                        np.asarray(logits[jnp.arange(n),
                                          jnp.asarray([pb - 1] * n)])
                del logits
                ran["prefill"].append((bb, pb))
            for cb in ctx_buckets:
                out, self.cache.pools = self._dispatch(
                    ("decode", bb, cb), self._step_fn, self.params,
                    self.cache.pools, zeros(bb), zeros(bb), zeros(bb, cb),
                    zeros(bb), *self._state_slots((), bb),
                    *self._window_decode_slots((), bb))
                if self.model.counter_names and cb == ctx_buckets[0]:
                    # what hands a step's ids to the step dispatched
                    # ahead of their host read
                    self._dispatch(("decode_ids", bb), self._ids_fn,
                                   out, bb)
                    ran["decode_ids"].append((bb,))
                np.asarray(out)
                ran["decode"].append((bb, cb))
            if not self._suffix_mode:
                continue
            chunk = min(self.prefill_chunk or self.max_len, hi)
            for cw in _reachable(self.chunk_buckets, 1, chunk):
                for sb in _reachable(self.ctx_buckets, 1, hi):
                    logits, self.cache.pools = self._dispatch(
                        ("sprefill", bb, cw, sb), self._sprefill_fn,
                        self.params, self.cache.pools,
                        jnp.asarray(zeros(bb, cw)),
                        jnp.asarray(zeros(bb)),
                        jnp.asarray(zeros(bb, sb)),
                        jnp.asarray(zeros(bb, cw)),
                        *self._state_slots((), bb),
                        *((zeros(bb),) if self._stateful else ()))
                    if self.model.counter_names:
                        logits, _ = logits
                    for n in sizes:
                        np.asarray(logits[jnp.arange(n)]
                                   if self._stateful else
                                   logits[jnp.arange(n),
                                          jnp.asarray([cw - 1] * n)])
                    del logits
                    ran["suffix_prefill"].append((bb, cw, sb))
        return ran

    def _dispatch(self, key, fn, *args):
        """Run one jit program, accounting compiles the way the
        executor does (HT901's runtime half): first sighting of a
        signature key incs ``jit_compiles`` under a ``jit_compile``
        span, steady-state dispatches ride ``device_dispatch``."""
        tel = self.telemetry
        if key not in self._signatures:
            self._signatures.add(key)   # lock-ok: HT601 warm_up is the one caller off the scheduler's thread, and runs before the first submit
            from jax.tree_util import tree_leaves
            attrs = {"subgraph": "serving_engine", "shape_key": str(key),
                     "leaves_in": len(tree_leaves(args))}
            t0 = tel.clock()
            # telemetry on or off, a profile says which step compiled
            with _telemetry.annotate("jit_compile", **attrs):
                out = fn(*args)
            attrs["leaves_out"] = len(tree_leaves(out))
            if key[0] == "decode" and self.program_leaves is None:
                self.program_leaves = {   # lock-ok: HT601 one reference assigned once, by warm_up before the first submit or else by the scheduler's thread
                    "in": attrs["leaves_in"], "out": attrs["leaves_out"]}
            tel.complete("jit_compile", t0, tel.clock(), attrs)
            tel.inc("jit_compiles")
            return out
        with tel.span("device_dispatch", subgraph="serving_engine"):
            return fn(*args)

    def _prefill_admitted(self, admitted):
        import jax.numpy as jnp
        tel = self.telemetry
        with self._leaf("serve.prefill.build", _PREFILL_HOST):
            groups = {}
            for s in admitted:
                pb = next_bucket(s.prompt.shape[0], self.prompt_buckets)
                groups.setdefault(pb, []).append(s)
        last_row = self.model.prefill_last_row
        # one program holds at most prefill_token_cap prompt tokens: a
        # bucket's group goes in as many programs as that takes
        split = []
        for pb, group in sorted(groups.items()):
            width = self._prefill_width(pb)
            split += [(pb, group[i:i + width])
                      for i in range(0, len(group), width)]
        for pb, group in split:
            with self._leaf("serve.prefill.build", _PREFILL_HOST):
                bb = next_bucket(len(group), self.batch_buckets)
                ids = np.zeros((bb, pb), np.int32)
                slots = np.zeros((bb, pb), np.int32)   # 0 = scratch block
                for i, s in enumerate(group):
                    p = s.prompt.shape[0]
                    ids[i, :p] = s.prompt
                    ids[i, p:] = s.prompt[-1]   # edge pad stays in-vocab
                    slots[i, :p] = self.cache.slot_mapping(s.id, 0, p)
                ids, slots = jnp.asarray(ids), jnp.asarray(slots)
                window_slots = self._window_prefill_slots(group, bb, pb)
                if last_row:
                    last_pos = np.zeros(bb, np.int32)
                    last_pos[:len(group)] = [s.prompt.shape[0] - 1
                                             for s in group]
                else:
                    rows = jnp.arange(len(group))
                    last_pos = jnp.asarray([s.prompt.shape[0] - 1
                                            for s in group])
            with self._leaf("serve.prefill.device", _PREFILL_HOST,
                            batch_bucket=bb, prompt_bucket=pb):
                t0 = _clock() if tel.enabled else 0
                if last_row:
                    # the program takes each prompt's last row through
                    # the head itself: [bb, V] leaves it
                    (logits, counted), pools = self._dispatch(
                        ("prefill", bb, pb), self._prefill_fn,
                        self.params, self.cache.pools, ids, slots,
                        last_pos, *self._state_slots(group, bb),
                        *window_slots)
                else:
                    logits, pools = self._dispatch(
                        ("prefill", bb, pb), self._prefill_fn,
                        self.params, self.cache.pools, ids, slots)
                    logits, counted = logits[rows, last_pos], None
                self.cache.pools = pools
                # t1, the end of the host sync, is the first-token time
                last, first = self._prefill_sync(logits)
                records = self._count("prefill", counted, t0, first[0])
            with self._leaf("serve.prefill.sample", _PREFILL_HOST):
                for i, s in enumerate(group):
                    p = s.prompt.shape[0]
                    tok = _choose_token(last[i], s.temperature, s.seed, 0)
                    s.generated.append(tok)
                    if records is not None:
                        s.records.append(records[i])
                    s.pending = tok
                    s.n_written = p
                    s.prefill_pos = p
                    self._first_token(s, first)
                if tel.enabled:
                    real = sum(s.prompt.shape[0] for s in group)
                    tel.inc(f"{self.name}_prefill_tokens", real)
                    tel.inc(f"{self.name}_prefill_pad_tokens",
                            bb * pb - real)
                    tel.inc(f"{self.name}_tokens", len(group))

    def _prefill_sync(self, logits):
        """The host's wait for a prefill's last rows: ``(rows, the
        clock's reading when they had come)``."""
        with self._leaf("serve.prefill.sync", _PREFILL_DEVICE):
            last = np.asarray(logits)
        self._lap(_PREFILL_HOST)
        return last, self._reading()

    def _first_token(self, seq, reading):
        """Token 0 of ``seq`` exists as of ``reading``, the end of its
        prefill's host sync: the TTFT point (a replay after a
        preemption does not move it) and the end of its prefill, or of
        a replay that had only this token to earn back."""
        if seq.t_first_token_ns is None:
            seq.t_first_token_ns = seq.future.t_first_token_ns = reading[0]
        self._runs_if_caught_up(seq, reading)

    def _runs_if_caught_up(self, seq, reading):
        """A prefilling request, or a replaying one that holds again as
        many tokens as its preemption threw away, runs from here."""
        state = seq.tl.state
        if state == "prefill" or (state == "replay" and
                                  len(seq.generated) >= seq.tokens_lost):
            seq.tl.mark(reading, "run")
            if self._stall_t0:
                self._born.append(seq)

    def _cow_or_preempt(self, s, start, stop):
        """Copy-on-write the blocks positions ``[start, stop)`` touch
        before ``s`` writes them, preempting the youngest running
        sequence when the copy can't be allocated (same victim policy
        as lazy-reserve exhaustion; the victim replays exactly).
        Returns False when ``s`` itself was the last resort victim."""
        while True:
            try:
                self.cache.ensure_writable(s.id, start, stop)
                return True
            except KVCacheExhausted:
                victim = self._running[-1]
                self._preempt(victim)
                if victim is s:
                    return False

    def _prefill_suffix_step(self, prefilling):
        """One chunk of prompt prefill per still-prefilling sequence:
        the prefix-cache/chunked path (``gpt_paged_suffix_prefill``).
        Each sequence computes ``min(prefill_chunk, remaining)`` tokens
        from its ``prefill_pos`` — the first non-cached position on a
        fresh admission — grouped per chunk bucket; the final chunk
        samples token 0 and publishes the prompt's blocks to the prefix
        cache."""
        import jax.numpy as jnp
        tel = self.telemetry
        chunk = self.prefill_chunk or self.max_len
        with self._leaf("serve.prefill.build", _PREFILL_HOST):
            groups = {}
            for s in prefilling:
                if s not in self._running:
                    continue    # preempted by an earlier group's CoW
                w = min(chunk, s.prompt.shape[0] - s.prefill_pos)
                # shared blocks this chunk writes into copy FIRST, so
                # the write slots below point at private storage
                if not self._cow_or_preempt(s, s.prefill_pos,
                                            s.prefill_pos + w):
                    continue
                cw = next_bucket(w, self.chunk_buckets)
                groups.setdefault(cw, []).append((s, w))
        for cw, group in sorted(groups.items()):
            with self._leaf("serve.prefill.build", _PREFILL_HOST):
                group = [(s, w) for s, w in group if s in self._running]
                if not group:
                    continue
                bb = next_bucket(len(group), self.batch_buckets)
                sb = next_bucket(max(s.prefill_pos + w for s, w in group),
                                 self.ctx_buckets)
                ids = np.zeros((bb, cw), np.int32)
                starts = np.zeros(bb, np.int32)
                write_slots = np.zeros((bb, cw), np.int32)  # 0 = scratch
                slot_grid = np.zeros((bb, sb), np.int32)
                slot_grid[:len(group)] = self.cache.gather_slots(
                    [s.id for s, _ in group], sb)
                for i, (s, w) in enumerate(group):
                    pos = s.prefill_pos
                    ids[i, :w] = s.prompt[pos:pos + w]
                    ids[i, w:] = s.prompt[pos + w - 1]  # edge pad in-vocab
                    starts[i] = pos
                    write_slots[i, :w] = self.cache.slot_mapping(
                        s.id, pos, pos + w)
                finishing = [(i, s, w) for i, (s, w) in enumerate(group)
                             if s.prefill_pos + w >= s.prompt.shape[0]]
                state_args = ()
                if self._stateful:
                    # each row's slot and its count of real tokens: the
                    # program continues the slot's state over them and
                    # puts each row's LAST real position through the head
                    lens = np.zeros(bb, np.int32)
                    lens[:len(group)] = [w for _, w in group]
                    state_args = self._state_slots(
                        [s for s, _ in group], bb) + (lens,)
                ids, starts = jnp.asarray(ids), jnp.asarray(starts)
                slot_grid = jnp.asarray(slot_grid)
                write_slots = jnp.asarray(write_slots)
                if finishing:
                    rows = jnp.asarray([i for i, _, _ in finishing])
                    last_pos = jnp.asarray([w - 1 for _, _, w in finishing])
            with self._leaf("serve.prefill.device", _PREFILL_HOST,
                            batch_bucket=bb, prompt_bucket=cw,
                            ctx_bucket=sb):
                t0 = _clock() if tel.enabled else 0
                logits, pools = self._dispatch(
                    ("sprefill", bb, cw, sb), self._sprefill_fn,
                    self.params, self.cache.pools, ids, starts, slot_grid,
                    write_slots, *state_args)
                self.cache.pools = pools
                counted = None
                if self.model.counter_names:
                    logits, counted = logits
                # a chunk that ends no prompt is not waited for
                last, first = self._prefill_sync(
                    logits[rows] if self._stateful
                    else logits[rows, last_pos]) \
                    if finishing else (None, self._reading())
                t1 = first[0]
                records = self._count("prefill", counted, t0, t1)
            with self._leaf("serve.prefill.sample", _PREFILL_HOST):
                for j, (i, s, w) in enumerate(finishing):
                    tok = _choose_token(last[j], s.temperature, s.seed, 0)
                    s.generated.append(tok)
                    if records is not None:
                        s.records.append(records[i])
                    s.pending = tok
                cached_resolved = 0
                for i, (s, w) in enumerate(group):
                    first_chunk = s.prefill_pos == s.cached_tokens
                    if first_chunk:
                        cached_resolved += s.cached_tokens
                    s.prefill_pos += w
                    s.n_written = s.prefill_pos
                    if not s.prefilling():
                        # prompt fully resident: publish it for later
                        # hits (the cache freezes these blocks; the
                        # first decode write past the tail
                        # copy-on-writes)
                        self.cache.insert_prefix(s.id, s.prompt)
                        self._first_token(s, first)
                if tel.enabled:
                    computed = sum(w for _, w in group)
                    tel.complete("serve_prefill_chunk", t0, t1,
                                 {"seqs": len(group),
                                  "tokens": int(computed),
                                  "bucket": int(cw),
                                  "cached": int(cached_resolved)})
                    tel.inc(f"{self.name}_prefill_tokens", computed)
                    tel.inc(f"{self.name}_prefill_pad_tokens",
                            bb * cw - computed)
                    tel.inc(f"{self.name}_tokens", len(finishing))

    def _ensure_capacity_lazy(self, active):
        """Lazy-reserve growth: make every active sequence's table
        cover its write position, preempting the youngest running
        sequence on exhaustion. Returns the surviving active list."""
        for s in list(active):
            if s not in self._running:
                continue            # already preempted as a victim
            while s.n_written + 1 > self.cache.capacity_tokens(s.id):
                try:
                    self.cache.extend_seq(s.id, s.n_written + 1)
                except KVCacheExhausted:
                    victim = self._running[-1]
                    self._preempt(victim)
                    if victim is s:
                        break
        return [s for s in active if s in self._running]

    def _preempt(self, victim):
        """Free a sequence's blocks and requeue it at the waiting head;
        recompute reproduces its tokens ((seed, index)-keyed
        sampling). A decode program in flight is read first: the
        victim's last token goes with its blocks like the others."""
        self._read_flight()
        self.cache.free_seq(victim.id)
        lost = len(victim.generated)
        victim.tokens_lost = lost
        victim.generated = []
        victim.records = []
        victim.pending = None
        victim.n_written = 0
        victim.prefill_pos = 0
        victim.cached_tokens = 0
        victim.preempts += 1
        with self._cond:
            self._running.remove(victim)
            self._waiting.appendleft(victim)
        # from here until it holds ``lost`` tokens again it replays
        victim.tl.mark(self._reading(), "replay")
        if self.telemetry.enabled:
            self.telemetry.inc(f"{self.name}_preemptions")
            self.telemetry.instant("serve_preempt",
                                   request_id=victim.rid, tokens=lost)
            self.telemetry.flight_record("serve", "preempt",
                                         tag=victim.rid)

    def _decode_once(self):
        """Dispatch one decode step for every active sequence and read
        what is due. The loop keeps at most ONE program unread: when the
        program in flight holds exactly this step's rows (same
        sequences, same order, so the same batch bucket; all greedy)
        and the build moved no block, this step is dispatched FIRST,
        its ``tokens`` argument the device array the one in flight
        returns, and the one in flight is read SECOND, while the device
        runs this one. Anything else (an admission or a prefill, read
        in :meth:`step`; a preemption, read in :meth:`_preempt`; a
        copy-on-write) finds the program in flight read before this
        step is built from host values. A step that ends a row (known
        by COUNT: there is no stop token) or holds a sampled row is
        read before this returns, so a finish, an admission and the
        host's draw see what the synchronous loop showed them: the
        programs run, their order and their results are the same."""
        tel = self.telemetry
        with self._leaf("serve.decode.build", _DECODE_HOST):
            active = [s for s in self._running
                      if len(s.generated) + s.unread < s.max_new
                      and not s.prefilling()]
            cows = self.cache.cow_copies
            if self.reserve == "lazy":
                active = self._ensure_capacity_lazy(active)
            if self.prefix_cache:
                # the first write past a cached/frozen prompt tail lands
                # in a shared block — copy it before computing write
                # slots (reserve="full" admission pre-charged this block)
                for s in list(active):
                    if s in self._running:
                        self._cow_or_preempt(s, s.n_written,
                                             s.n_written + 1)
                active = [s for s in active if s in self._running]
            if not active:
                return      # and nothing in flight: its rows would go on
            n = len(active)
            bb = next_bucket(n, self.batch_buckets)
            cb = next_bucket(max(s.n_written for s in active) + 1,
                             self.ctx_buckets)
            positions = np.zeros(bb, np.int32)
            write_slots = np.zeros(bb, np.int32)       # 0 = scratch block
            slot_grid = np.zeros((bb, cb), np.int32)
            slot_grid[:n] = self.cache.gather_slots(
                [s.id for s in active], cb)
            for i, s in enumerate(active):
                positions[i] = s.n_written
                write_slots[i] = self.cache.slot_of(s.id, s.n_written)
            # an all-greedy step picks inside the program; a sampled
            # sequence needs its logits on the host (see _choose_token)
            device_pick = not any(s.temperature > 0.0 for s in active)
            # may this step stay unread when this returns?
            stays = device_pick and all(
                len(s.generated) + s.unread + 1 < s.max_new
                for s in active)
            window_slots = self._window_decode_slots(active, bb)
            flight, self._flight = self._flight, None
            ahead = flight is not None and flight.rows == active \
                and cows == self.cache.cow_copies
        if flight is not None and not ahead:
            self._read(flight)
        attrs = {"width": n, "batch_bucket": bb, "ctx_bucket": cb}
        with self._leaf("serve.decode.ahead" if ahead
                        else "serve.decode.device", _DECODE_HOST, **attrs):
            t0 = _clock() if tel.enabled else 0
            if not ahead:
                tokens = np.zeros(bb, np.int32)
                tokens[:n] = [s.pending for s in active]
            elif self.model.counter_names:
                tokens = self._dispatch(("decode_ids", bb), self._ids_fn,
                                        flight.out, bb)
            else:
                tokens = flight.out
            if device_pick:
                key, fn = ("decode", bb, cb), self._step_fn
            else:
                key, fn = ("decode_logits", bb, cb), self._logits_step_fn
            # the numpy arrays go to the device inside the jitted call,
            # with no put of their own
            out, pools = self._dispatch(
                key, fn, self.params, self.cache.pools, tokens,
                positions, slot_grid, write_slots,
                *self._state_slots(active, bb), *window_slots)
            self.cache.pools = pools
            step = _DecodeProgram(active, bb, attrs, device_pick, out, t0)
            self.decode_steps += 1
            self.decode_device_pick_steps += device_pick
            self.decode_ahead_steps += ahead
            for s in active:
                s.n_written += 1
                s.unread += 1
            if tel.enabled:
                tel.inc(f"{self.name}_decode_steps")
                if device_pick:
                    tel.inc(f"{self.name}_decode_device_pick_steps")
                if ahead:
                    tel.inc(f"{self.name}_decode_ahead_steps")
            if stays:
                out.copy_to_host_async()    # lands when the program ends
            elif not ahead:
                # read at once: the span runs from the dispatch through
                # the host sync, as it does in a synchronous loop
                self._sync(step)
        if ahead:
            self._read(flight)
        if stays:
            self._flight = step
        else:
            self._read(step)

    def _sync(self, step):
        """The one host sync of a decode program: ``[bb]`` int32 ids
        (the model's counters and row records behind them, where it has
        any), or ``[bb, V]`` float32 logits on the sampled route."""
        out, counted = step.out, None
        self._lap(_DECODE_DEVICE)       # the host blocks on the result
        if self.model.counter_names:
            if step.device_pick:
                out = np.asarray(out)
                out, counted = out[:step.bb], out[step.bb:]
            else:
                out, counted = out
        step.last = np.asarray(out)
        self._lap(_DECODE_HOST)
        step.out = None
        step.t1 = self._lap_ns
        step.records = self._count("decode", counted, step.t0, step.t1)

    def _read(self, step):
        """The host's half of a dispatched decode program: the sync
        (unless :meth:`_decode_once` made it inside the dispatch's
        span), then each row's token and record."""
        tel = self.telemetry
        if step.last is None:
            with self._leaf("serve.decode.device", _DECODE_HOST,
                            **step.attrs):
                self._sync(step)
        with self._leaf("serve.decode.sample", _DECODE_HOST):
            last = step.last
            if step.device_pick:
                last = last.tolist()        # Python ints, in one call
            for i, s in enumerate(step.rows):
                s.unread -= 1
                tok = last[i] if step.device_pick else _choose_token(
                    last[i], s.temperature, s.seed, len(s.generated))
                s.generated.append(tok)
                s.pending = tok
                if step.records is not None:
                    s.records.append(step.records[i])
                if s.tokens_lost:       # preempted once: replaying?
                    self._runs_if_caught_up(s, self._reading())
            if tel.enabled:
                tel.inc(f"{self.name}_tokens", len(step.rows))

    def _read_flight(self):
        """Read the decode program in flight, if there is one: after
        this the host's values are what a synchronous loop holds."""
        step, self._flight = self._flight, None
        if step is not None:
            self._read(step)

    def _finish_done(self):
        tel = self.telemetry
        with self._leaf("serve.finish", _DECODE_HOST):
            with self._cond:
                done = [s for s in self._running
                        if len(s.generated) >= s.max_new]
                for s in done:
                    self._running.remove(s)
            for s in done:
                self.cache.free_seq(s.id)
                reading = self._reading()
                t_retire = reading[0]
                if self._born and s in self._born:
                    s.tl.skip_stall(reading[1])     # retires in it
                account = s.tl.account_ns(reading)
                self._accounts.append(tuple(account.values()))
                s.future.t_retire_ns = t_retire
                s.future.account = {p: ns / 1e6
                                    for p, ns in account.items()}
                ms = (t_retire - s.t_submit_ns) / 1e6
                ttft_ms = None
                if s.t_first_token_ns is not None:
                    ttft_ms = (s.t_first_token_ns - s.t_submit_ns) / 1e6
                if tel.enabled:
                    _lifecycle.emit_request(
                        tel, s.tl, t_retire, len(s.generated), s.preempts,
                        s.future.account, self._stalls)
                    tel.flight_record("serve", "retire", tag=s.rid)
                    if ttft_ms is not None:
                        tel.observe("serve_ttft_ms", ttft_ms)
                        tel.observe(
                            "serve_tpot_ms",
                            (t_retire - s.t_first_token_ns) / 1e6
                            / max(1, len(s.generated) - 1))
                    tel.observe("serve_queue_wait_ms",
                                s.future.account["queue"])
                    tel.observe("serve_preempts", s.preempts)
                self.slo.note(True, ms, ttft_ms=ttft_ms)
                s.future.token_records = np.stack(
                    s.records[:s.max_new]) if s.records else None
                s.future.set_result(
                    np.asarray(s.generated[:s.max_new], np.int32))

    # ------------------------------------------------------------------
    def _loop(self):
        try:
            while True:
                with self._cond:
                    while not self._closed and not self._waiting \
                            and not self._running:
                        # in slices, a span each: a profile that starts
                        # inside a wait sees its next slice, and the
                        # clock's ``wait`` is never far behind
                        with self._leaf("serve.wait", _WAIT):
                            self._cond.wait(_WAIT_SLICE_S)
                    if self._closed and not self._waiting \
                            and not self._running:
                        return
                    if self._closed:
                        break       # drain what's in flight, then fail
                self.step()
        except BaseException as e:  # noqa: BLE001 — scheduler died
            self._fail_outstanding(
                RuntimeError(f"engine scheduler died: {e!r}"))
            raise
        finally:
            self._lap(_WAIT)    # the clock ends with the thread
        # closed with work outstanding: fail it rather than hang callers
        self._fail_outstanding(RuntimeError("engine closed"))

    def _fail_outstanding(self, exc):
        with self._cond:
            self._closed = True
            leftovers = list(self._waiting) + list(self._running)
            self._waiting.clear()
            self._running.clear()
            self._cond.notify_all()
        self._flight = None     # lock-ok: HT601 the scheduler's thread has ended (close() joined it) or is the caller; its rows fail below
        for s in leftovers:
            self.cache.free_seq(s.id)
            if not s.future.done():
                s.future.set_exception(exc)

    def close(self):
        """Stop the scheduler; outstanding Futures fail with
        "engine closed". Idempotent."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._fail_outstanding(RuntimeError("engine closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
