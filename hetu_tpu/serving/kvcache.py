"""Paged KV cache: fixed-size block pools + a block allocator.

A dense cache preallocates one ``[B, H, S_max, D]`` K/V pair per layer
per batch — every sequence pays for ``S_max`` positions whether it uses
8 or 800, and a new batch means a new allocation. This module is the
vLLM/PagedAttention shape instead:

* **one pooled buffer per layer** — ``[num_blocks, block_size, H*D]``
  for K and V, allocated once and shared by every sequence the engine
  ever serves. A row keeps its heads side by side in the minor
  dimension: with ``[..., H, D]`` minors the TPU tiles ``(H, D)`` to
  ``(8, 128)`` multiples — 2.7x the bytes at 12 heads of 64 — and
  every program that flattened the pool paid a padded pool-sized copy,
  which at a pool sized from ``bytes_limit`` the chip's compiler
  refused outright;
* **per-sequence block tables** — a sequence owns an ordered list of
  block ids; token position ``j`` lives in flat pool slot
  ``table[j // block_size] * block_size + j % block_size``. Sequences
  are contiguous *logically*, scattered *physically*;
* **a refcounted free-list allocator** with deterministic exhaustion
  behavior: ``alloc`` is all-or-nothing and raises
  :class:`KVCacheExhausted` (never partially allocates, never corrupts
  a neighbor's blocks); ``share`` bumps a live block's refcount so
  several sequences (or the prefix cache) can reference one physical
  block; ``free`` decrements and a block rejoins the free list only at
  refcount 0 — underflow / double-free of a shared block raises.
* **a prefix cache** (:class:`PrefixCache`): full prompt blocks key by
  a rolling hash of the token prefix, so a repeated system prompt
  resolves to the already-resident blocks — zero prefill compute, zero
  new blocks — and admission charges only the non-cached suffix.
  Blocks whose last sequence retired stay cached (refcount 1, held by
  the cache) on an LRU list and are evicted only under allocation
  pressure, never eagerly.
* **copy-on-write**: a sequence about to write into a block someone
  else also references (another sequence, or the cache's frozen tail
  entry) copies it first (:meth:`PagedKVCache.ensure_writable`), so
  shared partial tails are read-shared and write-private.

* **state slots** beside the rows, for a model whose layers (some of
  them) carry a fixed-size recurrent state a sequence instead of a row
  a token: one slot a running sequence, taken and given back with its
  blocks (:class:`PagedKVCache` says how a model asks for them).

* **window rings**, for a layer whose attention reads only the last
  ``window`` positions: such a layer keeps a pool of its own, and a
  sequence a second, SHORT table into it, at most ``ceil(window /
  block_size) + 1`` blocks used as a ring (position ``j`` lies in ring
  block ``(j // block_size) % ring``), so what a sequence holds there
  is bounded by ``window + one block`` whatever its length
  (:class:`PagedKVCache` says how a model asks for them).

**Physical block 0 is the scratch block.** Padded batch lanes (the
bucketing that keeps jit signatures bounded) write their garbage K/V
rows to slot ``0..block_size-1`` and gather from them behind a length
mask; the allocator never hands block 0 to a real sequence, so padding
can never corrupt live cache rows.

Sizing rides the HT4xx machinery (``analysis/memory.py``): with
``num_blocks=None`` the pool sizes itself against the resolved HBM
budget (explicit argument > ``HETU_HBM_BUDGET`` > the device's
advertised ``bytes_limit``) minus the model's parameter bytes and a
headroom fraction. On a CPU harness with no budget resolvable, pass
``num_blocks`` explicitly.
"""
from __future__ import annotations

import collections
import hashlib
import math

import numpy as np

from ..models.gpt import gpt_param_bytes  # noqa: F401  its older home

__all__ = ["KVCacheExhausted", "BlockAllocator", "PagedKVCache",
           "PrefixCache", "kv_block_bytes", "state_slot_bytes",
           "gpt_param_bytes", "blocks_for_budget", "ring_blocks",
           "DEFAULT_BLOCK_SIZE"]

DEFAULT_BLOCK_SIZE = 16

# fraction of the resolved HBM budget kept free for activations /
# compiler temps when auto-sizing the pool (the static HT4xx estimate
# is deliberately pessimistic the other way; serving steps are small)
_BUDGET_HEADROOM = 0.10


class KVCacheExhausted(RuntimeError):
    """Raised by :meth:`BlockAllocator.alloc` when the free list cannot
    cover a request. All-or-nothing: no blocks were allocated. The
    engine's admission plane turns this into queueing/rejection; seeing
    it escape means a caller bypassed admission control."""


def _pool_kinds(model):
    """What each entry of the cache's ``pools`` is, for a serving
    model: ``"rows"`` (a layer's paged pools, a row a token),
    ``"window"`` (a layer's paged pools that keep a sequence's last
    ``model.window_size`` rows, a ring) or ``"state"`` (fixed-size
    slots, one a sequence). A model without ``pool_kinds`` has rows in
    every layer."""
    return getattr(model, "pool_kinds", None) \
        or ("rows",) * model.num_cache_layers


def _itemsize(dtype):
    import jax.numpy as jnp     # numpy alone does not know bfloat16
    return jnp.dtype(dtype).itemsize


def kv_block_bytes(config, block_size, kind="rows"):
    """HBM bytes one cache block costs across the layers that HAVE
    rows, by the row layout of the configuration's serving model (a K
    and a V row of ``hidden`` float32 for GPT; one ``latent + rope`` row
    in the model's dtype for a latent-attention model; a ``k`` and a
    ``v`` row of the key/value heads alone in a hybrid's attention
    layers, and nothing in its state-space layers). ``kind="window"``:
    a block of the window layers' own pools."""
    model = config.serving_model()
    row = sum(width * _itemsize(dtype)
              for _, width, dtype in model.cache_layout())
    return _pool_kinds(model).count(kind) * int(block_size) * row


def ring_blocks(config, block_size):
    """Blocks a sequence's table into the window layers' pools holds
    at most, ``ceil(window / block_size) + 1`` (0 for a model without a
    window layer): the window's rows and the block being written."""
    model = config.serving_model()
    if "window" not in _pool_kinds(model):
        return 0
    return -(-int(model.window_size) // int(block_size)) + 1


def state_slot_bytes(config):
    """HBM bytes one sequence's recurrent state costs, whatever its
    length (0 for a model of rows alone)."""
    model = config.serving_model()
    entries = _pool_kinds(model).count("state")
    if not entries:
        return 0
    return entries * sum(int(np.prod(shape)) * _itemsize(dtype)
                         for _, shape, dtype in model.state_layout())


def blocks_for_budget(config, block_size=DEFAULT_BLOCK_SIZE, budget=None,
                      headroom=_BUDGET_HEADROOM, state_slots=0,
                      window_blocks=0):
    """KV blocks the resolved HBM budget affords after the model's
    parameters, ``state_slots`` sequences' recurrent state (and the
    scratch slot's), the window layers' pools of ``window_blocks``
    blocks (and their scratch block) and a headroom fraction. Returns
    ``None`` when no budget resolves (CPU harness without
    ``HETU_HBM_BUDGET``); raises when a budget resolves but can't fit
    even two blocks."""
    from ..analysis.memory import fmt_bytes, resolve_budget
    budget = resolve_budget(budget)
    if budget is None:
        return None
    param_bytes = config.serving_model().param_bytes()
    avail = int(budget * (1.0 - headroom)) - param_bytes \
        - state_slot_bytes(config) * (int(state_slots) + 1)
    window = kv_block_bytes(config, block_size, "window")
    if window:
        avail -= window * (int(window_blocks) + 1)
    nb = avail // kv_block_bytes(config, block_size)
    if nb < 2:
        raise ValueError(
            f"HBM budget {fmt_bytes(budget)} leaves room for {nb} KV "
            f"block(s) after {fmt_bytes(param_bytes)} of "
            f"parameters — the model doesn't fit a paged cache here")
    return int(nb)


class BlockAllocator:
    """Refcounted free-list over ``num_blocks`` usable block ids.

    ``alloc(n)`` is all-or-nothing (raises :class:`KVCacheExhausted`
    listing need vs. free, allocating nothing) and hands blocks out at
    refcount 1. ``share(blocks)`` bumps a live block's refcount — how
    the prefix cache and prefix-hit sequences reference one physical
    block. ``free(blocks)`` decrements; a block rejoins the free list
    only when its refcount reaches 0, and freeing a dead block (or
    decrementing past zero) raises ``ValueError`` without mutating
    anything. Blocks hand out lowest-id-first and freed blocks rejoin
    in sorted order, so identical alloc/share/free traces produce
    identical tables — exhaustion and reuse are deterministic, not
    load-dependent."""

    def __init__(self, num_blocks, block_size, first_id=0):
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._first = int(first_id)
        self._free = collections.deque(
            range(self._first, self._first + self.num_blocks))
        self._ref = {}          # block id -> refcount (live blocks only)

    @property
    def available(self):
        return len(self._free)

    @property
    def used(self):
        return len(self._ref)

    def refcount(self, block):
        """Live refcount of one block (0 when free/unknown)."""
        return self._ref.get(block, 0)

    def blocks_for_tokens(self, ntokens):
        return max(1, math.ceil(int(ntokens) / self.block_size))

    def alloc(self, n):
        n = int(n)
        if n > len(self._free):
            raise KVCacheExhausted(
                f"KV cache exhausted: need {n} block(s), "
                f"{len(self._free)} free of {self.num_blocks}")
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def share(self, blocks):
        """Add one reference to each (live) block — all-or-nothing:
        sharing a free/unknown block raises without mutating."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"share of non-live KV block {b}")
        for b in blocks:
            self._ref[b] += 1

    def free(self, blocks):
        """Drop one reference per listed block; blocks reaching
        refcount 0 rejoin the free list. Validated before any mutation:
        releasing more references than a block holds (double free /
        refcount underflow) raises ``ValueError`` and nothing changes.
        Returns the blocks that actually went free."""
        need = collections.Counter(blocks)
        for b, n in need.items():
            have = self._ref.get(b, 0)
            if n > have:
                raise ValueError(
                    f"double free of KV block {b}: releasing {n} "
                    f"reference(s) but it holds {have}")
        released = []
        for b, n in need.items():
            left = self._ref[b] - n
            if left == 0:
                del self._ref[b]
                released.append(b)
            else:
                self._ref[b] = left
        if released:
            # sorted re-insertion keeps reuse deterministic regardless
            # of the order sequences finished in
            self._free = collections.deque(
                sorted(list(self._free) + released))
        return released


def _chain_key(prev, tokens):
    """One rolling-hash step: digest of (previous chain key, this
    block's token ids). Position sensitivity is free — a chunk's key
    encodes every token before it, so identical token blocks at
    different offsets never collide."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


class PrefixCache:
    """Token-chunk -> resident-block map for prompt prefix sharing.

    Two entry kinds, both keyed off the rolling hash chain:

    * **full-block entries** — chain key of blocks ``0..i`` -> the
      physical block holding positions ``i*bs..(i+1)*bs-1``. Inserted
      when a prompt's full blocks finish prefilling; immutable by
      construction (a sequence never rewrites a filled position).
    * **tail entries** — ``(chain key, tail token tuple)`` -> the block
      holding the prompt's trailing partial block. A later prompt whose
      next tokens start with the stored tail shares the block for those
      rows; the block is frozen the moment it's inserted — ANY sequence
      extending into it (the inserter included) copies first
      (:meth:`PagedKVCache.ensure_writable`), which is the whole
      copy-on-write story.

    The cache holds one allocator reference per cached block (bumped by
    :class:`PagedKVCache` at insert), so a cached block whose sequences
    all retired survives at refcount 1 on the LRU list — eviction
    happens under allocation pressure (:meth:`PagedKVCache`'s
    ``_evict_for``), never eagerly. This class is pure host-side
    bookkeeping: refcounts and device copies belong to the owner."""

    def __init__(self, block_size):
        self.block_size = int(block_size)
        self._full = {}         # chain key -> block id
        self._tails = {}        # chain key -> {tail token tuple: block}
        self._entry = {}        # block id -> (kind, key[, tail tuple])
        # blocks cached but referenced by no sequence, oldest first —
        # the eviction ladder
        self._lru = collections.OrderedDict()
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.evictions = 0

    @property
    def cached_blocks(self):
        return len(self._entry)

    @property
    def evictable(self):
        return len(self._lru)

    def hit_rate(self):
        """Token-weighted lifetime hit rate over every match() call."""
        total = self.hit_tokens + self.miss_tokens
        return self.hit_tokens / total if total else 0.0

    def is_cached(self, block):
        return block in self._entry

    def match(self, prompt, count=True):
        """Longest cached prefix of ``prompt``: ``(blocks, ntokens)``
        — whole blocks first, then at most one partial tail. Pure
        lookup: refcounts and LRU state are untouched (``count=False``
        also skips the hit/miss accounting, for admission probes)."""
        prompt = np.asarray(prompt).reshape(-1)
        bs = self.block_size
        key = b""
        blocks, cached = [], 0
        for i in range(len(prompt) // bs):
            nxt = _chain_key(key, prompt[i * bs:(i + 1) * bs])
            b = self._full.get(nxt)
            if b is None:
                break
            blocks.append(b)
            cached += bs
            key = nxt
        # the partial tail: longest stored tail that prefixes the
        # remaining tokens (typically 0 or 1 candidates per key)
        best = None
        for tail, b in self._tails.get(key, {}).items():
            if len(tail) + cached <= len(prompt) and \
                    (best is None or len(tail) > len(best[0])) and \
                    tuple(int(t) for t in
                          prompt[cached:cached + len(tail)]) == tail:
                best = (tail, b)
        if best is not None:
            blocks.append(best[1])
            cached += len(best[0])
        if count:
            self.hit_tokens += cached
            self.miss_tokens += len(prompt) - cached
        return blocks, cached

    def insert_full(self, key_prefix_tokens, block):
        """Insert one full block under the chain key of every token up
        to and including its own. Returns True when inserted (False:
        the chunk was already cached — keep the existing block)."""
        prompt = np.asarray(key_prefix_tokens).reshape(-1)
        bs = self.block_size
        key = b""
        for i in range(len(prompt) // bs):
            key = _chain_key(key, prompt[i * bs:(i + 1) * bs])
        if key in self._full or block in self._entry:
            return False
        self._full[key] = block
        self._entry[block] = ("full", key)
        return True

    def insert_tail(self, full_prefix_tokens, tail_tokens, block):
        """Insert a partial-tail entry: ``tail_tokens`` at positions
        following the full-block prefix live in ``block`` rows
        ``0..len(tail)-1``. Returns True when inserted."""
        prompt = np.asarray(full_prefix_tokens).reshape(-1)
        bs = self.block_size
        key = b""
        for i in range(len(prompt) // bs):
            key = _chain_key(key, prompt[i * bs:(i + 1) * bs])
        tail = tuple(int(t) for t in np.asarray(tail_tokens).reshape(-1))
        if not tail or len(tail) >= bs:
            raise ValueError(f"tail must be 1..{bs - 1} tokens, "
                             f"got {len(tail)}")
        per_key = self._tails.setdefault(key, {})
        if tail in per_key or block in self._entry:
            return False
        per_key[tail] = block
        self._entry[block] = ("tail", key, tail)
        return True

    def mark_referenced(self, block):
        """A sequence took a reference to this cached block — it is no
        longer evictable."""
        self._lru.pop(block, None)

    def mark_unreferenced(self, block):
        """The last sequence referencing this cached block released it
        — it joins the evictable LRU tail (most recently used end)."""
        if block in self._entry:
            self._lru.pop(block, None)
            self._lru[block] = None

    def pop_lru(self):
        """Evict the least-recently-used unreferenced cached block:
        drops its map entry and returns the block id (caller releases
        the cache's allocator reference), or None when nothing is
        evictable."""
        if not self._lru:
            return None
        block, _ = self._lru.popitem(last=False)
        self.drop(block)
        self.evictions += 1
        return block

    def drop(self, block):
        """Remove a block's cache entry (eviction or CoW bookkeeping)."""
        ent = self._entry.pop(block, None)
        self._lru.pop(block, None)
        if ent is None:
            return
        if ent[0] == "full":
            self._full.pop(ent[1], None)
        else:
            per_key = self._tails.get(ent[1])
            if per_key is not None:
                per_key.pop(ent[2], None)
                if not per_key:
                    del self._tails[ent[1]]


def _cow_copy(pools, src, dst):
    """Copy one block's rows across every pool of every layer (jitted
    with the pools donated, so the copy is an in-HBM row move, not a
    pool round-trip)."""
    return [{name: pool.at[dst].set(pool[src])
             for name, pool in layer.items()} for layer in pools]


class PagedKVCache:
    """Per-layer pooled cache buffers + per-sequence block tables.

    The pools are jax arrays the engine threads through its (donated)
    jit calls; everything else — tables, the allocator, slot math — is
    host-side numpy. Which pools a layer has, how wide their rows are
    and in what dtype is the row layout of ``config``'s serving model
    (``config.serving_model().cache_layout()``: ``k`` and ``v`` for
    GPT, one latent row ``c`` for a latent-attention model).

    A model may have layers whose per-sequence memory is a fixed-size
    recurrent STATE instead of rows. It then says what each entry of
    :attr:`pools` is (``model.pool_kinds``: ``"rows"`` for a layer with
    rows, ``"state"`` for an entry of slots, which may hold the state
    of many layers) and what a slot holds (``model.state_layout()``:
    ``(name, shape, dtype)`` a buffer). A state entry is ``{name:
    [state_slots + 1, *shape]}``: a sequence takes one SLOT (the same
    in every such entry) with its blocks and gives it back with them;
    slot 0 is the scratch slot padded batch lanes write, as block 0 is
    for rows. A
    slot is handed out as it was left: the model's prefill writes it
    without reading it. ``state_slots`` is how many sequences may hold
    one at a time (the engine's ``max_batch_size``).

    A model may also have layers whose attention reads a WINDOW of
    the last ``model.window_size`` positions (``pool_kinds`` entries
    ``"window"``; same row layout). Those entries are pools of
    ``window_blocks`` blocks (+ scratch block 0) with an allocator of
    their own, and a sequence holds a second table into them
    (:attr:`window_tables`) of at most :attr:`ring` blocks, a RING
    indexed by position: ``j`` lies in ring block ``(j // block_size) %
    ring``, so a row is overwritten ``ring x block_size >= window +
    block_size`` positions later, when it has left the window.
    ``window_blocks`` defaults to ``state_slots x ring`` (every running
    sequence a whole ring). Both tables are taken and given back
    together, all or nothing; ``num_blocks``, ``used_blocks`` and
    ``utilization`` keep meaning the full layers' pool.
    ``prefix_cache=True`` is refused: a cached block of a window layer
    is gone once it has left the window.

    With ``prefix_cache=True`` the cache grows the prefix-sharing
    plane: :meth:`add_seq_prefix` resolves a prompt's cached prefix to
    shared blocks (refcount bumped per sharer), :meth:`insert_prefix`
    publishes a prefilled prompt's blocks for later requests,
    :meth:`ensure_writable` copy-on-writes shared blocks before a
    sequence extends into them, and retiring sequences leave cached
    blocks resident (LRU-evicted only under allocation pressure).
    Everything stays single-threaded under the engine's scheduler —
    none of this is locked."""

    def __init__(self, config, num_blocks=None,
                 block_size=DEFAULT_BLOCK_SIZE, budget=None,
                 telemetry=None, prefix_cache=False, state_slots=0,
                 window_blocks=None):
        from .. import telemetry as _telemetry
        self.config = config
        self.block_size = int(block_size)
        self._kinds = _pool_kinds(config.serving_model())
        # blocks of a sequence's ring in the window layers (0: none)
        self.ring = ring_blocks(config, self.block_size)
        if self.ring:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a model that has window "
                    "layers: a cached block of a window layer is gone "
                    "once it has left the window, and a hit would need "
                    "the window's rows rebuilt (not implemented; "
                    "ROADMAP.md Queue 2 A3)")
            if window_blocks is None:
                window_blocks = int(state_slots) * self.ring
            if int(window_blocks) < 1:
                raise ValueError(
                    "a model with window layers needs window_blocks >= 1 "
                    "(or state_slots, the sequences that hold a ring)")
        self.window_blocks = int(window_blocks) if self.ring else 0
        self.state_slots = int(state_slots) if "state" in self._kinds \
            else 0
        if "state" in self._kinds:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a model that has recurrent "
                    "state: a cached block of rows says nothing of the "
                    "state at its end (state snapshots are not "
                    "implemented; ROADMAP.md Queue 2 A6)")
            if self.state_slots < 1:
                raise ValueError(
                    "a model with recurrent state needs state_slots >= 1")
        if num_blocks is None:
            num_blocks = blocks_for_budget(
                config, self.block_size, budget, state_slots=state_slots,
                window_blocks=self.window_blocks)
            if num_blocks is None:
                raise ValueError(
                    "no HBM budget resolvable to size the KV pool "
                    "(CPU harness?): pass num_blocks= explicitly or "
                    "set HETU_HBM_BUDGET")
        self.num_blocks = int(num_blocks)
        self.telemetry = _telemetry.resolve(telemetry)
        # block 0 is the scratch block padded lanes target; real
        # sequences allocate from 1..num_blocks
        self.allocator = BlockAllocator(self.num_blocks, self.block_size,
                                        first_id=1)
        self.prefix = PrefixCache(self.block_size) if prefix_cache \
            else None
        self.pools = self._init_pools()
        self.tables = {}            # seq_id -> [block ids]
        # the window layers' pools: an allocator and a ring a sequence
        self.window_allocator = BlockAllocator(
            self.window_blocks, self.block_size, first_id=1)
        self.window_tables = {}     # seq_id -> [block ids], <= ring
        self._ring_rows = {}        # seq_id -> (blocks, its ring's slots)
        self.slots = {}             # seq_id -> state slot (1..state_slots)
        self._free_slots = list(range(self.state_slots, 0, -1))
        self.peak_utilization = 0.0
        self.cow_copies = 0
        self._cow_fn = None         # jitted lazily (one signature)

    def _init_pools(self):
        import jax.numpy as jnp
        model = self.config.serving_model()

        def layer(kind):
            if kind == "state":
                return {name: jnp.zeros((self.state_slots + 1, *shape),
                                        jnp.dtype(dtype))
                        for name, shape, dtype in model.state_layout()}
            blocks = self.window_blocks if kind == "window" \
                else self.num_blocks
            return {name: jnp.zeros((blocks + 1, self.block_size, width),
                                    jnp.dtype(dtype))
                    for name, width, dtype in model.cache_layout()}

        return [layer(kind) for kind in self._kinds]

    # -- accounting ------------------------------------------------------
    @property
    def used_blocks(self):
        return self.allocator.used

    @property
    def cached_blocks(self):
        """Cached blocks referenced by NO live sequence (the
        LRU-evictable pool the prefix cache keeps resident)."""
        return self.prefix.evictable if self.prefix is not None else 0

    @property
    def referenced_blocks(self):
        """Blocks at least one live sequence references."""
        return self.allocator.used - self.cached_blocks

    @property
    def utilization(self):
        """Fraction of the (non-scratch) pool held by live sequences
        (cached-but-unreferenced blocks are reclaimable, so they don't
        count here — see :attr:`cached_utilization`)."""
        return self.referenced_blocks / self.num_blocks

    @property
    def cached_utilization(self):
        """Fraction of the pool holding cached-unreferenced blocks."""
        return self.cached_blocks / self.num_blocks

    @property
    def state_slots_used(self):
        return len(self.slots)

    @property
    def window_blocks_used(self):
        return self.window_allocator.used

    def window_bytes(self):
        """Bytes the window layers' pools occupy (scratch block
        included; 0 for a model without a window layer)."""
        if not self.ring:
            return 0
        return kv_block_bytes(self.config, self.block_size, "window") \
            * (self.window_blocks + 1)

    def kv_bytes(self):
        """Bytes the paged pools occupy (scratch blocks included), the
        window layers' pools at their own size."""
        return kv_block_bytes(self.config, self.block_size) \
            * (self.num_blocks + 1) + self.window_bytes()

    def state_bytes(self):
        """Bytes the state slots occupy (scratch slot included)."""
        return state_slot_bytes(self.config) * (self.state_slots + 1)

    def hbm_bytes(self):
        """Bytes the cache occupies: rows and state."""
        return self.kv_bytes() + self.state_bytes()

    def can_admit(self, ntokens):
        return self.allocator.blocks_for_tokens(ntokens) \
            <= self.allocator.available + self.cached_blocks \
            and self._slot_free() \
            and self._ring_need(ntokens) <= self.window_allocator.available

    def _slot_free(self):
        return not self.state_slots or bool(self._free_slots)

    def _ring_need(self, ntokens):
        """Window blocks a sequence of ``ntokens`` positions holds."""
        return min(self.ring, self.allocator.blocks_for_tokens(ntokens))

    def slot_of_seq(self, seq_id):
        """The sequence's state slot (0, the scratch slot, for a model
        without state)."""
        return self.slots.get(seq_id, 0)

    def fits_at_all(self, ntokens):
        """Whether a sequence of ``ntokens`` could EVER be served by
        this pool (the submit-time guard)."""
        return self.allocator.blocks_for_tokens(ntokens) \
            <= self.allocator.num_blocks \
            and self._ring_need(ntokens) <= self.window_allocator.num_blocks

    def _note_util(self):
        u = self.utilization
        if u > self.peak_utilization:
            self.peak_utilization = u
        if self.telemetry.enabled:
            self.telemetry.set_gauge("kv_blocks_used",
                                     self.referenced_blocks)
            self.telemetry.set_gauge("kv_blocks_free",
                                     self.allocator.available)
            self.telemetry.set_gauge("kv_seqs", len(self.tables))
            self.telemetry.set_gauge("kv_hbm_utilization", u)
            if self.state_slots:
                self.telemetry.set_gauge("state_slots_used",
                                         len(self.slots))
            if self.ring:
                self.telemetry.set_gauge("window_blocks_used",
                                         self.window_allocator.used)
            if self.prefix is not None:
                self.telemetry.set_gauge("kv_blocks_cached",
                                         self.cached_blocks)
                self.telemetry.set_gauge("kv_hbm_utilization_cached",
                                         self.cached_utilization)
                self.telemetry.set_gauge("serve_prefix_hit_rate",
                                         self.prefix.hit_rate())

    # -- allocation under cache pressure --------------------------------
    def _evict_for(self, n):
        """Evict LRU cached-unreferenced blocks until ``n`` are free
        (or nothing is left to evict)."""
        if self.prefix is None:
            return
        while self.allocator.available < n:
            b = self.prefix.pop_lru()
            if b is None:
                return
            self.allocator.free([b])    # the cache's own reference
            if self.telemetry.enabled:
                self.telemetry.inc("serve_prefix_evictions")

    def _alloc(self, n):
        """Allocate ``n`` blocks, reclaiming cached-unreferenced blocks
        LRU-first when the free list alone can't cover it."""
        self._evict_for(n)
        return self.allocator.alloc(n)

    def _release_block(self, block):
        """Drop one reference; a cached block whose only remaining
        reference is the cache's moves to the evictable LRU."""
        self.allocator.free([block])
        if self.prefix is not None and self.prefix.is_cached(block) \
                and self.allocator.refcount(block) == 1:
            self.prefix.mark_unreferenced(block)

    # -- sequence lifecycle ---------------------------------------------
    def add_seq(self, seq_id, ntokens):
        """Allocate blocks covering ``ntokens`` positions for a new
        sequence (all-or-nothing; raises :class:`KVCacheExhausted`)."""
        if seq_id in self.tables:
            raise ValueError(f"sequence {seq_id} already has a table")
        if not self._slot_free():
            raise KVCacheExhausted(
                f"state slots exhausted: {self.state_slots} in use")
        # the ring first: it takes nothing when it cannot take all, and
        # goes back if the full layers' blocks cannot be had
        ring = self.window_allocator.alloc(self._ring_need(ntokens))
        try:
            blocks = self._alloc(self.allocator.blocks_for_tokens(ntokens))
        except KVCacheExhausted:
            self.window_allocator.free(ring)
            raise
        self.tables[seq_id] = blocks
        if self.ring:
            self.window_tables[seq_id] = ring
        if self.state_slots:
            self.slots[seq_id] = self._free_slots.pop()
        self._note_util()
        return blocks

    def match_prefix(self, prompt):
        """Pure admission probe: ``(shared_blocks, cached_tokens)`` the
        prompt would resolve against the prefix cache right now, with
        ``cached_tokens`` capped at ``len(prompt) - 1`` so prefill
        always recomputes at least the last prompt token (the logits
        the first sampled token needs)."""
        if self.prefix is None:
            return [], 0
        blocks, cached = self.prefix.match(prompt, count=False)
        return blocks, min(cached, len(np.asarray(prompt).reshape(-1)) - 1)

    def admit_blocks_needed(self, prompt, ntokens):
        """Blocks a prefix-aware admission must find for this request:
        the non-cached remainder of its table, plus the copy-on-write
        spares its writes into shared blocks will consume."""
        blocks, cached = self.match_prefix(prompt)
        need = self.allocator.blocks_for_tokens(ntokens) - len(blocks)
        p = len(np.asarray(prompt).reshape(-1))
        # suffix prefill's first write lands inside a shared block
        if blocks and cached // self.block_size < len(blocks):
            need += 1
        # the first decode write extends the (cache-frozen) prompt tail
        if self.prefix is not None and p % self.block_size != 0:
            need += 1
        return need

    def add_seq_prefix(self, seq_id, ntokens, prompt):
        """Prefix-aware :meth:`add_seq`: resolve the prompt's cached
        prefix to shared blocks (one reference each), allocate only the
        remainder, install the table. Returns ``(blocks,
        cached_tokens)`` — all-or-nothing (shared references roll back
        on exhaustion)."""
        if self.prefix is None:
            return self.add_seq(seq_id, ntokens), 0
        if seq_id in self.tables:
            raise ValueError(f"sequence {seq_id} already has a table")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        shared, cached = self.prefix.match(prompt)
        cached = min(cached, len(prompt) - 1)
        self.allocator.share(shared)
        for b in shared:
            self.prefix.mark_referenced(b)
        try:
            fresh = self._alloc(
                self.allocator.blocks_for_tokens(ntokens) - len(shared))
        except KVCacheExhausted:
            for b in shared:
                self._release_block(b)
            raise
        self.tables[seq_id] = shared + fresh
        self._note_util()
        return self.tables[seq_id], cached

    def insert_prefix(self, seq_id, prompt):
        """Publish a fully-prefilled prompt's blocks into the prefix
        cache: every full block under its rolling-hash chain key, plus
        one frozen tail entry for the trailing partial block. The cache
        takes one reference per published block (that reference is what
        keeps a retired prompt resident). No-op without a prefix cache;
        already-cached chunks keep their existing blocks."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        table = self.tables[seq_id]
        bs = self.block_size
        inserted = 0
        for i in range(len(prompt) // bs):
            b = table[i]
            if self.prefix.insert_full(prompt[:(i + 1) * bs], b):
                self.allocator.share([b])
                inserted += 1
        f = len(prompt) % bs
        if f:
            b = table[len(prompt) // bs]
            if self.prefix.insert_tail(prompt[:len(prompt) - f],
                                       prompt[len(prompt) - f:], b):
                self.allocator.share([b])
                inserted += 1
        self._note_util()
        return inserted

    def ensure_writable(self, seq_id, start, stop):
        """Copy-on-write guard for writes to positions ``[start,
        stop)``: any touched block someone else also references (a
        concurrent sharer, or the prefix cache's frozen entry) is
        copied into a fresh block first and the table repointed. When
        allocation for the copy can't be covered and the ONLY other
        referent is the cache, the entry is dropped instead (write in
        place — the cache relinquishes rather than kill the sequence).
        Returns the number of blocks copied."""
        table = self.tables[seq_id]
        bs = self.block_size
        copied = 0
        for i in range(int(start) // bs, (int(stop) - 1) // bs + 1):
            b = table[i]
            if self.allocator.refcount(b) <= 1:
                continue
            cache_only = (self.prefix is not None
                          and self.prefix.is_cached(b)
                          and self.allocator.refcount(b) == 2)
            try:
                (fresh,) = self._alloc(1)
            except KVCacheExhausted:
                if cache_only:
                    # relinquish the cache entry: the block becomes
                    # privately ours, no copy needed
                    self.prefix.drop(b)
                    self.allocator.free([b])
                    continue
                raise
            self._copy_block(b, fresh)
            table[i] = fresh
            self._release_block(b)
            copied += 1
            self.cow_copies += 1
            if self.telemetry.enabled:
                self.telemetry.inc("serve_cow_copies")
        if copied:
            self._note_util()
        return copied

    def _copy_block(self, src, dst):
        import jax
        import jax.numpy as jnp
        if self._cow_fn is None:
            self._cow_fn = jax.jit(_cow_copy, donate_argnums=(0,))
        self.pools = self._cow_fn(self.pools,
                                  jnp.int32(src), jnp.int32(dst))

    def extend_seq(self, seq_id, ntokens):
        """Grow a sequence's table to cover ``ntokens`` total positions
        (no-op when it already does)."""
        table = self.tables[seq_id]
        need = self.allocator.blocks_for_tokens(ntokens) - len(table)
        if need > 0:
            ring = self.window_tables.get(seq_id, [])
            more = self.window_allocator.alloc(
                self._ring_need(ntokens) - len(ring))
            try:
                table.extend(self._alloc(need))
            except KVCacheExhausted:
                self.window_allocator.free(more)
                raise
            ring.extend(more)
            self._note_util()
        return table

    def free_seq(self, seq_id):
        """Release a sequence's references. Unshared blocks return to
        the free list; cached blocks stay resident (the cache's
        reference) and become evictable once no sequence holds them."""
        blocks = self.tables.pop(seq_id, None)
        if blocks:
            # ONE allocator call a sequence: a call re-sorts the whole
            # free list, and a block at a time that was 200-440 ms of
            # the scheduler's thread for a long sequence's ~1,000 blocks
            # of a 34,816-block pool (my chip run, PR 54)
            self.allocator.free(blocks)
            if self.prefix is not None:
                for b in blocks:
                    if self.prefix.is_cached(b) \
                            and self.allocator.refcount(b) == 1:
                        self.prefix.mark_unreferenced(b)
        ring = self.window_tables.pop(seq_id, None)
        if ring:
            self.window_allocator.free(ring)
            self._ring_rows.pop(seq_id, None)
        slot = self.slots.pop(seq_id, None)
        if slot is not None:
            # lowest slot first, as blocks are handed out
            self._free_slots.append(slot)
            self._free_slots.sort(reverse=True)
        self._note_util()

    def capacity_tokens(self, seq_id):
        return len(self.tables[seq_id]) * self.block_size

    def assert_consistent(self):
        """Debug invariant sweep (tests call this after churn): every
        allocator refcount equals the number of table references plus
        the cache's, the free list and live set partition the pool, and
        every LRU block is genuinely unreferenced."""
        refs = collections.Counter()
        for table in self.tables.values():
            refs.update(table)
        if self.prefix is not None:
            refs.update(self.prefix._entry.keys())
        alloc = self.allocator
        assert dict(refs) == alloc._ref, \
            f"dangling refcounts: expected {dict(refs)} got {alloc._ref}"
        assert len(alloc._free) + len(alloc._ref) == alloc.num_blocks
        assert not (set(alloc._free) & set(alloc._ref))
        if self.prefix is not None:
            for b in self.prefix._lru:
                assert alloc.refcount(b) == 1, \
                    f"LRU block {b} is still referenced"
        if self.ring:
            ring = self.window_allocator
            held = [b for t in self.window_tables.values() for b in t]
            assert set(self.window_tables) == set(self.tables)
            assert all(len(t) <= self.ring
                       for t in self.window_tables.values())
            assert collections.Counter(held) == ring._ref, \
                f"window blocks {held} in tables, {ring._ref} allocated"
            assert len(ring._free) + len(ring._ref) == ring.num_blocks
        if self.state_slots:
            held = sorted(self.slots.values())
            assert set(self.slots) == set(self.tables)
            assert sorted(held + self._free_slots) == list(
                range(1, self.state_slots + 1)), \
                f"state slots {held} held, {self._free_slots} free"

    # -- slot math (host-side; the jit programs take these as inputs) ---
    def slot_of(self, seq_id, pos):
        """Flat pool slot of one position."""
        table = self.tables[seq_id]
        return table[pos // self.block_size] * self.block_size \
            + pos % self.block_size

    def slot_mapping(self, seq_id, start, stop):
        """Flat slots for positions ``[start, stop)`` as int32."""
        table = np.asarray(self.tables[seq_id], np.int32)
        pos = np.arange(start, stop)
        return (table[pos // self.block_size] * self.block_size
                + pos % self.block_size).astype(np.int32)

    def gather_slots(self, seq_ids, width):
        """``[len(seq_ids), width]`` int32 slot grid covering positions
        ``0..width-1`` per sequence; positions beyond a sequence's
        allocated blocks point at the scratch block (they sit behind
        the attention length mask anyway)."""
        bs = self.block_size
        off = np.arange(width, dtype=np.int64)
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, sid in enumerate(seq_ids):
            table = np.asarray(self.tables[sid], np.int64)
            cap = len(table) * bs
            w = min(width, cap)
            out[i, :w] = (table[off[:w] // bs] * bs
                          + off[:w] % bs).astype(np.int32)
        return out

    # -- the window layers' ring (a model with "window" entries) ---------
    def _ring_slots(self, seq_id, pos):
        """Flat slots, in the window layers' pools, of positions ``pos``
        (int64 array): ring block ``(j // block_size) % ring``."""
        table = np.asarray(self.window_tables[seq_id], np.int64)
        bs = self.block_size
        return (table[(pos // bs) % self.ring] * bs + pos % bs).astype(
            np.int32)

    def window_slot_of(self, seq_id, pos):
        """Flat ring slot of one position."""
        bs = self.block_size
        return self.window_tables[seq_id][(pos // bs) % self.ring] * bs \
            + pos % bs

    def window_slot_mapping(self, seq_id, start, stop):
        """Flat ring slots for positions ``[start, stop)`` as int32; at
        most ``ring x block_size`` of them, or later ones would land on
        earlier ones."""
        return self._ring_slots(seq_id, np.arange(start, stop))

    def ring_slots(self, seq_ids):
        """``[len(seq_ids), ring x block_size]`` int32: each sequence's
        ring as it lies, ring slot ``r`` in column ``r``; blocks a short
        sequence never took point at the scratch block (no position is
        ever held there)."""
        bs = self.block_size
        out = np.zeros((len(seq_ids), self.ring * bs), np.int32)
        for i, sid in enumerate(seq_ids):
            table = self.window_tables[sid]
            # a decode step asks every step, and a ring changes only
            # while it grows: the row is kept with the table's length
            kept = self._ring_rows.get(sid)
            if kept is None or kept[0] != len(table):
                row = (np.repeat(np.asarray(table, np.int32) * bs, bs)
                       + np.tile(np.arange(bs, dtype=np.int32), len(table)))
                kept = self._ring_rows[sid] = (len(table), row)
            out[i, :len(kept[1])] = kept[1]
        return out
