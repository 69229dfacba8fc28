"""Real-JAX inference engines: round-based and continuous (iteration-level)
batching over jit-compiled prefill/decode with KV caches.

Two execution backends the BCEdge scheduler can drive when serving an
actual model (``repro.launch.engine_serve``):

* ``InferenceEngine`` — the paper's round semantics (§IV-D): the dynamic
  batcher forms a (b, m_c) round, the whole batch runs prefill + a fixed
  number of decode steps to completion, then the next round starts.
* ``ContinuousBatchingEngine`` — iteration-level scheduling
  (docs/ARCHITECTURE.md §5): a fixed set of KV-cache *slots* is decoded
  one token per step; finished sequences are evicted at iteration
  boundaries and queued prompts are prefilled into the freed slots, so
  short sequences never wait for the longest one in their batch.

Both keep the jit compile cache small via shape bucketing: prompts are
padded to power-of-two-ish buckets, and the continuous engine decodes a
single fixed (n_slots, cache_len) shape for its whole lifetime.
The CPU recipes serve the reduced configs; on one TPU v5e chip the same
code serves a config at its published widths (chip_smoke.py), and a
config one chip cannot hold spans a tensor-parallel ``mesh``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.launch.sharding import (engine_cache_shardings,
                                   engine_param_shardings, replicated)
from repro.models import build_model
from repro.models.transformer import (_split_layers, pad_cache,
                                      paged_layer_kind, scatter_blocks,
                                      scatter_blocks_stacked)
from repro.serving.tracing import span


def _bucket(n: int, buckets=(1, 2, 4, 8, 16, 32, 64, 128)) -> int:
    for b in buckets:
        if n <= b:
            return b
    # clamping here would silently under-count S downstream (the cache-fit
    # check in ContinuousBatchingEngine.submit would pass for prompts that
    # do not fit), so over-length input is an error at the boundary
    raise ValueError(
        f"size {n} exceeds the largest bucket {buckets[-1]}")


SEQ_BUCKETS = (16, 32, 64, 128, 256, 512, 640)


def supports_prefix_cache(cfg: ModelConfig) -> bool:
    """Prefix caching shares physical KV *blocks*, so it needs every
    layer's decode state to live in the block pool: linear-attention KV
    only (``paged_layer_kind``). Recurrent states and windowed ring
    buffers are per-slot dense — a shared-prefix hit would also need the
    recurrent state at the block boundary, which the cache does not
    hold — and frontend/enc-dec models bypass the chunked path
    entirely."""
    if cfg.frontend is not None or cfg.enc_dec:
        return False
    return all(paged_layer_kind(cfg, k) for k in cfg.layer_kinds())

def supports_speculation(cfg: ModelConfig) -> bool:
    """Speculative decoding needs every layer's decode state to be
    REWINDABLE: rejected linear-attention KV rows are masked by ``pos``
    (dense) or freed back to the allocator at block granularity (paged),
    but a recurrent state advances irreversibly per token and a windowed
    ring buffer aliases rejected writes over live positions — neither
    can be rolled back. That is the same layer predicate prefix caching
    needs (all decode state in plain linear KV), so the gates coincide;
    frontend/enc-dec models additionally bypass the chunked forward the
    verify pass is built on."""
    return supports_prefix_cache(cfg)


def sample_tokens(logits, greedy: bool = True, seed: int = 0) -> np.ndarray:
    """Sample next tokens from ``logits`` (..., V) over the trailing
    vocabulary axis: argmax when ``greedy`` (the deterministic path every
    engine's token-identity guarantee rests on), else a seeded
    categorical draw. Accepts (V,), (B, V) or (B, W, V) — the single
    sampling site shared by round decode, admission, chunked-prefill
    completion, continuous decode and speculative verification. Returns
    an int32 ndarray shaped ``logits.shape[:-1]``."""
    if greedy:
        return np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
    key = jax.random.PRNGKey(seed)
    return np.asarray(
        jax.random.categorical(key, jnp.asarray(logits)).astype(jnp.int32))


def jit_cache_step(step: Callable, **shardings) -> Callable:
    """Jit a model step ``step(params, cache, batch) -> (out, cache)``
    with the cache DONATED: the step updates it in place
    (docs/ARCHITECTURE.md §5), so the caller must rebind the cache it
    passed to the one returned and keep no other reference to it.
    ``shardings``: the sharded engine's ``in_shardings`` /
    ``out_shardings``."""
    return jax.jit(step, donate_argnums=(1,), **shardings)


#: largest chunked-prefill piece; pieces are powers of two up to this, so
#: the chunk compile cache is bounded at one shape per piece size
_MAX_CHUNK = 512


def make_prefill_batch(cfg: ModelConfig, prompts: List[np.ndarray]
                       ) -> Tuple[Dict, int, np.ndarray]:
    """Left-pad ``prompts`` into a bucketed (B, S) token batch.

    Shared by both engines so a prompt prefilled alone (continuous
    admission) sees exactly the shapes it would see inside a round batch —
    one compiled prefill per (B-bucket, S-bucket) pair.
    """
    B = _bucket(len(prompts))
    S = _bucket(max(len(p) for p in prompts), buckets=SEQ_BUCKETS)
    toks = np.zeros((B, S), np.int32)
    lens = np.zeros((B,), np.int32)
    for i, p in enumerate(prompts):
        toks[i, S - len(p):] = p  # left-pad (last position = last token)
        lens[i] = len(p)
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.frontend is not None and not cfg.enc_dec:
        F = cfg.frontend_tokens
        batch["frontend_embeds"] = jnp.zeros(
            (B, F, cfg.d_model), jnp.float32)
    if cfg.enc_dec:
        batch["frontend_embeds"] = jnp.zeros(
            (B, max(8, S // 4), cfg.d_model), jnp.float32)
    return batch, S, lens


@dataclasses.dataclass
class GenerationResult:
    """Output of one round-mode ``generate`` call (paper §IV-D round)."""
    tokens: np.ndarray          # (B, new)
    prefill_ms: float
    decode_ms: float
    total_ms: float


class InferenceEngine:
    """Round-based (run-to-completion) execution backend (paper §IV-D).

    ``generate`` runs one (b,)-batch round: bucketed prefill, then
    ``max_new_tokens`` lock-step decode iterations for every request in
    the batch. This is the execution substrate the paper's (b, m_c)
    scheduler assumes; see ``ContinuousBatchingEngine`` for the
    iteration-level alternative.
    """

    def __init__(self, cfg: ModelConfig, max_seq: int = 512,
                 dtype=jnp.float32, seed: int = 0):
        self.cfg = cfg
        self.max_seq = max_seq
        self.model = build_model(cfg, remat=False)
        self.params = self.model.init(jax.random.PRNGKey(seed), dtype)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jit_cache_step(self.model.decode_step)

    def _make_batch(self, prompts: List[np.ndarray]
                    ) -> Tuple[Dict, int, np.ndarray]:
        return make_prefill_batch(self.cfg, prompts)

    def generate(self, prompts: List[np.ndarray], max_new_tokens: int = 8,
                 greedy: bool = True, seed: int = 0) -> GenerationResult:
        t0 = time.perf_counter()
        batch, S, lens = self._make_batch(prompts)
        B = batch["tokens"].shape[0]
        logits, cache = self._prefill(self.params, batch)
        logits.block_until_ready()
        t1 = time.perf_counter()
        cache = pad_cache(self.cfg, cache, max_new_tokens)
        F = 0
        if self.cfg.frontend is not None and not self.cfg.enc_dec:
            F = batch["frontend_embeds"].shape[1]
        pos = jnp.full((B,), F + S, jnp.int32)
        out = np.zeros((B, max_new_tokens), np.int32)
        rng = jax.random.PRNGKey(seed)
        tok = jnp.asarray(sample_tokens(logits[:, -1, :]))
        for t in range(max_new_tokens):
            out[:, t] = np.asarray(tok)
            logits, cache = self._decode(
                self.params, cache, {"tokens": tok[:, None], "pos": pos})
            if greedy:
                tok = jnp.asarray(sample_tokens(logits[:, -1, :]))
            else:
                rng, k = jax.random.split(rng)
                tok = jax.random.categorical(k, logits[:, -1, :]).astype(
                    jnp.int32)
            pos = pos + 1
        tok.block_until_ready()
        t2 = time.perf_counter()
        return GenerationResult(out[: len(prompts)],
                                (t1 - t0) * 1e3, (t2 - t1) * 1e3,
                                (t2 - t0) * 1e3)


# =====================================================================
# speculative proposers (docs/ARCHITECTURE.md §5)
# =====================================================================
class NGramProposer:
    """Self-speculative (prompt-lookup) drafting: find the most recent
    earlier occurrence of the context's trailing n-gram and propose the
    tokens that followed it, falling back to shorter n-grams and finally
    to repeating the last token. Pure host-side lookup — no extra model
    forward — so a wrong draft costs only the verify lane it rode in;
    the verification pass makes proposal quality a throughput knob,
    never a correctness one."""

    def __init__(self, n: int = 2):
        self.n = max(1, n)

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        """``context`` (1-D int32, prompt + emitted + pending) -> (k,)
        draft tokens continuing it."""
        ctx = np.asarray(context, np.int32)
        L = len(ctx)
        cont = None
        for n in range(min(self.n, L - 1), 0, -1):
            tail = ctx[L - n:]
            for i in range(L - n - 1, -1, -1):
                if np.array_equal(ctx[i:i + n], tail):
                    cont = ctx[i + n:i + n + k]
                    break
            if cont is not None and len(cont):
                break
        if cont is None or len(cont) == 0:
            cont = ctx[L - 1:] if L else np.zeros(1, np.int32)
        reps = -(-k // len(cont))
        return np.tile(cont, reps)[:k].astype(np.int32)


class DraftModelProposer:
    """Draft-model proposal: a small model greedily decodes ``k`` tokens
    from the (tail of the) full context, re-prefilled per call.
    Stateless by design — keeping a draft KV cache consistent across
    preemption, prefix sharing and rollback would mirror the entire
    target engine's bookkeeping for a heuristic whose only job is
    guessing; re-prefilling a bounded context window keeps the proposer
    trivially correct under every schedule. Verification guarantees
    output identity regardless of what the draft proposes."""

    def __init__(self, cfg: ModelConfig, seed: int = 0,
                 context_window: int = 256):
        self.engine = InferenceEngine(cfg, max_seq=1024, seed=seed)
        self.context_window = context_window

    def propose(self, context: np.ndarray, k: int) -> np.ndarray:
        ctx = np.asarray(context, np.int32)[-self.context_window:]
        if len(ctx) == 0:
            return np.zeros(k, np.int32)
        res = self.engine.generate([ctx], max_new_tokens=k)
        return res.tokens[0].astype(np.int32)


# =====================================================================
# continuous (iteration-level) batching
# =====================================================================
class BlockAllocator:
    """Reference-counted free-list allocator over a paged KV block pool,
    with a hash-keyed cache of full immutable prefix blocks
    (docs/ARCHITECTURE.md §5).

    ``n_blocks`` usable blocks of ``block_size`` tokens; physical ids are
    1..n_blocks (id 0 is the null block inactive batch rows write into,
    never handed out). Admission *reserves* a sequence's worst-case block
    count up front, so the lazy per-decode-boundary ``alloc_reserved``
    can never fail mid-sequence; eviction decrements refcounts and
    cancels the unfilled remainder of the reservation.

    Prefix caching (docs/ARCHITECTURE.md §5): the engine ``register``s a
    full, immutable prompt block under its token-chain hash key;
    ``acquire`` maps that physical block into another sequence at
    refcount+1, so N same-prefix residents hold the prefix ONCE. A block
    whose refcount drops to zero returns to the free list when it is
    unregistered, or parks in an LRU pool when it is cached —
    evicted-but-cached blocks are reclaimed (oldest first, cache entry
    invalidated) when an allocation finds the free list empty.

    Invariants (asserted in tests/test_paged_kv.py and fuzzed in
    tests/test_engine_fuzz.py):
      * ``n_free + n_cached + n_live == n_blocks`` (the three id sets
        are disjoint — conservation);
      * ``n_free + n_cached - n_reserved == n_available >= 0``;
      * a block mapped by k sequences has refcount k (no block is owned
        by two slots without a refcount);
      * the null block 0 is never allocated;
      * LRU reclaim only ever takes refcount-0 blocks.

    ``free`` verifies ownership against the outstanding-id set and raises
    on a double free (more ``free``s than the refcount ever granted) or
    a duplicate id within one call — a silently re-freed id would hand
    the same physical block to two sequences.

    Host tier (``host_blocks > 0``, docs/ARCHITECTURE.md §5): a second
    id space 1..host_blocks of host-memory blocks the engine can swap
    KV into. Two populations share it, under one LRU discipline that
    spans both tiers:
      * *swapped* blocks (``_host_live``) — a preempted sequence's KV,
        owned by its ``PreemptedRequest`` snapshot until resume or
        cancel frees them (never reclaimed underneath the owner);
      * *spilled* blocks (``_host_lru`` / ``_host_cache``) — refcount-0
        prefix-cache blocks that would otherwise be invalidated by
        device-LRU reclaim; their cache entry moves to the host tier
        instead, and a later ``acquire`` revives them back to a device
        block (``unspill_fn`` copies the bytes). Spilled entries are
        reclaimable (oldest first) when the host tier itself fills.
    Host conservation mirrors the device invariant:
    ``n_host_free + n_host_cached + n_host_live == n_host_blocks``.
    The allocator is pure bookkeeping — the engine provides
    ``spill_fn(device_id, host_id)`` / ``unspill_fn(host_id, device_id)``
    hooks that move the actual bytes.
    """

    def __init__(self, n_blocks: int, block_size: int,
                 host_blocks: int = 0):
        if n_blocks < 1:
            raise ValueError("need at least one usable block")
        if host_blocks < 0:
            raise ValueError(f"host_blocks must be >= 0, got {host_blocks}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free = list(range(n_blocks, 0, -1))  # pop() -> low ids first
        self._outstanding: Set[int] = set()
        self._refcount: Dict[int, int] = {}
        #: prefix cache: chain-hash key -> block id, plus the reverse map
        #: and the LRU pool of refcount-0 cached (reclaimable) blocks
        self._cache: Dict[str, int] = {}
        self._block_key: Dict[int, str] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.n_reserved = 0
        self.n_reclaimed = 0    # cached blocks evicted under pressure
        # ---- host tier ----
        self.n_host_blocks = host_blocks
        self._host_free = list(range(host_blocks, 0, -1))
        self._host_live: Set[int] = set()         # swapped sequence KV
        self._host_cache: Dict[str, int] = {}     # spilled prefix blocks
        self._host_key: Dict[int, str] = {}
        self._host_lru: "OrderedDict[int, None]" = OrderedDict()
        #: engine-provided byte movers; None = host tier is inert (the
        #: device LRU falls back to plain invalidation on reclaim)
        self.spill_fn: Optional[Callable[[int, int], None]] = None
        self.unspill_fn: Optional[Callable[[int, int], None]] = None
        self.n_spilled = 0      # device LRU entries demoted to host
        self.n_unspilled = 0    # host entries revived to device
        self.n_host_reclaimed = 0  # spilled entries evicted under pressure
        self.n_swapped_out = 0  # sequence blocks swapped device -> host
        self.n_swapped_in = 0   # sequence blocks swapped host -> device

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        """Refcount-0 blocks parked in the prefix-cache LRU pool —
        reclaimable, so they count toward ``n_available``."""
        return len(self._lru)

    @property
    def n_live(self) -> int:
        """Distinct physical blocks with refcount >= 1 (shared blocks
        count ONCE — the quantity budget accounting charges)."""
        return len(self._outstanding)

    @property
    def n_available(self) -> int:
        """Blocks neither live nor promised to an admitted slot
        (evicted-but-cached LRU blocks are reclaimable, so they count)."""
        return len(self._free) + len(self._lru) - self.n_reserved

    # ---- host tier (docs/ARCHITECTURE.md §5) -----------------------------
    @property
    def n_host_free(self) -> int:
        return len(self._host_free)

    @property
    def n_host_cached(self) -> int:
        """Spilled prefix blocks parked in the host LRU — reclaimable."""
        return len(self._host_lru)

    @property
    def n_host_live(self) -> int:
        """Host blocks owned by swapped (preempted) sequences — pinned
        until their snapshot resumes or is cancelled."""
        return len(self._host_live)

    @property
    def n_host_available(self) -> int:
        """Host blocks a swap-out could claim right now: free plus
        reclaimable spilled entries (live swapped blocks are never
        reclaimed underneath their owner)."""
        return len(self._host_free) + len(self._host_lru)

    def _host_alloc(self) -> Optional[int]:
        """One host block: free list first, then reclaim the oldest
        spilled entry (its cache key is invalidated — the spanning LRU's
        final eviction). None when every host block is swap-pinned."""
        if self._host_free:
            return self._host_free.pop()
        if self._host_lru:
            hid, _ = self._host_lru.popitem(last=False)
            key = self._host_key.pop(hid)
            del self._host_cache[key]
            self.n_host_reclaimed += 1
            return hid
        return None

    def swap_out_alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` host blocks for a preempted sequence's KV (the
        swap-out side of ``preempt(mode="swap")``). All-or-nothing:
        None when fewer than ``n`` are available."""
        if self.n_host_available < n:
            return None
        ids = []
        for _ in range(n):
            hid = self._host_alloc()
            assert hid is not None
            self._host_live.add(hid)
            ids.append(hid)
        self.n_swapped_out += n
        return ids

    def host_free(self, ids: List[int]) -> None:
        """Release a swap snapshot's host blocks (resume landed, or the
        request was cancelled). Same double-free discipline as ``free``."""
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate host block ids in host_free: {ids}")
        for i in ids:
            if i not in self._host_live:
                raise ValueError(
                    f"host_free of block {i}: not currently swapped out")
        for i in ids:
            self._host_live.discard(i)
            self._host_free.append(i)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(0, n_tokens) // self.block_size)

    def refcount(self, bid: int) -> int:
        return self._refcount.get(bid, 0)

    def reserve(self, n: int) -> bool:
        """Promise ``n`` blocks to a sequence; False when they are not
        available (the caller keeps the request queued)."""
        if self.n_available < n:
            return False
        self.n_reserved += n
        return True

    def unreserve(self, n: int) -> None:
        assert 0 <= n <= self.n_reserved
        self.n_reserved -= n

    def _reclaim_lru(self) -> int:
        """Evict the least-recently-parked cached block. With a host
        tier attached (``spill_fn`` set) the cache entry is demoted to a
        host block instead of invalidated — the device LRU spills into
        the host LRU, one eviction chain spanning both tiers; without
        one (or when the host tier is swap-pinned full) the entry is
        invalidated and the id behaves like a fresh free block."""
        bid, _ = self._lru.popitem(last=False)
        key = self._block_key.pop(bid)
        del self._cache[key]
        self.n_reclaimed += 1
        if self.spill_fn is not None:
            hid = self._host_alloc()
            if hid is not None:
                self.spill_fn(bid, hid)
                self._host_cache[key] = hid
                self._host_key[hid] = key
                self._host_lru[hid] = None
                self.n_spilled += 1
        return bid

    def alloc_reserved(self) -> int:
        """Convert one previously reserved block into a physical id,
        reclaiming from the cached-LRU pool when the free list is empty
        (never a block with live references — the LRU holds refcount-0
        blocks only)."""
        assert self.n_reserved > 0, "alloc without reservation"
        self.n_reserved -= 1
        bid = self._free.pop() if self._free else self._reclaim_lru()
        self._outstanding.add(bid)
        self._refcount[bid] = 1
        return bid

    def free(self, ids: List[int]) -> None:
        """Drop one reference per id. A block reaching refcount 0 returns
        to the free list — or parks in the cached-LRU pool when it is
        registered in the prefix cache, so a future same-prefix admission
        can revive it. Raises ``ValueError`` on an out-of-range id, a
        duplicate within ``ids``, or a double free (an id with no live
        references left) — any of which would corrupt the free list and
        alias one physical block to two sequences."""
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate block ids in free(): {ids}")
        for i in ids:
            if not 0 < i <= self.n_blocks:
                raise ValueError(
                    f"block id {i} outside 1..{self.n_blocks}")
            if i not in self._outstanding:
                raise ValueError(
                    f"double free of block {i}: not currently allocated")
        for i in ids:
            self._refcount[i] -= 1
            if self._refcount[i] > 0:
                continue  # still referenced by another sequence
            del self._refcount[i]
            self._outstanding.discard(i)
            if i in self._block_key:
                self._lru[i] = None  # evicted but cached (reclaimable)
            else:
                self._free.append(i)

    # ---- prefix cache (docs/ARCHITECTURE.md §5) --------------------------
    def cached(self, key: str) -> bool:
        """True when either tier holds ``key`` — a spilled host entry is
        still a hit (``acquire`` revives it to a device block)."""
        return key in self._cache or key in self._host_cache

    def cached_live(self, key: str) -> bool:
        """True when ``key``'s block is currently mapped by a live
        sequence — sharing it costs no extra capacity."""
        bid = self._cache.get(key)
        return bid is not None and bid in self._outstanding

    def register(self, key: str, bid: int) -> None:
        """Publish a full immutable block under its chain-hash key. The
        block must be live (its writer still owns it); first writer wins
        on a key collision, and a block only ever carries one key (its
        content determines the whole chain)."""
        assert bid in self._outstanding, f"register of non-live block {bid}"
        if key in self._cache or bid in self._block_key:
            return
        if key in self._host_cache:
            # a live device copy supersedes the spilled one: drop the
            # host entry so every key names exactly one physical block
            hid = self._host_cache.pop(key)
            del self._host_key[hid]
            self._host_lru.pop(hid, None)
            self._host_free.append(hid)
        self._cache[key] = bid
        self._block_key[bid] = key

    def acquire(self, key: str) -> Optional[int]:
        """Map the cached block for ``key`` into another sequence:
        refcount+1 for a live block (costs nothing), revival for an
        LRU-parked one (consumes one available block — refused when
        every remaining block is already promised to a reservation).
        Returns the block id, or None on a miss. A key whose block was
        spilled to the host tier is revived: a fresh device block is
        claimed (free list, else device-LRU reclaim), ``unspill_fn``
        copies the bytes back, and the cache entry moves home — also
        refused when every available block is promised."""
        bid = self._cache.get(key)
        if bid is not None:
            if bid in self._outstanding:
                self._refcount[bid] += 1
                return bid
            # revive from the LRU pool; guard the reservation promise
            if self.n_available < 1:
                return None
            del self._lru[bid]
            self._outstanding.add(bid)
            self._refcount[bid] = 1
            return bid
        hid = self._host_cache.get(key)
        if hid is None or self.unspill_fn is None:
            return None
        if self.n_available < 1:
            return None
        # detach the host entry FIRST: claiming the device block below
        # can itself reclaim-and-spill, and must not be able to evict
        # the very entry being revived
        del self._host_cache[key]
        del self._host_key[hid]
        del self._host_lru[hid]
        bid = self._free.pop() if self._free else self._reclaim_lru()
        self.unspill_fn(hid, bid)
        self._host_free.append(hid)
        self._outstanding.add(bid)
        self._refcount[bid] = 1
        self._cache[key] = bid
        self._block_key[bid] = key
        self.n_unspilled += 1
        return bid


@dataclasses.dataclass
class _Slot:
    """One KV-cache slot: the sequence prefilling or decoding in batch
    row i. The chunked-prefill state machine lives here: an admitted
    sequence starts PREFILLING (``prefill_pos < len(seq_tokens)``),
    advances by budget-bounded chunks into its ``staging`` cache, and
    becomes DECODING once the graft lands (docs/ARCHITECTURE.md §5)."""
    request_id: int = -1
    remaining: int = 0          # tokens still to emit
    n_emitted: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    submit_s: float = 0.0
    admit_s: float = 0.0
    # paged layout only: physical blocks owned, and how many of the
    # admission reservation remain unallocated (alloc-on-decode-boundary)
    blocks: List[int] = dataclasses.field(default_factory=list)
    n_outstanding: int = 0
    #: leading blocks of ``blocks`` mapped from the prefix cache at
    #: refcount+1 — immutable; graft/decode writes start past them
    n_shared: int = 0
    # chunked prefill state machine
    seq_tokens: Optional[np.ndarray] = None  # padded prompt (+ resume ctx)
    base_len: int = 0           # padded-prompt length at FIRST admission
    n_pad: int = 0              # leading bucket-padding rows of seq_tokens
    prefill_pos: int = 0        # tokens of seq_tokens processed so far
    staging: object = None      # single-seq cache chunks accumulate into
    # accounting satellites
    requested_new: int = 0      # caller-requested max_new (pre-clamp)
    truncated: bool = False
    n_preempted: int = 0
    #: engine-clock time the FIRST token landed (carried across
    #: preemption/resume so TTFT reflects the original first token;
    #: -1 before any token)
    first_token_s: float = -1.0
    # speculative decoding: drafts proposed / accepted for this sequence
    # since (re-)admission — preemption recomputes, so these reset with
    # the slot; the engine-level counters stay monotonic
    n_spec_proposed: int = 0
    n_spec_accepted: int = 0

    @property
    def active(self) -> bool:
        return self.request_id >= 0

    @property
    def prefilling(self) -> bool:
        return self.active and self.seq_tokens is not None \
            and self.prefill_pos < len(self.seq_tokens)


@dataclasses.dataclass
class PreemptedRequest:
    """Resumable snapshot of a preempted sequence (docs/RUNTIME.md §8).

    Two flavours, distinguished by ``host_blocks``:

    * **recompute** (``host_blocks is None``): ``seq_tokens`` holds the
      padded prompt plus every token emitted so far, re-prefilled in
      chunks on resume — greedy output is token-identical to an
      uninterrupted run.
    * **swap** (``preempt(mode="swap")``): the sequence's KV blocks were
      copied to the allocator's host tier instead of discarded.
      ``seq_tokens`` stays the original padded prompt; the emitted
      tokens, decode position and pending token are carried verbatim so
      resume re-maps the blocks onto fresh device ids and continues
      decoding with NO recompute. The snapshot owns its host blocks
      until resume or cancel, and is pinned to the engine whose host
      pool holds them (``host_engine_id``) — ``release_swap`` converts
      it back to a recompute snapshot when that engine goes away.
    """
    request_id: int
    seq_tokens: np.ndarray      # padded prompt (+ emitted, recompute only)
    base_len: int               # emitted tokens = seq_tokens[base_len:]
    max_new: int                # tokens still to emit
    submit_s: float
    requested_new: int
    truncated: bool
    n_preempted: int
    first_token_s: float = -1.0
    n_pad: int = 0              # leading bucket-padding rows of seq_tokens
    # ---- swap-mode state (None/unused for recompute snapshots) ----
    tokens: Optional[List[int]] = None   # emitted tokens (swap carries
    #                                      them outside seq_tokens)
    pos: int = -1                        # decode frontier at preemption
    pending_tok: int = 0                 # sampled-but-unwritten token
    host_blocks: Optional[List[int]] = None
    host_engine_id: int = 0              # id() of the owning engine

    @property
    def swapped(self) -> bool:
        return self.host_blocks is not None


def to_recompute(req: PreemptedRequest) -> PreemptedRequest:
    """Rebuild a swap snapshot as a recompute snapshot WITHOUT touching
    any allocator — for callers whose owning engine is already retired
    (its host pool, blocks included, died with it). Token identity
    holds: the recompute context is the padded prompt plus the emitted
    tokens, and greedy re-prefill regenerates the dropped pending token
    deterministically. Prefer ``engine.release_swap`` while the engine
    is alive — it returns the host blocks properly."""
    if not req.swapped:
        return req
    seq = np.concatenate([req.seq_tokens,
                          np.asarray(req.tokens, np.int32)])
    return PreemptedRequest(
        req.request_id, seq, base_len=req.base_len, max_new=req.max_new,
        submit_s=req.submit_s, requested_new=req.requested_new,
        truncated=req.truncated, n_preempted=req.n_preempted,
        first_token_s=req.first_token_s, n_pad=req.n_pad)


@dataclasses.dataclass
class _WaitingReq:
    """One queued admission: a fresh prompt, (``prepadded``) a preempted
    sequence whose bucket padding is already baked in, or (``swap``) a
    swap-mode snapshot whose KV waits in the host tier — admitted
    straight to DECODE, no prefill."""
    request_id: int
    prompt: np.ndarray
    max_new: int
    submit_s: float
    prepadded: bool = False
    base_len: int = -1          # resumes only
    n_pad: int = 0              # resumes only
    requested_new: int = 0
    truncated: bool = False
    n_preempted: int = 0
    first_token_s: float = -1.0
    swap: Optional[PreemptedRequest] = None


@dataclasses.dataclass
class ContinuousResult:
    """One finished sequence from the continuous engine
    (docs/ARCHITECTURE.md §5 accounting: per-request, not per-round)."""
    request_id: int
    tokens: np.ndarray          # (n_emitted,)
    submit_s: float             # perf_counter timestamps (engine clock)
    admit_s: float
    finish_s: float
    n_iters: int                # decode iterations this sequence was live
    #: fewer tokens than requested were emitted (submit-time cache-room
    #: clamp, or the capacity clip at cache_len) — surfaced so callers
    #: never mistake a truncated completion for a full one
    truncated: bool = False
    #: times this sequence was preempted and recomputed
    n_preempted: int = 0
    #: speculative drafts proposed / accepted while this sequence was
    #: resident (since the last re-admission, if it was preempted)
    n_spec_proposed: int = 0
    n_spec_accepted: int = 0
    #: engine-clock time the first token landed (preemption-safe: the
    #: ORIGINAL first token, not the post-resume one; -1 if none landed)
    first_token_s: float = -1.0
    #: the request was cancelled (client disconnect / explicit cancel):
    #: ``tokens`` holds the partial completion emitted before the cancel
    cancelled: bool = False

    @property
    def queue_wait_s(self) -> float:
        return self.admit_s - self.submit_s

    @property
    def ttft_s(self) -> float:
        """Submit -> first token on the engine clock (-1 if no token)."""
        return self.first_token_s - self.submit_s \
            if self.first_token_s >= 0 else -1.0

    @property
    def tpot_s(self) -> float:
        """Mean seconds per token after the first (-1 below 2 tokens)."""
        if self.first_token_s < 0 or len(self.tokens) < 2:
            return -1.0
        return (self.finish_s - self.first_token_s) \
            / (len(self.tokens) - 1)


class ContinuousBatchingEngine:
    """Iteration-level batching backend (docs/ARCHITECTURE.md §5; the
    SLICE/Orca-style execution mode the simulator's
    ``exec_mode="continuous"`` models analytically).

    A fixed number of KV-cache slots is allocated once at
    ``(n_slots, cache_len)``; every ``step()`` runs ONE jit-compiled
    decode iteration over all slots (a single compiled shape for the
    engine's lifetime). At iteration boundaries finished sequences are
    evicted — their slot is freed immediately — and queued prompts are
    prefilled (one compile per prompt-length bucket) and grafted into
    free slots. Admission cost is one host-side cache scatter per
    request, which is fine at the reduced-config scale this repo serves;
    a production engine would fuse the graft into the prefill kernel.

    ``kv_layout="paged"`` replaces the dense per-slot cache for linear
    attention layers with a block pool + ``BlockAllocator``: a slot only
    occupies the blocks its sequence actually needs (prompt bucket +
    requested decode tokens) instead of a full ``cache_len`` row, so the
    same token budget holds materially more concurrent sequences.
    Admission is gated on free blocks, blocks are physically allocated
    when decode crosses a block boundary, and eviction returns them to
    the free list. Greedy outputs are token-identical to the dense
    layout (asserted in tests/test_paged_kv.py).
    """

    def __init__(self, cfg: ModelConfig, max_slots: int = 4,
                 max_seq: int = 256, dtype=jnp.float32, seed: int = 0,
                 share_from: "ContinuousBatchingEngine" = None,
                 kv_layout: str = "dense", block_size: int = 16,
                 kv_blocks: int = None, kv_host_blocks: int = 0,
                 token_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 spec_k: int = 0, spec_ngram: int = 2,
                 proposer=None, prefill_mode: str = "auto",
                 mesh=None):
        if cfg.enc_dec:
            # cross-attention K/V is unmasked (_cross_core attends every
            # encoder row), so grafting a shorter prefilled ck/cv into the
            # slot cache would attend zero-padded garbage rows
            raise NotImplementedError(
                "continuous batching does not support encoder-decoder "
                "architectures yet; use InferenceEngine")
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        self.cfg = cfg
        self.n_slots = max(1, max_slots)
        self.cache_len = max_seq
        self.kv_layout = kv_layout
        self.dtype = dtype
        #: per-iteration cap on prefill-chunk + resident-decode tokens
        #: (docs/ARCHITECTURE.md §5; None = uncapped, prompts prefill in
        #: one pass of bucket-sized chunks). Mutable between steps — the
        #: PoolScheduler co-optimises it with (b, m_c).
        self.token_budget = token_budget
        #: chunked prefill needs plain token prompts; frontend models
        #: keep the single-shot prefill admission path (and therefore
        #: do not support preemption-resume)
        self.chunked = cfg.frontend is None and not cfg.enc_dec
        if prefix_cache:
            if kv_layout != "paged":
                raise ValueError(
                    "prefix_cache needs kv_layout='paged' (sharing is "
                    "block-granular)")
            if not supports_prefix_cache(cfg):
                raise ValueError(
                    f"{cfg.name}: prefix_cache needs every layer's decode "
                    "state in the block pool (linear attention only); "
                    "recurrent/windowed/frontend layers keep per-slot "
                    "dense state the cache cannot share")
        self.prefix_cache = prefix_cache
        #: chunked-prefill execution mode. "fused" runs every prefill
        #: chunk DIRECTLY against the paged pool through the slot's
        #: block table (repro.models.attention.attention_chunk_paged):
        #: no per-slot staging cache, no prefix gather, no completion
        #: graft scatter. "auto" picks fused whenever the layout
        #: supports it: paged + chunked + every layer's decode state in
        #: the block pool (the same gate as the prefix cache — a dense
        #: per-slot leaf cannot take a batch-1 chunk against the shared
        #: pool pytree). Layouts that fail the gate (dense, hybrid
        #: stacks) keep the staging-cache round trip: chunk into a
        #: per-slot staging cache, scatter-graft on completion. The
        #: legacy "staging" override for paged all-linear stacks is
        #: gone — fused is the only paged prefill path.
        if prefill_mode not in ("auto", "fused"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        fused_ok = (kv_layout == "paged" and self.chunked
                    and supports_prefix_cache(cfg))
        if prefill_mode == "fused" and not fused_ok:
            raise ValueError(
                "prefill_mode='fused' needs kv_layout='paged', the "
                "chunked-prefill path, and every layer's decode state "
                "in the block pool")
        self.fused_prefill = fused_ok if prefill_mode == "auto" \
            else prefill_mode == "fused"
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k > 0 and not supports_speculation(cfg):
            raise ValueError(
                f"{cfg.name}: speculative decoding needs every layer's "
                "decode state rewindable (linear attention only); "
                "recurrent states advance irreversibly and windowed ring "
                "buffers alias rejected writes over live positions")
        #: max speculation depth this engine compiled for (fixed: the
        #: dense scratch margin and the verify block-table padding depend
        #: on it); ``spec_k`` below is the CURRENT depth, mutable between
        #: steps — the PoolScheduler's fourth action axis — and clamped
        #: to ``spec_max`` at use
        self.spec_max = spec_k
        self.spec_k = spec_k
        self.proposer = proposer if proposer is not None \
            else NGramProposer(spec_ngram)
        self.n_spec_proposed = 0
        self.n_spec_accepted = 0
        self.n_spec_steps = 0
        self._spec_shapes: Set[int] = set()
        #: prefix-cache accounting (tokens; rate = hit / (hit + chunked))
        self.n_prefix_lookups = 0
        self.n_prefix_hits = 0
        self.n_prefix_hit_tokens = 0
        self.n_prefill_chunk_tokens = 0
        #: of those, rows that were bucket padding, not the users' own
        #: prompt tokens
        self.n_prefill_pad_rows = 0
        #: tensor parallelism (docs/ARCHITECTURE.md §11): a 1D
        #: ``("model",)`` mesh (launch/mesh.make_tp_mesh) this instance
        #: spans. Params are placed under the launch TP rules, the KV
        #: cache — dense slabs and the paged block pool alike — is
        #: HEAD-sharded, and the step functions are jitted with
        #: NamedSharding in/out specs. Block tables, the allocator and
        #: every slot/queue structure stay host-side (replicated): the
        #: scheduler's view of the engine is layout-independent.
        self.mesh = mesh
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"engine mesh needs a 'model' axis, got "
                    f"{mesh.axis_names}")
            tp = int(mesh.shape["model"])
            if tp > 1 and not self.chunked:
                raise NotImplementedError(
                    "tensor-parallel serving needs the chunked-prefill "
                    "path (frontend engines stay single-device)")
            if cfg.n_kv_heads % tp or cfg.n_heads % tp:
                raise ValueError(
                    f"{cfg.name}: tp_degree {tp} must divide n_heads "
                    f"{cfg.n_heads} and n_kv_heads {cfg.n_kv_heads} "
                    "(the KV pool is head-sharded over the model axis)")
        if share_from is not None and share_from.cfg == cfg:
            # co-resident instances of the same model share weights and
            # jit caches (docs/RUNTIME.md: spawn must be cheap for the
            # pool's scale_to to be a usable action); the KV slot cache
            # below stays per-instance. Sharing requires the SAME mesh:
            # the donor's params live in that layout and its jits carry
            # its in/out shardings, so the pool keys templates by
            # (model, device ids).
            if getattr(share_from, "mesh", None) != mesh:
                raise ValueError(
                    "share_from donor spans a different mesh; instances "
                    "share weights/jit only on the same devices")
            self.model = share_from.model
            self.params = share_from.params
            self._prefill = share_from._prefill
            self._prefill_chunk = share_from._prefill_chunk
            self._decode = share_from._decode
            self._verify = getattr(share_from, "_verify", None)
            if self._verify is None and supports_speculation(cfg):
                self._verify = jit_cache_step(self.model.verify_step)
        else:
            self.model = build_model(cfg, remat=False)
            key = jax.random.PRNGKey(seed)
            if mesh is None:
                self.params = self.model.init(key, dtype)
            else:
                # generated in place, shard by shard (out_shardings): a
                # model no single device can hold never exists whole
                self.params = jax.jit(
                    functools.partial(self.model.init, dtype=dtype),
                    out_shardings=engine_param_shardings(
                        mesh, self.model.abstract_params(dtype)))(key)
            self._prefill = jax.jit(self.model.prefill)
            if mesh is None:
                self._prefill_chunk = jit_cache_step(
                    self.model.prefill_chunk) if self.chunked else None
                self._decode = jit_cache_step(self.model.decode_step)
                self._verify = jit_cache_step(self.model.verify_step) \
                    if supports_speculation(cfg) else None
            else:
                # sharded step jits need the cache pytree for their
                # in/out specs — created after the cache init below
                self._prefill_chunk = None
                self._decode = None
                self._verify = None
        if kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got {kv_host_blocks}")
        if kv_host_blocks > 0:
            if kv_layout != "paged":
                raise ValueError(
                    "kv_host_blocks needs kv_layout='paged' (the host "
                    "tier swaps block-granular KV)")
            if mesh is not None and mesh.size > 1:
                raise ValueError(
                    "the host KV tier is single-device for now: swap-in "
                    "writes outside jit would drop the pool's sharding")
        self.kv_host_blocks = kv_host_blocks
        #: host-tier stack gate: swapping a sequence (or spilling a
        #: prefix block) moves ONLY block-pool state, so every layer's
        #: decode state must live there — the same all-linear predicate
        #: prefix caching needs. Hybrid stacks keep recompute-on-resume.
        self.swap_ok = kv_host_blocks > 0 and supports_prefix_cache(cfg)
        if kv_layout == "paged":
            self.block_size = block_size
            self.blocks_per_slot = -(-self.cache_len // block_size)
            if kv_blocks is None:
                # dense-equivalent worst case: admission can never refuse
                # a request the dense layout would have taken
                kv_blocks = self.n_slots * self.blocks_per_slot
            self.allocator = BlockAllocator(kv_blocks, block_size,
                                            host_blocks=kv_host_blocks)
            # pool array includes the null block 0 (id range 0..kv_blocks)
            init_cache = functools.partial(
                self.model.init_paged_cache, self.n_slots, self.cache_len,
                kv_blocks + 1, block_size, dtype)
            self.block_tables = np.zeros(
                (self.n_slots, self.blocks_per_slot), np.int32)
        else:
            self.block_size = 0
            self.allocator = None
            self.block_tables = None
            # speculative verify writes up to spec_max rows past a slot's
            # frontier before acceptance is known; dynamic_update_slice
            # CLAMPS out-of-bounds starts (it would silently overwrite
            # valid earlier rows), so the physical slab carries a scratch
            # margin. cache_len stays the LOGICAL capacity everywhere.
            init_cache = functools.partial(
                self.model.init_cache, self.n_slots,
                self.cache_len + self.spec_max, dtype)
        if mesh is None:
            self.cache = init_cache()
        else:
            # made in place like the params: heads sharded, block axis
            # whole (tables gather it locally on every shard). Then jit
            # the step functions with explicit NamedSharding in/out
            # specs. The batch dict — tokens, pos, block tables — is
            # replicated: every shard sees the same schedule. The same
            # cache shardings tree serves the per-slot staging caches
            # non-fused layouts chunk into (same pytree structure, and
            # specs never shard the batch/length dims that differ).
            cshard = engine_cache_shardings(mesh,
                                            jax.eval_shape(init_cache))
            self.cache = jax.jit(init_cache, out_shardings=cshard)()
            if self._decode is None:
                rep = replicated(mesh)
                sharded = functools.partial(
                    jit_cache_step,
                    in_shardings=(engine_param_shardings(mesh, self.params),
                                  cshard, rep),
                    out_shardings=(rep, cshard))
                self._prefill_chunk = sharded(self.model.prefill_chunk) \
                    if self.chunked else None
                self._decode = sharded(self.model.decode_step)
                self._verify = sharded(self.model.verify_step) \
                    if supports_speculation(cfg) else None
        self.host_pool = None
        if self.swap_ok:
            self.host_pool = self._make_host_pool()
            self.allocator.spill_fn = self._spill_block
            self.allocator.unspill_fn = self._unspill_block
        self.pos = np.zeros((self.n_slots,), np.int32)
        self.pending_tok = np.zeros((self.n_slots,), np.int32)
        self.slots = [_Slot() for _ in range(self.n_slots)]
        self.waiting: List[_WaitingReq] = []
        self.n_iters = 0
        self.n_admitted = 0
        self.n_evicted = 0
        self.n_preempted = 0
        self.n_cancelled = 0
        #: host-tier accounting (docs/ARCHITECTURE.md §5): swap-mode
        #: preempts/resumes, and observed transfers as (bytes, ms)
        #: samples — the pool's swap-bandwidth calibration reads these
        #: (latency_model.fit_swap_cost)
        self.n_swap_preempts = 0
        self.n_swap_resumes = 0
        self.swap_samples: List[Tuple[int, float]] = []
        #: push-mode lifecycle hooks (docs/RUNTIME.md §11). Both fire
        #: synchronously inside engine calls, so handlers must be cheap
        #: and must not reenter the engine.
        #: ``on_token(request_id, token, index)`` — per emitted token;
        #: ``index`` is the global completion position, stable across
        #: preemption/resume (re-prefilled context tokens never refire).
        self.on_token: Optional[Callable] = None
        #: ``on_state(request_id, state)`` with state in
        #: {"prefill", "decode"} — slot assignment and prefill completion
        self.on_state: Optional[Callable] = None
        #: tokens processed by the last step() (prefill chunks + resident
        #: decode) and whether it compiled a new shape — the pool's
        #: token-cost calibration reads both (docs/RUNTIME.md §8)
        self.last_step_tokens = 0
        self.last_step_compiled = False
        #: steps that compiled a new shape, and the rows of live
        #: sequences every decode (or verify) call carried
        self.n_compiled_steps = 0
        self.n_decode_rows = 0
        self._decode_warm = False
        self.prefill_shapes: Set[Tuple[int, int]] = set()
        self._next_id = 0
        self._t0 = time.perf_counter()

    # ---- bookkeeping -----------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _note_compile(self) -> None:
        """This step dispatches a shape it has not compiled before."""
        if not self.last_step_compiled:
            self.last_step_compiled = True
            self.n_compiled_steps += 1

    def _note_tokens(self, s: _Slot, n_new: int) -> None:
        """Stamp ``first_token_s`` and fire ``on_token`` for the last
        ``n_new`` entries of ``s.tokens``. Indices are global completion
        positions: tokens emitted before a preemption live in the
        re-prefilled context (``seq_tokens[base_len:]``) and offset the
        post-resume ones, so a streaming consumer sees every position
        exactly once."""
        if s.first_token_s < 0:
            s.first_token_s = self._now()
        if self.on_token is not None:
            prior = len(s.seq_tokens) - s.base_len \
                if s.seq_tokens is not None else 0
            base = prior + len(s.tokens) - n_new
            for j in range(n_new):
                self.on_token(s.request_id,
                              int(s.tokens[len(s.tokens) - n_new + j]),
                              base + j)

    @property
    def tp_degree(self) -> int:
        """Devices this instance spans (1 = single-device engine)."""
        return int(self.mesh.shape["model"]) if self.mesh is not None else 1

    @property
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    @property
    def active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.active]

    @property
    def decoding_slots(self) -> List[int]:
        """Active slots whose prefill has completed (the rows a decode
        iteration advances)."""
        return [i for i, s in enumerate(self.slots)
                if s.active and not s.prefilling]

    @property
    def prefilling_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.prefilling]

    @property
    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens not yet prefilled: the unprocessed remainder of
        in-slot chunked prefills plus the padded length of every waiting
        prompt — a state feature for the scheduler (docs/RUNTIME.md §8)."""
        backlog = sum(len(s.seq_tokens) - s.prefill_pos
                      for s in self.slots if s.prefilling)
        for w in self.waiting:
            if w.swap is not None:
                continue  # swap resumes re-map blocks, zero prefill
            backlog += len(w.prompt) if w.prepadded else \
                self._frontend_tokens() + _bucket(len(w.prompt),
                                                  buckets=SEQ_BUCKETS)
        return backlog

    def _frontend_tokens(self) -> int:
        return self.cfg.frontend_tokens if (self.cfg.frontend is not None
                                            and not self.cfg.enc_dec) else 0

    def _seq_tokens(self, prompt_len: int, max_new: int) -> int:
        """Cache positions a sequence occupies: frontend + bucketed
        prompt + decode tokens (left-pad rows included — they are
        attended, so the paged layout must hold them too)."""
        return self._frontend_tokens() \
            + _bucket(prompt_len, buckets=SEQ_BUCKETS) + max_new

    def request_blocks(self, prompt_len: int, max_new: int) -> int:
        """Worst-case blocks a request of this shape reserves at
        admission (paged layout)."""
        room = self.cache_len - self._seq_tokens(prompt_len, 0)
        return self.allocator.blocks_for(
            self._seq_tokens(prompt_len, min(max_new, room)))

    def resume_blocks(self, req: PreemptedRequest) -> int:
        """Worst-case blocks a preempted sequence reserves on resume:
        its already-padded context plus the tokens still to emit. For a
        swap snapshot that is frontier + remaining — numerically the
        same footprint (``pos + max_new`` is invariant along a decode),
        just derived from the carried position."""
        if req.swapped:
            return self.allocator.blocks_for(req.pos + req.max_new)
        return self.allocator.blocks_for(
            len(req.seq_tokens) + req.max_new)

    def admissible(self, prompt_len: int, max_new: int,
                   pending_blocks: int = 0,
                   resume: Optional[PreemptedRequest] = None,
                   prompt: Optional[np.ndarray] = None) -> bool:
        """Could a request of this shape be admitted right now? Dense:
        a free slot. Paged: a free slot AND enough unreserved blocks
        (the real memory constraint, docs/ARCHITECTURE.md §5).
        ``pending_blocks`` debits blocks a caller has already promised
        to earlier requests it routed this pass but that the engine has
        not reserved yet (reservation happens inside ``admit``). With
        ``resume`` the block need is the preempted sequence's padded
        context instead of the fresh-prompt shape. When the actual
        ``prompt`` tokens are given and the prefix cache is on, blocks
        the cache holds LIVE are discounted — sharing them costs no
        capacity, which is exactly the admission headroom prefix caching
        buys."""
        if not self.free_slots:
            return False
        if self.kv_layout != "paged":
            return True
        if resume is not None:
            need = self.resume_blocks(resume)
            # swap resumes never map shared prefix blocks (their KV
            # comes back from the host tier wholesale), so the sharing
            # discount applies to recompute snapshots only
            if self.prefix_cache and not resume.swapped:
                need -= self._live_shared_blocks_prepadded(
                    resume.seq_tokens)
        else:
            need = self.request_blocks(prompt_len, max_new)
            if self.prefix_cache and prompt is not None:
                need -= self._live_shared_blocks(prompt)
        return self.allocator.n_available - pending_blocks >= max(0, need)

    # ---- admission -------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 8) -> int:
        """Queue a prompt; it joins a slot at the next iteration boundary.

        Raises only when the prompt can never fit a sequence's
        ``cache_len`` budget. Transient pressure (no free slot, or — in
        the paged layout — no free blocks) just keeps it queued; the
        paged admission gate is the allocator's free-block count, not
        dense ``cache_len`` headroom. A ``max_new_tokens`` that exceeds
        the remaining cache room is clamped, and the clamp is RECORDED:
        the finished ``ContinuousResult`` carries ``truncated=True`` so
        callers never mistake a shortened completion for a full one."""
        S = _bucket(len(prompt), buckets=SEQ_BUCKETS)
        F = self._frontend_tokens()
        room = self.cache_len - (F + S)
        if room < 1:
            raise ValueError(
                f"prompt bucket {S} (+{F} frontend) does not fit cache_len "
                f"{self.cache_len}")
        if self.kv_layout == "paged":
            # a reservation that exceeds the whole pool could never be
            # admitted — queuing it would livelock the FIFO head forever
            # (same boundary rule as the over-length bucket check above)
            need = self.allocator.blocks_for(
                F + S + min(max_new_tokens, room))
            if need > self.allocator.n_blocks:
                raise ValueError(
                    f"request needs {need} blocks, pool has only "
                    f"{self.allocator.n_blocks}")
        rid = self._next_id
        self._next_id += 1
        granted = min(max_new_tokens, room)
        self.waiting.append(_WaitingReq(
            rid, np.asarray(prompt, np.int32), granted, self._now(),
            requested_new=max_new_tokens,
            truncated=granted < max_new_tokens))
        return rid

    def submit_resume(self, req: PreemptedRequest) -> int:
        """Re-queue a preempted sequence (possibly from another engine
        instance of the same model). A fresh engine request id is
        allocated — the caller correlates via its own bookkeeping; the
        engine-internal ``preempt(requeue=True)`` path keeps the original
        id instead. The padded context always fits ``cache_len`` because
        ``len(seq_tokens) + max_new`` equals the original admitted
        footprint."""
        if not self.chunked:
            raise NotImplementedError(
                "preemption-resume needs the chunked-prefill path "
                "(plain token prompts)")
        if req.swapped and req.host_engine_id != id(self):
            raise ValueError(
                "swap snapshot is pinned to the engine holding its host "
                "blocks; release_swap() it there to resume elsewhere")
        rid = self._next_id
        self._next_id += 1
        self.waiting.append(_WaitingReq(
            rid, np.asarray(req.seq_tokens, np.int32), req.max_new,
            req.submit_s, prepadded=True, base_len=req.base_len,
            n_pad=req.n_pad,
            requested_new=req.requested_new, truncated=req.truncated,
            n_preempted=req.n_preempted,
            first_token_s=req.first_token_s,
            swap=req if req.swapped else None))
        return rid

    # ---- prefix cache (docs/ARCHITECTURE.md §5) --------------------------
    @staticmethod
    @functools.lru_cache(maxsize=4096)
    def _chain_keys_cached(model: str, block_size: int,
                           seq_bytes: bytes) -> Tuple[str, ...]:
        """Memoized: the router hashes the same prompt once per
        candidate instance per pass otherwise — keys depend only on
        (model, block size, padded tokens), never on engine state."""
        seq = np.frombuffer(seq_bytes, np.int32)
        keys: List[str] = []
        h = hashlib.sha1(model.encode())
        for i in range(len(seq) // block_size):
            h.update(seq[i * block_size:(i + 1) * block_size].tobytes())
            keys.append(h.hexdigest())
        return tuple(keys)

    def _chain_keys(self, seq: np.ndarray) -> Tuple[str, ...]:
        """Chain-hash key per FULL block of ``seq``: an incremental
        digest over model id + the token ids up to and including that
        block, so a key matches iff the entire padded prefix matches
        (left-pad rows are attended, hence part of the content)."""
        return self._chain_keys_cached(
            self.cfg.name, self.block_size,
            np.ascontiguousarray(seq, np.int32).tobytes())

    def _prefix_lookup(self, seq: np.ndarray
                       ) -> Tuple[List[str], int, Optional[str]]:
        """Longest cached block-aligned prefix of ``seq``. Returns
        (keys of full blocks to map SHARED, first uncached token
        position, copy-on-write source key or None).

        When the cached chain covers the whole (block-aligned) sequence,
        the last block is NOT mapped shared: its final token must be
        recomputed (the first decode step needs its logits) and the
        graft that lands it writes the whole block — so the cached block
        is duplicated into the slot's private tail block on divergence
        (``_copy_pool_block``), and writes only ever target unshared
        blocks."""
        keys = self._chain_keys(seq)
        n_hit = 0
        for k in keys:
            if not self.allocator.cached(k):
                break
            n_hit += 1
        bs = self.block_size
        if n_hit and n_hit * bs >= len(seq):
            return keys[:n_hit - 1], len(seq) - 1, keys[n_hit - 1]
        return keys[:n_hit], n_hit * bs, None

    def _padded_seq(self, prompt: np.ndarray) -> np.ndarray:
        S = _bucket(len(prompt), buckets=SEQ_BUCKETS)
        seq = np.zeros((S,), np.int32)
        seq[S - len(prompt):] = prompt
        return seq

    def cached_prefix_tokens(self, prompt: np.ndarray,
                             prepadded: bool = False) -> int:
        """Tokens of ``prompt`` the prefix cache currently holds — the
        router's prefix-affinity signal (docs/RUNTIME.md §7). Read-only:
        nothing is acquired."""
        if not self.prefix_cache:
            return 0
        seq = np.asarray(prompt, np.int32) if prepadded \
            else self._padded_seq(np.asarray(prompt, np.int32))
        _, pos0, _ = self._prefix_lookup(seq)
        return pos0

    def _live_shared_blocks_prepadded(self, seq: np.ndarray) -> int:
        """Blocks an admission of the padded sequence ``seq`` would map
        from LIVE cached blocks (refcount >= 1) — sharing those costs no
        capacity, so ``admissible`` discounts them. LRU-parked hits are
        excluded: reviving one consumes an available block anyway."""
        keys = self._chain_keys(np.asarray(seq, np.int32))
        n = 0
        for k in keys:
            if not self.allocator.cached_live(k):
                break
            n += 1
        if n and n * self.block_size >= len(seq):
            n -= 1  # last block stays private (copy-on-write)
        return n

    def _live_shared_blocks(self, prompt: np.ndarray) -> int:
        return self._live_shared_blocks_prepadded(
            self._padded_seq(np.asarray(prompt, np.int32)))

    def _copy_pool_block(self, dst: int, src: int) -> None:
        """Device-copy one physical pool block across every paged layer
        (fused-prefill copy-on-write: a fully-covering cached chain's
        tail block is duplicated into the slot's private tail block, so
        re-scoring its final token never writes a shared block). Every
        layer is paged here — the fused gate mirrors
        ``supports_prefix_cache``."""
        def copy(c, stacked: bool):
            out = dict(c)
            for key in ("k", "v"):
                pool = c[key]
                out[key] = pool.at[:, dst].set(pool[:, src]) if stacked \
                    else pool.at[dst].set(pool[src])
            return out

        new: Dict = {}
        if "units" in self.cache:
            new["units"] = tuple(copy(c, stacked=True)
                                 for c in self.cache["units"])
        if "tail" in self.cache:
            new["tail"] = tuple(copy(c, stacked=False)
                                for c in self.cache["tail"])
        self.cache = new

    # ---- host KV tier: swap data plane (docs/ARCHITECTURE.md §5) ---------
    def _make_host_pool(self) -> Dict:
        """Pinned-host mirror of the paged pool: one numpy array per
        paged k/v leaf with the block axis resized to
        ``kv_host_blocks + 1`` (host ids 1.. index it directly; row 0 is
        dead, mirroring the device null block). Only built for
        fully-pageable stacks (``swap_ok``), so every layer is paged."""
        def mirror(c, stacked: bool):
            out = {}
            for key in ("k", "v"):
                p = c[key]
                shp = (p.shape[0], self.kv_host_blocks + 1) + p.shape[2:] \
                    if stacked else \
                    (self.kv_host_blocks + 1,) + p.shape[1:]
                out[key] = np.zeros(shp, p.dtype)
            return out

        hp: Dict = {}
        if "units" in self.cache:
            hp["units"] = tuple(mirror(c, stacked=True)
                                for c in self.cache["units"])
        if "tail" in self.cache:
            hp["tail"] = tuple(mirror(c, stacked=False)
                               for c in self.cache["tail"])
        return hp

    @property
    def swap_bytes_per_block(self) -> int:
        """Bytes one block occupies across every paged layer's k+v —
        the unit the swap-cost fit is priced in."""
        if self.host_pool is None:
            return 0
        n = 0
        for c in self.host_pool.get("units", ()):
            for key in ("k", "v"):
                p = c[key]
                n += p[:, 0].nbytes
        for c in self.host_pool.get("tail", ()):
            for key in ("k", "v"):
                n += c[key][0].nbytes
        return n

    def _swap_out_blocks(self, dev_ids: List[int],
                         host_ids: List[int]) -> None:
        """Copy physical pool blocks ``dev_ids`` into host blocks
        ``host_ids``: one fused gather + ``jax.device_get`` per layer
        (batched over the whole block run, not per block). The
        device_get blocks until the transfer lands, so the recorded
        (bytes, ms) sample measures true device->host bandwidth."""
        t0 = time.perf_counter()
        didx = jnp.asarray(dev_ids, jnp.int32)
        hidx = np.asarray(host_ids, np.int64)
        n_bytes = 0
        def pull(c, hpc, stacked: bool):
            nonlocal n_bytes
            for key in ("k", "v"):
                pool = c[key]
                g = pool[:, didx] if stacked else pool[didx]
                arr = np.asarray(jax.device_get(g))
                n_bytes += arr.nbytes
                if stacked:
                    hpc[key][:, hidx] = arr
                else:
                    hpc[key][hidx] = arr

        for c, hpc in zip(self.cache.get("units", ()),
                          self.host_pool.get("units", ())):
            pull(c, hpc, stacked=True)
        for c, hpc in zip(self.cache.get("tail", ()),
                          self.host_pool.get("tail", ())):
            pull(c, hpc, stacked=False)
        self.swap_samples.append(
            (n_bytes, (time.perf_counter() - t0) * 1e3))

    def _swap_in_blocks(self, host_ids: List[int],
                        dev_ids: List[int]) -> None:
        """Copy host blocks back into freshly allocated device blocks:
        one ``device_put`` + scatter per layer, DISPATCHED without
        blocking (jax async dispatch) — the copy overlaps the admission
        bookkeeping and whatever else runs before the next forward
        touches the pool, which is the swap-in-ahead-of-resume the
        scheduler's pricing assumes."""
        t0 = time.perf_counter()
        didx = jnp.asarray(dev_ids, jnp.int32)
        hidx = np.asarray(host_ids, np.int64)
        n_bytes = 0
        def push(c, hpc, stacked: bool):
            nonlocal n_bytes
            out = dict(c)
            for key in ("k", "v"):
                rows = hpc[key][:, hidx] if stacked else hpc[key][hidx]
                n_bytes += rows.nbytes
                out[key] = c[key].at[:, didx].set(rows) if stacked \
                    else c[key].at[didx].set(rows)
            return out

        new: Dict = {}
        if "units" in self.cache:
            new["units"] = tuple(
                push(c, hpc, stacked=True)
                for c, hpc in zip(self.cache["units"],
                                  self.host_pool["units"]))
        if "tail" in self.cache:
            new["tail"] = tuple(
                push(c, hpc, stacked=False)
                for c, hpc in zip(self.cache["tail"],
                                  self.host_pool["tail"]))
        self.cache = new
        self.swap_samples.append(
            (n_bytes, (time.perf_counter() - t0) * 1e3))

    def _spill_block(self, bid: int, hid: int) -> None:
        """Allocator spill hook: demote one reclaimed prefix block."""
        self._swap_out_blocks([bid], [hid])

    def _unspill_block(self, hid: int, bid: int) -> None:
        """Allocator revival hook: promote one spilled prefix block."""
        self._swap_in_blocks([hid], [bid])

    def _graft(self, one_cache, slot: int, block_ids=None,
               skip_blocks: int = 0) -> None:
        """Scatter a freshly-prefilled single-sequence cache into the
        persistent cache. Dense layers (and windowed/recurrent state in
        both layouts) write batch row ``slot``, zero-padding each leaf up
        to the slot cache's length axes (same semantics as ``pad_cache``:
        prefill wrote [0, S), decode writes from S on). Paged linear-KV
        layers instead ``scatter_blocks`` the prefilled rows into the
        physical blocks ``block_ids`` the allocator handed this slot —
        grafting is block-granular, no ``cache_len`` copy. The first
        ``skip_blocks`` ids are prefix-cache blocks mapped SHARED: they
        already hold the right content (possibly for other sequences
        too), so the scatter starts past them — writes only ever target
        unshared blocks."""
        def graft_layer(full_c, one_c, batch_axis: int):
            def leaf(t, s):
                row = jnp.take(s, 0, axis=batch_axis)
                tslice = t.shape[:batch_axis] + t.shape[batch_axis + 1:]
                pads = [(0, want - have)
                        for have, want in zip(row.shape, tslice)]
                if any(p != (0, 0) for p in pads):
                    row = jnp.pad(row, pads)
                idx = (slice(None),) * batch_axis + (slot,)
                return t.at[idx].set(row)
            return jax.tree.map(leaf, full_c, one_c)

        def graft_paged(full_c, one_c, stacked: bool):
            ids = jnp.asarray(block_ids[skip_blocks:], jnp.int32)
            # a chunked-prefill staging cache is cache_len long; only the
            # rows the allocated blocks cover are scattered (the written
            # prefix always fits them, the rest is zeros). Shared prefix
            # blocks are skipped: start is block-aligned by construction.
            start = skip_blocks * self.block_size
            cap = len(block_ids) * self.block_size
            scatter = scatter_blocks_stacked if stacked else scatter_blocks
            return {key: scatter(full_c[key],
                                 one_c[key][:, 0, start:cap] if stacked
                                 else one_c[key][0, start:cap], ids)
                    for key in ("k", "v")}

        paged = self.kv_layout == "paged"
        _, tail_kinds = _split_layers(self.cfg)
        new: Dict = {}
        if "units" in self.cache:
            new["units"] = tuple(
                graft_paged(fc, oc, stacked=True)
                if paged and paged_layer_kind(self.cfg, kind)
                else graft_layer(fc, oc, batch_axis=1)
                for kind, fc, oc in zip(self.cfg.block_pattern,
                                        self.cache["units"],
                                        one_cache["units"]))
        if "tail" in self.cache:
            new["tail"] = tuple(
                graft_paged(fc, oc, stacked=False)
                if paged and paged_layer_kind(self.cfg, kind)
                else graft_layer(fc, oc, batch_axis=0)
                for kind, fc, oc in zip(tail_kinds, self.cache["tail"],
                                        one_cache["tail"]))
        self.cache = new

    def admit(self) -> int:
        """Move waiting prompts into free slots. Returns #admissions.

        Chunked engines (plain token prompts) only ASSIGN the slot here —
        reserve blocks, build the padded token sequence, allocate the
        staging cache — and the prefill itself advances in budget-bounded
        chunks inside ``step()`` (docs/ARCHITECTURE.md §5), so admission
        never blocks resident decodes for a whole prompt. Frontend
        models keep the single-shot inline prefill.

        Paged layout: FIFO admission is additionally gated on the
        allocator — the head request's worst-case block count
        (prompt bucket + requested decode tokens) must be reservable, or
        it (and everything behind it) stays queued until evictions free
        blocks."""
        n = 0
        free = self.free_slots
        while self.waiting and free:
            w = self.waiting[0]
            if w.swap is not None:
                if not self._admit_swap(w, free):
                    break  # FIFO: head of queue blocks on memory
                n += 1
                continue
            if w.prepadded:
                seq = w.prompt
                base_len = w.base_len
                n_pad = w.n_pad
            else:
                S = _bucket(len(w.prompt), buckets=SEQ_BUCKETS)
                F = self._frontend_tokens()
                base_len = F + S
                n_pad = S - len(w.prompt)
                seq = None
                if self.chunked:
                    seq = np.zeros((S,), np.int32)
                    seq[S - len(w.prompt):] = w.prompt
            reserved = 0
            shared_ids: List[int] = []
            pos0 = 0
            cow_key: Optional[str] = None
            if self.kv_layout == "paged":
                need_tokens = len(seq) + w.max_new if seq is not None \
                    else self._seq_tokens(len(w.prompt), w.max_new)
                need = self.allocator.blocks_for(need_tokens)
                if self.prefix_cache and seq is not None:
                    # map the longest cached block-aligned prefix at
                    # refcount+1 and reserve only the remainder — the
                    # admission-capacity gain sharing buys. acquire can
                    # refuse an LRU revival (every remaining block
                    # promised): the chain simply stops there.
                    self.n_prefix_lookups += 1
                    hit_keys, pos0, cow_key = self._prefix_lookup(seq)
                    for k in hit_keys:
                        bid = self.allocator.acquire(k)
                        if bid is None:
                            break
                        shared_ids.append(bid)
                    if len(shared_ids) < len(hit_keys):
                        pos0 = len(shared_ids) * self.block_size
                        cow_key = None
                reserved = need - len(shared_ids)
                if not self.allocator.reserve(reserved):
                    if shared_ids:
                        self.allocator.free(shared_ids)
                    break  # FIFO: head of queue blocks on memory
            self.waiting.pop(0)
            slot = free.pop(0)
            if self.chunked:
                n0 = 0
                ids: List[int] = list(shared_ids)
                if self.kv_layout == "paged":
                    # physically allocate the uncached prefill prefix
                    # now; the decode tail of the reservation is claimed
                    # lazily at block boundaries in step(). block_tables
                    # stays on the null block until the prefill lands
                    # (mid-prefill dummy decode writes must keep sinking
                    # into the null block) — fused chunks carry their own
                    # table row built from ``ids``.
                    n0 = self.allocator.blocks_for(len(seq))
                    ids += [self.allocator.alloc_reserved()
                            for _ in range(n0 - len(shared_ids))]
                staging = None
                if self.fused_prefill:
                    # fused path: chunks attend the shared prefix blocks
                    # IN PLACE through the table — no staging cache, no
                    # prefix gather. A fully-covering cached chain still
                    # copies its tail block into the slot's private tail
                    # block (copy-on-write) so re-scoring the final
                    # token writes only unshared blocks.
                    if pos0 and cow_key is not None:
                        tmp = self.allocator.acquire(cow_key)
                        if tmp is None:  # LRU revival refused: shrink
                            pos0 = len(shared_ids) * self.block_size
                        else:
                            self._copy_pool_block(ids[-1], tmp)
                            self.allocator.free([tmp])
                else:
                    # non-fused (dense / hybrid) layouts never see prefix
                    # hits — the prefix cache requires the same layer gate
                    # as fused prefill — so the staging cache starts empty
                    assert pos0 == 0 and not shared_ids
                    staging = self.model.init_cache(1, self.cache_len,
                                                    self.dtype)
                if pos0:
                    self.n_prefix_hits += 1
                    self.n_prefix_hit_tokens += pos0
                self.slots[slot] = _Slot(
                    request_id=w.request_id, remaining=w.max_new,
                    submit_s=w.submit_s, admit_s=self._now(), blocks=ids,
                    n_outstanding=reserved - (n0 - len(shared_ids)),
                    n_shared=len(shared_ids), seq_tokens=seq,
                    base_len=base_len, n_pad=n_pad, prefill_pos=pos0,
                    staging=staging,
                    requested_new=w.requested_new, truncated=w.truncated,
                    n_preempted=w.n_preempted,
                    first_token_s=w.first_token_s)
                self.pos[slot] = 0
                if self.on_state is not None:
                    self.on_state(w.request_id, "prefill")
            else:
                self._admit_inline(w, slot, reserved)
            self.n_admitted += 1
            n += 1
        return n

    def _admit_swap(self, w: _WaitingReq, free: List[int]) -> bool:
        """Admit a swap-mode resume from the head of the queue: reserve
        the full remaining footprint, immediately convert the swapped
        portion into fresh device blocks, dispatch the host->device copy
        (async — jax dispatch returns before the transfer lands, and the
        next forward orders after it), release the host blocks, and hand
        the slot straight to the decode loop at its carried frontier.
        NO prefill happens: this is the whole point of the swap tier.
        Returns False (leaving the queue untouched) when the reservation
        cannot be met — the FIFO head blocks on memory, same as a fresh
        admission."""
        req = w.swap
        need = self.allocator.blocks_for(req.pos + req.max_new)
        if not self.allocator.reserve(need):
            return False
        self.waiting.pop(0)
        slot = free.pop(0)
        n_have = len(req.host_blocks)
        ids = [self.allocator.alloc_reserved() for _ in range(n_have)]
        self._swap_in_blocks(req.host_blocks, ids)
        self.allocator.host_free(req.host_blocks)
        self.block_tables[slot, :n_have] = ids
        # prefill_pos == len(seq_tokens): the slot is DECODING from the
        # first step — the re-mapped blocks already hold rows [0, pos)
        self.slots[slot] = _Slot(
            request_id=w.request_id, remaining=req.max_new,
            n_emitted=len(req.tokens), tokens=list(req.tokens),
            submit_s=req.submit_s, admit_s=self._now(), blocks=ids,
            n_outstanding=need - n_have, n_shared=0,
            seq_tokens=np.asarray(req.seq_tokens, np.int32),
            base_len=req.base_len, n_pad=req.n_pad,
            prefill_pos=len(req.seq_tokens),
            requested_new=req.requested_new, truncated=req.truncated,
            n_preempted=req.n_preempted, first_token_s=req.first_token_s)
        self.pos[slot] = req.pos
        self.pending_tok[slot] = req.pending_tok
        if self.prefix_cache:
            # the prompt chain came back bit-identical: re-publish any
            # full prompt blocks whose keys fell out of both tiers while
            # the sequence was swapped out (first writer wins, so keys
            # still cached elsewhere are untouched)
            for i, key in enumerate(self._chain_keys(
                    self.slots[slot].seq_tokens)):
                if i < n_have:
                    self.allocator.register(key, ids[i])
        self.n_admitted += 1
        self.n_swap_resumes += 1
        if self.on_state is not None:
            self.on_state(w.request_id, "decode")
        return True

    def _admit_inline(self, w: _WaitingReq, slot: int,
                      reserved: int) -> None:
        """Legacy single-shot prefill admission (frontend models only:
        their prompt carries frontend embeds the chunk path cannot
        replicate). Blocks every resident decode for the whole prefill."""
        batch, S, _ = make_prefill_batch(self.cfg, [w.prompt])
        self.prefill_shapes.add(tuple(batch["tokens"].shape))
        logits, one_cache = self._prefill(self.params, batch)
        F = 0
        if self.cfg.frontend is not None and not self.cfg.enc_dec:
            F = batch["frontend_embeds"].shape[1]
        if self.kv_layout == "paged":
            n0 = self.allocator.blocks_for(F + S)
            ids = [self.allocator.alloc_reserved() for _ in range(n0)]
            self.block_tables[slot, :n0] = ids
            self._graft(one_cache, slot, block_ids=ids)
            self.slots[slot] = _Slot(
                request_id=w.request_id, remaining=w.max_new,
                submit_s=w.submit_s, admit_s=self._now(), blocks=ids,
                n_outstanding=reserved - n0,
                requested_new=w.requested_new, truncated=w.truncated)
        else:
            self._graft(one_cache, slot)
            self.slots[slot] = _Slot(
                request_id=w.request_id, remaining=w.max_new,
                submit_s=w.submit_s, admit_s=self._now(),
                requested_new=w.requested_new, truncated=w.truncated)
        self.pos[slot] = F + S
        self.pending_tok[slot] = int(sample_tokens(logits[0, -1, :]))
        if self.on_state is not None:
            # single-shot prefill: the slot is decoding the moment
            # admission returns (QUEUED -> DECODE, docs/RUNTIME.md §11)
            self.on_state(w.request_id, "decode")

    # ---- chunked prefill (docs/ARCHITECTURE.md §5) -----------------------
    def _prefill_step(self, budget_left: int) -> int:
        """Advance in-slot chunked prefills by at most ``budget_left``
        tokens (power-of-two chunk pieces so the compile cache stays
        bounded at one shape per piece size). Returns tokens processed.
        A slot whose last chunk lands is grafted (non-fused layouts) or
        just published (fused mode) and joins the decode batch of this
        same iteration.

        Fused mode runs each chunk directly against the paged pool: the
        batch carries the slot's block-table row (built from its
        allocated blocks — the engine-level table stays on the null
        block until the prefill completes) and the chunk's K/V lands in
        the pool as it is computed, attending shared prefix blocks in
        place."""
        done_tokens = 0
        for i in list(self.prefilling_slots):
            s = self.slots[i]
            logits = None
            while s.prefilling and budget_left > 0:
                rem = len(s.seq_tokens) - s.prefill_pos
                c = min(rem, budget_left, _MAX_CHUNK)
                c = 1 << (c.bit_length() - 1)  # largest power of two <= c
                with span("repro.engine.prefill_piece"):
                    toks = s.seq_tokens[s.prefill_pos:s.prefill_pos + c]
                    shape = (c, self.cache_len)
                    if shape not in self.prefill_shapes:
                        self.prefill_shapes.add(shape)
                        self._note_compile()
                    batch = {"tokens": jnp.asarray(toks[None, :]),
                             "pos": jnp.asarray([s.prefill_pos], jnp.int32)}
                    if self.fused_prefill:
                        tbl = np.zeros((1, self.blocks_per_slot), np.int32)
                        tbl[0, :len(s.blocks)] = s.blocks
                        batch["block_tables"] = jnp.asarray(tbl)
                        logits, self.cache = self._prefill_chunk(
                            self.params, self.cache, batch)
                    else:
                        logits, s.staging = self._prefill_chunk(
                            self.params, s.staging, batch)
                self.n_prefill_pad_rows += max(
                    0, min(s.prefill_pos + c, s.n_pad) - s.prefill_pos)
                s.prefill_pos += c
                budget_left -= c
                done_tokens += c
            if logits is not None and not s.prefilling:
                self._finish_prefill(i, logits)
        self.n_prefill_chunk_tokens += done_tokens
        return done_tokens

    def _finish_prefill(self, slot: int, logits) -> None:
        """Last chunk landed: point the block table at the allocated
        prefix blocks and hand the slot to the decode loop. Non-fused
        layouts graft the staging cache into the slot first (skipping
        the shared prefix blocks, which are immutable); in fused mode
        the chunks already wrote the pool through the table, so there is
        nothing to scatter. With the prefix cache on, the now-complete
        full prompt blocks are published under their chain keys so later
        same-prefix admissions can share them."""
        s = self.slots[slot]
        if self.kv_layout == "paged":
            self.block_tables[slot, :len(s.blocks)] = s.blocks
            if not self.fused_prefill:
                self._graft(s.staging, slot, block_ids=s.blocks,
                            skip_blocks=s.n_shared)
            if self.prefix_cache:
                for i, key in enumerate(self._chain_keys(s.seq_tokens)):
                    if i >= s.n_shared:
                        self.allocator.register(key, s.blocks[i])
        else:
            self._graft(s.staging, slot)
        s.staging = None
        self.pos[slot] = s.prefill_pos
        with span("repro.engine.prefill_readback"):
            self.pending_tok[slot] = int(sample_tokens(logits[0, -1, :]))
        if self.on_state is not None:
            self.on_state(s.request_id, "decode")

    # ---- preemption (docs/RUNTIME.md §8) ---------------------------------
    def preemption_candidates(self) -> List[Tuple[int, int, int]]:
        """(slot, request_id, freeable_blocks) for every preemptible
        resident — decoding slots only, never a mid-chunk prefill (its
        staging work would be thrown away and re-bought immediately).
        A block mapped by other sequences too (refcount > 1) does not
        free capacity when this slot releases its reference, so only
        sole-reference blocks count as freeable."""
        if not self.chunked:
            return []
        out = []
        for i, s in enumerate(self.slots):
            if not s.active or s.prefilling:
                continue
            freeable = s.n_outstanding
            if self.kv_layout == "paged":
                freeable += sum(1 for b in s.blocks
                                if self.allocator.refcount(b) == 1)
            else:
                freeable += len(s.blocks)
            out.append((i, s.request_id, freeable))
        return out

    def can_swap(self, slot: int) -> bool:
        """Could the sequence in ``slot`` be preempted with
        ``mode="swap"`` right now? Needs the host tier (``swap_ok``:
        configured AND every layer's decode state in the block pool) and
        enough available host blocks to hold the slot's KV."""
        if not self.swap_ok:
            return False
        s = self.slots[slot]
        return s.active and not s.prefilling \
            and self.allocator.n_host_available >= len(s.blocks)

    def preempt(self, slot: int, requeue: bool = True,
                mode: str = "recompute") -> PreemptedRequest:
        """Evict the resident sequence in ``slot`` back to a waiting
        queue, returning its blocks (and the unconsumed reservation
        tail) to the allocator immediately.

        ``mode="recompute"`` (default): the snapshot resumes by
        re-prefilling the padded prompt plus every token emitted so
        far — greedy output is token-identical to an uninterrupted run
        (asserted in tests/test_preemption.py).

        ``mode="swap"``: the slot's KV blocks are copied to the host
        tier first (one batched device_get per layer), so resume only
        re-maps them onto fresh device blocks — no recompute at all.
        Emitted tokens, decode position and the pending token ride in
        the snapshot verbatim; output stays token-identical because the
        resumed state IS the preempted state (fuzzed against the
        recompute path in tests/test_engine_fuzz.py). Raises when
        ``can_swap(slot)`` does not hold — callers price and pick the
        mode (docs/RUNTIME.md §8), the engine never falls back silently.

        ``requeue=True`` reinserts at the head of THIS engine's FIFO
        (standalone use); a pool passes ``requeue=False`` and routes the
        snapshot through its own EDF queue (``submit_resume``)."""
        if mode not in ("recompute", "swap"):
            raise ValueError(f"unknown preempt mode {mode!r}")
        s = self.slots[slot]
        if not s.active:
            raise ValueError(f"slot {slot} holds no sequence")
        if s.prefilling:
            raise ValueError("cannot preempt a mid-chunk prefill")
        if not self.chunked:
            raise NotImplementedError(
                "preemption needs the chunked-prefill path "
                "(plain token prompts)")
        if mode == "swap":
            if not self.can_swap(slot):
                raise ValueError(
                    f"slot {slot} is not swappable (host tier off, "
                    "non-pageable stack, or host pool full)")
            host_ids = self.allocator.swap_out_alloc(len(s.blocks))
            assert host_ids is not None  # can_swap checked availability
            self._swap_out_blocks(s.blocks, host_ids)
            req = PreemptedRequest(
                s.request_id, s.seq_tokens, base_len=s.base_len,
                max_new=s.remaining, submit_s=s.submit_s,
                requested_new=s.requested_new, truncated=s.truncated,
                n_preempted=s.n_preempted + 1,
                first_token_s=s.first_token_s, n_pad=s.n_pad,
                tokens=list(s.tokens), pos=int(self.pos[slot]),
                pending_tok=int(self.pending_tok[slot]),
                host_blocks=host_ids, host_engine_id=id(self))
            self.n_swap_preempts += 1
        else:
            seq = np.concatenate([s.seq_tokens,
                                  np.asarray(s.tokens, np.int32)])
            req = PreemptedRequest(
                s.request_id, seq, base_len=s.base_len, max_new=s.remaining,
                submit_s=s.submit_s, requested_new=s.requested_new,
                truncated=s.truncated, n_preempted=s.n_preempted + 1,
                first_token_s=s.first_token_s, n_pad=s.n_pad)
        if self.kv_layout == "paged":
            self.allocator.free(s.blocks)
            self.allocator.unreserve(s.n_outstanding)
            self.block_tables[slot, :] = 0
        self.pos[slot] = 0
        self.slots[slot] = _Slot()
        self.n_preempted += 1
        if requeue:
            self.waiting.insert(0, _WaitingReq(
                req.request_id, req.seq_tokens, req.max_new, req.submit_s,
                prepadded=True, base_len=req.base_len, n_pad=req.n_pad,
                requested_new=req.requested_new, truncated=req.truncated,
                n_preempted=req.n_preempted,
                first_token_s=req.first_token_s,
                swap=req if req.swapped else None))
        return req

    def release_swap(self, req: PreemptedRequest) -> PreemptedRequest:
        """Convert a swap snapshot back into a recompute snapshot,
        freeing its host blocks — the escape hatch when the owning
        engine is draining/retired and the snapshot must resume
        elsewhere. Token identity is preserved: the recompute context is
        the padded prompt plus the emitted tokens, and greedy re-prefill
        regenerates the dropped pending token deterministically."""
        if not req.swapped:
            return req
        if req.host_engine_id != id(self):
            raise ValueError(
                "swap snapshot is pinned to a different engine's host "
                "pool")
        self.allocator.host_free(req.host_blocks)
        seq = np.concatenate([req.seq_tokens,
                              np.asarray(req.tokens, np.int32)])
        return PreemptedRequest(
            req.request_id, seq, base_len=req.base_len,
            max_new=req.max_new, submit_s=req.submit_s,
            requested_new=req.requested_new, truncated=req.truncated,
            n_preempted=req.n_preempted,
            first_token_s=req.first_token_s, n_pad=req.n_pad)

    # ---- cancellation (docs/RUNTIME.md §11) ------------------------------
    def cancel(self, request_id: int) -> Optional[ContinuousResult]:
        """Tear down ``request_id`` at WHATEVER phase it is in — queued,
        mid-chunk prefill, decoding, or requeued-after-preemption — and
        free its memory synchronously: blocks (shared prefix references
        included) return to the allocator and the unconsumed reservation
        tail is cancelled before this returns, so a mass disconnect
        frees capacity for the next admission pass, not after a drain.

        Returns a ``ContinuousResult`` with ``cancelled=True`` carrying
        the partial completion, or ``None`` if the id is not live here
        (already finished, or resident elsewhere in a pool). Unlike
        ``preempt`` this is legal mid-prefill: the staging cache /
        partially written pool blocks are simply discarded — nothing was
        registered in the prefix cache yet, so no key can reference
        them."""
        for qi, w in enumerate(self.waiting):
            if w.request_id == request_id:
                self.waiting.pop(qi)
                # a requeued preemption carries its pre-eviction tokens
                # in the prepadded context (recompute) or in the swap
                # snapshot; a fresh prompt has none
                if w.swap is not None:
                    self.allocator.host_free(w.swap.host_blocks)
                    emitted = np.asarray(w.swap.tokens, np.int32)
                else:
                    emitted = w.prompt[w.base_len:] if w.prepadded \
                        else np.zeros((0,), np.int32)
                self.n_cancelled += 1
                return ContinuousResult(
                    request_id, np.asarray(emitted, np.int32),
                    submit_s=w.submit_s, admit_s=-1.0,
                    finish_s=self._now(), n_iters=0,
                    truncated=w.truncated, n_preempted=w.n_preempted,
                    first_token_s=w.first_token_s, cancelled=True)
        for i, s in enumerate(self.slots):
            if not (s.active and s.request_id == request_id):
                continue
            emitted = s.tokens
            if s.seq_tokens is not None and s.base_len < len(s.seq_tokens):
                emitted = list(s.seq_tokens[s.base_len:]) + s.tokens
            res = ContinuousResult(
                request_id, np.asarray(emitted, np.int32),
                submit_s=s.submit_s, admit_s=s.admit_s,
                finish_s=self._now(), n_iters=len(emitted),
                truncated=s.truncated, n_preempted=s.n_preempted,
                n_spec_proposed=s.n_spec_proposed,
                n_spec_accepted=s.n_spec_accepted,
                first_token_s=s.first_token_s, cancelled=True)
            if self.kv_layout == "paged":
                # same free path as eviction: refcounted frees park
                # still-registered prefix blocks in the LRU pool
                self.allocator.free(s.blocks)
                self.allocator.unreserve(s.n_outstanding)
                self.block_tables[i, :] = 0
            self.pos[i] = 0
            self.slots[i] = _Slot()
            self.n_cancelled += 1
            self.n_evicted += 1
            return res
        return None

    # ---- iteration -------------------------------------------------------
    def step(self) -> List[ContinuousResult]:
        """One engine iteration: admit, advance chunked prefills under
        the per-iteration token budget, then ONE decode iteration over
        all slots; evicts after.

        The token budget caps prefill-chunk tokens plus resident decode
        tokens, so iteration latency stays bounded no matter how long
        the queued prompts are (docs/ARCHITECTURE.md §5). Returns the
        sequences that finished this iteration. Inactive slots decode a
        dummy token in place (their cache row is masked by ``pos`` and
        overwritten at the next admission), keeping the compiled decode
        shape fixed at (n_slots, 1). Each part runs under its profiler
        span (``repro.engine.*``, ``tracing.py``).
        """
        self.last_step_compiled = False
        with span("repro.engine.admit"):
            self.admit()
        n_dec = len(self.decoding_slots)
        budget = self.token_budget if self.token_budget is not None \
            else 1 << 62
        eff_k = self._effective_spec_k(n_dec, budget)
        self.last_step_tokens = self._prefill_step(
            max(0, budget - n_dec * (1 + eff_k)))
        active = self.decoding_slots
        if not active:
            return []
        if eff_k > 0:
            return self._step_speculative(active, eff_k)
        self.last_step_tokens += len(active)
        self.n_decode_rows += len(active)
        with span("repro.engine.emit"):
            for i in active:
                s = self.slots[i]
                s.tokens.append(int(self.pending_tok[i]))
                s.n_emitted += 1
                s.remaining -= 1
                self._note_tokens(s, 1)
        with span("repro.engine.decode_batch"):
            batch = {"tokens": jnp.asarray(self.pending_tok[:, None]),
                     "pos": jnp.asarray(self.pos)}
            if self.kv_layout == "paged":
                # alloc-on-decode-boundary: the write at ``pos`` needs its
                # block mapped before the decode runs; the admission
                # reservation guarantees the free list cannot be empty
                bs = self.block_size
                for i in active:
                    s = self.slots[i]
                    while self.pos[i] >= len(s.blocks) * bs:
                        bid = self.allocator.alloc_reserved()
                        s.n_outstanding -= 1
                        self.block_tables[i, len(s.blocks)] = bid
                        s.blocks.append(bid)
                batch["block_tables"] = jnp.asarray(self.block_tables)
        if not self._decode_warm:
            self._decode_warm = True
            self._note_compile()
        with span("repro.engine.decode_dispatch"):
            logits, self.cache = self._decode(self.params, self.cache, batch)
        with span("repro.engine.decode_readback"):
            nxt = sample_tokens(logits[:, -1, :])
        self.n_iters += 1
        with span("repro.engine.retire"):
            return self._retire(active, nxt)

    def _retire(self, active: List[int],
                nxt: np.ndarray) -> List[ContinuousResult]:
        """After a decode: finish and evict every slot with nothing left
        to emit (or no cache room left), advance the others by the token
        just sampled."""
        finished: List[ContinuousResult] = []
        now = self._now()
        for i in active:
            s = self.slots[i]
            # stay inside the cache: clip sequences at capacity (and
            # record the truncation — the caller asked for more tokens)
            if self.pos[i] + 1 >= self.cache_len and s.remaining > 0:
                s.truncated = True
                s.remaining = 0
            if s.remaining <= 0:
                emitted = s.tokens
                if s.seq_tokens is not None and s.base_len < len(s.seq_tokens):
                    # resumed sequence: tokens emitted before the
                    # preemption live in the re-prefilled context
                    emitted = list(s.seq_tokens[s.base_len:]) + s.tokens
                finished.append(ContinuousResult(
                    s.request_id, np.asarray(emitted, np.int32),
                    submit_s=s.submit_s, admit_s=s.admit_s, finish_s=now,
                    n_iters=len(emitted), truncated=s.truncated,
                    n_preempted=s.n_preempted,
                    first_token_s=s.first_token_s))
                if self.kv_layout == "paged":
                    # free-on-evict: blocks return to the pool, the
                    # unconsumed tail of the reservation is cancelled
                    self.allocator.free(s.blocks)
                    self.allocator.unreserve(s.n_outstanding)
                    self.block_tables[i, :] = 0
                    self.pos[i] = 0
                self.slots[i] = _Slot()
                self.n_evicted += 1
            else:
                self.pending_tok[i] = nxt[i]
                self.pos[i] = self.pos[i] + 1
        return finished

    # ---- speculative decoding (docs/ARCHITECTURE.md §5) ------------------
    def _effective_spec_k(self, n_dec: int, budget: int) -> int:
        """Speculation depth this iteration actually runs: the current
        ``spec_k`` clamped to the compiled ``spec_max``, degraded to fit
        ``n_dec * (1 + k)`` decode tokens inside the iteration token
        budget (the engine-level collapse to k=0 under pressure — the
        scheduler's guard does the same degradation proactively)."""
        k = min(max(0, self.spec_k), self.spec_max)
        if k and n_dec and self.token_budget is not None:
            k = max(0, min(k, budget // n_dec - 1))
        return k

    def _step_speculative(self, active: List[int],
                          k: int) -> List[ContinuousResult]:
        """One speculative iteration over the decoding slots: propose up
        to ``k`` draft tokens per slot from its own context, score the
        pending token + drafts in ONE ``(n_slots, 1+k)`` verify forward,
        accept the longest draft prefix matching the verify argmax, and
        roll the KV state back over the rejected tail — dense rows are
        masked by ``pos`` (never attended before being overwritten);
        paged blocks are freed back to the allocator at block
        granularity. Greedy output is token-identical to k=0 because
        acceptance IS greedy equality: every emitted token equals the
        argmax a sequential decode would have produced (asserted in
        tests/test_speculative.py and fuzzed in tests/test_engine_fuzz.py).

        Speculative writes start at ``pos >= prefill_len``, past every
        shared/registered prefix block, so rollback only ever frees
        sole-reference decode-region blocks (asserted in
        :meth:`_trim_blocks`)."""
        W = 1 + k
        with span("repro.engine.decode_batch"):
            toks, k_eff, batch = self._verify_batch(active, k)
        if W not in self._spec_shapes:
            self._spec_shapes.add(W)
            self._note_compile()
        self.n_decode_rows += len(active)
        with span("repro.engine.decode_dispatch"):
            logits, self.cache = self._verify(self.params, self.cache, batch)
        with span("repro.engine.decode_readback"):
            nxt_all = sample_tokens(logits)  # (n_slots, W) verify argmax
        self.n_iters += 1
        self.n_spec_steps += 1
        with span("repro.engine.retire"):
            return self._accept(active, toks, k_eff, nxt_all)

    def _verify_batch(self, active: List[int], k: int):
        """Host side of a speculative iteration: each slot's pending
        token and drafts, how many drafts each slot runs, and the verify
        forward's batch."""
        toks = np.zeros((self.n_slots, 1 + k), np.int32)
        k_eff: Dict[int, int] = {}
        for i in active:
            s = self.slots[i]
            # participation cap: never draft past the request's remaining
            # tokens or the logical cache capacity (rows j > k_i of the
            # fixed-width forward land in the null block / scratch margin
            # and their logits are ignored)
            ki = max(0, min(k, s.remaining - 1,
                            self.cache_len - 1 - int(self.pos[i])))
            k_eff[i] = ki
            toks[i, 0] = self.pending_tok[i]
            if ki > 0:
                context = np.concatenate(
                    [s.seq_tokens, np.asarray(s.tokens, np.int32),
                     [self.pending_tok[i]]]) \
                    if s.seq_tokens is not None \
                    else np.asarray(s.tokens + [self.pending_tok[i]],
                                    np.int32)
                toks[i, 1:1 + ki] = self.proposer.propose(context, ki)
        batch = {"tokens": jnp.asarray(toks),
                 "pos": jnp.asarray(self.pos)}
        if self.kv_layout == "paged":
            # pre-allocate blocks covering each slot's deepest draft row
            # (the admission reservation covers them: pos + k_i is within
            # the granted footprint), then hand the forward a block table
            # padded with null columns so rows past cache_len can never
            # clip into a live block (JAX clamps out-of-bounds gathers)
            bs = self.block_size
            for i in active:
                s = self.slots[i]
                top = int(self.pos[i]) + k_eff[i]
                while top >= len(s.blocks) * bs:
                    bid = self.allocator.alloc_reserved()
                    s.n_outstanding -= 1
                    self.block_tables[i, len(s.blocks)] = bid
                    s.blocks.append(bid)
            pad = -(-self.spec_max // bs)
            vt = np.zeros((self.n_slots, self.blocks_per_slot + pad),
                          np.int32)
            vt[:, :self.blocks_per_slot] = self.block_tables
            batch["block_tables"] = jnp.asarray(vt)
        return toks, k_eff, batch

    def _accept(self, active: List[int], toks: np.ndarray,
                k_eff: Dict[int, int],
                nxt_all: np.ndarray) -> List[ContinuousResult]:
        """After a verify forward: emit each slot's pending token and its
        accepted drafts, roll back the rejected tail, finish and evict
        the slots that are done."""
        finished: List[ContinuousResult] = []
        now = self._now()
        for i in active:
            s = self.slots[i]
            ki = k_eff[i]
            a = 0
            while a < ki and toks[i, a + 1] == nxt_all[i, a]:
                a += 1
            self.n_spec_proposed += ki
            self.n_spec_accepted += a
            s.n_spec_proposed += ki
            s.n_spec_accepted += a
            self.last_step_tokens += 1 + ki
            # emit the pending token plus the accepted drafts
            s.tokens.extend(int(t) for t in toks[i, :a + 1])
            s.n_emitted += a + 1
            s.remaining -= a + 1
            self._note_tokens(s, a + 1)
            new_pos = int(self.pos[i]) + a + 1
            if self.kv_layout == "paged":
                self._trim_blocks(i, new_pos)
            if new_pos >= self.cache_len and s.remaining > 0:
                s.truncated = True
                s.remaining = 0
            if s.remaining <= 0:
                emitted = s.tokens
                if s.seq_tokens is not None \
                        and s.base_len < len(s.seq_tokens):
                    emitted = list(s.seq_tokens[s.base_len:]) + s.tokens
                finished.append(ContinuousResult(
                    s.request_id, np.asarray(emitted, np.int32),
                    submit_s=s.submit_s, admit_s=s.admit_s, finish_s=now,
                    n_iters=len(emitted), truncated=s.truncated,
                    n_preempted=s.n_preempted,
                    n_spec_proposed=s.n_spec_proposed,
                    n_spec_accepted=s.n_spec_accepted,
                    first_token_s=s.first_token_s))
                if self.kv_layout == "paged":
                    self.allocator.free(s.blocks)
                    self.allocator.unreserve(s.n_outstanding)
                    self.block_tables[i, :] = 0
                    self.pos[i] = 0
                self.slots[i] = _Slot()
                self.n_evicted += 1
            else:
                # the model's next token after the accepted prefix — what
                # sequential decode would have produced as the new pending
                self.pending_tok[i] = nxt_all[i, a]
                self.pos[i] = new_pos
        return finished

    def _trim_blocks(self, slot: int, pos: int) -> None:
        """Block-granular KV rollback: free the trailing blocks past the
        last committed row ``pos - 1`` back to the allocator and restore
        the admission reservation, leaving the slot's block list exactly
        what an unspeculated decode at ``pos`` would hold (the
        alloc-on-decode-boundary loop re-claims them as the frontier
        advances). Only sole-reference decode-region blocks are ever
        trimmed: shared and registered prefix blocks cover rows below
        the prefill length, and ``pos`` never rolls back past it."""
        s = self.slots[slot]
        keep = self.allocator.blocks_for(pos)
        if keep >= len(s.blocks):
            return
        drop = s.blocks[keep:]
        for b in drop:
            assert self.allocator.refcount(b) == 1, \
                f"rollback would free block {b} with refcount " \
                f"{self.allocator.refcount(b)}"
        del s.blocks[keep:]
        self.block_tables[slot, keep:keep + len(drop)] = 0
        self.allocator.free(drop)
        ok = self.allocator.reserve(len(drop))
        assert ok, "re-reserving just-freed blocks cannot fail"
        s.n_outstanding += len(drop)

    def rollback(self, slot: int, n: int) -> None:
        """Undo the last ``n`` emitted tokens of the sequence in
        ``slot``: the committed context shrinks by ``n``, the pending
        token becomes what it was before those emissions, and (paged)
        the trailing KV blocks past the new frontier are freed back to
        the allocator with the reservation restored — the primitive the
        speculative path's rejection handling is built on, exposed for
        the property tests (tests/test_speculative.py). Re-decoding from
        the rolled-back state is token-identical: greedy decode is
        deterministic, and rows at or past the new ``pos`` are never
        attended before being overwritten."""
        if not supports_speculation(self.cfg):
            raise ValueError(
                f"{self.cfg.name}: rollback needs rewindable decode "
                "state (linear attention only)")
        s = self.slots[slot]
        if not s.active or s.prefilling:
            raise ValueError(f"slot {slot} is not decoding")
        if not 1 <= n <= len(s.tokens):
            raise ValueError(
                f"can roll back 1..{len(s.tokens)} tokens, got {n}")
        new_pos = int(self.pos[slot]) - n
        self.pending_tok[slot] = s.tokens[-n]
        del s.tokens[-n:]
        s.n_emitted -= n
        s.remaining += n
        self.pos[slot] = new_pos
        if self.kv_layout == "paged":
            self._trim_blocks(slot, new_pos)

    @property
    def spec_accept_rate(self) -> float:
        """Draft tokens accepted as a fraction of drafts proposed over
        the engine's lifetime — the scheduler's acceptance feature (0.0
        before any speculative step)."""
        return self.n_spec_accepted / self.n_spec_proposed \
            if self.n_spec_proposed else 0.0

    def run(self, prompts: List[np.ndarray], max_new_tokens: int = 8,
            max_iters: int = 10_000) -> List[ContinuousResult]:
        """Submit ``prompts`` and iterate until every sequence finishes."""
        for p in prompts:
            self.submit(p, max_new_tokens)
        done: List[ContinuousResult] = []
        while (self.waiting or self.active_slots) and max_iters > 0:
            done.extend(self.step())
            max_iters -= 1
        done.sort(key=lambda r: r.request_id)
        return done

    # ---- KV occupancy accounting (docs/ARCHITECTURE.md §5) --------------
    @property
    def kv_used_tokens(self) -> int:
        """Cache positions live sequences actually occupy (written or
        about to be written next iteration); mid-prefill sequences count
        the staging tokens their chunks have produced so far."""
        return int(sum(int(self.pos[i]) + 1 for i in self.decoding_slots)
                   + sum(self.slots[i].prefill_pos
                         for i in self.prefilling_slots))

    @property
    def kv_allocated_tokens(self) -> int:
        """Cache positions *committed*: the whole slab for the dense
        layout, LIVE blocks × block_size for the paged one — a block
        shared by N sequences is counted ONCE, and evicted-but-cached
        LRU blocks are reclaimable so they do not count."""
        if self.kv_layout == "paged":
            return self.allocator.n_live * self.block_size
        return self.n_slots * self.cache_len

    @property
    def kv_unique_used_tokens(self) -> int:
        """Distinct physical cache positions live sequences occupy:
        per-block coverage with shared blocks counted once (the paged
        counterpart of ``kv_used_tokens``, which stays per-sequence
        logical — under sharing the logical sum can exceed the physical
        footprint, which is the whole point)."""
        if self.kv_layout != "paged":
            return self.kv_used_tokens
        bs = self.block_size
        cov: Dict[int, int] = {}
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            if s.prefilling:
                # fused chunks write the pool directly, so every
                # prefilled token occupies its block; non-fused hybrid
                # layouts hold nothing in the pool until the graft (the
                # chunked prefix lives in the staging cache)
                c = s.prefill_pos if self.fused_prefill \
                    else min(s.prefill_pos, s.n_shared * bs)
            else:
                c = int(self.pos[i]) + 1
            for idx, bid in enumerate(s.blocks):
                t = min(bs, c - idx * bs)
                if t <= 0:
                    break
                cov[bid] = max(cov.get(bid, 0), t)
        return sum(cov.values())

    def kv_block_mapping(self) -> Tuple[int, int]:
        """(logical block mappings, distinct physical blocks) over the
        active slots — the pool sums these across instances to price
        effective blocks without reaching into slot internals."""
        mapped = [b for s in self.slots if s.active for b in s.blocks]
        return len(mapped), len(set(mapped))

    @property
    def kv_shared_frac(self) -> float:
        """Fraction of live block *mappings* backed by a physical block
        some other sequence also maps: 1 - distinct/logical. 0 without
        sharing."""
        logical, distinct = self.kv_block_mapping()
        return 1.0 - distinct / logical if logical else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Prompt tokens served from the prefix cache as a fraction of
        all prompt tokens processed (hit + chunked-prefill) over the
        engine's lifetime."""
        total = self.n_prefix_hit_tokens + self.n_prefill_chunk_tokens
        return self.n_prefix_hit_tokens / total if total else 0.0

    @property
    def kv_free_tokens(self) -> int:
        """Admission headroom in tokens: unreserved blocks (paged) or
        free slots × cache_len (dense)."""
        if self.kv_layout == "paged":
            return self.allocator.n_available * self.block_size
        return len(self.free_slots) * self.cache_len

    def stats(self) -> Dict[str, float]:
        """Counters + KV occupancy metrics, so benchmarks can report
        dense-vs-paged waste without poking engine internals.
        ``kv_waste_frac`` counts shared blocks ONCE (unique physical
        coverage over live allocation); ``kv_used_tokens`` stays
        per-sequence logical, so used/allocated can exceed 1 under
        sharing — that surplus is the capacity the prefix cache buys."""
        used = float(self.kv_used_tokens)
        uniq = float(self.kv_unique_used_tokens)
        alloc = float(self.kv_allocated_tokens)
        return {
            "n_iters": float(self.n_iters),
            "n_compiled_steps": float(self.n_compiled_steps),
            "n_decode_rows": float(self.n_decode_rows),
            "n_prefill_chunk_tokens": float(self.n_prefill_chunk_tokens),
            "n_prefill_pad_rows": float(self.n_prefill_pad_rows),
            "n_admitted": float(self.n_admitted),
            "n_evicted": float(self.n_evicted),
            "n_prefill_shapes": float(len(self.prefill_shapes)),
            "n_slots": float(self.n_slots),
            "kv_used_tokens": used,
            "kv_allocated_tokens": alloc,
            "kv_waste_frac": 1.0 - uniq / alloc if alloc else 0.0,
            "kv_reserved_tokens": float(
                self.allocator.n_reserved * self.block_size
                if self.kv_layout == "paged" else 0),
            "kv_cached_tokens": float(
                self.allocator.n_cached * self.block_size
                if self.kv_layout == "paged" else 0),
            "kv_shared_frac": self.kv_shared_frac,
            "prefix_hit_rate": self.prefix_hit_rate,
            "n_prefix_hits": float(self.n_prefix_hits),
            "queue_depth": float(len(self.waiting)),
            "n_preempted": float(self.n_preempted),
            "n_cancelled": float(self.n_cancelled),
            # host KV tier (docs/ARCHITECTURE.md §5)
            "kv_host_blocks": float(self.kv_host_blocks),
            "kv_host_free": float(
                self.allocator.n_host_free
                if self.kv_layout == "paged" else 0),
            "kv_host_live": float(
                self.allocator.n_host_live
                if self.kv_layout == "paged" else 0),
            "kv_host_cached": float(
                self.allocator.n_host_cached
                if self.kv_layout == "paged" else 0),
            "n_swap_preempts": float(self.n_swap_preempts),
            "n_swap_resumes": float(self.n_swap_resumes),
            "n_spilled": float(
                self.allocator.n_spilled
                if self.kv_layout == "paged" else 0),
            "n_unspilled": float(
                self.allocator.n_unspilled
                if self.kv_layout == "paged" else 0),
            "prefill_backlog_tokens": float(self.prefill_backlog_tokens),
            "token_budget": float(self.token_budget or 0),
            "spec_k": float(min(max(0, self.spec_k), self.spec_max)),
            "spec_accept_rate": self.spec_accept_rate,
            "n_spec_proposed": float(self.n_spec_proposed),
            "n_spec_accepted": float(self.n_spec_accepted),
            "n_spec_steps": float(self.n_spec_steps),
            "tp_degree": float(self.tp_degree),
        }
