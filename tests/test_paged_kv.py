"""Paged KV-cache subsystem: block allocator invariants, pure
gather/scatter block surgery, prefill-graft round trips across layer
kinds, token identity against the round engine, and the pool's shared
block budget (docs/ARCHITECTURE.md §5, docs/RUNTIME.md §7)."""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from conftest import KIND_CFGS, TINY, flat_live_blocks
from repro.config.base import ModelConfig
from repro.models.transformer import (gather_blocks, paged_layer_kind,
                                      scatter_blocks)
from repro.serving.engine import (DECODE_KV_BUCKETS, BlockAllocator,
                                  ContinuousBatchingEngine, InferenceEngine)
from repro.serving.runtime import ModelInstancePool


# ------------------------------------------------------------ allocator
def test_allocator_invariants():
    al = BlockAllocator(8, block_size=16)
    assert al.n_free == 8 and al.n_available == 8
    assert al.blocks_for(0) == 0
    assert al.blocks_for(1) == 1
    assert al.blocks_for(16) == 1
    assert al.blocks_for(17) == 2
    assert al.reserve(5)
    assert al.n_available == 3 and al.n_free == 8
    ids = [al.alloc_reserved() for _ in range(3)]
    assert len(set(ids)) == 3 and all(0 < i <= 8 for i in ids)
    assert al.n_free == 5 and al.n_reserved == 2 and al.n_available == 3
    assert not al.reserve(4)  # only 3 available
    al.free(ids)
    al.unreserve(2)
    assert al.n_free == 8 and al.n_available == 8 and al.n_reserved == 0


def test_allocator_never_hands_out_null_block():
    al = BlockAllocator(4, block_size=8)
    assert al.reserve(4)
    ids = [al.alloc_reserved() for _ in range(4)]
    assert sorted(ids) == [1, 2, 3, 4]  # id 0 (null) never allocated
    with pytest.raises(AssertionError):
        al.alloc_reserved()  # nothing reserved any more


# ----------------------------------------- refcount / prefix-cache
def test_refcount_decrement_to_zero_frees_exactly_once():
    """A block shared by two sequences frees on the SECOND release, and
    only once: unregistered blocks return to the free list, registered
    ones park in the cached-LRU pool (still reclaimable)."""
    al = BlockAllocator(4, block_size=8)
    assert al.reserve(2)
    a, b = al.alloc_reserved(), al.alloc_reserved()
    al.register("k-a", a)
    shared = al.acquire("k-a")
    assert shared == a and al.refcount(a) == 2
    assert al.n_live == 2  # refcounted blocks count ONCE
    al.free([a])
    assert al.refcount(a) == 1 and al.n_live == 2  # still held
    al.free([a])           # second (last) reference: parks in LRU
    assert al.refcount(a) == 0 and al.n_cached == 1 and al.n_free == 2
    al.free([b])           # unregistered: straight to the free list
    assert al.n_free == 3 and al.n_cached == 1
    assert al.n_free + al.n_cached + al.n_live == al.n_blocks


def test_double_free_still_raises_under_sharing():
    """More frees than references is a bug even when the block was
    legitimately shared for a while."""
    al = BlockAllocator(4, block_size=8)
    assert al.reserve(1)
    a = al.alloc_reserved()
    al.register("k", a)
    assert al.acquire("k") == a    # refcount 2
    al.free([a])
    al.free([a])                   # refcount 0: parked in LRU
    with pytest.raises(ValueError):
        al.free([a])               # third free of two references
    with pytest.raises(ValueError):
        al.free([a, a])            # duplicate within one call


def test_reserve_cancel_accounting_unchanged_by_cache_hits():
    """Cache hits must not leak into reservation accounting: acquiring a
    LIVE shared block costs nothing, and unreserve symmetry holds."""
    al = BlockAllocator(8, block_size=8)
    assert al.reserve(3)
    ids = [al.alloc_reserved() for _ in range(3)]
    for i, bid in enumerate(ids):
        al.register(f"k{i}", bid)
    avail0, res0 = al.n_available, al.n_reserved
    assert al.reserve(2)
    shared = [al.acquire(f"k{i}") for i in range(3)]  # live: free hits
    assert shared == ids
    assert al.n_available == avail0 - 2       # only the reserve moved it
    assert al.n_reserved == res0 + 2
    al.unreserve(2)
    al.free(shared)
    assert al.n_available == avail0 and al.n_reserved == res0


def test_lru_reclaim_never_frees_live_blocks():
    """Under pressure the allocator reclaims only refcount-0 cached
    blocks (oldest first, cache entry invalidated); live blocks are
    untouchable."""
    al = BlockAllocator(4, block_size=8)
    assert al.reserve(4)
    ids = [al.alloc_reserved() for _ in range(4)]
    for i, bid in enumerate(ids):
        al.register(f"k{i}", bid)
    al.free(ids[:2])               # k0, k1 parked in LRU (that order)
    assert al.n_cached == 2 and al.n_free == 0
    assert al.reserve(1)
    got = al.alloc_reserved()      # must reclaim the LRU-oldest: k0
    assert got == ids[0]
    assert not al.cached("k0")     # entry invalidated
    assert al.cached("k1") and al.cached("k2") and al.cached("k3")
    assert ids[2] in al._outstanding and ids[3] in al._outstanding
    assert al.n_reclaimed == 1


def test_acquire_refuses_lru_revival_that_breaks_reservations():
    """Reviving an evicted-but-cached block consumes an available block;
    when everything left is promised to reservations the acquire must
    miss instead of stealing the promise."""
    al = BlockAllocator(2, block_size=8)
    assert al.reserve(1)
    a = al.alloc_reserved()
    al.register("k", a)
    al.free([a])                   # parked in LRU; free list has 1
    assert al.reserve(2)           # promises BOTH remaining blocks
    assert al.n_available == 0
    assert al.acquire("k") is None  # revival would break the promise
    ids = [al.alloc_reserved(), al.alloc_reserved()]
    assert sorted(ids) == [1, 2]


#: the nightly fuzz job raises this (the bundled stub caps itself)
_MAX_EXAMPLES = int(os.environ.get("FUZZ_MAX_EXAMPLES", "25"))


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=_MAX_EXAMPLES, deadline=None)
def test_allocator_conservation_under_random_ops(seed):
    """Randomized schedule of reserve/alloc/free/register/acquire ops:
    conservation (free + cached + live == n_blocks), non-negative
    availability and exact refcounts hold after every operation."""
    import random

    r = random.Random(seed)
    al = BlockAllocator(8, block_size=4)
    live = []          # [bid, refs] we still owe frees for
    registered = 0
    for _ in range(60):
        op = r.randrange(5)
        if op == 0 and al.n_available > 0:
            al.reserve(1)
        elif op == 1 and al.n_reserved > 0:
            live.append([al.alloc_reserved(), 1])
        elif op == 2 and live:
            ent = r.choice(live)
            al.free([ent[0]])
            ent[1] -= 1
            if ent[1] == 0:
                live.remove(ent)
        elif op == 3 and live:
            bid = r.choice(live)[0]
            al.register(f"key-{registered}", bid)
            registered += 1
        elif op == 4 and registered:
            bid = al.acquire(f"key-{r.randrange(registered)}")
            if bid is not None:
                for ent in live:
                    if ent[0] == bid:
                        ent[1] += 1
                        break
                else:
                    live.append([bid, 1])
        assert al.n_free + al.n_cached + al.n_live == al.n_blocks
        assert al.n_available >= 0
        assert al.n_reserved <= al.n_free + al.n_cached
        for bid, refs in live:
            assert al.refcount(bid) == refs
    for bid, refs in live:
        for _ in range(refs):      # dup check is per call: one at a time
            al.free([bid])
    assert al.n_free + al.n_cached == al.n_blocks and al.n_live == 0


# ------------------------------------------------------------ pure API
def test_scatter_gather_blocks_round_trip():
    pool = jnp.zeros((6, 4, 2, 3))
    rows = jnp.arange(10 * 2 * 3, dtype=jnp.float32).reshape(10, 2, 3)
    ids = jnp.asarray([5, 2, 4], jnp.int32)  # 3 blocks = 12 slots >= 10
    pool2 = scatter_blocks(pool, rows, ids)
    back = gather_blocks(pool2, ids)
    np.testing.assert_array_equal(np.asarray(back[:10]), np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(back[10:]), 0.0)  # ragged tail
    # untouched blocks stay zero
    np.testing.assert_array_equal(np.asarray(pool2[0]), 0.0)
    with pytest.raises(ValueError):
        scatter_blocks(pool, rows, jnp.asarray([1, 2], jnp.int32))


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["unstacked", "stacked"])
@pytest.mark.parametrize("pad", [0, 5], ids=["at_edge", "padded"])
@pytest.mark.parametrize("width", DECODE_KV_BUCKETS)
def test_flat_decode_matches_table_decode(width, pad, stacked):
    """``attention_decode_paged`` over the flat live blocks attends what
    the table form attends: rows 0-3 decode over ``width - pad`` live
    blocks in all, each with a partly filled tail block, rows 0 and 1
    sharing their first (prefix) block, row 4 inactive on the null
    block; both forms write the same pool."""
    import jax
    from repro.models.attention import attention_decode_paged, attn_init

    cfg = ModelConfig(name="t-gqa", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=97)
    bs, B, total = 4, 5, width - pad
    rng = np.random.default_rng(width + pad)
    n = [total // 4 + (i < total % 4) for i in range(4)]
    nb = max(n) + 2                       # dead columns on the null block
    ids = rng.permutation(np.arange(1, total + 2)).astype(np.int32)
    tables = np.zeros((B, nb), np.int32)
    pos = np.zeros(B, np.int32)
    start = 0
    for b, k in enumerate(n):
        tables[b, :k] = ids[start:start + k]
        start += k
        pos[b] = (k - 1) * bs + rng.integers(0, bs - 1)   # partial tail
    tables[1, 0] = tables[0, 0]           # a prefix block both rows read
    live = flat_live_blocks(tables, pos, range(4), bs, width)
    assert live.shape == (3, width) and (live[1] >= 0).sum() == total
    shape = (total + 3, bs, cfg.n_kv_heads, cfg.head_dim)
    if stacked:
        shape = (2,) + shape
    cache = {name: jnp.asarray(rng.standard_normal(shape, np.float32))
             for name in ("k", "v")}
    p = attn_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model), np.float32))
    layer = jnp.int32(1) if stacked else None

    @jax.jit
    def decode(cache, live=None):
        return attention_decode_paged(p, x, cache, jnp.asarray(tables),
                                      jnp.asarray(pos), cfg, layer=layer,
                                      live=live)

    want, want_cache = decode(cache)
    got, got_cache = decode(cache, jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]),
                               rtol=1e-5, atol=1e-5)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                      np.asarray(want_cache[name]))


def test_paged_layer_kind_predicate():
    assert paged_layer_kind(TINY, "attn")
    assert not paged_layer_kind(TINY, "rwkv")
    assert not paged_layer_kind(TINY, "rglru")
    assert not paged_layer_kind(KIND_CFGS["windowed"], "local_attn")
    # dense arch with a global sliding window: ring buffer, not paged
    swa = ModelConfig(name="t-swa", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97,
                      sliding_window=32)
    assert not paged_layer_kind(swa, "attn")


# ------------------------------------------------- graft round trips
@pytest.mark.slow
@pytest.mark.parametrize("kind", sorted(KIND_CFGS))
@pytest.mark.parametrize("pool", ["roomy", "tight"])
def test_graft_round_trip_matches_fresh_run(kind, pool):
    """prefill -> graft -> decode through the slot engine must equal a
    fresh single-sequence round-engine run, for every layer-kind family.
    ``roomy`` is the default pool (a full row of blocks per slot), so
    both slots run at once; ``tight`` holds 5 blocks of 8, which fits
    only one of the three requests (3, 3 and 5 blocks) at a time, so
    block-gated queueing orders every admission."""
    cfg = KIND_CFGS[kind]
    kw = {"kv_blocks": 5} if pool == "tight" else {}
    eng = ContinuousBatchingEngine(cfg, max_slots=2, max_seq=64,
                                   block_size=8, **kw)
    ref = InferenceEngine(cfg, max_seq=64)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in (5, 11, 17)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    res, most_active = [], 0
    while eng.waiting or eng.active_slots:
        res.extend(eng.step())
        most_active = max(most_active, len(eng.active_slots))
    assert most_active == (1 if pool == "tight" else 2)
    res.sort(key=lambda r: r.request_id)
    assert len(res) == len(prompts)
    for p, r in zip(prompts, res):
        want = ref.generate([p], max_new_tokens=4).tokens[0]
        assert np.array_equal(r.tokens, want), kind
    assert eng.allocator.n_free == eng.allocator.n_blocks


def test_paged_matches_round_engine_on_mixed_lengths():
    """Acceptance: greedy outputs token-identical to the round engine,
    prompt by prompt, on a mixed-length prompt set that churns slots and
    block boundaries."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 97, n).astype(np.int32)
               for n in (3, 14, 9, 30, 6, 22, 12, 5)]
    ref = InferenceEngine(TINY, max_seq=64)
    paged = ContinuousBatchingEngine(TINY, max_slots=3, max_seq=64,
                                     block_size=8)
    rp = paged.run(prompts, max_new_tokens=7)
    assert [r.request_id for r in rp] == list(range(len(prompts)))
    for p, r in zip(prompts, rp):
        want = ref.generate([p], max_new_tokens=7).tokens[0]
        assert np.array_equal(r.tokens, want)
    # eviction really returned every block
    al = paged.allocator
    assert al.n_free == al.n_blocks and al.n_reserved == 0


# ------------------------------------------------- engine block gating
def test_block_gated_admission_queues_and_drains():
    """With a tiny block budget the engine admits what fits, queues the
    rest, and serves everything as evictions free blocks."""
    # 6 blocks of 8 = 48 tokens; each request needs bucket16 + 4 = 3 blocks
    eng = ContinuousBatchingEngine(TINY, max_slots=4, max_seq=64,
                                   block_size=8,
                                   kv_blocks=6)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 97, 10).astype(np.int32) for _ in range(5)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.admit()
    # only 2 of the 4 free slots could take a reservation (2*3=6 blocks)
    assert len(eng.active_slots) == 2
    assert eng.stats()["queue_depth"] == 3.0
    res = eng.run([], max_new_tokens=4)
    assert len(res) == 5
    assert all(len(r.tokens) == 4 for r in res)
    assert eng.allocator.n_free == eng.allocator.n_blocks


def test_submit_rejects_request_larger_than_block_pool():
    """Regression (review finding): a reservation that exceeds the whole
    pool could never be admitted — submit() must raise instead of
    livelocking the FIFO head forever."""
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                   block_size=16,
                                   kv_blocks=4)
    with pytest.raises(ValueError):
        # bucket 64 + 16 new = 80 tokens = 5 blocks > 4 total
        eng.submit(np.arange(1, 51, dtype=np.int32) % 97,
                   max_new_tokens=16)
    # a small request behind it still flows
    eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
    res = eng.run([], max_new_tokens=4)
    assert len(res) == 1


@pytest.mark.slow
def test_pool_rejects_never_admissible_request():
    """Regression (review finding): a request no current or future grant
    could hold is rejected by the router instead of blocking the EDF
    queue (and everything behind it) forever."""
    pool = ModelInstancePool({TINY.name: TINY}, max_instances=2,
                             max_slots=2, max_seq=128, seed=0,
                             block_size=16,
                             kv_block_budget=8, blocks_per_instance=4)
    pool.scale_to(TINY.name, 1)
    big = pool.submit(TINY.name, np.arange(1, 51, dtype=np.int32) % 97,
                      slo_ms=60_000.0, max_new_tokens=16)  # needs 5 > 4
    small = pool.submit(TINY.name, np.arange(1, 5, dtype=np.int32),
                        slo_ms=60_000.0, max_new_tokens=4)
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    assert by_id[big].rejected
    assert not by_id[small].rejected and len(by_id[small].tokens) == 4


@pytest.mark.slow
def test_route_does_not_oversubscribe_blocks_in_one_pass():
    """Regression (review finding): one route() pass must not admit two
    EDF heads against the same free blocks — the second stays in the
    pool queue (re-routable to whichever instance frees first) instead
    of being stranded in one engine's internal FIFO."""
    pool = ModelInstancePool({TINY.name: TINY}, max_instances=1,
                             max_slots=4, max_seq=64, seed=0,
                             block_size=8,
                             kv_block_budget=6, blocks_per_instance=6)
    pool.scale_to(TINY.name, 1)
    rng = np.random.default_rng(8)
    # each request reserves bucket16 + 8 = 3 blocks; 6 free -> only 2 fit
    for _ in range(3):
        pool.submit(TINY.name, rng.integers(1, 97, 10).astype(np.int32),
                    slo_ms=60_000.0, max_new_tokens=8)
    pool.route()
    inst = pool.running(TINY.name)[0]
    assert inst.n_resident == 2          # not 3: third was not submitted
    assert pool.queue_len(TINY.name) == 1
    assert len(inst.engine.waiting) == 2  # both admissible at the engine
    res = pool.run_until_drained()
    assert len(res) == 3 and not any(r.rejected for r in res)


def test_admissible_reflects_blocks_and_slots():
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64,
                                   block_size=8,
                                   kv_blocks=3)
    assert eng.admissible(4, 4)         # 16+4 tokens -> 3 blocks, all free
    eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
    eng.admit()
    assert not eng.admissible(4, 4)     # blocks exhausted, slot free
    one = ContinuousBatchingEngine(TINY, max_slots=1, max_seq=64,
                                   block_size=8)
    assert one.admissible(4, 4)
    one.submit(np.arange(1, 5, dtype=np.int32))
    one.admit()
    assert one.allocator.n_available > 0
    assert not one.admissible(4, 4)     # blocks free, no free slot


def test_stats_report_kv_occupancy_metrics():
    paged = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64,
                                     block_size=8)
    rng = np.random.default_rng(6)
    p = rng.integers(1, 97, 6).astype(np.int32)
    paged.submit(p, max_new_tokens=8)
    paged.step()
    s = paged.stats()
    for key in ("kv_used_tokens", "kv_allocated_tokens",
                "kv_waste_frac", "kv_reserved_tokens", "queue_depth"):
        assert key in s
    assert s["kv_used_tokens"] > 0
    # only the sequence's blocks are committed, not a full row per slot:
    # bucket 16 prefilled (2 blocks) plus the block decode wrote into;
    # used = the decode frontier 17 plus the row the next step writes
    assert s["kv_allocated_tokens"] == 3 * 8 < 2 * 64
    assert s["kv_used_tokens"] == 18
    assert s["kv_waste_frac"] == pytest.approx(1 - 18 / 24)
    # the 16 + 8 token reservation is wholly allocated by now
    assert s["kv_reserved_tokens"] == 0


# ------------------------------------------------- pool shared budget
@pytest.mark.slow
def test_pool_shared_block_budget_clamps_scale_to():
    """One shared budget: full-length grants fit once, right-sized
    grants fit four times; retiring instances returns their grant."""
    # budget = one full grant (2 slots * 64 tokens = 16 blocks of 8)
    common = dict(max_instances=4, max_slots=2, max_seq=64, seed=0,
                  kv_block_budget=16, block_size=8)
    full = ModelInstancePool({TINY.name: TINY}, **common)
    assert full.scale_to(TINY.name, 3) == 1  # grant-clamped
    paged = ModelInstancePool({TINY.name: TINY},
                              blocks_per_instance=4, **common)
    assert paged.scale_to(TINY.name, 4) == 4  # right-sized grants fit
    assert paged.kv_blocks_free == 0
    occ = paged.kv_occupancy()
    assert occ["budget_tokens"] == 16 * 8
    assert occ["committed_blocks"] == 16
    # drain-and-retire returns the grant to the shared budget
    paged.scale_to(TINY.name, 1)
    paged._sweep()
    assert paged.kv_blocks_free == 12
    assert paged.scale_to(TINY.name, 4) == 4


@pytest.mark.slow
def test_pool_paged_serves_and_calibrates_occupancy():
    """End to end: a paged pool under a shared budget serves a burst,
    reports real occupancy, and calibrates tokens-per-sequence."""
    pool = ModelInstancePool({TINY.name: TINY}, max_instances=2,
                             max_slots=2, max_seq=64, seed=0,
                             block_size=8,
                             kv_block_budget=32)
    pool.scale_to(TINY.name, 2)
    rng = np.random.default_rng(7)
    for _ in range(8):
        pool.submit(TINY.name,
                    rng.integers(1, 97, rng.integers(4, 12)).astype(
                        np.int32), slo_ms=60_000.0, max_new_tokens=6)
    res = pool.run_until_drained()
    assert len(res) == 8 and not any(r.rejected for r in res)
    assert len(pool.occupancy_samples) >= 8
    tps = pool.occupancy_tokens_per_seq()
    # sequences occupy bucket(<=16) + decode tokens: O(20ish)
    assert 8.0 < tps < 40.0
    stats = pool.stats()
    assert stats["kv_budget_tokens"] == 32 * 8
    # drained: nothing used any more
    assert pool.kv_used_tokens() == 0


@pytest.mark.slow
def test_pool_guard_uses_free_blocks():
    """PoolScheduler guard: with a calibrated occupancy model and a tiny
    budget, an oversized (b, m_c) is degraded to fit the real free-block
    budget instead of the analytic memory curve."""
    from repro.config.base import ServingConfig
    from repro.serving.bcedge import PoolScheduler

    pool = ModelInstancePool({TINY.name: TINY}, max_instances=4,
                             max_slots=4, max_seq=64, seed=0,
                             block_size=8,
                             kv_block_budget=12, blocks_per_instance=12)
    cfg = ServingConfig(batch_sizes=(1, 2, 4),
                        concurrency_levels=(1, 2, 3))
    sched = PoolScheduler(pool, cfg, slo_ms={TINY.name: 1000.0},
                          guard=True, learn=False)
    # calibrate: pretend each resident sequence occupies ~24 tokens
    pool.occupancy_samples = [(n, 24 * n) for n in (1, 2, 3, 4) * 3]
    # budget = 96 tokens; b=4, m_c=3 would need ~288 -> infeasible;
    # the guard must degrade to something that fits
    assert not sched._kv_feasible(TINY.name, 4, 3)
    assert sched._kv_feasible(TINY.name, 4, 1)
    a = cfg.pair_to_action(4, 3)
    applied = sched._apply(TINY.name, a)
    b, m_c = cfg.action_to_pair(applied)
    assert sched.guard_interventions == 1
    assert sched._kv_feasible(TINY.name, b, m_c)
    assert (b, m_c) != (4, 3)


# ------------------------------------------------- replay satellite
def test_replay_buffer_lazy_allocation():
    from repro.core.replay import ReplayBuffer

    buf = ReplayBuffer(4, capacity=1_000_000)
    # paper-sized capacity no longer eagerly commits (1e6, dim) arrays
    assert buf.allocated_rows == ReplayBuffer.INITIAL_ROWS
    for i in range(3000):
        buf.add(np.full(4, i), i, float(i), np.full(4, i + 1), False)
    assert len(buf) == 3000
    assert buf.allocated_rows == 4096  # doubled, still << capacity
    out = buf.sample(16)
    assert out["s"].shape == (16, 4) and out["a"].max() < 3000


def test_replay_buffer_ring_semantics_preserved():
    from repro.core.replay import ReplayBuffer

    buf = ReplayBuffer(2, capacity=10)
    for i in range(27):
        buf.add(np.full(2, i), i, float(i), np.full(2, i), i % 2)
    assert len(buf) == 10 and buf.full
    assert buf.allocated_rows == 10
    # ring holds exactly the last `capacity` transitions
    assert sorted(buf.a.tolist()) == list(range(17, 27))
    s = buf.sample(32)
    assert s["a"].min() >= 17
