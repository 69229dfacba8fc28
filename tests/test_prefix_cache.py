"""Prefix caching with copy-on-write block sharing
(docs/ARCHITECTURE.md §5): same-prefix sequences map full immutable
prompt blocks once (refcount+1), chunked prefill skips straight to the
first uncached token, evicted-but-cached blocks revive from an LRU pool,
greedy outputs stay token-identical, and stats count shared blocks once.
The randomized cross-feature schedules live in tests/test_engine_fuzz.py.
"""
import numpy as np
import pytest

from conftest import KIND_CFGS, TINY, make_pool
from repro.serving.engine import (ContinuousBatchingEngine,
                                  supports_prefix_cache)


def _mk(prefix_cache=True, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("seed", 0)
    return ContinuousBatchingEngine(TINY, block_size=8,
                                    prefix_cache=prefix_cache, **kw)


def _family(rng, n_prompts, prefix_len=24, tail_len=4):
    """Same-length prompts sharing one prefix (left-padding makes
    sharing length-sensitive, so the family keeps tails equal-length)."""
    prefix = rng.integers(1, 97, prefix_len).astype(np.int32)
    return [np.concatenate([prefix,
                            rng.integers(1, 97, tail_len).astype(np.int32)])
            for _ in range(n_prompts)]


# ------------------------------------------------------------ gating
def test_prefix_cache_requires_paged_and_pageable_layers():
    assert ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64,
                                    prefix_cache=True).prefix_cache
    for kind in ("windowed", "rglru", "rwkv", "swa"):
        assert not supports_prefix_cache(KIND_CFGS[kind])
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(KIND_CFGS[kind], max_slots=2,
                                     max_seq=64,
                                     block_size=8, prefix_cache=True)
    assert supports_prefix_cache(TINY)
    assert supports_prefix_cache(KIND_CFGS["tail"])


# ------------------------------------------------------------ sharing
@pytest.mark.slow
def test_sequential_same_prefix_hits_and_stays_token_identical():
    """The second identical-prefix request skips the cached prefix
    (prefill jumps to the first uncached token) and still produces the
    exact no-cache greedy output — including the full-cover case, where
    the tail block is copied on divergence rather than written shared."""
    rng = np.random.default_rng(0)
    prompts = _family(rng, 3)
    ref = _mk(prefix_cache=False)
    want = [ref.run([p], max_new_tokens=6)[0].tokens for p in prompts]

    eng = _mk()
    for p, w in zip(prompts, want):
        got = eng.run([p], max_new_tokens=6)[0].tokens
        assert np.array_equal(got, w)
    s = eng.stats()
    assert s["n_prefix_hits"] == 2.0           # first run seeds the cache
    assert s["prefix_hit_rate"] > 0.4
    # identical FULL prompt resubmitted: full-cover hit (CoW tail), and
    # the cached content must not have been corrupted by earlier writes
    again = eng.run([prompts[0]], max_new_tokens=6)[0].tokens
    assert np.array_equal(again, want[0])


@pytest.mark.slow
def test_concurrent_sharing_maps_blocks_once():
    """Same-prefix residents decode concurrently off ONE physical copy
    of the prefix: refcounts > 1, kv_shared_frac > 0, and the distinct
    live allocation is far below the logical per-sequence sum."""
    rng = np.random.default_rng(1)
    prompts = _family(rng, 4)
    eng = _mk()
    # seed the cache, then admit the rest at the same boundary
    eng.run([prompts[0]], max_new_tokens=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    eng.step()
    assert len(eng.active_slots) == 4
    shared = [b for s in eng.slots if s.active for b in s.blocks
              if eng.allocator.refcount(b) > 1]
    assert shared, "no block is shared across residents"
    st = eng.stats()
    assert st["kv_shared_frac"] > 0.3
    # logical usage exceeds the distinct physical allocation: that
    # surplus is the capacity the cache buys
    assert st["kv_used_tokens"] > st["kv_allocated_tokens"]
    assert 0.0 <= st["kv_waste_frac"] <= 1.0
    res = []
    while eng.active_slots or eng.waiting:
        res.extend(eng.step())
    ref = _mk(prefix_cache=False)
    for p, r in zip(prompts, sorted(res, key=lambda r: r.request_id)):
        assert np.array_equal(
            r.tokens, ref.run([p], max_new_tokens=8)[0].tokens)
    al = eng.allocator
    assert al.n_free + al.n_cached + al.n_live == al.n_blocks
    assert al.n_reserved == 0 and al.n_live == 0


def test_stats_count_shared_blocks_once():
    """Regression (fuzz-harness find): ``kv_waste_frac`` used the
    per-sequence logical sum, which double-counts refcount-shared blocks
    and went NEGATIVE under sharing; it now uses unique physical
    coverage over the distinct live allocation."""
    rng = np.random.default_rng(2)
    prompts = _family(rng, 3)
    eng = _mk()
    eng.run([prompts[0]], max_new_tokens=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    for _ in range(3):
        eng.step()
    st = eng.stats()
    assert st["kv_shared_frac"] > 0.0, "no sharing: regression untested"
    assert st["kv_waste_frac"] >= 0.0
    assert eng.kv_unique_used_tokens <= eng.kv_allocated_tokens
    assert eng.kv_used_tokens > eng.kv_unique_used_tokens


@pytest.mark.slow
def test_lru_reuse_and_reclaim_under_pressure():
    """Evicted-but-cached blocks revive on a later same-prefix admission
    (LRU pool), and when fresh allocations need the space the oldest
    cached blocks are reclaimed — never a live one — with outputs still
    token-identical."""
    rng = np.random.default_rng(3)
    fam_a = _family(rng, 2)
    eng = _mk(kv_blocks=12)  # tight: 96 tokens
    ref = _mk(prefix_cache=False)
    w0 = ref.run([fam_a[0]], max_new_tokens=4)[0].tokens
    assert np.array_equal(eng.run([fam_a[0]], max_new_tokens=4)[0].tokens,
                          w0)
    assert eng.allocator.n_cached > 0      # prompt blocks parked, cached
    # same prefix again: revived from the LRU pool, prefill mostly skipped
    assert np.array_equal(eng.run([fam_a[1]], max_new_tokens=4)[0].tokens,
                          ref.run([fam_a[1]], max_new_tokens=4)[0].tokens)
    assert eng.stats()["n_prefix_hits"] >= 1.0
    # now flood with DIFFERENT prefixes: the parked blocks must be
    # reclaimed (cache entries invalidated), allocation must not fail
    for _ in range(4):
        p = rng.integers(1, 97, 28).astype(np.int32)
        assert np.array_equal(eng.run([p], max_new_tokens=4)[0].tokens,
                              ref.run([p], max_new_tokens=4)[0].tokens)
    assert eng.allocator.n_reclaimed > 0
    al = eng.allocator
    assert al.n_free + al.n_cached + al.n_live == al.n_blocks


# ------------------------------------------------------------ admission
@pytest.mark.slow
def test_admissible_discounts_live_shared_blocks():
    """While a same-prefix sequence is resident, ``admissible`` prices
    only the unshared remainder — the admission headroom sharing buys."""
    rng = np.random.default_rng(4)
    prompts = _family(rng, 2)
    eng = _mk(kv_blocks=8)  # 64 tokens: one 32-bucket seq + decode fits
    eng.run([prompts[0]], max_new_tokens=2)  # seed cache (parks in LRU)
    eng.submit(prompts[0], max_new_tokens=8)
    eng.step()  # resident again, prefix blocks LIVE now
    assert eng.active_slots
    # worst case would need 5 blocks (32 + 8 tokens); only ~2 are free,
    # but 3 prompt blocks are live-shared -> admissible with the prompt
    assert not eng.admissible(len(prompts[1]), 8)
    assert eng.admissible(len(prompts[1]), 8, prompt=prompts[1])


@pytest.mark.slow
def test_admission_capacity_gain_vs_no_cache():
    """Under one tight block budget, same-prefix requests reach a
    strictly higher peak residency with the cache on."""
    rng = np.random.default_rng(5)
    prompts = _family(rng, 6, prefix_len=24, tail_len=4)

    def peak(prefix_cache):
        eng = _mk(prefix_cache=prefix_cache, kv_blocks=16, max_slots=6)
        eng.run([prompts[0]], max_new_tokens=2)   # warm/seed
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        peak_resident = 0
        while eng.active_slots or eng.waiting:
            eng.step()
            peak_resident = max(peak_resident, len(eng.active_slots))
        return peak_resident

    assert peak(True) > peak(False)


# ------------------------------------------------------------ pool
@pytest.mark.slow
def test_router_prefix_affinity_concentrates_same_prefix():
    """Same-prefix requests prefer the instance already holding the
    prefix instead of re-prefilling it on every instance."""
    rng = np.random.default_rng(6)
    prompts = _family(rng, 3, prefix_len=24, tail_len=4)
    pool = make_pool(TINY, max_instances=2, max_slots=4, max_seq=64,
                     block_size=8, kv_block_budget=64,
                     prefix_cache=True)
    pool.scale_to(TINY.name, 2)
    first = pool.submit(TINY.name, prompts[0], slo_ms=60_000.0,
                        max_new_tokens=4)
    pool.run_until_drained()
    rest = [pool.submit(TINY.name, p, slo_ms=60_000.0, max_new_tokens=4)
            for p in prompts[1:]]
    pool.run_until_drained()
    placed = dict(pool.admission_log)
    assert all(placed[r] == placed[first] for r in rest), placed
    assert pool.prefix_hit_rate() > 0.0


@pytest.mark.slow
def test_pool_prefix_cache_skips_unsupported_models():
    """A mixed pool downgrades per model: pageable models get the cache,
    recurrent/windowed ones serve correctly without it."""
    from repro.serving.runtime import ModelInstancePool

    cfgs = {TINY.name: TINY,
            KIND_CFGS["rglru"].name: KIND_CFGS["rglru"]}
    pool = ModelInstancePool(cfgs, max_instances=2, max_slots=2,
                             max_seq=64, seed=0,
                             block_size=8, prefix_cache=True)
    pool.scale_to(TINY.name, 1)
    pool.scale_to(KIND_CFGS["rglru"].name, 1)
    assert pool.running(TINY.name)[0].engine.prefix_cache
    assert not pool.running(KIND_CFGS["rglru"].name)[0].engine.prefix_cache
    rng = np.random.default_rng(7)
    for m in cfgs:
        pool.submit(m, rng.integers(1, 97, 10).astype(np.int32),
                    slo_ms=60_000.0, max_new_tokens=4)
    res = pool.run_until_drained()
    assert len(res) == 2 and not any(r.rejected for r in res)
