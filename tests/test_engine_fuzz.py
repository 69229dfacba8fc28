"""Randomized differential stress harness for the continuous engine
(docs/ARCHITECTURE.md §5).

Each seeded schedule interleaves submit / step / preempt-resume (both
recompute and host-tier SWAP flavours on offload-capable variants) /
CANCEL ops — plus live speculative-depth retuning on spec-capable
variants — over a pool of mixed-length prompts with shared AND divergent
prefixes, across nine engine variants (block sizes 16 and 8, prefix
cache on/off, token budget on/off — on the all-linear tiny model every
variant prefills fused; the hybrid layer-family sweeps run the
staging-cache path — tight block budgets that force LRU reclaim,
speculative k up to 4 with mid-flight k toggling, a kitchen-sink variant
stacking prefix cache + tight blocks + budget + speculation + a host KV
tier, and a dedicated offload variant whose tight device budget forces
prefix-block spills to host alongside swap-mode preemption), and
asserts:

* after EVERY operation — allocator conservation:
  ``n_free + n_cached + n_live == n_blocks`` (disjoint id sets),
  ``n_available >= 0``, refcount(b) == number of slots mapping b (no
  block owned by two slots without a refcount), block tables mirror the
  slot block lists, the null block is never mapped; host-tier
  conservation on offload variants:
  ``n_host_free + n_host_cached + n_host_live == n_host_blocks``
  (disjoint id sets) with the live host population EXACTLY the union
  of the waiting swap snapshots' block lists;
* for EVERY finished request — greedy output token-identical to a
  per-request uninterrupted oracle run (fresh single-slot engine,
  shared weights), regardless of how the schedule batched, preempted,
  chunked or block-shared it;
* for EVERY cancelled request — whatever it emitted before the cancel
  (at random phase: queued, mid-prefill, mid-decode, or preempted) is a
  PREFIX of its oracle run, and the cancel never perturbs survivors;
* after the drain — every reference returned (no leak, no double free).

``ENGINE_FUZZ_SCHEDULES`` sets the full-sweep schedule count (default
200 — the CI full-suite floor; the nightly fuzz job raises it). The
non-slow smoke variant keeps tier-1 fast.
"""
import os
import random

import numpy as np
import pytest

from conftest import KIND_CFGS, TINY
from repro.serving.engine import ContinuousBatchingEngine

N_SCHEDULES = int(os.environ.get("ENGINE_FUZZ_SCHEDULES", "200"))

MAX_SEQ = 128
MAX_NEW_CHOICES = (2, 4, 7)

_TEMPLATES = {}
_ORACLE = {}


def _template(cfg):
    """One weight/jit-cache donor per config, so every fuzz engine and
    every oracle run share identical parameters."""
    if cfg.name not in _TEMPLATES:
        _TEMPLATES[cfg.name] = ContinuousBatchingEngine(
            cfg, max_slots=1, max_seq=MAX_SEQ, seed=0)
    return _TEMPLATES[cfg.name]


def _oracle(cfg, prompt: np.ndarray, max_new: int) -> np.ndarray:
    """Uninterrupted single-request greedy run (memoized)."""
    key = (cfg.name, prompt.tobytes(), max_new)
    if key not in _ORACLE:
        eng = ContinuousBatchingEngine(cfg, max_slots=1, max_seq=MAX_SEQ,
                                       seed=0, share_from=_template(cfg))
        _ORACLE[key] = eng.run([prompt], max_new_tokens=max_new)[0].tokens
    return _ORACLE[key]


def _prompt_pool(cfg):
    """Mixed-length prompts: two shared-prefix families (equal and
    unequal tail lengths — unequal ones land at different pad offsets,
    so they must NOT share), divergent one-offs, and one exact duplicate
    (the full-cover copy-on-write path)."""
    rng = np.random.default_rng(99)
    v = cfg.vocab_size
    pool = []
    for _ in range(2):
        prefix = rng.integers(1, v, 24).astype(np.int32)
        for tail_len in (4, 4, 8):
            pool.append(np.concatenate(
                [prefix, rng.integers(1, v, tail_len).astype(np.int32)]))
    pool += [rng.integers(1, v, n).astype(np.int32)
             for n in (3, 9, 17, 30)]
    pool.append(pool[0].copy())  # exact duplicate
    return pool


def _check_invariants(eng, ctx: str) -> None:
    al = eng.allocator
    free, lru = set(al._free), set(al._lru)
    out = set(al._outstanding)
    assert not (free & lru) and not (free & out) and not (lru & out), \
        f"{ctx}: allocator id sets overlap"
    assert len(free) + len(lru) + len(out) == al.n_blocks, \
        f"{ctx}: conservation broken " \
        f"({len(free)}+{len(lru)}+{len(out)} != {al.n_blocks})"
    assert al.n_available >= 0, f"{ctx}: n_available < 0"
    assert al.n_reserved <= al.n_free + al.n_cached, \
        f"{ctx}: reservations exceed reclaimable blocks"
    counts = {}
    for s in eng.slots:
        if not s.active:
            continue
        assert len(set(s.blocks)) == len(s.blocks), \
            f"{ctx}: slot maps a block twice"
        for b in s.blocks:
            counts[b] = counts.get(b, 0) + 1
    assert 0 not in counts, f"{ctx}: null block mapped"
    for b, c in counts.items():
        assert al.refcount(b) == c, \
            f"{ctx}: block {b} mapped by {c} slots, refcount " \
            f"{al.refcount(b)}"
    assert set(counts) == out, \
        f"{ctx}: live blocks != mapped blocks"
    if al.n_host_blocks:
        hfree, hlive = set(al._host_free), set(al._host_live)
        hcache = set(al._host_cache.values())
        assert not (hfree & hcache) and not (hfree & hlive) \
            and not (hcache & hlive), \
            f"{ctx}: host-tier id sets overlap"
        assert len(hfree) + len(hcache) + len(hlive) \
            == al.n_host_blocks, \
            f"{ctx}: host conservation broken " \
            f"({len(hfree)}+{len(hcache)}+{len(hlive)} " \
            f"!= {al.n_host_blocks})"
        swap_ids = [b for w in eng.waiting if w.swap is not None
                    for b in w.swap.host_blocks]
        assert len(swap_ids) == len(set(swap_ids)), \
            f"{ctx}: host block shared by two swap snapshots"
        assert set(swap_ids) == hlive, \
            f"{ctx}: swap-pinned host blocks != waiting snapshots"
    for i, s in enumerate(eng.slots):
        if s.active and not s.prefilling:
            n = len(s.blocks)
            np.testing.assert_array_equal(
                eng.block_tables[i, :n], s.blocks, err_msg=ctx)
            assert not eng.block_tables[i, n:].any(), ctx
        else:
            # mid-prefill slots point at the null block until the
            # prefill lands, in BOTH modes: staging chunks (hybrid
            # stacks) write a staging cache, and fused chunks carry
            # their own table row — either way the decode batch's dummy
            # writes for this row must keep sinking into the null block
            assert not eng.block_tables[i].any(), ctx


N_VARIANTS = 9


def _engine_variant(cfg, variant: int):
    """Rotate the engine configurations the schedules exercise. On the
    all-linear configs every variant prefills FUSED — so the
    prefix-cache (2, 3, 7) and speculative (4, 5, 7) variants prove
    token-identity of fused prefill under preempt/resume/rollback/cancel
    interleavings. Variants 0 and 6 run the default block size 16, the
    rest 8. The hybrid layer-family sweeps below cover the staging-cache
    round trip (the path hybrid stacks keep)."""
    if variant == 0:
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg))
    if variant == 1:
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8)
    if variant == 2:
        kw = {"prefix_cache": True} \
            if cfg.name in ("tiny", "tiny-tail") else {}
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8,
            token_budget=12, **kw)
    if variant == 3:
        # tight block budget + prefix cache: forces queueing on memory,
        # LRU revivals and reclaims
        kw = {"prefix_cache": True} if cfg.name in ("tiny", "tiny-tail") \
            else {}
        return ContinuousBatchingEngine(
            cfg, max_slots=4, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8,
            kv_blocks=14, **kw)
    # speculative variants: propose/verify/rollback interleaved with
    # everything above. Only rewind-capable stacks can speculate — the
    # other layer families fall back to the plain paged variant so
    # every seed still runs a schedule.
    spec = {"spec_k": 4} if cfg.name in ("tiny", "tiny-tail") else {}
    if variant == 4:
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8,
            prefix_cache=bool(spec), **spec)
    if variant == 5:
        # tight budget + speculation: block rollback under LRU reclaim
        # pressure and budget-degraded effective k
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8,
            kv_blocks=16, token_budget=12, **spec)
    if variant == 6:
        # default block size + token budget: chunk boundaries that are
        # not block boundaries, interleaved with preempt/resume/cancel
        return ContinuousBatchingEngine(
            cfg, max_slots=3, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), token_budget=12)
    if variant == 7:
        # kitchen sink: prefix cache + tight blocks + token budget +
        # speculation + host tier stacked — every reclaim/rollback/
        # share/spill path at once
        kw = {"prefix_cache": True} if cfg.name in ("tiny", "tiny-tail") \
            else {}
        return ContinuousBatchingEngine(
            cfg, max_slots=4, max_seq=MAX_SEQ, seed=0,
            share_from=_template(cfg), block_size=8,
            kv_blocks=18, token_budget=12, kv_host_blocks=10,
            **kw, **spec)
    # KV offload: a tight device budget under prefix caching forces LRU
    # spills to the host tier, while the preempt op below exercises the
    # swap-mode snapshot/resume path against the recompute oracle
    kw = {"prefix_cache": True} if cfg.name in ("tiny", "tiny-tail") \
        else {}
    return ContinuousBatchingEngine(
        cfg, max_slots=4, max_seq=MAX_SEQ, seed=0,
        share_from=_template(cfg), block_size=8,
        kv_blocks=16, kv_host_blocks=12, **kw)


def _run_schedule(cfg, seed: int) -> None:
    rng = random.Random(seed)
    eng = _engine_variant(cfg, seed % N_VARIANTS)
    prompts = _prompt_pool(cfg)
    expected = {}
    results = {}
    cancelled = set()
    ctx = f"cfg={cfg.name} seed={seed} variant={seed % N_VARIANTS}"

    def step_engine():
        for r in eng.step():
            results[r.request_id] = r

    for _ in range(rng.randint(8, 18)):
        roll = rng.random()
        if roll < 0.40:
            p = rng.choice(prompts)
            mn = rng.choice(MAX_NEW_CHOICES)
            try:
                rid = eng.submit(p, max_new_tokens=mn)
            except ValueError:
                pass  # request larger than the whole pool: rejected
            else:
                expected[rid] = (p, mn)
        elif roll < 0.75:
            step_engine()
        elif roll < 0.85:
            # cancel a live request at whatever phase the schedule
            # caught it in — queued, mid-prefill, mid-decode, or
            # preempted-awaiting-resume; blocks must come back
            # synchronously and survivors must not notice
            live = sorted(set(expected) - set(results))
            if live:
                rid = rng.choice(live)
                r = eng.cancel(rid)
                assert r is not None and r.cancelled, \
                    f"{ctx}: cancel({rid}) did not land"
                results[rid] = r
                cancelled.add(rid)
        elif roll < 0.92 and eng.spec_max > 0:
            # the scheduler's fourth axis mid-flight: retune the live
            # proposal depth (speculate/verify/rollback must stay
            # token-identical at any k, switched at any boundary)
            eng.spec_k = rng.choice((0, 2, 4))
        else:
            cands = eng.decoding_slots
            if cands and eng.chunked:
                slot = rng.choice(cands)
                # offload-capable engines flip a coin between the two
                # eviction flavours: swap-resume must stay
                # token-identical to recompute-resume (both are checked
                # against the same uninterrupted oracle below)
                if eng.swap_ok and eng.can_swap(slot) \
                        and rng.random() < 0.5:
                    eng.preempt(slot, mode="swap")
                else:
                    eng.preempt(slot)  # requeue + resume
        _check_invariants(eng, ctx)

    guard = 600
    while (eng.waiting or eng.active_slots) and guard:
        step_engine()
        _check_invariants(eng, ctx)
        guard -= 1
    assert guard, f"{ctx}: engine failed to drain"
    assert set(results) == set(expected), \
        f"{ctx}: lost requests {set(expected) - set(results)}"
    for rid, (p, mn) in expected.items():
        got = results[rid]
        if rid in cancelled:
            # a cancelled request keeps whatever it had emitted — which
            # must be an oracle PREFIX (never a wrong token)
            assert got.cancelled, f"{ctx} rid={rid}: lost cancel flag"
            oracle = _oracle(cfg, p, mn)
            assert len(got.tokens) <= len(oracle) and np.array_equal(
                got.tokens, oracle[:len(got.tokens)]), \
                f"{ctx} rid={rid}: cancelled emission not an oracle " \
                f"prefix ({got.tokens} vs {oracle})"
            continue
        assert not got.truncated, f"{ctx} rid={rid}: unexpected clamp"
        assert np.array_equal(got.tokens, _oracle(cfg, p, mn)), \
            f"{ctx} rid={rid}: tokens diverge from oracle " \
            f"({got.tokens} vs {_oracle(cfg, p, mn)})"
    al = eng.allocator
    assert al.n_live == 0 and al.n_reserved == 0, \
        f"{ctx}: leaked references after drain"
    assert al.n_free + al.n_cached == al.n_blocks, ctx
    assert al.n_host_live == 0, \
        f"{ctx}: leaked host-tier blocks after drain"
    assert al.n_host_free + al.n_host_cached == al.n_host_blocks, ctx


def test_fuzz_smoke_schedules():
    """Tier-1 slice of the sweep: a handful of schedules covering every
    variant of the canonical tiny model once — including the
    speculative (4, 5), block-16 budget (6), kitchen-sink (7) and KV
    offload (8) variants."""
    for seed in range(N_VARIANTS):
        _run_schedule(TINY, seed)


@pytest.mark.slow
def test_fuzz_full_sweep_tiny():
    """The CI sweep: >= ENGINE_FUZZ_SCHEDULES seeded schedules (default
    200) on the canonical model across all nine engine variants."""
    for seed in range(N_SCHEDULES):
        _run_schedule(TINY, seed)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["tail", "rglru", "windowed"])
def test_fuzz_layer_families(kind):
    """Shorter sweeps over the other layer families: the unrolled tail
    (prefix-cacheable) plus recurrent and windowed stacks, whose
    per-slot leaves beside the block pool (staging chunks, graft) must
    hold under the same schedules."""
    cfg = KIND_CFGS[kind]
    for seed in range(max(8, N_SCHEDULES // 10)):
        _run_schedule(cfg, seed)
