"""KV offload tier (docs/RUNTIME.md §8): host-memory block tier in the
allocator, swap-mode preemption at the engine and pool levels, the
recompute-vs-swap pricing, and the three serving-stats /
preemption-accounting regression fixes that ride the same PR."""
import numpy as np
import pytest

from conftest import KIND_CFGS, TINY, make_cont_engine, make_pool
from repro.serving.engine import PreemptedRequest, to_recompute
from repro.serving.runtime import PoolRequest

VOCAB = TINY.vocab_size


def _prompt(rng, n):
    return rng.integers(1, VOCAB, n).astype(np.int32)


def _swap_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("kv_blocks", 24)
    kw.setdefault("kv_host_blocks", 16)
    kw.setdefault("prefix_cache", True)
    return make_cont_engine(TINY, **kw)


# ---------------------------------------------------------------- engine
def test_ctor_validation():
    with pytest.raises(ValueError):
        make_cont_engine(TINY, block_size=8, kv_host_blocks=-1)
    # an all-linear stack swaps whole blocks through the tier
    eng = make_cont_engine(TINY, block_size=8, kv_host_blocks=8)
    assert eng.swap_ok and eng.host_pool is not None
    # non-pageable stacks build the tier but never swap (recompute-only)
    eng = make_cont_engine(KIND_CFGS["rglru"], block_size=8,
                           kv_host_blocks=8)
    assert not eng.swap_ok


def test_swap_resume_token_identical_and_no_leak():
    """The acceptance-criterion identity at engine level: swap-resume ==
    recompute-resume == uninterrupted, and both tiers conserve."""
    rng = np.random.default_rng(0)
    p = _prompt(rng, 20)
    want = _swap_engine().run([p], max_new_tokens=12)[0].tokens
    for mode in ("recompute", "swap"):
        eng = _swap_engine()
        eng.submit(p, max_new_tokens=12)
        for _ in range(5):
            eng.step()
        slot = eng.decoding_slots[0]
        snap = eng.preempt(slot, requeue=False, mode=mode)
        assert snap.swapped == (mode == "swap")
        for _ in range(2):
            eng.step()  # idle while preempted
        rid = eng.submit_resume(snap)  # resume under a fresh engine id
        out = {}
        guard = 100
        while (eng.waiting or eng.active_slots) and guard:
            for r in eng.step():
                out[r.request_id] = r
            guard -= 1
        np.testing.assert_array_equal(out[rid].tokens, want, err_msg=mode)
        al = eng.allocator
        assert al.n_live == 0 and al.n_reserved == 0
        assert al.n_host_live == 0
        assert al.n_host_free + al.n_host_cached == al.n_host_blocks
    eng = _swap_engine()
    assert eng.n_swap_preempts == 0  # counters start clean


def test_swap_preempt_counts_and_samples():
    rng = np.random.default_rng(1)
    eng = _swap_engine()
    eng.submit(_prompt(rng, 16), max_new_tokens=8)
    for _ in range(4):
        eng.step()
    eng.preempt(eng.decoding_slots[0], mode="swap")  # requeue path
    assert eng.n_swap_preempts == 1
    assert eng.swap_samples and eng.swap_samples[-1][0] > 0
    while eng.waiting or eng.active_slots:
        eng.step()
    assert eng.n_swap_resumes == 1
    assert eng.allocator.n_host_live == 0


def test_swap_requires_host_capacity():
    """mode="swap" must raise (not silently fall back) when the host
    tier cannot hold the victim — callers price and pick the mode."""
    rng = np.random.default_rng(2)
    eng = _swap_engine(kv_host_blocks=1)  # 20-token seq needs 3+ blocks
    eng.submit(_prompt(rng, 20), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    slot = eng.decoding_slots[0]
    assert not eng.can_swap(slot)
    with pytest.raises(ValueError):
        eng.preempt(slot, mode="swap")


def test_prefix_spill_to_host_and_revival():
    """Cold prefix blocks spill to the host tier on LRU reclaim instead
    of invalidating, and a later same-prefix prompt revives them
    (unspill) with token-identical output."""
    rng = np.random.default_rng(3)
    prefix = _prompt(rng, 16)  # two full blocks at block_size=8
    # equal lengths: left-padding makes prefix sharing length-sensitive
    pa = np.concatenate([prefix, _prompt(rng, 4)])
    pb = np.concatenate([prefix, _prompt(rng, 4)])
    fillers = [_prompt(rng, 24), _prompt(rng, 24)]
    # device pool sized so the fillers' footprints force reclaim of the
    # cached prefix blocks left by the first run
    eng = _swap_engine(max_slots=1, kv_blocks=6, kv_host_blocks=16)
    want_a = eng.run([pa], max_new_tokens=4)[0].tokens
    for f in fillers:
        eng.run([f], max_new_tokens=4)
    assert eng.allocator.n_spilled > 0, "reclaim never spilled"
    # the spilled prefix revives from host for the next same-prefix run
    eng2_tokens = eng.run([pb], max_new_tokens=4)[0].tokens
    assert eng.allocator.n_unspilled > 0, "revival never unspilled"
    # correctness: replays of the ORIGINAL prompt still match a fresh run
    got_a = eng.run([pa], max_new_tokens=4)[0].tokens
    np.testing.assert_array_equal(got_a, want_a)
    fresh = _swap_engine(max_slots=1, kv_blocks=6, kv_host_blocks=16)
    np.testing.assert_array_equal(
        eng2_tokens, fresh.run([pb], max_new_tokens=4)[0].tokens)
    a = eng.allocator
    assert a.n_host_free + a.n_host_cached + a.n_host_live \
        == a.n_host_blocks


def test_cancel_waiting_swap_snapshot_frees_host_blocks():
    rng = np.random.default_rng(4)
    eng = _swap_engine()
    rid = eng.submit(_prompt(rng, 16), max_new_tokens=8)
    for _ in range(4):
        eng.step()
    eng.preempt(eng.decoding_slots[0], mode="swap")
    assert eng.allocator.n_host_live > 0
    r = eng.cancel(rid)
    assert r is not None and r.cancelled and len(r.tokens) > 0
    assert eng.allocator.n_host_live == 0
    assert eng.allocator.n_host_free + eng.allocator.n_host_cached \
        == eng.allocator.n_host_blocks


def test_release_swap_and_pinning():
    """release_swap converts a swap snapshot to recompute (freeing host
    blocks); foreign engines refuse both release and resume."""
    rng = np.random.default_rng(5)
    p = _prompt(rng, 16)
    want = _swap_engine().run([p], max_new_tokens=8)[0].tokens
    eng = _swap_engine()
    eng.submit(p, max_new_tokens=8)
    for _ in range(4):
        eng.step()
    snap = eng.preempt(eng.decoding_slots[0], requeue=False, mode="swap")
    other = _swap_engine()
    with pytest.raises(ValueError):
        other.submit_resume(snap)
    with pytest.raises(ValueError):
        other.release_swap(snap)
    rec = eng.release_swap(snap)
    assert not rec.swapped and eng.allocator.n_host_live == 0
    # the recompute snapshot resumes anywhere, token-identical
    rid = other.submit_resume(rec)
    out = {}
    while other.waiting or other.active_slots:
        for r in other.step():
            out[r.request_id] = r
    np.testing.assert_array_equal(out[rid].tokens, want)


def test_to_recompute_without_engine():
    """The module-level fallback rebuilds a recompute snapshot from the
    carried tokens when the owning engine is already retired."""
    snap = PreemptedRequest(
        request_id=7, seq_tokens=np.arange(1, 9, dtype=np.int32),
        base_len=8, max_new=3, submit_s=0.0, requested_new=5,
        truncated=False, n_preempted=1, tokens=[11, 12], pos=10,
        pending_tok=0, host_blocks=[1, 2], host_engine_id=123)
    rec = to_recompute(snap)
    assert not rec.swapped
    np.testing.assert_array_equal(
        rec.seq_tokens, np.array([1, 2, 3, 4, 5, 6, 7, 8, 11, 12]))
    # base_len stays at the prompt boundary: seq_tokens[base_len:] must
    # keep meaning "tokens already emitted" (the cancel path reads it)
    assert rec.base_len == 8 and rec.max_new == 3


# ------------------------------------------------------------------ pool
def _calibrated_swap_pool(preempt_mode="auto", **kw):
    kw.setdefault("max_instances", 2)
    kw.setdefault("max_slots", 1)
    kw.setdefault("max_seq", 64)
    kw.setdefault("preemption", True)
    kw.setdefault("block_size", 8)
    kw.setdefault("kv_block_budget", 64)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("kv_host_blocks", 16)
    pool = make_pool(TINY, preempt_mode=preempt_mode, **kw)
    pool.scale_to("tiny", 1)
    rng = np.random.default_rng(3)
    for _ in range(2):
        pool.submit("tiny", _prompt(rng, 6), slo_ms=60_000.0,
                    max_new_tokens=8)
    pool.run_until_drained()
    assert pool.contention()[0] > 0.0
    return pool, rng


def _preempt_once(pool, rng, hog_new=24):
    hog = pool.submit("tiny", _prompt(rng, 8), slo_ms=60_000.0,
                      max_new_tokens=hog_new)
    for _ in range(6):
        pool.step()
    urgent = pool.submit("tiny", _prompt(rng, 6), slo_ms=0.001,
                         max_new_tokens=2)
    return hog, urgent


@pytest.mark.parametrize("mode,swaps", [("recompute", 0), ("swap", 1),
                                        ("auto", 1)])
def test_pool_preempt_mode(mode, swaps):
    """Forced modes obey the flag; auto prefers swap while the swap fit
    is uncalibrated (the only way to collect samples). Either way the
    hog resumes and emits every token."""
    pool, rng = _calibrated_swap_pool(preempt_mode=mode)
    hog, urgent = _preempt_once(pool, rng)
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    assert pool.n_preempted == 1
    assert pool.n_swap_preempted == swaps
    assert len(by_id[hog].tokens) == 24
    assert len(by_id[urgent].tokens) == 2
    st = pool.stats()
    assert st["n_swap_preempted"] == float(swaps)
    for inst in pool.live():
        a = inst.engine.allocator
        assert a.n_host_live == 0
        assert a.n_host_free + a.n_host_cached == a.n_host_blocks


@pytest.mark.parametrize("n_devices", [None, 1], ids=["unplaced", "carved"])
def test_pool_swap_resume_token_identical(n_devices):
    """A swap-preempted request resumes token-identical to an
    uninterrupted run and leaves no block live on either tier — also on
    an instance carved onto a one-device mesh (``n_devices`` set), where
    the engine runs its mesh path and swap-ins write outside jit."""
    pool, rng = _calibrated_swap_pool(preempt_mode="swap",
                                      n_devices=n_devices)
    eng = pool.running("tiny")[0].engine
    assert (eng.mesh is not None) == (n_devices is not None)
    replay = np.random.default_rng()
    replay.bit_generator.state = rng.bit_generator.state
    prompts = [_prompt(replay, 8), _prompt(replay, 6)]
    hog, urgent = _preempt_once(pool, rng)
    got = {r.request_id: r.tokens for r in pool.run_until_drained()}
    assert pool.n_swap_preempted == 1
    ref = make_cont_engine(TINY, max_slots=1,
                           block_size=8)
    for rid, p, n in ((hog, prompts[0], 24), (urgent, prompts[1], 2)):
        want = ref.run([p], max_new_tokens=n)[0].tokens
        np.testing.assert_array_equal(got[rid], want)
    al = pool.running("tiny")[0].engine.allocator
    assert al.n_live == 0 and al.n_reserved == 0 and al.n_host_live == 0
    assert al.n_host_free + al.n_host_cached == al.n_host_blocks


def test_pool_auto_mode_prices_with_calibrated_fits():
    """With both fits calibrated, auto picks the cheaper side — force
    each side with extreme stub costs."""
    pool, rng = _calibrated_swap_pool(preempt_mode="auto")
    pool.token_cost = lambda tp_degree=None: (0.0, 1000.0)  # recompute slow
    pool.swap_cost = lambda: (0.01, 0.01)                   # swap ~free
    _preempt_once(pool, rng)
    pool.run_until_drained()
    assert pool.n_swap_preempted == 1

    pool, rng = _calibrated_swap_pool(preempt_mode="auto")
    pool.token_cost = lambda tp_degree=None: (0.0, 0.0001)  # recompute free
    pool.swap_cost = lambda: (10_000.0, 10_000.0)           # swap awful
    _preempt_once(pool, rng)
    pool.run_until_drained()
    assert pool.n_preempted == 1 and pool.n_swap_preempted == 0


def test_pool_swap_cancel_frees_host_blocks():
    pool, rng = _calibrated_swap_pool(preempt_mode="swap")
    hog, urgent = _preempt_once(pool, rng)
    # the hog is now a queued swap snapshot; cancel it there
    res = pool.cancel(hog)
    assert res is not None and res.cancelled and len(res.tokens) > 0
    pool.run_until_drained()
    for inst in pool.live():
        assert inst.engine.allocator.n_host_live == 0


def test_pool_swap_survives_source_retire():
    """A swap snapshot whose source engine drains away downgrades to
    recompute (releasing or rebuilding) and still finishes with every
    requested token — combined with the satellite-3 check that the
    respawned model starts with clean preemption bookkeeping."""
    pool, rng = _calibrated_swap_pool(preempt_mode="swap")
    hog, urgent = _preempt_once(pool, rng)
    # drain the urgent request, then retire the model entirely while the
    # hog is still a queued swap snapshot
    for _ in range(30):
        pool.step()
        if not any(i.n_resident for i in pool.live()):
            break
    pool.scale_to("tiny", 0)
    while pool.live():
        pool.step()
    assert pool.n_swap_preempted == 1
    # satellite 3: retire of the last instance cleared the per-model
    # preemption bookkeeping
    assert pool.preempts_by_model["tiny"] == 0
    assert "tiny" not in pool._last_preempt_step
    pool.scale_to("tiny", 1)
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    assert len(by_id[hog].tokens) == 24, "swap snapshot lost tokens"


def test_pool_state_has_host_feature():
    from repro.config.base import ServingConfig
    from repro.serving.bcedge import POOL_STATE_DIM, PoolScheduler

    pool, _ = _calibrated_swap_pool()
    scfg = ServingConfig(batch_sizes=(1,), concurrency_levels=(1,))
    sched = PoolScheduler(pool, scfg, slo_ms={"tiny": 1000.0},
                          learn=False, seed=0)
    s = sched._state("tiny")
    assert s.shape == (POOL_STATE_DIM,)
    occ = pool.kv_occupancy()
    assert {"host_blocks", "host_free", "host_live", "host_cached",
            "host_frac"} <= set(occ)
    assert s[-1] == pytest.approx(min(1.0, max(0.0, occ["host_frac"])))


# ------------------------------------------- satellite regression tests
def test_headroom_prices_preempted_snapshots():
    """Satellite 1: a preempted snapshot awaiting re-admission must
    contribute its remaining work to retry_after_s — context re-prefill
    + remaining decode for recompute snapshots, remaining decode only
    for swapped ones (their KV is already resident on the host)."""
    pool = make_pool(TINY, block_size=8)
    pool.scale_to("tiny", 1)
    pool.token_cost = lambda tp_degree=None: (1.0, 2.0)  # calibrated
    base = pool.admission_headroom("tiny", 8, 4)

    def _queued(resume):
        r = PoolRequest(999, "tiny", np.zeros((0,), np.int32),
                        1000.0, 64, 0.0, resume=resume)
        import heapq
        heapq.heappush(pool.queues["tiny"], (r.deadline_s, 0, r))
        out = pool.admission_headroom("tiny", 8, 4)
        pool.queues["tiny"].clear()
        return out

    rec = PreemptedRequest(
        request_id=999, seq_tokens=np.zeros((40,), np.int32), base_len=30,
        max_new=6, submit_s=0.0, requested_new=16, truncated=False,
        n_preempted=1)
    h = _queued(rec)
    # 40 context + 6 remaining — NOT 40 + 64 (the original budget) and
    # NOT zero (the pre-fix behaviour the issue calls out)
    assert h["backlog_tokens"] - base["backlog_tokens"] == 46.0
    assert h["retry_after_s"] > base["retry_after_s"]

    swp = PreemptedRequest(
        request_id=999, seq_tokens=np.zeros((40,), np.int32), base_len=40,
        max_new=6, submit_s=0.0, requested_new=16, truncated=False,
        n_preempted=1, tokens=[1, 2], pos=42, host_blocks=[1, 2, 3],
        host_engine_id=0)
    h = _queued(swp)
    # swapped: remaining decode only, the context never re-prefills
    assert h["backlog_tokens"] - base["backlog_tokens"] == 6.0


def test_stats_exclude_cancelled_timings():
    """Satellite 2: partial timings from cancelled requests must not
    enter ttft/tpot samples (they already sit outside SLO attainment);
    a mid-stream cancel leaves stats() over completed requests only."""
    pool = make_pool(TINY)
    pool.scale_to("tiny", 1)
    rng = np.random.default_rng(7)
    pool.submit("tiny", _prompt(rng, 6), max_new_tokens=6)
    pool.run_until_drained()
    n_before = len(pool.ttft_samples)
    assert n_before >= 1
    rid = pool.submit("tiny", _prompt(rng, 6), max_new_tokens=12)
    for _ in range(4):
        pool.step()  # first token has landed
    res = pool.cancel(rid)
    assert res is not None and res.cancelled \
        and res.first_token_s >= 0, "cancel must catch a started stream"
    pool.run_until_drained()
    assert len(pool.ttft_samples) == n_before, \
        "cancelled request's partial TTFT leaked into stats"
    # the defensive path: a cancelled engine result reaching _finish is
    # flagged and still excluded
    from repro.serving.engine import ContinuousResult
    inst = pool.live()[0]
    req_id = pool.submit("tiny", _prompt(rng, 4), max_new_tokens=2)
    pool.step()
    erid, req = next(iter(inst.requests.items()))
    fake = ContinuousResult(erid, np.array([1], np.int32), 0.0, 0.0, 1.0,
                            n_iters=1, first_token_s=0.5, cancelled=True)
    res = pool._finish(inst, fake)
    assert res.cancelled and res.utility == 0.0
    assert len(pool.ttft_samples) == n_before


def test_preempt_bookkeeping_cleared_on_retire():
    """Satellite 3: scale_to(0) + sweep of the last instance clears
    per-model cooldown and preempt counts, so a respawned model does not
    start inside a stale cooldown window."""
    pool = make_pool(TINY, preemption=True)
    pool.scale_to("tiny", 1)
    rng = np.random.default_rng(9)
    pool.submit("tiny", _prompt(rng, 6), max_new_tokens=2)
    pool.run_until_drained()
    pool.preempts_by_model["tiny"] = 5
    pool._last_preempt_step["tiny"] = pool.n_steps
    pool.scale_to("tiny", 0)
    while pool.live():
        pool.step()
    assert pool.preempts_by_model["tiny"] == 0
    assert "tiny" not in pool._last_preempt_step
    # a model that never spawned keeps its (zero) entry untouched
    pool.scale_to("tiny", 1)
    assert pool.preempts_by_model["tiny"] == 0
