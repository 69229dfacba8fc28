"""SLO-aware preemption (docs/RUNTIME.md §8) and allocator/queue
hardening: preempted blocks are fully returned, preempt-resume greedy
output is token-identical to an uninterrupted run, the pool policy
triggers/holds back correctly, double-frees raise, and
``run_until_drained`` no longer returns silent partial results."""
import numpy as np
import pytest

from conftest import TINY, make_pool
from repro.config.base import ServingConfig
from repro.serving.engine import BlockAllocator, ContinuousBatchingEngine
from repro.serving.simulator import EdgeServingEnv


def _prompt(rng, n):
    return rng.integers(1, 97, n).astype(np.int32)


# ------------------------------------------------- allocator hardening
def test_double_free_raises():
    """Regression: free() only range-checked ids, so a double-freed id
    entered the free list twice and one physical block could be handed
    to two sequences."""
    alloc = BlockAllocator(4, 16)
    assert alloc.reserve(2)
    a, b = alloc.alloc_reserved(), alloc.alloc_reserved()
    alloc.free([a])
    with pytest.raises(ValueError):
        alloc.free([a])          # double free
    with pytest.raises(ValueError):
        alloc.free([b, b])       # duplicate within one call
    with pytest.raises(ValueError):
        alloc.free([0])          # null block was never handed out
    alloc.free([b])
    assert alloc.n_free == 4


# ------------------------------------------------- engine mechanics
@pytest.mark.slow
@pytest.mark.parametrize("token_budget", [None, 8],
                         ids=["uncapped", "budget8"])
def test_preempt_returns_blocks_and_resumes_identically(token_budget):
    """The core invariants: (1) a preempted sequence's blocks are fully
    returned to the allocator, (2) after resume the greedy output is
    token-identical to an uninterrupted run. Under an 8-token budget the
    resume re-prefills its 36-token context over several iterations."""
    kw = {"block_size": 16, "token_budget": token_budget}
    rng = np.random.default_rng(0)
    prompt = _prompt(rng, 20)

    ref_eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                       seed=0, **kw)
    ref = ref_eng.run([prompt], max_new_tokens=10)[0].tokens

    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                   seed=0, **kw)
    eng.submit(prompt, max_new_tokens=10)
    # emit a few tokens, then evict mid-sequence
    while not (eng.decoding_slots
               and eng.slots[eng.decoding_slots[0]].n_emitted >= 4):
        eng.step()
    [slot] = eng.decoding_slots
    held = len(eng.slots[slot].blocks) + eng.slots[slot].n_outstanding
    avail_before = eng.allocator.n_available
    eng.preempt(slot)  # requeues at the engine FIFO head
    assert eng.n_preempted == 1
    assert eng.allocator.n_available == avail_before + held
    assert eng.allocator.n_free == eng.allocator.n_blocks
    assert eng.allocator.n_reserved == 0
    done = []
    n_prefill_iters = 0
    for _ in range(60):
        done.extend(eng.step())
        n_prefill_iters += bool(eng.prefilling_slots)
        if done:
            break
    assert len(done) == 1
    assert done[0].n_preempted == 1
    assert np.array_equal(done[0].tokens, ref)
    assert eng.allocator.n_free == eng.allocator.n_blocks
    # an uncapped resume re-prefills in one iteration; a capped one
    # leaves its context part-prefilled after all but the last
    assert n_prefill_iters == (0 if token_budget is None else 4)


@pytest.mark.slow
def test_preempt_refuses_mid_prefill_and_empty_slots():
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128,
                                   token_budget=8)
    rng = np.random.default_rng(1)
    with pytest.raises(ValueError):
        eng.preempt(0)  # nothing resident
    eng.submit(_prompt(rng, 60), max_new_tokens=2)  # bucket 64 > budget
    eng.step()
    [slot] = eng.prefilling_slots
    with pytest.raises(ValueError):
        eng.preempt(slot)  # never a mid-chunk prefill
    assert eng.preemption_candidates() == []


@pytest.mark.slow
def test_double_preempt_round_trip_stays_token_identical():
    """Two preemptions of the same sequence still reconstruct the exact
    uninterrupted greedy continuation (recompute covers prompt + all
    emitted context each time)."""
    rng = np.random.default_rng(2)
    prompt = _prompt(rng, 12)
    ref = ContinuousBatchingEngine(TINY, max_slots=1, max_seq=128,
                                   seed=0).run([prompt],
                                               max_new_tokens=12)[0].tokens
    eng = ContinuousBatchingEngine(TINY, max_slots=1, max_seq=128, seed=0)
    eng.submit(prompt, max_new_tokens=12)
    done = []
    kicked = 0
    for step in range(100):
        done.extend(eng.step())
        if done:
            break
        if step in (3, 9) and eng.decoding_slots:
            eng.preempt(eng.decoding_slots[0])
            kicked += 1
    assert kicked == 2 and len(done) == 1
    assert done[0].n_preempted == 2
    assert np.array_equal(done[0].tokens, ref)


# ------------------------------------------------- pool policy
def _calibrated_pool(**kw):
    """Pool with one running instance and a warm contention fit (the
    preemption trigger needs a calibrated service-time prediction)."""
    kw.setdefault("max_instances", 2)
    kw.setdefault("max_slots", 1)
    kw.setdefault("max_seq", 64)
    kw.setdefault("preemption", True)
    pool = make_pool(TINY, **kw)
    pool.scale_to("tiny", 1)
    rng = np.random.default_rng(3)
    for _ in range(2):
        pool.submit("tiny", _prompt(rng, 6), slo_ms=60_000.0,
                    max_new_tokens=8)
    pool.run_until_drained()
    assert pool.contention()[0] > 0.0
    return pool, rng


@pytest.mark.slow
def test_pool_preempts_largest_slack_for_urgent_request():
    pool, rng = _calibrated_pool()
    hog = pool.submit("tiny", _prompt(rng, 8), slo_ms=60_000.0,
                      max_new_tokens=24)
    for _ in range(6):  # hog resident and decoding, slots now full
        pool.step()
    urgent = pool.submit("tiny", _prompt(rng, 6), slo_ms=0.001,
                         max_new_tokens=2)
    res = pool.run_until_drained()
    assert pool.n_preempted == 1
    by_id = {r.request_id: r for r in res}
    # urgent got the slot and finished; the hog resumed afterwards and
    # still emitted every requested token
    assert len(by_id[urgent].tokens) == 2
    assert len(by_id[hog].tokens) == 24
    assert by_id[urgent].finish_s < by_id[hog].finish_s
    assert pool.report()["tiny"]["preempted"] == 1.0


@pytest.mark.slow
def test_pool_preemption_holds_back_without_urgency_or_margin():
    """Hysteresis: a waiting request with plenty of slack, or a victim
    that is no laxer than the waiter, must NOT trigger an eviction."""
    pool, rng = _calibrated_pool()
    pool.submit("tiny", _prompt(rng, 8), slo_ms=60_000.0,
                max_new_tokens=24)
    for _ in range(6):
        pool.step()
    # ample slack: waiting is cheaper than recompute
    pool.submit("tiny", _prompt(rng, 6), slo_ms=60_000.0,
                max_new_tokens=2)
    pool.run_until_drained()
    assert pool.n_preempted == 0


@pytest.mark.slow
def test_no_preemption_thrash_under_sustained_overload():
    """Sustained tight-SLO overload: cooldown + per-request caps keep
    preemptions rare and every admitted sequence completes in full."""
    pool, rng = _calibrated_pool()
    want = {}
    for k in range(10):
        rid = pool.submit("tiny", _prompt(rng, 6), slo_ms=0.001,
                          max_new_tokens=4)
        want[rid] = 4
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    for rid, n in want.items():
        assert len(by_id[rid].tokens) == n  # no sequence lost or clipped
    # at most one eviction per cooldown window ever fires
    assert pool.n_preempted <= pool.n_steps // pool.preempt_cooldown_steps \
        + 1


# ------------------------------------------------- drained-flag satellite
@pytest.mark.slow
def test_run_until_drained_raises_on_exhaustion():
    """Regression: max_steps exhaustion silently returned partial
    results, so benchmarks read partial completions as full drains."""
    pool = make_pool(TINY, max_instances=1, max_slots=1)
    pool.scale_to("tiny", 1)
    rng = np.random.default_rng(4)
    pool.submit("tiny", _prompt(rng, 6), slo_ms=60_000.0, max_new_tokens=8)
    with pytest.raises(RuntimeError, match="max_steps exhausted"):
        pool.run_until_drained(max_steps=2)
    # with room to finish, the same workload drains cleanly
    assert len(pool.run_until_drained()) == 1


@pytest.mark.slow
def test_run_until_drained_returns_on_unservable_queue():
    """Queued work whose model has NO running instance cannot progress:
    that is a clean return (everything drainable was drained), not an
    exhaustion error — and not a 10k-step spin."""
    pool = make_pool(TINY, max_instances=1, max_slots=1)
    rng = np.random.default_rng(5)
    pool.submit("tiny", _prompt(rng, 6), slo_ms=60_000.0, max_new_tokens=2)
    assert pool.run_until_drained() == []
    assert pool.queue_len("tiny") == 1
    assert pool.n_steps == 0  # detected immediately, no spin


# ------------------------------------------------- simulator twin
@pytest.mark.parametrize("seed", [0, 1])
def test_sim_preemption_conserves_requests(seed):
    cfg = ServingConfig(exec_mode="continuous", decode_steps_mean=4.0,
                        prefill_tokens_mean=24.0, token_budgets=(0, 16),
                        preemption=True, arrival_rps=60.0)
    env = EdgeServingEnv(cfg, episode_ms=3000.0, seed=seed)
    done, steps = False, 0
    while not done and steps < 400:
        _, _, done, _ = env.step(steps % cfg.n_actions)
        steps += 1
    served = sum(r.n_requests for r in env.history)
    queued = sum(len(q) for q in env.queues.values())
    dropped = sum(q.dropped for q in env.queues.values())
    in_flight = 0
    for _, _, kind, payload in env._events:
        if kind == "complete":
            in_flight += payload.n_requests
        elif kind == "iter":
            in_flight += len(payload.active) + len(payload.done)
    assert served + queued + in_flight + dropped == env.total_requests


def test_sim_token_budget_caps_iteration_tokens():
    """With a token budget, a session's planned iteration work never
    exceeds budget (decode rows included once prefill is paid)."""
    from repro.serving.request import Request
    from repro.serving.simulator import _Session

    reqs = []
    for i, (pf, dec) in enumerate([(40, 4), (0, 3), (10, 2)]):
        r = Request(model="m", input_type="text", input_shape=(1,),
                    slo_ms=1000.0, arrival_ms=0.0, decode_steps=dec,
                    prefill_tokens=pf)
        r.remaining = dec
        r.prefill_remaining = pf
        reqs.append(r)
    sess = _Session("m", 4, 1, 0.0, 0.0, 1e9, 0.0, None, 0,
                    token_budget=8)
    sess.active = reqs
    total, alloc = sess.plan_tokens()
    assert total <= 8
    assert alloc == [7, 0, 0]  # 1 decode row + 7 budgeted prefill tokens
    sess.token_budget = 0
    total, alloc = sess.plan_tokens()
    assert total == 1 + 40 + 10  # uncapped: all prefill in one iteration
