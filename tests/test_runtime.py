"""Multi-model instance-pool runtime: router EDF ordering, lifecycle
(scale/drain/retire) and two-model concurrent serving (docs/RUNTIME.md)."""
import numpy as np
import pytest

from repro.config.base import ModelConfig, ServingConfig
from repro.serving.bcedge import PoolScheduler
from repro.serving.engine import ContinuousBatchingEngine
from repro.serving.latency_model import fit_contention, predicted_iter_ms
from repro.serving.runtime import (DRAINING, RETIRED, RUNNING,
                                   ModelInstancePool)

TINY_A = ModelConfig(name="tiny-a", family="dense", n_layers=2, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=97)
TINY_B = ModelConfig(name="tiny-b", family="dense", n_layers=2, d_model=48,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=83)


def _prompt(rng, vocab=97, n=None):
    return rng.integers(1, vocab, n or rng.integers(4, 12)).astype(np.int32)


def _pool(**kw):
    kw.setdefault("max_instances", 4)
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 64)
    return ModelInstancePool({"tiny-a": TINY_A}, **kw)


def test_pool_kv_layout_keyword_accepts_only_the_block_pool():
    """The pool's ``kv_layout`` keyword selects nothing: ``"paged"`` (the
    default) builds the pool, any other value is refused up front."""
    assert _pool(kv_layout="paged").stats()["kv_budget_tokens"] == 0.0
    for bad in ("dense", "blocks", ""):
        with pytest.raises(ValueError, match="only KV layout"):
            _pool(kv_layout=bad)


# ------------------------------------------------------------ router
def test_router_admits_by_deadline():
    pool = _pool(max_slots=1)
    pool.scale_to("tiny-a", 1)
    rng = np.random.default_rng(0)
    t = pool.now()
    # same submit instant, deadlines out of submission order
    loose = pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                        max_new_tokens=2, submit_s=t)
    tight = pool.submit("tiny-a", _prompt(rng), slo_ms=5_000.0,
                        max_new_tokens=2, submit_s=t)
    mid = pool.submit("tiny-a", _prompt(rng), slo_ms=30_000.0,
                      max_new_tokens=2, submit_s=t)
    res = pool.run_until_drained()
    assert len(res) == 3
    admitted = [rid for rid, _ in pool.admission_log]
    assert admitted == [tight, mid, loose]


def test_router_balances_across_instances():
    pool = _pool(max_slots=2)
    pool.scale_to("tiny-a", 2)
    rng = np.random.default_rng(1)
    for _ in range(4):
        pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                    max_new_tokens=2)
    pool.step()
    used = {iid for _, iid in pool.admission_log}
    assert len(used) == 2  # least-loaded placement spreads the work
    pool.run_until_drained()


def test_slot_cap_is_the_b_axis():
    pool = _pool(max_slots=2)
    pool.scale_to("tiny-a", 1)
    pool.set_slot_cap("tiny-a", 1)
    rng = np.random.default_rng(2)
    for _ in range(3):
        pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                    max_new_tokens=2)
    pool.step()
    inst = pool.running("tiny-a")[0]
    assert inst.n_resident == 1  # capped below the engine's 2 slots
    res = pool.run_until_drained()
    assert len(res) == 3


def test_strict_admission_rejects_expired():
    pool = _pool(strict_admission=True)
    pool.scale_to("tiny-a", 1)
    rng = np.random.default_rng(3)
    dead = pool.submit("tiny-a", _prompt(rng), slo_ms=0.0,
                       max_new_tokens=2)  # deadline == submit instant
    ok = pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                     max_new_tokens=2)
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    assert by_id[dead].rejected and by_id[dead].violated
    assert not by_id[ok].rejected and len(by_id[ok].tokens) == 2
    assert pool.n_rejected == 1
    assert all(rid != dead for rid, _ in pool.admission_log)


# ------------------------------------------------------------ lifecycle
def test_scale_up_down_idempotent():
    pool = _pool()
    assert pool.scale_to("tiny-a", 3) == 3
    ids = sorted(i.instance_id for i in pool.running("tiny-a"))
    assert pool.scale_to("tiny-a", 3) == 3  # idempotent: same instances
    assert sorted(i.instance_id for i in pool.running("tiny-a")) == ids
    assert pool.scale_to("tiny-a", 1) == 1
    states = [i.state for i in pool.instances["tiny-a"]]
    assert states.count(RUNNING) == 1 and states.count(DRAINING) == 2
    pool.scale_to("tiny-a", 1)  # idempotent on the way down too
    assert [i.state for i in pool.instances["tiny-a"]] == states
    # scale-up revives draining instances instead of spawning new ones
    assert pool.scale_to("tiny-a", 2) == 2
    assert all(i.instance_id in ids for i in pool.running("tiny-a"))
    pool.step()  # sweep retires the remaining empty draining instance
    assert pool.total_live() == 2
    assert sum(1 for i in pool.retired if i.model == "tiny-a") == 1


def test_scale_to_clamps_at_max_instances():
    pool = ModelInstancePool({"tiny-a": TINY_A, "tiny-b": TINY_B},
                             max_instances=3, max_slots=2, max_seq=64)
    assert pool.scale_to("tiny-a", 2) == 2
    assert pool.scale_to("tiny-b", 4) == 1  # only one budget slot left
    assert pool.total_live() == 3
    with pytest.raises(RuntimeError):
        pool.spawn("tiny-b")


def test_drain_before_retire_finishes_resident_work():
    pool = _pool()
    pool.scale_to("tiny-a", 1)
    rng = np.random.default_rng(4)
    rid = pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                      max_new_tokens=6)
    pool.step()  # admitted
    pool.drain("tiny-a")
    inst = pool.live("tiny-a")[0]
    assert inst.state == DRAINING and inst.n_resident == 1
    # draining instances accept no new work
    late = pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                       max_new_tokens=2)
    res = pool.run_until_drained()
    by_id = {r.request_id: r for r in res}
    assert len(by_id[rid].tokens) == 6  # scale-down did not truncate
    assert late not in by_id  # still queued: no running instance took it
    assert pool.queue_len("tiny-a") == 1
    assert pool.states("tiny-a") == [RETIRED]
    # scale back up: the queued request is finally served
    pool.scale_to("tiny-a", 1)
    res2 = pool.run_until_drained()
    assert [r.request_id for r in res2] == [late]


# ------------------------------------------------------------ concurrency
def test_two_model_concurrent_smoke():
    pool = ModelInstancePool({"tiny-a": TINY_A, "tiny-b": TINY_B},
                             max_instances=4, max_slots=2, max_seq=64)
    pool.scale_to("tiny-a", 1)
    pool.scale_to("tiny-b", 1)
    rng = np.random.default_rng(5)
    for _ in range(3):
        pool.submit("tiny-a", _prompt(rng, 97), slo_ms=60_000.0,
                    max_new_tokens=3)
        pool.submit("tiny-b", _prompt(rng, 83), slo_ms=60_000.0,
                    max_new_tokens=3)
    res = pool.run_until_drained()
    assert len(res) == 6
    report = pool.report()
    assert report["tiny-a"]["served"] == 3
    assert report["tiny-b"]["served"] == 3
    assert all(len(r.tokens) == 3 for r in res)
    # both models really overlapped inside single pool iterations
    assert any(n >= 2 for n, _ in pool.contention_samples)


def test_pool_matches_standalone_engine_greedy():
    """Routing through the pool must not change what the model computes:
    same weights (shared seed), token-identical greedy output."""
    rng = np.random.default_rng(6)
    prompt = _prompt(rng, 97, 9)
    ref_eng = ContinuousBatchingEngine(TINY_A, max_slots=2, max_seq=64,
                                       seed=0)
    ref = ref_eng.run([prompt], max_new_tokens=4)[0].tokens
    pool = _pool(seed=0)
    pool.scale_to("tiny-a", 2)
    pool.submit("tiny-a", prompt, slo_ms=60_000.0, max_new_tokens=4)
    res = pool.run_until_drained()
    assert np.array_equal(res[0].tokens, ref)


def test_instances_share_weights_and_jit():
    pool = _pool()
    pool.scale_to("tiny-a", 3)
    a, b, c = pool.running("tiny-a")
    assert a.engine.params is b.engine.params is c.engine.params
    assert a.engine._decode is b.engine._decode


# ------------------------------------------------------------ calibration
def test_fit_contention_recovers_linear_model():
    t1, c = 4.0, 0.8
    samples = [(n, predicted_iter_ms(t1, c, n)) for n in (1, 2, 3, 4) * 8]
    ft1, fc = fit_contention(samples)
    assert ft1 == pytest.approx(t1, rel=1e-6)
    assert fc == pytest.approx(c, rel=1e-6)
    # single overlap level: slope unidentifiable, falls back to mean
    ft1, fc = fit_contention([(2, 5.0), (2, 7.0)])
    assert ft1 == pytest.approx(6.0) and fc == 0.0
    assert fit_contention([]) == (0.0, 0.0)


def test_pool_records_contention_samples():
    pool = _pool()
    pool.scale_to("tiny-a", 2)
    rng = np.random.default_rng(7)
    for _ in range(6):
        pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                    max_new_tokens=3)
    pool.run_until_drained()
    assert len(pool.contention_samples) >= 3
    assert all(ms > 0.0 for _, ms in pool.contention_samples)
    t1, c = pool.contention()
    assert t1 >= 0.0 and c >= 0.0


# ------------------------------------------------------------ scheduler
def test_pool_scheduler_drives_real_scaling():
    pool = ModelInstancePool({"tiny-a": TINY_A}, max_instances=3,
                             max_slots=2, max_seq=64)
    scfg = ServingConfig(batch_sizes=(1, 2), concurrency_levels=(1, 2, 3))
    sched = PoolScheduler(pool, scfg, slo_ms={"tiny-a": 60_000.0},
                          decode_steps_mean=3.0, guard=False, seed=0)
    applied = sched.control()
    b, m_c = applied["tiny-a"]
    assert pool.m_c("tiny-a") == m_c >= 1
    assert pool.slot_caps["tiny-a"] == b
    rng = np.random.default_rng(8)
    for _ in range(4):
        pool.submit("tiny-a", _prompt(rng), slo_ms=60_000.0,
                    max_new_tokens=2)
        sched.record(pool.step())
    applied = sched.control()  # closes the transition, re-decides
    assert pool.m_c("tiny-a") == applied["tiny-a"][1]
    pool.run_until_drained()


# ------------------------------------------------ speculation (fourth axis)
def test_set_spec_k_applies_to_live_engines_and_spawns():
    pool = _pool(block_size=8, spec_k=4)
    pool.scale_to("tiny-a", 1)
    eng = pool.live("tiny-a")[0].engine
    assert eng.spec_max == 4 and eng.spec_k == 4
    pool.set_spec_k("tiny-a", 2)
    assert eng.spec_k == 2 and pool.spec_ks["tiny-a"] == 2
    # future spawns inherit the CURRENT depth under the built cap
    pool.scale_to("tiny-a", 2)
    assert all(i.engine.spec_k == 2 for i in pool.live("tiny-a"))
    # clamped per-engine to the construction-time scratch capacity
    pool.set_spec_k("tiny-a", 99)
    assert all(i.engine.spec_k == 4 for i in pool.live("tiny-a"))


def test_spec_cap_zero_pool_is_inert():
    pool = _pool()  # spec off
    pool.scale_to("tiny-a", 1)
    eng = pool.live("tiny-a")[0].engine
    assert eng.spec_max == 0
    pool.set_spec_k("tiny-a", 4)  # always safe: clamps to 0
    assert eng.spec_k == 0
    assert pool.spec_accept_rate() == 0.0
    assert pool.stats()["spec_accept_rate"] == 0.0


def test_pool_speculative_serving_matches_baseline():
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng) for _ in range(4)]
    base = ContinuousBatchingEngine(TINY_A, max_slots=2, max_seq=64, seed=0)
    want = {tuple(p): base.run([p], max_new_tokens=4)[0].tokens
            for p in prompts}
    pool = _pool(block_size=8, spec_k=4)
    pool.scale_to("tiny-a", 1)
    rids = {pool.submit("tiny-a", p, slo_ms=60_000.0, max_new_tokens=4):
            tuple(p) for p in prompts}
    got = {}
    for _ in range(400):
        for r in pool.step():
            got[r.request_id] = r.tokens
        if len(got) == len(rids):
            break
    assert len(got) == len(rids)
    for rid, key in rids.items():
        np.testing.assert_array_equal(got[rid], want[key])
    assert pool.spec_accept_rate() >= 0.0


def test_guard_degrades_spec_k_first():
    """Infeasible k collapses toward 0 BEFORE the token budget,
    concurrency or batch degrade: the verify surcharge is pure overhead,
    so shedding it never costs capacity (docs/RUNTIME.md §8)."""
    from repro.serving.bcedge import POOL_STATE_DIM

    pool = _pool()
    scfg = ServingConfig(batch_sizes=(1, 2), concurrency_levels=(1, 2),
                         token_budgets=(0, 16), spec_depths=(0, 2, 4))
    sched = PoolScheduler(pool, scfg, slo_ms={"tiny-a": 1000.0},
                          decode_steps_mean=1.0, learn=False, seed=0)
    # calibrated token cost: 200ms/token makes any k > 0 overshoot the
    # 1000ms iteration budget at b=2 (work = b + k*b >= 6 tokens) while
    # k=0 with tb=0 prices nothing
    pool.token_cost = lambda: (0.0, 200.0)
    a = scfg.quad_to_action(2, 1, 0, 4)
    applied = sched._apply("tiny-a", a)
    b, m_c, tb, k = scfg.action_to_quad(applied)
    assert (b, m_c, tb, k) == (2, 1, 0, 0), (b, m_c, tb, k)
    assert sched.guard_interventions == 1
    assert pool.spec_ks["tiny-a"] == 0
    # the state vector carries the acceptance feature, winsorized to [0,1]
    pool.spec_accept_rate = lambda: 3.7
    s = sched._state("tiny-a")
    assert s.shape == (POOL_STATE_DIM,) == (13,)
    assert s[10] == 1.0
    pool.spec_accept_rate = lambda: -0.5
    assert sched._state("tiny-a")[10] == 0.0


def test_guard_prices_spec_k_through_token_cost():
    """_feasible: k*b extra verify tokens ride the token-cost fit — on
    top of the token budget when one is set, on top of the b-token
    decode floor when not."""
    pool = _pool()
    scfg = ServingConfig(batch_sizes=(1, 2), concurrency_levels=(1,),
                         token_budgets=(0, 8), spec_depths=(0, 4))
    sched = PoolScheduler(pool, scfg, slo_ms={"tiny-a": 1000.0},
                          decode_steps_mean=1.0, learn=False, seed=0)
    pool.token_cost = lambda: (0.0, 50.0)  # 50ms/token, 1000ms budget
    assert sched._feasible("tiny-a", 2, 1, 0, 0)       # nothing priced
    assert sched._feasible("tiny-a", 2, 1, 8, 0)       # 8 tok = 400ms
    assert sched._feasible("tiny-a", 2, 1, 0, 4)       # 2+8 tok = 500ms
    assert sched._feasible("tiny-a", 2, 1, 8, 4)       # 8+8 tok = 800ms
    assert not sched._feasible("tiny-a", 2, 1, 16, 4)  # 16+8 = 1200ms
    assert not sched._feasible("tiny-a", 2, 1, 0, 16)  # 2+32 = 1700ms
