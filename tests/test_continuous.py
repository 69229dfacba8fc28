"""Continuous (iteration-level) batching — engine slot mechanics and
simulator parity with round mode (docs/ARCHITECTURE.md §5/§6)."""
import numpy as np
import pytest

from conftest import (KIND_CFGS, TINY, flat_live_blocks, make_cont_engine,
                      make_pool)
from repro.config.base import ServingConfig
from repro.core.baselines import FixedScheduler
from repro.serving.bcedge import run_episode
from repro.serving.engine import ContinuousBatchingEngine, InferenceEngine
from repro.serving.simulator import EdgeServingEnv
from repro.serving.workload import PoissonWorkload


@pytest.fixture(scope="module")
def cont_engine():
    return ContinuousBatchingEngine(TINY, max_slots=3, max_seq=64)


# ------------------------------------------------------------ engine
def test_engine_slot_admission_and_eviction(cont_engine):
    eng = cont_engine
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, rng.integers(3, 12)).astype(np.int32)
               for _ in range(6)]
    res = eng.run(prompts, max_new_tokens=5)
    # more requests than slots: finished sequences freed slots for the rest
    assert eng.n_slots == 3 and len(prompts) == 6
    assert [r.request_id for r in res] == list(range(6))
    assert all(len(r.tokens) == 5 for r in res)
    assert eng.n_admitted == 6 and eng.n_evicted == 6
    assert len(eng.free_slots) == eng.n_slots  # fully drained
    # iteration-level: 6 sequences shared slots, far fewer iterations than
    # 6 sequential 5-token generations
    assert 5 <= eng.n_iters < 30


def test_engine_unequal_lengths_free_slots_early():
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64)
    rng = np.random.default_rng(1)
    long_p = rng.integers(1, 97, 8).astype(np.int32)
    eng.submit(long_p, max_new_tokens=8)
    for _ in range(3):
        eng.submit(rng.integers(1, 97, 5).astype(np.int32),
                   max_new_tokens=2)
    done = []
    for _ in range(20):
        done.extend(eng.step())
        if len(done) == 4:
            break
    assert len(done) == 4
    by_id = {r.request_id: r for r in done}
    assert len(by_id[0].tokens) == 8
    assert all(len(by_id[i].tokens) == 2 for i in (1, 2, 3))
    # short requests drained through the second slot while the long one
    # ran: total iterations ~ the LONGEST sequence, not the sum
    assert eng.n_iters <= 10


def test_engine_matches_round_engine_greedy():
    round_eng = InferenceEngine(TINY, max_seq=64)
    cont_eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=64)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in (4, 9, 13)]
    ref = [round_eng.generate([p], max_new_tokens=4).tokens[0]
           for p in prompts]
    res = cont_eng.run(prompts, max_new_tokens=4)
    for r, expected in zip(res, ref):
        assert np.array_equal(r.tokens, expected)


def test_engine_jit_cache_stays_bucketed():
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=128)
    rng = np.random.default_rng(3)
    # 8 distinct prompt lengths spanning 3 length buckets (16, 32, 64)
    lengths = (3, 9, 15, 17, 30, 33, 50, 60)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in lengths]
    res = eng.run(prompts, max_new_tokens=2)
    assert len(res) == len(lengths)
    assert eng.stats()["n_prefill_shapes"] <= 3  # buckets, not raw lengths
    # decode compiled exactly one shape: (n_slots, 1) for the lifetime
    if hasattr(eng._decode, "_cache_size"):
        assert eng._decode._cache_size() == 1


def test_engine_rejects_oversized_prompt():
    eng = ContinuousBatchingEngine(TINY, max_slots=2, max_seq=32)
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 40, dtype=np.int32))


def test_bucket_rejects_overlength_prompt():
    """Regression: _bucket silently clamped n > buckets[-1] to the
    largest bucket, so submit() under-counted S and its cache-fit check
    passed for prompts that do not actually fit the cache. (The largest
    bucket is 640 since the prefix-cache work: 512-token shared
    prefixes plus a tail must fit one bucket.)"""
    from repro.serving.engine import SEQ_BUCKETS, _bucket
    assert _bucket(512, buckets=SEQ_BUCKETS) == 512
    assert _bucket(640, buckets=SEQ_BUCKETS) == 640
    with pytest.raises(ValueError):
        _bucket(641, buckets=SEQ_BUCKETS)
    eng = ContinuousBatchingEngine(TINY, max_slots=1, max_seq=1024)
    with pytest.raises(ValueError):
        # would have been admitted pre-fix (clamped S=640 "fits" 1024)
        eng.submit(np.arange(1, 701, dtype=np.int32) % 97)


def test_engine_rejects_enc_dec():
    import dataclasses
    enc = dataclasses.replace(TINY, name="tiny-ed", enc_dec=True,
                              n_enc_layers=1)
    with pytest.raises(NotImplementedError):
        ContinuousBatchingEngine(enc, max_slots=2, max_seq=32)


# ------------------------------------------------------------ decode forms
#: prompt lengths, one wave each, whose live blocks (4 slots, blocks of
#: 4, 512 positions) take each flat width in turn and then outgrow them:
#: 5 blocks -> 32, 34 -> 64, 83 -> 128, 164 -> 256, 260 -> the tables
DECODE_WAVES = ((16,), (64, 64), (128, 128, 64), (256, 256, 64, 64),
                (256, 256, 256, 256))


def _decode_forms(eng):
    """Wrap ``eng._decode`` to record each call's form (its flat width,
    0 for the table gather) and check its flat input against the loop
    reference."""
    forms, decode = [], eng._decode

    def call(params, cache, batch):
        live = batch.get("live_blocks")
        forms.append(0 if live is None else live.shape[1])
        if live is not None:
            want = flat_live_blocks(eng.block_tables, eng.pos,
                                    eng.decoding_slots, eng.block_size,
                                    live.shape[1])
            np.testing.assert_array_equal(np.asarray(live), want)
        return decode(params, cache, batch)

    eng._decode = call
    return forms


def _run_waves(submit, drain):
    rng = np.random.default_rng(7)
    for wave in DECODE_WAVES:
        for n in wave:
            submit(rng.integers(1, 97, n).astype(np.int32))
        drain()


def _wave_engine():
    return make_cont_engine(TINY, max_slots=4, max_seq=512, block_size=4)


@pytest.mark.parametrize("total", [1, 32, 33, 256, 257])
def test_live_blocks_take_the_smallest_width_that_holds_them(total):
    eng = _wave_engine()
    n = [total // 4 + (i < total % 4) for i in range(4)]
    active = [i for i in range(4) if n[i]]
    ids = iter(range(1, total + 1))
    for i in active:
        eng.block_tables[i, :n[i]] = [next(ids) for _ in range(n[i])]
        eng.pos[i] = (n[i] - 1) * eng.block_size + i % eng.block_size
    live = eng._live_blocks(active)
    width = next((b for b in (32, 64, 128, 256) if total <= b), None)
    if width is None:
        assert live is None
    else:
        np.testing.assert_array_equal(live, flat_live_blocks(
            eng.block_tables, eng.pos, active, eng.block_size, width))


def test_decode_form_keeps_greedy_tokens(monkeypatch):
    """Greedy tokens of a mixed-length trace are the same whichever form
    each decode step takes: all tables (ceiling 0), flat up to 64
    blocks, flat up to 256."""
    import repro.serving.engine as engine_mod

    tokens, forms = {}, {}
    for ceiling in (0, 64, 256):
        monkeypatch.setattr(engine_mod, "DECODE_FLAT_MAX_BLOCKS", ceiling)
        eng = _wave_engine()
        forms[ceiling] = _decode_forms(eng)
        done = []
        _run_waves(lambda p: eng.submit(p, max_new_tokens=3),
                   lambda: done.extend(eng.run([], max_new_tokens=3)))
        tokens[ceiling] = {r.request_id: r.tokens.tolist() for r in done}
    assert set(forms[0]) == {0}
    assert set(forms[64]) == {0, 32, 64}
    assert set(forms[256]) == {0, 32, 64, 128, 256}
    assert tokens[64] == tokens[0] and tokens[256] == tokens[0]
    assert len(tokens[0]) == sum(map(len, DECODE_WAVES))


@pytest.mark.parametrize("kind", ["global", "swa"])
def test_decode_counters_count_what_ran(kind):
    """The counters add each step's flat width, or the tables' blocks;
    an all-windowed stack keeps no KV in the pool, so it gathers none."""
    eng = make_cont_engine(KIND_CFGS[kind], max_slots=4, max_seq=512,
                           block_size=4)
    forms = _decode_forms(eng)
    _run_waves(lambda p: eng.submit(p, max_new_tokens=3),
               lambda: eng.run([], max_new_tokens=3))
    st = eng.stats()
    assert st["n_decode_flat_steps"] == sum(w > 0 for w in forms)
    assert st["n_decode_kv_blocks"] == sum(
        w or eng.table_blocks for w in forms)
    if kind == "global":
        assert eng.decode_buckets == (32, 64, 128, 256)
        assert eng.table_blocks == eng.n_slots * eng.blocks_per_slot
        assert 0 in forms and st["n_decode_flat_steps"] > 0
    else:
        assert eng.decode_buckets == () and set(forms) == {0}
        assert st["n_decode_kv_blocks"] == 0


def test_warmup_compiles_every_decode_form():
    """After the pool's warm-up, a trace that steps through every flat
    width and the table form compiles nothing new."""
    pool = make_pool(TINY, max_instances=1, max_slots=4, max_seq=512,
                     block_size=4)
    pool.scale_to(TINY.name, 1)
    pool.warmup(prompt_lens=(16, 64, 128, 256))
    eng = pool.live(TINY.name)[0].engine
    if hasattr(eng._decode, "_cache_size"):
        assert eng._decode._cache_size() == 1 + len(eng.decode_buckets)
    compiled, flat = eng.n_compiled_steps, eng.n_decode_flat_steps
    forms = _decode_forms(eng)
    _run_waves(lambda p: pool.submit(TINY.name, p, slo_ms=600_000.0,
                                     max_new_tokens=3),
               pool.run_until_drained)
    assert set(forms) == {0} | set(eng.decode_buckets)
    assert eng.n_compiled_steps == compiled
    assert pool.stats()["n_decode_flat_steps"] - flat \
        == sum(w > 0 for w in forms)


# ------------------------------------------------------------ donation
def _drain(engines):
    """Step ``engines`` in alternation until all are idle: each step
    donates its engine's cache while the other's stays live."""
    out = [{} for _ in engines]
    for _ in range(200):
        busy = False
        for eng, got in zip(engines, out):
            if eng.waiting or eng.active_slots:
                busy = True
                for r in eng.step():
                    got[r.request_id] = r.tokens
        if not busy:
            return out
    raise AssertionError("engines did not drain")


@pytest.mark.parametrize("kind,spec_k", [("global", 0), ("global", 2),
                                         ("rglru", 0)],
                         ids=["fused", "speculative", "staging"])
def test_shared_engines_donating_in_alternation_stay_identical(kind,
                                                               spec_k):
    """Engines built with ``share_from`` share the donating step jits but
    own their caches: stepped in alternation (fused prefill, speculative
    verify, or a hybrid stack's staging prefill and per-slot state), each
    serves exactly what the same requests get served alone."""
    cfg = KIND_CFGS[kind]
    kw = dict(max_slots=2, max_seq=64, block_size=8,
              spec_k=spec_k)
    rng = np.random.default_rng(7)
    prompts = [[rng.integers(1, 97, n).astype(np.int32) for n in ns]
               for ns in ((5, 13, 9), (11, 4))]
    want = [[make_cont_engine(cfg, **kw).run([p], max_new_tokens=7)[0]
             .tokens for p in ps] for ps in prompts]
    donor = make_cont_engine(cfg, **kw)
    engines = [donor, make_cont_engine(cfg, share_from=donor, **kw)]
    assert engines[1]._decode is donor._decode
    ids = [[eng.submit(p, max_new_tokens=7) for p in ps]
           for eng, ps in zip(engines, prompts)]
    for got, rids, ws in zip(_drain(engines), ids, want):
        for rid, w in zip(rids, ws):
            np.testing.assert_array_equal(got[rid], w)


def test_swap_resume_and_prefix_hit_after_donated_steps():
    """Host-tier swap out and back in, and a prefix-cache hit, on a pool
    that many donated steps have updated in place: the swapped blocks
    and the shared prefix blocks read back what was written."""
    kw = dict(max_slots=2, max_seq=64, block_size=8,
              kv_blocks=24, kv_host_blocks=16, prefix_cache=True)
    rng = np.random.default_rng(8)
    first, other = (rng.integers(1, 97, n).astype(np.int32)
                    for n in (21, 6))
    sibling = np.concatenate([first[:16], rng.integers(1, 97, 5)
                              .astype(np.int32)])
    want = {name: make_cont_engine(TINY, **kw).run(
        [p], max_new_tokens=10)[0].tokens
        for name, p in (("first", first), ("other", other),
                        ("sibling", sibling))}
    eng = make_cont_engine(TINY, **kw)
    rid = {"first": eng.submit(first, max_new_tokens=10),
           "other": eng.submit(other, max_new_tokens=10)}
    got = {}
    for _ in range(5):
        for r in eng.step():
            got[r.request_id] = r.tokens
    slot = next(i for i in eng.decoding_slots
                if eng.slots[i].request_id == rid["first"])
    snap = eng.preempt(slot, requeue=False, mode="swap")
    assert snap.swapped
    for _ in range(3):  # donated steps while the blocks sit on the host
        for r in eng.step():
            got[r.request_id] = r.tokens
    rid["first"] = eng.submit_resume(snap)
    got.update(_drain([eng])[0])
    rid["sibling"] = eng.submit(sibling, max_new_tokens=10)
    got.update(_drain([eng])[0])
    assert eng.n_swap_resumes == 1 and eng.n_prefix_hits >= 1
    for name, w in want.items():
        np.testing.assert_array_equal(got[rid[name]], w, err_msg=name)


# ------------------------------------------------------------ workload
def test_workload_decode_steps_geometric():
    wl = PoissonWorkload(rps=30.0, seed=0, decode_steps_mean=6.0)
    steps = [wl.next_request().decode_steps for _ in range(4000)]
    assert min(steps) >= 1
    assert np.mean(steps) == pytest.approx(6.0, rel=0.15)
    wl1 = PoissonWorkload(rps=30.0, seed=0)  # default: single-shot
    assert all(wl1.next_request().decode_steps == 1 for _ in range(50))


# ------------------------------------------------------------ simulator
def _drive(cfg: ServingConfig, seed: int, action: int, episode_ms=3000.0):
    env = EdgeServingEnv(cfg, episode_ms=episode_ms, seed=seed)
    done, steps = False, 0
    while not done and steps < 400:
        _, _, done, _ = env.step(action)
        steps += 1
    return env


def _in_flight(env) -> int:
    n = 0
    for t, _, kind, payload in env._events:
        if kind == "complete":
            n += payload.n_requests
        elif kind == "iter":
            n += len(payload.active) + len(payload.done)
    return n


@pytest.mark.parametrize("seed,action", [(0, 5), (1, 20), (2, 41), (3, 9)])
def test_continuous_conserves_requests(seed, action):
    cfg = ServingConfig(exec_mode="continuous", decode_steps_mean=4.0)
    env = _drive(cfg, seed, action)
    served = sum(r.n_requests for r in env.history)
    queued = sum(len(q) for q in env.queues.values())
    dropped = sum(q.dropped for q in env.queues.values())
    assert served + queued + _in_flight(env) + dropped == env.total_requests


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_continuous_queue_waits_nonnegative(seed):
    cfg = ServingConfig(exec_mode="continuous", decode_steps_mean=4.0)
    env = _drive(cfg, seed, action=12)
    assert env.history, "no sessions completed"
    for rnd in env.history:
        assert rnd.exec_mode == "continuous"
        assert rnd.n_iters >= 1
        assert rnd.finish_ms >= rnd.start_ms >= rnd.decision_ms
        assert len(rnd.queue_waits_ms) == rnd.n_requests
        for w in rnd.queue_waits_ms:
            assert w >= 0.0
        for lat in rnd.latencies_ms:
            assert lat > 0.0
        if not rnd.overflow:
            assert len(rnd.request_utilities) == rnd.n_requests


def test_continuous_beats_round_on_decode_heavy():
    """Decode-heavy workload: iteration-level batching must win p50
    latency AND goodput over run-to-completion rounds."""
    summaries = {}
    for mode in ("round", "continuous"):
        cfg = ServingConfig(exec_mode=mode, decode_steps_mean=6.0)
        env = EdgeServingEnv(cfg, episode_ms=8000.0, seed=0)
        res = run_episode(env, FixedScheduler(cfg.pair_to_action(4, 2)),
                          predictor=None, guard=False, learn=False)
        summaries[mode] = res.summary
    assert summaries["continuous"]["p50_latency_ms"] < \
        summaries["round"]["p50_latency_ms"]
    assert summaries["continuous"]["goodput_rps"] >= \
        summaries["round"]["goodput_rps"]


def test_round_mode_single_shot_unchanged():
    """decode_steps_mean=1 keeps round mode in the paper's regime:
    every round is a single lock-step iteration."""
    cfg = ServingConfig()  # defaults: round, single-shot
    env = _drive(cfg, seed=0, action=5)
    assert env.history
    for rnd in env.history:
        assert rnd.exec_mode == "round"
        assert rnd.n_iters == 1


def test_continuous_sessions_batch_more_than_capacity():
    """Join/leave really happens: with slot capacity b*m_c = 8, sessions
    should serve more requests than their initial allocation when the
    queue is deep."""
    cfg = ServingConfig(exec_mode="continuous", decode_steps_mean=4.0,
                        arrival_rps=60.0)
    env = _drive(cfg, seed=0, action=cfg.pair_to_action(4, 2),
                 episode_ms=6000.0)
    assert any(r.n_requests > 8 for r in env.history if not r.overflow)
