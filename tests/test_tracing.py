"""The serving program's own profiler spans and work counters
(``serving/tracing.py``): a profiler trace of a few driver turns holds
every ``repro.*`` span, nested as the modules document; the engine counts
the rows of its decode calls, its prefill padding and its compiling
steps, and the pool sums them; the driver lets a waiting caller in
before its next step."""
import gc
import threading
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import TINY, make_cont_engine, make_pool
from repro.config import ServingConfig
from repro.serving.bcedge import PoolScheduler
from repro.serving.driver import ServingDriver
from repro.serving.runtime import ENGINE_COUNTERS

#: every span of the program, with the span each must lie inside (on
#: the same thread); None: none required
PARENT = {
    "repro.driver.turn": None,
    "repro.driver.lock_wait": "repro.driver.turn",
    "repro.driver.on_tick": "repro.driver.turn",
    "repro.driver.idle": "repro.driver.turn",
    "repro.python.gc": None,
    "repro.pool.sweep": "repro.driver.turn",
    "repro.pool.route": "repro.driver.turn",
    "repro.pool.instance_step": "repro.driver.turn",
    "repro.pool.finish": "repro.driver.turn",
    "repro.pool.calibrate": "repro.driver.turn",
    "repro.engine.admit": "repro.pool.instance_step",
    "repro.engine.prefill_piece": "repro.pool.instance_step",
    "repro.engine.prefill_readback": "repro.pool.instance_step",
    "repro.engine.emit": "repro.pool.instance_step",
    "repro.engine.decode_batch": "repro.pool.instance_step",
    "repro.engine.decode_dispatch": "repro.pool.instance_step",
    "repro.engine.decode_readback": "repro.pool.instance_step",
    "repro.engine.retire": "repro.pool.instance_step",
    "repro.scheduler.tick": "repro.driver.on_tick",
    "repro.scheduler.harvest": "repro.scheduler.tick",
    "repro.scheduler.state": "repro.scheduler.tick",
    "repro.scheduler.act": "repro.scheduler.tick",
    "repro.scheduler.update": "repro.scheduler.tick",
    "repro.scheduler.apply": "repro.scheduler.tick",
}
ENGINE_SPANS = ("repro.engine.admit", "repro.engine.prefill_piece",
                "repro.engine.prefill_readback",
                "repro.engine.decode_batch", "repro.engine.decode_dispatch",
                "repro.engine.decode_readback", "repro.engine.retire")


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, TINY.vocab_size, n).astype(np.int32)


def _traced(tmp_path: Path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host events it
    recorded, per thread: {thread: [(start, end, name)]}."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    xp, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    out = defaultdict(list)
    for p in ProfileData.from_file(str(xp)).planes:
        if p.name != "/host:CPU":
            continue
        for i, line in enumerate(p.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out[(i, line.name)].append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name))
    return out


def _inside(span, parents) -> bool:
    s, e, _ = span
    return any(ps <= s and e <= pe for ps, pe, _ in parents)


def test_driver_turns_record_every_program_span_nested(tmp_path):
    pool = make_pool(TINY, max_instances=1, max_slots=2, max_seq=64,
                     block_size=8)
    sched = PoolScheduler(pool, ServingConfig(batch_sizes=(2,),
                                              concurrency_levels=(1,)),
                          slo_ms={TINY.name: 5000.0}, seed=0)
    sched.control()
    pool.warmup(seed=0)

    def serve():
        with ServingDriver(pool, idle_sleep_s=0.001, on_tick=sched.tick,
                           tick_interval_s=0.0) as driver:
            for i in range(3):
                driver.submit(TINY.name, _prompt(6 + 5 * i, i),
                              slo_ms=5000.0, max_new_tokens=4)
            driver.drain(timeout_s=30.0)
            gc.collect()
            time.sleep(0.02)       # a few idle turns

    spans = _traced(tmp_path, serve)
    by_name = defaultdict(list)
    for thread, evs in spans.items():
        for ev in evs:
            by_name[ev[2]].append((thread, ev))
    assert set(by_name) == set(PARENT)
    for name, parent in PARENT.items():
        if parent is None:
            continue
        for thread, ev in by_name[name]:
            assert _inside(ev, [p for p in spans[thread] if p[2] == parent]), \
                f"{name} at {ev[:2]} lies outside every {parent}"


def test_speculative_steps_record_the_engine_spans(tmp_path):
    eng = make_cont_engine(TINY, max_slots=2, max_seq=64,
                           block_size=8, spec_k=2)
    prompts = [np.tile(_prompt(4, i), 3) for i in range(2)]
    rows = []
    real = eng._verify

    def verify(params, cache, batch):
        rows.append(len(eng.decoding_slots))
        return real(params, cache, batch)

    eng._verify = verify
    spans = _traced(tmp_path, lambda: eng.run(prompts, max_new_tokens=8))
    names = {ev[2] for evs in spans.values() for ev in evs}
    assert set(ENGINE_SPANS) <= names
    assert eng.n_spec_steps > 0 and rows
    assert eng.n_decode_rows == sum(rows)


@pytest.mark.parametrize("token_budget", [None, 8],
                         ids=["uncapped", "budget8"])
def test_prefill_padding_and_decode_rows_counted(token_budget):
    eng = make_cont_engine(TINY, max_slots=2, max_seq=64, block_size=8,
                           token_budget=token_budget)
    # both in the 16-token bucket: 11 + 4 rows of padding, prefilled once
    # (in two 8-row pieces each under the 8-token budget)
    eng.run([_prompt(5, 1), _prompt(12, 2)], max_new_tokens=3)
    st = eng.stats()
    assert st["n_prefill_pad_rows"] == 11 + 4
    assert st["n_prefill_chunk_tokens"] == 16 + 16
    # every decode call carries the sequences then decoding: 3 tokens each
    assert st["n_decode_rows"] == 2 * 3
    assert st["n_compiled_steps"] >= 1


def test_resumed_prefill_counts_its_padding_again():
    eng = make_cont_engine(TINY, max_slots=1, max_seq=64,
                           block_size=8)
    eng.submit(_prompt(5, 3), 6)
    while not eng.decoding_slots:
        eng.step()
    eng.step()
    eng.preempt(eng.decoding_slots[0])
    while eng.waiting or eng.active_slots:
        eng.step()
    # the recompute resume prefills the padded prompt (11 padding rows)
    # and the emitted tokens again
    assert eng.n_prefill_pad_rows == 2 * 11


def test_compiled_steps_count_steps_not_shapes():
    eng = make_cont_engine(TINY, max_slots=2, max_seq=64,
                           block_size=8)
    eng.submit(_prompt(5, 4), 4)
    eng.step()                # a new prefill piece and the first decode
    assert eng.last_step_compiled and eng.n_compiled_steps == 1
    eng.step()
    assert not eng.last_step_compiled and eng.n_compiled_steps == 1


def test_pool_stats_sum_the_engine_counters():
    pool = make_pool(TINY, max_instances=2, max_slots=2, max_seq=64,
                     block_size=8)
    pool.scale_to(TINY.name, 2)
    for i in range(4):
        pool.submit(TINY.name, _prompt(5 + 3 * i, i), slo_ms=5000.0,
                    max_new_tokens=3)
    pool.run_until_drained()
    st = pool.stats()
    live = pool.live(TINY.name)
    assert len(live) == 2
    for k in ENGINE_COUNTERS:
        assert st[k] == sum(i.engine.stats()[k] for i in live)
    assert st["n_decode_rows"] == 4 * 3


def test_gc_spans_registered_only_while_the_driver_runs():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64)
    driver = ServingDriver(pool)
    n = len(gc.callbacks)
    driver.start()
    try:
        assert len(gc.callbacks) == n + 1
    finally:
        driver.stop()
    assert len(gc.callbacks) == n
    driver.stop()
    assert len(gc.callbacks) == n


def test_waiting_caller_enters_before_the_next_step():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    step = pool.step

    def slow_step():
        time.sleep(0.01)
        return step()

    pool.step = slow_step
    driver = ServingDriver(pool).start()
    try:
        driver.submit(TINY.name, _prompt(6, 5), slo_ms=5000.0,
                      max_new_tokens=60)
        waited = []
        for _ in range(10):
            time.sleep(0.005)
            before = driver.n_loop_steps
            with driver.locked() as p:
                assert p is pool
                waited.append(driver.n_loop_steps - before)
        # the loop was stepping back to back, yet each caller got in
        # after at most the step already running when it asked
        assert max(waited) <= 1
    finally:
        driver.stop()


def test_a_stream_of_callers_does_not_starve_the_loop():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    driver = ServingDriver(pool).start()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            driver.stats()

    callers = [threading.Thread(target=hammer) for _ in range(4)]
    try:
        rid = driver.submit(TINY.name, _prompt(6, 6), slo_ms=5000.0,
                            max_new_tokens=20)
        for t in callers:
            t.start()
        deadline = time.perf_counter() + 30.0
        done = []
        while not done and time.perf_counter() < deadline:
            time.sleep(0.01)
            with driver.locked() as p:
                done = p.results(TINY.name)
    finally:
        stop.set()
        for t in callers:
            t.join(10.0)
        driver.stop()
    assert not any(t.is_alive() for t in callers)
    assert [r.request_id for r in done] == [rid]
    assert len(done[0].tokens) == 20
