"""Push-mode serving: request lifecycle, cancellation at every phase,
wall-clock timing, the background driver, and the HTTP front-end
(docs/RUNTIME.md §11).

Four layers, mirroring the stack:

* ``RequestLifecycle`` — the explicit state machine (legal edges only,
  timestamps and token counters stamped on transition);
* engine ``cancel()`` — queued / mid-prefill / mid-decode / preempted,
  with synchronous block free and token-identical survivors;
* pool ``cancel()`` + events + TTFT/TPOT stats — including the
  queue-head starvation regression (a cancelled-while-QUEUED request
  must leave the EDF queue immediately, not rot at the head);
* ``ServingDriver`` + ``ServingFrontend`` — background stepping, event
  streaming over HTTP, disconnect-cancel, and 429 backpressure.
"""
import asyncio
import json
import threading
import time

import numpy as np
import pytest

from conftest import TINY, make_cont_engine, make_pool
from repro.serving import request as lc
from repro.serving.driver import ServingDriver
from repro.serving.request import RequestLifecycle


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, TINY.vocab_size, n).astype(np.int32)


# ------------------------------------------------------------ lifecycle
def test_lifecycle_legal_path_and_stamps():
    events = []
    l = RequestLifecycle(7, enqueue_s=10.0,
                         on_event=lambda l, s: events.append(s))
    assert l.state == lc.QUEUED and not l.terminal
    l.to(lc.PREFILL, now_s=10.5)
    assert l.admit_s == 10.5
    l.token(42, 0, now_s=10.8)
    l.to(lc.DECODE, now_s=10.9)
    l.token(43, 1, now_s=11.0)
    l.to(lc.FINISHED, now_s=11.1)
    assert l.terminal and l.finish_s == 11.1
    assert l.first_token_s == 10.8 and l.n_tokens == 2
    assert l.ttft_s() == pytest.approx(0.8)
    assert l.tpot_s() == pytest.approx(0.3)  # (finish - first) / (n - 1)
    assert events == [lc.PREFILL, lc.DECODE, lc.FINISHED]


def test_lifecycle_illegal_edges_raise():
    l = RequestLifecycle(1, enqueue_s=0.0)
    with pytest.raises(ValueError):
        l.to(lc.FINISHED, now_s=1.0)  # QUEUED -/-> FINISHED
    l.to(lc.PREFILL, now_s=1.0)
    with pytest.raises(ValueError):
        l.to(lc.QUEUED, now_s=2.0)  # PREFILL -/-> QUEUED
    l.to(lc.DECODE, now_s=2.0)
    l.to(lc.QUEUED, now_s=3.0)  # preemption edge
    assert l.n_preempted == 1
    l.to(lc.DECODE, now_s=4.0)  # inline re-admission
    l.to(lc.CANCELLED, now_s=5.0)
    with pytest.raises(ValueError):
        l.to(lc.DECODE, now_s=6.0)  # terminal is terminal


def test_lifecycle_cancellable_from_every_nonterminal():
    for path in ([], [lc.PREFILL], [lc.PREFILL, lc.DECODE],
                 [lc.DECODE, lc.QUEUED]):
        l = RequestLifecycle(1, enqueue_s=0.0)
        t = 1.0
        for s in path:
            l.to(s, now_s=t)
            t += 1.0
        l.to(lc.CANCELLED, now_s=t)
        assert l.terminal and l.state == lc.CANCELLED


# ------------------------------------------------------- engine cancel
def _drain(eng, results):
    guard = 600
    while (eng.waiting or eng.active_slots) and guard:
        for r in eng.step():
            results[r.request_id] = r
        guard -= 1
    assert guard, "engine failed to drain"


def _assert_no_leak(eng):
    assert eng.allocator.n_live == 0
    assert eng.allocator.n_reserved == 0


def test_engine_cancel_queued_and_survivor_identity():
    eng = make_cont_engine(TINY, max_slots=1, max_seq=64,
                           block_size=8)
    p1, p2 = _prompt(8, 1), _prompt(8, 2)
    oracle = make_cont_engine(TINY, max_slots=1, max_seq=64,
                              share_from=eng).run(
        [p1], max_new_tokens=5)[0].tokens
    r1 = eng.submit(p1, max_new_tokens=5)
    r2 = eng.submit(p2, max_new_tokens=5)  # waits: single slot
    for _ in range(2):
        eng.step()
    res = eng.cancel(r2)
    assert res is not None and res.cancelled and res.request_id == r2
    assert not eng.waiting, "cancelled request still queued"
    results = {}
    _drain(eng, results)
    np.testing.assert_array_equal(results[r1].tokens, oracle)
    assert eng.stats()["n_cancelled"] == 1
    _assert_no_leak(eng)


def test_engine_cancel_mid_decode_frees_blocks_synchronously():
    eng = make_cont_engine(TINY, max_slots=2, max_seq=64,
                           block_size=8)
    p1, p2 = _prompt(8, 3), _prompt(8, 4)
    oracle = make_cont_engine(TINY, max_slots=1, max_seq=64,
                              share_from=eng).run(
        [p1], max_new_tokens=6)[0].tokens
    r1 = eng.submit(p1, max_new_tokens=6)
    r2 = eng.submit(p2, max_new_tokens=20)
    for _ in range(3):
        eng.step()
    live_before = eng.allocator.n_live
    res = eng.cancel(r2)
    assert res.cancelled and 0 < len(res.tokens) < 20
    assert eng.allocator.n_live < live_before, \
        "blocks not freed synchronously on cancel"
    results = {}
    _drain(eng, results)
    np.testing.assert_array_equal(results[r1].tokens, oracle)
    _assert_no_leak(eng)


def test_engine_cancel_mid_prefill_and_preempted():
    # token budget forces multi-chunk prefill AND enables preemption
    eng = make_cont_engine(TINY, max_slots=2, max_seq=64,
                           block_size=8,
                           token_budget=8)
    long_p, short_p = _prompt(30, 5), _prompt(6, 6)
    oracle = make_cont_engine(TINY, max_slots=1, max_seq=64,
                              share_from=eng).run(
        [short_p], max_new_tokens=5)[0].tokens
    rid_long = eng.submit(long_p, max_new_tokens=5)
    eng.step()  # first prefill chunk lands, slot is mid-prefill
    res = eng.cancel(rid_long)
    assert res is not None and res.cancelled
    _assert_no_leak(eng)

    # preempted phase: get one decoding, preempt it, cancel it
    rid_a = eng.submit(long_p, max_new_tokens=5)
    rid_b = eng.submit(short_p, max_new_tokens=5)
    guard = 100
    while rid_a not in [eng.slots[i].request_id
                        for i in eng.decoding_slots] and guard:
        eng.step()
        guard -= 1
    assert guard, "request never reached decode"
    slot = next(i for i in eng.decoding_slots
                if eng.slots[i].request_id == rid_a)
    eng.preempt(slot)
    res = eng.cancel(rid_a)  # cancelled while preempted-awaiting-resume
    assert res is not None and res.cancelled
    results = {}
    _drain(eng, results)
    np.testing.assert_array_equal(results[rid_b].tokens, oracle)
    assert eng.stats()["n_cancelled"] == 2
    _assert_no_leak(eng)


def test_engine_cancel_unknown_or_finished_is_noop():
    eng = make_cont_engine(TINY, max_slots=1, max_seq=64)
    assert eng.cancel(999) is None
    rid = eng.submit(_prompt(6, 7), max_new_tokens=2)
    results = {}
    _drain(eng, results)
    assert rid in results
    assert eng.cancel(rid) is None  # already finished
    assert eng.stats()["n_cancelled"] == 0


# --------------------------------------------------------- pool cancel
def test_pool_cancel_dequeues_immediately_no_head_starvation():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64,
                     block_size=8)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    # r0 occupies the only slot for a while
    r0 = pool.submit(TINY.name, _prompt(6, 8), slo_ms=5000.0,
                     max_new_tokens=24)
    pool.step()
    # r1 goes to the EDF queue HEAD (tightest deadline), r2 behind it
    r1 = pool.submit(TINY.name, _prompt(6, 9), slo_ms=10.0,
                     max_new_tokens=4)
    r2 = pool.submit(TINY.name, _prompt(6, 10), slo_ms=8000.0,
                     max_new_tokens=4)
    assert len(pool.queues[TINY.name]) == 2
    res = pool.cancel(r1)
    assert res is not None and res.cancelled
    # the regression: the cancelled head must leave the queue NOW —
    # not linger as a tombstone that starves r2 behind it
    assert len(pool.queues[TINY.name]) == 1
    pool.run_until_drained()
    by_id = {r.request_id: r for r in pool.results(TINY.name)}
    assert not by_id[r0].cancelled and not by_id[r2].cancelled
    assert len(by_id[r2].tokens) == 4
    assert pool.stats()["n_cancelled"] == 1
    rep = pool.report()[TINY.name]
    assert rep["cancelled"] == 1
    # cancelled requests are excluded from attainment accounting
    assert rep["served"] == 2


def test_pool_cancel_running_and_unknown():
    pool = make_pool(TINY, max_instances=1, max_slots=2, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    rid = pool.submit(TINY.name, _prompt(6, 11), slo_ms=5000.0,
                      max_new_tokens=24)
    for _ in range(3):
        pool.step()
    res = pool.cancel(rid)
    assert res is not None and res.cancelled and len(res.tokens) < 24
    assert pool.cancel(rid) is None  # second cancel: no-op
    assert pool.cancel(12345) is None
    pool.run_until_drained()


def test_pool_events_and_wallclock_stats():
    pool = make_pool(TINY, max_instances=1, max_slots=2, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    events = []
    rid = pool.submit(TINY.name, _prompt(6, 12), slo_ms=5000.0,
                      max_new_tokens=4)
    pool.add_listener(rid, events.append)
    pool.run_until_drained()
    kinds = [e["event"] for e in events]
    assert kinds.count("token") == 4
    assert kinds[-1] == "finished"
    assert kinds.index("prefill" if "prefill" in kinds else "decode") \
        < kinds.index("token")
    tok_events = [e for e in events if e["event"] == "token"]
    assert [e["index"] for e in tok_events] == [0, 1, 2, 3]
    res = pool.results(TINY.name)[-1]
    assert res.first_token_s > 0 and res.ttft_ms >= 0
    assert res.tpot_ms >= 0
    st = pool.stats()
    assert st["ttft_ms_p99"] > 0 and st["tpot_ms_p50"] >= 0
    req_lc = events[-1]  # finished event carries the terminal payload
    assert req_lc["request_id"] == rid


def test_pool_admission_headroom_fields():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    head = pool.admission_headroom(TINY.name, 8, 4)
    assert head["admissible_now"] and head["queue_depth"] == 0
    # clog the slot + queue
    pool.submit(TINY.name, _prompt(6, 13), slo_ms=5000.0,
                max_new_tokens=24)
    pool.step()
    for s in range(4):
        pool.submit(TINY.name, _prompt(6, 20 + s), slo_ms=5000.0,
                    max_new_tokens=8)
    head = pool.admission_headroom(TINY.name, 8, 4)
    assert not head["admissible_now"] and head["queue_depth"] == 4
    assert head["retry_after_s"] > 0 and head["backlog_tokens"] > 0
    pool.run_until_drained()


# -------------------------------------------------------------- driver
def test_driver_background_submit_and_events():
    pool = make_pool(TINY, max_instances=1, max_slots=2, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    done = threading.Event()
    events = []

    def listener(ev):
        events.append(ev)
        if ev["event"] in ("finished", "cancelled", "rejected"):
            done.set()

    with ServingDriver(pool, idle_sleep_s=0.001) as driver:
        assert driver.running
        rid = driver.submit(TINY.name, _prompt(6, 14), slo_ms=5000.0,
                            max_new_tokens=4)
        driver.add_listener(rid, listener)
        assert done.wait(timeout=30.0), "no terminal event from driver"
        driver.drain(timeout_s=30.0)
    assert not driver.running
    assert [e["event"] for e in events][-1] == "finished"
    assert driver.n_loop_steps > 0


def test_driver_cancel_and_stop_idempotent():
    pool = make_pool(TINY, max_instances=1, max_slots=1, max_seq=64)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    driver = ServingDriver(pool).start()
    try:
        rid = driver.submit(TINY.name, _prompt(6, 15), slo_ms=5000.0,
                            max_new_tokens=48)
        deadline = time.perf_counter() + 30.0
        res = None
        while res is None and time.perf_counter() < deadline:
            time.sleep(0.01)
            res = driver.cancel(rid)
        assert res is not None and res.cancelled
    finally:
        driver.stop()
        driver.stop()  # idempotent
    assert pool.stats()["n_cancelled"] == 1


# ---------------------------------------------------------------- http
def _http_stack(backpressure=True, max_queue_depth=2, max_slots=2):
    pool = make_pool(TINY, max_instances=1, max_slots=max_slots,
                     max_seq=64, block_size=8)
    pool.scale_to(TINY.name, 1)
    pool.warmup(seed=0)
    driver = ServingDriver(pool)
    from repro.launch.server import ServingFrontend
    fe = ServingFrontend(driver, port=0, backpressure=backpressure,
                         max_queue_depth=max_queue_depth)
    return pool, driver, fe


def test_http_stream_end_to_end():
    from repro.serving.workload import http_generate

    async def run():
        pool, driver, fe = _http_stack()
        driver.start()
        await fe.start()
        try:
            out = await http_generate("127.0.0.1", fe.port, TINY.name,
                                      _prompt(8, 16), 5, 5000.0)
        finally:
            await fe.stop()
            driver.stop()
        return pool, out

    pool, out = asyncio.run(run())
    assert out.outcome == "finished" and out.n_tokens == 5
    assert out.ttft_s >= 0 and out.tpot_s >= 0
    assert pool.stats()["n_cancelled"] == 0


def test_http_disconnect_cancels_and_frees():
    from repro.serving.workload import _read_chunked_events

    async def run():
        pool, driver, fe = _http_stack()
        driver.start()
        await fe.start()
        try:
            # raw client: read up to the FIRST token event, then hang up
            # mid-stream — deterministic regardless of decode speed
            body = json.dumps({"model": TINY.name,
                               "prompt": _prompt(8, 17).tolist(),
                               "max_new_tokens": 48,
                               "slo_ms": 5000.0}).encode()
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", fe.port)
            writer.write((f"POST /v1/generate HTTP/1.1\r\n"
                          f"Content-Length: {len(body)}\r\n\r\n"
                          ).encode() + body)
            await writer.drain()
            status = await reader.readline()
            assert b"200" in status, status
            while await reader.readline() not in (b"\r\n", b"\n", b""):
                pass
            async for ev in _read_chunked_events(reader):
                if ev.get("event") == "token":
                    break
            writer.close()
            deadline = time.perf_counter() + 30.0
            while pool.stats()["n_cancelled"] < 1 \
                    and time.perf_counter() < deadline:
                await asyncio.sleep(0.01)
            await asyncio.get_running_loop().run_in_executor(
                None, driver.drain, 30.0)
        finally:
            await fe.stop()
            driver.stop()
        return pool, fe

    pool, fe = asyncio.run(run())
    assert fe.n_disconnects == 1
    assert pool.stats()["n_cancelled"] == 1
    for inst in pool.live():
        assert inst.engine.allocator.n_live == 0
        assert inst.engine.allocator.n_reserved == 0


def test_http_backpressure_429_with_retry_after():
    from repro.serving.workload import http_generate

    async def run():
        pool, driver, fe = _http_stack(max_queue_depth=1, max_slots=1)
        driver.start()
        await fe.start()
        try:
            # pin the only slot first and wait until the pool reports
            # non-admissible — otherwise all 6 checks below race ahead
            # of the driver thread and see a still-empty engine
            driver.submit(TINY.name, _prompt(8, 29), slo_ms=5000.0,
                          max_new_tokens=48)
            deadline = time.perf_counter() + 30.0
            while pool.admission_headroom(TINY.name, 8, 32)[
                    "admissible_now"] and time.perf_counter() < deadline:
                await asyncio.sleep(0.001)
            outs = await asyncio.gather(*(
                http_generate("127.0.0.1", fe.port, TINY.name,
                              _prompt(8, 30 + i), 32, 5000.0)
                for i in range(6)))
        finally:
            await fe.stop()
            driver.stop()
        return fe, outs

    fe, outs = asyncio.run(run())
    throttled = [o for o in outs if o.outcome == "throttled"]
    assert throttled, "no 429 under saturation"
    assert all(o.retry_after_s > 0 for o in throttled)
    assert any(o.outcome == "finished" for o in outs)
    assert fe.n_throttled == len(throttled)


def test_http_bad_requests():
    async def run():
        pool, driver, fe = _http_stack()
        driver.start()
        await fe.start()
        results = []
        try:
            for body in (json.dumps({"model": "nope", "prompt": [1]}),
                         json.dumps({"model": TINY.name, "prompt": []}),
                         "not json"):
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", fe.port)
                data = body.encode()
                writer.write((f"POST /v1/generate HTTP/1.1\r\n"
                              f"Content-Length: {len(data)}\r\n\r\n"
                              ).encode() + data)
                await writer.drain()
                status = await reader.readline()
                results.append(status.decode())
                writer.close()
        finally:
            await fe.stop()
            driver.stop()
        return results

    for status in asyncio.run(run()):
        assert "400" in status, status
