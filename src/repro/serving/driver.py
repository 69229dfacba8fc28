"""Background serving driver (docs/RUNTIME.md §11): the non-blocking
iteration loop that turns the pull-mode pool (caller drives ``step()``)
into a push-mode serving core.

``ServingDriver`` owns a daemon thread that steps a
:class:`~repro.serving.runtime.ModelInstancePool` continuously whenever
work is pending, sleeping briefly when idle. Every pool access — the
loop's ``step()``, front-end ``submit``/``cancel``, the scheduler's
control epoch — serialises on one re-entrant lock, so the pool itself
stays single-threaded (engines hold jit caches and numpy state that are
not thread-safe) while callers never block on a drain.

The optional ``on_tick`` hook is the scheduler's new decision cadence:
instead of deciding "between drains", the driver invokes it on a
wall-clock interval against live queue state (BCEdge's Eq. 1 slot,
docs/RUNTIME.md §2) while holding the pool lock.

Lifecycle events reach front-ends through the pool's per-request
listeners (``pool.add_listener``), which fire inside ``step()`` on THIS
thread — listeners must be cheap and non-reentrant (bridge to your own
loop, e.g. ``asyncio.call_soon_threadsafe``).

A caller that takes the lock through the facade (``locked()`` and every
method built on it) is counted as waiting, and the loop lets the callers
already waiting in before its next step: a lock released and re-taken at
once would otherwise go straight back to the loop, and a ``cancel``
would wait for the request to finish. Callers that arrive while it waits
go at the next turn, so a stream of them cannot starve the loop either.
With nobody waiting the loop steps back to back.

The loop's turns carry profiler spans (``tracing.py``):
``repro.driver.turn`` around each turn, holding ``lock_wait`` (letting
callers in and taking the lock), ``on_tick``, the pool's own spans and
``idle`` (the sleep when there was nothing to step); garbage collections
show as ``repro.python.gc`` while the driver runs.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Callable, Iterator, Optional

from repro.serving.runtime import ModelInstancePool, PoolResult
from repro.serving.tracing import GcSpans, span


class ServingDriver:
    """Steps ``pool`` on a background thread; thread-safe facade for
    submit/cancel/stats. Usable as a context manager::

        with ServingDriver(pool, on_tick=sched_tick) as driver:
            rid = driver.submit("qwen", prompt, slo_ms=500.0)
            ...
    """

    def __init__(self, pool: ModelInstancePool,
                 idle_sleep_s: float = 0.002,
                 on_tick: Optional[Callable] = None,
                 tick_interval_s: float = 0.25):
        self.pool = pool
        self.idle_sleep_s = idle_sleep_s
        #: ``on_tick(pool)`` invoked under the pool lock at most once per
        #: ``tick_interval_s`` — the scheduler's wall-clock control epoch
        self.on_tick = on_tick
        self.tick_interval_s = tick_interval_s
        self.lock = threading.RLock()
        #: callers of ``locked()`` that have asked for the lock, and
        #: those that have released it again, guarded by ``_handoff``
        #: (the loop waits on it for the callers ahead of its next step)
        self._arrived = 0
        self._left = 0
        self._handoff = threading.Condition()
        self._gc_spans = GcSpans()
        self.n_loop_steps = 0
        self.n_ticks = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._next_tick = 0.0
        #: a loop-thread exception is re-raised to the NEXT caller of
        #: stop() instead of dying silently on a daemon thread
        self._error: Optional[BaseException] = None

    # ---- lifecycle -------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "ServingDriver":
        if self.running:
            raise RuntimeError("driver already running")
        self._stop.clear()
        self._error = None
        self._next_tick = time.perf_counter()
        self._thread = threading.Thread(
            target=self._loop, name="serving-driver", daemon=True)
        gc.callbacks.append(self._gc_spans)
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the loop (idempotent). Re-raises a loop-thread crash so
        test/benchmark harnesses cannot pass on a dead driver."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():  # pragma: no cover - hang guard
                raise RuntimeError("serving driver failed to stop")
            self._thread = None
        if self._gc_spans in gc.callbacks:
            gc.callbacks.remove(self._gc_spans)
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def __enter__(self) -> "ServingDriver":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- thread-safe pool facade ----------------------------------------
    @contextlib.contextmanager
    def locked(self) -> Iterator[ModelInstancePool]:
        """Hold the pool lock, and the pool, for the body; the loop lets
        this caller in before its next step."""
        with self._handoff:
            self._arrived += 1
        try:
            with self.lock:
                yield self.pool
        finally:
            with self._handoff:
                self._left += 1
                self._handoff.notify_all()

    def submit(self, *args, **kwargs) -> int:
        with self.locked() as pool:
            return pool.submit(*args, **kwargs)

    def cancel(self, request_id: int) -> Optional[PoolResult]:
        with self.locked() as pool:
            return pool.cancel(request_id)

    def add_listener(self, request_id: int, fn: Callable) -> None:
        with self.locked() as pool:
            pool.add_listener(request_id, fn)

    def remove_listener(self, request_id: int) -> None:
        with self.locked() as pool:
            pool.remove_listener(request_id)

    def admission_headroom(self, *args, **kwargs):
        with self.locked() as pool:
            return pool.admission_headroom(*args, **kwargs)

    def stats(self):
        with self.locked() as pool:
            return pool.stats()

    def report(self):
        with self.locked() as pool:
            return pool.report()

    def drain(self, timeout_s: float = 60.0) -> None:
        """Block the CALLING thread until the pool has no progressable
        work (the background loop keeps stepping; this only polls)."""
        deadline = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline:
            with self.locked() as pool:
                if not (pool._work_pending() and pool._can_progress()):
                    return
            time.sleep(self.idle_sleep_s)
        raise TimeoutError(f"pool not drained after {timeout_s}s")

    # ---- loop ------------------------------------------------------------
    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                with span("repro.driver.turn"):
                    if not self._turn():
                        # idle (or unprogressable until a tick scales
                        # up): yield the lock so submits/cancels never
                        # starve
                        with span("repro.driver.idle"):
                            time.sleep(self.idle_sleep_s)
        except BaseException as e:  # noqa: BLE001 - surfaced in stop()
            self._error = e

    def _turn(self) -> bool:
        """One turn under the lock: the tick when due, then one pool
        step if there is work it can move. Returns whether it stepped."""
        with span("repro.driver.lock_wait"):
            if self._left != self._arrived:
                # as many callers must leave as had asked before now;
                # later ones wait for the next turn, so they cannot
                # starve the loop in turn
                with self._handoff:
                    ahead = self._arrived
                    self._handoff.wait_for(lambda: self._left >= ahead)
            self.lock.acquire()
        try:
            now = time.perf_counter()
            if self.on_tick is not None and now >= self._next_tick:
                self._next_tick = now + self.tick_interval_s
                with span("repro.driver.on_tick"):
                    self.on_tick(self.pool)
                self.n_ticks += 1
            if not (self.pool._work_pending() and self.pool._can_progress()):
                return False
            self.pool.step()
            self.n_loop_steps += 1
            return True
        finally:
            self.lock.release()
