"""The system under test: ``ServingDriver`` (background step loop,
scheduler tick) -> ``PoolScheduler`` + ``ModelInstancePool`` ->
``ContinuousBatchingEngine`` (paged KV, fused chunked prefill) -> the
jitted ``prefill_chunk`` / ``decode_step``, composed as ``serve_http``
composes it, without its HTTP front end.

The front end is left out because it cannot serve sustained load: it
takes the driver's lock for every submit and every stream's end, and the
driver's loop takes that lock again right after each step, so under
continuous load the front end waits for seconds and requests never end
(``PERF.md``, Open questions, first item). The load reaches the pool
through the driver's own ``on_tick`` hook instead (``load.py``), on the
driver's thread, under its lock.

The benchmark builds the stack from the cell's own files, not through
``serve_http`` (which fixes ``max_seq`` at 128): the configuration's
``max_slots`` and ``max_seq`` (or the cell's override), paged KV and one
instance (``m_c = 1``). The scheduler ticks on the deployed cadence,
through a one-action ``ServingConfig`` (``batch_sizes=(max_slots,)``,
``concurrency_levels=(1,)``): its SAC act/update cost stays on the path,
but an untrained policy cannot move the allocation from one run to the
next.

Weights are the benchmark's, not the program's: made on the device from
the seed by the configuration's reference module, in the type the
program serves (which may not lie below the precision the configuration
states), checked against the structure, shapes and dtypes of the tree
the program built, and handed to the engine in its place. With ``spans=True`` the benchmark wraps the
program's calls in profiler spans (``trace_reduce.SPANS``) and records
what each call was given, for the needed-work counts.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from load import CONTROL_S

#: scheduler decisions made at set-up, so that its SAC update (which
#: starts once the replay holds a batch of 32) compiles before the window
SAC_WARM_DECISIONS = 34


def buckets_used(serving: Dict[str, Any], prompt_len: Dict[str, Any]
                 ) -> List[int]:
    """Prompt buckets a mix can reach: the shapes to warm up."""
    lo, hi = int(prompt_len["min"]), int(prompt_len["max"])
    out, prev = [], 0
    for b in serving["prompt_buckets"]:
        if b >= lo and prev < hi:
            out.append(int(b))
        prev = b
    return out


class System:
    def __init__(self, cell, seed: int, ref, sizes: Dict[str, Any],
                 spans: bool = False, log: Callable[[str], None] = print):
        from repro.config import ServingConfig, get_config
        from repro.serving.bcedge import PoolScheduler
        from repro.serving.runtime import ModelInstancePool

        self.cell, self.log = cell, log
        serving = cell.serving
        cfg = dataclasses.replace(get_config(cell.config["registry"]),
                                  **cell.config.get("overrides", {}))
        want = ref.program_config(sizes)
        got = {k: getattr(cfg, k) for k in want}
        if got != want:
            raise ValueError(f"program config {cfg.name} differs from its "
                             f"source: {got} != {want}")
        self.cfg = cfg
        prog_seed = int(seed) % 2 ** 31
        t = time.perf_counter()
        self.pool = ModelInstancePool(
            {cfg.name: cfg}, max_instances=1,
            max_slots=int(serving["max_slots"]),
            max_seq=int(serving["max_seq"]), seed=prog_seed,
            kv_layout="paged", block_size=int(serving["block_size"]))
        mix = cell.traffic
        self.slo_ms = float(mix["ttft_limit_ms"] + mix["tpot_limit_ms"]
                            * mix["output_len"]["max"])
        self.sched = PoolScheduler(
            self.pool, ServingConfig(batch_sizes=(int(serving["max_slots"]),),
                                     concurrency_levels=(1,)),
            slo_ms={cfg.name: self.slo_ms}, seed=prog_seed)
        self.sched.control()                      # spawns the instance
        self.engine = self.pool.live(cfg.name)[0].engine
        log(f"set-up: pool and engine built in "
            f"{time.perf_counter() - t:.2f} s")

        t = time.perf_counter()
        self.weights = self._load_weights(ref, sizes, seed)
        log(f"set-up: {self.dtype} weights made from the seed and loaded "
            f"in {time.perf_counter() - t:.2f} s")

        t = time.perf_counter()
        for _ in range(SAC_WARM_DECISIONS):
            self.sched.control()
        lens = tuple(buckets_used(serving, mix["prompt_len"]))
        self.pool.warmup(prompt_lens=lens, seed=prog_seed)
        log(f"set-up: warmed prompt buckets {lens}, decode "
            f"({serving['max_slots']}, 1) and the scheduler in "
            f"{time.perf_counter() - t:.2f} s; prefill pieces "
            f"{sorted(self.engine.prefill_shapes)}")

        self.calls: List[tuple] = []
        self._first_real: Dict[int, int] = {}
        self.tick: Callable = self.sched.tick
        if spans:
            self._wrap()
        self.driver = None
        self.load = None
        self._next_tick = 0.0

    # ---- weights ---------------------------------------------------------
    def _load_weights(self, ref, sizes, seed) -> Dict[str, Any]:
        eng = self.engine
        shape = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        want = shape(eng.params)
        served = {a.dtype for a in jax.tree.leaves(want)}
        stated = jnp.dtype(self.cell.config["precision"]["stated"])
        dtype = next(iter(served))
        if len(served) != 1 or not jnp.issubdtype(dtype, jnp.floating) \
                or dtype.itemsize < stated.itemsize:
            raise ValueError(f"program serves {sorted(map(str, served))}, "
                             f"below the configuration's stated {stated}")
        self.dtype = dtype
        eng.params = None            # free the program's own weights first
        gc.collect()
        w = ref.init_weights(sizes, seed, dtype)
        tree = ref.program_tree(w, sizes)
        if jax.tree.structure(tree) != jax.tree.structure(want) \
                or jax.tree.leaves(shape(tree)) != jax.tree.leaves(want):
            raise ValueError("the program's parameter layout changed: "
                             "the reference module's program_tree no "
                             "longer matches it")
        eng.params = tree
        return w

    def reload_weights(self, ref, sizes, seed: int) -> None:
        """Serve another seed's weights (``calibrate.py`` reads many
        seeds in one process)."""
        self.weights = None
        self.weights = self._load_weights(ref, sizes, seed)

    # ---- spans and call records ------------------------------------------
    def _span(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kwargs)
        return wrapped

    def _wrap(self) -> None:
        eng, pool = self.engine, self.pool
        step, decode, prefill = eng.step, eng._decode, eng._prefill_chunk

        def engine_step():
            with jax.profiler.TraceAnnotation("engine.step"):
                out = step()
            self.calls.append(("step", time.perf_counter()))
            return out

        def decode_call(params, cache, batch):
            ctx = [int(eng.pos[i]) + 1 for i in eng.decoding_slots]
            self.calls.append(("decode", time.perf_counter(), ctx))
            with jax.profiler.TraceAnnotation("dispatch.decode_step"):
                return decode(params, cache, batch)

        def prefill_call(params, cache, batch):
            toks = np.asarray(batch["tokens"])[0]
            pos = int(np.asarray(batch["pos"])[0])
            key = int(np.asarray(batch["block_tables"])[0, 0])
            if pos == 0:
                nz = np.flatnonzero(toks)
                self._first_real[key] = int(nz[0]) if len(nz) else len(toks)
            first = self._first_real.get(key, pos)
            self.calls.append(("prefill", time.perf_counter(), pos,
                               len(toks), first))
            with jax.profiler.TraceAnnotation("dispatch.prefill_chunk"):
                return prefill(params, cache, batch)

        eng.step = engine_step
        eng._decode = decode_call
        eng._prefill_chunk = prefill_call
        pool.step = self._span("pool.step", pool.step)
        self.tick = self._span("scheduler.tick", self.sched.tick)
        self._hook = self._span("client.hook", self._hook)

    # ---- serving ---------------------------------------------------------
    def start(self) -> None:
        """Start the driver; it serves whatever ``serve`` hands it."""
        from repro.serving.driver import ServingDriver

        self.driver = ServingDriver(self.pool, on_tick=self._on_tick,
                                    tick_interval_s=0.0).start()

    def _hook(self, load, pool) -> None:
        load.hook(pool)

    def _on_tick(self, pool) -> None:
        load = self.load
        if load is not None:
            self._hook(load, pool)
            return
        now = time.perf_counter()
        if now >= self._next_tick:
            self._next_tick = now + CONTROL_S
            self.tick(pool)

    def serve(self, load) -> None:
        """Hand ``load`` to the running driver (``load.wait`` follows)."""
        load.tick = self.tick
        load.start()
        self.load = load

    def stop(self) -> None:
        self.load = None
        if self.driver is not None:
            self.driver.stop()

    def release(self) -> None:
        """Drop the program's state (cache, engine, pool), keeping the
        benchmark's weights for the reference."""
        self.engine = self.pool = self.sched = None
        self.driver = self.load = None
        gc.collect()
