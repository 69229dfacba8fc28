"""Real-engine serving driver (importable entry point for
``python -m repro.launch.serve --engine``; docs/ARCHITECTURE.md §6).

The BCEdge scheduler batching REAL model inference — the registered
architecture at its published widths (or, with ``reduced=True``, the
2-layer smoke reduction the CPU recipes use) running under jit,
wall-clock latencies and all. Requests with token prompts arrive
Poisson; utilities are computed from measured latencies (Eq. 3).

Three serving modes:

* ``round`` — the SAC scheduler picks the batch size per round and the
  ``InferenceEngine`` runs each round to completion (paper §IV-D);
* ``continuous`` — the ``ContinuousBatchingEngine`` decodes a fixed set
  of KV slots one iteration at a time; arrivals are submitted as they
  land and join at iteration boundaries (docs/ARCHITECTURE.md §5);
* ``--models a,b`` multi-model pool serve — N concurrent engine
  instances across heterogeneous models behind the ``ModelInstancePool``
  runtime, with the ``PoolScheduler`` driving the REAL (b, m_c) action
  per model (docs/RUNTIME.md; continuous-only).

Run:  PYTHONPATH=src python -m repro.launch.serve --engine --reduced
      PYTHONPATH=src python -m repro.launch.serve --engine --reduced \
          --exec-mode continuous
      PYTHONPATH=src python -m repro.launch.serve --engine --reduced \
          --models qwen3-0.6b,recurrentgemma-2b --exec-mode continuous
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence

import numpy as np

from repro.config import get_config, get_reduced_config
from repro.config.base import ModelConfig, ServingConfig
from repro.core.interference import NNInterferencePredictor
from repro.core.sac import SACAgent, SACConfig
from repro.core.utility import utility
from repro.serving.bcedge import PoolScheduler
from repro.serving.engine import (SEQ_BUCKETS, ContinuousBatchingEngine,
                                  InferenceEngine, _bucket,
                                  supports_speculation)
from repro.serving.runtime import ModelInstancePool

#: unique-tail length _shared_prefix_prompts appends to every prefix
#: (fixed: left-padding makes prefix sharing length-sensitive)
_PREFIX_TAIL = 8


def _serve_max_seq(shared_prefix_tokens: int, default: int = 128) -> int:
    """Cache length sized to the generated workload: a templated prompt
    (prefix + fixed tail) must fit its bucket AND leave decode room —
    with the historical default for untemplated traffic."""
    if not shared_prefix_tokens:
        return default
    bucket = _bucket(shared_prefix_tokens + _PREFIX_TAIL,
                     buckets=SEQ_BUCKETS)
    return max(default, bucket + 64)


def load_config(arch: str, reduced: bool) -> ModelConfig:
    """The registered config at its published widths, or its smoke
    reduction when ``reduced`` (an explicit choice, as in
    ``repro.launch.train --reduced``)."""
    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    print(f"loading {'reduced' if reduced else 'full'} {cfg.name} "
          f"(d={cfg.d_model}, L={cfg.n_layers})...")
    return cfg


def _report(served: int, violations: int, rounds: int, lat_sum: float,
            dur: float, slo_ms: float, label: str) -> None:
    print(f"[{label}] served {served} requests in {dur:.1f}s "
          f"({served/max(dur,1e-6):.1f} rps) over {rounds} rounds/iters")
    print(f"[{label}] mean latency {lat_sum/max(served,1):.0f}ms, "
          f"violations {violations/max(served,1):.1%} (SLO {slo_ms:.0f}ms)")


def serve_round(arch: str = "qwen3-0.6b", duration_s: float = 20.0,
                rps: float = 12.0, slo_ms: float = 1500.0,
                reduced: bool = False) -> None:
    """Round mode: SAC picks b per round, engine runs it to completion."""
    cfg = load_config(arch, reduced)
    engine = InferenceEngine(cfg, max_seq=128)
    # warm the compile cache
    engine.generate([np.arange(8, dtype=np.int32)], max_new_tokens=2)

    scfg = ServingConfig(batch_sizes=(1, 2, 4, 8),
                         concurrency_levels=(1,))
    agent = SACAgent(4, scfg.n_actions,
                     SACConfig(batch_size=32, lr=1e-3), seed=0)
    rng = np.random.default_rng(0)

    queue = []
    t0 = time.perf_counter()
    next_arrival = rng.exponential(1.0 / rps)
    served = violations = rounds = 0
    lat_sum = 0.0
    state = np.zeros(4, np.float32)
    while time.perf_counter() - t0 < duration_s:
        now = time.perf_counter() - t0
        while next_arrival <= now:
            queue.append((next_arrival,
                          rng.integers(1, cfg.vocab_size,
                                       rng.integers(4, 24)).astype(np.int32)))
            next_arrival += rng.exponential(1.0 / rps)
        if not queue:
            time.sleep(0.002)
            continue
        oldest_age = now - queue[0][0]
        state = np.array([np.log1p(len(queue)), oldest_age,
                          np.log1p(served), 1.0], np.float32)
        a = agent.act(state)
        b, _ = scfg.action_to_pair(a)
        batch = queue[:b]
        queue = queue[b:]
        res = engine.generate([p for _, p in batch], max_new_tokens=4)
        done_t = time.perf_counter() - t0
        lats = [(done_t - arr) * 1000.0 for arr, _ in batch]
        viol = sum(1 for l in lats if l > slo_ms)
        served += len(batch)
        violations += viol
        lat_sum += sum(lats)
        rounds += 1
        u = utility(len(batch) / max(res.total_ms / 1000, 1e-3),
                    np.mean(lats) / 1000.0,
                    slo_ms / 1000.0 * len(batch), 1) - 2.0 * viol / len(batch)
        s2 = np.array([np.log1p(len(queue)), 0.0, np.log1p(served), 1.0],
                      np.float32)
        agent.observe(state, a, u, s2, False)
        agent.update()
    _report(served, violations, rounds, lat_sum,
            time.perf_counter() - t0, slo_ms, "round")


def _shared_prefix_prompts(rng, vocab: int, shared_prefix_tokens: int,
                           population: int = 4):
    """Prompt factory for templated workloads: draws one of
    ``population`` fixed shared prefixes plus a random unique tail of a
    FIXED length (left-padding makes sharing length-sensitive — see
    docs/ARCHITECTURE.md §5)."""
    prefixes = [rng.integers(1, vocab, shared_prefix_tokens).astype(
        np.int32) for _ in range(population)]

    def draw():
        tail = rng.integers(1, vocab, _PREFIX_TAIL).astype(np.int32)
        return np.concatenate(
            [prefixes[int(rng.integers(population))], tail])
    return draw


def serve_continuous(arch: str = "qwen3-0.6b", duration_s: float = 20.0,
                     rps: float = 12.0, slo_ms: float = 1500.0,
                     max_slots: int = 4,
                     kv_block_budget: Optional[int] = None,
                     token_budget: Optional[int] = None,
                     prefix_cache: bool = False,
                     shared_prefix_tokens: int = 0,
                     spec_k: int = 0, reduced: bool = False) -> None:
    """Continuous mode: arrivals are submitted into the slot engine as
    they land and join the running batch at iteration boundaries.
    ``kv_block_budget`` caps the engine's block pool (default: one
    full-length row of blocks per slot). ``token_budget``
    caps per-iteration prefill+decode tokens (chunked prefill,
    docs/ARCHITECTURE.md §5). ``prefix_cache`` shares full immutable
    prompt blocks across same-prefix sequences;
    ``shared_prefix_tokens`` makes the generated workload templated so
    the cache has something to hit. ``spec_k`` enables self-speculative
    decoding: up to k n-gram drafts per slot verified in one forward
    (docs/ARCHITECTURE.md §speculation); models whose cache cannot
    rewind serve with it off."""
    cfg = load_config(arch, reduced)
    if spec_k > 0 and not supports_speculation(cfg):
        print(f"{cfg.name}: cache not rewindable "
              f"(recurrent/windowed layers); serving with spec_k=0")
        spec_k = 0
    print(f"[continuous] {max_slots} slots, "
          f"token budget {token_budget or 'uncapped'}, "
          f"prefix cache {'on' if prefix_cache else 'off'}, "
          f"spec_k {spec_k or 'off'}...")
    engine = ContinuousBatchingEngine(cfg, max_slots=max_slots,
                                      max_seq=_serve_max_seq(
                                          shared_prefix_tokens),
                                      kv_blocks=kv_block_budget,
                                      token_budget=token_budget,
                                      prefix_cache=prefix_cache,
                                      spec_k=spec_k)
    rng = np.random.default_rng(0)
    draw_prompt = _shared_prefix_prompts(
        rng, cfg.vocab_size, shared_prefix_tokens) \
        if shared_prefix_tokens else None

    t0 = time.perf_counter()
    next_arrival = rng.exponential(1.0 / rps)
    submit_t = {}
    served = violations = 0
    lat_sum = 0.0
    while time.perf_counter() - t0 < duration_s:
        now = time.perf_counter() - t0
        while next_arrival <= now:
            prompt = draw_prompt() if draw_prompt is not None else \
                rng.integers(1, cfg.vocab_size,
                             rng.integers(4, 24)).astype(np.int32)
            rid = engine.submit(prompt, max_new_tokens=4)
            submit_t[rid] = next_arrival
            next_arrival += rng.exponential(1.0 / rps)
        if not engine.active_slots and not engine.waiting:
            time.sleep(0.002)
            continue
        for r in engine.step():
            done_t = time.perf_counter() - t0
            lat = (done_t - submit_t.pop(r.request_id, done_t)) * 1000.0
            served += 1
            lat_sum += lat
            violations += int(lat > slo_ms)
    _report(served, violations, engine.n_iters, lat_sum,
            time.perf_counter() - t0, slo_ms, "continuous")
    print(f"[continuous] engine stats: {engine.stats()}")


def serve_pool(models: Sequence[str] = ("qwen3-0.6b", "recurrentgemma-2b"),
               duration_s: float = 20.0, rps: float = 12.0,
               slo_ms: float = 2000.0, max_instances: int = 4,
               max_slots: int = 4, max_new_tokens: int = 4,
               control_ms: float = 500.0, seed: int = 0,
               kv_block_budget: Optional[int] = None,
               token_budget: Optional[int] = None,
               preemption: bool = False,
               kv_host_blocks: int = 0,
               preempt_mode: str = "auto",
               prefix_cache: bool = False,
               shared_prefix_tokens: int = 0,
               spec_k: int = 0, reduced: bool = False
               ) -> Dict[str, Dict[str, float]]:
    """Multi-model pool serve (docs/RUNTIME.md): Poisson arrivals per model
    are routed by deadline into a ``ModelInstancePool`` of live engine
    instances while the ``PoolScheduler`` re-decides (b, m_c) per model once
    per Eq.-1 slot (clamped to [control_ms, 2000] ms). ``kv_block_budget``
    caps the blocks every instance's pool shares (docs/RUNTIME.md §7).
    ``token_budget`` adds the per-iteration token cap as a third scheduler
    axis and ``preemption`` enables SLO-aware eviction (docs/RUNTIME.md §8).
    ``kv_host_blocks`` gives every instance a host-memory KV tier so
    eviction can swap instead of recompute; ``preempt_mode`` picks
    recompute/swap/auto (costed, per victim). ``prefix_cache`` shares full
    immutable prompt blocks across same-prefix sequences on pageable models,
    with router prefix affinity (docs/RUNTIME.md §7); pair it with
    ``shared_prefix_tokens`` so the generated workload is templated.
    ``spec_k`` caps self-speculative decoding and adds the proposal depth as
    the FOURTH scheduler axis (k ∈ {0, k/2, k}; rewind-capable models only,
    docs/ARCHITECTURE.md §speculation). Returns the pool's per-model
    report."""
    cfgs = {m: load_config(m, reduced) for m in models}
    pool = ModelInstancePool(cfgs, max_instances=max_instances,
                             max_slots=max_slots,
                             max_seq=_serve_max_seq(shared_prefix_tokens),
                             seed=seed,
                             strict_admission=True,
                             predictor=NNInterferencePredictor(seed=seed),
                             kv_block_budget=kv_block_budget,
                             preemption=preemption,
                             kv_host_blocks=max(0, kv_host_blocks),
                             preempt_mode=preempt_mode,
                             prefix_cache=prefix_cache,
                             spec_k=spec_k)
    per_model_mc = max(1, max_instances // max(1, len(cfgs)))
    scfg = ServingConfig(
        batch_sizes=tuple(b for b in (1, 2, 4, 8) if b <= max_slots),
        concurrency_levels=tuple(range(1, per_model_mc + 1)),
        token_budgets=(0,) if not token_budget
        else (0, 2 * token_budget, token_budget),
        spec_depths=(0,) if not spec_k
        else tuple(sorted({0, max(1, spec_k // 2), spec_k})))
    sched = PoolScheduler(pool, scfg,
                          slo_ms={m: slo_ms for m in cfgs},
                          decode_steps_mean=max_new_tokens, seed=seed)
    sched.control()  # initial allocation (spawns the first instances)

    # warm the jit caches (prefill buckets + decode shape) so compile
    # time never counts against request SLOs, then zero the metrics
    for m in cfgs:
        if pool.m_c(m) == 0:
            pool.scale_to(m, 1)
    pool.warmup(seed=seed)

    rng = np.random.default_rng(seed)
    draw_prompt = {m: _shared_prefix_prompts(rng, cfg.vocab_size,
                                             shared_prefix_tokens)
                   for m, cfg in cfgs.items()} if shared_prefix_tokens \
        else None
    per_rps = rps / max(1, len(cfgs))
    next_arrival = {m: rng.exponential(1.0 / per_rps) for m in cfgs}
    next_control = control_ms / 1000.0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration_s:
        now = time.perf_counter() - t0
        for m, cfg in cfgs.items():
            while next_arrival[m] <= now:
                prompt = draw_prompt[m]() if draw_prompt is not None \
                    else rng.integers(1, cfg.vocab_size,
                                      rng.integers(4, 24)).astype(np.int32)
                pool.submit(m, prompt, slo_ms=slo_ms,
                            max_new_tokens=max_new_tokens)
                next_arrival[m] += rng.exponential(1.0 / per_rps)
        if now >= next_control:
            applied = sched.control()
            slot = max(pool.slot_ms(m) for m in cfgs)
            next_control = now + float(
                np.clip(slot, control_ms, 2000.0)) / 1000.0
            print(f"[pool t={now:5.1f}s] (b, m_c)={applied} "
                  f"live={pool.total_live()}")
        if not any(i.n_resident for i in pool.live()) \
                and not any(pool.queues.values()):
            time.sleep(0.002)
            continue
        sched.record(pool.step())

    report = pool.report()
    dur = time.perf_counter() - t0
    for m, row in report.items():
        print(f"[pool:{m}] served {row['served']:.0f} "
              f"({row['served']/max(dur,1e-6):.1f} rps), "
              f"SLO attainment {row['slo_attainment']:.1%}, "
              f"mean latency {row['mean_latency_ms']:.0f}ms, "
              f"utility {row['mean_utility']:.2f}, m_c={row['m_c']:.0f}, "
              f"preempted {row['preempted']:.0f}")
    print(f"[pool] stats: {pool.stats()}")
    print(f"[pool] guard interventions: {sched.guard_interventions}")
    return report


def serve_http(models: Sequence[str] = ("qwen3-0.6b",),
               host: str = "127.0.0.1", port: int = 8808,
               slo_ms: float = 2000.0, max_instances: int = 4,
               max_slots: int = 4, seed: int = 0,
               kv_block_budget: Optional[int] = None,
               backpressure: bool = True, max_queue_depth: int = 8,
               control_ms: float = 500.0,
               ready: Optional[Callable[..., None]] = None,
               configs: Optional[Dict] = None,
               reduced: bool = False) -> None:
    """Push-mode HTTP serving (docs/RUNTIME.md §11): the pool runs on a
    background :class:`~repro.serving.driver.ServingDriver` thread with
    the ``PoolScheduler`` re-deciding (b, m_c) on a wall-clock tick, and
    an asyncio :class:`~repro.launch.server.ServingFrontend` streams
    per-token events over HTTP until interrupted, or until another
    thread calls the front-end's ``close_threadsafe()``. ``ready(fe)``
    fires with the :class:`~repro.launch.server.ServingFrontend` once
    its socket is bound (``fe.port``; ``port=0`` picks an ephemeral
    port). ``configs`` overrides the registry lookup with explicit
    ``ModelConfig`` objects — tools/server_smoke.py serves a tiny
    throwaway model that way."""
    import asyncio

    from repro.launch.server import ServingFrontend
    from repro.serving.driver import ServingDriver

    cfgs = configs or {m: load_config(m, reduced) for m in models}
    pool = ModelInstancePool(cfgs, max_instances=max_instances,
                             max_slots=max_slots, max_seq=128, seed=seed,
                             kv_block_budget=kv_block_budget)
    per_model_mc = max(1, max_instances // max(1, len(cfgs)))
    scfg = ServingConfig(
        batch_sizes=tuple(b for b in (1, 2, 4, 8) if b <= max_slots),
        concurrency_levels=tuple(range(1, per_model_mc + 1)))
    sched = PoolScheduler(pool, scfg,
                          slo_ms={m: slo_ms for m in cfgs},
                          seed=seed)
    sched.control()
    for m in cfgs:
        if pool.m_c(m) == 0:
            pool.scale_to(m, 1)
    pool.warmup(seed=seed)

    async def _run() -> None:
        with ServingDriver(pool, on_tick=sched.tick,
                           tick_interval_s=control_ms / 1000.0) as driver:
            fe = ServingFrontend(driver, host=host, port=port,
                                 backpressure=backpressure,
                                 max_queue_depth=max_queue_depth,
                                 default_slo_ms=slo_ms)
            await fe.start()
            print(f"[http] serving {sorted(cfgs)} on "
                  f"http://{host}:{fe.port} "
                  f"(backpressure {'on' if backpressure else 'off'})")
            if ready is not None:
                ready(fe)
            try:
                await fe.serve_forever()
            except asyncio.CancelledError:
                pass
            finally:
                await fe.stop()
                print(f"[http] stopped; stats: {driver.stats()}")

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        print("[http] interrupted")


def main(exec_mode: str = "round", arch: str = "qwen3-0.6b",
         duration_s: float = 20.0, rps: float = 12.0,
         slo_ms: float = 1500.0, models: Optional[Sequence[str]] = None,
         max_instances: int = 4,
         kv_block_budget: Optional[int] = None,
         token_budget: Optional[int] = None,
         preemption: bool = False, kv_host_blocks: int = 0,
         preempt_mode: str = "auto", prefix_cache: bool = False,
         shared_prefix_tokens: float = 0.0, spec_k: int = 0,
         serve_http_port: Optional[int] = None,
         backpressure: bool = True, max_queue_depth: int = 8,
         reduced: bool = False) -> None:
    if serve_http_port is not None:
        serve_http(models or [arch], port=serve_http_port, slo_ms=slo_ms,
                   reduced=reduced,
                   max_instances=max_instances,
                   kv_block_budget=kv_block_budget,
                   backpressure=backpressure,
                   max_queue_depth=max_queue_depth)
    elif models:
        if exec_mode != "continuous":
            print("multi-model pool serving is continuous-only; "
                  "running with --exec-mode continuous")
        serve_pool(models, duration_s, rps, slo_ms,
                   max_instances=max_instances,
                   kv_block_budget=kv_block_budget,
                   token_budget=token_budget, preemption=preemption,
                   kv_host_blocks=kv_host_blocks,
                   preempt_mode=preempt_mode,
                   prefix_cache=prefix_cache,
                   shared_prefix_tokens=int(shared_prefix_tokens),
                   spec_k=spec_k, reduced=reduced)
    elif exec_mode == "continuous":
        serve_continuous(arch, duration_s, rps, slo_ms,
                         kv_block_budget=kv_block_budget,
                         token_budget=token_budget,
                         prefix_cache=prefix_cache,
                         shared_prefix_tokens=int(shared_prefix_tokens),
                         spec_k=spec_k, reduced=reduced)
    else:
        if token_budget or preemption or prefix_cache or spec_k \
                or kv_host_blocks:
            print("chunked prefill / preemption / prefix caching / "
                  "speculation / KV offload are continuous-engine "
                  "features; ignored in round mode")
        serve_round(arch, duration_s, rps, slo_ms, reduced=reduced)


if __name__ == "__main__":
    main()
