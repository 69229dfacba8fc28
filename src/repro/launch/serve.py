"""Serving launcher: BCEdge scheduler over the edge simulator (default) or
the real-JAX engine (``--engine``), in round or continuous execution mode
(docs/ARCHITECTURE.md §5).

    PYTHONPATH=src python -m repro.launch.serve --platform xavier_nx \
        --episodes 6 --rps 30
    PYTHONPATH=src python -m repro.launch.serve --exec-mode continuous \
        --decode-steps 6
    PYTHONPATH=src python -m repro.launch.serve --engine --arch qwen3-0.6b
    PYTHONPATH=src python -m repro.launch.serve --engine --reduced \
        --models qwen3-0.6b,recurrentgemma-2b --exec-mode continuous \
        --max-instances 4
    PYTHONPATH=src python -m repro.launch.serve --engine --serve-http \
        --port 8808 --models qwen3-0.6b

``--engine`` serves the registered config at its published widths;
``--reduced`` swaps in the 2-layer smoke reduction the CPU recipes use.
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="xavier_nx",
                    choices=["xavier_nx", "jetson_tx2", "jetson_nano",
                             "tpu_v5e"])
    ap.add_argument("--rps", type=float, default=30.0)
    ap.add_argument("--episodes", type=int, default=6)
    ap.add_argument("--episode-ms", type=float, default=20_000.0)
    ap.add_argument("--no-guard", action="store_true")
    ap.add_argument("--exec-mode", default="round",
                    choices=["round", "continuous"],
                    help="round = run-to-completion (b, m_c) rounds "
                         "(paper §IV-D); continuous = iteration-level "
                         "batching (docs/ARCHITECTURE.md §5)")
    ap.add_argument("--decode-steps", type=float, default=1.0,
                    help="mean decode iterations per request (geometric); "
                         ">1 makes the workload autoregressive")
    ap.add_argument("--engine", action="store_true",
                    help="serve a real model instead of the sim")
    ap.add_argument("--reduced", action="store_true",
                    help="--engine: serve the 2-layer smoke reduction "
                         "of each arch instead of its published widths")
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--models", default=None,
                    help="comma-separated arch ids for the multi-model "
                         "pool serve mode (docs/RUNTIME.md); requires "
                         "--engine, continuous-only")
    ap.add_argument("--max-instances", type=int, default=4,
                    help="pool-wide live engine instance budget shared "
                         "by all --models")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="continuous-engine KV cache layout: dense "
                         "per-slot slabs or the vLLM-style block pool "
                         "(docs/ARCHITECTURE.md §5)")
    ap.add_argument("--kv-block-budget", type=int, default=None,
                    help="total KV blocks shared by all pool instances "
                         "(paged only; default: unlimited, each "
                         "instance gets its dense-equivalent grant)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-iteration cap on prefill-chunk + decode "
                         "tokens (chunked prefill, docs/ARCHITECTURE.md "
                         "§5); engine/pool: fixed cap + scheduler axis; "
                         "simulator: adds an action level. Default: "
                         "uncapped")
    ap.add_argument("--preemption", action="store_true",
                    help="SLO-aware preemption: evict the largest-slack "
                         "resident when an urgent request cannot be "
                         "admitted (docs/RUNTIME.md §8)")
    ap.add_argument("--kv-host-blocks", type=int, default=0,
                    help="host-memory KV block tier per paged engine "
                         "instance: preempted sequences can swap their "
                         "blocks to host instead of recomputing, and "
                         "the prefix cache spills cold blocks there "
                         "before invalidating (docs/RUNTIME.md §8). "
                         "Default: 0 (no host tier)")
    ap.add_argument("--preempt-mode", default="auto",
                    choices=["auto", "recompute", "swap"],
                    help="preemption eviction mode: recompute frees KV "
                         "and re-prefills on resume, swap moves it to "
                         "the host tier (needs --kv-host-blocks), auto "
                         "prices both with the calibrated token-cost "
                         "and swap-bandwidth fits and picks the "
                         "cheaper per victim (docs/RUNTIME.md §8)")
    ap.add_argument("--prefill-tokens", type=float, default=0.0,
                    help="simulator: mean prompt tokens per request "
                         "(geometric; 0 = single-shot, no prefill "
                         "modeling)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="vLLM-style prefix caching: paged engines "
                         "share full immutable prompt blocks at "
                         "refcount+1 (copy-on-write tails, LRU reuse of "
                         "evicted blocks; docs/ARCHITECTURE.md §5); the "
                         "simulator skips already-paid shared prefixes. "
                         "Engine/pool modes require --kv-layout paged")
    ap.add_argument("--shared-prefix-tokens", type=float, default=0.0,
                    help="templated workload: every prompt starts with "
                         "one of a small population of shared prefixes "
                         "of this many tokens (the regime "
                         "--prefix-cache exploits). Default: 0 (off)")
    ap.add_argument("--serve-http", action="store_true",
                    help="push-mode HTTP serving (docs/RUNTIME.md §11): "
                         "background driver steps the pool, asyncio "
                         "front-end streams per-token ndjson events; "
                         "requires --engine. Runs until interrupted")
    ap.add_argument("--port", type=int, default=8808,
                    help="--serve-http listen port (0 = ephemeral)")
    ap.add_argument("--no-backpressure", action="store_true",
                    help="--serve-http: disable 429 admission "
                         "backpressure (accept-everything)")
    ap.add_argument("--max-queue-depth", type=int, default=8,
                    help="--serve-http: queued requests tolerated per "
                         "model before non-admissible arrivals get "
                         "429 + Retry-After")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding depth: propose up "
                         "to k n-gram draft tokens per slot and verify "
                         "them in one forward (docs/ARCHITECTURE.md "
                         "§speculation); engine/pool: cap + fourth "
                         "scheduler axis; simulator: adds an action "
                         "level. Continuous-only. Default: 0 (off)")
    args = ap.parse_args()

    if args.models and not args.engine:
        ap.error("--models requires --engine (the simulator is already "
                 "multi-tenant over the paper's Table-IV models)")
    if args.prefix_cache and args.engine and args.kv_layout != "paged":
        ap.error("--prefix-cache on the engine needs --kv-layout paged "
                 "(sharing is block-granular)")
    if args.serve_http and not args.engine:
        ap.error("--serve-http requires --engine (the HTTP front-end "
                 "streams real engine tokens)")
    if args.kv_host_blocks and args.kv_layout != "paged":
        ap.error("--kv-host-blocks needs --kv-layout paged (the host "
                 "tier holds KV blocks)")
    if args.preempt_mode == "swap" and args.kv_host_blocks <= 0:
        ap.error("--preempt-mode swap needs --kv-host-blocks > 0 "
                 "(there is nowhere to swap to)")

    enable_compile_cache()
    if args.engine:
        from repro.launch import engine_serve

        models = [m for m in (args.models or "").split(",") if m] or None
        engine_serve.main(exec_mode=args.exec_mode, arch=args.arch,
                          models=models, max_instances=args.max_instances,
                          kv_layout=args.kv_layout,
                          kv_block_budget=args.kv_block_budget,
                          token_budget=args.token_budget,
                          preemption=args.preemption,
                          kv_host_blocks=max(0, args.kv_host_blocks),
                          preempt_mode=args.preempt_mode,
                          prefix_cache=args.prefix_cache,
                          shared_prefix_tokens=args.shared_prefix_tokens,
                          spec_k=max(0, args.spec_k),
                          serve_http_port=args.port if args.serve_http
                          else None,
                          backpressure=not args.no_backpressure,
                          max_queue_depth=args.max_queue_depth,
                          reduced=args.reduced)
        return

    from repro.config.base import ServingConfig
    from repro.core.interference import NNInterferencePredictor
    from repro.core.sac import SACAgent, SACConfig
    from repro.serving.bcedge import run_episode
    from repro.serving.features import state_dim
    from repro.serving.simulator import EdgeServingEnv

    from repro.serving.profiler import PerformanceProfiler

    cfg = ServingConfig(platform=args.platform, arrival_rps=args.rps,
                        exec_mode=args.exec_mode,
                        decode_steps_mean=max(1.0, args.decode_steps),
                        prefill_tokens_mean=max(0.0, args.prefill_tokens),
                        token_budgets=(0,) if not args.token_budget
                        else (0, args.token_budget),
                        preemption=args.preemption,
                        shared_prefix_tokens=max(
                            0.0, args.shared_prefix_tokens),
                        prefix_cache=args.prefix_cache,
                        spec_depths=(0,) if args.spec_k <= 0
                        else (0, args.spec_k))
    env0 = EdgeServingEnv(cfg, episode_ms=1.0)
    agent = SACAgent(state_dim(env0.models), cfg.n_actions,
                     SACConfig(batch_size=256, lr=5e-4))
    pred = None if args.no_guard else NNInterferencePredictor()
    profiler = PerformanceProfiler()
    for ep in range(args.episodes):
        env = EdgeServingEnv(cfg, episode_ms=args.episode_ms, seed=ep)
        res = run_episode(env, agent, pred, guard=not args.no_guard)
        profiler.reset_env()
        profiler.poll(env)
        s = res.summary
        util = profiler.utilization()
        print(f"ep{ep}: utility={s['mean_utility']:.2f} "
              f"thr={s['throughput_rps']:.1f}rps "
              f"goodput={s['goodput_rps']:.1f}rps "
              f"viol={s['slo_violation_rate']:.1%} "
              f"lat={s['mean_latency_ms']:.0f}ms "
              f"busy={util['busy_frac']:.0%} "
              f"overhead={sum(res.overhead_ms)/max(len(res.overhead_ms),1):.2f}ms/decision")
    # profiler-informed per-model configurations (§IV-E)
    for m in env0.models:
        best = profiler.best_config(m, max_violation=0.2)
        if best:
            print(f"profile[{m}]: best (b, m_c) within 20% violations "
                  f"= {best}")


if __name__ == "__main__":
    main()
