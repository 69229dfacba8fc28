"""Benchmark harness entry point — one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows.
``BENCH_FAST=0 PYTHONPATH=src python -m benchmarks.run`` for full-length
runs; the default is the fast profile (shorter episodes, fewer seeds).
``--only fig7`` runs a single module. ``--smoke`` runs every module at
toy scale (the CI job that keeps benchmark scripts from rotting —
numbers are meaningless, only the code paths are exercised).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODULES = [
    "fig1_motivation",
    "fig7_utility",
    "fig8_9_timeline",
    "fig10_convergence",
    "fig11_12_scalability",
    "fig13_interference",
    "fig14_15_slo",
    "fig16_overhead",
    "fig_continuous_vs_round",
    "fig_multimodel_concurrency",
    "fig_preemption_chunked",
    "fig_prefix_cache",
    "fig_speculative",
    "fig_sharded_engine",
    "fig_async_serving",
    "fig_kv_offload",
    "roofline_table",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--smoke", action="store_true",
                    help="toy-scale run of every module (CI rot check)")
    args = ap.parse_args()
    if args.smoke:
        # must land before benchmarks.common is imported by any module
        os.environ["BENCH_SMOKE"] = "1"
    fast = args.smoke or os.environ.get("BENCH_FAST", "1") != "0"
    failures = 0
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            mod.main(fast=fast)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.00,ERROR", flush=True)
            traceback.print_exc()
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
