"""Beyond-paper figure: prefix caching with copy-on-write block sharing
(docs/ARCHITECTURE.md §5; recipe + expected numbers in
docs/EXPERIMENTS.md §Prefix cache).

Templated edge workload: every prompt is a 512-token shared prefix (a
system prompt / task preamble) plus a short per-request tail — the
regime where duplicated prefix KV is the dominant memory waste. Two
engines under the SAME tight block budget drain the same burst:

1. **no-cache baseline** — every request re-prefills and physically
   stores the full prompt, so the budget caps concurrent residency at
   ``budget / request_blocks``;
2. **prefix cache** — full immutable prompt blocks are shared at
   refcount+1 (copy-on-write tails, LRU revival of evicted blocks), so
   after the first request each admission charges only its private
   tail + decode blocks.

Asserted (the PR's acceptance bar):
  * >= 2x peak admission capacity (concurrently resident sequences),
  * >= 2x prefill-token reduction (chunked-prefill work actually run),
  * greedy outputs token-identical per request across the two engines.

Artifacts: ``benchmarks/out/fig_prefix_cache.json`` (always) and
``benchmarks/out/fig_prefix_cache.png`` (when matplotlib is available).

Run:  PYTHONPATH=src python -m benchmarks.fig_prefix_cache
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import FAST, SMOKE, emit
from repro.config.base import ModelConfig
from repro.serving.engine import ContinuousBatchingEngine

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")

TINY = ModelConfig(name="tiny-prefix", family="dense", n_layers=2,
                   d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                   vocab_size=211)

BLOCK_SIZE = 16
PREFIX_TOKENS = 512        # the acceptance point: 512-token shared prefix
TAIL_TOKENS = 16           # per-request unique tail (fixed length:
#                            left-padding makes sharing length-sensitive)
MAX_NEW = 16
MAX_SEQ = 704              # prompt bucket 640 + decode room
MAX_SLOTS = 8
BUDGET_BLOCKS = 96         # ~2.3 no-cache requests' worth of blocks
N_REQUESTS = 12


def _workload(seed: int = 0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, TINY.vocab_size, PREFIX_TOKENS).astype(
        np.int32)
    return [np.concatenate(
        [prefix, rng.integers(1, TINY.vocab_size, TAIL_TOKENS).astype(
            np.int32)]) for _ in range(N_REQUESTS)]


def _run(prefix_cache: bool, prompts, share_from=None):
    eng = ContinuousBatchingEngine(
        TINY, max_slots=MAX_SLOTS, max_seq=MAX_SEQ, seed=0,
        share_from=share_from, block_size=BLOCK_SIZE,
        kv_blocks=BUDGET_BLOCKS, prefix_cache=prefix_cache)
    # seed request: with the cache on it publishes the prefix blocks;
    # the baseline pays the same warmup, so the comparison stays fair
    eng.run([prompts[0]], max_new_tokens=MAX_NEW)
    for p in prompts:
        eng.submit(p, max_new_tokens=MAX_NEW)
    chunk0 = eng.n_prefill_chunk_tokens
    peak_resident = 0
    peak_shared = 0.0
    outputs = {}
    t0 = time.perf_counter()
    while (eng.waiting or eng.active_slots) and eng.n_iters < 20_000:
        for r in eng.step():
            outputs[r.request_id] = r.tokens
        peak_resident = max(peak_resident, len(eng.active_slots))
        peak_shared = max(peak_shared, eng.stats()["kv_shared_frac"])
    dur_s = time.perf_counter() - t0
    assert len(outputs) == N_REQUESTS, \
        f"{len(outputs)}/{N_REQUESTS} drained"
    s = eng.stats()
    return eng, {
        "prefix_cache": prefix_cache,
        "budget_blocks": BUDGET_BLOCKS,
        "peak_resident": peak_resident,
        "prefill_tokens": int(eng.n_prefill_chunk_tokens - chunk0),
        "prefix_hit_rate": s["prefix_hit_rate"],
        "peak_kv_shared_frac": peak_shared,
        "kv_waste_frac": s["kv_waste_frac"],
        "makespan_s": dur_s,
        "throughput_rps": N_REQUESTS / max(dur_s, 1e-6),
        "outputs": outputs,
    }


def _plot(rows: list, path: str) -> bool:
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:  # noqa: BLE001
        return False
    fig, axes = plt.subplots(1, 3, figsize=(11, 3.3))
    labels = ["no cache", "prefix cache"]
    axes[0].bar(labels, [r["peak_resident"] for r in rows],
                color=["#888", "#2a7"])
    axes[0].set_title("peak resident sequences\n(same block budget)")
    axes[1].bar(labels, [r["prefill_tokens"] for r in rows],
                color=["#888", "#2a7"])
    axes[1].set_title("prefill tokens actually run")
    axes[2].bar(labels, [r["peak_kv_shared_frac"] for r in rows],
                color=["#888", "#2a7"])
    axes[2].set_title("peak shared-block fraction")
    fig.suptitle(
        f"{PREFIX_TOKENS}-token shared prefixes, "
        f"{BUDGET_BLOCKS * BLOCK_SIZE}-token KV budget")
    fig.tight_layout()
    fig.savefig(path, dpi=120)
    plt.close(fig)
    return True


def main(fast: bool = FAST) -> dict:
    global PREFIX_TOKENS, TAIL_TOKENS, MAX_SEQ, BUDGET_BLOCKS, N_REQUESTS
    if SMOKE:
        # toy scale: the code paths, not the numbers
        PREFIX_TOKENS, TAIL_TOKENS = 48, 8
        MAX_SEQ, BUDGET_BLOCKS, N_REQUESTS = 128, 24, 4
    prompts = _workload()
    base_eng, base = _run(False, prompts)
    _, cached = _run(True, prompts, share_from=base_eng)

    # token identity: per request id (submission order matches)
    for rid, toks in base.pop("outputs").items():
        assert np.array_equal(toks, cached["outputs"][rid]), \
            f"request {rid}: cached output diverges from baseline"
    cached.pop("outputs")

    cap_ratio = cached["peak_resident"] / max(1, base["peak_resident"])
    prefill_ratio = base["prefill_tokens"] \
        / max(1, cached["prefill_tokens"])
    for row in (base, cached):
        label = "cached" if row["prefix_cache"] else "baseline"
        emit(f"fig_prefix.{label}", 0.0,
             f"resident={row['peak_resident']} "
             f"prefill_tokens={row['prefill_tokens']} "
             f"hit={row['prefix_hit_rate']:.2f} "
             f"shared={row['peak_kv_shared_frac']:.2f}")
    emit("fig_prefix.capacity_ratio", 0.0, f"{cap_ratio:.2f}x")
    emit("fig_prefix.prefill_reduction", 0.0, f"{prefill_ratio:.2f}x")
    if not SMOKE:
        # the PR's acceptance bar (docs/EXPERIMENTS.md §Prefix cache)
        assert cap_ratio >= 2.0, \
            f"admission capacity gain {cap_ratio:.2f}x < 2x"
        assert prefill_ratio >= 2.0, \
            f"prefill-token reduction {prefill_ratio:.2f}x < 2x"

    os.makedirs(OUT_DIR, exist_ok=True)
    payload = {"prefix_tokens": PREFIX_TOKENS, "tail_tokens": TAIL_TOKENS,
               "block_size": BLOCK_SIZE, "max_new_tokens": MAX_NEW,
               "budget_blocks": BUDGET_BLOCKS, "n_requests": N_REQUESTS,
               "rows": [base, cached], "capacity_ratio": cap_ratio,
               "prefill_reduction": prefill_ratio,
               "token_identical": True}
    json_path = os.path.join(OUT_DIR, "fig_prefix_cache.json")
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    emit("fig_prefix.json", 0.0, json_path)
    png_path = os.path.join(OUT_DIR, "fig_prefix_cache.png")
    if _plot([base, cached], png_path):
        emit("fig_prefix.plot", 0.0, png_path)
    return payload


if __name__ == "__main__":
    main()
