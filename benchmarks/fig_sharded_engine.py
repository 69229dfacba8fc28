"""Beyond-paper figure: what TP degree buys a KV-tight pool
(docs/RUNTIME.md §10; recipe in docs/EXPERIMENTS.md §Sharded engine).

The scheduler's fifth axis is the TP degree: a degree-d instance spans
d devices of the shared set, head-sharding its paged block pool over
the mesh's model axis. Because each KV block is spread over d devices,
the pool charges a degree-d instance only ``ceil(grant/d)`` blocks of
the SHARED per-device budget while the engine keeps the full grant —
one budget block buys d pool blocks. On a budget-bound pool that is
the capacity the guard trades against the collective surcharge
(``tp_collective_ms_per_token``) when it prices the layout.

Measured panel (a CPU subprocess — the forced-host device flag must
predate the jax import, ``benchmarks/run.py`` imports every figure into
one process, and the child is pinned to ``JAX_PLATFORMS=cpu`` so it
never contends for a chip the parent holds): two pools drain the SAME
decode-heavy trace under the same tight ``kv_block_budget``, one
pinned to the tp_degrees=(1,)
layout, one at the tp_degree=2 layout the guard may now pick. Engine
admission reserves worst-case blocks per request, so the tp=1 pool
holds half the residents and its queue waits double; goodput (requests
served within SLO per second) and per-request Eq.-3 utility — both
computed from wall-clock latency including queue wait — improve at
tp=2 despite the slower sharded step. Each layout drains the trace
``N_REPS`` times (interleaved, pools reused so compiles stay out of
the measured region) and the median drain is reported.

Analytic panel (in-process): per-degree KV capacity multiplier and the
collective surcharge for the 7B-ish shape from
``roofline_table.TP_SHAPES``, showing the trade the guard prices.

Asserted (the PR's acceptance bar, skipped in SMOKE): tp=2 goodput
AND mean utility strictly above the tp_degrees=(1,) layout on the
same trace.

Artifacts: ``benchmarks/out/fig_sharded_engine.json`` (always) and
``benchmarks/out/fig_sharded_engine.png`` (when matplotlib is there).

Run:  PYTHONPATH=src python -m benchmarks.fig_sharded_engine
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import FAST, SMOKE, emit
from benchmarks.roofline_table import TP_SHAPES
from repro.serving.bcedge import tp_collective_ms_per_token

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

N_REQ = 32
MAX_NEW = 40
PROMPT_LEN = 24
KV_BUDGET = 40          # blocks — tight: the binding resource
SLO_MS = 600_000.0      # generous: goodput == drained throughput
N_REPS = 5              # interleaved drains per layout; median reported

_CODE_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np
from repro.config.base import ModelConfig
from repro.serving.runtime import ModelInstancePool

TINY = ModelConfig(name="tiny-tp", family="dense", n_layers=2,
                   d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                   vocab_size=97)
RNG = np.random.default_rng(3)


def make_pool(tp):
    pool = ModelInstancePool({"tiny-tp": TINY}, max_instances=2,
                             max_slots=12, max_seq=128,
                             block_size=8,
                             kv_block_budget=P["kv_budget"],
                             tp_degree=tp, n_devices=2, seed=0)
    assert pool.scale_to("tiny-tp", 1) == 1
    # warm pass compiles prefill/decode for this layout: every
    # measured drain below reuses the pool, so compile time never
    # lands inside a measured makespan
    for _ in range(2):
        pool.submit("tiny-tp", RNG.integers(1, 97, P["prompt_len"])
                    .astype(np.int32), slo_ms=P["slo_ms"],
                    max_new_tokens=P["max_new"])
    pool.run_until_drained()
    return pool


def drain_once(pool):
    rids = {pool.submit("tiny-tp",
                        RNG.integers(1, 97, P["prompt_len"])
                        .astype(np.int32), slo_ms=P["slo_ms"],
                        max_new_tokens=P["max_new"])
            for _ in range(P["n_req"])}
    pool.run_until_drained(max_steps=50_000)
    rs = [r for r in pool.results("tiny-tp") if r.request_id in rids]
    assert len(rs) == P["n_req"] and not any(r.rejected for r in rs)
    makespan = max(r.finish_s for r in rs) - min(r.submit_s for r in rs)
    good = [r for r in rs if not r.violated]
    lat = [r.latency_ms for r in rs]
    return {"goodput_rps": len(good) / makespan,
            "mean_utility": float(np.mean([r.utility for r in rs])),
            "mean_latency_ms": float(np.mean(lat)),
            "p95_latency_ms": float(np.percentile(lat, 95)),
            "makespan_s": makespan}


def summarize(pool, reps):
    # per-metric median over the drains: one slow-machine blip cannot
    # flip the layout comparison
    med = {k: float(np.median([r[k] for r in reps])) for k in reps[0]}
    inst = pool.running("tiny-tp")[0]
    per_req = inst.engine.request_blocks(P["prompt_len"], P["max_new"])
    out = dict(med)
    out.update({
        "tp": inst.tp_degree,
        "kv_charge_blocks": inst.kv_blocks,
        "kv_pool_blocks": inst.engine.allocator.n_blocks,
        "resident_capacity": inst.engine.allocator.n_blocks // per_req,
        "reps": reps})
    return out


# interleave the layouts' drains so machine-load drift hits both
pools = {1: make_pool(1), 2: make_pool(2)}
reps = {1: [], 2: []}
for _ in range(P["n_reps"]):
    for tp in (1, 2):
        reps[tp].append(drain_once(pools[tp]))
out = {"tp1": summarize(pools[1], reps[1]),
       "tp2": summarize(pools[2], reps[2])}
print("RESULT " + json.dumps(out))
"""


def _measure() -> dict:
    params = {"n_req": N_REQ, "max_new": MAX_NEW,
              "prompt_len": PROMPT_LEN, "kv_budget": KV_BUDGET,
              "slo_ms": SLO_MS, "n_reps": N_REPS}
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    # a virtual-CPU-mesh measurement: the child must never reach for an
    # accelerator, which the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    code = f"P = {json.dumps(params)}\n" + _CODE_BODY
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line in:\n{out.stdout[-2000:]}")


def _analytic() -> list:
    """Per-degree capacity multiplier vs collective surcharge for the
    7B server shape — the two sides of the guard's layout price."""
    label, cfg, b, ctx = TP_SHAPES[0]
    return [{"shape": label, "tp": d, "kv_capacity_x": float(d),
             "collective_ms_per_token": tp_collective_ms_per_token(cfg, d)}
            for d in (1, 2, 4, 8)]


def _plot(meas: dict, path: str) -> bool:
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:  # noqa: BLE001
        return False
    fig, (ax, ax2) = plt.subplots(1, 2, figsize=(9, 4))
    labels = ["tp_degrees=(1,)", "guard picks tp=2"]
    colors = ["#888", "#2a7"]
    rows = [meas["tp1"], meas["tp2"]]
    ax.bar(labels, [r["goodput_rps"] for r in rows], color=colors)
    for i, r in enumerate(rows):
        ax.text(i, r["goodput_rps"],
                f"{r['resident_capacity']} resident\n"
                f"{r['kv_pool_blocks']} blocks", ha="center", va="bottom")
    ax.set_ylabel("goodput (req/s within SLO)")
    ax.set_title("same trace, same shared KV budget")
    ax2.bar(labels, [r["mean_latency_ms"] for r in rows], color=colors)
    ax2.set_ylabel("mean latency ms (incl. queue wait)")
    ax2.set_title("queue wait under the block budget")
    fig.suptitle("TP degree as a scheduler axis on a KV-tight pool")
    fig.tight_layout()
    fig.savefig(path, dpi=120)
    plt.close(fig)
    return True


def main(fast: bool = FAST) -> dict:
    global N_REQ, MAX_NEW, N_REPS
    if SMOKE:
        # toy scale: the code paths, not the numbers
        N_REQ, MAX_NEW, N_REPS = 6, 8, 1

    meas = _measure()
    t1, t2 = meas["tp1"], meas["tp2"]
    for k, r in (("tp1", t1), ("tp2", t2)):
        emit(f"fig_sharded.{k}", 0.0,
             f"goodput={r['goodput_rps']:.2f}rps "
             f"utility={r['mean_utility']:.3f} "
             f"lat={r['mean_latency_ms']:.0f}ms "
             f"residents={r['resident_capacity']} "
             f"blocks={r['kv_pool_blocks']}")
    emit("fig_sharded.gain", 0.0,
         f"goodput={t2['goodput_rps']/max(t1['goodput_rps'],1e-9):.2f}x "
         f"capacity={t2['kv_pool_blocks']}/{t1['kv_pool_blocks']}blocks")
    if not SMOKE:
        # the PR's acceptance bar (docs/EXPERIMENTS.md §Sharded engine)
        assert t2["goodput_rps"] > t1["goodput_rps"], \
            f"tp=2 goodput {t2['goodput_rps']:.3f} not above " \
            f"tp_degrees=(1,) {t1['goodput_rps']:.3f}"
        assert t2["mean_utility"] > t1["mean_utility"], \
            f"tp=2 utility {t2['mean_utility']:.4f} not above " \
            f"tp_degrees=(1,) {t1['mean_utility']:.4f}"

    arows = _analytic()
    emit("fig_sharded.analytic", 0.0,
         f"{arows[0]['shape']}: " + " ".join(
             f"tp{r['tp']}={r['collective_ms_per_token']*1e3:.0f}us/tok"
             for r in arows[1:]))

    os.makedirs(OUT_DIR, exist_ok=True)
    payload = {"n_req": N_REQ, "max_new": MAX_NEW,
               "prompt_len": PROMPT_LEN, "kv_budget": KV_BUDGET,
               "measured": meas, "analytic": arows}
    json_path = os.path.join(OUT_DIR, "fig_sharded_engine.json")
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    emit("fig_sharded.json", 0.0, json_path)
    png_path = os.path.join(OUT_DIR, "fig_sharded_engine.png")
    if _plot(meas, png_path):
        emit("fig_sharded.plot", 0.0, png_path)
    return payload


if __name__ == "__main__":
    main()
