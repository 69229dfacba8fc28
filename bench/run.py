"""The benchmark: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for. It builds the served stack (``system.py``) from the cell's files,
makes the weights from the seed, warms every shape the cell's traffic
uses, feeds the cell's traffic to the running serving driver
(``load.py``) for ``--seconds``, checks what was served against the
configuration's plain reference (``check.py``), and prints one JSON
object as its last line of standard output:

- ``--trace 0``: the cell's end-to-end metrics, from the requests'
  events stamped on the host clock;
- ``--trace 1``: its per-layer metrics, from a profiler trace of part of
  the window, the program's counters, and the client.

Earlier lines (standard error) give set-up times, how late requests were
submitted, the client's time on the driver's thread, compilations inside
the window (there should be none),
the pool's own host-clock latencies as a cross-check, and last the
numbers compared with their limits. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

import check  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from load import Load  # noqa: E402

#: a request of the window may finish this long after the window closes;
#: one still open then never came
DRAIN_CAP_S = 60.0
#: the traced part of a ``--trace 1`` window: at most this long, starting
#: this far in
TRACE_S = 4.0
TRACE_LEAD_S = 2.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_devices(chips: int):
    """The devices of this run; exits non-zero without a TPU or with
    fewer chips than the cell asks for. Never falls back to the CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: JAX finds no accelerator ({e})")
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX reports {devs[0].platform!r}); "
                 "the benchmark does not fall back to the CPU")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs


def device_peaks(kind: str) -> Dict[str, float]:
    table = spec.read_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json; add its published peaks")
    return table[kind]


class CompileWatch:
    """Times of every trace and backend compile JAX reports."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring

        self.spans: List[tuple] = []
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            now = time.perf_counter()
            self.spans.append((now - duration, now, event))

    def inside(self, lo: float, hi: float) -> int:
        return sum(1 for s, e, _ in self.spans if e > lo and s < hi)

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on)


def load_for(cell) -> Dict[str, Any]:
    """The cell's load parameters, with the drain cap as the cool-down
    an open loop keeps sending through."""
    load = dict(cell.load)
    load.setdefault("cool_s", DRAIN_CAP_S)
    return load


def drive(sysm, cell, seed: int, seconds: float,
          during: Optional[Callable[[float, float], None]] = None
          ) -> Dict[str, Any]:
    """Serve one window of the cell's traffic from ``seed``;
    ``during(ws, we)`` runs on this thread once the window is fixed (the
    traced run starts and stops the profiler there)."""
    reqs = traffic.requests(cell.traffic, load_for(cell), seconds,
                            sysm.cfg.vocab_size, seed)
    load = Load(cell.traffic, load_for(cell), reqs, sysm.cfg.name, seconds)
    sysm.serve(load)
    if during is not None:
        during(load.ws, load.we)
    load.wait(DRAIN_CAP_S)
    return {"records": list(load.records), "window": (load.ws, load.we),
            "late": load.lateness(), "client_cost": load.client_cost(),
            "reqs": {r.idx: r for r in reqs}}


def traced(trace_dir: Path, seconds: float):
    """Start and stop the profiler over part of the window, inside the
    host span the trace reduction takes as the window."""
    import jax

    span = {}

    def during(ws: float, we: float) -> None:
        t0 = ws + min(TRACE_LEAD_S, seconds / 4.0)
        t1 = t0 + min(TRACE_S, seconds / 2.0)
        time.sleep(max(0.0, t0 - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            span["t0"] = time.perf_counter()
            time.sleep(max(0.0, t1 - time.perf_counter()))
            span["t1"] = time.perf_counter()
        jax.profiler.stop_trace()

    return during, span


def per_layer(cell, ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def prepare(cell, seed: int, spans: bool = False, on_chip: bool = True):
    """The devices (a TPU with the cell's chips, unless ``on_chip`` is
    off), their peaks, the compile cache at its fixed path, and the
    system under test set up for ``seed``. Returns (devices, peaks,
    reference module, sizes, system)."""
    import jax

    if on_chip:
        devs = require_devices(cell.chips)
        peaks = device_peaks(devs[0].device_kind)
    else:
        devs, peaks = jax.devices(), None
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # cache every executable, however fast it compiled, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{cache}")
    ref_mod = spec.reference_module(cell.config)
    sizes = ref_mod.sizes(cell.config)
    from system import System

    sysm = System(cell, seed, ref_mod, sizes, spans=spans, log=log)
    return devs, peaks, ref_mod, sizes, sysm


def run(cell, seed: int, seconds: float, trace: bool,
        on_chip: bool = True, t_process: float = T_PROCESS
        ) -> Dict[str, Any]:
    """One run; returns the result line as a dict."""
    watch = CompileWatch()
    devs, peaks, ref_mod, sizes, sysm = prepare(cell, seed, trace, on_chip)
    try:
        sysm.start()
        with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
            tmp = Path(tmp)
            during, span = traced(tmp / "trace", seconds) if trace \
                else (None, {})
            res = drive(sysm, cell, seed, seconds, during)
            red = None
            if trace:
                xp = trace_reduce.find_xplane(tmp / "trace")
                red = trace_reduce.reduce(xp, len(devs)) if xp else None
        mem = devs[0].memory_stats() or {}
        peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    finally:
        sysm.stop()
    pool_stats = sysm.pool.stats()
    ws, we = res["window"]
    setup_s = ws - t_process
    records = res["records"]
    e2e = stats.end_to_end(records, (ws, we), cell.traffic, DRAIN_CAP_S)
    info = e2e.pop("info")
    log(f"set-up: {setup_s:.3f} s from process start to the window")
    log(f"submitted late by (submit - due): {res['late']}")
    log(f"client work on the driver's thread inside the window: "
        f"{res['client_cost']}")
    log(f"compilations inside the window: {watch.inside(ws, we)} "
        f"(of {len(watch.spans)} in the run)")
    log(f"requests: {info}")
    log("pool's own host clock (cross-check, trailing window): "
        + json.dumps({k: pool_stats[k] for k in (
            "ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50", "tpot_ms_p99",
            "token_base_ms", "token_per_ms")}))
    watch.close()

    # ---- the comparison, with the program's state freed -----------------
    weights = sysm.weights
    sysm.release()
    reqs = res["reqs"]
    picked = check.sample(records, seed)
    t = time.perf_counter()
    got = dict.fromkeys(check.NUMBERS)
    if picked:
        items = [{"prompt": reqs[r["idx"]].prompt, "tokens": r["tokens"],
                  "serving": cell.serving} for r in picked]
        g = check.gaps(ref_mod, sizes, weights, items,
                       int(cell.serving["max_seq"]))
        got = check.numbers(g)
        n_tok = sum(len(it["tokens"]) for it in items)
        agree = {f: sum(o["agree"][f] for o in g) for f in g[0]["agree"]}
        log(f"reference: {len(items)} requests, {n_tok} served tokens, "
            f"greedy agreement {agree} of {n_tok}, in "
            f"{time.perf_counter() - t:.1f} s")
    faults = stats.protocol_faults(records)
    lost = stats.never_came(records)
    limits = cell.load["limits"]
    checks = {k: {"value": got[k], "limit": float(limits[k])}
              for k in check.NUMBERS}
    checks.update({"wrong_answers": {"value": len(faults), "limit": 0},
                   "never_finished": {"value": len(lost), "limit": 0}})
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    for f in faults[:5]:
        log(f"wrong answer: {f}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    line: Dict[str, Any] = {"correct": correct,
                            "attempted": info["attempted"],
                            "failed": info["failed"]}
    if trace:
        ctx = {"records": records, "trace": red, "span": span,
               "calls": sysm.calls,
               "sizes": sizes, "peak": peaks,
               "wbytes": sysm.dtype.itemsize,
               "memory_peak_bytes": peak_bytes}
        line["metrics"] = per_layer(cell, ctx)
        if red and "error" not in red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
            log(f"trace: {json.dumps(red)}")
        elif red:
            log(f"trace: {red['error']}")
    else:
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in e2e.items() if k in units}
    line["device"] = device
    line["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return line


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    line = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
