"""Reduce a JAX profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read: busy and idle time over the traced window, the
device time of each executable, the top device operations, and the
device's idle gaps labelled by what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` alone. The traced
window is the host span ``bench.window`` that ``run.py`` opens around
the trace; host spans that label the gaps are the ones ``system.py``
adds around the program's calls (``SPANS``, innermost first).
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: host spans that label an idle gap, innermost first
SPANS = ("dispatch.decode_step", "dispatch.prefill_chunk", "engine.step",
         "pool.step", "scheduler.tick", "client.hook")
WINDOW_SPAN = "bench.window"
NO_SPAN = "outside pool.step (driver idle or waiting for its lock)"
#: idle gaps shorter than this are left out of the gap breakdown (they
#: still count as idle time)
MIN_GAP_NS = 20_000

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.3
    bf16[8,128]``: the instruction and its result shape."""
    name, _, rest = text.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{name} {shape.group(1)}" if shape else name


def self_times(events) -> Dict[str, float]:
    """Per name, the time an event ran less the time of the events nested
    inside it (a loop's body ops appear inside the loop op)."""
    out: Dict[str, float] = {}
    stack: List[list] = []                # [end, name]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= d
        out[name] = out.get(name, 0.0) + d
        stack.append([s + d, name])
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
    return None


def reduce(path: Path, n_devices: int = 1) -> Dict:
    """Numbers of one trace. ``n_devices``: the chips the run used; busy
    time is averaged over them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = sorted((p for p in pd.planes if _DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(_DEVICE_PLANE.match(p.name).group(1)))
    planes = planes[:n_devices]
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    spans: Dict[str, List[Tuple[float, float]]] = {k: [] for k in SPANS}
    window = None
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (float(e.start_ns),
                              float(e.start_ns + e.duration_ns))
                elif e.name in spans:
                    spans[e.name].append((float(e.start_ns),
                                          float(e.start_ns + e.duration_ns)))
    if not planes:
        return {"error": "no TPU device plane in the trace"}
    for iv in spans.values():
        iv.sort()
    per_dev = []
    for plane in planes:
        ops = _events(plane, "XLA Ops") or []
        mods = _events(plane, "XLA Modules") or []
        if window is None:
            evs = ops or mods
            window = (min(s for _, s, _ in evs),
                      max(s + d for _, s, d in evs))
        lo, hi = window
        busy = _clip(_union([(s, s + d) for _, s, d in (ops or mods)]),
                     lo, hi)
        per_mod: Dict[str, float] = {}
        for name, s, d in mods:
            if lo <= s < hi:
                k = module_name(name)
                per_mod[k] = per_mod.get(k, 0.0) + d
        mod_iv = sorted((s, s + d, module_name(n)) for n, s, d in mods)
        starts = [m[0] for m in mod_iv]

        def owner(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mod_iv[i][2] if i >= 0 and t < mod_iv[i][1] else "?"

        per_op = self_times([(f"{owner(s)}/{op_name(n)}", s, d)
                             for n, s, d in ops if lo <= s < hi])
        per_dev.append((busy, per_mod, per_op))
    lo, hi = window
    busy0 = per_dev[0][0]
    gaps = _gaps(busy0, lo, hi)
    labelled: Dict[str, float] = {}
    for s, e in gaps:
        if e - s >= MIN_GAP_NS:
            lab = _label(spans, (s + e) / 2.0)
            labelled[lab] = labelled.get(lab, 0.0) + (e - s)
    busy_s = sum(sum(e - s for s, e in b) for b, _, _ in per_dev) \
        / len(per_dev) / 1e9
    mods_all: Dict[str, float] = {}
    ops_all: Dict[str, float] = {}
    for _, pm, po in per_dev:
        for k, v in pm.items():
            mods_all[k] = mods_all.get(k, 0.0) + v / len(per_dev)
        for k, v in po.items():
            ops_all[k] = ops_all.get(k, 0.0) + v / len(per_dev)
    top = sorted(ops_all.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(labelled.items(), key=lambda kv: -kv[1])[:10]
    span_s = {}
    for k, iv in spans.items():
        inside = [(s, e) for s, e in iv if lo <= s < hi]
        if inside:
            span_s[k] = [len(inside), sum(e - s for s, e in inside) / 1e9]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s,
            "modules_s": {k: v / 1e9 for k, v in mods_all.items()},
            "spans": span_s,
            "device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in idle],
            "n_gaps": len(gaps)}


def _gaps(busy, lo, hi) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans: Dict[str, List[Tuple[float, float]]], t: float) -> str:
    """Innermost host span covering ``t``. Spans of one name never
    overlap each other, so one bisection per name finds the candidate."""
    for name in SPANS:
        iv = spans[name]
        i = bisect.bisect_right(iv, (t, float("inf"))) - 1
        if i >= 0 and iv[i][0] <= t <= iv[i][1]:
            return name
    return NO_SPAN


def seconds_of(modules_s: Dict[str, float], key: str) -> float:
    """Device seconds of every executable whose name contains ``key``."""
    return sum(v for k, v in modules_s.items() if key in k)
