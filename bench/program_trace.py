"""The device's idle time, put down to the program's own spans.

The serving program covers its work with profiler spans named
``repro.<layer>.<part>`` (``src/repro/serving/tracing.py``). Over the
traced window of a trace this module splits the device's idle time (the
gaps between its operations, ``trace_reduce._gaps``) by the innermost
program span open at each instant:

- ``program_idle_s``: per layer (``driver``, ``pool``, ``engine``,
  ``scheduler``), the idle seconds whose innermost program span belongs
  to the layer. ``repro.python.gc`` counts as ``driver``; idle time that
  no program span covers goes to ``outside``. Every gap counts, however
  short, so the layers and ``outside`` sum to ``window_s - busy_s`` of
  ``trace_reduce.reduce``.
- ``program_idle_top``: the ten innermost spans with the most idle
  seconds.
- ``driver_turn_max_s``: the longest interval between the starts of two
  consecutive ``repro.driver.turn`` spans inside the window.

``split`` is the arithmetic; ``reduce`` reads it from a trace file with
the window and the device's busy intervals taken as ``trace_reduce``
takes them.
"""
from __future__ import annotations

import heapq
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import trace_reduce

PREFIX = "repro."
TURN_SPAN = "repro.driver.turn"
OUTSIDE = "outside"

Span = Tuple[float, float, str]              # start, end, name


def layer_of(name: str) -> str:
    """``repro.engine.emit`` -> ``engine``; a garbage collection belongs
    to the driver, whose thread it stalls."""
    part = name.split(".")[1]
    return "driver" if part == "python" else part


def innermost_idle(gaps: List[Tuple[float, float]],
                   spans: List[Span]) -> Dict[Optional[str], float]:
    """Per span name, the part of ``gaps`` (sorted, disjoint) during
    which that span is the innermost one open; under None the part no
    span covers. Innermost is the open span that started last (the
    shorter of two that started together): on one thread, the deepest."""
    points = sorted({t for s, e, _ in spans for t in (s, e)}
                    | {t for g in gaps for t in g})
    order = sorted(spans)
    out: Dict[Optional[str], float] = {}
    heap: List[Tuple[float, float, str]] = []  # (-start, end, name)
    nxt = gi = 0
    for a, b in zip(points, points[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps):
            break
        while nxt < len(order) and order[nxt][0] <= a:
            s, e, name = order[nxt]
            heapq.heappush(heap, (-s, e, name))
            nxt += 1
        if not gaps[gi][0] <= a:             # [a, b] is busy time
            continue
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][2] if heap else None
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def split(spans: List[Span], gaps: List[Tuple[float, float]],
          lo: float, hi: float) -> Dict:
    """The program's keys of a reduction, from its spans, the device's
    idle ``gaps`` and the window ``[lo, hi)``, all in nanoseconds."""
    by_name = innermost_idle(gaps, spans)
    layers: Dict[str, float] = {}
    for name, ns in by_name.items():
        k = OUTSIDE if name is None else layer_of(name)
        layers[k] = layers.get(k, 0.0) + ns / 1e9
    top = sorted(((n, ns / 1e9) for n, ns in by_name.items()
                  if n is not None), key=lambda kv: -kv[1])[:10]
    starts = sorted(s for s, _, n in spans if n == TURN_SPAN and lo <= s < hi)
    turn = max((b - a for a, b in zip(starts, starts[1:])), default=None)
    return {"program_idle_s": layers,
            "program_idle_top": [[n, s] for n, s in top],
            "driver_turn_max_s": None if turn is None else turn / 1e9}


def program_spans(pd) -> List[Span]:
    """Every ``repro.*`` event on the host plane of a ``ProfileData``."""
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events
            if e.name.startswith(PREFIX)]


def reduce(path: Path) -> Dict:
    """``split`` of a trace file, over the window and the first device's
    busy time as ``trace_reduce.reduce`` takes them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    dev = [p for p in pd.planes if trace_reduce._DEVICE_PLANE.match(p.name)]
    if not dev:
        return {"error": "no TPU device plane in the trace"}
    dev = min(dev, key=lambda p: int(
        trace_reduce._DEVICE_PLANE.match(p.name).group(1)))
    evs = trace_reduce._events(dev, "XLA Ops") \
        or trace_reduce._events(dev, "XLA Modules") or []
    window = next(((float(e.start_ns), float(e.start_ns + e.duration_ns))
                   for p in pd.planes if p.name == "/host:CPU"
                   for line in p.lines for e in line.events
                   if e.name == trace_reduce.WINDOW_SPAN), None)
    if window is None:
        window = (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))
    lo, hi = window
    busy = trace_reduce._clip(
        trace_reduce._union([(s, s + d) for _, s, d in evs]), lo, hi)
    out = split(program_spans(pd), trace_reduce._gaps(busy, lo, hi), lo, hi)
    out.update(window_s=(hi - lo) / 1e9,
               busy_s=sum(e - s for s, e in busy) / 1e9)
    return out
