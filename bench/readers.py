"""Helpers the per-layer metric readers share (``bench/metrics``).

A reader gets the run's context: the requests' ``records``, the reduced
``trace`` (or None), the traced ``span`` on the host clock, the program
``calls`` that ``system.py`` recorded (``("decode", t, contexts)``,
``("prefill", t, start, rows, first_real)``, ``("step", t)``), the
configuration's ``sizes``, the device's ``peak`` row, the served element
size ``wbytes`` and ``memory_peak_bytes``. It returns a number, or None when there is
nothing to read, and never 0 for a share of a roofline or a peak.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import trace_reduce
import work


def traced(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduced trace, or None when the run has none to read."""
    red = ctx.get("trace")
    if not red or "error" in red or not ctx.get("span"):
        return None
    return red


def calls(ctx: Dict[str, Any], kind: str) -> List[tuple]:
    """Program calls of ``kind`` made inside the traced span."""
    t0, t1 = ctx["span"]["t0"], ctx["span"]["t1"]
    return [c for c in ctx["calls"] if c[0] == kind and t0 <= c[1] <= t1]


def per_step(ctx: Dict[str, Any]) -> List[Dict[str, int]]:
    """Per engine iteration ended inside the traced span: the users' own
    tokens it processed (``real``: real prompt rows of its prefill pieces
    plus its active decoding rows) and its prefill rows, padding
    (``prefill_pad``) included (``prefill_rows``). A call belongs to the
    iteration whose ``step`` record follows it."""
    if not ctx.get("span"):
        return []
    t0, t1 = ctx["span"]["t0"], ctx["span"]["t1"]
    out: List[Dict[str, int]] = []
    acc = {"real": 0, "prefill_rows": 0, "prefill_pad": 0}
    for c in ctx["calls"]:
        if c[0] == "decode":
            acc["real"] += len(c[2])
        elif c[0] == "prefill":
            real = work.real_rows(c[2], c[3], c[4])
            acc["real"] += real
            acc["prefill_rows"] += c[3]
            acc["prefill_pad"] += c[3] - real
        elif c[0] == "step":
            if t0 <= c[1] <= t1:
                out.append(acc)
            acc = {"real": 0, "prefill_rows": 0, "prefill_pad": 0}
    return out


def needed(ctx: Dict[str, Any], kind: str) -> List[Dict[str, float]]:
    """Needed work of each traced call of ``kind``."""
    s, wb = ctx["sizes"], ctx["wbytes"]
    if kind == "decode":
        return [work.decode(s, c[2], wb) for c in calls(ctx, "decode")
                if c[2]]
    return [work.prefill(s, c[2], c[3], c[4], wb)
            for c in calls(ctx, "prefill")]


def roofline(ctx: Dict[str, Any], kind: str, module: str) -> Optional[float]:
    """Least time the traced calls of ``kind`` could take on this chip,
    over the device time of the executables named like ``module``, in
    percent."""
    red = traced(ctx)
    if red is None or ctx["peak"] is None:
        return None
    dev_s = trace_reduce.seconds_of(red["modules_s"], module)
    need = needed(ctx, kind)
    bound = sum(work.bound_s(w, ctx["peak"]) for w in need)
    if dev_s <= 0.0 or bound <= 0.0:
        return None
    return 100.0 * bound / dev_s


def step_mfu(ctx: Dict[str, Any]) -> Optional[float]:
    """Model FLOPs the traced calls needed, over the device's busy time
    at the peak rate, in percent."""
    red = traced(ctx)
    if red is None or ctx["peak"] is None or red["busy_s"] <= 0.0:
        return None
    flops = sum(w["flops"] for k in ("decode", "prefill")
                for w in needed(ctx, k))
    if flops <= 0.0:
        return None
    return 100.0 * flops / (red["busy_s"] * ctx["peak"]["flops_per_s"])
