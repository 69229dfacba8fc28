"""End-to-end metrics from the requests' records (``load.py``).

Every request sent inside the window counts, in every metric: one that
failed (refused by the pool, an error, or not finished within the drain
cap) is in ``failed``, misses both latency limits, and enters the latency
percentiles with the time it had waited when the run gave up on it (the
end of the drain cap less its due time). So a failure can only make a
tail look worse, never better. Percentiles interpolate linearly
(``numpy.percentile``).
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def window_records(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [r for r in records if r["phase"] == "window"]


def finished(r: Dict[str, Any]) -> bool:
    return r["status"] == "finished" and r["t_first"] is not None


def ttft_s(r: Dict[str, Any], give_up: float) -> float:
    return r["t_first"] - r["due"] if finished(r) else give_up - r["due"]


def tpot_s(r: Dict[str, Any], give_up: float) -> float:
    if not finished(r):
        return give_up - r["due"]
    if r["n_tokens"] < 2:
        return 0.0
    return (r["t_finish"] - r["t_first"]) / (r["n_tokens"] - 1)


def attained(r: Dict[str, Any], mix: Dict[str, Any]) -> bool:
    return finished(r) and r["protocol"] is None \
        and ttft_s(r, 0.0) * 1e3 <= mix["ttft_limit_ms"] \
        and tpot_s(r, 0.0) * 1e3 <= mix["tpot_limit_ms"]


def end_to_end(records: List[Dict[str, Any]], window: tuple,
               mix: Dict[str, Any], drain_cap_s: float) -> Dict[str, Any]:
    """The four end-to-end metrics, with the counts and
    medians printed beside them."""
    ws, we = window
    give_up = we + drain_cap_s
    win = window_records(records)
    if not win:
        raise RuntimeError("no request was sent inside the window")
    ttft = np.asarray([ttft_s(r, give_up) for r in win]) * 1e3
    tpot = np.asarray([tpot_s(r, give_up) for r in win]) * 1e3
    # tokens delivered inside the window, from any request: the user's
    # own prompt tokens once its first token arrives, and each output
    prompt_tok = sum(r["prompt_len"] for r in records if r["prompt_in_window"])
    out_tok = sum(r["n_win_tokens"] for r in records)
    n_fin = sum(1 for r in win if finished(r))
    return {
        "ttft_p95_ms": float(np.percentile(ttft, 95)),
        "tpot_p95_ms": float(np.percentile(tpot, 95)),
        "throughput_tok_s": (prompt_tok + out_tok) / (we - ws),
        "slo_attainment": 100.0 * sum(attained(r, mix) for r in win)
        / len(win),
        "info": {
            "attempted": len(win), "finished": n_fin,
            "failed": len(win) - n_fin,
            "by_status": {s: sum(1 for r in win if r["status"] == s)
                          for s in sorted({r["status"] for r in win})},
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "tpot_p50_ms": float(np.percentile(tpot, 50)),
            "beyond_p95": int((ttft > np.percentile(ttft, 95)).sum()),
            "prompt_tokens": prompt_tok, "output_tokens": out_tok,
        },
    }


def protocol_faults(records: List[Dict[str, Any]]) -> List[str]:
    """Answers that said the wrong thing: server errors and broken event
    streams, of requests sent in the window."""
    return [f"request {r['idx']}: {r['protocol']}"
            for r in window_records(records) if r["protocol"] is not None]


def never_came(records: List[Dict[str, Any]]) -> List[int]:
    """Requests of the window still open when the drain cap ran out."""
    return [r["idx"] for r in window_records(records)
            if r["status"] == "unfinished"]
