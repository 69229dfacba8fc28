"""Find an open-loop cell's knee once, by a sweep of fixed rates.

    python bench/sweep.py --workload <cell> --rates 4,6,8 --seconds 20

sets the cell's system up once and serves its traffic at each rate in
turn, for ``--seconds`` each, printing a line per rate: SLO attainment,
TTFT and TPOT tails, throughput, and whether the backlog grew over the
window (the median TTFT of its last third against its first third). The
knee is the highest rate at which at least 90% of requests meet both
limits with no growing backlog; the cell's rate is set from it once, as a
number in its file. Not part of a run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402


def backlog_ratio(records, window) -> float:
    """Median TTFT of the window's last third over its first third."""
    win = sorted((r for r in stats.window_records(records)
                  if stats.finished(r)), key=lambda r: r["due"])
    if len(win) < 6:
        return float("nan")
    k = len(win) // 3
    first = np.median([stats.ttft_s(r, 0.0) for r in win[:k]])
    last = np.median([stats.ttft_s(r, 0.0) for r in win[-k:]])
    return float(last / first)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = spec.resolve(args.workload)

    devs, _, ref_mod, sizes, sysm = run.prepare(cell, args.seed)
    sysm.start()
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.load = dict(cell.load, rate_rps=rate)
            res = run.drive(sysm, cell, args.seed + i, args.seconds)
            e2e = stats.end_to_end(res["records"], res["window"],
                                   cell.traffic, run.DRAIN_CAP_S)
            row = {"rate_rps": rate, "backlog_ratio": backlog_ratio(
                res["records"], res["window"]), **e2e, "late": res["late"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        sysm.stop()
    print(json.dumps({"device": devs[0].device_kind, "sweep": rows}))


if __name__ == "__main__":
    main()
