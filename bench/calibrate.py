"""Readings that a cell's correctness limit is set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8

sets the cell's system up once and, for each seed, serves that seed's
weights and traffic at the cell's own load for a short window, then
compares a run's sample of what was served with the float32 reference
(the lower reading: ``check.gaps``'s ``served``) and puts the control
in the program's place (the reference in the next lower precision: the
upper reading). Prints one JSON line per seed and a summary. Not part of
a run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import check  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402

#: sample sizes (served tokens) the readings are taken at
SIZES = (1000, 3000)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    cell = spec.resolve(args.workload)

    seeds = [int(s) for s in args.seeds.split(",")]
    devs, _, ref_mod, sizes, sysm = run.prepare(cell, seeds[0])
    rows = []
    try:
        for seed in seeds:
            sysm.reload_weights(ref_mod, sizes, seed)
            sysm.start()
            res = run.drive(sysm, cell, seed, args.seconds)
            sysm.stop()
            reqs = res["reqs"]
            picked = check.sample(res["records"], seed, max(SIZES))
            items = [{"prompt": reqs[r["idx"]].prompt, "tokens": r["tokens"],
                      "serving": cell.serving} for r in picked]
            t = time.perf_counter()
            g = check.gaps(ref_mod, sizes, sysm.weights, items,
                           int(cell.serving["max_seq"]),
                           control=cell.config["precision"]["control"])
            by_idx = {r["idx"]: o for r, o in zip(picked, g)}
            row = {"seed": seed, "reference_s": time.perf_counter() - t,
                   "faults": len(stats.protocol_faults(res["records"])),
                   "forms": {f: sum(o["agree"][f] > o["agree"][f2]
                                    for o in g for f2 in o["agree"]
                                    if f2 != f) for f in g[0]["agree"]}}
            for n in SIZES:
                sub = [by_idx[r["idx"]] for r in
                       check.sample(res["records"], seed, n)]
                row[n] = {k: dict(check.numbers(sub, k),
                                  flips=int(sum((o[k][check.form_of(o)] > 0)
                                                .sum() for o in sub)))
                          for k in ("served", "control")}
                row[n]["tokens"] = int(sum(len(o["served"][check.form_of(o)])
                                           for o in sub))
                row[n]["requests"] = len(sub)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        sysm.stop()
    for n in SIZES:
        for k in check.NUMBERS:
            served = [r[n]["served"][k] for r in rows]
            control = [r[n]["control"][k] for r in rows]
            print(json.dumps({"workload": cell.name, "tokens": n,
                              "number": k, "lower_reading": max(served),
                              "upper_reading": min(control),
                              "served": served, "control": control}))


if __name__ == "__main__":
    main()
