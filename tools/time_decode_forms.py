#!/usr/bin/env python
"""Time the paged decode step in each of its forms: the table gather
(every slot's whole row of blocks) and the flat gather of the live
blocks at each width of a ladder.

The engine pads a decode step's live blocks to the smallest width of
``repro.serving.engine.DECODE_KV_BUCKETS`` that holds them and falls
back to the tables above ``DECODE_FLAT_MAX_BLOCKS``; this script gives
the numbers that ladder and ceiling rest on. Each form is compiled
first, then run ``--iters`` times back to back and timed to the last
result on the host clock, with every slot decoding; in the flat form
each slot owns an equal share of the width (its cost depends on the
width only). ``--layers 2`` rehearses it on the CPU.

    PYTHONPATH=src python tools/time_decode_forms.py --config qwen3-0.6b \\
        --slots 16 --max-seq 768 --widths 32,64,128,256,512

Prints one JSON line per form.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config
from repro.serving.engine import ContinuousBatchingEngine


def time_forms(cfg, slots: int, max_seq: int, block_size: int,
               widths, iters: int):
    eng = ContinuousBatchingEngine(cfg, max_slots=slots, max_seq=max_seq,
                                   block_size=block_size)
    per_slot = eng.blocks_per_slot
    tables = np.arange(1, slots * per_slot + 1, dtype=np.int32).reshape(
        slots, per_slot)
    pos = np.full(slots, per_slot * block_size // 2, np.int32)
    batch = {"tokens": jnp.ones((slots, 1), jnp.int32),
             "pos": jnp.asarray(pos),
             "block_tables": jnp.asarray(tables)}
    out = []
    for width in (0,) + tuple(widths):
        b = dict(batch)
        if width:
            # each slot owns an equal share of the width, from column 0
            share = max(1, width // slots)
            owner = np.repeat(np.arange(slots), share)[:width]
            col = np.tile(np.arange(share), slots)[:width]
            live = np.zeros((3, width), np.int32)
            live[1] = -1
            n = len(owner)
            live[:, :n] = (tables[owner, col], owner, col * block_size)
            b["live_blocks"] = jnp.asarray(live)
        t = time.perf_counter()
        logits, eng.cache = eng._decode(eng.params, eng.cache, b)
        logits.block_until_ready()
        compile_s = time.perf_counter() - t
        t = time.perf_counter()
        for _ in range(iters):
            logits, eng.cache = eng._decode(eng.params, eng.cache, b)
        logits.block_until_ready()
        step_ms = (time.perf_counter() - t) / iters * 1e3
        out.append({"config": cfg.name, "slots": slots, "max_seq": max_seq,
                    "table_blocks": slots * per_slot,
                    "form": f"flat{width}" if width else "table",
                    "step_ms": step_ms, "first_call_s": compile_s,
                    "iters": iters,
                    "device": jax.devices()[0].device_kind})
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen3-0.6b")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (0: as is)")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=768)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--widths", default="32,64,128,256,512")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()
    cfg = get_config(args.config)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    for row in time_forms(cfg, args.slots, args.max_seq, args.block_size,
                          [int(w) for w in args.widths.split(",")],
                          args.iters):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
