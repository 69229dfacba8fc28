"""Work that a step of a dense GQA decoder needs, counted from shapes.

These are the numerators of the roofline shares and of ``step_mfu``.
They count what the algorithm needs, whatever implements it:

- every weight read once per call, at the served dtype (the embedding
  table only as the rows looked up, unless it is also the output head);
- the keys and values of live positions only: a row attends the
  positions of its own sequence up to itself, and writes its own;
- the users' own prompt tokens, never the bucket padding in front of
  them, and the rows of active slots, never the dummy rows of free ones.

So the gathers over a block table's whole width, a copy of the cache
that a step makes because its buffer is not donated, padding and dummy
rows are never counted: removing them raises a share, and no
implementation can push one past 100%.

``s`` is the dict of ``sizes()`` from the configuration's reference
module; ``wbytes`` the bytes of one served weight or cache element.
"""
from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(s: Dict) -> int:
    """Weights one token multiplies through: every layer's projections
    and the output head."""
    d, hd = s["d"], s["head_dim"]
    layer = (d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd
             + s["heads"] * hd * d + 3 * d * s["ffn"])
    return s["layers"] * layer + d * s["vocab"]


def weight_bytes(s: Dict, wbytes: int) -> int:
    """Bytes of every weight a call reads (norm scales included)."""
    d, hd = s["d"], s["head_dim"]
    norms = s["layers"] * (2 * d + (2 * hd if s["qk_norm"] else 0)) + d
    return (matmul_params(s) + norms) * wbytes


def kv_bytes_per_token(s: Dict, wbytes: int) -> int:
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * wbytes


def _attn_flops(s: Dict, keys: int) -> int:
    """q.k and p.v over ``keys`` attended positions, all layers."""
    return 4 * s["layers"] * s["heads"] * s["head_dim"] * keys


def decode(s: Dict, contexts: Iterable[int], wbytes: int) -> Dict[str, float]:
    """One decode call: ``contexts`` holds, for each active row, the
    positions it attends (its cache plus the token itself)."""
    ctx = list(contexts)
    rows, keys = len(ctx), sum(ctx)
    flops = 2 * matmul_params(s) * rows + _attn_flops(s, keys)
    kvb = kv_bytes_per_token(s, wbytes)
    nbytes = (weight_bytes(s, wbytes) + kvb * keys
              + rows * s["d"] * wbytes)             # embedding rows read
    return {"flops": float(flops), "bytes": float(nbytes)}


def real_rows(start: int, rows: int, first_real: int) -> int:
    """Rows of a prefill piece over ``[start, start + rows)`` that hold
    the user's own tokens, which begin at ``first_real``."""
    return max(0, start + rows - max(start, first_real))


def prefill(s: Dict, start: int, rows: int, first_real: int,
            wbytes: int) -> Dict[str, float]:
    """One prefill piece over positions ``[start, start + rows)`` of a
    sequence whose own tokens begin at ``first_real`` (the padding before
    it is not the user's). Only real rows count, each attending the real
    positions up to itself."""
    lo = max(start, first_real)
    hi = start + rows
    real = real_rows(start, rows, first_real)
    # row at position t attends t - first_real + 1 real positions
    keys = sum(t - first_real + 1 for t in range(lo, hi))
    kvb = kv_bytes_per_token(s, wbytes)
    earlier = max(0, lo - first_real)               # real cache read
    flops = 2 * matmul_params(s) * real + _attn_flops(s, keys)
    nbytes = (weight_bytes(s, wbytes) + kvb * (earlier + real)
              + real * s["d"] * wbytes)
    if real == 0:
        flops = nbytes = 0.0
    return {"flops": float(flops), "bytes": float(nbytes)}


def bound_s(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the chip could take for ``work``: the larger of its
    operations over the peak rate and its bytes over the bandwidth."""
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
