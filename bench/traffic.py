"""The one traffic generator: turns a mix file's parameters and a seed
into concrete requests. NumPy and the standard library only.

A mix file (``bench/traffic/<mix>.json``) holds:

- ``loop``: ``"open"`` (Poisson arrivals at the cell's rate) or
  ``"closed"`` (the cell's clients, each sending its next request when
  the previous one ends);
- ``prompt_len`` / ``output_len``: a distribution, ``{"dist":
  "lognormal", "median", "sigma", "min", "max"}`` (rounded, clipped) or
  ``{"dist": "uniform", "min", "max"}`` (inclusive integers);
- ``ttft_limit_ms`` / ``tpot_limit_ms``: the latency limits a request
  must meet to count as attained;
- ``trace_seed``: the seed of the mix's one trace.

The trace is drawn once from ``trace_seed``: independent exponential
gaps (a Poisson process at the cell's rate) and independent sizes, so it
keeps the bursts real traffic has. Every run serves that same trace; the
run's ``--seed`` draws the prompts' token ids (and, elsewhere, the
weights), which change no request's size or timing. So two seeds put the
same work into the window at the same times, and the spread between runs
is the system's, not the sampler's. Token ids are uniform in ``[1,
vocab)``: id 0 is the engine's pad token, so a served prompt's own tokens
are never 0.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np

#: phases of a run, in time order; only "window" requests are measured
PHASES = ("warm", "window", "cool")
_PHASE_CODE = {p: i for i, p in enumerate(PHASES)}
#: a closed loop's request list covers this long past the window
CLOSED_SLACK_S = 10.0


@dataclasses.dataclass
class Request:
    idx: int                 # position in the run's request list
    phase: str               # warm | window | cool
    due_s: float             # open loop: offset from the window's start
    prompt: np.ndarray       # int32 token ids, never 0
    max_new: int
    slo_ms: float            # end-to-end deadline sent to the server


def _rng(seed: int, phase: str, purpose: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), _PHASE_CODE[phase], purpose])


def draw(dist: Dict[str, Any], n: int, rng: np.random.Generator
         ) -> np.ndarray:
    """``n`` independent integer sizes from ``dist``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if dist["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n).astype(np.int64)
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def poisson_offsets(rate: float, span_s: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of a Poisson process at ``rate`` in ``[0,
    span_s)``: cumulative sums of independent exponential gaps."""
    out: List[float] = []
    t = rng.exponential(1.0 / rate)
    while t < span_s:
        out.append(t)
        t += rng.exponential(1.0 / rate)
    return np.asarray(out)


def slo_ms(mix: Dict[str, Any], max_new: int) -> float:
    """End-to-end deadline that meeting both limits implies."""
    return float(mix["ttft_limit_ms"] + mix["tpot_limit_ms"] * max_new)


def _group(mix: Dict[str, Any], n: int, seed: int, phase: str, vocab: int,
           start_idx: int, offsets: np.ndarray) -> List[Request]:
    trace = int(mix["trace_seed"])
    p_len = draw(mix["prompt_len"], n, _rng(trace, phase, 1))
    o_len = draw(mix["output_len"], n, _rng(trace, phase, 2))
    tok = _rng(seed, phase, 3)
    return [Request(start_idx + i, phase, float(offsets[i]),
                    tok.integers(1, vocab, int(p_len[i])).astype(np.int32),
                    int(o_len[i]), slo_ms(mix, int(o_len[i])))
            for i in range(n)]


def open_loop(mix: Dict[str, Any], rate: float, warm_s: float,
              window_s: float, cool_s: float, vocab: int,
              seed: int) -> List[Request]:
    """Poisson arrivals at ``rate``: a warm-up before the window, the
    window itself, and load that goes on while the window's requests
    drain (so they finish under the load they were measured in). Due
    times are offsets from the window's start."""
    out: List[Request] = []
    for phase, t0, span in (("warm", -warm_s, warm_s),
                            ("window", 0.0, window_s),
                            ("cool", window_s, cool_s)):
        offs = t0 + poisson_offsets(
            rate, span, _rng(int(mix["trace_seed"]), phase, 0))
        if len(offs):
            out += _group(mix, len(offs), seed, phase, vocab, len(out), offs)
    return out


def closed_loop(mix: Dict[str, Any], n: int, vocab: int,
                seed: int) -> List[Request]:
    """The first ``n`` requests the clients of a closed loop send, in
    order. Their phase is set by when they are sent; ``due_s`` is
    unused."""
    return _group(mix, n, seed, "window", vocab, 0, np.zeros(n))


def requests(mix: Dict[str, Any], load: Dict[str, Any], window_s: float,
             vocab: int, seed: int) -> List[Request]:
    """Every request a run of this mix and load may send."""
    if mix["loop"] == "open":
        return open_loop(mix, float(load["rate_rps"]), float(load["warm_s"]),
                         window_s, float(load["cool_s"]), vocab, seed)
    if mix["loop"] == "closed":
        # more than the clients can send in the run at the cell's bound
        # on one client's rate; a client that runs out stops sending
        span = float(load["warm_s"]) + window_s + CLOSED_SLACK_S
        n = int(load["clients"] * span * load["max_rps_per_client"]) + 1
        return closed_loop(mix, n, vocab, seed)
    raise ValueError(f"unknown loop {mix['loop']!r}")
