"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the finished requests of the window (drawn from the seed, always with
the longest one in it) is run once through the configuration's plain
float32 reference at the highest matmul precision, teacher-forced over
the prompt and the tokens the server streamed. At every served position
it reads how far the reference's logit of the served token lies below
its best logit: greedy decoding picks the best logit, so only a near-tie
may flip under the program's rounding, and a wrong token leaves a whole
logit gap. Two numbers of those gaps are compared, each with its own
limit in the cell's file (``limits``): the widest gap of the sample
(``logit_gap_widest``), which one wrong token fails, and their mean over
every served position (``logit_gap_mean``), which a small error at many
positions fails.

The prompt a served completion continues is the prompt as sent, or, as
the engine serves it today, that prompt left-padded with the pad token to
its length bucket (the pad rows are attended). Each sampled request is
read against the form whose greedy choices its served tokens follow most
often, so a program that stops padding is held to the same limit.

The control (run by ``calibrate.py`` and the tests, never by a run) puts
the reference in the program's place at the next precision below the
one the configuration states (its ``precision.control``, below
``precision.stated``: the type every matrix product's inputs are
rounded to): at each position of the same prompts and tokens it reads
the gap of the token that the lower precision puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

#: the numbers compared, each with a limit in the cell's ``limits``
NUMBERS = ("logit_gap_widest", "logit_gap_mean")
#: a run compares at least this many served tokens
MIN_TOKENS = 1000
#: reference rows per call
ROWS = 4


def sample(records: List[Dict[str, Any]], seed: int,
           min_tokens: int = MIN_TOKENS) -> List[Dict[str, Any]]:
    """Finished requests of the window, the longest first, then in an
    order drawn from the seed, until ``min_tokens`` served tokens."""
    done = sorted((r for r in records if r["phase"] == "window"
                   and r["status"] == "finished" and r.get("tokens")),
                  key=lambda r: r["idx"])
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + r["n_tokens"],
                                       r["idx"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 0xC4EC]).permutation(len(rest))
    out, n = [longest], longest["n_tokens"]
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += rest[i]["n_tokens"]
    return out


def prompt_forms(prompt: np.ndarray, serving: Dict[str, Any]
                 ) -> Dict[str, np.ndarray]:
    """The prompt as sent, and left-padded to its bucket."""
    L = len(prompt)
    b = min(x for x in serving["prompt_buckets"] if x >= L)
    padded = np.concatenate([np.full(b - L, serving["pad_token"], np.int32),
                             prompt.astype(np.int32)])
    return {"as sent": prompt.astype(np.int32), "bucket-padded": padded}


def gaps(ref_mod, sizes, weights, items: List[Dict[str, Any]],
         length: int, control: Optional[str] = None
         ) -> List[Dict[str, Any]]:
    """Per item (dicts with ``prompt``, np.int32, ``tokens``, served, and
    ``serving``) and prompt form: the gap at each served position, how
    many served tokens the reference's greedy choice agrees with, and,
    where ``control`` names the type the control rounds its products'
    inputs to, the control's gaps at the same positions."""
    import jax.numpy as jnp

    forms = list(prompt_forms(items[0]["prompt"], items[0]["serving"]))
    ref = ref_mod.Reference(sizes)
    ctl = ref_mod.Reference(sizes, precision=None,
                            operands=jnp.dtype(control)) if control else None
    out = [{"served": {}, "control": {}, "agree": {}} for _ in items]
    for form in forms:
        seqs, spans = [], []
        for it in items:
            p = prompt_forms(it["prompt"], it["serving"])[form]
            toks = np.asarray(it["tokens"], np.int32)
            seqs.append(np.concatenate([p, toks]))
            spans.append((len(p) - 1, len(p) - 1 + len(toks)))
        for i0, n, block in ref_mod.blocks(seqs, length, ROWS):
            query = np.zeros_like(block)
            query[:, :-1] = block[:, 1:]
            h = ref.hidden(weights, block)
            best, at, arg = ref.stats(weights, h, query)
            if ctl is not None:
                _, _, carg = ctl.stats(weights, ctl.hidden(weights, block),
                                       query)
                _, cat, _ = ref.stats(weights, h, carg)
            for j in range(n):
                lo, hi = spans[i0 + j]
                o = out[i0 + j]
                o["served"][form] = best[j, lo:hi] - at[j, lo:hi]
                o["agree"][form] = int((arg[j, lo:hi] == query[j, lo:hi]).sum())
                if ctl is not None:
                    o["control"][form] = best[j, lo:hi] - cat[j, lo:hi]
    return out


def form_of(item: Dict[str, Any]) -> str:
    """The prompt form a request's served tokens follow: the one whose
    reference agrees with most of them."""
    return max(item["agree"], key=lambda f: item["agree"][f])


def numbers(per_item: List[Dict[str, Any]], key: str = "served"
            ) -> Dict[str, float]:
    """The numbers compared, over every served position of the sample,
    each request read in the form its served tokens follow: the widest
    gap and the mean gap."""
    g = np.concatenate([o[key][form_of(o)] for o in per_item])
    return {"logit_gap_widest": float(g.max()),
            "logit_gap_mean": float(g.mean())}
