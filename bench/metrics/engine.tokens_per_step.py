"""The users' own tokens an engine iteration processes: the real prompt
tokens of its prefill pieces (never the bucket padding before them) plus
one per active decoding row, averaged over the iterations of the traced
span that did work. Read from what ``system.py`` records of each call
the engine makes (``serving/engine.py``)."""
from readers import per_step

LAYER = "engine (serving/engine.py)"
MOVES = "throughput_tok_s"


def read(ctx):
    steps = [s for s in per_step(ctx) if s["real"] > 0]
    if not steps:
        return None
    return sum(s["real"] for s in steps) / len(steps)
