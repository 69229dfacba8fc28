"""Share of the rows of the traced prefill pieces that were bucket
padding, not the users' own prompt tokens (``serving/engine.py`` pads a
prompt on the left to its length bucket and prefills the padding too).
Packing or dropping the padding lowers it."""
from readers import per_step

LAYER = "engine (serving/engine.py)"
MOVES = "ttft_p95_ms"


def read(ctx):
    steps = per_step(ctx)
    rows = sum(s["prefill_rows"] for s in steps)
    if not rows:
        return None
    return 100.0 * sum(s["prefill_pad"] for s in steps) / rows
