"""Share of its roofline that the jitted ``decode_step`` executable
(``models/transformer.py``) reaches: the least time the chip needs for
the traced decode calls (``work.decode``: weights once, the KV of live
positions, active rows only), over the executable's device time."""
from readers import roofline

LAYER = "model step (models/transformer.py)"
MOVES = "tpot_p95_ms"


def read(ctx):
    return roofline(ctx, "decode", "decode_step")
