"""Share of its roofline that the jitted ``prefill_chunk`` executable
(``models/transformer.py``) reaches: the least time the chip needs for
the traced prefill pieces (``work.prefill``: weights once, the users' own
prompt tokens, the KV of their live positions), over its device time."""
from readers import roofline

LAYER = "model step (models/transformer.py)"
MOVES = "ttft_p95_ms"


def read(ctx):
    return roofline(ctx, "prefill", "prefill_chunk")
