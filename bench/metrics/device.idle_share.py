"""Share of the traced window in which no operation ran on the device:
one less the union of the device's operation intervals over the
window."""
from readers import traced

LAYER = "device"
MOVES = "tpot_p95_ms"


def read(ctx):
    red = traced(ctx)
    if red is None or red["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
