"""Mean host time of one scheduler decision (``PoolScheduler.tick``:
state, SAC act and update, applying the action, ``serving/bcedge.py``)
in the traced span. The driver holds the pool while it ticks, so the
device idles for it."""
from readers import traced

LAYER = "driver, pool and scheduler (serving/driver.py, runtime.py, bcedge.py)"
MOVES = "tpot_p95_ms"


def read(ctx):
    red = traced(ctx)
    if red is None or "scheduler.tick" not in red["spans"]:
        return None
    n, total_s = red["spans"]["scheduler.tick"]
    return 1000.0 * total_s / n if n else None
