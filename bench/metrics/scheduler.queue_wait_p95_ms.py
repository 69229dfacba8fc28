"""95th percentile over the window's requests of the time from a
request's submission to its ``prefill`` event: the wait in the pool's
queue, under the driver and the scheduler (``serving/driver.py``,
``serving/runtime.py``, ``serving/bcedge.py``), before a slot takes it."""
import numpy as np

LAYER = "driver, pool and scheduler (serving/driver.py, runtime.py, bcedge.py)"
MOVES = "ttft_p95_ms"


def read(ctx):
    waits = [r["t_prefill"] - r["sent"] for r in ctx["records"]
             if r["phase"] == "window" and r["t_prefill"] is not None]
    if not waits:
        return None
    return 1000.0 * float(np.percentile(waits, 95))
