"""The whole step's share of the chip's peak: the model FLOPs the traced
calls needed (decode and prefill, ``work.py``) over the device's busy
time at the bf16 peak. Float32 at the default TPU matmul precision is one
bf16 pass, so the bf16 peak is the rate it can reach. Counts every
device operation, whatever its name, so it still bounds a step whose
kernels a later change renames or replaces."""
from readers import step_mfu

LAYER = "model step (models/transformer.py)"
MOVES = "tpot_p95_ms"


def read(ctx):
    return step_mfu(ctx)
