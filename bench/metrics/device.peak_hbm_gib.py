"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
read once the window has drained."""
LAYER = "device"
MOVES = "throughput_tok_s"


def read(ctx):
    b = ctx.get("memory_peak_bytes") or 0
    return b / 2 ** 30 if b > 0 else None
