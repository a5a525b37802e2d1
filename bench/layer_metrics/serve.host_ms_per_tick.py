import numpy as np


def read(ctx):
    """Mean over ticks of the benchmark's tick clock less that tick's
    DFRServer.tick_seconds entry (the device step)."""
    n = min(len(ctx.tick_clock), len(ctx.tick_seconds))
    if n == 0:
        return None
    return 1e3 * float(np.mean(ctx.tick_clock[:n] - ctx.tick_seconds[-n:]))
