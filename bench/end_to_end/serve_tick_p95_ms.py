from bench.shares import percentile


def read(ctx):
    """95th percentile of the whole-tick wall time over every tick of the
    window, by the benchmark's clock around DFRServer.step()."""
    p = percentile(ctx.tick_clock, 95)
    return None if p is None else 1e3 * p
