from bench.shares import percentile


def read(ctx):
    """95th percentile of DFRServer.tick_seconds (the session step up to
    block_until_ready) over the window's ticks."""
    p = percentile(ctx.tick_seconds, 95)
    return None if p is None else 1e3 * p
