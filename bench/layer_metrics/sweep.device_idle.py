from bench.shares import idle_pct


def read(ctx):
    """1 - device busy / window, from the profiler trace."""
    return idle_pct(ctx)
