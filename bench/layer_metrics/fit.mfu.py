from bench.shares import mfu_pct


def read(ctx):
    """Operations the fits required (counts.fit_call) over the window's
    time at the bf16 peak."""
    return mfu_pct(ctx, ctx.record["calls"] * ctx.fit_counts["required_ops"])
