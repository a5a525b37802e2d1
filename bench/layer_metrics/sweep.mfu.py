from bench.shares import mfu_pct


def read(ctx):
    """Operations the sweep's maps required (counts_cmt.sweep_call: the
    cavity's ticks, Gram, solves, predictions) over the window's time at the
    bf16 peak."""
    return mfu_pct(ctx, ctx.record["calls"] * ctx.sweep_counts["required_ops"])
