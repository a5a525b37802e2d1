from bench.shares import mfu_pct


def read(ctx):
    """Operations the served streams required (counts.serve_required) over
    the window's time at the bf16 peak."""
    r = ctx.record
    ops = ctx.counts.serve_required(
        periods=r["periods"], fit_periods=r["fit_periods"],
        solves=r["refresh_solves"], n=ctx.shape["n"], c=ctx.shape["c"],
        n_lambdas=len(ctx.config["ridge_l2"]))
    return mfu_pct(ctx, ops)
