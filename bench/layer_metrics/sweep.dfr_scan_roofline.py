from bench.shares import kernel_roofline_pct


def read(ctx):
    """The per-lane-parameter dfr_scan's roofline share: least time of its
    traced calls at the sweep's chunk shape (counts_cmt.dfr_scan, the
    parameter tiles included) over their trace time."""
    return kernel_roofline_pct(ctx, "dfr_scan", ctx.sweep_counts["kernels"]["dfr_scan"])
