from bench.shares import kernel_roofline_pct


def read(ctx):
    """dfr_scan's roofline share: least time of its traced calls at the
    fit's chunk shape over their trace time."""
    return kernel_roofline_pct(ctx, "dfr_scan", ctx.fit_counts["kernels"]["dfr_scan"])
