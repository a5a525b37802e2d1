from bench.shares import kernel_roofline_pct


def read(ctx):
    """ridge_gram_into's roofline share: least time of its traced calls at
    the fit's chunk shape over their trace time."""
    return kernel_roofline_pct(ctx, "ridge_gram_into",
                               ctx.fit_counts["kernels"]["ridge_gram_into"])
