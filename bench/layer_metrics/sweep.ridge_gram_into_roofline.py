from bench.shares import kernel_roofline_pct


def read(ctx):
    """ridge_gram_into's roofline share in the sweep: least time of its
    traced calls at the sweep's chunk shape (counts_cmt.sweep_call) over
    their trace time."""
    return kernel_roofline_pct(ctx, "ridge_gram_into",
                               ctx.sweep_counts["kernels"]["ridge_gram_into"])
