from bench.shares import kernel_roofline_pct


def read(ctx):
    """ridge_gram_into's roofline share over the whole slab, from its
    traced calls."""
    per_call = ctx.counts.serve_tick(**ctx.shape)["ridge_gram_into"]
    return kernel_roofline_pct(ctx, "ridge_gram_into", per_call)
