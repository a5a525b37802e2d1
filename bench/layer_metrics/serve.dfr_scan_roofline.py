from bench.shares import kernel_roofline_pct


def read(ctx):
    """dfr_scan's roofline share over the whole slab (every slot is
    stepped), from its traced calls."""
    per_call = ctx.counts.serve_tick(**ctx.shape)["dfr_scan"]
    return kernel_roofline_pct(ctx, "dfr_scan", per_call)
