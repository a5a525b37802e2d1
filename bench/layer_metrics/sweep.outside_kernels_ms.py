def read(ctx):
    """Device busy time per sweep call outside dfr_scan and ridge_gram_into:
    the Gram's padding, the GCV solve and its eigh, layout copies and the
    test evaluation's readout."""
    ts = ctx.trace_summary
    if not ts or ts["busy_s"] <= 0 or not ctx.record.get("calls"):
        return None
    kernels = sum(k["seconds"] for k in ts["kernels"].values())
    return 1e3 * (ts["busy_s"] - kernels) / ctx.record["calls"]
