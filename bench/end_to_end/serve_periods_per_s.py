def read(ctx):
    """Valid stream periods predicted and folded, over the window's wall
    time (host work included)."""
    r = ctx.record
    return r["periods"] / r["window_s"] if r.get("ticks") else None
