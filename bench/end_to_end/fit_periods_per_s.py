def read(ctx):
    """Instance-periods (B x (T_train + T_test)) of every whole call in the
    window, over the window's wall time."""
    r = ctx.record
    return r["periods"] / r["window_s"] if r.get("calls") else None
