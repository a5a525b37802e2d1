def read(ctx):
    """Process start to the start of the measured window."""
    return ctx.setup_s
