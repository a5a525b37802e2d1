from bench.scopes import ms_per_call


def read(ctx):
    """Device self time of one sweep call in state collection: the
    program's dfrc.collect scope (the chunk scan with the cavity's dfr_scan
    and the Gram fold), less the solve nested in it, from a call traced
    after the window (bench/scopes.py)."""
    return ms_per_call(ctx, "dfrc.collect")
