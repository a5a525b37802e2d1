from bench.scopes import ms_per_call


def read(ctx):
    """Device self time of one sweep call in the test evaluation: the
    program's dfrc.eval scope (the cavity's test states, readout, NRMSE and
    SER), from a call traced after the window (bench/scopes.py)."""
    return ms_per_call(ctx, "dfrc.eval")
