from bench.scopes import ms_per_call


def read(ctx):
    """Device self time of one fit call in the test evaluation: the
    program's dfrc.eval scope (test states, readout, NRMSE), from a call
    traced after the window (bench/scopes.py)."""
    return ms_per_call(ctx, "dfrc.eval")
