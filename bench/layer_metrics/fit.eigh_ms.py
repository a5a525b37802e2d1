from bench.scopes import ms_per_call


def read(ctx):
    """Device self time of one fit call in the solve's eigendecomposition:
    the program's dfrc.eigh scope, from a call traced after the window
    (bench/scopes.py)."""
    return ms_per_call(ctx, "dfrc.eigh")
