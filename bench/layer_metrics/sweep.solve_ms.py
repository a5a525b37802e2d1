from bench.scopes import ms_per_call


def read(ctx):
    """Device self time of one sweep call in the GCV readout solve: the
    program's dfrc.solve scope and the eigh inside it (dfrc.eigh, its
    Rayleigh-Ritz refinement included), from a call traced after the
    window (bench/scopes.py)."""
    return ms_per_call(ctx, "dfrc.solve", "dfrc.eigh")
