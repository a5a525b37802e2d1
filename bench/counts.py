"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of the roofline and mfu metrics: counts of what
the problem requires, not of what an implementation happens to execute.
Shapes are the logical ones (no lane, tile or feature padding), so a
share computed from them never exceeds what the chip could do.

Node update (Silicon MR map, paper Eq. 6-7, per virtual node, period and
lane): the masked input u = j*m (1), the drive a*(u + g*s_tau) (3), the
charge and discharge candidates (3), the comparison and the select (2).
"""

from __future__ import annotations

import math

NODE_OPS = 9


def dfr_scan(b: int, k: int, n: int, out_bytes: int = 4) -> dict:
    """One reservoir-scan call over ``b`` lanes, ``k`` periods, ``n`` nodes:
    read the inputs, the carry and the mask, write the states and the
    final carry."""
    return {"ops": NODE_OPS * b * k * n,
            "bytes": 4 * b * k + out_bytes * b * k * n + 2 * 4 * b * n + 4 * n}


def ridge_gram_into(b: int, t: int, f: int, c: int, x_bytes: int = 4) -> dict:
    """Fold one [b, t, f] chunk into running Gram [b, f, f] and moment
    [b, f, c] stacks: read the chunk and its targets once, read and write
    both stacks."""
    return {"ops": 2 * b * t * f * (f + c),
            "bytes": x_bytes * b * t * f + 4 * b * t * c + 2 * 4 * b * f * (f + c)}


def ridge_solve(f: int, c: int, n_lambdas: int) -> float:
    """One instance's ridge solves, one per lambda: a Cholesky factorisation
    of the F x F system and two triangular solves per output column."""
    return n_lambdas * (f ** 3 / 3 + 2 * f * f * c)


def fit_call(*, batch: int, t_train: int, t_test: int, n: int, c: int,
             washout: int, chunk: int, n_lambdas: int) -> dict:
    """One batched fit: what the problem requires, and each kernel's calls.

    Required: node updates over train and test, the Gram over the fit
    window, the solves, and the test predictions.
    """
    f = n + 1
    t_fit = t_train - washout
    required = (NODE_OPS * batch * (t_train + t_test) * n
                + 2 * batch * t_fit * f * (f + c)
                + batch * ridge_solve(f, c, n_lambdas)
                + 2 * batch * t_test * f * c)
    chunks_train = math.ceil(t_train / chunk)
    chunks_test = math.ceil(t_test / chunk)
    scan = dfr_scan(batch, chunk, n)
    gram = ridge_gram_into(batch, chunk, f, c)
    return {
        "required_ops": required,
        "kernels": {
            "dfr_scan": {"calls": chunks_train + chunks_test, **scan},
            "ridge_gram_into": {"calls": chunks_train, **gram},
        },
    }


def serve_tick(*, slots: int, chunk: int, n: int, c: int) -> dict:
    """One serving tick's kernel calls over the whole slab."""
    f = n + 1
    return {
        "dfr_scan": {"calls": 1, **dfr_scan(slots, chunk, n)},
        "ridge_gram_into": {"calls": 1, **ridge_gram_into(slots, chunk, f, c)},
    }


def serve_required(*, periods: int, fit_periods: int, solves: int, n: int,
                   c: int, n_lambdas: int) -> float:
    """What the served streams required: node updates and predictions for
    every valid period, the Gram fold for the periods past washout, and
    one solve per active stream on each refresh tick."""
    f = n + 1
    return (NODE_OPS * periods * n + 2 * periods * f * c
            + 2 * fit_periods * f * (f + c)
            + solves * ridge_solve(f, c, n_lambdas))


def roofline_seconds(ops: float, nbytes: float, peak: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
