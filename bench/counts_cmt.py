"""Operations and bytes a CMT cavity sweep needs, computed from shapes.

The yardstick of ``sweep.mfu`` and ``sweep.dfr_scan_roofline``, counted
from the cavity's equations (``bench/reference_cmt.py``; DESIGN.md section
14), not from any implementation.  Logical shapes: no lane padding.

Per virtual-node tick and lane, before the substeps (``TICK_OPS``): the
masked input u = j m (1), the drive max(u + gamma s_tau, 0) (3), the branch
u > s_left and the two selects of kappa and r_lin (3), r_lin = loss / tau_L
(1), E = max(s_left, 0) (1), and the closure pw E, its square, fc_gain
times it and th_gain pw E (4).  Per substep (``SUBSTEP_OPS``): delta = d -
fcd N + th_shift T (4), the Lorentzian 1 / (1 + delta^2) (3), r = r_lin +
tpa pw E + fca N (5), x = r dt (1), e^(-x) (1), phi1 = (1 - e^(-x)) / x
(2), E e^(-x) + kappa L P dt phi1 (6), N's relaxation to fc_gain (pw E)^2
(6) and T's to th_gain pw E (4, pw E shared).
"""

from __future__ import annotations

import math

from bench import counts

TICK_OPS = 13
SUBSTEP_OPS = 32
PARAM_LEAVES = 3


def node_ops(n_substeps: int) -> int:
    """Operations of one node's tick with ``n_substeps`` substeps."""
    return TICK_OPS + SUBSTEP_OPS * n_substeps


def dfr_scan(b: int, k: int, n: int, n_substeps: int) -> dict:
    """One per-lane-parameter reservoir-scan call over ``b`` lanes, ``k``
    periods, ``n`` nodes: the Silicon MR scan's inputs, carry, mask and
    states (``counts.dfr_scan``), each lane's parameters read once, and
    the cavity's tick."""
    base = counts.dfr_scan(b, k, n)
    return {"ops": node_ops(n_substeps) * b * k * n,
            "bytes": base["bytes"] + 4 * PARAM_LEAVES * b}


def sweep_call(*, lanes: int, t_train: int, t_test: int, n: int, washout: int,
               chunk: int, n_lambdas: int, n_substeps: int) -> dict:
    """One sweep call: what the map requires, and each kernel's calls.

    Required: the cavity's ticks over train and test, the Gram over the fit
    window, the solves and the test predictions, for every lane.
    """
    f, c = n + 1, 1
    t_fit = t_train - washout
    required = (node_ops(n_substeps) * lanes * (t_train + t_test) * n
                + 2 * lanes * t_fit * f * (f + c)
                + lanes * counts.ridge_solve(f, c, n_lambdas)
                + 2 * lanes * t_test * f * c)
    chunks_train = math.ceil(t_train / chunk)
    chunks_test = math.ceil(t_test / chunk)
    return {
        "required_ops": required,
        "kernels": {
            "dfr_scan": {"calls": chunks_train + chunks_test,
                         **dfr_scan(lanes, chunk, n, n_substeps)},
            "ridge_gram_into": {"calls": chunks_train,
                                **counts.ridge_gram_into(lanes, chunk, f, c)},
        },
    }
