"""Names of the fit program's phases, as the profiler sees them.

Device scopes (``jax.named_scope``) reach the compiled HLO as the
``op_name`` metadata of every instruction traced inside them; an op
belongs to the innermost ``dfrc.*`` component of its ``op_name``, so the
solve called inside state collection counts as the solve, and the
``eigh`` inside the solve as the ``eigh``:

* ``dfrc.input``   -- normalisation, sample-and-hold and gain of the inputs;
* ``dfrc.collect`` -- reservoir states, their Gram fold and the noise
  diagonal: the streaming fits (``ridge.fit_ridge_streaming*``, around
  their jit, so the constants the compiler hoists to its boundary count
  too), ``fit_ridge_batched`` and the materialised path's states;
* ``dfrc.solve``   -- the GCV ridge solve (``ridge.solve_gcv``,
  ``solve_gcv_svd``, ``fit_ridge``);
* ``dfrc.eigh``    -- the eigendecomposition inside ``solve_gcv``;
* ``dfrc.eval``    -- the test evaluation and its metrics.

Inside ``dfrc.eigh`` the divide and conquer (``qdwh_eigh``) names its two
kinds of step, without the ``dfrc.`` prefix so that ``dfrc.eigh`` stays
their innermost phase and its time counts them both: ``eigh_split`` (the
QDWH splits) and ``eigh_leaf`` (the Jacobi leaves).  At F <= 256 on the
TPU the Rayleigh-Ritz refinement of XLA's Jacobi is ``eigh_refine``.

Host spans (``jax.profiler.TraceAnnotation``) mark ``Experiment.run`` on
the profiler's host clock: ``dfrc.prepare`` (canonicalise and transfer the
inputs), ``dfrc.dispatch`` (the jitted program's call) and ``dfrc.fetch``
(the device-to-host transfer of the results).  Inside ``dfrc.prepare``,
``dfrc.sweep_params`` checks a device sweep's per-lane parameters.  Without
an active trace each span is one no-op context.
"""

from __future__ import annotations

import functools

import jax

INPUT = "dfrc.input"
COLLECT = "dfrc.collect"
SOLVE = "dfrc.solve"
EIGH = "dfrc.eigh"
EVAL = "dfrc.eval"
DEVICE_SCOPES = (INPUT, COLLECT, SOLVE, EIGH, EVAL)
EIGH_SPLIT = "eigh_split"
EIGH_LEAF = "eigh_leaf"
EIGH_REFINE = "eigh_refine"

PREPARE = "dfrc.prepare"
DISPATCH = "dfrc.dispatch"
FETCH = "dfrc.fetch"
HOST_SPANS = (PREPARE, DISPATCH, FETCH)
SWEEP_PARAMS = "dfrc.sweep_params"


def scoped(name: str):
    """Decorator: trace the whole body of a function under ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
