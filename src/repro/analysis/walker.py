"""Hardened jaxpr walker: intermediate-tensor accounting with provenance.

The streaming fused path (DESIGN.md §8) exists to keep the full [B, T, N]
state tensor out of HBM; these helpers make that property *checkable* by
walking a traced jaxpr and collecting the abstract value every equation
produces, together with the **path of enclosing primitives** that leads to
it (e.g. ``("scan", "pjit")`` = inside a jit called from a chunk-scan body).
The path is what turns "a [4, 256, 24] tensor exists" into "the scan body
re-materializes the stream" — and what lets `state_tensor_bytes` separate a
true state tensor from an unrelated array whose axis is numerically equal
to ``t_len`` (DESIGN.md §11).

Descent is exhaustive: any ``Jaxpr``/``ClosedJaxpr`` reachable through an
equation's params is entered, however it is nested (tuples of branches,
dicts, ``closed_call``/``custom_jvp_call``/``custom_vjp_call`` wrappers,
``while``/``cond``/``scan``/``pjit``/``remat`` bodies).  The pre-hardening
walker flattened only one tuple level and so went blind behind primitives
that stash their jaxpr deeper; `tests/test_analysis.py` pins the fixed
behaviour per primitive.

Equations inside a ``pallas_call`` body are skipped by default: a kernel's
jaxpr describes per-*block* VMEM compute, not HBM-resident arrays, and in
interpret mode it contains emulation loops that are not real scans.  The
`VmemBudget` rule (rules.py) is the one consumer that inspects kernel
internals, and it does so through the pallas eqn params, not this walk.
"""

from __future__ import annotations

import dataclasses

import jax

from jax.extend import core as jax_core


def _sub_jaxprs(params):
    """Yield every Jaxpr/ClosedJaxpr nested anywhere in an eqn's params.

    Recurses through tuples/lists/dicts so ``cond`` branches, paired
    ``while`` jaxprs, and any deeper container a primitive uses are all
    found — the old single-level flatten is the blind spot ISSUE 7 fixes.
    """
    def visit(value):
        if isinstance(value, jax_core.ClosedJaxpr):
            yield value.jaxpr
        elif isinstance(value, jax_core.Jaxpr):
            yield value
        elif isinstance(value, (tuple, list)):
            for leaf in value:
                yield from visit(leaf)
        elif isinstance(value, dict):
            for leaf in value.values():
                yield from visit(leaf)

    for value in params.values():
        yield from visit(value)


def walk_eqns_with_path(jaxpr, *, skip_pallas: bool = True, _path=()):
    """Depth-first ``(eqn, path)`` pairs over all equations, entering
    sub-jaxprs.  ``path`` is the tuple of enclosing primitive names, outermost
    first; top-level equations have ``path == ()``."""
    for eqn in jaxpr.eqns:
        yield eqn, _path
        if skip_pallas and eqn.primitive.name == "pallas_call":
            continue
        sub_path = _path + (eqn.primitive.name,)
        for sub in _sub_jaxprs(eqn.params):
            yield from walk_eqns_with_path(sub, skip_pallas=skip_pallas,
                                           _path=sub_path)


def walk_eqns(jaxpr, *, skip_pallas: bool = True):
    """Depth-first iterator over all equations, entering sub-jaxprs."""
    for eqn, _ in walk_eqns_with_path(jaxpr, skip_pallas=skip_pallas):
        yield eqn


def trace_jaxpr(fn, *args, **kwargs):
    """ClosedJaxpr of ``fn(*args, **kwargs)`` (no execution)."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


@dataclasses.dataclass(frozen=True)
class Intermediate:
    """One array named by the traced program, with provenance."""

    shape: tuple
    dtype: str
    nbytes: int
    prim: str               # primitive of the producing equation
    path: tuple             # enclosing primitive names, outermost first

    @property
    def elems(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n

    def where(self) -> str:
        return "/".join(self.path + (self.prim,))


def intermediate_records(closed_jaxpr) -> list:
    """Every `Intermediate` produced by equations in the program.

    Covers every intermediate array the traced computation names —
    sub-jaxpr (scan body, pjit, cond branch, custom-derivative wrapper)
    outputs included, pallas kernel-internal VMEM blocks excluded.
    """
    out = []
    for eqn, path in walk_eqns_with_path(closed_jaxpr.jaxpr):
        for var in eqn.outvars:
            aval = var.aval
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                nbytes = int(aval.size) * aval.dtype.itemsize
                out.append(Intermediate(tuple(aval.shape), str(aval.dtype),
                                        nbytes, eqn.primitive.name, path))
    return out


def intermediate_shapes(closed_jaxpr) -> list:
    """All (shape, nbytes) pairs produced by equations in the program."""
    return [(r.shape, r.nbytes) for r in intermediate_records(closed_jaxpr)]


def max_intermediate_bytes(closed_jaxpr) -> int:
    """Largest single intermediate array in the program, in bytes."""
    return max((r.nbytes for r in intermediate_records(closed_jaxpr)),
               default=0)


def _dims_match_template(shape, template) -> bool:
    """True if ``shape``'s dims are a permutation of ``template``'s."""
    return sorted(int(d) for d in shape) == sorted(int(d) for d in template)


def state_tensor_records(closed_jaxpr, t_len: int, min_elems: int, *,
                         benign_shapes=()) -> list:
    """All "state-like" intermediates: carry the stream axis (a dim ==
    ``t_len``) at state-tensor scale (>= ``min_elems`` elements), and match
    none of the ``benign_shapes`` templates.

    ``benign_shapes`` disambiguates the collision case where an *unrelated*
    axis is numerically equal to ``t_len`` — e.g. a [B, Fq, Fq] Gram when
    the padded feature count Fq happens to equal the chunk length, or a
    padded batch equal to K.  Each template is a dim multiset (order
    ignored): an intermediate whose dims are a permutation of a template is
    exempt.  Templates name *structurally known* blocks (Gram [B, Fq, Fq],
    chunk state [B, chunk_padded, Fq], ...) — a genuine [B, t_len, N] state
    tensor matches none of them and is still flagged.  The returned records
    carry provenance (`Intermediate.where()`) so a report shows *where* the
    offending tensor lives.
    """
    out = []
    for rec in intermediate_records(closed_jaxpr):
        if t_len not in rec.shape or rec.elems < min_elems:
            continue
        if any(_dims_match_template(rec.shape, t) for t in benign_shapes):
            continue
        out.append(rec)
    return out


def state_tensor_bytes(closed_jaxpr, t_len: int, min_elems: int, *,
                       benign_shapes=()) -> int:
    """Largest "state-like" intermediate, in bytes (0 = property holds).

    The element floor is what separates a state tensor from the O(B·T)
    input/target streams that legitimately carry the T axis: pass
    ``B·t_len·N`` (full-stream check; 0 == the streaming property holds) or
    ``B·chunk·N`` with ``t_len=chunk`` (the streamed path's peak live state
    block — lane/feature padding of the kernel layouts is included in the
    measured tensor, so compare against a padded budget).  See
    `state_tensor_records` for ``benign_shapes``.
    """
    return max((r.nbytes for r in state_tensor_records(
        closed_jaxpr, t_len, min_elems, benign_shapes=benign_shapes)),
        default=0)


def eqn_paths(closed_jaxpr, prim_name: str) -> list:
    """Provenance paths (incl. the primitive itself) of every ``prim_name``
    equation in the program — pallas kernel bodies excluded."""
    return [path + (prim_name,)
            for eqn, path in walk_eqns_with_path(closed_jaxpr.jaxpr)
            if eqn.primitive.name == prim_name]


def count_scans(closed_jaxpr) -> int:
    """Number of ``lax.scan`` equations (pallas kernel bodies excluded)."""
    return len(eqn_paths(closed_jaxpr, "scan"))


def count_pallas_calls(closed_jaxpr) -> int:
    """Number of ``pallas_call`` equations anywhere in the program.

    The WDM streaming guard uses this to pin the per-lane-mask claim
    (DESIGN.md §9): all R wavelength channels run as ONE dfr_scan launch
    plus ONE accumulate-into Gram launch per chunk-scan body — a program
    that vmapped ``pallas_call`` per channel would show R× the count.
    """
    return len(eqn_paths(closed_jaxpr, "pallas_call"))


def pallas_eqns(closed_jaxpr) -> list:
    """All ``(eqn, path)`` pairs for pallas_call equations in the program."""
    return [(eqn, path)
            for eqn, path in walk_eqns_with_path(closed_jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call"]
