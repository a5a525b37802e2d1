"""Declarative program contracts over traced jaxprs (DESIGN.md §11).

A `Program` wraps one entry point — a callable over array-only positional
arguments — and lazily produces the artifacts the rules inspect: the traced
``ClosedJaxpr``, the flat `Intermediate` records with provenance, and (for
donation checks) the lowered StableHLO text.  A `Rule` looks at a Program
and returns `Violation`s; an empty list means the contract holds.  Rules
never execute the program: everything is static, which is what makes the
checks trustworthy on the CPU/interpret-mode dev loop — they pin properties
of the *lowered program*, not of one backend's runtime behaviour.

The built-in catalog covers the repo's load-bearing claims:

- `NoStateTensor`   — the streaming paths never materialize [B, T, N]
- `MaxScans` / `MaxPallasCalls` — one chunk scan, one launch pair per chunk
- `NoDtypeAbove`    — no accidental f64 promotion in a hot path
- `NoSilentUpcast`  — bf16 chunk paths don't re-materialize f32 chunks
- `DonationHonored` — donate_argnums / input_output_aliases survive lowering
- `NoHostCallback`  — no host round-trips inside jitted hot paths
- `VmemBudget`      — per-pallas_call VMEM estimate + tile-alignment check
"""

from __future__ import annotations

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT_BYTES

from .walker import (Intermediate, count_pallas_calls, count_scans, eqn_paths,
                     intermediate_records, pallas_eqns, state_tensor_records,
                     trace_jaxpr, walk_eqns_with_path)

# The scoped-VMEM limit both kernels compile with (repro.kernels); override
# per rule.
VMEM_BYTES = VMEM_LIMIT_BYTES

# Primitives that round-trip through the host from inside a jitted program.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "outside_call",
})


@dataclasses.dataclass(frozen=True)
class Violation:
    """One broken contract, with enough provenance to find the culprit."""

    rule: str
    message: str
    path: tuple = ()            # enclosing primitive names, outermost first
    shape: tuple = None
    dtype: str = None

    def as_dict(self) -> dict:
        d = {"rule": self.rule, "message": self.message,
             "path": list(self.path)}
        if self.shape is not None:
            d["shape"] = [int(s) for s in self.shape]
        if self.dtype is not None:
            d["dtype"] = self.dtype
        return d

    def __str__(self) -> str:
        where = "/".join(self.path) or "<top>"
        return f"[{self.rule}] {self.message} (at {where})"


def _rec_violation(rule: str, message: str, rec: Intermediate) -> Violation:
    return Violation(rule=rule, message=message, path=rec.path + (rec.prim,),
                     shape=rec.shape, dtype=rec.dtype)


class Program:
    """One analyzable entry point: a callable + example (array) arguments.

    ``fn`` must take array-only positional arguments — registry builders
    close over static configuration (configs, masks, flags) so the traced
    signature is purely arrays.  ``donate_argnums`` mirrors how the serving /
    training code jits the same callable; `DonationHonored` lowers with it
    and checks the aliasing actually survives into StableHLO.
    """

    def __init__(self, fn, args, *, donate_argnums=(), name: str = ""):
        self.fn = fn
        self.args = tuple(args)
        self.donate_argnums = tuple(donate_argnums)
        self.name = name
        self._closed_jaxpr = None
        self._records = None
        self._lowered_text = None

    @property
    def closed_jaxpr(self):
        if self._closed_jaxpr is None:
            self._closed_jaxpr = trace_jaxpr(self.fn, *self.args)
        return self._closed_jaxpr

    @property
    def records(self) -> list:
        if self._records is None:
            self._records = intermediate_records(self.closed_jaxpr)
        return self._records

    @property
    def lowered_text(self) -> str:
        """StableHLO of ``jit(fn, donate_argnums=...)`` — donation metadata
        (``tf.aliasing_output`` argument attributes) is only visible here,
        never in the jaxpr.  ``keep_unused=True``: jit otherwise prunes
        donated-but-unused leaves (e.g. a SessionState field the refresh
        path recomputes) from the lowered signature, which would make the
        aliasing count undercount legitimately-donated buffers."""
        if self._lowered_text is None:
            jitted = jax.jit(self.fn, donate_argnums=self.donate_argnums,
                             keep_unused=True)
            self._lowered_text = jitted.lower(*self.args).as_text()
        return self._lowered_text

    def donated_leaf_count(self) -> int:
        return sum(len(jax.tree_util.tree_leaves(self.args[i]))
                   for i in self.donate_argnums)


class Rule:
    """Base contract: ``check(program)`` returns a list of `Violation`s."""

    name = "Rule"

    def check(self, program: Program) -> list:
        raise NotImplementedError

    def describe(self) -> str:
        return self.name


class NoStateTensor(Rule):
    """No intermediate carries the stream axis at state-tensor scale.

    ``t_len`` is the stream length; ``min_elems`` the element floor that
    separates a state tensor from the O(B·T) input streams; ``benign_shapes``
    are dim-multiset templates for structurally-known blocks whose axes
    *happen* to equal ``t_len`` (walker.state_tensor_records).  ``max_bytes``
    turns the rule from "must not exist" (0, the default) into a budget —
    used for the peak live chunk block of streamed programs.
    """

    name = "NoStateTensor"

    def __init__(self, t_len: int, min_elems: int, *, benign_shapes=(),
                 max_bytes: int = 0, what: str = "state tensor"):
        self.t_len = int(t_len)
        self.min_elems = int(min_elems)
        self.benign_shapes = tuple(tuple(s) for s in benign_shapes)
        self.max_bytes = int(max_bytes)
        self.what = what

    def describe(self) -> str:
        bound = f"<= {self.max_bytes}B" if self.max_bytes else "none"
        return (f"{self.name}(t_len={self.t_len}, "
                f"min_elems={self.min_elems}, {bound})")

    def check(self, program: Program) -> list:
        out = []
        for rec in state_tensor_records(program.closed_jaxpr, self.t_len,
                                        self.min_elems,
                                        benign_shapes=self.benign_shapes):
            if rec.nbytes > self.max_bytes:
                out.append(_rec_violation(
                    self.name,
                    f"{self.what} {rec.shape} {rec.dtype} = {rec.nbytes}B "
                    f"carries the t_len={self.t_len} axis above "
                    f"{self.max_bytes}B", rec))
        return out


class _MaxPrim(Rule):
    prim = ""

    def __init__(self, limit: int):
        self.limit = int(limit)

    def describe(self) -> str:
        return f"{self.name}({self.limit})"

    def check(self, program: Program) -> list:
        paths = eqn_paths(program.closed_jaxpr, self.prim)
        if len(paths) <= self.limit:
            return []
        listing = ", ".join("/".join(p) for p in paths)
        return [Violation(self.name,
                          f"{len(paths)} {self.prim} eqns > limit "
                          f"{self.limit}: {listing}")]


class MaxScans(_MaxPrim):
    """At most N ``lax.scan`` equations (the streaming paths pin ONE)."""

    name = "MaxScans"
    prim = "scan"


class MaxPallasCalls(_MaxPrim):
    """At most N ``pallas_call`` launches (DESIGN.md §9: one dfr_scan + one
    Gram launch per program, no per-channel or per-chunk fan-out)."""

    name = "MaxPallasCalls"
    prim = "pallas_call"


class NoDtypeAbove(Rule):
    """No floating/complex intermediate wider than ``limit`` — catches the
    accidental f64 promotion an x64-enabled host or stray float64 literal
    drags into a hot path."""

    name = "NoDtypeAbove"

    def __init__(self, limit="float32"):
        self.limit = jnp.dtype(limit)

    def describe(self) -> str:
        return f"{self.name}({self.limit.name})"

    def check(self, program: Program) -> list:
        out = []
        for rec in program.records:
            try:
                dt = jnp.dtype(rec.dtype)
            except TypeError:
                # extended dtypes (e.g. the PRNG ``key<fry>`` of a traced
                # fault-injection seed) are opaque integer data, never a
                # float-width promotion — out of scope for this rule
                continue
            if (jnp.issubdtype(dt, jnp.inexact)
                    and dt.itemsize > self.limit.itemsize):
                out.append(_rec_violation(
                    self.name, f"{rec.dtype} intermediate {rec.shape} wider "
                    f"than {self.limit.name}", rec))
        return out


class NoSilentUpcast(Rule):
    """A bf16-chunk program must not re-materialize >= f32 arrays at chunk
    scale: the HBM-traffic halving (DESIGN.md §9) is void if a wide copy of
    each chunk exists anyway.  Same shape grammar as `NoStateTensor`, but
    filtering on *wide* dtypes only."""

    name = "NoSilentUpcast"

    def __init__(self, chunk_len: int, min_elems: int, *, benign_shapes=(),
                 wide="float32"):
        self.chunk_len = int(chunk_len)
        self.min_elems = int(min_elems)
        self.benign_shapes = tuple(tuple(s) for s in benign_shapes)
        self.wide = jnp.dtype(wide)

    def describe(self) -> str:
        return (f"{self.name}(chunk_len={self.chunk_len}, "
                f"min_elems={self.min_elems}, wide>={self.wide.name})")

    def check(self, program: Program) -> list:
        out = []
        for rec in state_tensor_records(program.closed_jaxpr, self.chunk_len,
                                        self.min_elems,
                                        benign_shapes=self.benign_shapes):
            dt = jnp.dtype(rec.dtype)
            if (jnp.issubdtype(dt, jnp.floating)
                    and dt.itemsize >= self.wide.itemsize):
                out.append(_rec_violation(
                    self.name, f"chunk-scale {rec.dtype} block {rec.shape} "
                    f"in a narrow-chunk program", rec))
        return out


class NoHostCallback(Rule):
    """No host-callback primitives (pure/io/debug callbacks) inside the
    program — a serving or training hot path must never round-trip through
    Python per step."""

    name = "NoHostCallback"

    def check(self, program: Program) -> list:
        out = []
        for eqn, path in walk_eqns_with_path(program.closed_jaxpr.jaxpr):
            if eqn.primitive.name in CALLBACK_PRIMS:
                out.append(Violation(
                    self.name, f"host callback `{eqn.primitive.name}` in "
                    f"jitted program", path=path + (eqn.primitive.name,)))
        return out


class DonationHonored(Rule):
    """Declared aliasing survives into the lowered program.

    Two layers: (a) if the Program declares ``donate_argnums``, every donated
    leaf must appear as a ``tf.aliasing_output`` argument attribute in the
    StableHLO — XLA silently drops donation on shape/dtype mismatch, which
    would double the serving slab's footprint without failing any test;
    (b) ``min_pallas_aliases`` pins pallas-level ``input_output_aliases``
    pairs (the accumulate-into Gram kernels), which a refactor could drop by
    calling the non-aliased kernel variant.
    """

    name = "DonationHonored"

    def __init__(self, *, min_donated: int = None, min_pallas_aliases: int = 0):
        self.min_donated = min_donated
        self.min_pallas_aliases = int(min_pallas_aliases)

    def describe(self) -> str:
        return (f"{self.name}(donated>={self.min_donated}, "
                f"pallas_aliases>={self.min_pallas_aliases})")

    def check(self, program: Program) -> list:
        out = []
        if program.donate_argnums or self.min_donated is not None:
            expect = (self.min_donated if self.min_donated is not None
                      else program.donated_leaf_count())
            got = program.lowered_text.count("tf.aliasing_output")
            if got < expect:
                out.append(Violation(
                    self.name, f"{got} aliased buffers in lowered program, "
                    f"expected >= {expect} (donate_argnums="
                    f"{program.donate_argnums})"))
        if self.min_pallas_aliases:
            got = sum(len(tuple(eqn.params.get("input_output_aliases") or ()))
                      for eqn, _ in pallas_eqns(program.closed_jaxpr))
            if got < self.min_pallas_aliases:
                out.append(Violation(
                    self.name, f"{got} pallas input_output_aliases pairs, "
                    f"expected >= {self.min_pallas_aliases} (accumulate-into "
                    f"kernel dropped?)"))
        return out


class VmemBudget(Rule):
    """Every ``pallas_call`` fits in VMEM and its blocks are tile-aligned.

    The VMEM estimate is static, from the kernel's own refs: in/out blocks
    are counted twice (Mosaic double-buffers the grid pipeline) plus scratch
    once.  The alignment check generalizes the guard ``dfr_scan`` enforces
    for its own blocks (dfr_scan.py): a *multi-tile* block of a sub-f32
    dtype must start on a (min_sublanes(dtype), 128) boundary — interpret
    mode happily computes misaligned blocks that real Mosaic rejects, so
    this is exactly the class of bug that survives CPU-only CI.  Single-tile
    blocks (block spans the whole axis) are exempt; f32 sublane layout is
    left to Mosaic relayout, matching the kernel's own policy.
    """

    name = "VmemBudget"

    def __init__(self, limit_bytes: int = VMEM_BYTES, *,
                 check_alignment: bool = True):
        self.limit_bytes = int(limit_bytes)
        self.check_alignment = check_alignment

    def describe(self) -> str:
        return f"{self.name}({self.limit_bytes}B)"

    @staticmethod
    def estimate_bytes(eqn) -> int:
        """Static VMEM footprint of one pallas_call eqn: 2× each in/out
        block (double buffering) + scratch."""
        gm = eqn.params["grid_mapping"]
        refs = list(eqn.params["jaxpr"].invars)
        n_idx = getattr(gm, "num_index_operands", 0)
        n_scratch = getattr(gm, "num_scratch_operands", 0)
        body = refs[n_idx:len(refs) - n_scratch]
        scratch = refs[len(refs) - n_scratch:] if n_scratch else []

        def ref_bytes(var):
            aval = var.aval
            size = 1
            for d in aval.shape:
                size *= int(d)
            return size * jnp.dtype(aval.dtype).itemsize

        return (2 * sum(ref_bytes(v) for v in body)
                + sum(ref_bytes(v) for v in scratch))

    @staticmethod
    def _block_dim(d) -> int:
        """One BlockSpec dim as an int: ``Blocked``/``Element`` dims carry
        ``block_size``; a squeezed (``None``/``Squeezed``) dim is 1."""
        size = getattr(d, "block_size", d)
        return size if isinstance(size, int) else 1

    @staticmethod
    def _aligned(block_shape, full_shape, dtype):
        """None if OK, else a human-readable misalignment description."""
        from repro.kernels.dfr_scan import min_sublanes
        if len(block_shape) < 2 or len(full_shape) < len(block_shape):
            return None
        full = full_shape[len(full_shape) - len(block_shape):]
        b_lane, f_lane = int(block_shape[-1]), int(full[-1])
        if b_lane < f_lane and b_lane % 128:
            return f"lane dim {b_lane} of multi-tile block not 128-aligned"
        b_sub, f_sub = int(block_shape[-2]), int(full[-2])
        dt = jnp.dtype(dtype)
        min_sub = min_sublanes(dt)
        if b_sub < f_sub and dt.itemsize < 4 and b_sub % min_sub:
            return (f"sublane dim {b_sub} of multi-tile {dt.name} block not "
                    f"a multiple of {min_sub}")
        return None

    def check(self, program: Program) -> list:
        out = []
        for eqn, path in pallas_eqns(program.closed_jaxpr):
            kname = eqn.params.get("name") or "<unnamed>"
            est = self.estimate_bytes(eqn)
            if est > self.limit_bytes:
                out.append(Violation(
                    self.name, f"pallas_call `{kname}` needs ~{est}B VMEM "
                    f"> budget {self.limit_bytes}B",
                    path=path + ("pallas_call",)))
            if not self.check_alignment:
                continue
            gm = eqn.params["grid_mapping"]
            fulls = [jax.ShapeDtypeStruct(s.shape, s.dtype)
                     for s in tuple(gm.in_shapes) + tuple(gm.out_shapes)]
            blocks = [tuple(self._block_dim(d) for d in bm.block_shape)
                      for bm in gm.block_mappings]
            for block, full in zip(blocks, fulls):
                msg = self._aligned(block, full.shape, full.dtype)
                if msg:
                    out.append(Violation(
                        self.name, f"pallas_call `{kname}` block {block} of "
                        f"{tuple(full.shape)}: {msg}",
                        path=path + ("pallas_call",),
                        shape=block, dtype=jnp.dtype(full.dtype).name))
        return out


_MOSAIC_CALL = re.compile(
    r'%([A-Za-z_][\w-]*?)(?:\.\d+)? = .*custom_call_target="tpu_custom_call"')


def mosaic_kernels(compiled_text: str) -> collections.Counter:
    """Kernels Mosaic compiled into a TPU executable, by ``pallas_call``
    name, from ``jax.jit(f).lower(...).compile().as_text()``.

    Each compiled kernel is one ``tpu_custom_call`` instruction named after
    its ``pallas_call``.  A kernel run in interpret mode lowers to plain HLO
    and leaves no such instruction, so a program whose kernels were quietly
    interpreted counts none here.
    """
    return collections.Counter(_MOSAIC_CALL.findall(compiled_text))


def check_rules(program: Program, rules) -> list:
    """Evaluate ``rules`` against ``program``; flat list of violations."""
    out = []
    for rule in rules:
        out.extend(rule.check(program))
    return out


__all__ = [
    "CALLBACK_PRIMS", "VMEM_BYTES", "Violation", "Program", "Rule",
    "NoStateTensor", "MaxScans", "MaxPallasCalls", "NoDtypeAbove",
    "NoSilentUpcast", "NoHostCallback", "DonationHonored", "VmemBudget",
    "check_rules", "count_scans", "count_pallas_calls", "mosaic_kernels",
]
