"""The fit program's device time by ``dfrc.*`` scope, for the per-layer
metrics ``fit.solve_ms``, ``fit.eigh_ms``, ``fit.collect_ms`` and
``fit.eval_ms``.

The profiler trace names a device op by its HLO instruction alone; the
scope the instruction was traced in (``jax.named_scope``, see
``repro.pipeline.scopes``) is in the compiled program's HLO text, as its
``op_name`` metadata.  ``op_scopes`` reads that text into a map from
instruction name to innermost ``dfrc.*`` scope, the join key being the
name that ``trace.op_name`` takes from an op event.

The harness reduces and removes its trace of the window before the
readers run, so ``profile`` (once a run, kept on ``ctx``) traces one more
fit call after the window, on the first rotation, and splits that call's
device self time by scope (``reduce_scopes``).  It also puts each idle gap
of that call under the innermost program or bench span covering it
(``idle_by_span``).  Both are logged.  A program without
``Experiment.lowered`` has no scopes: nothing more is traced and the
readers report nothing.
"""

from __future__ import annotations

import collections
import json
import re
import shutil
import time

from bench import trace

PREFIX = "dfrc."
UNSCOPED = "unscoped"
BENCH_SPANS = ("fit.call",)
NO_SPAN = "no span"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_path: str, prefix: str = PREFIX) -> str | None:
    """The innermost component of an ``op_name`` path that starts with
    ``prefix``, with transform wrappers (``vmap(...)``, ``jit(...)``,
    ``transpose(jvp(...))``) taken off; None if there is none."""
    found = None
    for part in op_path.split("/"):
        while (m := _WRAPPER.match(part)) is not None:
            part = m.group(1)
        if part.startswith(prefix):
            found = part
    return found


def op_scopes(hlo_text: str, prefix: str = PREFIX) -> dict:
    """{instruction name: innermost ``prefix`` scope} of an HLO module's
    text (``compiled.as_text()``).

    A fusion counts under its fused root's scope, else its own, else the
    commonest scope inside it.  An instruction with no ``op_name`` at all
    was made by the compiler (the copies that move a value between memory
    spaces, say): it counts under the first of its users that has a scope,
    else under the commonest scope of its computation.  Instructions of
    the program outside every scope are left out."""
    own, comp_of, roots, calls = {}, {}, {}, {}
    users, inside = collections.defaultdict(list), collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        is_root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        # "" marks an op of the program in no scope; None, no op_name at all
        scope = (scope_of(op.group(1), prefix) or "") if op else None
        own[name], comp_of[name] = scope, comp
        if scope:
            inside[comp].append(scope)
        if is_root:
            roots[comp] = name
        if " fusion(" in rest and (c := _CALLS.search(rest)):
            calls[name] = c.group(1)
        for operand in _OPERAND.findall(rest.split("), ")[0]):
            users[operand].append(name)

    def commonest(comp):
        return collections.Counter(inside[comp]).most_common(1)[0][0] if inside[comp] else None

    memo = {}

    def resolve(name):
        if name in memo:
            return memo[name]
        memo[name] = None                      # a cycle resolves to nothing
        scope = own.get(name)
        if name in calls:
            root = roots.get(calls[name])
            scope = (resolve(root) if root else None) or scope
            scope = scope or commonest(calls[name])
        if scope is None:
            scope = next((s for u in users[name] if (s := resolve(u))), None)
            scope = scope or commonest(comp_of[name])
        memo[name] = scope
        return scope

    return {n: s for n in own if (s := resolve(n))}


def is_span(name: str) -> bool:
    """A program span (``dfrc.*``) or a bench span around its calls."""
    return name.startswith(PREFIX) or name in BENCH_SPANS


def _span_label(host):
    """t -> name of the innermost program or bench span covering ``t``."""
    spans = [h for h in host if is_span(h[0])]

    def label(t):
        cover = [(e - s, n) for n, s, e in spans if s <= t <= e]
        return min(cover)[1] if cover else NO_SPAN

    return label


def reduce_scopes(devices, host, scopes: dict, top: int = 10) -> dict:
    """Inside the ``trace.WINDOW_SPAN`` span (else the ops' extent): the
    seconds busy, the self time of the ops under each scope of ``scopes``
    (``unscoped`` for instructions not in it), the top ``unscoped`` ops,
    and the idle gaps under the innermost program or bench span
    (``idle_by_span``), each averaged over devices like ``trace.reduce``'s
    ``busy_s``."""
    spans = [(s, e) for name, s, e in host if name == trace.WINDOW_SPAN]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        every = [(s, e) for ops in devices for _, s, e in ops]
        lo = min((s for s, _ in every), default=0.0)
        hi = max((e for _, e in every), default=0.0)
    busy, per_scope, unscoped = 0.0, collections.Counter(), collections.Counter()
    idle = collections.Counter()
    label = _span_label(host)
    for ops in devices:
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        merged = trace.union([(s, e) for _, s, e in inside], lo, hi)
        busy += sum(e - s for s, e in merged) * 1e-9
        for name, secs in trace.self_times(inside):
            per_scope[scopes.get(name, UNSCOPED)] += secs
            if name not in scopes:
                unscoped[name] += secs
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle[label((s + e) / 2)] += (e - s) * 1e-9
    n_dev = max(len(devices), 1)
    per_scope[UNSCOPED] += 0.0
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n_dev,
        "scopes": {k: v / n_dev for k, v in per_scope.most_common()},
        "unscoped_ops": [[n, s / n_dev] for n, s in unscoped.most_common(top)],
        "idle_by_span": [[n, s / n_dev] for n, s in idle.most_common(top)],
    }


def _experiment(ctx):
    """The ``Experiment`` whose bound ``run`` the fit kind keeps, if it
    has ``lowered``."""
    exp = getattr(getattr(ctx, "run", None), "__self__", None)
    return exp if callable(getattr(exp, "lowered", None)) else None


def profile(ctx) -> dict | None:
    """``reduce_scopes`` of one traced fit call after the window, made once
    a run and kept on ``ctx``; None in an untraced run or where the
    program has no scopes."""
    if hasattr(ctx, "scope_summary"):
        return ctx.scope_summary
    ctx.scope_summary = None
    exp = _experiment(ctx)
    if not ctx.trace or exp is None or not getattr(ctx, "rotations", None):
        return None
    import jax
    from jax.profiler import ProfileData

    from bench.harness import ROOT, traced

    arrays = ctx.rotations[0][1]
    t0 = time.perf_counter()
    scopes = op_scopes(exp.lowered(*arrays).compile().as_text())
    t1 = time.perf_counter()
    trace_dir = ROOT / ".bench_trace" / f"{ctx.workload}.scopes"
    with traced(True, trace_dir):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation(BENCH_SPANS[0]):
                ctx.run(*arrays)
    t2 = time.perf_counter()
    path = trace.find_xplane(str(trace_dir))
    if path is not None:
        devices, host = trace.planes_of(ProfileData.from_file(path))
        if devices:
            ctx.scope_summary = reduce_scopes(devices, host, scopes)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.log(f"scopes: map {t1 - t0:.3f} s ({len(scopes)} instructions), traced "
            f"call {t2 - t1:.3f} s, reduce {time.perf_counter() - t2:.3f} s")
    if ctx.scope_summary is not None:
        ctx.log("scopes of one call: " + json.dumps(ctx.scope_summary))
    return ctx.scope_summary


def ms_per_call(ctx, *names: str) -> float | None:
    """Device self time of one fit call under the scopes ``names``, in ms;
    None where the call was not split by scope or holds none of them."""
    summary = profile(ctx)
    if summary is None:
        return None
    found = [summary["scopes"][s] for s in names if s in summary["scopes"]]
    return 1e3 * sum(found) if found else None
