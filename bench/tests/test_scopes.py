"""The device-time split by the program's dfrc.* scopes (bench/scopes.py):
the HLO scope map, the reduction of a trace by it, and the readers'
behaviour on a program with no scopes."""

import re
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, scopes, trace

ROOT = Path(__file__).resolve().parents[2]


def test_scope_of_takes_the_innermost_scope_through_transforms():
    assert scopes.scope_of("jit(f)/vmap(dfrc.solve)/eigh/jit(eigh)/eigh") == "dfrc.solve"
    assert scopes.scope_of("jit(f)/dfrc.collect/checkpoint/transpose(jvp(dfrc.eigh))/dot"
                          ) == "dfrc.eigh"
    assert scopes.scope_of("jit(f)/jit(dfrc.eval)/while/body") == "dfrc.eval"
    assert scopes.scope_of("jit(f)/jit(fit)/while") is None


def test_op_scopes_of_a_compiled_program():
    """Nested, vmapped and inner-jit scopes: each instruction goes to the
    innermost dfrc.* scope it was traced in."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(z):
        with jax.named_scope("dfrc.jitted"):
            return jnp.cumsum(jnp.tanh(z) * 3.0, axis=0)

    def sym(v):
        with jax.named_scope("dfrc.inner"):
            return jnp.linalg.eigh(v @ v.T + 1.0)[0]

    def f(x, y):
        with jax.named_scope("dfrc.outer"):
            a = jnp.matmul(jnp.sin(x), y)
            b = jax.vmap(sym)(jnp.stack([a, a * 2.0]))
            c = inner(a + b[0, 0])
        return a, b, c

    x = jnp.ones((8, 8), jnp.float32)
    text = jax.jit(f).lower(x, x).compile().as_text()
    got = scopes.op_scopes(text)
    assert set(got.values()) == {"dfrc.outer", "dfrc.inner", "dfrc.jitted"}
    kind = {}
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m and m.group(2) in got:
            for op in ("custom-call(", "dot(", "cumsum"):
                if op in m.group(3):
                    kind.setdefault(op, set()).add(got[m.group(2)])
    assert kind["custom-call("] == {"dfrc.inner"}      # the eigh
    assert "dfrc.outer" in kind["dot("]


HLO = """HloModule m

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/dfrc.collect/dfrc.solve/mul"}
}

%fused_b (q: f32[4]) -> (f32[4], f32[4]) {
  %q = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%q), metadata={op_name="jit(f)/vmap(dfrc.eval)/neg"}
  %abs.1 = f32[4]{0} abs(%q), metadata={op_name="jit(f)/vmap(dfrc.eval)/abs"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%neg.1, %abs.1)
}

%body (t: (f32[4])) -> (f32[4]) {
  %t = (f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element(%t), index=0
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%get-tuple-element.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %exp.1 = f32[4]{0} exponential(%get-tuple-element.1), metadata={op_name="jit(f)/dfrc.eigh/exp"}
  ROOT %tuple.2 = (f32[4]{0}) tuple(%copy-done.1)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), calls=%fused_a, metadata={op_name="jit(f)/dfrc.input/mul"}
  %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_b
  %copy.1 = f32[4]{0} copy(%fusion.1)
  %sub.1 = f32[4]{0} subtract(%x, %x), metadata={op_name="jit(f)/jit(g)/sub"}
  ROOT %add.1 = f32[4]{0} add(%copy.1, %sub.1), metadata={op_name="jit(f)/dfrc.input/add"}
}
"""


def test_op_scopes_fusion_root_and_compiler_made_ops():
    got = scopes.op_scopes(HLO)
    assert got["fusion.1"] == "dfrc.solve"      # the root's scope, not its own
    assert got["fusion.2"] == "dfrc.eval"       # tuple root: the scope inside
    assert got["add.1"] == "dfrc.input"
    assert got["copy.1"] == "dfrc.input"        # no op_name: its user's scope
    # no op_name and no scoped user: the commonest scope of its computation
    assert got["copy-done.1"] == got["copy-start.1"] == "dfrc.eigh"
    assert "sub.1" not in got                   # the program's, in no scope


def _scoped_run():
    ns = 1e9
    device = [("while.1", 0.1 * ns, 0.5 * ns),      # collect, holding
              ("custom-call.4", 0.2 * ns, 0.3 * ns),  # the eigh
              ("fusion.9", 0.3 * ns, 0.4 * ns),       # the solve
              ("copy.2", 0.55 * ns, 0.6 * ns),        # in no scope
              ("fusion.3", 0.7 * ns, 0.8 * ns),       # eval
              ("late", 1.5 * ns, 1.6 * ns)]           # outside the window
    host = [(trace.WINDOW_SPAN, 0.0, 1.0 * ns),
            ("fit.call", 0.0, 0.85 * ns),
            ("dfrc.prepare", 0.0, 0.08 * ns),
            ("dfrc.dispatch", 0.08 * ns, 0.1 * ns),
            ("dfrc.fetch", 0.1 * ns, 0.68 * ns),
            ("np.asarray", 0.5 * ns, 0.62 * ns),   # not a span: ignored
            ("dfrc.fetch", 0.69 * ns, 0.85 * ns)]
    by_name = {"while.1": "dfrc.collect", "custom-call.4": "dfrc.eigh",
               "fusion.9": "dfrc.solve", "fusion.3": "dfrc.eval"}
    return device, host, by_name


def test_reduce_scopes_splits_self_time_by_scope():
    device, host, by_name = _scoped_run()
    got = scopes.reduce_scopes([device], host, by_name)
    s = got["scopes"]
    assert s["dfrc.collect"] == pytest.approx(0.2)
    assert s["dfrc.eigh"] == pytest.approx(0.1)
    assert s["dfrc.solve"] == pytest.approx(0.1)
    assert s["dfrc.eval"] == pytest.approx(0.1)
    assert s["unscoped"] == pytest.approx(0.05)
    assert got["busy_s"] == pytest.approx(trace.reduce([device], host)["busy_s"])
    assert sum(s.values()) == pytest.approx(got["busy_s"])
    assert got["unscoped_ops"] == [["copy.2", pytest.approx(0.05)]]
    # two devices: averaged, like busy_s
    two = scopes.reduce_scopes([device, device], host, by_name)
    assert two["scopes"] == pytest.approx(s)
    assert two["busy_s"] == pytest.approx(got["busy_s"])


def test_idle_by_span_labels_gaps_by_program_spans():
    device, host, by_name = _scoped_run()
    got = scopes.reduce_scopes([device], host, by_name)
    spans = dict(got["idle_by_span"])
    # [0, .1] under dfrc.prepare (midpoint .05); [.5, .55] and [.6, .7]
    # under dfrc.fetch (np.asarray is no span); [.8, 1.0] after every span
    assert spans["dfrc.prepare"] == pytest.approx(0.1)
    assert spans["dfrc.fetch"] == pytest.approx(0.05 + 0.1)
    assert spans["no span"] == pytest.approx(0.2)
    assert sum(spans.values()) == pytest.approx(got["window_s"] - got["busy_s"])
    host_after = [h for h in host if h[0] != "dfrc.fetch"]
    spans = dict(scopes.reduce_scopes([device], host_after, by_name)["idle_by_span"])
    assert spans["fit.call"] == pytest.approx(0.05 + 0.1)
    assert spans["no span"] == pytest.approx(0.2)


class _Program:
    """A jitted function behind the two methods ``profile`` uses of an
    ``Experiment``: ``run`` and ``lowered``."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        def f(x):
            with jax.named_scope("dfrc.solve"):
                return jnp.tanh(x) @ x

        self.f = jax.jit(f)

    def run(self, x):
        return np.asarray(self.f(x))

    def lowered(self, x):
        return self.f.lower(x)


def _ctx(run, trace_on=True):
    lines = []
    x = np.ones((8, 8), np.float32)
    ctx = types.SimpleNamespace(trace=trace_on, run=run, rotations=[(0, (x,))],
                                workload="unit-test", log=lines.append)
    return ctx, lines


def test_readers_report_nothing_for_a_program_without_scopes():
    """A program without ``Experiment.lowered`` has no scopes: the readers
    trace nothing more and leave their metrics out."""

    class Plain:
        def run(self, x):
            return x

    for run in (Plain().run, lambda x: x):
        ctx, lines = _ctx(run)
        for name in ("solve", "eigh", "collect", "eval"):
            assert _reader(f"fit.{name}_ms").read(ctx) is None
        assert ctx.scope_summary is None and lines == []
    ctx, lines = _ctx(_Program().run, trace_on=False)
    assert scopes.ms_per_call(ctx, "dfrc.solve") is None and lines == []


def test_profile_traces_one_call_once():
    """On the CPU the trace holds no TPU plane: the call is traced, the
    map built and the trace removed, and the readers report nothing."""
    ctx, lines = _ctx(_Program().run)
    assert scopes.profile(ctx) is None
    assert len(lines) == 1 and "traced call" in lines[0]
    assert int(re.search(r"\((\d+) instructions\)", lines[0]).group(1)) > 0
    assert scopes.ms_per_call(ctx, "dfrc.solve") is None
    assert len(lines) == 1                      # made once a run
    assert not (ROOT / ".bench_trace" / "unit-test.scopes").exists()


def _reader(name):
    return harness._load(harness.BENCH / "layer_metrics" / f"{name}.py")
