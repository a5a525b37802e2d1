"""The static-analysis subsystem itself (DESIGN.md §11).

Three layers, mirroring the package: the hardened walker (descent through
wrapper primitives + provenance paths), the rule engine (each built-in rule
catches a deliberately violating synthetic mini-program, with the right
provenance), and the CLI gate (exit codes + report).  These are the tests
of the *checker* — the repo's real programs are checked by the registry in
CI and by the migrated guards in test_streaming/test_wdm_streaming/
test_serving.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (DonationHonored, MaxPallasCalls, MaxScans,
                            NoDtypeAbove, NoHostCallback, NoSilentUpcast,
                            NoStateTensor, Program, VmemBudget,
                            intermediate_records, mosaic_kernels,
                            state_tensor_bytes, state_tensor_records,
                            trace_jaxpr)
from repro.analysis.walker import _sub_jaxprs

# ---------------------------------------------------------------------------
# walker: descent + provenance
# ---------------------------------------------------------------------------


def _wrapped_programs():
    """One program per wrapper primitive, each hiding a distinctive
    [8, 8] @ [8, 8] matmul inside the wrapped sub-jaxpr."""

    @jax.custom_jvp
    def f_jvp_wrapped(x):
        return (x @ x.T).sum()

    @f_jvp_wrapped.defjvp
    def _f_jvp(primals, tangents):
        return f_jvp_wrapped(primals[0]), jnp.zeros(())

    @jax.custom_vjp
    def f_vjp_wrapped(x):
        return (x @ x.T).sum()

    f_vjp_wrapped.defvjp(lambda x: (f_vjp_wrapped(x), x),
                         lambda res, ct: (jnp.zeros_like(res),))

    return {
        "custom_jvp_call": f_jvp_wrapped,
        "custom_vjp_call": f_vjp_wrapped,
        "while": lambda x: jax.lax.while_loop(
            lambda c: c[1] < 2, lambda c: (c[0] @ c[0].T, c[1] + 1),
            (x, 0))[0].sum(),
        "cond": lambda x: jax.lax.cond(
            x[0, 0] > 0, lambda v: (v @ v.T).sum(), lambda v: v.sum(), x),
        "remat": jax.checkpoint(lambda x: (x @ x.T).sum()),
    }


@pytest.mark.parametrize("wrapper", sorted(_wrapped_programs()))
def test_walker_descends_wrapper_subjaxprs(wrapper):
    """Sub-jaxprs behind custom-derivative / control-flow wrappers are
    walked, and the matmul inside carries the wrapper in its provenance
    path — the pre-hardening walker could not express (or in deeper
    nestings, even find) this."""
    fn = _wrapped_programs()[wrapper]
    cj = trace_jaxpr(fn, jnp.ones((8, 8), jnp.float32))
    hits = [r for r in intermediate_records(cj)
            if r.prim == "dot_general" and r.shape == (8, 8)]
    assert hits, f"matmul inside {wrapper} not found"
    assert any(r.path for r in hits), [r.where() for r in hits]
    # the path names the wrapper (jax spells custom_vjp as *_jaxpr)
    assert any(wrapper.split("_")[0] in p for r in hits for p in r.path), (
        wrapper, [r.where() for r in hits])


def test_sub_jaxprs_finds_deeply_nested_containers():
    """Jaxprs nested in tuples-of-tuples and dicts inside eqn params are
    found — the old single-level flatten (the closed_call-style blind spot)
    missed everything below the first container level."""
    cj = trace_jaxpr(lambda x: x * 2.0, jnp.ones((2,), jnp.float32))
    params = {
        "deep_tuple": (((cj,),),),
        "in_dict": {"k": cj.jaxpr},
        "scalar": 3,
        "mixed": [1, {"j": (cj,)}, "s"],
    }
    found = list(_sub_jaxprs(params))
    assert len(found) == 3
    assert all(f is cj.jaxpr for f in found)


# ---------------------------------------------------------------------------
# state_tensor_bytes: false-positive disambiguation (ISSUE 7 satellite)
# ---------------------------------------------------------------------------


def test_state_tensor_benign_template_exempts_axis_collision():
    """An unrelated axis numerically equal to t_len (here: a [B, F, F] Gram
    with F == chunk length) no longer false-positives once the structurally
    known shape is declared benign — while a genuine state tensor carrying
    the same axis value is still flagged, with provenance."""
    b, t, f = 2, 64, 64                    # F == t: the collision case

    def prog(x):                           # x: [B, t, F] chunk features
        gram = jnp.einsum("btf,btg->bfg", x, x)      # [B, F, F], F == t
        state = jnp.cumsum(x[..., :8], axis=1)       # [B, t, 8]: true state
        return gram.sum() + state.sum()

    cj = trace_jaxpr(prog, jnp.ones((b, t, f), jnp.float32))
    floor = b * t * 8
    # naive check flags the Gram (axis collision)
    assert state_tensor_bytes(cj, t, floor) >= b * f * f * 4
    # template-exempted check still flags the genuine [B, t, 8] tensor ...
    recs = state_tensor_records(cj, t, floor, benign_shapes=((b, f, f),))
    assert recs and all(sorted(r.shape) != sorted((b, f, f)) for r in recs)
    assert any(r.shape == (b, t, 8) for r in recs)
    assert all(isinstance(r.where(), str) and r.where() for r in recs)
    # ... and a fully-benign program comes out clean
    cj_g = trace_jaxpr(lambda x: jnp.einsum("btf,btg->bfg", x, x).sum(),
                       jnp.ones((b, t, f), jnp.float32))
    assert state_tensor_bytes(cj_g, t, floor,
                              benign_shapes=((b, f, f),)) == 0


# ---------------------------------------------------------------------------
# rule engine: each rule catches its synthetic violation, with provenance
# ---------------------------------------------------------------------------


def test_rule_no_state_tensor_flags_materialized_scan_output():
    b, n, t = 2, 16, 50

    def prog(x):                           # stacks [t, B, N]: the tensor
        def step(s, u):                    # the streaming path must never
            s = jnp.tanh(s + u[:, None])   # materialize
            return s, s
        _, ys = jax.lax.scan(step, jnp.zeros((b, n)), x)
        return ys.sum()

    prog_ok_src = lambda x: jax.lax.scan(
        lambda s, u: (jnp.tanh(s + u[:, None]), u.sum()),
        jnp.zeros((b, n)), x)[1].sum()

    rule = NoStateTensor(t, b * t * n)
    viols = rule.check(Program(prog, (jnp.ones((t, b), jnp.float32),)))
    assert viols
    assert any(v.shape == (t, b, n) and v.path[-1] == "scan" for v in viols)
    assert not rule.check(Program(prog_ok_src,
                                  (jnp.ones((t, b), jnp.float32),)))


def test_rule_max_scans_reports_paths():
    def prog(x):
        a = jax.lax.scan(lambda c, u: (c + u, c), 0.0, x)[0]
        b = jax.lax.scan(lambda c, u: (c * u, c), 1.0, x)[0]
        return a + b

    viols = MaxScans(1).check(Program(prog, (jnp.ones((8,), jnp.float32),)))
    assert len(viols) == 1 and "2 scan eqns" in viols[0].message


def test_rule_max_pallas_calls():
    from repro.core import SiliconMR, make_mask
    from repro.kernels.dfr_scan import dfr_scan
    model, mask = SiliconMR(), make_mask(8, seed=0)
    j, s0 = jnp.zeros((2, 16), jnp.float32), jnp.zeros((2, 8), jnp.float32)
    prog = Program(lambda jj, s: dfr_scan(model, jj, mask, s,
                                          interpret=True).sum(), (j, s0))
    assert not MaxPallasCalls(1).check(prog)
    viols = MaxPallasCalls(0).check(prog)
    assert len(viols) == 1 and "pallas_call" in viols[0].message


def test_rule_no_dtype_above_catches_f64_literal():
    """An f64 leak via a float64 literal (only expressible with x64 on —
    with x64 off jax weakens the literal and the program stays clean)."""
    def prog(x):
        return x * np.float64(2.0) + jnp.asarray(1.0, jnp.float64)

    with jax.enable_x64(True):
        viols = NoDtypeAbove("float32").check(
            Program(prog, (jnp.ones((4,), jnp.float32),)))
    assert viols and all(v.dtype == "float64" for v in viols)

    # same program under default x64-off config: weak literal, no violation
    assert not NoDtypeAbove("float32").check(
        Program(prog, (jnp.ones((4,), jnp.float32),)))


def test_rule_no_host_callback_with_provenance():
    def leaf(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    def prog(x):                           # callback *inside* a scan body
        return jax.lax.scan(lambda c, u: (c + leaf(u), c), 0.0, x)[0]

    viols = NoHostCallback().check(
        Program(prog, (jnp.ones((4,), jnp.float32),)))
    assert viols and viols[0].path[-1] == "pure_callback"
    assert "scan" in viols[0].path

    def prog_print(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    viols = NoHostCallback().check(
        Program(prog_print, (jnp.ones((4,), jnp.float32),)))
    assert viols and "debug_print" in viols[0].message


def test_rule_donation_honored_detects_dropped_alias():
    x = jnp.ones((8, 8), jnp.float32)
    # donated and shape-compatible: alias survives lowering
    donated = Program(lambda v: v + 1.0, (x,), donate_argnums=(0,))
    assert not DonationHonored().check(donated)
    # donated but no output can reuse the buffer: XLA drops the alias
    # silently — exactly the regression this rule exists to catch
    shrunk = Program(lambda v: v[:2].sum(), (x,), donate_argnums=(0,))
    viols = DonationHonored().check(shrunk)
    assert viols and "aliased buffers" in viols[0].message
    # an un-donated program fails an explicit donation expectation
    undonated = Program(lambda v: v + 1.0, (x,))
    assert DonationHonored(min_donated=1).check(undonated)
    # pallas-level: a plain program has no input_output_aliases pairs
    assert DonationHonored(min_pallas_aliases=2).check(undonated)


def test_rule_no_silent_upcast():
    b, chunk, n = 2, 32, 16

    def bad(x):                            # bf16 chunk upcast to f32 at scale
        wide = x.astype(jnp.float32) * 2.0
        return wide.sum()

    def good(x):                           # widens only a sub-floor slice
        # (note jnp.sum over the chunk axis would NOT be clean: it
        # accumulates bf16 inputs through a full-size f32 convert)
        return (x * jnp.bfloat16(2.0))[:, :, :1].astype(jnp.float32).sum()

    arr = jnp.ones((b, chunk, n), jnp.bfloat16)
    rule = NoSilentUpcast(chunk, b * chunk * n)
    viols = rule.check(Program(bad, (arr,)))
    assert viols and viols[0].dtype == "float32"
    assert not rule.check(Program(good, (arr,)))


def _copy_kernel_program(shape, dtype, block):
    """Trace-only pallas copy kernel with an explicit block shape."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    grid = tuple(s // b for s, b in zip(shape, block))

    def run(x):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))],
            out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            interpret=True,
        )(x)

    return Program(run, (jnp.zeros(shape, dtype),))


def test_rule_vmem_budget_overflow():
    # one 32 MiB f32 block, double-buffered in+out = 128 MiB > the 64 MiB
    # scoped-VMEM limit the kernels compile with
    prog = _copy_kernel_program((4096, 2048), jnp.float32, (4096, 2048))
    viols = VmemBudget().check(prog)
    assert viols and "VMEM" in viols[0].message
    assert not VmemBudget(limit_bytes=256 * 2 ** 20).check(prog)


def test_rule_vmem_alignment_sub_f32_multi_tile():
    """A multi-tile bf16 block off the (16, 128) boundary is exactly the
    class of bug interpret mode computes happily and real Mosaic rejects
    (the dfr_scan guard, generalized to every pallas_call)."""
    bad = _copy_kernel_program((32, 256), jnp.bfloat16, (4, 256))
    viols = VmemBudget().check(bad)
    assert viols and "sublane" in viols[0].message
    # aligned bf16 blocks, single-tile blocks, and f32 at the same geometry
    # (Mosaic relayouts f32) are all fine
    assert not VmemBudget().check(
        _copy_kernel_program((32, 256), jnp.bfloat16, (16, 256)))
    assert not VmemBudget().check(
        _copy_kernel_program((32, 256), jnp.float32, (4, 256)))
    assert not VmemBudget(check_alignment=False).check(bad)


def test_mosaic_kernels_counts_only_compiled_kernels():
    """An interpreted kernel lowers to plain HLO: the compiled program then
    holds no ``tpu_custom_call`` and ``mosaic_kernels`` finds nothing."""
    prog = _copy_kernel_program((16, 128), jnp.float32, (8, 128))
    text = jax.jit(prog.fn).lower(*prog.args).compile().as_text()
    assert mosaic_kernels(text) == {}
    line = ('  %dfr_scan.3 = (f32[8,4,128]{2,1,0}) custom-call(f32[8,4,128] '
            '%p0), custom_call_target="tpu_custom_call", backend_config={}')
    assert mosaic_kernels(line + "\n" + line) == {"dfr_scan": 2}


# ---------------------------------------------------------------------------
# registry + CLI gate
# ---------------------------------------------------------------------------


def test_cli_entry_point_ok_and_report(tmp_path):
    from repro.analysis.cli import main
    out = tmp_path / "report.json"
    rc = main(["--entry-point", "session_step", "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] and report["n_violations"] == 0
    (entry,) = report["entry_points"]
    assert entry["name"] == "session_step" and entry["rules"]


def test_cli_seeded_violation_exits_nonzero(tmp_path):
    from repro.analysis.cli import main
    out = tmp_path / "report.json"
    rc = main(["--seed-violation", "--entry-point", "seeded_violation",
               "--out", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    assert not report["ok"]
    (entry,) = report["entry_points"]
    viols = [v for r in entry["rules"] for v in r["violations"]]
    assert viols and all(v["rule"] == "NoStateTensor" for v in viols)
    assert any(v["path"] for v in viols)   # provenance reaches the report


def test_cli_unknown_entry_point_rejected():
    from repro.analysis.cli import main
    with pytest.raises(KeyError, match="bogus"):
        main(["--entry-point", "bogus", "--out", "/dev/null"])


def test_registry_names_cover_issue_surface():
    from repro.analysis.registry import entry_point_names
    names = set(entry_point_names())
    assert {"experiment_ref", "experiment_fast", "experiment_kernel",
            "experiment_streaming", "fit_ridge_streaming",
            "fit_ridge_streaming_wdm", "session_step",
            "session_step_refresh", "serve_dfr_step",
            "reservoir_lm_train_step"} <= names


def test_pipeline_introspect_shim_reexports():
    """Legacy import path still works and resolves to repro.analysis."""
    from repro.pipeline import introspect
    import repro.analysis.walker as walker
    for name in ("walk_eqns", "trace_jaxpr", "intermediate_shapes",
                 "max_intermediate_bytes", "state_tensor_bytes",
                 "count_scans", "count_pallas_calls"):
        assert getattr(introspect, name) is getattr(walker, name)
