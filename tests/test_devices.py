"""Device subsystem tests (DESIGN.md §14): CMT cavity physics, calibration
parity against the paper model, and the batched design-space sweep.

Fixed-seed and grid-based throughout — this module must run on minimal
images without hypothesis; the hypothesis-generalised versions of the
split/parity invariants live in tests/test_properties.py (gracefully
skipped when hypothesis is absent, conftest.py).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MODEL_REGISTRY, SiliconMR, make_mask, register_model, tasks
from repro.core.graph import ReservoirStage, chain
from repro.core.reservoir import generate_states
from repro.devices import (CMTSweepParams, MRCavityCMT, SweepGrid, SweepResult,
                           calibrated_twin, calibration_report, node_parity,
                           pipeline_cache_size, run_device_sweep)
from repro.pipeline import Experiment, ExperimentConfig

N = 16
K = 40
B = 3
MASK = make_mask(N, seed=3)
MR = SiliconMR()
TWIN = calibrated_twin(MR)                       # zero-power limit
CMT_HOT = calibrated_twin(MR, power_mw=1.0)      # nonlinear mechanisms on


def _stream(seed: int, k: int = K, b: int | None = B):
    rng = np.random.default_rng(seed)
    shape = (k,) if b is None else (b, k)
    return jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_contains_cmt():
    assert MODEL_REGISTRY["mr_cavity_cmt"] is MRCavityCMT
    register_model("mr_cavity_cmt", MRCavityCMT)  # idempotent re-register
    with pytest.raises(ValueError, match="already registered"):
        register_model("mr_cavity_cmt", SiliconMR)


# ---------------------------------------------------------------------------
# calibration: the CMT low-power limit IS the paper model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 2, 4, 8])
def test_calibrated_twin_tick_parity_any_substeps(m):
    """The exponential integrator telescopes: the calibrated zero-power tick
    map is substep-count independent and matches SiliconMR to f32 rounding."""
    twin = calibrated_twin(MR, n_substeps=m)
    assert node_parity(MR, twin) < 1e-5


def test_calibrated_twin_requires_zero_tpa():
    with pytest.raises(ValueError, match="beta_tpa"):
        calibrated_twin(SiliconMR(beta_tpa=0.3))


def test_small_signal_gains_match():
    rep = calibration_report(MR, TWIN)
    for branch in ("charge", "discharge"):
        assert rep[branch]["max_abs_delta"] < 1e-3


def test_stream_parity_low_power():
    """Whole-stream states of the twin track SiliconMR, not just one tick."""
    j = _stream(0)
    a = generate_states(MR, j, MASK, method="ref")
    b = generate_states(TWIN, j, MASK, method="ref")
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_low_power_narma_parity():
    """NRMSE-level mirror of the benchmark acceptance gate (small sizing)."""
    ds = tasks.narma10(600, seed=0)
    kw = dict(n_nodes=24, washout=40, ridge_l2=(1e-8, 1e-6),
              state_method="fast", state_noise_rel=0.0)
    r_mr = Experiment(ExperimentConfig(model=MR, **kw)).run_dataset(ds)
    r_tw = Experiment(ExperimentConfig(model=TWIN, **kw)).run_dataset(ds)
    assert abs(float(r_mr.nrmse[0]) - float(r_tw.nrmse[0])) < 2e-2


# ---------------------------------------------------------------------------
# integrator: substep convergence, path parity, chunked resume
# ---------------------------------------------------------------------------


def test_substep_convergence_with_nonlinearity_on():
    """With free carriers/thermal active the tick map depends on substep
    count; it must converge toward the fine-step limit monotonically in M."""
    g = jnp.linspace(0.0, 1.0, 7, dtype=jnp.float32)
    u, st, sp = jnp.meshgrid(g, g, g, indexing="ij")

    def tick(m):
        return dataclasses.replace(CMT_HOT, n_substeps=m).node_update(u, st, sp)

    ref = tick(64)
    errs = [float(jnp.max(jnp.abs(tick(m) - ref))) for m in (1, 4, 16)]
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 1e-2


def test_fast_matches_ref_bitwise():
    j = _stream(1)
    a = generate_states(CMT_HOT, j, MASK, method="ref")
    b = generate_states(CMT_HOT, j, MASK, method="fast")
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_kernel_matches_ref():
    j = _stream(2)
    a = generate_states(CMT_HOT, j, MASK, method="ref")
    b = generate_states(CMT_HOT, j, MASK, method="kernel")
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


@pytest.mark.parametrize("swept", [False, True], ids=["point", "swept"])
@pytest.mark.parametrize("method", ["ref", "fast", "kernel"])
def test_chunk_resume_bit_exact(method, swept):
    """Resuming from the carried final state replays the uninterrupted scan
    exactly — the CMT adiabatic closure is a function of the carried state
    alone, so chunk boundaries are invisible; with per-lane device
    parameters too (on the kernel path, its parameter tiles)."""
    j = _stream(3)
    p = _lane_grid() if swept else None
    full = generate_states(CMT_HOT, j, MASK, method=method, dev_params=p)
    s0, out = None, []
    for lo, hi in ((0, 13), (13, 14), (14, K)):
        states, s0 = generate_states(CMT_HOT, j[:, lo:hi], MASK, s0=s0,
                                     method=method, return_final=True,
                                     dev_params=p)
        out.append(np.asarray(states))
    assert np.array_equal(np.concatenate(out, axis=1), np.asarray(full))


# ---------------------------------------------------------------------------
# swept parameters: lanes == points, finiteness, validation
# ---------------------------------------------------------------------------


def _lane_grid():
    return CMTSweepParams(detune=jnp.asarray([-0.5, 0.0, 1.0], jnp.float32),
                          loss_scale=jnp.asarray([1.0, 1.2, 1.5], jnp.float32),
                          power=jnp.asarray([0.0, 0.5, 1.0], jnp.float32))


@pytest.mark.parametrize("method", ["ref", "fast", "kernel"])
def test_swept_lanes_match_unswept_points(method):
    """Each batch lane of a dev_params run equals the dedicated model built
    at that grid point (κ pinned to the base model's calibration anchor —
    sweeping detune moves the Lorentzian, not the pump calibration)."""
    j = _stream(4)
    p = _lane_grid()
    swept = generate_states(CMT_HOT, j, MASK, method=method, dev_params=p)
    for lane in range(B):
        point = dataclasses.replace(
            CMT_HOT, detune=float(p.detune[lane]),
            loss_scale=float(p.loss_scale[lane]),
            power_mw=float(p.power[lane]),
            kappa_charge=CMT_HOT.kappa_c, kappa_discharge=CMT_HOT.kappa_d)
        ref = generate_states(point, j[lane], MASK, method=method)
        assert float(jnp.max(jnp.abs(swept[lane] - ref))) < 1e-5


def test_states_finite_over_parameter_box():
    """No NaN/inf anywhere on a (detune × loss ≥ 1 × power) box (loss < 1
    raises the loop gain above unity by construction — documented unstable)."""
    grid = SweepGrid(detune=(-2.0, 0.0, 2.0), loss_scale=(1.0, 1.5, 2.0),
                     power=(0.0, 1.0, 2.0))
    lanes = grid.lanes()
    j = _stream(5, b=grid.size)
    states = generate_states(CMT_HOT, j, MASK, method="fast", dev_params=lanes)
    assert bool(jnp.all(jnp.isfinite(states)))


def test_dev_params_scalar_leaves_broadcast():
    j = _stream(6)
    p0 = CMTSweepParams(detune=0.0, loss_scale=1.0, power=1.0)
    a = generate_states(CMT_HOT, j, MASK, method="fast", dev_params=p0)
    point = dataclasses.replace(CMT_HOT, power_mw=1.0,
                                kappa_charge=CMT_HOT.kappa_c,
                                kappa_discharge=CMT_HOT.kappa_d)
    b = generate_states(point, j, MASK, method="fast")
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def _random_lanes(seed: int, b: int) -> CMTSweepParams:
    """A seeded random grid over the benchmark's box (loss >= 1)."""
    rng = np.random.default_rng(seed)
    return CMTSweepParams(
        detune=jnp.asarray(rng.uniform(-2.0, 2.0, b), jnp.float32),
        loss_scale=jnp.asarray(rng.uniform(1.0, 4.0, b), jnp.float32),
        power=jnp.asarray(rng.uniform(0.0, 2.0, b), jnp.float32))


@pytest.mark.parametrize("n_substeps", [1, 4])
@pytest.mark.parametrize("b", [5, 130], ids=["one_tile_padded", "two_sublanes_padded"])
def test_kernel_params_match_ref(b, n_substeps):
    """The Pallas scan with per-lane parameter tiles against the sequential
    oracle at the same parameters; both batches pad lanes (to 128 and 256)."""
    model = dataclasses.replace(CMT_HOT, n_substeps=n_substeps)
    j = _stream(8, k=24, b=b)
    p = _random_lanes(9 + b, b)
    a = generate_states(model, j, MASK, method="ref", dev_params=p)
    k = generate_states(model, j, MASK, method="kernel", dev_params=p)
    assert bool(jnp.all(jnp.isfinite(k)))
    assert float(jnp.max(jnp.abs(a - k))) < 1e-6


def test_kernel_zero_power_lane_is_silicon_mr():
    """A power = 0 lane at the calibration point (detune 0, loss 1) of the
    swept kernel gives the Silicon MR kernel's states."""
    j = _stream(10)
    p = CMTSweepParams(detune=jnp.zeros((B,)), loss_scale=jnp.ones((B,)),
                       power=jnp.zeros((B,)))
    a = generate_states(MR, j, MASK, method="kernel")
    k = generate_states(CMT_HOT, j, MASK, method="kernel", dev_params=p)
    assert float(jnp.max(jnp.abs(a - k))) < 1e-5


def test_kernel_sweep_matches_fast_experiment():
    """A chan-eq map through Experiment.run on the kernel path gives the
    fast path's NRMSE and SER at every grid point."""
    ds = tasks.channel_equalization(900, seed=4)
    g = 6
    p = _random_lanes(11, g)
    args = [np.broadcast_to(np.asarray(a, np.float32)[None], (g, len(a)))
            for a in (ds.inputs_train, ds.targets_train, ds.inputs_test,
                      ds.targets_test)]
    kw = dict(model=CMT_HOT, n_nodes=N, washout=30, ridge_l2=(1e-8, 1e-6, 1e-4),
              state_noise_rel=0.0, stream_chunk_k=64, quantize=True)
    fast = Experiment(ExperimentConfig(state_method="fast", **kw)).run(*args, dev_params=p)
    kern = Experiment(ExperimentConfig(state_method="kernel", readout_use_kernel=True,
                                       **kw)).run(*args, dev_params=p)
    assert np.all(np.isfinite(kern.nrmse))
    np.testing.assert_allclose(kern.nrmse, fast.nrmse, atol=1e-4)
    assert np.max(np.abs(kern.ser - fast.ser)) <= 1.0 / len(ds.targets_test)


@pytest.mark.parametrize("state_method", ["fast", "kernel"])
def test_experiment_states_are_the_fitted_states(state_method):
    """Experiment.states gives the states run fits and evaluates on: the
    readout run returns reproduces its reported NRMSE on them."""
    ds = tasks.channel_equalization(700, seed=5)
    g = 4
    p = _random_lanes(13, g)
    args = [np.broadcast_to(np.asarray(a, np.float32)[None], (g, len(a)))
            for a in (ds.inputs_train, ds.targets_train, ds.inputs_test,
                      ds.targets_test)]
    exp = Experiment(ExperimentConfig(
        model=CMT_HOT, n_nodes=N, washout=30, ridge_l2=(1e-8, 1e-6, 1e-4),
        state_noise_rel=0.0, stream_chunk_k=64, state_method=state_method,
        readout_use_kernel=True, collect_y_pred=False))
    res = exp.run(*args, dev_params=p)
    tr, te = exp.states(args[0], args[2], dev_params=p)
    assert tr.shape == (g, len(ds.inputs_train), N)
    assert te.shape == (g, len(ds.inputs_test), N)
    x = np.concatenate([np.asarray(te, np.float64), np.ones(te.shape[:2] + (1,))], axis=2)
    y = np.einsum("btf,bf->bt", x, res.readout_w.astype(np.float64))
    target = args[3].astype(np.float64)
    nrmse = np.sqrt(np.mean((y - target) ** 2, axis=1) / np.var(target, axis=1))
    np.testing.assert_allclose(res.nrmse, nrmse, rtol=1e-5)


def test_dev_params_rejected_on_kernel_path():
    """The kernel path takes per-lane device parameters for one shared
    mask; per-channel WDM masks with them stay rejected."""
    from repro.pipeline.experiment import _gen_states

    cfg = ExperimentConfig(model=CMT_HOT, n_nodes=N, state_method="kernel")
    masks = jnp.stack([MASK] * B)
    with pytest.raises(NotImplementedError, match="per-channel"):
        _gen_states(cfg, masks, _stream(7), wdm=True, dev_params=_lane_grid())


def test_experiment_dev_params_validation():
    ds = tasks.narma10(200, seed=0)
    base = dict(model=CMT_HOT, n_nodes=N, washout=20, state_noise_rel=0.0)
    args = (ds.inputs_train[None, :], ds.targets_train[None, :],
            ds.inputs_test[None, :], ds.targets_test[None, :])
    p0 = CMTSweepParams(detune=0.0, loss_scale=1.0, power=0.0)
    topo = chain(ReservoirStage(model=CMT_HOT, n_nodes=N, mask_seed=3))
    with pytest.raises(ValueError, match="topology"):
        Experiment(ExperimentConfig(topology=topo, stream_chunk_k=16,
                                    **base)).run(*args, dev_params=p0)
    bad = CMTSweepParams(detune=jnp.zeros((2,)), loss_scale=1.0, power=0.0)
    with pytest.raises(ValueError, match="batch lane"):
        Experiment(ExperimentConfig(**base)).run(*args, dev_params=bad)


# ---------------------------------------------------------------------------
# sweep driver: grid algebra, one-program execution, no-retrace
# ---------------------------------------------------------------------------


def test_sweep_grid_lanes_fold_roundtrip():
    grid = SweepGrid(detune=(-1.0, 1.0), loss_scale=(1.0, 1.5, 2.0),
                     power=(0.0, 1.0))
    assert grid.shape == (2, 3, 2) and grid.size == 12
    lanes = grid.lanes()
    folded = grid.fold(lanes.detune)
    for i, d in enumerate(grid.detune):
        assert np.all(folded[i] == d)
    idx = (1, 2, 0)
    flat = np.ravel_multi_index(idx, grid.shape)
    assert grid.point(idx) == {"detune": float(lanes.detune[flat]),
                               "loss_scale": float(lanes.loss_scale[flat]),
                               "power": float(lanes.power[flat])}


def test_stable_region_summary():
    grid = SweepGrid(detune=(0.0, 1.0), loss_scale=(1.0,), power=(0.0, 1.0))
    nrmse = np.array([[[0.2, 0.9]], [[np.inf, 0.3]]])
    res = SweepResult(grid=grid, nrmse=nrmse,
                      ser=np.zeros_like(nrmse), lam=np.zeros_like(nrmse))
    region = res.stable_region(nrmse_max=0.4)
    assert region["summary"]["n_stable"] == 2
    assert region["summary"]["best_point"]["nrmse"] == 0.2
    assert region["map"].tolist() == [[[True, False]], [[False, True]]]
    assert region["summary"]["stable_detune"] == [0.0, 1.0]
    assert region["summary"]["stable_power"] == [0.0, 1.0]


@pytest.mark.parametrize("state_method", ["fast", "kernel"])
def test_run_device_sweep_one_program_no_retrace(state_method):
    """The whole map from one compiled program: a second sweep with NEW grid
    values (same shapes) must leave the pipeline's jit cache untouched, on
    the kernel path (parameter tiles are operands) as on the jnp one."""
    ds = tasks.narma10(300, seed=0)
    grid = SweepGrid(detune=(-0.5, 0.5), loss_scale=(1.0,), power=(0.0, 1.0))
    res = run_device_sweep(TWIN, grid, ds, n_nodes=N, washout=20,
                           stream_chunk_k=32, ridge_l2=(1e-6, 1e-4),
                           state_method=state_method)
    assert res.nrmse.shape == grid.shape
    assert np.all(np.isfinite(res.nrmse))
    c0 = pipeline_cache_size()
    shifted = SweepGrid(detune=(-0.25, 0.75), loss_scale=(1.1,),
                        power=(0.25, 1.25))
    res2 = run_device_sweep(TWIN, shifted, ds, n_nodes=N, washout=20,
                            stream_chunk_k=32, ridge_l2=(1e-6, 1e-4),
                            state_method=state_method)
    assert pipeline_cache_size() == c0
    assert not np.array_equal(res.nrmse, res2.nrmse)


# ---------------------------------------------------------------------------
# composition: the CMT model rides the reservoir-graph stages unchanged
# ---------------------------------------------------------------------------


def test_cmt_in_composed_graph():
    topo = chain(ReservoirStage(model=CMT_HOT, n_nodes=12, mask_seed=3,
                                link="sin2", link_gain=0.28),
                 ReservoirStage(model=TWIN, n_nodes=4, mask_seed=10))
    ds = tasks.narma10(300, seed=0)
    cfg = ExperimentConfig(model=CMT_HOT, n_nodes=topo.width, washout=20,
                           ridge_l2=(1e-6,), topology=topo, stream_chunk_k=32,
                           state_method="fast", state_noise_rel=0.0)
    res = Experiment(cfg).run_dataset(ds)
    assert np.isfinite(res.nrmse).all()
