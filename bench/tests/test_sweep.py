"""The CMT cavity sweep cell (``chaneq-cmt30.sweep-g4096``) on the CPU: the
program's per-lane-parameter kernel against the plain reference, and whole
runs through ``harness.run_cell`` at a small grid, which a sound run passes
and each planted fault, the program with bfloat16 states (one step below
the configuration's float32) and the reference with its readout one
precision step down (the control) fail."""

import copy

import numpy as np
import pytest

from bench import harness, reference_cmt
from bench.kinds import sweep

WORKLOAD = "chaneq-cmt30.sweep-g4096"
SEED = 2**31 + 77
SMALL = {"config": {"task.n_symbols": 1200, "fit.stream_chunk_k": 128},
         "mix": {"detune": {"linspace": [-2.0, 2.0, 4]},
                 "loss_scale": {"geomspace": [1.0, 4.0, 2]},
                 "power_mw": {"linspace": [0.0, 2.0, 2]}}}


def _run(**kw):
    return harness.run_cell(WORKLOAD, SEED, 0.5, False, scale=SMALL, **kw)


@pytest.mark.parametrize("n_substeps", [1, 4])
def test_kernel_states_match_reference(n_substeps):
    """The program's kernel states, lane by lane at each lane's own
    operating point, against the plain float32 reference."""
    import jax.numpy as jnp

    from repro.core.reservoir import generate_states
    from repro.devices import CMTSweepParams

    _, _, config, mix, _ = harness.cell_spec(WORKLOAD, SMALL)
    config["model"]["n_substeps"] = n_substeps
    rng = np.random.default_rng(n_substeps)
    b, k = 12, 160
    lanes = {"detune": rng.uniform(-2, 2, b), "loss_scale": rng.uniform(1, 4, b),
             "power": rng.uniform(0, 2, b)}
    lanes = {key: v.astype(np.float32) for key, v in lanes.items()}
    tr = rng.uniform(-3, 3, (b, k)).astype(np.float32)
    te = rng.uniform(-3, 3, (b, k // 2)).astype(np.float32)
    mask = sweep.generate.mask(config)
    x_fit, x_te = reference_cmt.fit_states(config, mask, tr, te, lanes)

    lo, hi = tr.min(1, keepdims=True), tr.max(1, keepdims=True)
    j = np.concatenate([tr, te], axis=1)
    j = (j - lo) * (1.0 / (hi - lo + 1e-12))
    states = generate_states(sweep.model(config), jnp.asarray(j), jnp.asarray(mask),
                             method="kernel",
                             dev_params=CMTSweepParams(**lanes))
    got = np.asarray(states)
    washout = config["washout"]
    assert np.max(np.abs(got[:, washout:k] - x_fit)) < 1e-5
    assert np.max(np.abs(got[:, k:] - x_te)) < 1e-5


def test_sound_run_is_correct():
    assert _run()["correct"] is True


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "alter_answer"])
def test_fault_is_not_correct(fault):
    assert _run(fault=fault)["correct"] is False


def test_high_readout_control_is_not_correct():
    """The control -- the reference with its readout at Precision.HIGH, one
    step below the configuration's HIGHEST -- fails the harness's check on
    the numbers that judge the readout alone."""
    numbers = {}
    assert _run(variant="reference_high", numbers_out=numbers)["correct"] is False
    limits = harness.cell_spec(WORKLOAD)[4]["limits"]
    assert numbers["readout_excess"] > limits["readout_excess"]
    assert numbers["readout_eval_gap"] > limits["readout_eval_gap"]


def test_bfloat16_states_are_not_correct():
    scale = copy.deepcopy(SMALL)
    scale["config"]["fit.stream_state_dtype"] = "bfloat16"
    assert harness.run_cell(WORKLOAD, SEED, 0.5, False, scale=scale)["correct"] is False
