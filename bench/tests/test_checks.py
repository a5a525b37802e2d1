"""The check that decides ``correct``: a sound run passes it, and it fails
for the control and for each fault planted under the timed path.

Each test drives a whole run (set-up, window, check) through
``harness.run_cell``, which skips the look for a chip, at a size the CPU
holds, with the kernels in interpret mode.  The control is the reference
in the program's place with its readout at ``Precision.HIGH`` (emulated
exactly on any device).
"""

import pytest

from bench import harness

SEED = 2**31 + 77
SMALL = {
    "narma10-mr900.fit-b512": (0.5, {
        "config": {"n_nodes": 48, "task.n_samples": 600, "fit.stream_chunk_k": 64},
        "mix": {"instances_per_call": 16, "rotations": 2}}),
    "chaneq-mr30.fit-b512": (0.5, {
        "config": {"task.n_symbols": 3000, "fit.stream_chunk_k": 128},
        "mix": {"instances_per_call": 32, "rotations": 2}}),
    "chaneq-mr30.serve-full": (4.0, {
        "config": {"serve.slots": 32},
        "mix": {"clients": 32, "pool_streams": 16}}),
    "chaneq-mr30.serve-sparse": (4.0, {
        "config": {"serve.slots": 32},
        "mix": {"clients": 8, "pool_streams": 16}}),
}
FAULTS = ("unchanged_state", "half_batch", "alter_answer")


def _run(workload, **kw):
    seconds, scale = SMALL[workload]
    return harness.run_cell(workload, SEED, seconds, False, scale=scale,
                            held_out=True, **kw)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    assert _run(workload)["correct"] is True


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload):
    assert _run(workload, variant="reference_high")["correct"] is False


# serve-sparse's clients fill the low slots only, so half of the slab left
# unstepped changes no answer there: that cell cannot have the fault.
CASES = [(w, f) for w in sorted(SMALL) for f in FAULTS
         if (w, f) != ("chaneq-mr30.serve-sparse", "half_batch")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    assert _run(workload, fault=fault)["correct"] is False
