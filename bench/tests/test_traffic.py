"""The benchmark's generator copies give the program's arrays, bit for bit."""

import numpy as np
import pytest

from bench.traffic import generate, tasks

FIELDS = ("inputs_train", "targets_train", "inputs_test", "targets_test")
SEEDS = [0, 1, 407, 2**31 + 11, 123456789012]


@pytest.mark.parametrize("seed", SEEDS)
def test_narma10_matches_program(seed):
    from repro.core import tasks as program

    want = program.narma10(seed=seed)
    for got, field in zip(tasks.narma10(seed=seed), FIELDS):
        assert np.array_equal(got, getattr(want, field))


def test_narma10_batch_matches_program():
    from repro.core import tasks as program

    batch = tasks.narma10_batch(SEEDS)
    for row, seed in enumerate(SEEDS):
        want = program.narma10(seed=seed)
        for got, field in zip(batch, FIELDS):
            assert np.array_equal(got[row], getattr(want, field))


@pytest.mark.parametrize("seed", SEEDS)
def test_channel_equalization_matches_program(seed):
    from repro.core import tasks as program

    want = program.channel_equalization(seed=seed)
    for got, field in zip(tasks.channel_equalization(seed=seed), FIELDS):
        assert np.array_equal(got, getattr(want, field))


def test_chan_eq_streams_match_program():
    from repro.launch.serve_dfr import chan_eq_requests

    want = chan_eq_requests(6, 2048, 32, seed=2**31 + 3)
    got = tasks.chan_eq_streams(6, 2048, 32, seed=2**31 + 3)
    for req, (j, y) in zip(want, got):
        assert j.dtype == req.j.dtype and np.array_equal(j, req.j)
        assert y.dtype == req.y.dtype and np.array_equal(y, req.y)


@pytest.mark.parametrize("n_nodes,seed", [(900, 1), (30, 7), (30, 2**31 + 9)])
def test_mask_matches_program(n_nodes, seed):
    from repro.core.masking import make_mask

    assert np.array_equal(tasks.make_mask(n_nodes, seed=seed),
                          np.asarray(make_mask(n_nodes, seed=seed)))


def test_same_seed_same_inputs():
    config = {"n_nodes": 30, "mask_levels": [0.0, 1.0], "mask_seed": 1,
              "task": {"name": "channel_equalization", "n_symbols": 600,
                       "snr_db": 24.0, "train_frac": 2 / 3}}
    mix = {"instances_per_call": 8, "pool_seed": 5}
    seed = 2**31 + 99
    a, b = generate.fit_pool(config, mix, seed), generate.fit_pool(config, mix, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert np.array_equal(generate.mask(config), generate.mask(config))
    # another seed: the same instances, in another order
    other = generate.fit_pool(config, mix, seed + 1)
    assert not np.array_equal(a[0], other[0])
    assert sorted(map(bytes, a[0])) == sorted(map(bytes, other[0]))
