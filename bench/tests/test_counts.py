"""Operation and byte counts of each cell's shapes repeat exactly."""

import pytest

from bench import counts

NARMA = dict(batch=512, t_train=1000, t_test=1000, n=900, c=1, washout=60,
             chunk=256, n_lambdas=5)
CHANEQ = dict(batch=512, t_train=6000, t_test=3000, n=30, c=1, washout=60,
              chunk=256, n_lambdas=5)


@pytest.mark.parametrize("shape,calls", [(NARMA, (8, 4)), (CHANEQ, (36, 24))])
def test_fit_counts_repeat(shape, calls):
    a, b = counts.fit_call(**shape), counts.fit_call(**shape)
    assert a == b
    assert (a["kernels"]["dfr_scan"]["calls"],
            a["kernels"]["ridge_gram_into"]["calls"]) == calls


def test_narma_fit_counts_exact():
    got = counts.fit_call(**NARMA)
    f = 901
    assert got["kernels"]["dfr_scan"]["ops"] == 9 * 512 * 256 * 900
    assert got["kernels"]["dfr_scan"]["bytes"] == (
        4 * 512 * 256 + 4 * 512 * 256 * 900 + 8 * 512 * 900 + 4 * 900)
    assert got["kernels"]["ridge_gram_into"]["ops"] == 2 * 512 * 256 * f * (f + 1)
    assert got["kernels"]["ridge_gram_into"]["bytes"] == (
        4 * 512 * 256 * f + 4 * 512 * 256 + 8 * 512 * f * (f + 1))
    assert got["required_ops"] == (9 * 512 * 2000 * 900 + 2 * 512 * 940 * f * (f + 1)
                                   + 512 * 5 * (f ** 3 / 3 + 2 * f * f)
                                   + 2 * 512 * 1000 * f)


def test_serve_counts_repeat():
    shape = dict(slots=4096, chunk=32, n=30, c=1)
    assert counts.serve_tick(**shape) == counts.serve_tick(**shape)
    assert counts.serve_tick(**shape)["dfr_scan"]["ops"] == 9 * 4096 * 32 * 30
    req = dict(periods=4096 * 32, fit_periods=4000 * 32, solves=1024, n=30,
               c=1, n_lambdas=5)
    assert counts.serve_required(**req) == counts.serve_required(**req)


def test_roofline_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 50, peak) == (10.0, "compute")
    assert counts.roofline_seconds(100, 50, peak) == (5.0, "memory")
