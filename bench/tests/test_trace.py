"""The trace -> metrics reduction, on hand-made events and on a small
trace recorded on one TPU v5e (record_trace.py)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_and_clips():
    got = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)], lo=1, hi=8)
    assert got == [[1, 3], [5, 8]]


def test_reduce_busy_kernels_and_gaps():
    ns = 1e9
    device = [("dfr_scan.3", 0.1 * ns, 0.3 * ns),
              ("fusion.1", 0.25 * ns, 0.35 * ns),     # overlaps: counted once
              ("ridge_gram_into", 0.5 * ns, 0.6 * ns),
              ("dfr_scan.7", 0.8 * ns, 0.9 * ns),
              ("late", 1.5 * ns, 1.6 * ns)]           # outside the window
    host = [(trace.WINDOW_SPAN, 0.0, 1.0 * ns),
            ("fit.call", 0.0, 0.7 * ns),
            ("wait", 0.4 * ns, 0.45 * ns),
            ("pack", 0.6 * ns, 0.8 * ns)]
    got = trace.reduce([device], host, kernels=("dfr_scan", "ridge_gram_into"))
    assert got["window_s"] == pytest.approx(1.0)
    assert got["busy_s"] == pytest.approx(0.25 + 0.1 + 0.1)
    assert got["kernels"]["dfr_scan"] == {"seconds": pytest.approx(0.3), "events": 2}
    assert got["kernels"]["ridge_gram_into"]["events"] == 1
    gaps = dict((n, s) for n, s in got["idle_gaps"])
    # [0, .1] under fit.call; [.35, .5] midpoint .425 in "wait";
    # [.6, .8] in "pack"; [.9, 1.0] under no host event but the window
    assert gaps["fit.call"] == pytest.approx(0.1)
    assert gaps["wait"] == pytest.approx(0.15)
    assert gaps["pack"] == pytest.approx(0.2)
    assert gaps["no host event"] == pytest.approx(0.1)
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"])


def test_base_name():
    assert trace.base_name("ridge_gram_into.12") == "ridge_gram_into"
    assert trace.base_name("dfr_scan") == "dfr_scan"


def test_op_name_from_hlo_text():
    text = ('%dfr_scan.19 = (f32[256,30,1,128]{3,2,1,0}, f32[30,1,128]) '
            'custom-call(f32[256,1,128] %bitcast.171), custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "dfr_scan.19"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_self_times_subtract_nested_events():
    got = dict(trace.self_times([("while.1", 0.0, 10e9), ("a", 1e9, 3e9),
                                 ("b", 4e9, 5e9), ("c", 12e9, 13e9)]))
    assert got == {"while.1": 7.0, "a": 2.0, "b": 1.0, "c": 1.0}


def test_recorded_trace_reduces_to_its_known_numbers():
    from jax.profiler import ProfileData

    devices, host = trace.planes_of(
        ProfileData.from_file(str(DATA / "small_trace.xplane.pb")))
    got = trace.reduce(devices, host, kernels=("dfr_scan", "ridge_gram_into"))
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.408643123, rel=1e-9)
    assert got["busy_s"] == pytest.approx(0.14745018, rel=1e-9)
    # 30 calls of the fit: 6 scan chunks and 4 Gram folds each
    assert got["kernels"]["dfr_scan"]["events"] == 180
    assert got["kernels"]["ridge_gram_into"]["events"] == 120
    assert got["kernels"]["dfr_scan"]["seconds"] == pytest.approx(0.070976128, rel=1e-9)
    assert got["kernels"]["ridge_gram_into"]["seconds"] == pytest.approx(0.005801631, rel=1e-9)
    assert [n for n, _ in got["device_ops"][:2]] == ["custom-call.3", "dfr_scan.19"]
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)
