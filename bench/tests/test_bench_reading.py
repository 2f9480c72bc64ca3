"""The arithmetic of `bench/reading.py` on records built by hand: the
per-iteration readings of a solve cell and a name scope's roofline
share, each of which reads nothing where the record has nothing to
read."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, reading  # noqa: E402
from bench import trace as tr  # noqa: E402

V5E = peaks.peaks("TPU v5 lite")


def _trace(busy_s: float) -> tr.Reduced:
    return tr.Reduced(window_s=10.0, busy_s=busy_s, devices=1,
                      kernel_events=0, device_ops=[], idle_gaps=[])


def _rec(**kw) -> dict:
    """A traced run's record on a v5e: a calls cell unless `kw` gives
    iterations."""
    rec = {"unit": "call", "requests": 40, "window_s": 10.0,
           "setup_s": 12.0, "dispatch_s": 0.004, "traced_requests": 20,
           "iterations": None, "traced_iterations": None,
           "work_bytes": 8e6, "work_flops": 4e6, "iteration_bytes": None,
           "iteration_flops": None, "peaks": V5E, "trace": _trace(2.0),
           "scope_s": None, "scope_work": None}
    return {**rec, **kw}


def _solve(**kw) -> dict:
    return _rec(**{"iterations": 600, "traced_iterations": 300,
                   "iteration_bytes": 6.4e9, "iteration_flops": 3.52e10,
                   **kw})


def test_iterations_per_request_reads_the_measured_window():
    assert reading.iterations_per_request(_solve()) == 15.0
    assert reading.iterations_per_request(_rec()) is None
    assert reading.iterations_per_request(_solve(requests=0)) is None


def test_iteration_us_is_busy_time_per_traced_iteration():
    assert reading.iteration_us(_solve()) == pytest.approx(1e6 * 2.0 / 300)
    assert reading.iteration_us(_rec()) is None
    assert reading.iteration_us(_solve(trace=None,
                                       traced_iterations=None)) is None
    assert reading.iteration_us(_solve(traced_iterations=0)) is None


def test_iteration_roofline_takes_the_larger_bound_per_iteration():
    # 6.4 GB at 819 GB/s (7.81 ms) binds; 35.2 GFLOP at 197 TFLOP/s is
    # 0.18 ms
    want = 100.0 * 300 * (6.4e9 / 819e9) / 2.0
    assert reading.iteration_roofline_pct(_solve()) == pytest.approx(want)
    flops_bound = _solve(iteration_bytes=1.0, iteration_flops=197e10)
    assert reading.iteration_roofline_pct(flops_bound) == pytest.approx(
        100.0 * 300 * 0.01 / 2.0)
    for missing in (_rec(), _solve(peaks=None), _solve(trace=None),
                    _solve(iteration_bytes=None, iteration_flops=None),
                    _solve(trace=_trace(0.0))):
        assert reading.iteration_roofline_pct(missing) is None


def test_scope_roofline_counts_requests_in_calls_and_iterations_in_solves():
    work = {"mvt.g0": (64e6, 32e6), "pad": (128e6, 0)}
    calls = _rec(scope_s={"mvt.g0": 0.004, "pad": 0.002}, scope_work=work)
    assert reading.scope_roofline_pct(calls, "mvt.g0") == pytest.approx(
        100.0 * 20 * (64e6 / 819e9) / 0.004)
    assert reading.scope_roofline_pct(calls, "pad") == pytest.approx(
        100.0 * 20 * (128e6 / 819e9) / 0.002)
    solve = _solve(scope_s={"cg.g0": 1.5}, scope_work={"cg.g0": (6.4e9, 0)})
    assert reading.scope_roofline_pct(solve, "cg.g0") == pytest.approx(
        100.0 * 300 * (6.4e9 / 819e9) / 1.5)
    for rec, scope in ((calls, "mvt.g1"),                # no work given
                       (_rec(scope_s={"mvt.g1": 0.1},
                             scope_work={"mvt.g1": (1, 1)}), "mvt.g0"),
                       ({**calls, "scope_s": None}, "mvt.g0"),
                       ({**calls, "scope_work": None}, "mvt.g0"),
                       ({**calls, "peaks": None}, "mvt.g0"),
                       ({**calls, "traced_requests": None}, "mvt.g0")):
        assert reading.scope_roofline_pct(rec, scope) is None


def test_the_call_roofline_reads_as_before():
    assert reading.roofline_pct(_rec(), "call") == pytest.approx(
        100.0 * 20 * max(8e6 / 819e9, 4e6 / 197e12) / 2.0)
    assert reading.roofline_pct(_rec(peaks=None), "call") is None
