"""The trace reduction (`bench/trace.py`): its interval arithmetic on
made-up events, and the whole reduction on a small trace recorded on
the CPU backend."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loop, trace  # noqa: E402

Event = trace.Event


def test_union_merges_overlaps_and_keeps_holes():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.holes([(0, 3), (5, 8)], -1, 10) == [(-1, 0), (3, 5),
                                                   (8, 10)]
    assert trace.clip([(0, 3), (5, 8), (9, 12)], 1, 10) == [
        (1, 3), (5, 8), (9, 10)]


def test_self_times_subtract_nested_events():
    evs = [Event("while", 0, 100), Event("fusion", 10, 30),
           Event("kernel", 40, 90), Event("inner", 50, 60),
           Event("after", 120, 130)]
    assert trace.self_times(evs) == {"while": 30, "fusion": 20,
                                     "kernel": 40, "inner": 10,
                                     "after": 10}


def test_pallas_kernels_are_tpu_custom_calls():
    hlo = ('%custom-call.3 = f32[256,1] custom-call(...), '
           'custom_call_target="tpu_custom_call"')
    assert trace.is_pallas_kernel(Event("custom-call.3", 0, 1,
                                        {"long_name": hlo}))
    assert trace.is_pallas_kernel(Event("custom-call.7", 0, 1))
    # a TPU's XLA Ops line names each op by its HLO text
    tpu = ('%gemv.1 = f32[4096,1]{1,0:T(8,128)S(1)} custom-call(f32[1] '
           '%constant.3, f32[4096,4096] %pad.19), '
           'custom_call_target="tpu_custom_call"')
    assert trace.is_pallas_kernel(Event(tpu, 0, 1, {"hlo_op": "gemv.1"}))
    pad = ('%pad.19 = f32[4096,4096]{1,0:T(8,128)S(1)} pad(f32[4000,4000] '
           '%inputs__A__.1, f32[] %constant.6), padding=0_96x0_96')
    assert not trace.is_pallas_kernel(Event(pad, 0, 1))
    # XLA's own custom call: its HLO text names another target
    xla = ('%custom-call.9 = f32[64,64] custom-call(...), '
           'custom_call_target="Sharding"')
    assert not trace.is_pallas_kernel(Event("custom-call.9", 0, 1,
                                            {"long_name": xla}))
    assert not trace.is_pallas_kernel(Event("fusion.1", 0, 1,
                                            {"long_name": "%fusion.1"}))
    assert not trace.is_pallas_kernel(Event("copy", 0, 1))


def test_gap_label_names_the_innermost_annotation_and_call():
    host = [Event("bench.window", 0, 100), Event("bench.dispatch", 10, 30),
            Event("PjitFunction(solve)", 12, 28),
            Event("bench.wait", 30, 50)]
    assert trace.label_at(20, host) == "bench.dispatch / PjitFunction(solve)"
    assert trace.label_at(40, host) == "bench.wait"
    assert trace.label_at(70, host) == "bench.window"
    assert trace.label_at(200, host).startswith("outside")


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace of the benchmark's own loop on the CPU backend: each
    request dispatches a jitted product and then sleeps inside its
    wait, so the device idles inside `bench.wait`."""
    import jax
    import jax.numpy as jnp
    from jax import profiler

    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((256, 256), jnp.float32)
    f(a).block_until_ready()

    def wait(out):
        out.block_until_ready()
        time.sleep(0.01)

    d = tmp_path_factory.mktemp("trace")
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with profiler.trace(str(d), profiler_options=opts):
        win = loop.run(lambda i: f(a), wait, lambda o: False,
                       lambda i, o: None, seconds=0.1, ahead=0)
    return win, trace.reduce(trace.load(str(d)),
                             is_kernel=lambda ev: "dot" in ev.name)


def test_cpu_trace_reduces_to_busy_idle_and_labelled_gaps(cpu_trace):
    win, red = cpu_trace
    assert red.devices == 1
    assert red.window_s == pytest.approx(win.seconds, rel=0.05)
    assert 0 < red.busy_s < red.window_s
    # every request slept 10 ms inside bench.wait with the device idle
    assert red.window_s - red.busy_s >= 0.01 * win.requests * 0.9
    label, seconds = red.idle_gaps[0]
    assert label.startswith("bench.wait")
    assert seconds >= 0.009
    assert red.idle_gaps == sorted(red.idle_gaps, key=lambda g: -g[1])
    assert len(red.idle_gaps) <= trace.TOP


def test_cpu_trace_selects_kernel_events_and_ranks_ops(cpu_trace):
    win, red = cpu_trace
    # one dot per request: those in the window, plus at most one cut
    assert win.requests <= red.kernel_events <= win.requests + 1
    names = [name for name, _ in red.device_ops]
    assert any("dot" in n for n in names)
    secs = [s for _, s in red.device_ops]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
