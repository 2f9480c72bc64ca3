"""Device time by name scope (`bench/scopes.py`) on made-up traces and
on traces recorded on the CPU backend, the layers probe
(`bench/layers.py`) on a small cell, and the benchmark's own readers
with the library's recording on."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, scopes  # noqa: E402
from test_bench_harness import small_cell, small_sizes  # noqa: E402


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


HLO = """HloModule jit_program, is_scheduled=true
ENTRY %main.6 (inputs__A__.1: f32[2800,2800]) -> f32[2800] {
  %pad.21 = f32[2816,3072]{1,0} pad(%inputs__A__.1, %constant.13), padding=0_16x0_272, metadata={op_name="jit(program)/gesummv.g0/jit(gemv)/pad/jit(_pad)/pad" stack_frame_id=12}
  %pad.20 = f32[2816,3072]{1,0} pad(%custom-call, %constant.13), padding=0_16x0_272, metadata={op_name="jit(program)/gesummv.g1/jit(gemv)/pad/jit(_pad)/pad" stack_frame_id=12}
  %gemv = f32[2816,1]{1,0} custom-call(%pad.21), custom_call_target="tpu_custom_call", metadata={op_name="jit(program)/gesummv.g0/jit(gemv)/pallas_call" stack_frame_id=19}
  ROOT %slice_reduce_fusion = f32[2800]{0} fusion(%gemv), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/gesummv.g0/jit(gemv)/slice"}
}
"""


def _tpu_op(instr: str, start: float, end: float) -> Ev:
    """An op of a TPU's `XLA Ops` line: named by its HLO text."""
    return Ev(f"%{instr} = f32[2816,3072]{{1,0:T(8,128)S(1)}} op(...)",
              start, end - start)


def _tpu_trace(ops) -> Profile:
    return Profile([Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_program(3202498600)", 0, 1000)]),
        Line("XLA Ops", ops)])])


def test_op_paths_and_pad_ops_are_read_from_hlo_text():
    paths = scopes.op_paths([HLO])
    assert set(paths) == {"jit_program"}
    assert paths["jit_program"]["pad.20"].endswith("/pad/jit(_pad)/pad")
    assert "gemv" in paths["jit_program"]
    assert "slice_reduce_fusion" in paths["jit_program"]
    assert scopes.is_pad_op(_tpu_op("pad.21", 0, 1).name.replace(
        " op(", " pad("))
    assert not scopes.is_pad_op(_tpu_op("gemv", 0, 1).name)


def test_pad_scope_takes_the_union_of_overlapping_pad_ops():
    pd = _tpu_trace([_tpu_op("pad.21", 100, 400),
                     _tpu_op("pad.20", 300, 500),     # overlaps pad.21
                     _tpu_op("gemv", 500, 900),
                     _tpu_op("pad.21", 950, 960)])
    paths = scopes.op_paths([HLO])
    # 100..500 and 950..960, where the sum of durations would be 510
    assert scopes.scope_times(pd, paths, 0, 1000)["pad"] == \
        pytest.approx(410e-9)
    assert scopes.scope_times(pd, paths, 200, 1000)["pad"] == \
        pytest.approx(310e-9)
    # a group's scope takes its pad, its kernel and its slice
    assert scopes.scope_times(pd, paths, 0, 1000)["gesummv.g0"] == \
        pytest.approx((400 - 100 + 900 - 500 + 10) * 1e-9)


def test_scope_times_gives_each_group_and_pad_its_union_on_each_device():
    one = [_tpu_op("pad.21", 100, 400), _tpu_op("gemv", 400, 900),
           _tpu_op("pad.20", 950, 990)]
    two = [_tpu_op("pad.21", 0, 100), _tpu_op("slice_reduce_fusion",
                                             100, 300)]
    pd = Profile([Plane(f"/device:TPU:{i}", [
        Line("XLA Modules", [Ev("jit_program(3202498600)", 0, 1000)]),
        Line("XLA Ops", ops)]) for i, ops in enumerate((one, two))])
    times = scopes.scope_times(pd, scopes.op_paths([HLO]), 0, 1000)
    # the mean over both devices, the pad counted in its group as well
    assert times == pytest.approx({"gesummv.g0": (800 + 300) / 2 * 1e-9,
                                   "gesummv.g1": 40 / 2 * 1e-9,
                                   "pad": (340 + 100) / 2 * 1e-9})
    assert scopes.scope_times(pd, {}, 0, 1000) is None


def test_pad_us_reads_the_pads_self_time_per_traced_call():
    from bench import trace as tr
    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "pad_us.call.py")
    ops = [[_tpu_op("gemv", 0, 1).name, 0.5],
           [_tpu_op("pad.21", 0, 1).name.replace(" op(", " pad("), 0.3],
           ["pad_pad_fusion", 0.1],             # the CPU backend's name
           ["pad_dot_fusion", 0.2],             # a dot, not a pad
           ["copy.2", 0.05]]
    red = tr.Reduced(window_s=2.0, busy_s=1.0, devices=1,
                     kernel_events=4, device_ops=ops, idle_gaps=[])
    rec = {"unit": "call", "trace": red, "traced_requests": 1000}
    assert reader.read(rec) == pytest.approx(1e6 * 0.4 / 1000)
    assert reader.read({**rec, "trace": None}) is None
    assert reader.read({**rec, "traced_requests": None}) is None


def test_scope_seconds_is_none_where_no_op_joins():
    pd = _tpu_trace([_tpu_op("pad.21", 100, 400)])
    assert scopes.scope_times(pd, {}, 0, 1000) is None
    other = {"jit_other": scopes.op_paths([HLO])["jit_program"]}
    assert scopes.scope_times(pd, other, 0, 1000) is None
    # joined, but nothing under the scope: no entry, not None
    gemv = _tpu_trace([_tpu_op("gemv", 100, 400)])
    times = scopes.scope_times(gemv, scopes.op_paths([HLO]), 0, 1000)
    assert "pad" not in times and times["gesummv.g0"] > 0


def test_cpu_ops_join_by_their_instruction_and_module_stats():
    pd = Profile([Plane("/host:CPU", [Line("tf_XLAEigen/1", [
        Ev("pad.21", 0, 50, {"hlo_op": "pad.21",
                             "hlo_module": "jit_program"}),
        Ev("gemv", 60, 40, {"hlo_op": "gemv",
                            "hlo_module": "jit_program"})])])])
    paths = scopes.op_paths([HLO])
    assert scopes.scope_times(pd, paths, 0, 1000)["pad"] == \
        pytest.approx(50e-9)


def test_a_recorded_cpu_trace_attributes_device_time_to_groups(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    c = small_cell("polybench.gesummv")
    kind = harness.prepare(c, 2 ** 31 + 3)
    exe = kind.entry.__self__
    paths = scopes.op_paths([exe.hlo_text(**kind.fixed, **kind.pool[0])])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            kind.wait(kind.send(i))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    inf = float("inf")
    times = scopes.scope_times(pd, paths, 0, inf)
    for group in ("gesummv.g0", "gesummv.g1"):
        assert times[group] > 0


@pytest.fixture
def small_cells(monkeypatch):
    """`harness.cell` at the sizes a test run holds, and no persistent
    compile cache."""
    cell = harness.cell

    def small(name):
        c = cell(name)
        c.config = {**c.config, **small_sizes(c.module)}
        return c

    monkeypatch.setattr(harness, "cell", small)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)


def test_layers_refuses_to_run_without_a_tpu(capsys):
    from bench import layers
    assert layers.main(["--workload", "polybench.mvt", "--seed", "1",
                        "--seconds", "0.1", "--record", "0"]) == 3
    assert capsys.readouterr().out == ""


def test_layers_reads_every_layer_of_a_small_cell(small_cells, capsys,
                                                   monkeypatch):
    from repro import obs

    from bench import layers
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(layers, "device", lambda chips: cpu)
    try:
        assert layers.main(["--workload", "polybench.mvt", "--seed",
                            str(2 ** 31 + 5), "--seconds", "0.3",
                            "--record", "1"]) == 0
    finally:
        obs.disable()
        obs.reset()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    # `send` wraps `Executable.run`: the front door is inside dispatch
    assert 0 < out["front_door_us.call"] <= out["dispatch_us"]
    assert out["host_gc_us.call"] >= 0 and out["compile_s.setup"] >= 0
    assert out["device"] == cpu
    # N = 300 is padded: the pads' self time and their scope both read
    assert out["pad_us.call"] > 0 and out["pad_scope_us.call"] > 0
    assert all(s >= layers.GAP_S for _, s in out["gaps"])


def test_the_benchmarks_readers_read_alike_with_recording_on(
        tmp_path, monkeypatch):
    """The library's recording puts its spans on the bench thread's
    host line; the benchmark's own readers and breakdown read the same
    things as with it off."""
    from repro import obs
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")

    def traced_line():
        c = small_cell("polybench.mvt")
        kind = harness.prepare(c, 2 ** 31 + 13)
        m = harness.measure(kind, 0.2, trace=True, setup_s=1.0,
                            log=lambda s: None)
        return harness.result_line(c, m, trace=True)

    off = traced_line()
    obs.enable()
    try:
        on = traced_line()
    finally:
        obs.disable()
        obs.reset()
    assert set(on["metrics"]) == set(off["metrics"])
    assert on["metrics"]["kernel_launches.call"] == \
        off["metrics"]["kernel_launches.call"]
    for line in (on, off):
        assert line["metrics"]["pad_us.call"]["value"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(label.startswith(("bench.", "outside"))
                   for label, _ in line["breakdown"]["idle_gaps"])
        assert 0 <= line["metrics"]["device_idle.call"]["value"] < 100
