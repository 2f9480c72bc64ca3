"""The `solves` request kind: a solve of a loop program as one request,
through the harness's own entry points (`prepare`, `measure`,
`result_line`), on a small SPD system of the tests' own
(`spd-small.json` and `.py` beside this file) solved by the library's
CG and block-CG loop specs."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, kinds  # noqa: E402
from test_bench_harness import BM, small_cell  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
SEED = 2 ** 33 + 21
PROGRAMS = ["cg", "block_cg"]


def solve_cell(program: str, config=None, **traffic) -> harness.Cell:
    """A cell of the `solves` kind, built directly: `harness.cell`
    reads traffic only from `bench/traffic/`."""
    cfg = json.loads((HERE / "spd-small.json").read_text())
    return harness.Cell(
        name=f"spd-small.{program}", chips=1, config={**cfg, **(config or {})},
        module=harness.load_module(HERE / "spd-small.py"),
        traffic={"kind": "solves", "program": program, "pool": 3,
                 "ahead": 2, "sample": 4, **traffic},
        end_to_end=BM["end_to_end"], per_layer=BM["per_layer"])


def measured(c, kind, trace=False, seconds=0.2):
    m = harness.measure(kind, seconds, trace=trace, setup_s=1.0,
                        log=lambda s: None)
    return m, harness.result_line(c, m, trace=trace)


def failed_checks(line) -> set:
    return {n for n, chk in line["checks"].items()
            if chk["value"] > chk["limit"]}


def counting(kind) -> list:
    """Keep the result of every solve sent from now on."""
    entry, counts = kind.entry, []

    def run(**kw):
        out = entry(**kw)
        counts.append(out)
        return out

    kind.entry = run
    return counts


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_solve_cell_is_correct_and_counts_its_iterations(program):
    c = solve_cell(program)
    kind = harness.prepare(c, SEED)
    assert isinstance(kind, kinds.Solves) and kind.unit == "call"
    sent = counting(kind)
    m, line = measured(c, kind)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert set(line["checks"]) == {"err", "unconverged"}
    assert len(sent) == m.rec["requests"] == line["attempted"]
    assert m.rec["iterations"] == sum(int(o.iterations) for o in sent)
    assert m.rec["iterations"] >= m.rec["requests"]
    assert m.rec["traced_iterations"] is None
    assert (m.rec["iteration_bytes"], m.rec["iteration_flops"]) == \
        c.module.iteration_work(c.config, program)
    assert line["metrics"]["calls_per_s"]["value"] == pytest.approx(
        m.rec["requests"] / m.rec["window_s"])
    assert line["metrics"]["setup_s"]["value"] == 1.0


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_traced_solve_window_counts_its_own_iterations(program, tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    c = solve_cell(program)
    kind = harness.prepare(c, SEED + 1)
    sent = counting(kind)
    # a short window: the CPU's trace of a solve holds every loop op
    m, line = measured(c, kind, trace=True, seconds=0.03)
    assert line["correct"], line["checks"]
    measured_window = sent[:m.rec["requests"]]
    traced_window = sent[m.rec["requests"]:]
    assert len(traced_window) == m.rec["traced_requests"]
    assert m.rec["iterations"] == sum(int(o.iterations)
                                      for o in measured_window)
    assert m.rec["traced_iterations"] == sum(int(o.iterations)
                                             for o in traced_window)
    # the unit-aware readers read a solve cell as they read calls
    assert 0 <= line["metrics"]["device_idle.call"]["value"] < 100
    assert line["metrics"]["dispatch_us"]["value"] > 0
    # `Executable.hlo_text` takes no loop program: no scope is timed
    assert m.rec["scope_s"] is None


def _perturbed(entry):
    def run(**kw):
        out = entry(**kw)
        return dataclasses.replace(out, x=1.01 * out.x)
    return run


def _stale(entry):
    """Each solve returns what the solve before it should have."""
    last: list = []

    def run(**kw):
        last.append(entry(**kw))
        return last[-2] if len(last) > 1 else last[-1]
    return run


def _half_left_out(entry):
    """Half of the right-hand sides reach the solve as zeros."""
    def run(A, x0, **kw):
        (name, b), = kw.items()
        half = b.shape[-1] // 2 if b.ndim == 2 else b.shape[0] // 2
        return entry(A=A, x0=x0, **{name: b.at[..., :half].set(0)
                                    if b.ndim == 2 else b.at[:half].set(0)})
    return run


FAULTS = {"perturbed": _perturbed, "stale": _stale,
          "half_left_out": _half_left_out}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("program", PROGRAMS)
def test_a_faulty_solution_fails_err(program, fault):
    c = solve_cell(program)
    kind = harness.prepare(c, SEED)
    kind.entry = FAULTS[fault](kind.entry)
    _, line = measured(c, kind)
    # a zero column breaks block-CG down as well (0/0 step lengths)
    assert "err" in failed_checks(line)
    assert line["failed"] > 0


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_solve_capped_at_one_iteration_fails_unconverged(program):
    c = solve_cell(program, config={"max_iters": 1})
    kind = harness.prepare(c, SEED)
    _, line = measured(c, kind)
    assert "unconverged" in failed_checks(line)
    assert line["checks"]["unconverged"]["value"] == len(kind.sample)
    assert line["failed"] == len(kind.sample)


@pytest.mark.parametrize("program", PROGRAMS)
def test_the_plain_solve_in_the_librarys_place(program):
    """The control path: the plain CG at the configuration's precision
    is correct, and one precision below it is not."""
    c = solve_cell(program)
    _, line = measured(c, harness.prepare(c, SEED, precision="highest"))
    assert line["correct"], line["checks"]
    _, line = measured(c, harness.prepare(c, SEED, precision="high"))
    assert failed_checks(line) == {"err"}


def test_solves_in_flight_are_each_handed_on_once():
    c = solve_cell("block_cg", ahead=3)
    kind = harness.prepare(c, SEED)
    keep, kept = kind.keep, []

    def once(i, out):
        kept.append(i)
        keep(i, out)

    kind.keep = once
    m, line = measured(c, kind)
    assert line["correct"], line["checks"]
    assert kept == list(range(m.rec["requests"]))
    assert kind.count == m.rec["requests"] > 3


def test_a_status_that_no_solve_can_end_in_is_refused():
    c = solve_cell("cg", status="CONVERGD")
    with pytest.raises(ValueError, match="CONVERGD"):
        harness.prepare(c, SEED)


def test_calls_count_no_iterations():
    c = small_cell("polybench.mvt")
    kind = harness.prepare(c, SEED)
    m, line = measured(c, kind)
    assert line["correct"], line["checks"]
    assert m.rec["iterations"] is None
    assert m.rec["traced_iterations"] is None
    assert m.rec["iteration_bytes"] is None
    assert m.rec["iteration_flops"] is None


@pytest.mark.parametrize("program, want", [
    # A (96 x 96) read once; x, r, p (96 x s) each read and written;
    # the product's 2 n² s flops and 10 n s for the two dots and three
    # vector updates
    ("cg", (96 * 96 * 4 + 6 * 96 * 4, 2 * 96 * 96 + 10 * 96)),
    ("block_cg", (96 * 96 * 4 + 6 * 96 * 4 * 4,
                  2 * 96 * 96 * 4 + 10 * 96 * 4))])
def test_iteration_work_counts_the_least_bytes_of_one_iteration(program,
                                                                want):
    c = solve_cell(program)
    assert c.module.iteration_work(c.config, program) == want


def test_a_configuration_may_give_no_iteration_work_and_scope_work(
        monkeypatch):
    c = solve_cell("cg")
    monkeypatch.delattr(c.module, "iteration_work")
    m, _ = measured(c, harness.prepare(c, SEED))
    assert m.rec["iteration_bytes"] is None
    assert m.rec["iteration_flops"] is None
    assert m.rec["scope_work"] is None
    work = {"cg_matvec.g0": (96 * 96 * 4, 2 * 96 * 96)}
    monkeypatch.setattr(c.module, "scope_work",
                        lambda cfg, name: work, raising=False)
    m, _ = measured(c, harness.prepare(c, SEED))
    assert m.rec["scope_work"] == work


def test_only_hlo_texts_refusal_of_a_loop_program_reads_as_no_text():
    c = solve_cell("cg")
    kind = harness.prepare(c, SEED)
    with pytest.raises(TypeError, match="for dataflow programs"):
        kind.exe.hlo_text(**kind.fixed, **kind.pool[0])
    assert kind.hlo_text() is None

    class Broken:
        @staticmethod
        def hlo_text(**inputs):
            raise TypeError("an input of the wrong type")

    kind.exe = Broken()
    with pytest.raises(TypeError, match="wrong type"):
        kind.hlo_text()
    kind.exe = None                 # the plain reference in its place
    assert kind.hlo_text() is None
