"""What decides `correct` fails what it must: the control (the plain
reference in the library's place, one precision below the
configuration's) and each fault a cell can have, planted under the
timed path. Everything but the look for a chip runs as in a real run,
at sizes a test run can hold. The control's readings at the cells'
own sizes on the chip are in PERF.md."""
from __future__ import annotations

import dataclasses
import pathlib
import sys
from collections.abc import Mapping

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from test_bench_harness import CELLS, small_cell  # noqa: E402

SEED = 2 ** 33 + 3
# the operand whose product each program's fault leaves out
LEFT_OUT = {"mvt": "y2", "gesummv": "B"}


def run(c, kind) -> dict:
    m = harness.measure(kind, 0.2, trace=False, setup_s=1.0,
                        log=lambda s: None)
    return harness.result_line(c, m, trace=False)


def failed_checks(line) -> set:
    return {n for n, chk in line["checks"].items()
            if chk["value"] > chk["limit"]}


def check_reference(c) -> None:
    line = run(c, harness.prepare(c, SEED, precision="highest"))
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_in_the_programs_place_is_correct(name):
    check_reference(small_cell(name))


def check_control(c) -> None:
    line = run(c, harness.prepare(c, SEED, precision="high"))
    assert not line["correct"]
    assert "err" in failed_checks(line)


@pytest.mark.parametrize("name", CELLS)
def test_control_one_precision_below_is_not_correct(name):
    check_control(small_cell(name))


def _altered(out):
    """One element of the answer changed where it is produced: of the
    first output of a call, of the solution of a solve."""
    if isinstance(out, Mapping):
        name = sorted(out)[0]
        return {**out, name: out[name].at[0].multiply(1.001)}
    return dataclasses.replace(out, x=out.x.at[0].multiply(1.001))


def check_altered(c) -> None:
    kind = harness.prepare(c, SEED)
    entry = kind.entry
    kind.entry = lambda **kw: _altered(entry(**kw))
    assert not run(c, kind)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_an_altered_answer_is_not_correct(name):
    check_altered(small_cell(name))


def check_stale(c) -> None:
    """Each request returns what the one before it should have: the
    fault of a program that hands back a stale result."""
    kind = harness.prepare(c, SEED)
    entry, last = kind.entry, []

    def stale(**kw):
        out = entry(**kw)
        last.append(out)
        return last[-2] if len(last) > 1 else out

    kind.entry = stale
    line = run(c, kind)
    assert not line["correct"]
    assert line["failed"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_to_another_call_is_not_correct(name):
    check_stale(small_cell(name))


def _half(v):
    """The first half of the last axis zeroed: half of a panel's
    columns, half of a vector's entries."""
    return v.at[..., :v.shape[-1] // 2].set(0)


def check_half_left_out(c) -> None:
    """One of the program's two matrix products is left out, its
    operand reaching the program as zeros (`LEFT_OUT`); in a program
    not listed there, half of each request's own inputs."""
    kind = harness.prepare(c, SEED)
    entry = kind.entry
    operand = LEFT_OUT.get(c.traffic["program"])
    if operand is not None:
        kind.entry = lambda **kw: entry(**{**kw, operand: 0 * kw[operand]})
    else:
        own = set(kind.pool[0])
        kind.entry = lambda **kw: entry(**{
            k: _half(v) if k in own else v for k, v in kw.items()})
    assert "err" in failed_checks(run(c, kind))


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_work_left_out_is_not_correct(name):
    check_half_left_out(small_cell(name))


# every check of this file, by name, for a cell built elsewhere
CELL_CHECKS = {"reference": check_reference, "control": check_control,
               "altered": check_altered, "stale": check_stale,
               "half_left_out": check_half_left_out}
