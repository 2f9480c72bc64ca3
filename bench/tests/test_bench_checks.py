"""What decides `correct` fails what it must: the control (the plain
reference in the library's place, one precision below the
configuration's) and each fault a cell can have, planted under the
timed path. Everything but the look for a chip runs as in a real run,
at sizes a test run can hold. The control's readings at the cells'
own sizes on the chip are in PERF.md."""
from __future__ import annotations

import pathlib
import sys

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


@pytest.mark.parametrize("name", CELLS)
def test_reference_in_the_programs_place_is_correct(name):
    c = small_cell(name)
    line = run(c, harness.prepare(c, SEED, precision="highest"))
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_one_precision_below_is_not_correct(name):
    c = small_cell(name)
    line = run(c, harness.prepare(c, SEED, precision="high"))
    assert not line["correct"]
    assert failed_checks(line) == {"err"}


def _altered(out):
    """One element of the answer changed where it is produced."""
    name = sorted(out)[0]
    return {**out, name: out[name].at[0].multiply(1.001)}


@pytest.mark.parametrize("name", CELLS)
def test_an_altered_answer_is_not_correct(name):
    c = small_cell(name)
    kind = harness.prepare(c, SEED)
    entry = kind.entry
    kind.entry = lambda **kw: _altered(entry(**kw))
    assert not run(c, kind)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_to_another_call_is_not_correct(name):
    """Each call returns what the call before it should have: the
    fault of a program that hands back a stale result."""
    c = small_cell(name)
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
def test_half_of_the_work_left_out_is_not_correct(name):
    """One of the program's two matrix products is left out: its
    operand reaches the program as zeros."""
    c = small_cell(name)
    kind = harness.prepare(c, SEED)
    entry, operand = kind.entry, LEFT_OUT[c.traffic["program"]]
    kind.entry = lambda **kw: entry(**{**kw, operand: 0 * kw[operand]})
    assert failed_checks(run(c, kind)) == {"err"}
