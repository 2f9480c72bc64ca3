"""A cell of a new configuration is new files and new entries, with no
file of the benchmark edited: a copy of the benchmark takes a `solves`
cell of the tests' own configuration `spd-small`, with a traffic mix
and a metric reader that only it has, and every per-cell check of the
tests passes on it, through the same functions as on the cells of
`BENCHMARK.json`."""
from __future__ import annotations

import filecmp
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import test_bench_checks as checks  # noqa: E402
import test_bench_harness as bench  # noqa: E402

CELL = "spd-small.block_cg"
MIX = "spd-small.block_cg"
READER = "iters.call"
NEW_FILES = {f"bench/traffic/{MIX}.json", f"bench/metrics/{READER}.py"}


@pytest.fixture(scope="module")
def room(tmp_path_factory) -> pathlib.Path:
    """A checkout's benchmark, copied, with the new cell's entries in
    `BENCHMARK.json` and its mix and reader as new files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({
        "name": "spd-small", "source": "bench/tests/spd-small.py",
        "file": "bench/tests/spd-small.json", "reduced": [],
        "why": "a small seeded SPD system, solved by block-CG"})
    bm["workloads"].append({
        "name": CELL, "config": "spd-small", "traffic": MIX, "chips": 1,
        "why": "n=96, s=4: one block-CG solve a request, 2 in flight"})
    bm["per_layer"].append({
        "name": READER, "unit": "iterations", "better": "lower",
        "source": "program_counter", "layer": "loop driver",
        "moves": "calls_per_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench" / "traffic" / f"{MIX}.json").write_text(json.dumps(
        {"kind": "solves", "program": "block_cg", "pool": 3, "ahead": 2,
         "sample": 4}))
    (root / "bench" / "metrics" / f"{READER}.py").write_text(
        "from bench import reading\n\n\n"
        "def read(rec):\n"
        "    return reading.iterations_per_request(rec)\n")
    return root


def test_the_new_cell_is_new_files_and_entries_only(room):
    ours = {p.relative_to(ROOT).as_posix()
            for p in (ROOT / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}
    theirs = {p.relative_to(room).as_posix()
              for p in (room / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    assert theirs - ours == NEW_FILES and ours <= theirs
    assert all(filecmp.cmp(ROOT / f, room / f, shallow=False) for f in ours)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((room / "BENCHMARK.json").read_text())
    for key, entries in bm.items():
        assert new[key][:len(entries)] == entries \
            if isinstance(entries, list) else new[key] == entries
    bench.check_well_formed(new, room)


def _traced(c):
    m, line = bench.check_traced(c, seconds=0.05)
    assert m.rec["scope_s"] is None      # a loop program gives no text
    assert line["metrics"][READER]["value"] == pytest.approx(
        m.rec["iterations"] / m.rec["requests"])


CELL_CHECKS = {"parts": bench.check_parts,
               "untraced": bench.check_untraced,
               "traced": _traced,
               **checks.CELL_CHECKS}


@pytest.mark.parametrize("check", CELL_CHECKS)
def test_every_per_cell_check_passes_on_the_new_cell(room, check,
                                                     tmp_path, monkeypatch):
    from bench import harness
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    c = bench.small_cell(CELL, root=room)
    assert c.root == room and c.traffic["kind"] == "solves"
    CELL_CHECKS[check](c)
