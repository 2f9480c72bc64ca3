"""The harness finds every part of a cell by name, the work functions
count the bytes each cell cannot do without, the data and the plain
references agree with what the device is given, and the measurement
path refuses to run anywhere but on a TPU in a checkout.

The per-cell checks are functions of a `harness.Cell` (`check_*`), run
on every cell of `BENCHMARK.json` at its configuration's `SMALL`, so
that a cell of a new configuration needs no edit here
(`test_bench_room.py` shows it)."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import gen, harness, kinds, peaks, scopes  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCHED = [w["name"] for w in BM["workloads"]]
CELLS = BENCHED
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
GROUP = re.compile(r"^.+\.g\d+$")
# room for the rounding of sums of nanoseconds turned into seconds
ROUND = 1 + 1e-9
# the tests' own configuration of the `solves` kind
SPD_SMALL = harness.BENCH / "tests" / "spd-small.py"


def small_sizes(module) -> dict:
    """The configuration module's `SMALL`: the sizes, merged over its
    configuration, at which the tests run its cells on the CPU."""
    sizes = getattr(module, "SMALL", None)
    if not isinstance(sizes, dict):
        raise AttributeError(
            f"{module.__file__} gives no SMALL: a dict of the sizes a "
            f"test run on the CPU can hold, merged over the "
            f"configuration ({{}} where it holds them already)")
    return sizes


def small_cell(name: str, root: pathlib.Path = ROOT) -> harness.Cell:
    c = harness.cell(name, root=root)
    c.config = {**c.config, **small_sizes(c.module)}
    return c


def test_every_configuration_gives_small_sizes():
    files = [ROOT / c["file"] for c in BM["configs"]]
    for path in [f.with_suffix(".py") for f in files] + [SPD_SMALL]:
        module = harness.load_module(path)
        cfg = json.loads(path.with_suffix(".json").read_text())
        assert set(small_sizes(module)) <= set(cfg), path
    bare = type(sys)("no_small")
    bare.__file__ = "no-small.py"
    with pytest.raises(AttributeError, match="no-small.py gives no SMALL"):
        small_sizes(bare)


def check_parts(c: harness.Cell) -> None:
    """Every part of the cell is found by name."""
    assert c.chips in (1, 4)
    assert c.traffic["kind"] in kinds.KINDS
    metrics = {m["name"] for m in c.end_to_end + c.per_layer}
    assert "setup_s" in metrics and len(c.end_to_end) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        reader = harness.load_module(c.root / "bench" / "metrics"
                                     / f"{m['name']}.py")
        assert callable(reader.read)


def test_every_part_of_every_cell_is_found_by_name():
    for name in BENCHED:
        check_parts(harness.cell(name))
    for m in BM["end_to_end"] + BM["per_layer"]:
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        assert callable(reader.read)


def check_well_formed(bm: dict, root: pathlib.Path) -> None:
    benched = [w["name"] for w in bm["workloads"]]
    e2e = {m["name"] for m in bm["end_to_end"]}
    names = ([c["name"] for c in bm["configs"]] + benched + list(e2e)
             + [m["name"] for m in bm["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", benched)) <= set(benched)
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        # a metric's cells all report the end-to-end metric it moves
        moved = next(e for e in bm["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", benched))
    for w in bm["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    for c in bm["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert c["file"].startswith("bench/")


def test_names_units_and_moves_are_well_formed():
    check_well_formed(BM, ROOT)


def test_work_functions_count_the_least_bytes_of_each_cell():
    def work(cell):
        c = harness.cell(cell)
        return c.module.work(c.config, c.traffic["program"])

    # mvt: A once, y1 y2 x1 x2 read, x1 x2 written
    assert work("polybench.mvt") == (4000 * 4000 * 4 + 6 * 4000 * 4,
                                     4 * 4000 * 4000 + 2 * 4000)
    # gesummv: A and B once, x read, y written
    assert work("polybench.gesummv") == (2 * 2800 * 2800 * 4 + 2 * 2800 * 4,
                                         4 * 2800 * 2800 + 3 * 2800)


def test_host_and_device_make_the_same_data_from_a_large_seed():
    import jax
    import jax.numpy as jnp

    seed = 2 ** 31 + 12345
    k0, k1 = gen.key(seed, 3)
    index = jnp.arange(600, dtype=jnp.uint32).reshape(20, 30)
    dev = jax.jit(lambda a, b: gen.uniform_from_key(jnp, a, b, index))(
        jnp.uint32(k0), jnp.uint32(k1))
    host = gen.uniform(np, seed, 3, (20, 30))
    np.testing.assert_array_equal(np.asarray(dev), host)
    assert host.min() >= -1 and host.max() < 1
    assert not np.array_equal(host, gen.uniform(np, seed + 1, 3, (20, 30)))


def test_polybench_reference_matches_the_device_inputs():
    c = small_cell("polybench.gesummv")
    cfg, mod = c.config, c.module
    mats, pool = mod.inputs(cfg, "gesummv", 9, 3)
    a, bm = (np.asarray(mats[k], np.float64) for k in ("A", "B"))
    x = np.asarray(pool[2]["x"], np.float64)
    want = mod.reference(cfg, "gesummv", 9, [2])["y"][0]
    np.testing.assert_allclose(want, 1.5 * a @ x + 1.2 * bm @ x, rtol=1e-12)


def check_untraced(c: harness.Cell) -> tuple:
    """A small run of the cell is correct and reports its end-to-end
    metrics."""
    kind = harness.prepare(c, 2 ** 31 + 7)
    m = harness.measure(kind, 0.2, trace=False, setup_s=1.0,
                        log=lambda s: None)
    line = harness.result_line(c, m, trace=False)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {e["name"] for e in c.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert m.rec["scope_s"] is None and m.rec["trace"] is None
    return m, line


@pytest.mark.parametrize("name", CELLS)
def test_small_cell_runs_and_is_correct_on_the_cpu(name):
    check_untraced(small_cell(name))


TRACED = [m["name"] for m in BM["per_layer"]
          if m["source"] == "device_trace"]


@pytest.fixture(scope="module")
def untraced_rec() -> dict:
    """What `harness.measure` writes for an untraced `calls` run, with a
    TPU's peaks, so that no reader finds nothing only for want of
    them."""
    c = small_cell("polybench.mvt")
    m = harness.measure(harness.prepare(c, 2 ** 31 + 9), 0.0, trace=False,
                        setup_s=1.0, log=lambda s: None)
    return {**m.rec, "peaks": peaks.peaks("TPU v5 lite")}


def test_the_untraced_record_holds_every_key(untraced_rec):
    assert untraced_rec["traced_requests"] is None
    for key in ("iterations", "traced_iterations", "iteration_bytes",
                "iteration_flops", "trace", "scope_s", "scope_work"):
        assert key in untraced_rec and untraced_rec[key] is None, key


@pytest.mark.parametrize("metric", TRACED)
def test_device_readers_find_nothing_without_a_trace(metric, untraced_rec):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    assert reader.read(dict(untraced_rec)) is None


# what a traced run on the CPU reads, for each metric that lists the
# cell: the host clock's numbers from the untraced window
READ_OFF_A_TPU = {
    "dispatch_us": lambda v, rec: v == pytest.approx(
        1e6 * rec["dispatch_s"] / rec["requests"]),
    "device_idle.call": lambda v, rec: 0 <= v < 100,
}


def layer_scopes(hlo_text: str, under: str = "") -> set:
    """The fusion-group scopes (`<program>.g<i>`) of a compiled text,
    or of those of its ops whose path holds the scope `under`."""
    return {name for ops in scopes.op_paths([hlo_text]).values()
            for path in ops.values() if not under or under in
            path.split("/") for name in path.split("/") if GROUP.match(name)}


def check_traced(c: harness.Cell, seconds: float = 0.2) -> tuple:
    """A small traced run of the cell is correct, reads the host clock
    from its untraced window and the device from its trace, and times
    each fusion group of an entry that gives its compiled text."""
    kind = harness.prepare(c, 2 ** 31 + 11)
    m = harness.measure(kind, seconds, trace=True, setup_s=1.0,
                        log=lambda s: None)
    rec = m.rec
    assert rec["requests"] >= 1 and rec["traced_requests"] >= 1
    assert m.device["window_s"] > 0 and m.device["busy_s"] > 0
    line = harness.result_line(c, m, trace=True)
    assert line["correct"], line["checks"]
    assert line["attempted"] == rec["requests"] + rec["traced_requests"]
    listed = {p["name"] for p in c.per_layer}
    assert set(line["metrics"]) <= listed
    for name, reads in READ_OFF_A_TPU.items():
        if name in listed:
            assert reads(line["metrics"][name]["value"], rec), name
    # off a TPU there are no peaks, so no share of one is read
    assert not [n for n in line["metrics"] if "roofline" in n or "mfu" in n]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not harness.TRACE_DIR.exists()
    text = kind.hlo_text()
    if text is None:
        assert rec["scope_s"] is None
    else:
        times = rec["scope_s"]
        groups = layer_scopes(text)
        assert groups and groups <= set(times)
        assert all(times[g] > 0 for g in groups)
        # the groups run one after another, so their sum is busy time;
        # a pad runs inside its group, so it is counted in both
        assert sum(times[g] for g in groups) <= m.device["busy_s"] * ROUND
        padded = sum(times[g] for g in layer_scopes(text, under="pad"))
        assert times.get("pad", 0.0) <= padded * ROUND
    return m, line


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reads_the_host_clock_from_its_untraced_window(name):
    check_traced(small_cell(name))


def test_the_sample_is_uniform_over_calls_and_drawn_from_the_seed():
    def sampled(seed):
        k = object.__new__(kinds.Calls)
        k.size, k.sample, k.count = 4, [], 0
        k.pool = [None] * 3
        k._rng = __import__("random").Random(seed)
        for i in range(40):
            k.keep(i, None)
        return [i for i, _, _ in k.sample]

    assert sampled(5) == sampled(5) and sampled(5) != sampled(6)
    hits = np.zeros(40)
    for seed in range(2000):
        hits[sampled(seed)] += 1
    # each of 40 calls is kept with chance 4/40: 200 of 2000 draws
    assert hits.min() > 140 and hits.max() < 260


@pytest.mark.parametrize("ahead, finished", [(0, False), (3, False),
                                              (3, True)])
def test_the_loop_keeps_ahead_in_flight_and_waits_for_every_request(
        ahead, finished):
    """At most `ahead` requests stay outstanding beyond the newest, and
    fewer where those at the head have already finished."""
    from bench import loop

    outstanding, most, waited, done = set(), [0], [], []

    def send(i):
        outstanding.add(i)
        most[0] = max(most[0], len(outstanding))
        return i

    def wait(out):
        outstanding.discard(out)
        waited.append(out)

    win = loop.run(send, wait, lambda out: finished,
                   lambda i, out: done.append((i, out)),
                   seconds=0.05, ahead=ahead)
    assert win.requests > ahead and not outstanding
    assert most[0] == (1 if finished else ahead + 1)
    assert waited == list(range(win.requests))
    assert done == [(i, i) for i in range(win.requests)]


def test_seeds_outside_64_bits_are_refused():
    assert gen.key(2 ** 64 - 1, 0) != gen.key(0, 0)
    for bad in (-1, 2 ** 64):
        with pytest.raises(ValueError):
            gen.key(bad, 0)


def test_peaks_of_an_unknown_device_are_an_error():
    assert peaks.peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def _run(args, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


ARGS = ["--workload", "polybench.mvt", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_run_refuses_without_a_tpu():
    p = _run(ARGS, ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(ARGS, tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
