"""The harness finds every part of a cell by name, the work functions
count the bytes each cell cannot do without, the data and the plain
references agree with what the device is given, and the measurement
path refuses to run anywhere but on a TPU in a checkout."""
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

from bench import gen, harness, kinds, peaks  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCHED = [w["name"] for w in BM["workloads"]]
CELLS = BENCHED
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# sizes a test run can hold; every other key is the configuration's own
SMALL = {"polybench-xl": {"mvt": {"N": 300},
                          "gesummv": {"N": 200, "alpha": 1.5, "beta": 1.2}}}


def small_cell(name: str) -> harness.Cell:
    c = harness.cell(name)
    c.config = {**c.config, **SMALL[c.config["name"]]}
    return c


def test_every_part_of_every_cell_is_found_by_name():
    for name in BENCHED:
        c = harness.cell(name)
        assert c.chips == 1
        assert c.traffic["kind"] in kinds.KINDS
        metrics = {m["name"] for m in c.end_to_end + c.per_layer}
        assert "setup_s" in metrics and len(c.end_to_end) >= 2
        assert c.per_layer
    for m in BM["end_to_end"] + BM["per_layer"]:
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        assert callable(reader.read)


def test_names_units_and_moves_are_well_formed():
    e2e = {m["name"] for m in BM["end_to_end"]}
    names = ([c["name"] for c in BM["configs"]] + BENCHED + list(e2e)
             + [m["name"] for m in BM["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", BENCHED)) <= set(BENCHED)
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        # a metric's cells all report the end-to-end metric it moves
        moved = next(e for e in BM["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", BENCHED))
    for w in BM["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    for c in BM["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert c["file"].startswith("bench/")


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


@pytest.mark.parametrize("name", CELLS)
def test_small_cell_runs_and_is_correct_on_the_cpu(name):
    c = small_cell(name)
    kind = harness.prepare(c, 2 ** 31 + 7)
    m = harness.measure(kind, 0.2, trace=False, setup_s=1.0,
                        log=lambda s: None)
    line = harness.result_line(c, m, trace=False)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {e["name"] for e in c.end_to_end
                                    if name in e.get("workloads", [name])}
    assert all(v["value"] > 0 for v in line["metrics"].values())


TRACED = [m["name"] for m in BM["per_layer"]
          if m["source"] == "device_trace"]


@pytest.mark.parametrize("metric", TRACED)
def test_device_readers_find_nothing_without_a_trace(metric):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    rec = {"unit": "call", "requests": 10, "window_s": 1.0, "setup_s": 1.0,
           "dispatch_s": 0.01, "traced_requests": None, "work_bytes": 8,
           "work_flops": 8, "peaks": peaks.peaks("TPU v5 lite"),
           "trace": None}
    assert reader.read(rec) is None


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reads_the_host_clock_from_its_untraced_window(name):
    c = small_cell(name)
    kind = harness.prepare(c, 2 ** 31 + 11)
    m = harness.measure(kind, 0.2, trace=True, setup_s=1.0,
                        log=lambda s: None)
    rec = m.rec
    assert rec["requests"] >= 1 and rec["traced_requests"] >= 1
    assert m.device["window_s"] > 0 and m.device["busy_s"] > 0
    line = harness.result_line(c, m, trace=True)
    assert line["correct"], line["checks"]
    assert line["attempted"] == rec["requests"] + rec["traced_requests"]
    assert line["metrics"]["dispatch_us"]["value"] == pytest.approx(
        1e6 * rec["dispatch_s"] / rec["requests"])
    # off a TPU there are no peaks, so no roofline share is read
    assert "hbm_roofline.call" not in line["metrics"]
    assert 0 <= line["metrics"]["device_idle.call"]["value"] < 100
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not harness.TRACE_DIR.exists()


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
