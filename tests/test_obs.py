"""repro.obs: registry semantics, JSONL + CLI, and the instrumentation
threaded through lowering / fusion / codegen / solver driver — plus the
`Executable.profile` drift report for both program kinds.

Tests that need recording ON use `obs.capture()` so nothing leaks into
the process registry other tests (and the disabled-by-default gate in
test_perf_paths) rely on.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro import blas, obs
from repro.obs.__main__ import main as obs_cli
from repro.solvers import specs

# uniquely named copies of the canonical anchored chain: a cached
# compile skips the pipeline entirely (and so emits no spans/events),
# so instrumentation tests must force a fresh lowering
def _gemv_chain(name):
    return {
        "name": name,
        "routines": [
            {"blas": "gemv", "name": "mv",
             "scalars": {"alpha": 1.0, "beta": 0.0},
             "inputs": {"A": "A", "x": "p", "y": "y0"},
             "connections": {"out": "up.x"}, "outputs": {"out": "q"}},
            {"blas": "axpy", "name": "up",
             "scalars": {"alpha": {"input": "neg_alpha"}},
             "inputs": {"y": "r"},
             "connections": {"out": "rn.x"},
             "outputs": {"out": "r_next"}},
            {"blas": "nrm2", "name": "rn", "outputs": {"out": "rnorm"}},
        ],
    }


def _cg_ops(n=16):
    return {"A": jnp.eye(n, dtype=jnp.float32) * 2.0,
            "b": jnp.ones(n, jnp.float32),
            "x0": jnp.zeros(n, jnp.float32)}


# ---------------------------------------------------------------------------
# Registry core
# ---------------------------------------------------------------------------


def test_disabled_by_default_records_nothing():
    assert not obs.enabled()
    assert obs.span("x") is obs.NULL_SPAN
    obs.counter("c")
    obs.event("e")
    assert obs.records() == []
    assert obs.counters() == {}


def test_span_counter_event_record_shapes():
    with obs.capture() as reg:
        with obs.span("outer", program="p"):
            with obs.span("inner"):
                pass
            obs.counter("hits", 2, mode="dataflow")
            obs.event("decided", reason="because")
        recs = list(reg.records)
    inner, ctr, evt, outer = recs       # spans record on exit
    assert inner["kind"] == "span" and inner["name"] == "inner"
    assert inner["path"] == "outer/inner"       # nesting is recorded
    assert inner["dur_s"] >= 0.0
    assert outer["name"] == "outer"
    assert outer["attrs"] == {"program": "p"}
    assert outer["dur_s"] >= inner["dur_s"]
    assert ctr == {"kind": "counter", "name": "hits", "n": 2,
                   "attrs": {"mode": "dataflow"}}
    assert evt["kind"] == "event" and evt["name"] == "decided"
    assert evt["attrs"] == {"reason": "because"}
    assert reg.counters == {"hits": 2}


def test_capture_is_scoped():
    with obs.capture() as inner_reg:
        obs.event("inside")
        assert obs.enabled()
        assert len(inner_reg.records) == 1
    assert not obs.enabled()        # outer (disabled) registry restored
    assert obs.records() == []      # nothing leaked


def test_enable_disable_reset():
    obs.enable()
    try:
        obs.event("a")
        obs.counter("c")
        assert len(obs.records()) == 2
        obs.reset()
        assert obs.records() == [] and obs.counters() == {}
    finally:
        obs.disable()
        obs.reset()


# ---------------------------------------------------------------------------
# JSONL export + CLI
# ---------------------------------------------------------------------------


def _write_jsonl(tmp_path):
    with obs.capture() as reg:
        with obs.span("work", stage="s"):
            obs.counter("widgets", 3)
        obs.event("done", ok=True)
        path = reg.export_jsonl(tmp_path / "trace.jsonl")
    return path


def test_jsonl_roundtrip_and_summary(tmp_path):
    path = _write_jsonl(tmp_path)
    recs = obs.load_jsonl(path)
    assert [r["kind"] for r in recs] == ["counter", "span", "event"]
    s = obs.summarize_records(recs)
    assert s["spans"]["work"]["count"] == 1
    assert s["counters"]["widgets"] == 3
    assert s["events"]["done"] == 1
    assert "work" in obs.format_summary(s)


def test_cli_summarize_trace_diff(tmp_path, capsys):
    path = str(_write_jsonl(tmp_path))
    assert obs_cli(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "work" in out and "widgets" in out
    assert obs_cli(["trace", path, "--kind", "span", "--limit", "5"]) == 0
    assert "[span] work" in capsys.readouterr().out
    assert obs_cli(["diff", path, path]) == 0
    assert "B/A" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Pipeline instrumentation: lowering spans, cache counters, fusion
# decisions, codegen group tags
# ---------------------------------------------------------------------------


def test_lowering_spans_and_cache_counters():
    spec = _gemv_chain("obs_probe_lowering")
    with obs.capture() as reg:
        blas.compile(spec)                       # miss: full pipeline
        blas.compile(spec)                       # hit: cached IR
        recs = list(reg.records)
        ctrs = dict(reg.counters)
    span_names = {r["name"] for r in recs if r["kind"] == "span"}
    assert {"lowering.parse", "lowering.graph", "lowering.infer",
            "lowering.fuse", "lowering.place",
            "lowering.emit"} <= span_names
    assert ctrs.get("lowering.cache.miss", 0) == 1
    assert ctrs.get("lowering.cache.hit", 0) == 1
    done = [r for r in recs if r["kind"] == "event"
            and r["name"] == "lowering.done"]
    assert len(done) == 1                        # once per fresh lower
    assert done[0]["attrs"]["program"] == "obs_probe_lowering"


def test_fusion_decision_events():
    """The anchored chain absorbs its level-1 consumers: the planner's
    reasoning surfaces as one decision event per anchor candidate."""
    with obs.capture() as reg:
        blas.compile(_gemv_chain("obs_probe_fusion"))
        evts = [r for r in reg.records if r["kind"] == "event"
                and r["name"] in ("fusion.absorb", "fusion.reject")]
    absorbs = [e for e in evts if e["name"] == "fusion.absorb"]
    assert absorbs, "gemv anchor must absorb its axpy/nrm2 consumers"
    for e in evts:
        a = e["attrs"]
        assert a["program"] == "obs_probe_fusion"
        assert a["anchor"] == "mv"
        assert a["direction"] in ("down", "up")
        if e["name"] == "fusion.reject":
            assert a["reason"]


def test_codegen_group_events_tag_every_group():
    with obs.capture() as reg:
        exe = blas.compile(_gemv_chain("obs_probe_codegen"))
        evts = [r for r in reg.records if r["kind"] == "event"
                and r["name"] == "codegen.group"]
    assert len(evts) == len(exe._impl.ir.groups)
    kinds = {e["attrs"]["kind"] for e in evts}
    assert "anchored" in kinds                  # the gemv group
    anchored = [e for e in evts if e["attrs"]["kind"] == "anchored"]
    assert anchored[0]["attrs"]["anchor"] == "mv"
    assert "mv" in anchored[0]["attrs"]["routines"]


# ---------------------------------------------------------------------------
# Solver telemetry (satellite: history + per-solve export)
# ---------------------------------------------------------------------------


def test_solver_result_event_and_history_trimmed():
    exe = blas.compile(specs.CG_LOOP, max_iters=8)
    ops = _cg_ops()
    with obs.capture() as reg:
        res = exe.run(**ops)
        evts = [r for r in reg.records if r["kind"] == "event"
                and r["name"] == "solver.result"]
    assert len(evts) == 1
    a = evts[0]["attrs"]
    assert a["program"] == "cg"
    assert a["iterations"] == int(res.iterations)
    assert a["converged"] == bool(res.converged)
    assert a["final_residual"] == pytest.approx(float(res.residual))
    # history_trimmed drops the NaN tail past the stopping point
    trimmed = res.history_trimmed()
    assert len(trimmed) == int(res.iterations) + 1
    assert not jnp.isnan(jnp.asarray(trimmed)).any()
    assert jnp.isnan(res.history).sum() == len(res.history) - len(trimmed)


def test_solver_result_event_batched():
    exe = blas.compile(specs.CG_LOOP, max_iters=8)
    n, nrhs = 16, 3
    A = jnp.eye(n, dtype=jnp.float32) * 2.0
    B = jnp.stack([jnp.ones(n), 2.0 * jnp.ones(n),
                   3.0 * jnp.ones(n)]).astype(jnp.float32)
    with obs.capture() as reg:
        res = exe.batched(A=A, b=B, x0=jnp.zeros_like(B),
                          axes={"A": None})
        evts = [r for r in reg.records if r["kind"] == "event"
                and r["name"] == "solver.result"]
    assert len(evts) == 1
    a = evts[0]["attrs"]
    assert a["batch"] == nrhs
    assert a["iterations"] == [int(k) for k in res.iterations]
    assert a["converged"] == [bool(c) for c in res.converged]
    trimmed = res.history_trimmed()
    assert len(trimmed) == nrhs
    for lane, k in enumerate(res.iterations):
        assert len(trimmed[lane]) == int(k) + 1


# ---------------------------------------------------------------------------
# profile(): the modeled-vs-measured drift report (acceptance criteria)
# ---------------------------------------------------------------------------


def test_profile_dataflow_axpydot():
    import repro.core as core
    exe = blas.compile(core.AXPYDOT_SPEC)
    n = 64
    rep = exe.profile({"v": n, "w": n, "u": n}, iters=2)
    assert rep.kind == "dataflow" and rep.iters == 2
    assert len(rep.rows) == len(exe._impl.ir.groups)
    row = rep.rows[0]
    assert set(row.routines) == {"zcalc", "zdot"}   # fused group
    assert row.modeled_bytes > 0
    assert row.modeled_time_s > 0
    assert row.measured_s is not None and row.measured_s > 0
    assert row.drift == pytest.approx(
        row.measured_s / row.modeled_time_s)
    # modeled bytes apply the fusion savings in dataflow mode
    cr = exe.cost_report({"v": n, "w": n, "u": n})
    assert rep.modeled_bytes == cr.bytes
    j = rep.to_json()
    assert j["drift"] == rep.drift
    assert j["groups"][0]["routines"] == list(row.routines)
    json.dumps(j)                                # JSON-serializable


def test_profile_loop_cg():
    exe = blas.compile(specs.CG_LOOP, max_iters=4)
    rep = exe.profile({"A": (16, 16), "b": 16, "x0": 16}, iters=2)
    assert rep.kind == "loop"
    programs = {r.program for r in rep.rows}
    assert "cg_matvec" in programs               # the gemv body stage
    assert all(r.measured_s is not None for r in rep.rows)
    assert all((r.drift or 0) > 0 for r in rep.rows)
    assert rep.modeled_bytes > 0
    assert str(rep)                              # table renders


def test_profile_runs_without_enabling_obs():
    exe = blas.compile(specs.CG_LOOP, max_iters=4)
    assert not obs.enabled()
    exe.profile({"A": (16, 16), "b": 16, "x0": 16}, iters=1)
    assert not obs.enabled()
    assert obs.records() == []                   # scoped, no leakage


def test_profile_rejects_bad_iters_and_class_solvers():
    from repro.solvers import BiCGStab
    exe = blas.compile(specs.CG_LOOP)
    with pytest.raises(ValueError):
        exe.profile({"A": (8, 8), "b": 8, "x0": 8}, iters=0)
    wrapped = blas.Executable.from_solver(BiCGStab())
    with pytest.raises(TypeError):
        wrapped.profile({"A": (8, 8), "b": 8})


# ---------------------------------------------------------------------------
# The profiler bridge, aggregates, the GC hook, compile spans and the
# group / pad name scopes
# ---------------------------------------------------------------------------


def _profile(tmp_path, body):
    """Run `body` under a profiler session; the session's ProfileData."""
    import glob

    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return ProfileData.from_file(path)


def _host_events(pd, names):
    """{line name: [(name, start_ns, end_ns, stats)]} of host events
    named in `names`."""
    import warnings

    out = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            with warnings.catch_warnings():
                # the profiler's stats type lacks __module__
                warnings.simplefilter("ignore", DeprecationWarning)
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name in names]
            if evs:
                out[line.name] = evs
    return out


def test_spans_nest_on_the_host_line_of_a_profiler_trace(tmp_path):
    import time

    def body():
        with obs.span("obs_probe.outer", call=3):
            with obs.span("obs_probe.inner"):
                time.sleep(0.005)
            time.sleep(0.002)

    with obs.capture() as reg:
        pd = _profile(tmp_path, body)
        recs = {r["name"]: r for r in reg.records}
    lines = _host_events(pd, {"obs_probe.outer", "obs_probe.inner"})
    assert len(lines) == 1                  # one thread ran both
    (evs,) = lines.values()
    ev = {name: (s, e, st) for name, s, e, st in evs}
    (os_, oe, ostats), (is_, ie, _) = (ev["obs_probe.outer"],
                                       ev["obs_probe.inner"])
    assert os_ <= is_ and ie <= oe          # nested by its parent
    assert ostats["call"] == 3              # attributes are metadata
    assert recs["obs_probe.inner"]["path"] == "obs_probe.outer/obs_probe.inner"
    for name, (s, e, _) in ev.items():
        dur = recs[name]["dur_s"]
        assert abs((e - s) * 1e-9 - dur) <= max(50e-6, 0.05 * dur)


def test_blas_run_aggregate_counts_calls_and_appends_no_record():
    import repro.core as core
    exe = blas.compile(core.AXPYDOT_SPEC)
    n = 64
    ops = {"neg_alpha": -0.5, "v": jnp.ones(n), "w": jnp.ones(n),
           "u": jnp.ones(n)}
    exe.run(**ops)                          # compiles
    before = obs.aggregates()["blas.run"]
    with obs.capture() as reg:
        for _ in range(4):
            exe.run(**ops)
        assert reg.records == []            # no record per call
    for _ in range(3):
        exe.run(**ops)                      # counted with recording off
    after = obs.aggregates()["blas.run"]
    assert after["count"] - before["count"] == 7
    assert after["total_s"] > before["total_s"]
    assert after["max_s"] >= before["max_s"]
    assert obs.records() == []


def test_gc_hook_counts_pauses_and_annotates_them(tmp_path):
    import gc

    obs.enable()
    try:
        hook = obs.core._gc_hook
        assert gc.callbacks.count(hook) == 1
        before = obs.aggregates().get("host.gc.gen2", {"count": 0})
        gc.collect()
        assert obs.aggregates()["host.gc.gen2"]["count"] \
            == before["count"] + 1
        pd = _profile(tmp_path, gc.collect)
        (evs,) = _host_events(pd, {"host.gc"}).values()
        assert evs[0][3]["generation"] == 2
    finally:
        obs.disable()
        obs.reset()
    assert hook not in gc.callbacks


def test_compile_spans_and_their_union():
    obs.enable()
    try:
        jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)(jnp.ones(7))
        spans = [r for r in obs.records() if r["name"] == "jax.compile"]
    finally:
        obs.disable()
        obs.reset()
    assert {s["attrs"]["stage"] for s in spans} == {"trace", "lower",
                                                    "compile"}
    total = obs.compile_seconds(spans)
    assert 0 < total <= sum(s["dur_s"] for s in spans) + 1e-9
    # disabled: the listener is gone, so a new compile records nothing
    jax.jit(lambda x: jnp.cos(x) - 0.5)(jnp.ones(7))
    assert obs.records() == []


def test_compile_seconds_counts_nested_and_overlapping_spans_once():
    def sp(name, t, dur):
        return {"kind": "span", "name": name, "t": t, "dur_s": dur}

    recs = [sp("jax.compile", 0.0, 1.0),       # a trace ...
            sp("jax.compile", 0.2, 0.3),       # ... with one nested
            sp("lowering.emit", 0.9, 0.6),     # overlaps its end
            sp("solver.solve", 0.0, 9.0),      # not a compile
            sp("jax.compile", 3.0, 0.5)]
    assert obs.compile_seconds(recs) == pytest.approx(2.0)
    assert obs.compile_seconds(recs, t0=0.5, t1=3.25) == pytest.approx(
        1.25)


def test_export_writes_one_summary_record_per_aggregate(tmp_path):
    obs.aggregate("obs_probe.agg").add(0.25)
    obs.aggregate("obs_probe.agg").add(0.5)
    path = obs.export(str(tmp_path / "agg.jsonl"))
    recs = [r for r in obs.load_jsonl(path) if r["kind"] == "aggregate"]
    mine = [r for r in recs if r["name"] == "obs_probe.agg"]
    assert mine == [{"kind": "aggregate", "name": "obs_probe.agg",
                     "count": 2, "total_s": 0.75, "max_s": 0.5}]
    s = obs.summarize_records(recs)
    assert s["aggregates"]["obs_probe.agg"]["mean_s"] == 0.375
    assert "aggregates:" in obs.format_summary(s)


def test_mvt_ops_carry_their_group_and_pad_scopes():
    import re

    b = blas.program("obs_probe_mvt")
    b.gemv(alpha=1.0, beta=1.0, A="A", x="y1", y="x1", out="x1_out")
    b.gemvt(alpha=1.0, beta=1.0, A="A", x="y2", y="x2", out="x2_out")
    exe = blas.compile(b, tiles="default")
    n = 300                                 # not a multiple of a block
    args = {"A": jnp.ones((n, n)),
            **{k: jnp.ones(n) for k in ("y1", "y2", "x1", "x2")}}
    text = jax.jit(exe._impl.ir.fn).lower(args).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    groups = {g for p in paths for g in re.findall(r"/(obs_probe_mvt\.g\d+)/",
                                                   p)}
    assert groups == {"obs_probe_mvt.g0", "obs_probe_mvt.g1"}
    assert any("/pad/" in p for p in paths)


def test_spans_annotate_when_recording_began_before_jax_was_imported(
        tmp_path):
    """`enable()` before `import jax`: collections that start while jax
    is half imported must not leave the profiler bridge off for good."""
    import os
    import pathlib
    import subprocess
    import sys

    code = f"""
import gc, glob
from repro import obs
obs.enable()
gc.set_threshold(50)             # collect often while jax imports
import jax
gc.set_threshold(700)
from jax.profiler import ProfileData
jax.profiler.start_trace({str(tmp_path)!r})
with obs.span("obs_probe.early"):
    pass
jax.profiler.stop_trace()
(path,) = glob.glob({str(tmp_path / "**" / "*.xplane.pb")!r}, recursive=True)
names = {{e.name for p in ProfileData.from_file(path).planes
         for line in p.lines for e in line.events}}
print("obs_probe.early" in names)
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(pathlib.Path(obs.__file__).parents[2])}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[-1] == "True"
