"""The kinds of request a traffic mix can send, and how each is checked
against the plain reference.

A traffic file (`bench/traffic/<mix>.json`) names its `kind` and the
parameters below; the configuration's module (`bench/configs/
<config>.py`) supplies the data, the plain reference and the work
function. Nothing here knows a configuration by name.

`calls`: one request is one call of the dataflow program `program`,
  built by the configuration with the `repro.blas` builder and
  compiled once by `repro.blas.compile`, on inputs cycled from a pool
  of `pool` made at set-up. One client sends them with up to `ahead`
  calls in flight (`bench.loop`). A sample of `sample` calls, drawn
  from the seed, is checked against the float64 reference on the
  host.
  The configuration module gives `program(cfg, name)`, `inputs(cfg,
  name, seed, pool)`, `reference(cfg, name, seed, pool_indices)`,
  `work(cfg, name)`, `reference_program(cfg, name, precision)`,
  `LIMITS` and `SMALL` (the sizes a test run on the CPU can hold: a
  dict merged over the configuration). It may give `scope_work(cfg,
  name)`: {name scope: (bytes, flops)} that one call of the scope's
  ops cannot do without, for a fusion group (`<program>.g<i>`) or
  `pad` (`reading.scope_roofline_pct`).

`solves`: one request is one solve, one `Executable.run` of the loop
  program `program`. The configuration's `program(cfg, name)` returns
  a loop spec (or a builder loop) with its `rtol` and `max_iters`
  baked in, compiled once as for `calls`. `fixed` holds the resident
  operands (the matrix and any preconditioner data), the pool each
  request's right-hand-side panel and `x0`. The traffic parameters
  are those of `calls`, and `status`: the `repro.guard.status` name
  that every checked solve must end in (`"CONVERGED"` by default).
  A solve is in flight until its `x`, `iterations` and `status` are
  ready; `keep` adds each completed solve's `iterations` to the
  kind's `iterations`, which the harness reads around each window.
  `reference(cfg, name, seed, pool_indices)` gives `{"x": (k, n, s)
  float64}`, and the check reports `err`, the worst column's
  ‖x_j − x*_j‖ / ‖x*_j‖ over the sampled solves, and `unconverged`,
  how many of them ended in another status than `status`. The
  control (`reference_program`) returns a mapping with `x`,
  `iterations` and `status` (a `repro.guard.status` code), read
  through the same accessor as the library's `SolverResult`. The
  configuration may give `iteration_work(cfg, name)`: the (bytes,
  flops) that one loop iteration cannot do without
  (`reading.iteration_roofline_pct`); its `scope_work` is per
  iteration.

`precision`, where given, puts the configuration's plain reference in
the library's place, computed at that precision: `"highest"` is the
configuration's own (float32), `"high"` the three-pass bfloat16
product below it. The benchmark's runs never do; the control runs and
the tests do.
"""
from __future__ import annotations

import random
import sys
from collections.abc import Mapping
from typing import Optional

import jax
import numpy as np


def _f64(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float64)


def _reading(v: float) -> float:
    """A reading to compare with its limit: a NaN or infinite one (a
    poisoned answer) becomes the largest float, which fails any limit
    and still prints as a JSON number."""
    return float(v) if np.isfinite(v) else sys.float_info.max


def _field(out, name: str):
    """A solve's `name`: an attribute of the library's `SolverResult`,
    a key of the mapping that the control returns."""
    return out[name] if isinstance(out, Mapping) else getattr(out, name)


class Calls:
    unit = "call"
    iterations = None        # a solve's kind counts its iterations

    def __init__(self, cfg, module, traffic, seed: int,
                 precision: Optional[str] = None):
        self.cfg, self.module, self.traffic = cfg, module, traffic
        self.seed = seed
        self.limits = module.LIMITS
        self.program = traffic["program"]
        self.ahead = int(traffic["ahead"])
        self.fixed, self.pool = module.inputs(cfg, self.program, seed,
                                              int(traffic["pool"]))
        if precision is None:
            from repro import blas
            self.exe = blas.compile(module.program(cfg, self.program),
                                    tiles="default")
            self.entry = self.exe.run
        else:
            self.exe = None
            self.entry = module.reference_program(cfg, self.program,
                                                  precision)
        jax.block_until_ready((self.fixed, self.pool))
        self.size = int(traffic["sample"])
        self._rng = random.Random(seed)
        self.sample: list = []       # [(request, pool index, outputs)]
        self.count = 0

    def send(self, i: int):
        return self.entry(**self.fixed, **self.pool[i % len(self.pool)])

    @staticmethod
    def wait(out) -> None:
        jax.block_until_ready(list(out.values()))

    @staticmethod
    def ready(out) -> bool:
        return all(v.is_ready() for v in out.values())

    def keep(self, i: int, out) -> None:
        """Reservoir sampling: every call of the run's windows is
        equally likely to be among the `sample` checked."""
        self.count += 1
        item = (i, i % len(self.pool), out)
        if len(self.sample) < self.size:
            self.sample.append(item)
            return
        j = self._rng.randrange(self.count)
        if j < self.size:
            self.sample[j] = item

    def work(self) -> tuple:
        """(bytes, flops) that one call cannot do without."""
        return self.module.work(self.cfg, self.program)

    def _given(self, name: str):
        """What the configuration's optional `name(cfg, program)` gives,
        or None where it gives no such function."""
        f = getattr(self.module, name, None)
        return None if f is None else f(self.cfg, self.program)

    def iteration_work(self) -> Optional[tuple]:
        """A call runs no loop, so no iteration has work of its own."""
        return None

    def scope_work(self) -> Optional[dict]:
        return self._given("scope_work")

    def hlo_text(self) -> Optional[str]:
        """The compiled HLO text of the library's entry at the pool's
        first inputs, whose `op_name`s `bench.scopes` joins a trace's
        ops to. None for the plain reference in the library's place,
        and where `Executable.hlo_text` refuses the program (it takes
        dataflow programs only)."""
        if self.exe is None:
            return None
        try:
            return self.exe.hlo_text(**self.fixed, **self.pool[0])
        except TypeError as e:
            if "hlo_text() is for dataflow programs" not in str(e):
                raise
            return None

    def check(self) -> tuple:
        """({"err": worst ‖out − ref‖ / ‖ref‖}, failed calls), over
        every output of every sampled call."""
        want = self.module.reference(self.cfg, self.program, self.seed,
                                     [k for _, k, _ in self.sample])
        err, failed = 0.0, 0
        for row, (_, _, out) in enumerate(self.sample):
            e = max(_reading(np.linalg.norm(_f64(out[name]) - ref[row])
                             / np.linalg.norm(ref[row]))
                    for name, ref in want.items())
            failed += int(e > self.limits["err"])
            err = max(err, e)
        return {"err": err}, failed


class Solves(Calls):
    """One request is one solve of a loop program; one solve is one
    call of it, so the unit stays `call`."""

    def __init__(self, cfg, module, traffic, seed: int,
                 precision: Optional[str] = None):
        from repro.guard.status import STATUS_NAMES
        codes = {name: code for code, name in STATUS_NAMES.items()}
        name = traffic.get("status", "CONVERGED")
        if name not in codes:
            raise ValueError(f"status {name!r} is not one of {sorted(codes)}")
        self.status = codes[name]
        super().__init__(cfg, module, traffic, seed, precision)
        self.iterations = 0

    def iteration_work(self) -> Optional[tuple]:
        return self._given("iteration_work")

    @staticmethod
    def _arrays(out) -> list:
        return [_field(out, k) for k in ("x", "iterations", "status")]

    @staticmethod
    def wait(out) -> None:
        jax.block_until_ready(Solves._arrays(out))

    @staticmethod
    def ready(out) -> bool:
        return all(v.is_ready() for v in Solves._arrays(out))

    def keep(self, i: int, out) -> None:
        # a completed solve's count is one scalar, already on the host's
        # side of the wait
        self.iterations += int(_field(out, "iterations"))
        super().keep(i, out)

    def check(self) -> tuple:
        """({"err": worst column's ‖x_j − x*_j‖ / ‖x*_j‖, "unconverged":
        solves not ended in `status`}, failed solves), over the sampled
        solves."""
        want = self.module.reference(self.cfg, self.program, self.seed,
                                     [k for _, k, _ in self.sample])["x"]
        err, unconverged, failed = 0.0, 0, 0
        for ref, (_, _, out) in zip(want, self.sample):
            x = _f64(_field(out, "x")).reshape(ref.shape)
            e = _reading(np.max(np.linalg.norm(x - ref, axis=0)
                                / np.linalg.norm(ref, axis=0)))
            off = int(_field(out, "status")) != self.status
            failed += int(e > self.limits["err"] or off)
            unconverged += int(off)
            err = max(err, e)
        return {"err": err, "unconverged": unconverged}, failed


KINDS = {"calls": Calls, "solves": Solves}
