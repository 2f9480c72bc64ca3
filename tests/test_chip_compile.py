"""Compile the main-path kernels for a described TPU v5e, no chip attached.

Interpret mode runs every other test of the kernels, and the chip's
compiler refuses things the interpreter accepts (a scalar store into
VMEM did exactly that to every reduction). Each case here lowers a
main-path program at a size that lives in HBM with `interpret=False`,
compiles it for one chip of a described `v5e:2x2` topology, and
requires a `tpu_custom_call` in the compiled program. Nothing runs.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process may load the TPU's library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro import blas
from repro.kernels import axpydot as axpydot_mod, dot as dot_mod, \
    gemv as gemv_mod
from repro.solvers import specs

L1_N = 1 << 24
CG_N = 16384
BLOCK_CG_S = 8
L2_N = 10000          # not a multiple of the gemv block shape


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache
    # but never read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _axpydot_exe():
    b = blas.program("axpydot")
    z = b.axpy(alpha=b.input("neg_alpha"), x="v", y="w")
    b.dot(x=z, y="u", out="beta")
    return blas.compile(b, interpret=False, tiles="default")


def _gemv_axpy_nrm2_exe():
    b = blas.program("gemv_axpy_nrm2")
    q = b.gemv(alpha=1.0, beta=0.0, A="A", x="p", y="y0", out="q")
    rn = b.axpy(alpha=b.input("neg_alpha"), x=q, y="r", out="r_next")
    b.nrm2(x=rn, out="rnorm")
    return blas.compile(b, interpret=False, tiles="default")


def _axpy_iamax_exe():
    b = blas.program("axpy_iamax")
    z = b.axpy(alpha=b.input("al"), x="v", y="w")
    b.iamax(x=z, out="i")
    return blas.compile(b, interpret=False, tiles="default")


def _solve_fn(spec, rhs):
    exe = blas.compile(spec, interpret=False, tiles="default",
                       max_iters=200)

    def solve(a, b, x0):
        res = exe.run(A=a, x0=x0, tol=1e-5, **{rhs: b})
        return res.x, res.iterations, res.status
    return solve


def _case(name):
    """(function, argument shapes) of one case; shapes are
    (shape, dtype) pairs placed on the described chip by the test."""
    vec = ((L1_N,), jnp.float32)
    scalar = ((), jnp.float32)
    if name == "dot":
        return (lambda x, y: dot_mod.dot(x, y, interpret=False),
                [vec, vec])
    if name == "nrm2":
        return lambda x: dot_mod.nrm2(x, interpret=False), [vec]
    if name == "iamax":
        return lambda x: dot_mod.iamax(x, interpret=False), [vec]
    if name == "axpydot":
        return (lambda a, w, v, u: axpydot_mod.axpydot(
            a, w, v, u, interpret=False), [scalar, vec, vec, vec])
    if name == "fused_axpydot":
        exe = _axpydot_exe()
        return (lambda a, v, w, u: exe.one(neg_alpha=a, v=v, w=w, u=u),
                [scalar, vec, vec, vec])
    if name == "fused_axpy_iamax":
        exe = _axpy_iamax_exe()
        return (lambda a, v, w: exe.one(al=a, v=v, w=w),
                [scalar, vec, vec])
    if name == "cg_loop":
        cg_vec = ((CG_N,), jnp.float32)
        return (_solve_fn(specs.CG_LOOP, "b"),
                [((CG_N, CG_N), jnp.float32), cg_vec, cg_vec])
    if name == "block_cg_loop":
        panel = ((CG_N, BLOCK_CG_S), jnp.float32)
        return (_solve_fn(specs.BLOCK_CG_LOOP, "B"),
                [((CG_N, CG_N), jnp.float32), panel, panel])
    l2_vec = ((L2_N,), jnp.float32)
    l2_mat = ((L2_N, L2_N), jnp.float32)
    if name == "gemv":
        return (lambda a, x, y: gemv_mod.gemv(1.0, a, x, 0.0, y,
                                              interpret=False),
                [l2_mat, l2_vec, l2_vec])
    if name == "fused_gemv_axpy_nrm2":
        exe = _gemv_axpy_nrm2_exe()

        def run(al, a, p, y0, r):
            out = exe.run(neg_alpha=al, A=a, p=p, y0=y0, r=r)
            return out["q"], out["r_next"], out["rnorm"]
        return run, [scalar, l2_mat, l2_vec, l2_vec, l2_vec]
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "dot", "nrm2", "iamax", "axpydot", "fused_axpydot",
    "fused_axpy_iamax", "cg_loop", "block_cg_loop", "gemv",
    "fused_gemv_axpy_nrm2"])
def test_compiles_for_v5e(one_chip, name):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    calls = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    mem = compiled.memory_analysis()
    # for the record: gemv pads A to the block shape on every call, so
    # an unaligned n shows up here as a padded copy of A
    print(f"{name}: {calls} tpu_custom_call(s), temp "
          f"{mem.temp_size_in_bytes} bytes")
    assert calls >= 1, f"{name}: no Pallas kernel in the compiled program"
