"""Facts about the accelerator: the table of chip peaks and the
placement of JAX's persistent compilation cache.

Importing this module touches no JAX state, so both the BLAS layer
(`blas.executable`'s cost model) and the model stack
(`launch.roofline`) can use the one peaks table without either pulling
in the other.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator generation."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # chip-to-chip bytes/s per link
    source: str


# `device_kind` as JAX reports it for a TPU v5e chip
V5E = "TPU v5 lite"

PEAKS = {
    V5E: Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of '
               'interconnect over 4 links'),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of `device_kind`. A device missing from the table is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def use_compile_cache(checkout: pathlib.Path) -> str:
    """Point JAX's persistent compilation cache at a fixed place and
    return it: `JAX_COMPILATION_CACHE_DIR` when set (JAX reads it
    itself, so nothing is overridden), else `<checkout>/.jax_cache`.
    Entry points call this from `main`; importing the package never
    does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(pathlib.Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
