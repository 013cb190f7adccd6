"""Device setup: meshes, per-chip peak rates, and the compile cache.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required: smoke tests see 1 CPU device, only the
dry-run forces 512 host devices via XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import os
import pathlib
from typing import NamedTuple, Tuple

import jax

from repro.compat import make_mesh

# Fixed so that every run of this checkout hits the same cache entries
# (the directory is part of the cache key).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set here; otherwise the cache goes to :data:`CACHE_DIR`, the
    checkout's ``.jax_cache/``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """(data, model) mesh over every device JAX sees on this host."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))


def dp_axes_of(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


class ChipPeaks(NamedTuple):
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes_per_s: float  # per chip
    ici_bytes_per_s: float  # per chip-to-chip link


# Published peaks per chip, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s interconnect over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a kind not in :data:`CHIP_PEAKS` raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}"
        ) from None
