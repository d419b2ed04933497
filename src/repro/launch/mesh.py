"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices (see ``dryrun.py``).

Target hardware: TPU v5e pods — 256 chips/pod (16×16), 2 pods for the
multi-pod config. Peak per chip: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (constants mirrored in ``launch/analysis.py``).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)
