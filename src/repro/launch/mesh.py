"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the ``pod``
axis hosts the CQ-GGADMM worker graph (slow inter-pod links are exactly the
links the paper's censoring/quantization compresses).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; the dry-run process sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use.
"""
from __future__ import annotations

import jax

from repro.core.topology import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    if n >= 4:
        return make_mesh((n // 2, 2), ("data", "model"))
    return make_mesh((n, 1), ("data", "model"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1
