"""Logical-axis sharding: model code names axes, the runtime maps them.

Model code calls ``constrain(x, ("batch", "seq", "embed"))``; if a mesh
context is active (set by the launcher), the logical names are
translated to mesh axes through the current rule set and a
``with_sharding_constraint`` is applied. Without a context (unit tests,
single-device smoke runs) it is the identity, so models stay mesh-agnostic.

Rule sets are plain dicts  logical name -> mesh axis (or None / tuple).
The tensor-parallel rules live in ``repro.launch.sharding``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxis = Union[None, str, Tuple[str, ...]]

_ctx = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def current_rules() -> Dict[str, MeshAxis]:
    return getattr(_ctx, "rules", {})


@contextlib.contextmanager
def logical_sharding(mesh: Mesh, rules: Dict[str, MeshAxis]):
    prev = (current_mesh(), current_rules())
    _ctx.mesh, _ctx.rules = mesh, rules
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def spec_for(logical_axes: Tuple[Optional[str], ...],
             rules: Optional[Dict[str, MeshAxis]] = None) -> PartitionSpec:
    rules = rules if rules is not None else current_rules()
    return PartitionSpec(*[
        rules.get(name) if name is not None else None
        for name in logical_axes
    ])


def constrain(x: jax.Array, logical_axes: Tuple[Optional[str], ...]):
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        # model code sometimes annotates the canonical rank; skip mismatches
        return x
    spec = spec_for(logical_axes)
    if all(s is None for s in spec):
        # an all-None constraint would FORCE replication — never what we
        # want; let GSPMD propagate instead.
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))

