"""Random weights from the seed, made by the benchmark in one jitted call.

The program is given weights in its own parameter layout (read from
``jax.eval_shape`` of its init, shapes only) but with values the benchmark
draws itself, so the plain reference can be given the very same weights
without taking anything the program made. Scales follow the published
inits the program's layers use: 1/sqrt(fan-in) projections, 0.02
embeddings, unit norm scales, forget-gate biases at 3, small gate
projections.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def _leaf_init(path: str, shape, key) -> jax.Array:
    if path.endswith("['scale']"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("['fbias']") or path.endswith("['fgate']['b']"):
        return jnp.full(shape, 3.0, jnp.float32)
    normal = jax.random.normal(key, shape, jnp.float32)
    if "['embed']" in path:
        return normal * 0.02
    if "['igate']" in path or "['fgate']" in path:
        return normal * 0.01
    if "['r']" in path:                      # sLSTM recurrent (H, dh, 4dh)
        return normal / jnp.sqrt(float(shape[-2]))
    return normal / jnp.sqrt(float(shape[-2]))   # (..., fan_in, fan_out)


def maker(layout: Any) -> Callable[[jax.Array], Any]:
    """``layout`` (a tree of ShapeDtypeStructs) -> ``key -> params``, to be
    traced inside one jit."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def make(key):
        leaves = [_leaf_init(jax.tree_util.keystr(p), x.shape,
                             jax.random.fold_in(key, i))
                  for i, (p, x) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make


def program_layout(model_cfg) -> Any:
    """The program's parameter tree, shapes only."""
    from repro.models import registry
    return jax.eval_shape(lambda: registry.init_params(
        model_cfg, jax.random.PRNGKey(0)))


def make_params(model_cfg, key, n_workers: int = 0) -> Any:
    """Weights on the device from ``key``: one tree, or ``n_workers``
    identical copies stacked on a leading axis (every consensus worker
    starts from the same point). Not jitted here: callers trace it inside
    their own jitted set-up."""
    one = maker(program_layout(model_cfg))(key)
    if not n_workers:
        return one
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), one)
