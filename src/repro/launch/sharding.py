"""Sharding policy: param-tree PartitionSpecs and activation rules.

Policy (2D meshes: a worker or batch axis and ``model``):
  * Tensor parallelism over ``model``: column-parallel input projections
    (q/k/v, wi_*, up, in_proj, wx), row-parallel output projections
    (o, wo, down, out_proj); vocab-sharded embedding table and untied
    output head (lm_head).
  * MoE: expert-parallel over ``model`` when num_experts divides the axis,
    else ff-dim TP inside each expert.
  * ADMM consensus training adds a leading worker axis on every parameter,
    sharded over the worker mesh axis (``workers`` in ``launch/train.py``).

Every proposed axis is divisibility-checked against the actual leaf shape —
a spec never over-shards a dimension.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np
from jax.sharding import PartitionSpec

COL_PARALLEL = ("q", "k", "v", "wi_gate", "wi_up", "up", "wx", "in_proj",
                "igate", "fgate", "router", "lm_head")
ROW_PARALLEL = ("o", "wo", "down", "out_proj")


def _mesh_axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _checked(mesh, shape, spec_axes) -> PartitionSpec:
    """Drop axes that do not divide the corresponding dim."""
    out = []
    for dim, axis in zip(shape, spec_axes):
        if axis is not None and dim % _mesh_axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return PartitionSpec(*out)


def expert_axis(mesh, cfg) -> Optional[str]:
    """Mesh axis that carries the expert dim. A dedicated 'expert' axis
    (the EP mesh view, e.g. 16x8x2 data/expert/tp over the same 256 chips)
    wins; else the model axis when the expert count divides it."""
    if cfg.num_experts:
        if "expert" in mesh.shape and \
                cfg.num_experts % mesh.shape["expert"] == 0:
            return "expert"
        if cfg.num_experts % _mesh_axis_size(mesh, "model") == 0:
            return "model"
    return None


def tp_axes(mesh):
    """Tensor-parallel mesh axes for dense (non-expert) weights: on the EP
    mesh view the expert axis folds into TP so attention keeps its full
    16-way sharding."""
    return ("expert", "model") if "expert" in mesh.shape else "model"


def param_spec(path: str, shape: Tuple[int, ...], mesh, cfg, *,
               worker_axis: Optional[str] = None) -> PartitionSpec:
    """PartitionSpec for one parameter leaf.

    path: jax keystr of the leaf (e.g. "['stack']['units']['p0']['mlp']
    ['wi_gate']['w']"); shape excludes any worker axis (added by caller via
    `worker_axis`).
    """
    tp = tp_axes(mesh)

    def named(*axes):
        lead = (worker_axis,) if worker_axis else ()
        full_shape = shape if not worker_axis else shape[1:]
        spec = _checked(mesh, full_shape, axes)
        return PartitionSpec(*(lead + tuple(spec)))

    rank = len(shape) - (1 if worker_axis else 0)
    # moe expert stacks: (E, d, f) / (E, f, d) (+ optional scan axis in front)
    if "'moe'" in path and "router" not in path:
        ep = expert_axis(mesh, cfg)
        if ep == "expert":
            ff_tp = "model"                # EP mesh: ff TP on the leftover
        elif ep is not None:               # experts on the model axis
            ff_tp = None
        else:
            ff_tp = tp
        if rank == 4:      # (n_units, E, in, out)
            if ep:
                return named(None, ep, None, ff_tp)
            return named(None, None, None, tp)
        if rank == 3:
            if ep:
                return named(ep, None, ff_tp)
            return named(None, None, tp)

    if path.endswith("['table']"):      # embedding (V, D)
        return named(tp, None)

    is_col = any(f"'{n}'" in path for n in COL_PARALLEL)
    is_row = any(f"'{n}'" in path for n in ROW_PARALLEL)
    if rank >= 2 and (is_col or is_row):
        axes = [None] * rank
        # last two dims are (in, out); leading dims are scan/stack axes
        if is_col and not is_row:
            axes[-1] = tp
        else:
            axes[-2] = tp
        return named(*axes)
    # conv weights, scales, biases, gates: replicate (modulo worker axis)
    return named(*([None] * rank))


# ------------------------------------------------------ activation rules --
def activation_rules(mesh, cfg, *, batch_axes=("data",),
                     worker_mode: bool = False,
                     worker_axis: str = "data") -> Dict[str, Any]:
    """Logical-name -> mesh-axis map for with_sharding_constraint calls.

    batch_axes: axes carrying the (global or per-worker) batch dimension.
    worker_mode: under ADMM a mesh axis carries workers (``worker_axis``);
    the per-worker batch stays unsharded inside each worker slice.
    """
    tp = tp_axes(mesh)
    batch = tuple(a for a in batch_axes if a in mesh.shape) or None
    if batch is not None and len(batch) == 1:
        batch = batch[0]
    # expert parallelism claims one axis for the expert dim; the ff dim
    # inside each expert may use the model axis only when the expert dim
    # does not (a PartitionSpec may use each mesh axis once).
    ep = expert_axis(mesh, cfg)
    rules: Dict[str, Any] = {
        "batch": None if worker_mode else batch,
        "worker": worker_axis if worker_axis in mesh.shape else None,
        "seq": None,
        "embed": None,
        # MoE expert ff: "model" only on the EP mesh; dense archs use full TP
        "ff": ("model" if ep == "expert" else
               None if ep is not None else
               tp if cfg.d_ff % _mesh_axis_size(mesh, tp) == 0 else None),
        "heads": tp if cfg.num_heads % _mesh_axis_size(mesh, tp) == 0
        else None,
        "vocab": tp if cfg.vocab_size % _mesh_axis_size(mesh, tp) == 0
        else None,
        "expert": ep,
        # MoE dispatch-buffer capacity axis: when the experts have no axis
        # of their own, shard the capacity dim instead (memory relief for
        # the (E*C, D) buffer at 1M-token prefill).
        "expert_cap": None if ep else tp,
    }
    return rules
