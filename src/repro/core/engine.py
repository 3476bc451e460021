"""Layer-aware unified CQ-GGADMM consensus engine (DESIGN.md §Engine).

One pytree-native stepper serves the paper's whole algorithm family —
GGADMM / C-GGADMM / Q-GGADMM / CQ-GGADMM (Algorithms 1 and 2) and the
Jacobian C-ADMM baseline — for every workload in the repo: a flat ``(N, d)``
vector is just the trivial one-leaf pytree, a transformer parameter tree is
the general case. The two seed steppers (``core/cq_ggadmm.py`` flat,
``core/consensus.py`` pytree) are thin adapters over this module.

Structure per iteration (vectorized over the leading worker axis N, group
selection by masks so one traced program serves any bipartite graph):

  phase 1 (heads):  theta_H <- local argmin of the augmented Lagrangian
                    quantize (grouped) -> candidate, censor -> theta_hat_H
  phase 2 (tails):  same, neighbors see the fresh head theta_hat
  dual:             alpha += rho * (D - A) theta_hat            (Eq. 23)

Two orthogonal generalizations beyond the seed steppers:

* **Quantization groups** (L-FGADMM-style, Elgabli et al. 2019): the
  quantizer side-information ``(R, b, Δ)`` is shaped ``(N, G)`` where G is
  the number of groups. ``groups="model"`` (G=1) reproduces the paper's
  whole-model quantization bit-for-bit; ``groups="leaf"`` (G=num_leaves)
  gives per-layer ranges — each layer gets its own range R_g, bit growth
  per Eq. (18) applied group-wise, and payload
  ``sum_g b_g d_g + G * overhead`` (QSGD-style accounting). Layers with
  small dynamic range stop paying for the largest layer's range.
* **Censoring modes**: ``censor_mode="global"`` is the paper's single
  whole-model norm test; ``censor_mode="group"`` (a new scenario) censors
  each group independently with thresholds tau_g = tau * sqrt(d_g / d), so
  quiet layers stay silent while hot layers still transmit. The transmitted
  payload counts only the groups that pass.

Local solvers are pluggable: :class:`ExactSolver` wraps the closed-form /
Newton ``PrimalSolver`` objects of ``core/solvers.py`` (convex experiments);
:class:`InexactSolver` runs K Adam/SGD steps on the augmented Lagrangian
(neural workloads; the inexact-ADMM deviation recorded in DESIGN.md §5).

**Exchange layout** (DESIGN.md §Packing): quantize, censor, the phase
mixes and the dual work on each leaf of the tree in its own shape; no
``(N, D)`` row is built on the default path. On a TPU a leaf lies in
whole (8, 128) tiles, while a packed row of a few workers fills a
fraction of each tile's sublanes, so packing costs a relayout copy and
every pass over the row runs at a fraction of the vector unit. The
quantizer reduces per-leaf ranges to ``(N, G)``, runs the Eq. (18)
schedule once, and rounds each leaf with its own columns of the packed
draw (:func:`quantization.uniform_columns`), so it gives the packed
round's numbers bit for bit; the censor sums per-leaf squared norms to
``(N, G)``. One body of each serves workers on one device and workers
split over devices (inside ``shard_map``). The packed ``(N, D)`` buffer
(``core/packing.py``) remains where a Pallas kernel takes one:
``use_pallas_quant`` runs the fused ``kernels.ops.
stoch_quantize_grouped_fused`` on it (its jnp oracle is the leaf path's
parity reference), and ``use_pallas_mix`` the mix kernels. The relevant
:class:`EngineConfig` knobs:

* ``groups``: ``"model"`` (G=1), ``"leaf"``, a named block spec
  (``"block:attn,mlp,embed"``), ``"auto:K"``, an explicit leaf->group
  tuple, or index buckets ``((0, 1), (2,))`` — every spec compiles to the
  same per-leaf id map (:func:`resolve_groups`, DESIGN.md §Groups);
* ``use_pallas_quant`` / ``use_pallas_mix``: route the exchange through
  the Pallas kernels over the packed buffer instead;
* ``censor_mode="group"``: the per-group norm test on the ``(N, G)``
  sums.

``obs.metrics.engine_exchange_layout`` counts, at trace time, the layout
each part took.

PRNG compatibility note: the stochastic-rounding uniforms are the phase
key's draw over the full model width — for a one-leaf tree this is
exactly the seed draw, so the G=1 flat path reproduces the seed
``cq_ggadmm`` trajectories bit-for-bit (golden tests in
``tests/test_engine.py``); for multi-leaf trees each leaf draws its own
columns of the draw over the packed width, which replaces the per-leaf
key split of the old unfused loop (kept as
``grouped_quantize_step_unfused`` for parity benchmarks). A phase that
quantizes only its acting workers' rows draws only those rows: bit for
bit the same rows of the draw over all N workers, so the row path
changes no number.

**Acting rows** (DESIGN.md §Engine): in an alternating step each phase
mixes for and solves, quantizes and censors only its own group's
workers — their rows are static per graph — and writes them back into
the N-row state. The masked path (compute all N rows, keep the acting
ones) remains where rows cannot be split off: the Jacobian step (every
worker acts), workers placed across devices (a mesh or the sharded
backend), and, for the mix and the solve only, an :class:`ExactSolver`
or a call without a batch, whose problem data is not per-row input.
``obs.metrics.engine_phase_rows`` counts, at trace time, the rows each
part computes on each path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import censoring as censor_lib
from repro.core import packing
from repro.core import quantization as quant_lib
from repro.core import topology as topo_lib
from repro.core.censoring import CensorConfig, threshold
from repro.core.graph import WorkerGraph
from repro.core.quantization import QuantConfig
from repro.obs.metrics import (engine_exchange_layout_counter,
                               engine_phase_rows_counter,
                               engine_worker_layout_counter)

_EPS = 1e-12

Tree = Any


# ------------------------------------------------------------- tree utils --
def _over_worker(reduce_fn, x: jax.Array) -> jax.Array:
    """``reduce_fn`` over every axis of ``x`` but the leading worker axis,
    in ``x``'s own shape: (N,)."""
    return reduce_fn(x, axis=tuple(range(1, x.ndim)))


def tree_worker_dot(a: Tree, b: Tree) -> jax.Array:
    """Per-worker inner product over all leaves: (N,)."""
    parts = jax.tree_util.tree_map(
        lambda x, y: _over_worker(
            jnp.sum, x.astype(jnp.float32) * y.astype(jnp.float32)), a, b)
    return sum(jax.tree_util.tree_leaves(parts))


def tree_worker_sqnorm(a: Tree) -> jax.Array:
    return tree_worker_dot(a, a)


def tree_worker_maxabs(a: Tree) -> jax.Array:
    """Per-worker max |.| over all leaves: (N,)."""
    parts = jax.tree_util.tree_map(
        lambda x: _over_worker(jnp.max, jnp.abs(x.astype(jnp.float32))), a)
    leaves = jax.tree_util.tree_leaves(parts)
    return jnp.max(jnp.stack(leaves, axis=0), axis=0)


def tree_dim(a: Tree) -> int:
    """Total model dimension d (per worker)."""
    leaves = jax.tree_util.tree_leaves(a)
    return sum(int(x.size // x.shape[0]) for x in leaves)


# Two programs that sum the same f32 terms in another order — a Pallas mix
# and a jnp matmul, a sparse and a dense mix — agree only to rounding, and
# stochastic quantization turns a rounding difference into a whole
# quantization step, which later rounds carry on. Such runs are compared by
# their censor decisions and, for states and trajectories, by
# ``tree_rel_err`` against this bound (tests and ``chip_smoke.py`` alike).
# The dual ``alpha`` is where a mix's rounding shows: on the tests'
# workloads the Pallas and sparse mixes part it from the dense matmul's by
# at most 4.3e-3, while a mix whose operands are rounded to bfloat16 (a
# TPU's default f32 matmul precision) parts it by 2.7e-2 and more.
CROSS_PROGRAM_RTOL = 1e-2


def tree_rel_err(a: Tree, b: Tree) -> float:
    """Relative L2 error ``||a - b|| / ||b||`` over all leaves of two trees
    of equal structure, summed in float64 on the host one row at a time
    (LM-width leaves would lose the sum in float32). Zero when both are
    zero; infinite when only ``b`` is."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        raise ValueError(f"trees differ: {len(la)} vs {len(lb)} leaves")
    num = den = 0.0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            raise ValueError(f"leaf shapes differ: {x.shape} vs {y.shape}")
        for xr, yr in zip(x.reshape(-1, x.shape[-1] if x.ndim else 1),
                          y.reshape(-1, y.shape[-1] if y.ndim else 1)):
            yr = yr.astype(np.float64)
            d = xr.astype(np.float64) - yr
            num += float(d @ d)
            den += float(yr @ yr)
    if den == 0.0:
        return 0.0 if num == 0.0 else float("inf")
    return float(np.sqrt(num / den))


def tree_mix(adjacency: jax.Array, a: Tree, use_kernel: bool = False) -> Tree:
    """Neighbor sum: out_n = sum_m A[n, m] leaf_m (dense backend on a bare
    adjacency array; the engine itself mixes through the pluggable
    :mod:`~repro.core.topology` backends), each leaf in its own shape;
    ``use_kernel`` mixes the packed ``(N, D)`` view with one Pallas
    ``bipartite_mix`` call for the whole tree."""
    return topo_lib.mix_dense(adjacency, a, use_kernel=use_kernel)


def tree_where_worker(mask: jax.Array, a: Tree, b: Tree) -> Tree:
    """Select a_n where mask_n > 0 else b_n, leaf-wise."""
    def sel(x, y):
        m = mask.reshape((mask.shape[0],) + (1,) * (x.ndim - 1))
        return jnp.where(m > 0, x, y)
    return jax.tree_util.tree_map(sel, a, b)


def _contiguous(rows: np.ndarray) -> bool:
    return bool(np.all(np.diff(rows) == 1))


def take_rows(a: Tree, rows: np.ndarray) -> Tree:
    """Rows ``rows`` (static) of every leaf: a slice when they are
    contiguous, else a gather."""
    if _contiguous(rows):
        lo, hi = int(rows[0]), int(rows[-1]) + 1
        return jax.tree_util.tree_map(lambda x: x[lo:hi], a)
    idx = jnp.asarray(rows, jnp.int32)
    return jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0), a)


def put_rows(a: Tree, rows: np.ndarray, new: Tree) -> Tree:
    """``a`` with rows ``rows`` (static) of every leaf replaced by ``new``'s:
    one ``dynamic-update-slice`` when the rows are contiguous (in place in
    a donated buffer), else a scatter."""
    if _contiguous(rows):
        lo = int(rows[0])
        return jax.tree_util.tree_map(
            lambda x, y: jax.lax.dynamic_update_slice_in_dim(
                x, y.astype(x.dtype), lo, axis=0), a, new)
    idx = jnp.asarray(rows, jnp.int32)
    return jax.tree_util.tree_map(lambda x, y: x.at[idx].set(y), a, new)


# ------------------------------------------------------- group resolution --
GroupSpec = Union[str, Tuple]

GroupSpecError = packing.GroupSpecError


def resolve_groups(theta: Tree, groups: GroupSpec) -> Tuple[int, ...]:
    """Leaf index -> group id, aligned with ``tree_leaves`` order.

    Spec grammar (DESIGN.md §Groups):

    * ``"model"``: every leaf in group 0 (G=1, the paper's whole-model mode).
    * ``"leaf"``: leaf i in group i (G=num_leaves, L-FGADMM layer-wise mode).
    * ``"block:attn,mlp,embed"``: named block buckets — each name claims the
      leaves whose path matches its alias set (``packing.BUCKET_ALIASES``,
      falling back to the name itself as a path substring); unmatched
      leaves land in ``rest``. Unknown and empty buckets raise
      :class:`GroupSpecError`.
    * ``"auto:K"``: <= K groups. Resolution here is the deterministic
      shape-balanced contiguous partition (works under ``eval_shape``); the
      live range-statistics refinement is :class:`AutoGrouper`'s job.
    * flat int tuple: validated leaf -> contiguous group ids ``0..G-1``.
    * tuple of index tuples ``((0, 1), (2,))``: explicit leaf-index buckets;
      must partition the leaves (overlap / gap => :class:`GroupSpecError`).
    """
    n_leaves = len(jax.tree_util.tree_leaves(theta))
    if isinstance(groups, str):
        if groups == "model":
            return (0,) * n_leaves
        if groups == "leaf":
            return tuple(range(n_leaves))
        packing.validate_spec_syntax(groups)
        if groups.startswith("block:"):
            return packing.resolve_block_groups(
                theta, packing.parse_block_spec(groups))
        return packing.resolve_auto_groups(theta,
                                           packing.parse_auto_spec(groups))
    nested = [isinstance(g, (tuple, list)) for g in groups]
    if groups and all(nested):
        return packing.resolve_index_buckets(theta, groups)
    if any(nested):
        raise GroupSpecError(
            f"mixed tuple spec {groups!r}: use either a flat leaf->group "
            f"id tuple like (0, 0, 1) or index buckets like ((0, 1), (2,))"
            f" — not both")
    ids = tuple(int(g) for g in groups)
    if len(ids) != n_leaves:
        raise GroupSpecError(f"group spec covers {len(ids)} leaves, "
                             f"tree has {n_leaves}")
    n_groups = max(ids) + 1
    if set(ids) != set(range(n_groups)):
        raise GroupSpecError(
            f"group ids must be contiguous 0..G-1, got {ids}")
    return ids


def group_dims(theta: Tree, group_ids: Sequence[int]) -> Tuple[int, ...]:
    """Per-group parameter counts d_g (static)."""
    leaves = jax.tree_util.tree_leaves(theta)
    dims = [0] * (max(group_ids) + 1)
    for leaf, g in zip(leaves, group_ids):
        dims[g] += int(leaf.size // leaf.shape[0])
    return tuple(dims)


def _group_reduce(per_leaf: Sequence[jax.Array], group_ids: Sequence[int],
                  n_groups: int, reduce_fn) -> jax.Array:
    """Combine per-leaf (N,) stats into (N, G) via reduce_fn over each group."""
    cols = []
    for g in range(n_groups):
        members = [per_leaf[i] for i, gi in enumerate(group_ids) if gi == g]
        cols.append(members[0] if len(members) == 1
                    else reduce_fn(jnp.stack(members, axis=0)))
    return jnp.stack(cols, axis=1)


# ------------------------------------------------------ grouped quantizer --
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GroupQuantState:
    """Grouped quantizer state: q_hat mirrors the parameter pytree (leading
    worker axis N); side-information ``(R, b, Δ)`` is ``(N, G)`` — one value
    per worker per quantization group. G=1 is the paper's single
    ``(R_n^k, b_n^k)`` per transmission.
    """

    q_hat: Tree
    range_prev: jax.Array   # (N, G)
    bits_prev: jax.Array    # (N, G)
    delta_prev: jax.Array   # (N, G)
    initialized: jax.Array  # (N, G)

    @property
    def n_groups(self) -> int:
        return int(self.range_prev.shape[-1])

    @staticmethod
    def create(theta: Tree, n_groups: int, b0: int = 2,
               hat_dtype=None) -> "GroupQuantState":
        n = jax.tree_util.tree_leaves(theta)[0].shape[0]
        return GroupQuantState(
            q_hat=jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, hat_dtype or x.dtype), theta),
            range_prev=jnp.zeros((n, n_groups), jnp.float32),
            bits_prev=jnp.full((n, n_groups), float(b0), jnp.float32),
            delta_prev=jnp.zeros((n, n_groups), jnp.float32),
            initialized=jnp.zeros((n, n_groups), jnp.float32),
        )


def _leaf_keys(key: jax.Array, n_leaves: int):
    # Single-leaf trees use the phase key directly so the G=1 flat path is
    # bit-identical to the seed flat stepper (see module docstring).
    if n_leaves == 1:
        return [key]
    return list(jax.random.split(key, n_leaves))


def grouped_quantize_step(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], use_kernel: bool = False,
    rows: Optional[np.ndarray] = None, n_workers: Optional[int] = None,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """One grouped stochastic-quantization round (Eqs. 14-20, group-wise).

    Single-leaf trees run the direct (seed-bit-compatible) path; multi-leaf
    trees run leaf by leaf, each leaf in its own shape
    (:func:`grouped_quantize_step_leaves`), or, with ``use_kernel``, the
    fused Pallas kernel over the packed ``(N, D)`` buffer
    (:func:`_grouped_quantize_step_packed`). All draw each parameter's
    uniform from the same place of the packed draw.

    With ``rows``, ``state`` and ``theta`` hold only those rows of an
    ``n_workers``-row state, and each draws exactly its own rows of the
    uniforms the full state would draw, so the result equals those rows of
    the full round.

    Returns ``(new_state, candidate_tree, bits (N, G), payload (N,))`` where
    payload = sum_g b_g d_g + G * overhead — each group ships its own
    ``(R_g, b_g)`` side information.
    """
    if len(jax.tree_util.tree_leaves(theta)) == 1:
        return grouped_quantize_step_unfused(state, theta, key, cfg,
                                             group_ids, use_kernel,
                                             rows, n_workers)
    if use_kernel:
        return _grouped_quantize_step_packed(state, theta, key, cfg,
                                             group_ids, use_kernel, rows,
                                             n_workers)
    return grouped_quantize_step_leaves(state, theta, key, cfg, group_ids,
                                        rows, n_workers)


def _uniforms(key: jax.Array, shape: Tuple[int, ...],
              rows: Optional[np.ndarray], n_workers: Optional[int]
              ) -> jax.Array:
    """The stochastic rounding's uniforms for a ``shape`` block: the whole
    draw ``jax.random.uniform(key, shape)``, or, with ``rows``, those rows
    of the draw over ``(n_workers,) + shape[1:]``."""
    if rows is None:
        return jax.random.uniform(key, shape, jnp.float32)
    width = int(np.prod(shape[1:], dtype=np.int64))
    return quant_lib.uniform_rows(key, n_workers, width,
                                  rows).reshape(shape)


def _finish_packed_step(state: GroupQuantState, pk, out, range_new, bits,
                        delta, cfg: QuantConfig):
    """Shared tail of the packed paths: degenerate-group state carry,
    unpack, QSGD payload accounting. All (N, G)-sized — the (N, D) work is
    already done by the quantize call."""
    return _finish_step(state, packing.unpack(pk, out, like=state.q_hat),
                        range_new, bits, delta, cfg, pk.group_dims)


def _finish_step(state: GroupQuantState, q_hat_new, range_new, bits, delta,
                 cfg: QuantConfig, dims: Sequence[int]):
    degen = range_new <= _EPS                                     # (N, G)
    new_state = GroupQuantState(
        q_hat=q_hat_new,
        range_prev=jnp.where(degen, state.range_prev, range_new),
        bits_prev=bits,
        delta_prev=jnp.where(degen, state.delta_prev, delta),
        initialized=jnp.ones_like(state.initialized),
    )
    dims_arr = jnp.asarray(dims, jnp.float32)
    payload = jnp.sum(bits * dims_arr[None, :], axis=-1) \
        + float(len(dims) * cfg.b_overhead)
    return new_state, q_hat_new, bits, payload


def _grouped_quantize_step_packed(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], use_kernel: bool = False,
    rows: Optional[np.ndarray] = None, n_workers: Optional[int] = None,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """Fused path: the whole grouped round — range reduction, Eq. (18) bit
    schedule, quantize, degenerate passthrough — in one call over the
    packed buffer, for the Pallas kernel's sake. ``use_kernel=True`` routes
    it through the single ``pallas_call`` of
    ``kernels.stoch_quantize_grouped_fused`` (the range min/max happens
    *inside* the kernel); ``use_kernel=False`` runs its bit-identical jnp
    oracle, the parity reference of :func:`grouped_quantize_step_leaves`."""
    pk = packing.make_packing(theta, group_ids)
    theta_p = packing.pack(pk, theta)                     # (N, D) f32
    qprev_p = packing.pack(pk, state.q_hat)               # (N, D) f32

    # One draw for the whole packed buffer with the phase key (the fused
    # analog of the seed's single whole-vector draw).
    uniforms = _uniforms(key, theta_p.shape, rows, n_workers)
    gid_cols = jnp.asarray(pk.col_group_ids)
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        fused = kernel_ops.stoch_quantize_grouped_fused
    else:
        from repro.kernels import ref as kernel_ref
        fused = kernel_ref.stoch_quantize_grouped_fused_ref
    out, range_new, bits, delta = fused(
        theta_p, qprev_p, uniforms, state.bits_prev, state.range_prev,
        state.initialized, gid_cols, group_runs=pk.group_runs,
        omega=cfg.omega, b0=cfg.b0, b_max=cfg.b_max)
    return _finish_packed_step(state, pk, out, range_new, bits, delta, cfg)


def _on_blocks(topo: topo_lib.ShardedTopology, fn, in_specs, out_specs,
               *args):
    """``fn`` on each device's own blocks of ``args``, laid out as
    ``in_specs``: what crosses devices is what ``fn`` reduces itself over
    ``topo.rest`` (the axes a worker spans)."""
    return jax.shard_map(fn, mesh=topo.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _block_starts(spec, local_shape: Tuple[int, ...], mesh) -> list:
    """Where a device's block of a leaf laid out as ``spec`` starts, per
    axis (inside ``shard_map``)."""
    starts = []
    for k, size in enumerate(local_shape):
        ax = spec[k] if k < len(spec) else None
        idx = 0
        for a in (() if ax is None else ax if isinstance(ax, tuple)
                  else (ax,)):
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        starts.append(idx * size)
    return starts


def _quant_specs(topo: topo_lib.ShardedTopology):
    from jax.sharding import PartitionSpec
    rows = PartitionSpec(topo.axis)
    return GroupQuantState(q_hat=topo.leaf_specs, range_prev=rows,
                           bits_prev=rows, delta_prev=rows,
                           initialized=rows)


def _quantize_leaves(t_leaves, q_leaves, bits_prev, range_prev, initialized,
                     uniforms, cfg: QuantConfig, group_ids: Sequence[int],
                     n_groups: int, reduce_range=None):
    """The grouped round (Eqs. 14-20, group-wise) leaf by leaf, each leaf in
    its own shape, with no packed row: per-leaf ``max |theta - q_hat|``
    reduced to (n, G) (and by ``reduce_range`` where the leaves are blocks
    of larger ones), the Eq. (18) schedule, and leaf ``i`` rounded with
    ``uniforms(i, leaf)``. Returns ``(q_hat leaves, range_new, bits,
    delta)``."""
    from repro.kernels.ref import stoch_quantize_ref
    f32 = jnp.float32
    range_new = _group_reduce(
        [_over_worker(jnp.max, jnp.abs(t.astype(f32) - q.astype(f32)))
         for t, q in zip(t_leaves, q_leaves)],
        group_ids, n_groups, lambda s: jnp.max(s, axis=0))       # (n, G)
    if reduce_range is not None:
        range_new = reduce_range(range_new)
    bits, delta, degen = quant_lib.bit_schedule(
        bits_prev, range_new, range_prev, initialized, cfg.omega, cfg.b0,
        cfg.b_max)
    out = []
    for i, (t, q) in enumerate(zip(t_leaves, q_leaves)):
        g = group_ids[i]
        q32 = q.astype(f32)
        fresh = stoch_quantize_ref(t.astype(f32), q32, uniforms(i, t),
                                   delta[:, g], range_new[:, g])
        keep = degen[:, g].reshape((t.shape[0],) + (1,) * (t.ndim - 1))
        out.append(jnp.where(keep, q32, fresh).astype(q.dtype))
    return out, range_new, bits, delta


def _packed_columns(theta: Tree) -> Tuple[int, list]:
    """The packed row's width D and each leaf's first column in it."""
    widths = [int(x.size // x.shape[0])
              for x in jax.tree_util.tree_leaves(theta)]
    return sum(widths), [int(o) for o in np.cumsum([0] + widths[:-1])]


def grouped_quantize_step_leaves(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], rows: Optional[np.ndarray] = None,
    n_workers: Optional[int] = None,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """The packed round (:func:`_grouped_quantize_step_packed`) leaf by
    leaf on one device, bit for bit: each leaf draws its own columns of
    the packed draw (:func:`quantization.uniform_columns`), and, with
    ``rows``, only those rows of an ``n_workers``-row state."""
    leaves, treedef = jax.tree_util.tree_flatten(theta)
    n = n_workers if rows is not None else leaves[0].shape[0]
    d, offsets = _packed_columns(theta)

    def uniforms(i, t):
        return quant_lib.uniform_columns(key, n, d, offsets[i],
                                         (n,) + t.shape[1:], rows=rows)

    out, range_new, bits, delta = _quantize_leaves(
        leaves, jax.tree_util.tree_leaves(state.q_hat), state.bits_prev,
        state.range_prev, state.initialized, uniforms, cfg, group_ids,
        state.n_groups)
    return _finish_step(state, jax.tree_util.tree_unflatten(treedef, out),
                        range_new, bits, delta, cfg,
                        group_dims(theta, group_ids))


def grouped_quantize_step_split(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], topo: topo_lib.ShardedTopology,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """:func:`grouped_quantize_step_leaves` for workers whose leaves are
    split over several devices (``topo.leaf_specs``), on each device's own
    blocks: each block draws the uniforms at its own positions of the
    packed draw, so every parameter is rounded with the number the packed
    path gives it; only the (N, G) ranges reduce over the worker's
    devices."""
    from jax.sharding import PartitionSpec
    leaves = jax.tree_util.tree_leaves(theta)
    n = leaves[0].shape[0]
    shapes = [x.shape for x in leaves]
    d, offsets = _packed_columns(theta)
    dims = group_dims(theta, group_ids)
    specs = jax.tree_util.tree_leaves(
        topo.leaf_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))

    def local(theta_l, q_hat_l, bits_prev, range_prev, initialized, key):
        t_leaves, treedef = jax.tree_util.tree_flatten(theta_l)

        def uniforms(i, t):
            return quant_lib.uniform_columns(
                key, n, d, offsets[i], shapes[i],
                block=(_block_starts(specs[i], t.shape, topo.mesh), t.shape))

        out, range_new, bits, delta = _quantize_leaves(
            t_leaves, jax.tree_util.tree_leaves(q_hat_l), bits_prev,
            range_prev, initialized, uniforms, cfg, group_ids, len(dims),
            reduce_range=lambda r: jax.lax.pmax(r, topo.rest))
        return (jax.tree_util.tree_unflatten(treedef, out), range_new, bits,
                delta)

    rows = PartitionSpec(topo.axis)
    q_hat_new, range_new, bits, delta = _on_blocks(
        topo, local,
        (topo.leaf_specs, topo.leaf_specs, rows, rows, rows, PartitionSpec()),
        (topo.leaf_specs, rows, rows, rows),
        theta, state.q_hat, state.bits_prev, state.range_prev,
        state.initialized, key)
    return _finish_step(state, q_hat_new, range_new, bits, delta, cfg, dims)


def _where_rows_on_blocks(topo: topo_lib.ShardedTopology, mask: jax.Array,
                          a: Tree, b: Tree, specs) -> Tree:
    """:func:`tree_where_worker` on each device's own blocks."""
    from jax.sharding import PartitionSpec
    return _on_blocks(topo, tree_where_worker,
                      (PartitionSpec(topo.axis), specs, specs), specs,
                      mask, a, b)


def grouped_quantize_step_twopass(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], use_kernel: bool = False,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """The pre-fusion packed path, kept for benchmarks and parity tests:
    the grouped (N, G) min/max side information is computed in a separate
    ``segment_maxabs`` pass over the packed buffer *before* the quantize
    call — one extra full read of (N, D) on the hot path, which is exactly
    what the fused path deletes (``benchmarks/bench_engine.py``
    ``fused_range``). Value-identical to the fused path."""
    pk = packing.make_packing(theta, group_ids)
    theta_p = packing.pack(pk, theta)                     # (N, D) f32
    qprev_p = packing.pack(pk, state.q_hat)               # (N, D) f32

    range_new = packing.segment_maxabs(pk, theta_p - qprev_p)     # (N, G)
    bits, delta, degen = quant_lib.bit_schedule(
        state.bits_prev, range_new, state.range_prev, state.initialized,
        cfg.omega, cfg.b0, cfg.b_max)

    uniforms = jax.random.uniform(key, theta_p.shape, jnp.float32)
    gid_cols = jnp.asarray(pk.col_group_ids)
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.stoch_quantize_grouped(
            theta_p, qprev_p, uniforms, delta, range_new, gid_cols)
    else:
        from repro.kernels import ref as kernel_ref
        out = kernel_ref.stoch_quantize_grouped_ref(
            theta_p, qprev_p, uniforms, delta, range_new, gid_cols)
    # degenerate groups (nothing moved): keep the old reconstruction
    out = jnp.where(jnp.take(degen, gid_cols, axis=1), qprev_p, out)
    return _finish_packed_step(state, pk, out, range_new, bits, delta, cfg)


def grouped_quantize_step_unfused(
    state: GroupQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
    group_ids: Sequence[int], use_kernel: bool = False,
    rows: Optional[np.ndarray] = None, n_workers: Optional[int] = None,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """Per-leaf reference loop (one uniform draw + one quantize call per
    leaf). Single-leaf trees MUST take this path — it draws with the phase
    key directly, which is the seed-golden PRNG contract; for multi-leaf
    trees it exists as the dispatch-overhead baseline
    (``benchmarks/bench_engine.py``) and as a semantics reference. Note the
    multi-leaf PRNG differs from the packed path (per-leaf key split vs one
    packed draw), so the two are not bit-comparable across leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(theta)
    q_leaves = jax.tree_util.tree_leaves(state.q_hat)
    n_groups = state.n_groups
    dims = group_dims(theta, group_ids)

    diff_maxabs = [jnp.max(jnp.abs(t.astype(jnp.float32)
                                   - q.astype(jnp.float32))
                           .reshape(t.shape[0], -1), axis=-1)
                   for t, q in zip(leaves, q_leaves)]
    range_new = _group_reduce(diff_maxabs, group_ids, n_groups,
                              lambda s: jnp.max(s, axis=0))       # (N, G)
    bits, delta, degen = quant_lib.bit_schedule(
        state.bits_prev, range_new, state.range_prev, state.initialized,
        cfg.omega, cfg.b0, cfg.b_max)
    levels = jnp.exp2(bits) - 1.0

    keys = _leaf_keys(key, len(leaves))

    def quant_leaf(t, q, k, g):
        n = t.shape[0]
        shape1 = (n,) + (1,) * (t.ndim - 1)
        d_g = jnp.maximum(delta[:, g], _EPS)
        r_g = range_new[:, g]
        uniforms = _uniforms(k, t.shape, rows, n_workers)
        if use_kernel:
            from repro.kernels import ops as kernel_ops
            flat = t.reshape(n, -1)
            out = kernel_ops.stoch_quantize(
                flat.astype(jnp.float32),
                q.reshape(n, -1).astype(jnp.float32),
                uniforms.reshape(n, -1), d_g, r_g)
            return out.reshape(t.shape).astype(q.dtype)
        sd = d_g.reshape(shape1)
        r = r_g.reshape(shape1)
        lv = levels[:, g].reshape(shape1)
        c = (t.astype(jnp.float32) - q.astype(jnp.float32) + r) / sd  # Eq. 14
        fl = jnp.floor(c)
        qq = jnp.clip(fl + (uniforms < (c - fl)).astype(jnp.float32),
                      0.0, lv)                                        # Eq. 15
        return (q.astype(jnp.float32) + sd * qq - r).astype(q.dtype)  # Eq. 20

    new_leaves = []
    for i, (t, q, k) in enumerate(zip(leaves, q_leaves, keys)):
        g = group_ids[i]
        fresh = quant_leaf(t, q, k, g)
        # degenerate group (nothing moved): keep the old reconstruction
        m = degen[:, g].reshape((t.shape[0],) + (1,) * (t.ndim - 1))
        new_leaves.append(jnp.where(m, q, fresh))
    q_hat_new = jax.tree_util.tree_unflatten(treedef, new_leaves)

    new_state = GroupQuantState(
        q_hat=q_hat_new,
        range_prev=jnp.where(degen, state.range_prev, range_new),
        bits_prev=bits,
        delta_prev=jnp.where(degen, state.delta_prev, delta),
        initialized=jnp.ones_like(state.initialized),
    )
    dims_arr = jnp.asarray(dims, jnp.float32)
    payload = jnp.sum(bits * dims_arr[None, :], axis=-1) \
        + float(n_groups * cfg.b_overhead)
    return new_state, q_hat_new, bits, payload


def identity_quantize_step(
    state: GroupQuantState, theta: Tree,
) -> Tuple[GroupQuantState, Tree, jax.Array, jax.Array]:
    """Unquantized pass-through with 32-bit payload accounting (GGADMM)."""
    n = state.range_prev.shape[0]
    q_cast = jax.tree_util.tree_map(
        lambda t, q: t.astype(q.dtype), theta, state.q_hat)
    new_state = dataclasses.replace(
        state, q_hat=q_cast, initialized=jnp.ones_like(state.initialized))
    bits = jnp.full_like(state.bits_prev, 32.0)
    payload = jnp.full((n,), 32.0 * tree_dim(theta), jnp.float32)
    return new_state, theta, bits, payload


# -------------------------------------------------------------- solvers --
class PrimalSolver(Protocol):
    """Flat exact solver (core/solvers.py): batched argmin of
    f_n + <theta, v_n> + quad_n/2 ||theta||^2 over (N, d) arrays."""

    def primal_solve(self, v: jax.Array, rho_d: jax.Array,
                     theta_init: Optional[jax.Array] = None) -> jax.Array:
        ...


class LocalSolver(Protocol):
    """Engine-facing local solver over pytrees."""

    def init_opt(self, theta: Tree) -> Tuple[Tree, Tree]:
        ...

    def solve(self, theta0: Tree, v: Tree, quad: jax.Array,
              mu: Tree, nu: Tree, batch: Any) -> Tuple[Tree, Tree, Tree]:
        ...


def _flatten_worker(tree: Tree) -> jax.Array:
    """Tree -> (N, d) via the shared packed layout (one leaf: plain
    reshape, dtype untouched; multi-leaf: concat in leaf order, promoted
    dtype — matching jnp.concatenate's own promotion)."""
    leaves = jax.tree_util.tree_leaves(tree)
    pk = packing.make_packing(tree, (0,) * len(leaves))
    return packing.pack(pk, tree,
                        dtype=jnp.result_type(*[x.dtype for x in leaves]))


def _unflatten_worker(flat: jax.Array, like: Tree) -> Tree:
    pk = packing.make_packing(
        like, (0,) * len(jax.tree_util.tree_leaves(like)))
    return packing.unpack(pk, flat, like=like)


@dataclasses.dataclass(frozen=True)
class ExactSolver:
    """Adapter: a flat ``PrimalSolver`` (closed form / Newton) as the
    engine's local solver. The tree is raveled per worker, solved as one
    (N, d) system, and unraveled — for a one-leaf (N, d) tree this is the
    identity transform, so numerics match the seed flat stepper exactly."""

    problem: PrimalSolver

    def init_opt(self, theta: Tree) -> Tuple[Tree, Tree]:
        del theta
        return (), ()

    def solve(self, theta0, v, quad, mu, nu, batch):
        del batch
        flat = self.problem.primal_solve(
            _flatten_worker(v), quad, theta_init=_flatten_worker(theta0))
        return _unflatten_worker(flat, theta0), mu, nu


@dataclasses.dataclass(frozen=True)
class InexactSolver:
    """K Adam (or SGD) steps on g(theta) = f(theta) + <theta, v> +
    quad/2 ||theta||^2 — the inexact-ADMM local solver for non-convex f_n
    (DESIGN.md §5). Optimizer moments persist across outer iterations.

    When the engine is given a batch, ``grad_fn(theta, batch)`` must be
    row-wise: worker n's gradient depends only on row n of ``theta`` and
    row n of every ``batch`` leaf (a ``jax.vmap`` over workers is). An
    alternating step then solves only the acting group's rows, passing
    ``grad_fn`` those rows of both. Called without a batch, ``grad_fn``
    always sees all N rows (it may close over N-row problem data)."""

    grad_fn: Optional[Callable[[Tree, Any], Tree]] = None
    local_steps: int = 4
    local_lr: float = 1e-3
    use_adam: bool = True
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8

    def init_opt(self, theta: Tree) -> Tuple[Tree, Tree]:
        if not self.use_adam:
            return (), ()
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), theta)
        return zeros, jax.tree_util.tree_map(jnp.copy, zeros)

    def solve(self, theta0, v, quad, mu0, nu0, batch):
        def aug_grad(th):
            g = self.grad_fn(th, batch)

            def one(gl, thl, vl):
                shape1 = (thl.shape[0],) + (1,) * (thl.ndim - 1)
                return (gl.astype(jnp.float32) + vl.astype(jnp.float32)
                        + quad.reshape(shape1) * thl.astype(jnp.float32))
            return jax.tree_util.tree_map(one, g, th, v)

        if not self.use_adam:                      # plain SGD, no moments
            def sgd_body(i, th):
                g = aug_grad(th)
                return jax.tree_util.tree_map(
                    lambda p, gl: (p.astype(jnp.float32)
                                   - self.local_lr * gl).astype(p.dtype),
                    th, g)

            th = jax.lax.fori_loop(0, self.local_steps, sgd_body, theta0)
            return th, mu0, nu0

        b1, b2, eps = self.b1, self.b2, self.eps

        def body(i, carry):
            th, mu, nu = carry
            g = aug_grad(th)
            t = i + 1.0
            b1c = 1.0 - b1 ** t
            b2c = 1.0 - b2 ** t

            def upd(p, gl, m, vv):
                m_new = b1 * m + (1 - b1) * gl
                v_new = b2 * vv + (1 - b2) * jnp.square(gl)
                step = (m_new / b1c) / (jnp.sqrt(v_new / b2c) + eps)
                return ((p.astype(jnp.float32) - self.local_lr * step)
                        .astype(p.dtype), m_new, v_new)

            out = jax.tree_util.tree_map(upd, th, g, mu, nu)
            is_triple = lambda o: isinstance(o, tuple)  # noqa: E731
            th2 = jax.tree_util.tree_map(lambda o: o[0], out,
                                         is_leaf=is_triple)
            mu2 = jax.tree_util.tree_map(lambda o: o[1], out,
                                         is_leaf=is_triple)
            nu2 = jax.tree_util.tree_map(lambda o: o[2], out,
                                         is_leaf=is_triple)
            return th2, mu2, nu2

        return jax.lax.fori_loop(0, self.local_steps, body,
                                 (theta0, mu0, nu0))


# ------------------------------------------------------------- the engine --
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Hyperparameters of the unified stepper.

    ``groups``/``censor_mode`` are the layer-aware switches; everything else
    matches the seed ``ADMMConfig`` (the flat adapter aliases this class).
    """

    rho: float = 1.0
    alternating: bool = True          # GADMM grouping; False => Jacobian ADMM
    censor: CensorConfig = dataclasses.field(default_factory=CensorConfig)
    quantize: Optional[QuantConfig] = None
    groups: GroupSpec = "model"       # "model"|"leaf"|"block:..."|"auto:K"|
    #                                   explicit ids | index buckets
    censor_mode: str = "global"       # "global" (paper) | "group" (new)
    mix_backend: str = "dense"        # "dense" | "sparse" | "sharded"
    use_pallas_mix: bool = False      # route the mix through its kernel
    use_pallas_quant: bool = False
    hat_dtype: Optional[str] = None   # narrow theta_hat/q_hat/alpha replicas
    regroup_every: int = 0            # auto:K re-clustering period (0 = off)

    def __post_init__(self):
        assert self.censor_mode in ("global", "group")
        assert self.mix_backend in topo_lib.BACKENDS, self.mix_backend
        if isinstance(self.groups, str):
            # fail loudly on a typo'd spec at config construction — the old
            # behavior surfaced only as an unrelated int() error deep in
            # resolve_groups (or not at all)
            packing.validate_spec_syntax(self.groups)
        if self.regroup_every < 0:
            raise ValueError(f"regroup_every must be >= 0, "
                             f"got {self.regroup_every}")

    @property
    def name(self) -> str:
        if not self.alternating:
            return "c-admm" if self.censor.enabled else "jacobian-admm"
        tag = "ggadmm"
        if self.censor.enabled:
            tag = "c-" + tag
        if self.quantize is not None:
            tag = ("cq-" + tag[2:]) if tag.startswith("c-") else "q-" + tag
        return tag


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineState:
    """Every per-worker quantity as the same pytree with leading axis N.

    ``opt_mu``/``opt_nu`` are the local solver's persistent moments (empty
    tuples for exact solvers). For the flat adapter theta IS the (N, d)
    array — a bare array is a one-leaf pytree."""

    theta: Tree          # per-worker primal theta_n^k
    theta_hat: Tree      # last *transmitted* value per worker
    alpha: Tree          # duals alpha_n^k
    quant: GroupQuantState
    opt_mu: Tree
    opt_nu: Tree
    k: jax.Array         # iteration counter


def n_groups_of(theta: Tree, groups: GroupSpec) -> int:
    return max(resolve_groups(theta, groups)) + 1


# ------------------------------------------------------- auto-grouping --
@jax.jit
def _leaf_maxabs_stack(theta: Tree, q_hat: Tree) -> jax.Array:
    return jnp.stack([jnp.max(jnp.abs(t.astype(jnp.float32)
                                      - q.astype(jnp.float32)))
                      for t, q in zip(jax.tree_util.tree_leaves(theta),
                                      jax.tree_util.tree_leaves(q_hat))])


def leaf_log_ranges(theta: Tree, q_hat: Tree) -> np.ndarray:
    """Host-side per-leaf log2 quantizer range: max over workers and
    coordinates of ``|theta - q_hat|`` per leaf, floored at 2^-40. One
    jitted (L,) reduction and a single device->host transfer, run only at
    regroup events (outside the training jit)."""
    vals = np.asarray(_leaf_maxabs_stack(theta, q_hat), np.float64)
    return np.log2(np.maximum(vals, 2.0 ** -40))


def remap_group_state(quant: GroupQuantState, old_ids: Sequence[int],
                      new_ids: Sequence[int]) -> GroupQuantState:
    """Carry the (N, G) quantizer-chain state across a regroup event.

    Each new group inherits the *most conservative* side information of the
    old groups its leaves came from: max range/bits/delta (a larger R and b
    keep the Eq. (18) growth rule's Δ^k <= ω Δ^{k-1} contract satisfiable)
    and min ``initialized`` (a new group touching any uninitialized old
    group restarts at b0). ``q_hat`` replicas are per-coordinate and carry
    over untouched — regrouping never desynchronizes receiver replicas."""
    old_ids = tuple(int(g) for g in old_ids)
    new_ids = tuple(int(g) for g in new_ids)
    if len(old_ids) != len(new_ids):
        raise ValueError(f"remap across different trees: {len(old_ids)} "
                         f"vs {len(new_ids)} leaves")
    if old_ids == new_ids:
        return quant
    cols_r, cols_b, cols_d, cols_i = [], [], [], []
    for g in range(max(new_ids) + 1):
        olds = sorted({old_ids[i] for i, ng in enumerate(new_ids)
                       if ng == g})
        idx = jnp.asarray(olds, jnp.int32)
        cols_r.append(jnp.max(quant.range_prev[:, idx], axis=1))
        cols_b.append(jnp.max(quant.bits_prev[:, idx], axis=1))
        cols_d.append(jnp.max(quant.delta_prev[:, idx], axis=1))
        cols_i.append(jnp.min(quant.initialized[:, idx], axis=1))
    return GroupQuantState(
        q_hat=quant.q_hat,
        range_prev=jnp.stack(cols_r, axis=1),
        bits_prev=jnp.stack(cols_b, axis=1),
        delta_prev=jnp.stack(cols_d, axis=1),
        initialized=jnp.stack(cols_i, axis=1),
    )


@dataclasses.dataclass
class AutoGrouper:
    """Driver-side re-clustering loop for ``groups="auto:K"``.

    Holds an EMA of per-leaf log2 ranges and, every ``regroup_every``
    rounds, re-runs the greedy adjacent-merge clustering
    (``packing.greedy_range_grouping``). Group ids are segment indices in
    leaf order — monotone over leaves — so ids never permute between
    regroup events (only segment boundaries move), keeping the compiled
    step's static layout (and therefore the phase-key PRNG stream, which is
    drawn per packed buffer independent of G) deterministic for a given
    seed. The caller (``launch/train.py``) swaps ``EngineConfig.groups``
    for the returned explicit ids, remaps the quantizer state with
    :func:`remap_group_state`, and re-jits the step when ids change."""

    k: int
    regroup_every: int
    ema: float = 0.5
    log_ranges: Optional[np.ndarray] = None

    @staticmethod
    def from_config(cfg: "EngineConfig") -> Optional["AutoGrouper"]:
        if (isinstance(cfg.groups, str) and cfg.groups.startswith("auto:")
                and cfg.regroup_every > 0):
            return AutoGrouper(k=packing.parse_auto_spec(cfg.groups),
                               regroup_every=cfg.regroup_every)
        return None

    def should_regroup(self, step_idx: int) -> bool:
        return (self.regroup_every > 0 and step_idx > 0
                and step_idx % self.regroup_every == 0)

    def regroup(self, theta: Tree, q_hat: Tree) -> Tuple[int, ...]:
        stats = leaf_log_ranges(theta, q_hat)
        if self.log_ranges is None:
            self.log_ranges = stats
        else:
            self.log_ranges = (self.ema * self.log_ranges
                               + (1.0 - self.ema) * stats)
        dims = [int(x.size // x.shape[0])
                for x in jax.tree_util.tree_leaves(theta)]
        return packing.greedy_range_grouping(self.log_ranges, dims, self.k)


def init_state(theta: Tree, cfg: EngineConfig,
               solver: Optional[LocalSolver] = None) -> EngineState:
    """Engine state from per-worker initial parameters (leading axis N)."""
    qcfg = cfg.quantize or QuantConfig()
    hat_dtype = jnp.dtype(cfg.hat_dtype) if cfg.hat_dtype else None
    g = n_groups_of(theta, cfg.groups)
    mu, nu = solver.init_opt(theta) if solver is not None else ((), ())

    def hat_zeros(x):
        return jnp.zeros(x.shape, hat_dtype or x.dtype)

    return EngineState(
        theta=theta,
        theta_hat=jax.tree_util.tree_map(hat_zeros, theta),
        alpha=jax.tree_util.tree_map(hat_zeros, theta),  # alpha^0 in col(M_-)
        quant=GroupQuantState.create(theta, g, b0=qcfg.b0,
                                     hat_dtype=hat_dtype),
        opt_mu=mu,
        opt_nu=nu,
        k=jnp.zeros((), jnp.int32),
    )


def _censor_masks(theta_hat: Tree, candidate: Tree, cfg: EngineConfig,
                  group_ids: Sequence[int], n_groups: int,
                  k_next: jax.Array,
                  split: Optional[topo_lib.ShardedTopology] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Returns ``(worker_mask (n,), group_mask (n, G))`` censoring decisions
    for the n rows ``candidate`` and ``theta_hat`` hold. The squared norms
    of ``candidate - theta_hat`` sum leaf by leaf, each leaf in its own
    shape, to (n, G); ``split`` (the topology of workers whose leaves are
    split over devices): on each device's own blocks, and only the (n, G)
    sums cross devices."""
    leaves = jax.tree_util.tree_leaves(candidate)
    n = leaves[0].shape[0]
    if not cfg.censor.enabled:
        ones = jnp.ones((n,), jnp.float32)
        return ones, jnp.ones((n, n_groups), jnp.float32)

    def sqnorms(cand, hat):
        return _group_reduce(
            [_over_worker(jnp.sum, jnp.square(c.astype(jnp.float32)
                                              - h.astype(jnp.float32)))
             for c, h in zip(jax.tree_util.tree_leaves(cand),
                             jax.tree_util.tree_leaves(hat))],
            group_ids, n_groups, lambda s: jnp.sum(s, axis=0))   # (n, G)

    tau = threshold(cfg.censor, k_next)
    if split is None:
        sq = sqnorms(candidate, theta_hat)
    else:
        from jax.sharding import PartitionSpec
        sq = _on_blocks(
            split, lambda c, h: jax.lax.psum(sqnorms(c, h), split.rest),
            (split.leaf_specs, split.leaf_specs), PartitionSpec(split.axis),
            candidate, theta_hat)
    if cfg.censor_mode == "global":
        cmask = (jnp.sqrt(jnp.sum(sq, axis=-1)) >= tau).astype(jnp.float32)
        return cmask, jnp.broadcast_to(cmask[:, None], (n, n_groups))
    # per-group censoring: tau_g^2 proportional to d_g so the group
    # thresholds partition the global budget (sum_g tau_g^2 = tau^2).
    # Threshold math lives in core.censoring so every spec shape shares it.
    gmask = censor_lib.group_censor_mask(
        jnp.sqrt(sq), censor_lib.group_thresholds(
            tau, group_dims(candidate, group_ids), tree_dim(candidate)))
    return jnp.max(gmask, axis=-1), gmask


def _solves_rows(solver: LocalSolver, batch: Any) -> bool:
    """Whether a phase may solve its acting rows alone: an
    :class:`InexactSolver` given a batch carries each worker's data in that
    batch's rows (its row-wise ``grad_fn`` contract). An
    :class:`ExactSolver`'s problem, or a ``grad_fn`` called without a
    batch, may hold N rows of data of its own."""
    return batch is not None and isinstance(solver, InexactSolver)


def _quantize(quant: GroupQuantState, theta: Tree, key: jax.Array,
              cfg: EngineConfig, group_ids: Sequence[int],
              rows: Optional[np.ndarray] = None,
              n_workers: Optional[int] = None,
              split: Optional[topo_lib.ShardedTopology] = None):
    if cfg.quantize is not None and split is not None:
        return grouped_quantize_step_split(quant, theta, key, cfg.quantize,
                                           group_ids, split)
    if cfg.quantize is not None:
        return grouped_quantize_step(quant, theta, key, cfg.quantize,
                                     group_ids,
                                     use_kernel=cfg.use_pallas_quant,
                                     rows=rows, n_workers=n_workers)
    return identity_quantize_step(quant, theta)


def _count_rows(part: str, rows: Optional[np.ndarray], n: int) -> None:
    if rows is None:
        engine_phase_rows_counter().inc(n, part=part, path="masked")
    else:
        engine_phase_rows_counter().inc(len(rows), part=part, path="rows")


def _count_layout(part: str, layout: str) -> None:
    engine_exchange_layout_counter().inc(1, part=part, layout=layout)


def _quantize_layout(theta: Tree, cfg: EngineConfig,
                     split: Optional[topo_lib.ShardedTopology]) -> str:
    """The layout :func:`_quantize` works on: ``packed`` where
    :func:`grouped_quantize_step` takes the Pallas kernel over the ``(N,
    D)`` buffer, else ``leaf``."""
    packs = (cfg.quantize is not None and cfg.use_pallas_quant
             and split is None and len(jax.tree_util.tree_leaves(theta)) > 1)
    return "packed" if packs else "leaf"


def _phase(state: EngineState, phase_mask: jax.Array, solver: LocalSolver,
           topo: topo_lib.Topology, rho_d: jax.Array, cfg: EngineConfig,
           key: jax.Array, batch: Any,
           participation: Optional[jax.Array] = None,
           rows: Optional[np.ndarray] = None,
           split: Optional[topo_lib.ShardedTopology] = None,
           ) -> Tuple[EngineState, jax.Array, jax.Array, jax.Array,
                      jax.Array, jax.Array, jax.Array, jax.Array]:
    """One group's primal update + (grouped quantize) + (censor) + commit.

    The neighbor aggregation goes through the pluggable ``topo`` backend
    (dense matmul / sparse edge gather / sharded SPMD — DESIGN.md
    §Topology).

    Each part runs under a ``jax.named_scope`` (``admm.mix``,
    ``admm.solve``, ``admm.quantize``, ``admm.censor``, ``admm.commit``;
    ``make_step`` adds ``admm.dual`` and ``admm.metrics``): metadata that
    names the compiled program's instructions by the part of the round
    they belong to, and adds no op (DESIGN.md §Observability).

    Returns the 8-tuple ``(new_state, tx_mask (N,), payload_bits (N,),
    candidate_payload_bits (N,), bits (N, G), group_tx (N, G),
    censor_mask (N,), offered_payload_bits (N,))`` restricted to
    ``phase_mask`` (zeros elsewhere). ``payload_bits`` counts only bits
    actually put on the wire — a censored worker contributes exactly zero;
    ``candidate_payload_bits`` is what the transmission would have cost had
    censoring not suppressed it (the pre-fix metric, kept for
    energy-what-if accounting).

    ``participation`` is the fleet-fault hook (DESIGN.md §Fleet): an
    optional (N,) 0/1 mask of workers whose transmission arrives on time
    this round. A timed-out worker is treated exactly like a censored
    worker (the paper's machinery already prices "sent nothing this
    round"): its local primal/quantizer chain still advances, but its
    ``theta_hat`` commit is suppressed, its tx decision is forced to 0,
    and it contributes exactly ZERO payload bits. The composed transmit
    decision is always ``timeout_mask & censor_mask``
    (``censoring.compose_tx_mask``). ``censor_mask``/``offered_payload``
    report the censor-only decision and the bits the worker *offered* to
    ship before the timeout composition — the staleness buffer charges
    these at delivery time. With ``participation=None`` (the synchronous
    golden path) ``censor_mask == tx_mask`` and
    ``offered_payload == payload_bits``, bit-for-bit.

    ``rows`` (static; the nonzero rows of ``phase_mask``) selects the
    acting-rows path: quantize, and solve where the solver's data rides in
    ``batch`` (:func:`_solves_rows`), take those rows, compute, and write
    them back into the N-row state; ``None`` computes all N rows and keeps
    ``phase_mask``'s. Both give the same numbers.

    ``split`` (the topology of workers that span several devices each,
    with ``rows`` ``None``): quantize, censor and commit on each device's
    own blocks (:func:`grouped_quantize_step_split`), the same numbers
    again.
    """
    group_ids = resolve_groups(state.theta, cfg.groups)
    n = phase_mask.shape[0]
    solve_rows = rows if _solves_rows(solver, batch) else None
    n_groups = max(group_ids) + 1
    rho = cfg.rho
    with jax.named_scope("admm.mix"):
        # where the phase solves its acting rows alone, only their
        # neighbour sums and primal terms are computed
        _count_layout("mix", topo.layout)
        neigh = topo.mix(state.theta_hat, rows=solve_rows)
        if cfg.alternating:
            # GGADMM primal, Eqs. (11)/(12)/(21)/(22)
            alpha = state.alpha if solve_rows is None \
                else take_rows(state.alpha, solve_rows)
            v = jax.tree_util.tree_map(
                lambda a, nm: a.astype(jnp.float32)
                - rho * nm.astype(jnp.float32), alpha, neigh)
            quad = rho_d
        else:
            # Jacobian C-ADMM primal (Liu et al., 2019b): proximal
            # self-anchor
            def jac_v(a, th, nm):
                shape1 = (th.shape[0],) + (1,) * (th.ndim - 1)
                return (a.astype(jnp.float32)
                        - rho_d.reshape(shape1) * th.astype(jnp.float32)
                        - rho * nm.astype(jnp.float32))
            v = jax.tree_util.tree_map(jac_v, state.alpha, state.theta_hat,
                                       neigh)
            quad = 2.0 * rho_d

    _count_rows("solve", solve_rows, n)
    with jax.named_scope("admm.solve"):
        if solve_rows is None:
            theta_full, mu_full, nu_full = solver.solve(
                state.theta, v, quad, state.opt_mu, state.opt_nu, batch)
            theta = tree_where_worker(phase_mask, theta_full, state.theta)
            mu = tree_where_worker(phase_mask, mu_full, state.opt_mu)
            nu = tree_where_worker(phase_mask, nu_full, state.opt_nu)
        else:
            def rows_of(a):
                return take_rows(a, solve_rows)
            theta_r, mu_r, nu_r = solver.solve(
                rows_of(state.theta), v, rows_of(quad),
                rows_of(state.opt_mu), rows_of(state.opt_nu), rows_of(batch))
            theta = put_rows(state.theta, solve_rows, theta_r)
            mu = put_rows(state.opt_mu, solve_rows, mu_r)
            nu = put_rows(state.opt_nu, solve_rows, nu_r)

    _count_rows("quantize", rows, n)
    with jax.named_scope("admm.quantize"):
        _count_layout("quantize", _quantize_layout(theta, cfg, split))
        if rows is None:
            quant_new, candidate, bits, payload = _quantize(
                state.quant, theta, key, cfg, group_ids, split=split)
            cand_rows = candidate
        else:
            quant_r, cand_rows, bits_r, payload_r = _quantize(
                take_rows(state.quant, rows), take_rows(theta, rows), key,
                cfg, group_ids, rows, n)
            quant_new = put_rows(state.quant, rows, quant_r)
            # the masked path's candidate on the acting rows; the others
            # are masked out by the censor step, as there
            candidate = quant_new.q_hat if cfg.quantize is not None \
                else theta
            bits = put_rows(jnp.zeros_like(state.quant.bits_prev), rows,
                            bits_r)
            payload = put_rows(jnp.zeros((n,), jnp.float32), rows,
                               payload_r)

    with jax.named_scope("admm.censor"):
        _count_layout("censor", "leaf")
        tx_mask, payload_tx, candidate_payload, group_tx, censor_mask, \
            offered_payload = _censor_and_price(
                state, cand_rows, theta, bits, payload, phase_mask, cfg,
                group_ids, n_groups, participation, split, rows)
        pm_col = phase_mask[:, None]
        phase_bits = bits * pm_col

    with jax.named_scope("admm.commit"):
        if rows is None:
            # quantizer replicas advance for the acting phase's workers
            # only (they ran Eq. (20) this phase; censoring does not roll
            # the chain back)
            if split is None:
                quant_new = tree_where_worker(phase_mask, quant_new,
                                              state.quant)
            else:
                quant_new = _where_rows_on_blocks(
                    split, phase_mask, quant_new, state.quant,
                    _quant_specs(split))
        new_state = _commit(state, candidate, quant_new, group_tx,
                            group_ids, theta, mu, nu, split)
    return (new_state, tx_mask, payload_tx, candidate_payload, phase_bits,
            group_tx, censor_mask, offered_payload)


def _censor_and_price(state: EngineState, candidate: Tree, theta: Tree,
                      bits: jax.Array, payload: jax.Array,
                      phase_mask: jax.Array, cfg: EngineConfig,
                      group_ids: Sequence[int], n_groups: int,
                      participation: Optional[jax.Array],
                      split: Optional[topo_lib.ShardedTopology] = None,
                      rows: Optional[np.ndarray] = None):
    """The censor test (Eq. 13/14), its composition with ``participation``
    and the phase, and the bits each decision puts on the wire. With
    ``rows`` (static), ``candidate`` holds only those rows and only they
    are tested: the others are masked out by the phase. Returns
    ``(tx_mask, payload_tx, candidate_payload, group_tx, censor_mask,
    offered_payload)``."""
    k_next = (state.k + 1).astype(jnp.float32)
    hat = state.theta_hat if rows is None \
        else take_rows(state.theta_hat, rows)
    cmask_cens, gmask_cens = _censor_masks(hat, candidate, cfg, group_ids,
                                           n_groups, k_next, split)
    if rows is not None:
        n = phase_mask.shape[0]
        cmask_cens = put_rows(jnp.zeros((n,), jnp.float32), rows,
                              cmask_cens)
        gmask_cens = put_rows(jnp.zeros((n, n_groups), jnp.float32), rows,
                              gmask_cens)
    if participation is not None:
        # timeout composes AFTER the censor test: tx = timeout & censor.
        # The censor decision itself (and the quantizer chain below) is
        # timeout-agnostic — the worker computed its update on time, the
        # network just didn't deliver it.
        cmask, group_cmask = censor_lib.compose_tx_mask(
            participation, cmask_cens, gmask_cens)
    else:
        cmask, group_cmask = cmask_cens, gmask_cens
    censor_mask = cmask_cens * phase_mask          # censor-only decision
    tx_mask = cmask * phase_mask                   # only this phase acts
    group_tx = group_cmask * phase_mask[:, None]
    candidate_payload = payload * phase_mask       # cost had nothing censored
    if cfg.censor_mode == "group" and cfg.censor.enabled:
        # payload counts only the transmitted groups (+ their overhead)
        dims = jnp.asarray(group_dims(theta, group_ids), jnp.float32)
        overhead = float(cfg.quantize.b_overhead) \
            if cfg.quantize is not None else 0.0
        per_group = bits * dims[None, :] + overhead
        payload_tx = jnp.sum(per_group * group_tx, axis=-1)
        offered_payload = payload_tx if participation is None else jnp.sum(
            per_group * gmask_cens * phase_mask[:, None], axis=-1)
    else:
        # global mode: a censored link costs zero bits (censoring's whole
        # value proposition) — mask by the transmit decision, not the phase
        payload_tx = payload * tx_mask
        offered_payload = payload_tx if participation is None \
            else payload * censor_mask
    return (tx_mask, payload_tx, candidate_payload, group_tx, censor_mask,
            offered_payload)


def _commit(state: EngineState, candidate: Tree,
            quant: GroupQuantState, group_tx: jax.Array,
            group_ids: Sequence[int], theta: Tree, mu: Tree,
            nu: Tree, split: Optional[topo_lib.ShardedTopology] = None
            ) -> EngineState:
    """The phase's new state: ``theta_hat`` committed where each leaf's
    group transmitted, beside the phase's merged primal, quantizer and
    optimizer state. Reads no field of ``state`` that the phase replaced,
    so the acting-rows path updates those buffers in place. ``split``: the
    commit runs on each device's own blocks."""
    def commit_hat(group_tx, theta_hat, candidate):
        # theta_hat: each leaf commits where its group transmitted
        hat_leaves, treedef = jax.tree_util.tree_flatten(theta_hat)
        cand_leaves = jax.tree_util.tree_leaves(candidate)
        new_hat = []
        for i, (h, c) in enumerate(zip(hat_leaves, cand_leaves)):
            m = group_tx[:, group_ids[i]].reshape(
                (h.shape[0],) + (1,) * (h.ndim - 1))
            new_hat.append(jnp.where(m > 0, c.astype(h.dtype), h))
        return jax.tree_util.tree_unflatten(treedef, new_hat)

    if split is None:
        theta_hat = commit_hat(group_tx, state.theta_hat, candidate)
    else:
        from jax.sharding import PartitionSpec
        theta_hat = _on_blocks(
            split, commit_hat,
            (PartitionSpec(split.axis), split.leaf_specs, split.leaf_specs),
            split.leaf_specs, group_tx, state.theta_hat, candidate)
    return dataclasses.replace(state, theta=theta, theta_hat=theta_hat,
                               quant=quant, opt_mu=mu, opt_nu=nu)


MetricsFn = Callable[[EngineState, Any], Dict[str, jax.Array]]


def _phase_rows(graph: WorkerGraph, cfg: EngineConfig,
                topo: topo_lib.Topology, mesh: Any
                ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """The head and tail phases' acting rows (static), or ``None`` for a
    phase that keeps the masked path: every phase of a Jacobian step, and
    every phase when the workers are spread over devices (a ``mesh`` or
    the sharded backend), where a row gather would move parameters between
    them."""
    if not cfg.alternating or mesh is not None or topo.backend == "sharded":
        return None, None
    is_head = np.asarray(graph.head_mask, bool)
    return tuple(np.flatnonzero(m) if m.any() else None
                 for m in (is_head, ~is_head))


def make_step(graph: WorkerGraph, cfg: EngineConfig, solver: LocalSolver,
              extra_metrics: Optional[MetricsFn] = None, *,
              mesh: Any = None, worker_axis: Optional[str] = None,
              topology: Optional[topo_lib.Topology] = None,
              leaf_specs: Any = None):
    """Build the jittable per-iteration engine step.

    ``step(state, batch, key[, participation]) -> (state, metrics)``;
    ``batch`` is forwarded to the local solver (None for data-free exact
    solvers). ``participation`` is the optional (N,) on-time mask of the
    fleet harness (``fleet/sim.py``): a timed-out worker is composed into
    the censoring decision (tx = timeout & censor, zero payload bits) —
    ``None`` (default) is the synchronous golden path, traced without any
    fault machinery. Metrics always carry per-worker ``tx_mask``,
    ``payload_bits`` (bits actually transmitted — zero for censored OR
    timed-out workers), ``candidate_payload_bits`` (what the round would
    have cost uncensored), ``censor_mask``/``offered_payload_bits`` (the
    censor-only decision and its cost before the timeout composition —
    equal to ``tx_mask``/``payload_bits`` on the golden path), plus the
    layer-aware ``group_tx``/``bits_per_group`` diagnostics and the
    ``dual_residual`` convergence term ``||rho (D - A) theta_hat||²``
    (free — it reuses the dual update's Laplacian);
    ``extra_metrics(state, batch)`` appends problem-specific entries
    (residuals, losses).

    Every graph operation rides the ``cfg.mix_backend`` topology backend;
    ``mesh``/``worker_axis`` are forwarded to the sharded backend (the
    production ADMM bundle passes its SPMD mesh — other callers can leave
    them unset). A caller that already built a matching ``topology``
    (e.g. to share it with a metrics fn) can pass it instead.

    Where the mesh has axes besides ``worker_axis`` (each worker spans
    several devices, its leaves split over them as ``leaf_specs``, the
    PartitionSpecs of the worker-stacked parameter tree, say), quantize
    and censor run leaf by leaf in each leaf's own layout and the sharded
    backend mixes each leaf in its layout: only (N, G) scalars cross a
    worker's devices outside the local solve (DESIGN.md §Engine).
    """
    topo = topology if topology is not None else topo_lib.build(
        graph, cfg.mix_backend, use_pallas_mix=cfg.use_pallas_mix,
        mesh=mesh, worker_axis=worker_axis, leaf_specs=leaf_specs)
    per_worker = topo_lib.devices_per_worker(mesh, worker_axis)
    split = topo if getattr(topo, "leaf_specs", None) is not None else None
    head = jnp.asarray(graph.head_mask, jnp.float32)
    tail = 1.0 - head
    rho_d = cfg.rho * topo.degrees
    head_rows, tail_rows = _phase_rows(graph, cfg, topo, mesh)

    def step(state: EngineState, batch, key: jax.Array,
             participation: Optional[jax.Array] = None):
        engine_worker_layout_counter().inc(
            1, workers=str(graph.n), chips_per_worker=str(per_worker))
        k1, k2 = jax.random.split(key)
        if cfg.alternating:
            state, tx_h, pay_h, cand_h, bits_h, gtx_h, cm_h, off_h = _phase(
                state, head, solver, topo, rho_d, cfg, k1, batch,
                participation=participation, rows=head_rows, split=split)
            state, tx_t, pay_t, cand_t, bits_t, gtx_t, cm_t, off_t = _phase(
                state, tail, solver, topo, rho_d, cfg, k2, batch,
                participation=participation, rows=tail_rows, split=split)
            tx_mask = tx_h + tx_t
            payload = pay_h + pay_t
            candidate_payload = cand_h + cand_t
            bits_g = bits_h + bits_t
            group_tx = gtx_h + gtx_t
            censor_mask = cm_h + cm_t
            offered_payload = off_h + off_t
        else:
            all_mask = jnp.ones_like(head)
            (state, tx_mask, payload, candidate_payload, bits_g, group_tx,
             censor_mask, offered_payload) = \
                _phase(state, all_mask, solver, topo, rho_d, cfg, k1,
                       batch, participation=participation, split=split)

        # Dual update, Eq. (23): alpha += rho * (D - A) theta_hat. The
        # Laplacian goes through the same topology backend (and therefore
        # the same kernel routing) as the phase mixes — the seed bug where
        # the dual step silently dropped ``use_pallas_mix`` cannot recur.
        with jax.named_scope("admm.dual"):
            _count_layout("dual", topo.layout)
            lap = topo.laplacian(state.theta_hat)

            def dual(a, lp):
                return (a.astype(jnp.float32)
                        + cfg.rho * lp).astype(a.dtype)

            alpha = jax.tree_util.tree_map(dual, state.alpha, lap)
            # squared norm of the dual step rho (D - A) theta_hat, from
            # the Laplacian already computed for alpha (no extra mix);
            # -> 0 exactly at consensus of the transmitted models
            dual_residual = (cfg.rho ** 2) * topo.dual_residual(lap)
        state = dataclasses.replace(state, alpha=alpha, k=state.k + 1)

        metrics = {
            "tx_mask": tx_mask,
            "payload_bits": payload,
            "candidate_payload_bits": candidate_payload,
            "bits_per_group": bits_g,
            "group_tx": group_tx,
            "censor_mask": censor_mask,
            "offered_payload_bits": offered_payload,
            "dual_residual": dual_residual,
        }
        if extra_metrics is not None:
            with jax.named_scope("admm.metrics"):
                metrics.update(extra_metrics(state, batch))
        return state, metrics

    return step


def flat_metrics(graph: WorkerGraph,
                 mix_backend: Union[str, topo_lib.Topology] = "dense",
                 ) -> MetricsFn:
    """Seed flat-stepper diagnostics: pairwise primal residual (Eq. 28) and
    the theta trajectory (for objective / distance-to-optimum curves).

    The residual reduction rides the topology backend: dense keeps the
    seed's O(N²·d) pairwise form bit-for-bit; sparse sums per-edge
    differences in O(E·d). ``mix_backend`` may be a backend name or an
    already-built :class:`~repro.core.topology.Topology` (so adapters
    share one instance with ``make_step``)."""
    topo = (mix_backend if isinstance(mix_backend, topo_lib.Topology)
            else topo_lib.build(graph, mix_backend))

    def fn(state: EngineState, batch) -> Dict[str, jax.Array]:
        del batch
        theta = _flatten_worker(state.theta)
        return {"primal_residual": topo.primal_residual(theta),
                "theta": theta}

    return fn


def consensus_metrics(loss_fn: Optional[Callable] = None, *,
                      split: bool = False) -> MetricsFn:
    """Training diagnostics: deviation from the worker mean (+ loss).
    ``split``: the workers' leaves are split over devices, so the squared
    deviations sum in each leaf's own shape, and the loss, a forward pass
    with the model's own collectives, runs under its own scope
    (``admm.metrics/loss``)."""

    def fn(state: EngineState, batch) -> Dict[str, jax.Array]:
        mean_theta = jax.tree_util.tree_map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True),
            state.theta)
        dev = jax.tree_util.tree_map(
            lambda x, m: x.astype(jnp.float32) - m, state.theta, mean_theta)
        if split:
            err = sum(jnp.sum(jnp.square(x))
                      for x in jax.tree_util.tree_leaves(dev))
        else:
            err = jnp.sum(tree_worker_sqnorm(dev))
        out = {"consensus_err": err}
        if loss_fn is not None and split:
            with jax.named_scope("loss"):
                out["loss"] = loss_fn(state.theta, batch)
        elif loss_fn is not None:
            out["loss"] = loss_fn(state.theta, batch)
        return out

    return fn


def run(graph: WorkerGraph, cfg: EngineConfig, solver: LocalSolver,
        theta0: Tree, iters: int, seed: int = 0,
        extra_metrics: Optional[MetricsFn] = None,
        topology: Optional[topo_lib.Topology] = None,
        ) -> Tuple[EngineState, Dict[str, jax.Array]]:
    """Scan the engine step for ``iters`` iterations (batch-free problems)
    and return the final state plus stacked per-iteration metrics."""
    state = init_state(theta0, cfg, solver)
    step = make_step(graph, cfg, solver, extra_metrics, topology=topology)
    keys = jax.random.split(jax.random.PRNGKey(seed), iters)

    def body(carry, key):
        new_state, m = step(carry, None, key)
        return new_state, m

    return jax.lax.scan(body, state, keys)
