"""Pluggable topology backends for the consensus engine (DESIGN.md
§Topology).

Every place the engine touches the communication graph — the neighbor
aggregation ``A @ V`` of the primal updates, the Laplacian term
``(D - A) theta_hat`` of the dual update (Eq. 23), and the pairwise primal
residual (Eq. 28) — now goes through ONE object, a :class:`Topology` built
from a :class:`~repro.core.graph.WorkerGraph`. Three interchangeable
backends (selected by ``EngineConfig.mix_backend``):

* **dense** — the seed semantics: ``(N, N) @ (N, D)`` matmul against the
  full adjacency, optionally through the ``bipartite_mix`` MXU Pallas
  kernel (``use_pallas_mix``). O(N²·D) work; bit-golden vs the frozen seed
  stepper, and the default.
* **sparse** — the graph's precomputed edge-list/CSR arrays
  (``WorkerGraph.edge_src/edge_dst``): gather the source rows and
  ``segment_sum`` them into the destination rows — O(E·D) work, no (N, N)
  operand in the program at all (the adjacency never leaves the host).
  ``use_pallas_mix`` routes through the ``edge_gather_mix`` Pallas kernel
  (degree-padded CSR + scalar-prefetch row gather) instead of the jnp
  gather/segment pair.
* **sharded** — SPMD neighbor mixing: ``shard_map`` over the worker mesh
  axis with *explicit* input/output shardings. Each worker shard holds its
  adjacency row block, all-gathers the peer rows once, and emits its own
  output block — one explicit collective instead of an XLA-chosen
  collective-permute chain.

All three agree to fp tolerance (``tests/test_topology.py``); dense is
exactly the old ``engine.tree_mix`` math so the G=1 flat path stays
bit-for-bit golden. Where each wins is measured in
``benchmarks/bench_engine.py`` and discussed in DESIGN.md §Topology — on
CPU the Eigen matmul is compute-bound and beats XLA's scalarized
gather/scatter at any paper density, so sparse's wall-time win is an
accelerator/scale story; its unconditional win at p ≤ 0.5 is state size
(O(E) edge arrays vs the O(N²) adjacency operand).

Trees mix leaf by leaf, each leaf in its own shape: the dense sum is a
contraction over the worker axis, the sparse gather and segment-sum run
along it, and the sharded backend mixes each leaf in its own layout. No
leaf is reshaped to ``(N, d)``: on a TPU a leaf lies in whole (8, 128)
tiles, while an ``(N, D)`` row of a few workers fills only N of a tile's 8
sublanes, so packing costs a relayout copy and every pass over the packed
row runs at a fraction of the vector unit. Only the Pallas kernels
(``use_pallas_mix``) take the packed ``(N, D)`` buffer
(``core/packing.py``), whose layout they are written for.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.graph import WorkerGraph

Tree = Any

BACKENDS = ("dense", "sparse", "sharded")


def _apply_flat(fn, a: Tree) -> Tree:
    """Apply a Pallas kernel's ``(N, d) -> (N, d)`` map to a tree: through
    the packed buffer when all leaves share a dtype (one call for the whole
    tree), one ``(N, d)`` view per leaf otherwise."""
    def one(x):
        return fn(x.reshape(x.shape[0], -1)).reshape(x.shape)

    leaves = jax.tree_util.tree_leaves(a)
    if len(leaves) > 1 and len({x.dtype for x in leaves}) == 1:
        pk = packing.make_packing(a, (0,) * len(leaves))
        buf = packing.pack(pk, a, dtype=leaves[0].dtype)
        return packing.unpack(pk, fn(buf), like=a)
    return jax.tree_util.tree_map(one, a)


def _take_rows(x: jax.Array, rows: Optional[np.ndarray]) -> jax.Array:
    return x if rows is None else x[np.asarray(rows)]


def _neighbor_sum(adjacency: jax.Array, values: jax.Array) -> jax.Array:
    """``A @ V`` over the leading worker axis of ``values``, in its own
    shape, with no operand rounded: a TPU's default f32 matmul rounds its
    operands to bfloat16, which would round every model the workers
    exchange. An ``(N, d)`` leaf, whose rows already lie along the tiles,
    takes the full-precision matmul (exact for a 0/1 adjacency; the seed's
    flat stepper, bit for bit). A leaf of higher rank lies in whole tiles
    of its last two axes, and a dot over its worker axis would move that
    axis into the tiles and back (a relayout copy of the leaf on a TPU):
    it takes ``sum_m A[:, m] * V[m]``, added in the order of ``m``, as
    element-wise work. Each product with a 0/1 adjacency is exact, and so
    is the sum where a worker has one neighbour."""
    a = adjacency.astype(values.dtype)
    if values.ndim == 2:
        return jnp.matmul(a, values, precision=jax.lax.Precision.HIGHEST)
    col = (a.shape[0],) + (1,) * (values.ndim - 1)
    out = a[:, 0].reshape(col) * values[0:1]
    for m in range(1, a.shape[1]):
        out = out + a[:, m].reshape(col) * values[m:m + 1]
    return out


@dataclasses.dataclass(frozen=True)
class Topology:
    """Graph-structure operations behind one interface.

    Subclasses implement ``_mix_leaf`` on one leaf in its own shape and
    ``_mix_flat``, the Pallas kernel on a packed ``(N, d)`` buffer;
    everything else (tree dispatch, the Laplacian dual term, residuals) is
    shared, so every engine consumer of the graph — phase mix, dual
    update, metrics — automatically uses the selected backend (and its
    kernel routing: the seed bug of the dual step silently skipping
    ``use_pallas_mix`` cannot recur, there is no second mix implementation
    to drift)."""

    n: int
    degrees: jax.Array          # (N,) float32
    use_pallas: bool = False

    backend = "abstract"

    @property
    def layout(self) -> str:
        """What :meth:`mix` works on: ``packed`` (the Pallas kernels' one
        ``(N, D)`` buffer) or ``leaf`` (each leaf in its own shape)."""
        return "packed" if self.use_pallas else "leaf"

    def _mix_leaf(self, x: jax.Array,
                  rows: Optional[np.ndarray]) -> jax.Array:
        raise NotImplementedError

    def _mix_flat(self, flat: jax.Array) -> jax.Array:
        raise NotImplementedError

    # ------------------------------------------------------------- mix --
    def mix(self, a: Tree, rows: Optional[np.ndarray] = None) -> Tree:
        """Neighbor sum per worker: out_n = sum_{m in N_n} a_m, for every
        worker or, with ``rows`` (static), for those rows alone."""
        if self.use_pallas:
            return jax.tree_util.tree_map(
                lambda x: _take_rows(x, rows), _apply_flat(self._mix_flat, a))
        return jax.tree_util.tree_map(lambda x: self._mix_leaf(x, rows), a)

    # ----------------------------------------------------- dual update --
    def laplacian(self, a: Tree) -> Tree:
        """Graph Laplacian applied per leaf: ``(D - A) a`` in f32 — the
        dual ascent direction of Eq. (23)."""
        neigh = self.mix(a)

        def one(x, nm):
            shape1 = (x.shape[0],) + (1,) * (x.ndim - 1)
            return (self.degrees.reshape(shape1) * x.astype(jnp.float32)
                    - nm.astype(jnp.float32))

        return jax.tree_util.tree_map(one, a, neigh)

    # -------------------------------------------------------- residuals --
    def primal_residual(self, theta: jax.Array) -> jax.Array:
        """Pairwise primal residual sum_{(n,m) in E} ||theta_n - theta_m||²
        (Eq. 28) over a flat ``(N, d)`` view."""
        raise NotImplementedError

    def rebuild(self, graph: WorkerGraph) -> "Topology":
        """Re-derive this backend's graph metadata for a *new* graph —
        membership changed (fleet join/leave) or the topology was redrawn —
        preserving the backend selection and kernel routing. The dense
        adjacency, the sparse CSR/edge arrays, and the sharded mesh
        bindings are all rebuilt from the new :class:`WorkerGraph`'s cached
        metadata; everything the engine compiled against (the Topology
        interface) is unchanged, so callers just re-jit their step against
        the returned instance."""
        kwargs = {}
        if self.backend == "sharded":
            # the mesh axis must still divide the new worker count;
            # build() re-validates and re-binds the same mesh axes
            kwargs = {"mesh": self.mesh, "worker_axis": self.axis,
                      "leaf_specs": self.leaf_specs}
        return build(graph, self.backend, use_pallas_mix=self.use_pallas,
                     **kwargs)

    def dual_residual(self, lap: Tree) -> jax.Array:
        """Squared norm of a Laplacian image, summed over the tree. With
        ``lap = laplacian(theta_hat)`` (already in hand from the dual
        update — no extra mix) this is the unscaled dual-ascent-direction
        magnitude ``||(D - A) theta_hat||²``, which vanishes exactly at
        consensus (the all-equal vector spans ker(D - A) on a connected
        graph)."""
        parts = jax.tree_util.tree_map(
            lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), lap)
        return sum(jax.tree_util.tree_leaves(parts))


@dataclasses.dataclass(frozen=True)
class DenseTopology(Topology):
    """Seed semantics: one matmul against the full (N, N) adjacency."""

    adjacency: jax.Array = None  # (N, N)

    backend = "dense"

    def _mix_leaf(self, x: jax.Array,
                  rows: Optional[np.ndarray]) -> jax.Array:
        return _neighbor_sum(_take_rows(self.adjacency, rows), x)

    def _mix_flat(self, flat: jax.Array) -> jax.Array:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.bipartite_mix(self.adjacency, flat)

    def primal_residual(self, theta: jax.Array) -> jax.Array:
        diffs = theta[:, None, :] - theta[None, :, :]
        return jnp.sum(self.adjacency
                       * jnp.sum(diffs ** 2, axis=-1)) / 2.0


@dataclasses.dataclass(frozen=True)
class SparseTopology(Topology):
    """Edge-list/CSR backend: gather + segment_sum over directed edges.

    O(E·D) work and O(E) topology state; the (N, N) adjacency is never an
    operand of the traced program. ``use_pallas`` switches the mix to the
    ``edge_gather_mix`` kernel over the degree-padded neighbor table."""

    edge_src: jax.Array = None      # (2E,) int32, dst-sorted
    edge_dst: jax.Array = None      # (2E,) int32, sorted
    und_head: jax.Array = None      # (E,) int32 undirected edge heads
    und_tail: jax.Array = None      # (E,) int32 undirected edge tails
    # degree-padded CSR, only materialized for the kernel path (it is
    # O(N·max_degree), not O(E) — a star graph pays ~N²/4 for it)
    nbr_table: jax.Array = None     # (N, S) int32
    nbr_valid: jax.Array = None     # (N, S) f32 1/0 slot validity

    backend = "sparse"

    def _mix_leaf(self, x: jax.Array,
                  rows: Optional[np.ndarray]) -> jax.Array:
        src = x.at[self.edge_src].get(mode="promise_in_bounds")
        return _take_rows(jax.ops.segment_sum(
            src, self.edge_dst, num_segments=self.n,
            indices_are_sorted=True), rows)

    def _mix_flat(self, flat: jax.Array) -> jax.Array:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.edge_gather_mix(
            flat, self.nbr_table, self.nbr_valid).astype(flat.dtype)

    def primal_residual(self, theta: jax.Array) -> jax.Array:
        t32 = theta.astype(jnp.float32)
        diff = (t32.at[self.und_head].get(mode="promise_in_bounds")
                - t32.at[self.und_tail].get(mode="promise_in_bounds"))
        return jnp.sum(jnp.square(diff))


@dataclasses.dataclass(frozen=True)
class ShardedTopology(DenseTopology):
    """SPMD mixing: shard_map over the worker mesh axis.

    Each shard keeps its (N/w, N) adjacency row block and its block of
    the workers' rows of a leaf, all-gathers the peer rows once (tiled,
    one explicit collective over exactly the worker axis), and writes only
    its own output block — in_specs/out_specs pin every operand's layout
    so XLA never has to invent a collective-permute schedule. Each leaf
    mixes in its own layout: ``leaf_specs`` where the workers' leaves are
    split over devices, else the worker axis with the leaf's last axis
    split over the non-worker axes (tensor parallel) whenever it divides,
    so each device mixes only its tile and no cross-replica resharding is
    introduced. ``use_pallas`` (without ``leaf_specs``) runs each shard's
    local row-block matmul on the packed buffer with the
    ``bipartite_mix`` MXU kernel; the residual reduction is inherited
    from the dense backend."""

    mesh: Any = None
    axis: str = "workers"
    rest: Tuple[str, ...] = ()      # non-worker mesh axes (feature split)
    # PartitionSpecs of a worker-stacked parameter tree whose workers span
    # several devices each: the mix then runs leaf by leaf in each leaf's
    # own layout, so no device gathers more than its own share of a row
    leaf_specs: Any = None

    backend = "sharded"

    @property
    def layout(self) -> str:
        return "leaf" if self.leaf_specs is not None else super().layout

    def _rest_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.rest],
                           dtype=np.int64))

    def mix(self, a: Tree, rows: Optional[np.ndarray] = None) -> Tree:
        if rows is not None:
            raise ValueError("the sharded backend mixes every worker's rows")
        if self.layout == "packed":
            return super().mix(a)
        if self.leaf_specs is not None:
            return jax.tree_util.tree_map(self._mix_spec, a, self.leaf_specs)
        return jax.tree_util.tree_map(
            lambda x: self._mix_spec(x, self._default_spec(x)), a)

    def _default_spec(self, x: jax.Array):
        """A leaf's rows over the worker axis, its last axis over the
        non-worker axes where they divide it."""
        from jax.sharding import PartitionSpec as P
        if self.rest and x.ndim > 1 and x.shape[-1] % self._rest_size() == 0:
            return P(self.axis, *([None] * (x.ndim - 2)), self.rest)
        return P(self.axis)

    def _mix_spec(self, x: jax.Array, spec) -> jax.Array:
        """One leaf in its own layout: each device gathers its share of
        every worker's leaf over the worker axis alone."""
        from jax.sharding import PartitionSpec as P

        def local(a_blk, v_blk):
            v_all = jax.lax.all_gather(v_blk, self.axis, axis=0, tiled=True)
            out = jnp.tensordot(a_blk.astype(v_all.dtype), v_all, axes=1,
                                precision=jax.lax.Precision.HIGHEST)
            return out.astype(v_blk.dtype)

        return jax.shard_map(local, mesh=self.mesh,
                             in_specs=(P(self.axis, None), spec),
                             out_specs=spec, check_vma=False)(
                                 self.adjacency, x)

    def _mix_flat(self, flat: jax.Array) -> jax.Array:
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops as kernel_ops

        feat = self.rest if (self.rest and
                             flat.shape[1] % self._rest_size() == 0) else None

        def local(a_blk, v_blk):
            v_all = jax.lax.all_gather(v_blk, self.axis, axis=0, tiled=True)
            return kernel_ops.bipartite_mix(a_blk, v_all)

        vspec = P(self.axis, feat)
        return jax.shard_map(local, mesh=self.mesh,
                             in_specs=(P(self.axis, None), vspec),
                             out_specs=vspec, check_vma=False)(
                                 self.adjacency, flat)


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``. JAX's own default is
    ``Explicit`` axes, which type the sharded mix's outputs as sharded and
    then refuse to vmap them against unsharded operands (the local
    solves); every mesh in this repo is built here."""
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def worker_mesh(devices):
    """1-D ``workers`` mesh over ``devices`` (one row block per device)."""
    return make_mesh((len(devices),), ("workers",), devices=devices)


def devices_per_worker(mesh, worker_axis: Optional[str]) -> int:
    """How many devices each worker's rows span: the product of ``mesh``'s
    axes other than ``worker_axis`` (1 without a mesh, and where several
    workers share a device)."""
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names
                        if a != worker_axis], dtype=np.int64))


def worker_devices(n_workers: int, devices=None) -> list:
    """The devices ``n_workers`` workers are spread over: the most of
    ``devices`` (default: all local devices) that take equal blocks of
    worker rows — all of them when they divide the worker count, one when
    nothing else divides it."""
    devices = list(jax.devices() if devices is None else devices)
    return devices[:math.gcd(n_workers, len(devices))]


def _default_worker_mesh(n: int):
    """1-D device mesh for standalone sharded runs (tests / quickstart)
    over ``worker_devices(n)`` (1-wide on one device: the shard_map then
    runs single-shard — same math, same program structure)."""
    return worker_mesh(worker_devices(n))


def build(graph: WorkerGraph, backend: str = "dense", *,
          use_pallas_mix: bool = False,
          mesh: Any = None, worker_axis: Optional[str] = None,
          leaf_specs: Any = None) -> Topology:
    """Build the selected topology backend from a worker graph.

    ``mesh``/``worker_axis`` are only consulted by the sharded backend
    (the production bundle passes its mesh; standalone callers get a
    1-D mesh over the local devices), and so are ``leaf_specs``, the
    PartitionSpecs of the mixed parameter tree where each worker spans
    several devices (``ShardedTopology.leaf_specs``)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown mix backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    degrees = jnp.asarray(graph.degrees, jnp.float32)
    if backend == "dense":
        return DenseTopology(n=graph.n, degrees=degrees,
                             use_pallas=use_pallas_mix,
                             adjacency=jnp.asarray(graph.adjacency))
    if backend == "sparse":
        edges = np.asarray(graph.edges, dtype=np.int64)
        if use_pallas_mix:
            table, valid = graph.neighbor_table
            table, valid = jnp.asarray(table), jnp.asarray(valid)
        else:
            table = valid = None
        return SparseTopology(
            n=graph.n, degrees=degrees, use_pallas=use_pallas_mix,
            edge_src=jnp.asarray(graph.edge_src),
            edge_dst=jnp.asarray(graph.edge_dst),
            und_head=jnp.asarray(edges[:, 0].astype(np.int32)),
            und_tail=jnp.asarray(edges[:, 1].astype(np.int32)),
            nbr_table=table, nbr_valid=valid)
    if mesh is None:
        mesh, worker_axis = _default_worker_mesh(graph.n), "workers"
    if worker_axis is None:
        worker_axis = mesh.axis_names[0]
    axis_size = mesh.shape[worker_axis]
    if graph.n % axis_size != 0:
        raise ValueError(
            f"sharded mix needs workers ({graph.n}) divisible by mesh axis "
            f"{worker_axis!r} ({axis_size})")
    rest = tuple(a for a in mesh.axis_names if a != worker_axis)
    return ShardedTopology(n=graph.n, degrees=degrees,
                           use_pallas=use_pallas_mix,
                           adjacency=jnp.asarray(graph.adjacency),
                           mesh=mesh, axis=worker_axis, rest=rest,
                           leaf_specs=leaf_specs)


def mix_dense(adjacency: jax.Array, a: Tree,
              use_kernel: bool = False) -> Tree:
    """Legacy helper behind ``engine.tree_mix``: dense neighbor sum on a
    bare adjacency array (no WorkerGraph required). One implementation —
    this is the dense backend's own mix."""
    topo = DenseTopology(n=adjacency.shape[0], degrees=None,
                         use_pallas=use_kernel, adjacency=adjacency)
    return topo.mix(a)
