"""Packed buffer view of worker-stacked pytrees (DESIGN.md §Packing).

This module flattens all leaves of a worker-stacked tree into ONE
contiguous ``(N, D)`` buffer, the slab the Pallas kernels of the exchange
take (``use_pallas_quant`` / ``use_pallas_mix``): grouped quantization then
runs as a single fused kernel call. The engine's default exchange works on
each leaf in its own shape instead and builds no such buffer (DESIGN.md
§Packing):

* :class:`Packing` holds the *static* layout metadata — leaf shapes/dtypes,
  flat dims, column offsets, and the per-column group-id map ``col_group_ids``
  that tells the fused kernel which quantization group each column belongs
  to. Instances are cached by ``(treedef, shapes, dtypes, group_ids)`` via
  :func:`make_packing`, so repeated traces reuse the same metadata (and the
  same host-side id array).
* :func:`pack` / :func:`unpack` move between the tree view and the buffer
  view. Leaves are concatenated in ``tree_leaves`` order, each reshaped to
  ``(N, d_leaf)``; a one-leaf tree packs to a plain reshape (no concat), so
  the flat ``(N, d)`` seed workload is the identity transform.
* :func:`segment_maxabs` is the two-pass path's grouped side
  information: per-worker per-group ``max |.|`` (quantizer range R_g),
  ``(N, G)``.

Everything here is jit-traceable; the cache only avoids re-deriving static
layout (and keeps ``col_group_ids`` as one host array per layout).

This module also owns the **structured group-spec language** (DESIGN.md
§Groups): named block buckets (``"block:attn,mlp,embed"``), shape-balanced
auto partitions (``"auto:K"``), explicit index buckets
(``((0, 1), (2, 3))``) and the greedy range-similarity clustering that the
engine's :class:`~repro.core.engine.AutoGrouper` re-runs from live range
statistics. Every spec form compiles down to the same per-leaf group-id
tuple, so the quantizer, the group-censor norms and the payload
accounting are spec-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any

# Layout cache: (treedef, shapes, dtypes, group_ids) -> Packing. Layouts are
# tiny and the set of distinct model/group structures per process is small,
# so an unbounded dict is fine (mirrors jax's own tracing caches).
_CACHE: Dict[Tuple, "Packing"] = {}


@dataclasses.dataclass(frozen=True)
class Packing:
    """Static layout of a worker-stacked pytree as one ``(N, D)`` buffer."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]    # per-leaf shapes (worker axis incl)
    dtypes: Tuple[Any, ...]                # per-leaf dtypes
    dims: Tuple[int, ...]                  # per-leaf flat dim d_i
    offsets: Tuple[int, ...]               # per-leaf column offset
    group_ids: Tuple[int, ...]             # leaf index -> group id
    n_groups: int
    group_dims: Tuple[int, ...]            # per-group parameter counts d_g
    # per-group static contiguous column runs ((offset, size), ...): adjacent
    # same-group leaves are merged, so a group occupies as few maximal slices
    # as the layout allows (exactly one when ``sorted_ids``). This is the
    # static metadata the fused in-kernel range reduction slices by.
    group_runs: Tuple[Tuple[Tuple[int, int], ...], ...]
    # (D,) int32 column -> group id map; one host array per cached layout
    col_group_ids: np.ndarray = dataclasses.field(compare=False, repr=False)

    @property
    def dim(self) -> int:
        """Total packed width D (= model dimension per worker)."""
        return sum(self.dims)

    @property
    def n_leaves(self) -> int:
        return len(self.dims)

    @property
    def sorted_ids(self) -> bool:
        """Whether column group ids are non-decreasing (then every group's
        columns form one contiguous slice)."""
        ids = self.group_ids
        return all(ids[i] <= ids[i + 1] for i in range(len(ids) - 1))


def make_packing(tree: Tree, group_ids: Sequence[int]) -> Packing:
    """Build (or fetch the cached) packing for ``tree`` with per-leaf
    quantization ``group_ids`` (aligned with ``tree_leaves`` order)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot pack an empty pytree")
    shapes = tuple(tuple(int(s) for s in x.shape) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    ids = tuple(int(g) for g in group_ids)
    if len(ids) != len(leaves):
        raise ValueError(f"group spec covers {len(ids)} leaves, "
                         f"tree has {len(leaves)}")
    key = (treedef, shapes, dtypes, ids)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit

    dims = tuple(int(np.prod(s[1:], dtype=np.int64)) for s in shapes)
    offsets, off = [], 0
    for d in dims:
        offsets.append(off)
        off += d
    n_groups = max(ids) + 1
    gdims = [0] * n_groups
    for d, g in zip(dims, ids):
        gdims[g] += d
    cols = np.concatenate([np.full(d, g, np.int32)
                           for d, g in zip(dims, ids)])
    runs: list = [[] for _ in range(n_groups)]
    for off_i, d, g in zip(offsets, dims, ids):
        if d == 0:
            continue
        if runs[g] and runs[g][-1][0] + runs[g][-1][1] == off_i:
            runs[g][-1] = (runs[g][-1][0], runs[g][-1][1] + d)
        else:
            runs[g].append((off_i, d))
    pk = Packing(treedef=treedef, shapes=shapes, dtypes=dtypes, dims=dims,
                 offsets=tuple(offsets), group_ids=ids, n_groups=n_groups,
                 group_dims=tuple(gdims),
                 group_runs=tuple(tuple(r) for r in runs),
                 col_group_ids=cols)
    _CACHE[key] = pk
    return pk


def pack(pk: Packing, tree: Tree, dtype=jnp.float32) -> jax.Array:
    """Tree view -> ``(N, D)`` buffer (leaves concatenated in leaf order)."""
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    if len(leaves) == 1:
        return leaves[0].reshape(n, -1).astype(dtype)
    return jnp.concatenate(
        [x.reshape(n, -1).astype(dtype) for x in leaves], axis=1)


def unpack(pk: Packing, buf: jax.Array, like: Tree = None) -> Tree:
    """``(N, D)`` buffer -> tree view. Shapes come from the packing; dtypes
    come from ``like`` when given (e.g. narrowed ``hat_dtype`` replicas),
    else from the packed tree's original dtypes."""
    n = buf.shape[0]
    dtypes = (tuple(x.dtype for x in jax.tree_util.tree_leaves(like))
              if like is not None else pk.dtypes)
    out = []
    for shape, dt, d, off in zip(pk.shapes, dtypes, pk.dims, pk.offsets):
        out.append(buf[:, off:off + d].reshape((n,) + shape[1:]).astype(dt))
    return jax.tree_util.tree_unflatten(pk.treedef, out)


def segment_maxabs(pk: Packing, buf: jax.Array) -> jax.Array:
    """Per-worker per-group ``max |buf|`` — the grouped quantizer range
    R_g: ``(N, G)``. Transpose-free: each leaf occupies a static
    contiguous column slice, so every leaf reduces along axis 1 and
    leaves sharing a group combine with one more max (order-independent,
    so value-identical to a transposed segment max)."""
    absbuf = jnp.abs(buf)
    if pk.n_groups == 1:
        return jnp.max(absbuf, axis=1)[:, None]
    per_group = [[] for _ in range(pk.n_groups)]
    for off, d, g in zip(pk.offsets, pk.dims, pk.group_ids):
        per_group[g].append(jnp.max(absbuf[:, off:off + d], axis=1))
    cols = [parts[0] if len(parts) == 1
            else jnp.max(jnp.stack(parts, axis=0), axis=0)
            for parts in per_group]
    return jnp.stack(cols, axis=1)


# ------------------------------------------------------------ group specs --
class GroupSpecError(ValueError):
    """Malformed group spec: bad syntax, unknown/empty bucket, or an index
    bucketing that is not a partition of the leaves. Subclasses ValueError
    so pre-existing callers catching ValueError keep working."""


# Canonical bucket vocabulary: bucket name -> path substrings that place a
# leaf in it. Matching is first-listed-bucket-wins over a lowercased
# ``jax.tree_util.keystr`` path; a spec name outside this table matches
# leaves whose path contains the name itself (so ad-hoc trees can be
# bucketed by their own key names). "rest" is the explicit catch-all.
BUCKET_ALIASES: Dict[str, Tuple[str, ...]] = {
    "embed": ("embed", "unembed", "vocab", "wte", "wpe", "lm_head"),
    "attn": ("attn", "attention", "qkv"),
    "mlp": ("mlp", "ffn", "moe", "expert", "glu", "feed_forward"),
    "ssm": ("ssm", "mamba", "conv", "slstm", "mlstm"),
    "norm": ("norm", "ln1", "ln2", "rmsnorm", "layernorm"),
    "rest": (),
}
_BUCKET_ORDER = ("embed", "attn", "mlp", "ssm", "norm")


def leaf_paths(tree: Tree) -> Tuple[str, ...]:
    """Lowercased ``keystr`` path per leaf, aligned with ``tree_leaves``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return tuple(jax.tree_util.keystr(path).lower() for path, _ in flat)


def bucket_of(path: str) -> str:
    """Canonical bucket of one leaf path (``"rest"`` when nothing hits)."""
    p = path.lower()
    for name in _BUCKET_ORDER:
        if any(tok in p for tok in BUCKET_ALIASES[name]):
            return name
    return "rest"


def tree_bucket_names(tree: Tree) -> Tuple[str, ...]:
    """Sorted canonical bucket names present in ``tree`` (the vocabulary a
    ``block:`` spec can name for this model; exported per-architecture by
    ``models.registry.param_bucket_names``)."""
    return tuple(sorted({bucket_of(p) for p in leaf_paths(tree)}))


def parse_block_spec(spec: str) -> Tuple[str, ...]:
    """``"block:a,b,c"`` -> ``("a", "b", "c")`` with syntax validation."""
    body = spec[len("block:"):] if spec.startswith("block:") else spec
    names = tuple(n.strip().lower() for n in body.split(","))
    if not body.strip() or any(not n for n in names):
        raise GroupSpecError(
            f"malformed block spec {spec!r}: expected "
            f"'block:<name>[,<name>...]' with non-empty names")
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise GroupSpecError(
            f"block spec {spec!r} repeats bucket(s) {sorted(dupes)}")
    return names


def parse_auto_spec(spec: str) -> int:
    """``"auto:K"`` -> K (positive int) with syntax validation."""
    body = spec[len("auto:"):] if spec.startswith("auto:") else spec
    try:
        k = int(body)
    except ValueError:
        raise GroupSpecError(
            f"malformed auto spec {spec!r}: expected 'auto:<K>' with "
            f"integer K >= 1") from None
    if k < 1:
        raise GroupSpecError(f"auto spec {spec!r}: K must be >= 1")
    return k


def validate_spec_syntax(spec: str) -> None:
    """Tree-independent syntax check of a string group spec; raises
    :class:`GroupSpecError` on anything unrecognized (so a typo'd
    ``EngineConfig.groups`` / ``--groups`` fails loudly at config
    construction instead of silently misresolving later)."""
    if spec in ("model", "leaf"):
        return
    if spec.startswith("block:"):
        parse_block_spec(spec)
        return
    if spec.startswith("auto:"):
        parse_auto_spec(spec)
        return
    raise GroupSpecError(
        f"unknown group spec {spec!r}: expected 'model', 'leaf', "
        f"'block:<b1,b2,...>', 'auto:<K>', a leaf->group id tuple, or a "
        f"tuple of leaf-index buckets")


def _name_patterns(name: str) -> Tuple[str, ...]:
    return (name,) + BUCKET_ALIASES.get(name, ())


def resolve_block_groups(tree: Tree, names: Sequence[str]) -> Tuple[int, ...]:
    """Named-bucket resolution: bucket j of the spec takes every leaf whose
    path matches one of its patterns (first-listed bucket wins on overlap);
    leaves matching no bucket fall into ``"rest"`` — either the explicitly
    listed position or an appended trailing group.

    Raises :class:`GroupSpecError` for a name that matches nothing anywhere
    (unknown bucket) or matches nothing *in this tree* / lost every leaf to
    an earlier bucket (empty bucket)."""
    names = tuple(n.lower() for n in names)
    paths = leaf_paths(tree)
    rest_slot = names.index("rest") if "rest" in names else None
    ids = []
    for p in paths:
        gid = None
        for j, name in enumerate(names):
            if name == "rest":
                continue
            if any(tok in p for tok in _name_patterns(name)):
                gid = j
                break
        if gid is None:
            gid = rest_slot if rest_slot is not None else len(names)
        ids.append(gid)
    used = set(ids)
    for j, name in enumerate(names):
        if j in used or name == "rest":   # an unused catch-all is legal
            continue
        if name not in BUCKET_ALIASES \
                and not any(any(tok in p for tok in _name_patterns(name))
                            for p in paths):
            raise GroupSpecError(
                f"unknown bucket {name!r}: not a canonical bucket "
                f"({sorted(BUCKET_ALIASES)}) and matches no leaf path; "
                f"this tree's buckets: {tree_bucket_names(tree)}")
        raise GroupSpecError(
            f"empty bucket {name!r}: no leaf of this tree lands in it "
            f"(buckets present: {tree_bucket_names(tree)}; earlier-listed "
            f"buckets win overlapping leaves)")
    # compact to contiguous ids 0..G-1 preserving spec order (+ trailing
    # rest), so downstream group ids always form a partition
    remap = {g: i for i, g in enumerate(sorted(used))}
    return tuple(remap[g] for g in ids)


def resolve_index_buckets(tree: Tree,
                          buckets: Sequence[Sequence[int]]) -> Tuple[int, ...]:
    """Explicit tuple-of-tuples spec: ``((0, 1), (2,))`` puts leaves 0, 1 in
    group 0 and leaf 2 in group 1. Must be a partition of ``range(L)`` —
    overlaps, out-of-range indices, empty buckets and uncovered leaves all
    raise :class:`GroupSpecError`."""
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    ids: Dict[int, int] = {}
    for j, bucket in enumerate(buckets):
        members = tuple(int(i) for i in bucket)
        if not members:
            raise GroupSpecError(f"index bucket {j} is empty")
        for i in members:
            if not 0 <= i < n_leaves:
                raise GroupSpecError(
                    f"index bucket {j} names leaf {i}, tree has "
                    f"{n_leaves} leaves")
            if i in ids:
                raise GroupSpecError(
                    f"overlapping spec: leaf {i} appears in buckets "
                    f"{ids[i]} and {j}")
            ids[i] = j
    missing = sorted(set(range(n_leaves)) - set(ids))
    if missing:
        raise GroupSpecError(
            f"index buckets do not cover leaves {missing} "
            f"(every leaf must appear in exactly one bucket)")
    return tuple(ids[i] for i in range(n_leaves))


def _leaf_dims(tree: Tree) -> Tuple[int, ...]:
    return tuple(int(x.size // x.shape[0])
                 for x in jax.tree_util.tree_leaves(tree))


def resolve_auto_groups(tree: Tree, k: int) -> Tuple[int, ...]:
    """Shape-only initial ``auto:K`` partition: contiguous leaf segments
    with balanced parameter counts (boundaries at the cumulative-dim
    quantiles). Deterministic and computable from abstract shapes, so it
    works under ``jax.eval_shape`` (the production bundle's init path); the
    range-statistics refinement happens outside jit via
    :func:`greedy_range_grouping` / ``engine.AutoGrouper``."""
    dims = _leaf_dims(tree)
    n_leaves = len(dims)
    k = min(int(k), n_leaves)
    cum = np.cumsum(np.asarray(dims, np.float64))
    bounds, prev = [], 0
    for j in range(1, k):
        i = int(np.searchsorted(cum, j * cum[-1] / k, side="right"))
        i = min(max(i, prev + 1), n_leaves - (k - j))
        bounds.append(i)
        prev = i
    ids, g = [], 0
    for i in range(n_leaves):
        while g < len(bounds) and i >= bounds[g]:
            g += 1
        ids.append(g)
    return tuple(ids)


def greedy_range_grouping(log_ranges: np.ndarray, dims: Sequence[int],
                          k: int) -> Tuple[int, ...]:
    """Cluster leaves into <= K contiguous groups by log-range similarity:
    start from one segment per leaf and greedily merge the adjacent pair
    with the closest dim-weighted mean log-range (ties -> lowest index).

    Contiguity in leaf order is the stability device: group ids are the
    segment index in leaf order, so they are monotone over leaves and can
    never permute between regroup events — only boundaries move. Pure
    host-side numpy (runs outside jit, every ``regroup_every`` rounds)."""
    lr = np.asarray(log_ranges, np.float64)
    w = np.asarray(dims, np.float64)
    n_leaves = lr.shape[0]
    if w.shape[0] != n_leaves:
        raise ValueError(f"{n_leaves} log-ranges vs {w.shape[0]} dims")
    k = max(1, min(int(k), n_leaves))
    # per-segment running sums (sum_w, sum_w*lr) make each merge O(L):
    # one argmin over the adjacent-gap vector (first-minimum tie-break,
    # i.e. lowest index) plus an O(1) neighbor update — O(L^2) total
    # instead of recomputing every mean from member lists (O(L^3))
    counts = [1] * n_leaves                      # leaves per segment
    sum_w = list(w)
    sum_ws = list(w * lr)
    means = np.asarray([s / max(t, 1e-30) for s, t in zip(sum_ws, sum_w)])
    for _ in range(n_leaves - k):
        j = int(np.argmin(np.abs(np.diff(means))))
        counts[j] += counts.pop(j + 1)
        sum_w[j] += sum_w.pop(j + 1)
        sum_ws[j] += sum_ws.pop(j + 1)
        means = np.delete(means, j + 1)
        means[j] = sum_ws[j] / max(sum_w[j], 1e-30)
    ids = []
    for g, c in enumerate(counts):
        ids.extend([g] * c)
    return tuple(ids)
