"""Paged KV-cache over the registry cache pytrees.

The physical layout lives in ``models/layers.py`` (``init_paged_kv_cache``:
a shared (num_pages, page_size, KV, hd) pool + per-sequence block tables;
the attention path writes/gathers through the table). This module owns
everything around it:

* ``PagePool`` — the host-side allocator. Lowest-id-first allocation and
  FIFO-deterministic free bookkeeping, so a replayed run makes identical
  placement decisions; ``defrag()`` compacts live pages to the low indices
  and returns the remap the device applies with :func:`apply_page_remap`.
  Pages are refcounted: ``alloc`` hands out pages at refcount 1,
  ``retain`` lets a second sequence map the same physical page
  (copy-on-write prefix sharing), and ``free`` only recycles a page once
  its count reaches zero — ``free`` returns the recycled subset so the
  caller knows which pages to invalidate on device.
* ``PrefixIndex`` — full-page content hashes (chained on the parent
  page's hash, so a page's identity encodes its whole prefix) mapping to
  the physical page that first materialized that content. The scheduler
  consults it at admission to map shared-prefix pages instead of
  refilling them.
* ``init_paged_cache`` — a paged decode cache with the exact pytree
  structure of ``registry.init_cache`` (stacked-unit axes and all), so the
  model stack scans it unchanged. Attention-family blocks get page pools;
  recurrent blocks (Mamba2 state + conv tail, xLSTM cells) page trivially
  as ONE block per sequence — their state is fixed-size, so it stays
  slot-indexed ``(max_seqs, ...)`` and admission just zeroes the slot.
* device-side updaters (``admit_slot`` / ``release_slot`` /
  ``apply_page_remap``) — jitted whole-tree transforms driven by the
  scheduler between model steps. ``kv_pos`` of a page is invalidated on
  every (re)allocation AND on free, so a recycled page can never leak a
  previous sequence's entries into the attention mask.

Encoder-decoder (cross-attention) caches are not paged — whisper-small
serves through the contiguous path (DESIGN.md §Serving).
"""
from __future__ import annotations

import functools
import hashlib
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import blocks, layers, registry

_POOL_LEAVES = ("k_pages", "v_pages", "k_scale", "v_scale", "kv_pos")
_ATTN_KINDS = ("attn", "swa", "moe", "shared_attn")


def pages_needed(total_len: int, page_size: int) -> int:
    return -(-int(total_len) // int(page_size))


# ------------------------------------------------------------- allocator --
class PageAllocError(RuntimeError):
    """Raised when an allocation exceeds the free-page budget."""


class PagePool:
    """Host-side page allocator with deterministic placement.

    Free pages live in a min-heap: every allocation takes the lowest ids
    available, so two runs over the same request stream produce identical
    block tables (the replayability contract the scheduler tests pin).

    Pages carry refcounts for copy-on-write prefix sharing: ``alloc``
    returns pages at count 1, ``retain`` bumps a live page when a second
    block table maps it, and ``free`` decrements — a page only returns to
    the free heap (and is reported back to the caller for device-side
    kv_pos invalidation) when its count hits zero.
    """

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(self.num_pages))
        heapq.heapify(self._free)
        self._refs: Dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Physical pages with refcount >= 1 (not the sum of refcounts —
        a page shared by a thousand sequences still occupies one page)."""
        return len(self._refs)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PageAllocError(
                f"requested {n} pages, {len(self._free)} free")
        ids = [heapq.heappop(self._free) for _ in range(n)]
        for i in ids:
            self._refs[i] = 1
        return ids

    def retain(self, ids: Sequence[int]) -> None:
        """Add a reference to live pages (a new block table maps them)."""
        for i in ids:
            i = int(i)
            if i not in self._refs:
                raise PageAllocError(f"retain of free page {i}")
            self._refs[i] += 1

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def ref_stats(self) -> Tuple[int, int]:
        """(sum of refcounts, pages with refcount > 1) — the obs-layer
        PagePool gauges; O(live pages), host-only."""
        total = 0
        shared = 0
        for v in self._refs.values():
            total += v
            shared += v > 1
        return total, shared

    def free(self, ids: Sequence[int]) -> List[int]:
        """Drop one reference per page; returns the subset whose count hit
        zero and was recycled — only those may be kv_pos-invalidated on
        device (other owners still attend through the rest)."""
        recycled: List[int] = []
        for i in ids:
            i = int(i)
            rc = self._refs.get(i)
            if rc is None:
                raise PageAllocError(f"double free of page {i}")
            if rc == 1:
                del self._refs[i]
                heapq.heappush(self._free, i)
                recycled.append(i)
            else:
                self._refs[i] = rc - 1
        return recycled

    def defrag(self) -> np.ndarray:
        """Compact live pages to the lowest physical ids.

        Returns ``old_to_new`` (num_pages,) int32 — a permutation mapping
        every physical page id to its post-compaction id (live pages keep
        their relative order; free pages fill the tail). The caller must
        apply it to the device cache (:func:`apply_page_remap`) and to any
        host-side page lists it holds (including a :class:`PrefixIndex`
        via its ``remap``). Refcounts ride along with their page — a
        multiply-referenced page stays multiply referenced at its new id.
        The pool's own free list is rebuilt to the tail ids."""
        live = sorted(self._refs)
        old_to_new = np.full((self.num_pages,), -1, np.int32)
        for new, old in enumerate(live):
            old_to_new[old] = new
        nxt = len(live)
        for old in range(self.num_pages):
            if old_to_new[old] < 0:
                old_to_new[old] = nxt
                nxt += 1
        self._refs = {int(old_to_new[p]): rc
                      for p, rc in self._refs.items()}
        self._free = list(range(len(live), self.num_pages))
        heapq.heapify(self._free)
        return old_to_new


# ---------------------------------------------------------- prefix index --
class PrefixIndex:
    """Content index over FULL pages for copy-on-write prefix sharing.

    A page's identity is the chained hash ``h_i = sha256(h_{i-1} ||
    tokens[i*ps:(i+1)*ps])`` with a fixed root — identical token windows
    at different depths hash differently, so a hit means the ENTIRE
    prefix up to and including that page matches. A hash maps to the SET
    of physical pages holding that content (a same-tick cohort of
    identical prompts materializes duplicates before any of them is
    indexed), so the hash survives as long as ANY copy is live; lookup
    returns the lowest live page id (deterministic placement). The
    inverse map lets a recycled or defrag-remapped page be
    dropped/followed. Only full, completely written pages are ever
    registered: partial tails mutate, and the chain hash of a page is
    only defined once all its tokens are known.
    """

    ROOT = b"paged-kv-prefix-root"

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self._by_hash: Dict[bytes, set] = {}
        self._by_page: Dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_hash)

    @staticmethod
    def chain(parent: bytes, tokens) -> bytes:
        return hashlib.sha256(
            parent + np.asarray(tokens, np.int32).tobytes()).digest()

    def hash_chain(self, tokens) -> List[bytes]:
        """Chained hash for every full page of ``tokens`` (len // ps)."""
        toks = np.asarray(tokens, np.int32)
        ps, h, out = self.page_size, self.ROOT, []
        for i in range(len(toks) // ps):
            h = self.chain(h, toks[i * ps:(i + 1) * ps])
            out.append(h)
        return out

    def lookup(self, h: bytes) -> Optional[int]:
        pages = self._by_hash.get(h)
        return min(pages) if pages else None

    def register(self, h: bytes, page: int) -> None:
        """A physical page indexes at most one hash; one hash may be held
        by several duplicate pages."""
        page = int(page)
        if page in self._by_page:
            return
        self._by_hash.setdefault(h, set()).add(page)
        self._by_page[page] = h

    def drop_page(self, page: int) -> None:
        h = self._by_page.pop(int(page), None)
        if h is not None:
            pages = self._by_hash[h]
            pages.discard(int(page))
            if not pages:
                del self._by_hash[h]

    def remap(self, old_to_new) -> None:
        """Follow a :meth:`PagePool.defrag` permutation."""
        o2n = np.asarray(old_to_new)
        self._by_page = {int(o2n[p]): h for p, h in self._by_page.items()}
        self._by_hash = {}
        for p, h in self._by_page.items():
            self._by_hash.setdefault(h, set()).add(p)


# ------------------------------------------------------- cache structure --
def make_paged_block_cache(kind: str, cfg, max_seqs: int, num_pages: int,
                           page_size: int, pages_per_seq: int,
                           dtype=jnp.bfloat16, kv_bits: int = 32):
    """Paged decode-time state for one block. Attention-family blocks get
    the shared page pool (the SWA window is enforced by the attention mask,
    not the pool — pages hold the full context); recurrent blocks keep
    their slot-indexed fixed-size state (one trivial "page" per sequence).
    ``kv_bits`` in (8, 4) stores attention pools as low-bit codes + scale
    side info (recurrent state is never quantized — it is O(1) per
    sequence, not the HBM-bound payload)."""
    if kind in _ATTN_KINDS:
        return layers.init_paged_kv_cache(
            max_seqs, num_pages, page_size, pages_per_seq,
            cfg.num_kv_heads, cfg.resolved_head_dim, dtype,
            kv_bits=kv_bits)
    if kind == "xattn":
        raise NotImplementedError(
            "encoder-decoder caches are not paged; serve whisper-small "
            "through the contiguous path (DESIGN.md §Serving)")
    return blocks.make_cache(kind, cfg, max_seqs, page_size, None, dtype)


def init_paged_cache(cfg, max_seqs: int, num_pages: int, page_size: int,
                     pages_per_seq: int, dtype=jnp.bfloat16,
                     kv_bits: int = 32) -> Dict:
    """Paged analog of ``registry.init_cache``: same pytree structure
    (stacked units / rem), so ``registry.decode_step`` runs on it
    unchanged. Every attention layer shares the one logical block table
    (stacked along the unit axis with the rest of the cache — a few KB of
    int32 duplication that keeps the scan machinery untouched)."""
    if cfg.is_encoder_decoder:
        raise NotImplementedError(
            "encoder-decoder caches are not paged (DESIGN.md §Serving)")
    unit, n_full, rem = registry.segments(cfg)
    caches: Dict = {"units": {}, "rem": {}}
    for i, kind in enumerate(unit):
        if n_full == 0:
            break
        one = make_paged_block_cache(kind, cfg, max_seqs, num_pages,
                                     page_size, pages_per_seq, dtype,
                                     kv_bits=kv_bits)
        caches["units"][f"p{i}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape), one)
    for i, kind in enumerate(rem):
        caches["rem"][f"p{i}"] = make_paged_block_cache(
            kind, cfg, max_seqs, num_pages, page_size, pages_per_seq, dtype,
            kv_bits=kv_bits)
    return caches


# -------------------------------------------------------- leaf taxonomy --
def _leaf_info(path):
    """(name, stacked) for one cache leaf — stacked leaves carry the
    leading scanned-unit axis."""
    s = jax.tree_util.keystr(path)
    name = s.rsplit("'", 3)[-2] if "'" in s else s
    return name, "'units'" in s


def _map_cache(cache, pool_fn, table_fn, seq_fn):
    """tree_map with the serving taxonomy: page-pool leaves, block tables,
    per-sequence (recurrent) leaves. Each fn gets (leaf, stacked)."""
    def leaf(path, x):
        name, stacked = _leaf_info(path)
        if name in _POOL_LEAVES:
            return pool_fn(x, stacked, name)
        if name == "block_tables":
            return table_fn(x, stacked)
        return seq_fn(x, stacked)
    return jax.tree_util.tree_map_with_path(leaf, cache)


# ------------------------------------------------------ device updaters --
def _invalidate_kv_pos(x, stacked, name, row):
    """Mark every page in ``row`` as unwritten (kv_pos = -1); -1 entries
    in the row map to the out-of-bounds page and are dropped. Shared by
    admission and release — the ONE place the invalidation rule lives."""
    if name != "kv_pos":
        return x
    num_pages = x.shape[1] if stacked else x.shape[0]
    pages = jnp.where(row >= 0, row, num_pages)            # OOB -> dropped
    if stacked:
        return x.at[:, pages].set(-1, mode="drop")
    return x.at[pages].set(-1, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def admit_slot(cache, slot, row, fresh_row=None):
    """Bind sequence slot ``slot`` to the physical pages in ``row``
    ((pages_per_seq,) int32, -1 = unmapped tail): writes the block-table
    row, invalidates kv_pos on every newly bound FRESH page (stale
    entries from a previous owner must never be attendable), and zeroes
    the slot's recurrent state. ``fresh_row`` defaults to ``row``; a
    prefix-sharing admission passes only the freshly allocated subset —
    shared pages keep their kv_pos (that content is exactly what the new
    sequence attends through)."""
    inval = row if fresh_row is None else fresh_row

    def table(x, stacked):
        if stacked:
            return x.at[:, slot].set(row)
        return x.at[slot].set(row)

    def seq(x, stacked):
        if stacked:
            return x.at[:, slot].set(jnp.zeros(x.shape[2:], x.dtype))
        return x.at[slot].set(jnp.zeros(x.shape[1:], x.dtype))

    return _map_cache(
        cache, lambda x, stacked, name: _invalidate_kv_pos(x, stacked,
                                                           name, inval),
        table, seq)


@functools.partial(jax.jit, donate_argnums=(0,))
def release_slot(cache, slot, row):
    """Unbind slot ``slot``: clear its block-table row and invalidate the
    released pages' kv_pos so the recycled pages are inert until the next
    ``admit_slot`` rebinds them. With refcounted sharing the caller must
    pass only the RECYCLED pages (refcount hit zero) in ``row`` — pages
    still referenced by another sequence keep their content attendable;
    without sharing the slot's own row is exactly that set."""
    def table(x, stacked):
        empty = jnp.full(row.shape, -1, jnp.int32)
        if stacked:
            return x.at[:, slot].set(empty)
        return x.at[slot].set(empty)

    return _map_cache(
        cache, lambda x, stacked, name: _invalidate_kv_pos(x, stacked,
                                                           name, row),
        table, lambda x, stacked: x)


@functools.partial(jax.jit, donate_argnums=(0,))
def map_pages(cache, slot, logicals, pages):
    """Bind physical ``pages`` at logical indices ``logicals`` of slot
    ``slot``'s block-table row (demand paging under watermark admission:
    pages are mapped when the sequence actually reaches them, not
    reserved up front). Freshly allocated pages get their kv_pos
    invalidated."""
    def table(x, stacked):
        if stacked:
            return x.at[:, slot, logicals].set(pages)
        return x.at[slot, logicals].set(pages)

    return _map_cache(
        cache, lambda x, stacked, name: _invalidate_kv_pos(x, stacked,
                                                           name, pages),
        table, lambda x, stacked: x)


@functools.partial(jax.jit, donate_argnums=(0,))
def unmap_pages(cache, slot, logicals, recycled_row):
    """Drop logical pages from slot ``slot``'s block-table row (SWA
    window recycling: pages fully behind the attention window are dead
    weight). Only ``recycled_row`` — the pages whose refcount hit zero —
    is kv_pos-invalidated."""
    def table(x, stacked):
        neg = jnp.full(logicals.shape, -1, jnp.int32)
        if stacked:
            return x.at[:, slot, logicals].set(neg)
        return x.at[slot, logicals].set(neg)

    return _map_cache(
        cache, lambda x, stacked, name: _invalidate_kv_pos(x, stacked,
                                                           name,
                                                           recycled_row),
        table, lambda x, stacked: x)


@functools.partial(jax.jit, donate_argnums=(0,))
def fork_pages(cache, slot, logicals, srcs, dsts, write_pos):
    """Copy-on-write fork: duplicate physical pages ``srcs`` into
    ``dsts`` across EVERY pool leaf (f32 K/V slabs, or quantized codes
    AND their scale side info — a forked page must be bit-identical to
    its donor), rebind slot ``slot``'s block-table row at ``logicals`` to
    the copies, and invalidate kv_pos entries at positions >=
    ``write_pos`` in the copies: the donor may have written its own
    divergent tokens past the shared point, and those must never be
    attendable by the forker."""
    def pool(x, stacked, name):
        axis = 1 if stacked else 0
        slab = jnp.take(x, srcs, axis=axis)
        if name == "kv_pos":
            slab = jnp.where(slab < write_pos, slab, -1)
        if stacked:
            return x.at[:, dsts].set(slab)
        return x.at[dsts].set(slab)

    def table(x, stacked):
        if stacked:
            return x.at[:, slot, logicals].set(dsts)
        return x.at[slot, logicals].set(dsts)

    return _map_cache(cache, pool, table, lambda x, stacked: x)


# -------------------------------------------------- preemption swap I/O --
def _npz_safe(arr: np.ndarray) -> np.ndarray:
    """bfloat16 (an ml_dtypes extension type) does not survive an NPZ
    round-trip — store its raw bits as uint16."""
    if arr.dtype.name == "bfloat16":
        return arr.view(np.uint16)
    return arr


def _npz_restore(slab: np.ndarray, target_dtype) -> np.ndarray:
    if jnp.dtype(target_dtype).name == "bfloat16" \
            and slab.dtype == np.uint16:
        return slab.view(jnp.bfloat16.dtype)
    return slab


def extract_pages(cache, pages) -> Dict[str, np.ndarray]:
    """Pull the pool slabs (K/V payload + scales + kv_pos) for physical
    ``pages`` to host numpy, keyed by the leaf's tree path — the state a
    swap-mode preemption saves so readmission can skip recompute."""
    idx = jnp.asarray(pages, jnp.int32)
    out: Dict[str, np.ndarray] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name, stacked = _leaf_info(path)
        if name in _POOL_LEAVES:
            axis = 1 if stacked else 0
            out[jax.tree_util.keystr(path)] = _npz_safe(
                np.asarray(jnp.take(leaf, idx, axis=axis)))
    return out


def insert_pages(cache, slabs: Dict[str, np.ndarray], pages):
    """Inverse of :func:`extract_pages` into freshly allocated ``pages``
    (the physical ids need not match the ones extracted — block tables
    are rebuilt by the caller)."""
    idx = jnp.asarray(pages, jnp.int32)

    def leaf(path, x):
        key = jax.tree_util.keystr(path)
        if key not in slabs:
            return x
        _, stacked = _leaf_info(path)
        slab = jnp.asarray(_npz_restore(slabs[key], x.dtype), x.dtype)
        if stacked:
            return x.at[:, idx].set(slab)
        return x.at[idx].set(slab)
    return jax.tree_util.tree_map_with_path(leaf, cache)


def extract_seq_state(cache, slot: int) -> Dict[str, np.ndarray]:
    """Per-sequence (recurrent) leaves sliced at ``slot`` — the other
    half of a swap-mode preemption for hybrid/recurrent architectures."""
    out: Dict[str, np.ndarray] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name, stacked = _leaf_info(path)
        if name in _POOL_LEAVES or name == "block_tables":
            continue
        axis = 1 if stacked else 0
        out[jax.tree_util.keystr(path)] = _npz_safe(np.asarray(
            jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=axis)))
    return out


def insert_seq_state(cache, state: Dict[str, np.ndarray], slot: int):
    """Inverse of :func:`extract_seq_state` (possibly into a different
    slot)."""
    def leaf(path, x):
        key = jax.tree_util.keystr(path)
        if key not in state:
            return x
        _, stacked = _leaf_info(path)
        axis = 1 if stacked else 0
        slab = jnp.asarray(_npz_restore(state[key], x.dtype), x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(x, slab, slot,
                                                   axis=axis)
    return jax.tree_util.tree_map_with_path(leaf, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_page_remap(cache, old_to_new, new_to_old):
    """Apply a ``PagePool.defrag()`` permutation on device: permute the
    pools so page ``o`` moves to ``old_to_new[o]``, and rewrite every
    mapped block-table entry. Content-preserving by construction — decode
    after a defrag is bit-identical to decode without one (pinned by the
    scheduler tests)."""
    def pool(x, stacked, name):
        axis = 1 if stacked else 0
        return jnp.take(x, new_to_old, axis=axis)

    def table(x, stacked):
        return jnp.where(x >= 0, jnp.take(old_to_new,
                                          jnp.clip(x, 0, None)), -1)

    return _map_cache(cache, pool, table, lambda x, stacked: x)


def slice_slot(cache, slot):
    """View the paged cache as a batch-1 cache for sequence ``slot``: the
    shared page pools pass through whole (chunked prefill writes land in
    them through the slot's block-table row), while per-sequence leaves
    (recurrent state, block tables) are sliced to that slot. Lets the
    scheduler prefill one sequence with (1, chunk)-shaped jit steps
    regardless of ``max_seqs``."""
    def seq_slice(x, stacked):
        axis = 1 if stacked else 0
        return jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=axis)

    return _map_cache(cache, lambda x, stacked, name: x,
                      seq_slice, seq_slice)


def merge_slot(cache, updated_slice, slot):
    """Inverse of :func:`slice_slot` after a model step: pool leaves take
    the updated values (they were written globally through the block
    table); per-sequence leaves scatter the batch-1 slice back."""
    def leaf(path, old, new):
        name, stacked = _leaf_info(path)
        if name in _POOL_LEAVES:
            return new
        axis = 1 if stacked else 0
        return jax.lax.dynamic_update_slice_in_dim(old, new, slot, axis=axis)
    return jax.tree_util.tree_map_with_path(leaf, cache, updated_slice)


def build_block_table_row(pages: Sequence[int], pages_per_seq: int
                          ) -> np.ndarray:
    row = np.full((pages_per_seq,), -1, np.int32)
    row[: len(pages)] = np.asarray(pages, np.int32)
    return row


# ------------------------------------------------------------- metrics --
def cache_page_bytes(cache) -> int:
    """Bytes held by the page pools (the quantity paging exists to bound):
    K/V payload plus, for quantized pools, the scale side info — everything
    a decode step's attention must read per cached token. ``kv_pos`` and
    block tables are bookkeeping, identical across storage modes, and not
    counted."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name, _ = _leaf_info(path)
        if name in ("k_pages", "v_pages", "k_scale", "v_scale"):
            total += leaf.size * leaf.dtype.itemsize
    return total


def cache_bytes_per_token(cache) -> float:
    """Pool bytes per cached-token slot, summed over every layer (K + V +
    side info). This is the modeled HBM-read cost of attending one cached
    token in one decode step — at context C a step reads ~C times this per
    sequence — the quantity the ``long_context`` bench section gates."""
    slots = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name, _ = _leaf_info(path)
        if name == "kv_pos" and leaf.ndim >= 2:
            slots = leaf.shape[-2] * leaf.shape[-1]     # num_pages * ps
            break
    if not slots:
        raise ValueError("not a paged cache (no pool-shaped kv_pos leaf)")
    return cache_page_bytes(cache) / slots
