"""Shared neural-net layers: norms, projections, rotary embeddings, GQA
attention (full / sliding-window / cross), gated MLP.

Functional style: params are nested dicts of jnp arrays; every layer is a
pair of ``<name>_init(key, ...) -> params`` and ``<name>(params, x, ...)``.
Sharding is applied by the runtime through ``repro.runtime.partitioning``
activation constraints — model code stays mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.obs.metrics import attention_path_counter
from repro.runtime import partitioning as P


# ---------------------------------------------------------------- basics --
def dense_init(key, in_dim: int, out_dim: int, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    return {"w": (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
                  * scale)}


def dense(params, x):
    return jnp.einsum("...d,df->...f", x, params["w"].astype(x.dtype))


def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(x.dtype)


def embed_init(key, vocab: int, dim: int):
    return {"table": jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02}


def embed(params, tokens, dtype=jnp.bfloat16):
    """Embedding lookup. Where the rules shard the table over vocab (TP),
    a plain gather makes GSPMD replicate the gathered activations
    ("involuntary full rematerialization"); the TPU-idiomatic form is a
    one-hot matmul — each shard contracts its vocab slice on the MXU and
    the partial results reduce-scatter, so nothing is ever replicated. The
    matmul runs in the model's compute ``dtype``: one nonzero term a row,
    so it reads the table exactly as the gather does."""
    table = params["table"]
    if P.current_mesh() is None or P.current_rules().get("vocab") is None:
        return jnp.take(table, tokens, axis=0)
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=dtype)
    onehot = P.constrain(onehot, ("batch", "seq", "vocab"))
    return jnp.einsum("...v,vd->...d", onehot, table.astype(dtype))


def unembed(params, x):
    """Tied unembedding: logits = x @ table^T, sharded over vocab."""
    logits = jnp.einsum("...d,vd->...v", x, params["table"].astype(x.dtype))
    return P.constrain(logits, ("batch", None, "vocab"))


def lm_head(params, x):
    """Untied output head: logits = x @ w, ``w`` (D, V) sharded over
    vocab."""
    return P.constrain(dense(params, x), ("batch", None, "vocab"))


# ----------------------------------------------------------------- rotary --
def _rope_angles(positions, head_dim: int, theta: float):
    """positions (...,) -> (..., head_dim/2) angles."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x, positions, theta: float,
               mrope_sections: Optional[Tuple[int, int, int]] = None,
               scale: Optional[float] = None):
    """Rotary embedding. x: (B, S, H, D). positions: (B, S) or (B, S, 3)
    for M-RoPE, where the three planes are (temporal, height, width) and
    `mrope_sections` splits the D/2 frequency bands among them
    (qwen2-vl, arXiv:2409.12191). A `scale` multiplies the rotated
    float32 values before their one cast back to x's dtype."""
    head_dim = x.shape[-1]
    if mrope_sections is not None:
        assert positions.ndim == 3 and positions.shape[-1] == 3
        angles_per_plane = _rope_angles(
            jnp.moveaxis(positions, -1, 0), head_dim, theta)  # (3, B, S, D/2)
        sections = jnp.concatenate([
            jnp.full((n,), i, jnp.int32)
            for i, n in enumerate(mrope_sections)])           # (D/2,)
        angles = jnp.take_along_axis(
            jnp.moveaxis(angles_per_plane, 0, -1),            # (B, S, D/2, 3)
            sections[None, None, :, None], axis=-1)[..., 0]
    else:
        if positions.ndim == 3:            # text-only M-RoPE degenerate case
            positions = positions[..., 0]
        angles = _rope_angles(positions, head_dim, theta)     # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    if scale is not None:
        out = out * scale
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention --
@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int


def attention_init(key, dims: AttnDims):
    ks = jax.random.split(key, 4)
    d, h, kv, hd = dims.d_model, dims.num_heads, dims.num_kv_heads, dims.head_dim
    return {
        "q": dense_init(ks[0], d, h * hd),
        "k": dense_init(ks[1], d, kv * hd),
        "v": dense_init(ks[2], d, kv * hd),
        "o": dense_init(ks[3], h * hd, d, scale=1.0 / jnp.sqrt(h * hd)),
    }


def _attn_mask(q_positions, kv_positions, causal: bool,
               window: Optional[int]):
    """(B, Sq, Skv) boolean mask (True = attend). kv_position -1 = unwritten."""
    q = q_positions[:, :, None]
    k = kv_positions[:, None, :] if kv_positions.ndim == 2 \
        else kv_positions[None, None, :]
    mask = (k >= 0)
    if causal:
        mask = mask & (k <= q)
    if window is not None:
        mask = mask & ((q - k) < window)
    return jnp.broadcast_to(mask, (q.shape[0], q.shape[1], k.shape[-1]))


def mha(q, k, v, mask):
    """q (B,Sq,H,D), k/v (B,Skv,KV,D), mask (B,Sq,Skv) -> (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    groups = h // kv
    q = q.reshape(b, sq, kv, groups, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


# Query-chunk threshold above which attention runs blockwise. Dense
# attention materializes (B, H, Sq, Skv) f32 logits — at 32k prefill that is
# tens of GB per chip; the blockwise path scans q in chunks of BLOCK_Q so
# only (B, H, BLOCK_Q, Skv) is ever live (exact, not an approximation).
# 2048 also routes the 4k TRAIN length through the blockwise path: with the
# per-chunk remat below, backward peak attention memory drops from
# O(S^2) to O(BLOCK_Q * S) per layer.
MHA_BLOCKWISE_THRESHOLD = 2048
BLOCK_Q = 512


def mha_blockwise(q, k, v, q_positions, kv_positions, causal, window,
                  block_q: int = BLOCK_Q):
    """Exact attention with the query axis processed in chunks.

    q (B,Sq,H,D), k/v (B,Skv,KV,D); q_positions (B,Sq); kv_positions (B,Skv)
    or (Skv,). The per-chunk mask is built from positions so no (Sq,Skv)
    tensor is ever materialized. TPU adaptation of flash attention: chunk
    work is MXU einsums; the chunk loop is a lax.scan (sequential grid), and
    softmax over the full kv axis inside a chunk avoids the online-rescale
    bookkeeping that GPUs need for shared-memory tiling.
    """
    b, sq, h, d = q.shape
    pad = (-sq) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded queries get position -1 -> they attend only to slot 0
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)),
                              constant_values=0)
    nc = q.shape[1] // block_q
    q_c = q.reshape(b, nc, block_q, h, d).swapaxes(0, 1)
    qp_c = q_positions.reshape(b, nc, block_q).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_body(qc, qpc, k, v):
        mask = _attn_mask(qpc, kv_positions, causal, window)
        return mha(qc, k, v, mask)

    def chunk(_, inp):
        qc, qpc = inp                                   # (B,bq,H,D), (B,bq)
        return None, chunk_body(qc, qpc, k, v)

    _, outs = jax.lax.scan(chunk, None, (q_c, qp_c))
    out = outs.swapaxes(0, 1).reshape(b, nc * block_q, h, d)
    return out[:, :sq]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_spec(mesh, h: int, kv: int, b: int) -> Optional[PartitionSpec]:
    """The shard_map spec of q, k and v (B, S, heads, D) under ``mesh``:
    batch and heads over the axes their rules name, so the KV heads a chip
    holds are the ones its query heads read; ``None`` where a rule's axes
    do not divide the dimension."""
    rules = P.current_rules()

    def size(axis):
        names = axis if isinstance(axis, tuple) else (axis,)
        return int(np.prod([mesh.shape[n] for n in names if n]))
    batch, heads = rules.get("batch"), rules.get("heads")
    if b % size(batch) or h % size(heads) or kv % size(heads):
        return None
    return PartitionSpec(batch, None, heads, None)


def attention_path(s: int, head_dim: int, heads: int, kv_heads: int, *,
                   canonical: bool, causal: bool, cached: bool,
                   cross: bool, batch: int = 1) -> str:
    """Which attention runs: ``flash`` (``kernels/flash_attention``) for
    causal self-attention over canonical positions ``0..s-1`` with no
    cache, on a TPU, past ``MHA_BLOCKWISE_THRESHOLD`` and at a sequence a
    flash block divides (and, under a mesh, whose heads split evenly);
    else ``blockwise`` past the threshold and ``dense`` below it."""
    if s <= MHA_BLOCKWISE_THRESHOLD:
        return "dense"
    from repro.kernels import flash_attention
    mesh = P.current_mesh()
    if (canonical and causal and not cached and not cross and _on_tpu()
            and flash_attention.block_sizes(s, head_dim) is not None
            and (mesh is None or _flash_spec(mesh, heads, kv_heads, batch)
                 is not None)):
        return "flash"
    return "blockwise"


def mha_flash(q, k, v, window: Optional[int]):
    """The flash kernel over canonical positions; ``q`` carries the
    ``1/sqrt(D)`` scale. Under a mesh it runs inside ``shard_map``, each
    chip on its own batch rows and heads (GSPMD cannot partition the
    kernel's custom call)."""
    from repro.kernels import ops as kernel_ops
    fn = functools.partial(kernel_ops.flash_attention, window=window)
    mesh = P.current_mesh()
    if mesh is None:
        return fn(q, k, v)
    spec = _flash_spec(mesh, q.shape[2], k.shape[2], q.shape[0])
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def init_kv_cache(batch: int, cache_len: int, num_kv: int, head_dim: int,
                  dtype=jnp.bfloat16):
    """Ring-buffer KV cache. kv_pos tracks the absolute position stored in
    each slot (-1 = empty); entry for position p lives at slot p % cache_len,
    so a cache_len == sliding_window ring serves SWA decode in O(window)
    memory and a cache_len == seq_len ring is an ordinary linear cache."""
    return {
        "k": jnp.zeros((batch, cache_len, num_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv, head_dim), dtype),
        "kv_pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def _cache_write(cache, k, v, q_positions):
    """Scatter S new (k, v) entries at slots positions % cache_len."""
    w = cache["k"].shape[1]
    slots = q_positions % w                                   # (B, S)
    bidx = jnp.arange(k.shape[0])[:, None]
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    cpos = cache["kv_pos"].at[bidx, slots].set(q_positions)
    return {"k": ck, "v": cv, "kv_pos": cpos}


# ------------------------------------------------------- paged KV cache --
def init_paged_kv_cache(batch: int, num_pages: int, page_size: int,
                        pages_per_seq: int, num_kv: int, head_dim: int,
                        dtype=jnp.bfloat16, kv_bits: int = 32):
    """Paged KV cache: a shared page pool plus per-sequence block tables.

    ``k_pages``/``v_pages`` are the physical pool — ``num_pages`` pages of
    ``page_size`` token slots each, shared by every sequence. ``kv_pos`` is
    pool-shaped (-1 = unwritten slot) so a freed-and-recycled page never
    leaks stale entries into another sequence's attention: the allocator
    invalidates a page's kv_pos on (re)allocation and the mask does the
    rest. ``block_tables[b, l]`` maps sequence b's logical page l to a
    physical page id (-1 = unmapped). The entry for absolute position p
    lives at (block_tables[b, p // page_size], p % page_size), so gathering
    a sequence's pages in logical order reproduces the linear cache layout
    exactly — which is what makes paged decode bit-identical to a
    contiguous cache of length pages_per_seq * page_size (DESIGN.md
    §Serving).

    ``kv_bits`` in (8, 4) switches the pools to low-bit storage: uint8
    ``ref.kv_page_quantize`` codes (4-bit packs two codes per byte along
    head_dim) plus per-(page, slot, KV-head) f32 ranges in
    ``k_scale``/``v_scale`` — entries are quantized at write time and
    dequantized at gather/kernel time (DESIGN.md §Serving, "KV page
    quantization"). ``dtype`` then only shapes the kv_bits=32 pools.

    Copy-on-write contract: nothing at this layer knows whether a
    physical page is referenced by one block-table row or many — sharing
    is purely a block-table phenomenon, which is why prefix sharing
    (serving/paging.PrefixIndex + fork_pages) needs ZERO kernel or
    attention-path changes. The layer guarantees two properties the
    sharing scheduler builds on: (1) a write is a deterministic function
    of (k, v, position) — including quantized pools, where
    ``kv_page_quantize`` rounds deterministically — so a fully written
    page's bytes depend only on the tokens and positions it covers; and
    (2) writes land strictly through ``block_tables``, so the scheduler
    can guarantee exclusivity by forking BEFORE a write ever targets a
    multiply-referenced page (DESIGN.md §Serving, "Prefix sharing")."""
    common = {
        "kv_pos": jnp.full((num_pages, page_size), -1, jnp.int32),
        "block_tables": jnp.full((batch, pages_per_seq), -1, jnp.int32),
    }
    if kv_bits == 32:
        return {
            "k_pages": jnp.zeros((num_pages, page_size, num_kv, head_dim),
                                 dtype),
            "v_pages": jnp.zeros((num_pages, page_size, num_kv, head_dim),
                                 dtype),
            **common,
        }
    if kv_bits not in (8, 4):
        raise ValueError(f"kv_bits must be 32, 8 or 4, got {kv_bits}")
    if kv_bits == 4 and head_dim % 2:
        raise ValueError("4-bit KV pages need an even head_dim")
    hd_store = head_dim if kv_bits == 8 else head_dim // 2
    return {
        "k_pages": jnp.zeros((num_pages, page_size, num_kv, hd_store),
                             jnp.uint8),
        "v_pages": jnp.zeros((num_pages, page_size, num_kv, hd_store),
                             jnp.uint8),
        "k_scale": jnp.zeros((num_pages, page_size, num_kv), jnp.float32),
        "v_scale": jnp.zeros((num_pages, page_size, num_kv), jnp.float32),
        **common,
    }


def is_paged_cache(cache) -> bool:
    return isinstance(cache, dict) and "k_pages" in cache


def paged_kv_bits(cache, head_dim: int) -> int:
    """Storage bits of a paged cache's pools, recovered from structure:
    full-precision caches carry no scale leaves; quantized pools are uint8
    codes whose last axis is head_dim (8-bit) or head_dim // 2 (4-bit
    packed)."""
    if "k_scale" not in cache:
        return 32
    return 8 if cache["k_pages"].shape[-1] == head_dim else 4


def paged_page_slabs(cache, pages):
    """Everything physically stored for the given pool pages: a dict of
    ``{leaf_name: (len(pages), page_size, ...)}`` slices over every pool
    leaf (K/V payload, quantized scale side info, kv_pos). This is the
    unit a copy-on-write fork must duplicate bit-exactly — the serving
    tests compare donor and fork slabs for byte equality (and distinct
    physical ids) to pin that a fork never aliases its donor."""
    idx = jnp.asarray(pages, jnp.int32)
    return {name: jnp.take(cache[name], idx, axis=0)
            for name in ("k_pages", "v_pages", "k_scale", "v_scale",
                         "kv_pos") if name in cache}


def _paged_slots(cache, q_positions):
    """(physical page, in-page slot) for each (b, s) position; invalid
    positions (< 0 — padding / inactive decode slots) map to the
    out-of-bounds page ``num_pages`` so scatters with mode="drop" discard
    them and gathers never see them."""
    num_pages, page_size = cache["kv_pos"].shape
    logical = q_positions // page_size                        # (B, S)
    valid = (q_positions >= 0) & (logical < cache["block_tables"].shape[1])
    phys = jnp.take_along_axis(cache["block_tables"],
                               jnp.clip(logical, 0, None), axis=1)
    valid = valid & (phys >= 0)
    phys = jnp.where(valid, phys, num_pages)                  # OOB -> drop
    return phys, q_positions % page_size


def _paged_cache_write(cache, k, v, q_positions):
    """Scatter S new (k, v) entries through the block table into the pool.
    Quantized pools encode each entry at write time (per-token ranges land
    in the scale leaves alongside the codes), so prefill chunks and decode
    steps fill pages in their storage format — nothing re-encodes later."""
    phys, slots = _paged_slots(cache, q_positions)            # (B, S)
    pf, sf = phys.reshape(-1), slots.reshape(-1)

    def flat(a):
        return a.reshape((-1,) + a.shape[2:])

    new = dict(cache)
    if "k_scale" in cache:
        from repro.kernels import ref as kernel_ref
        bits = paged_kv_bits(cache, k.shape[-1])
        kq, kr = kernel_ref.kv_page_quantize(k, kv_bits=bits)
        vq, vr = kernel_ref.kv_page_quantize(v, kv_bits=bits)
        new["k_pages"] = cache["k_pages"].at[pf, sf].set(flat(kq),
                                                        mode="drop")
        new["v_pages"] = cache["v_pages"].at[pf, sf].set(flat(vq),
                                                        mode="drop")
        new["k_scale"] = cache["k_scale"].at[pf, sf].set(flat(kr),
                                                        mode="drop")
        new["v_scale"] = cache["v_scale"].at[pf, sf].set(flat(vr),
                                                        mode="drop")
    else:
        kf = flat(k).astype(cache["k_pages"].dtype)
        vf = flat(v).astype(cache["v_pages"].dtype)
        new["k_pages"] = cache["k_pages"].at[pf, sf].set(kf, mode="drop")
        new["v_pages"] = cache["v_pages"].at[pf, sf].set(vf, mode="drop")
    new["kv_pos"] = cache["kv_pos"].at[pf, sf].set(
        q_positions.reshape(-1), mode="drop")
    return new


def paged_gather(cache, head_dim: Optional[int] = None):
    """Gather each sequence's pages in logical order into a contiguous view.

    Returns (k, v, kv_pos) shaped (B, pages_per_seq * page_size, ...) —
    elementwise equal to a linear cache of that length (unmapped pages
    surface kv_pos = -1, so the mask removes them). Quantized pools are
    dequantized to f32 after the gather; ``head_dim`` is required then (the
    packed 4-bit layout is not recoverable from pool shapes alone)."""
    bt = cache["block_tables"]                                # (B, P)
    b, p = bt.shape
    ps = cache["kv_pos"].shape[1]
    safe = jnp.where(bt >= 0, bt, 0)
    mapped = (bt >= 0)[:, :, None]                            # (B, P, 1)

    def take(pool):
        g = jnp.take(pool, safe, axis=0)                      # (B, P, ps, ...)
        return g.reshape((b, p * ps) + g.shape[3:])

    k, v = take(cache["k_pages"]), take(cache["v_pages"])
    if "k_scale" in cache:
        if head_dim is None:
            raise ValueError("quantized paged cache: paged_gather needs "
                             "head_dim to undo the code packing")
        from repro.kernels import ref as kernel_ref
        bits = paged_kv_bits(cache, head_dim)
        k = kernel_ref.kv_page_dequantize(k, take(cache["k_scale"]),
                                          kv_bits=bits, head_dim=head_dim)
        v = kernel_ref.kv_page_dequantize(v, take(cache["v_scale"]),
                                          kv_bits=bits, head_dim=head_dim)
    kv_pos = jnp.where(mapped, jnp.take(cache["kv_pos"], safe, axis=0), -1)
    return k, v, kv_pos.reshape(b, p * ps)


def _use_paged_kernel(s: int, window) -> bool:
    """Route single-token paged decode through the Pallas block-table
    gather kernel. Off by default (the jnp gather path is the bit-golden
    reference); REPRO_PAGED_ATTN_KERNEL=1 turns it on. Windowed (SWA)
    attention stays on the gather path — the kernel masks by context
    length only."""
    import os
    return (s == 1 and window is None
            and os.environ.get("REPRO_PAGED_ATTN_KERNEL", "0") == "1")


def _paged_attn_kernel_out(cache, q, q_positions):
    """(B, 1, H, hd) attention output via the paged-attention decode
    kernel: K/V pages are gathered through the block table inside the
    ``pallas_call`` (scalar prefetch), never materialized contiguously.
    Quantized pools ship their codes + scale side info into the kernel,
    which dequantizes each page right after its DMA."""
    from repro.kernels import ops as kernel_ops
    ctx_lens = jnp.maximum(q_positions[:, 0] + 1, 0)          # (B,)
    kw = {}
    if "k_scale" in cache:
        kw = dict(k_scale=cache["k_scale"], v_scale=cache["v_scale"],
                  kv_bits=paged_kv_bits(cache, q.shape[-1]))
    out = kernel_ops.paged_attention_decode(
        q[:, 0], cache["k_pages"], cache["v_pages"],
        cache["block_tables"], ctx_lens, **kw)
    return out[:, None].astype(q.dtype)


def attention_apply(params, dims: AttnDims, x, positions, *,
                    causal: bool = True, window: Optional[int] = None,
                    rope_theta: float = 10000.0,
                    mrope_sections=None, use_rope: bool = True,
                    cache: Optional[dict] = None,
                    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                    canonical_positions: bool = False):
    """Self- (or cross-, via kv_override) attention with optional ring cache.

    Returns (out, new_cache). `positions` is (B, S) absolute (or (B, S, 3)
    for M-RoPE; plane 0 = temporal is used for masking).
    `canonical_positions` says they are ``0..S-1`` in every row (the model
    built them), which lets `attention_path` pick the flash kernel.
    """
    b, s, _ = x.shape
    h, kv, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    q_positions = positions if positions.ndim == 2 else positions[..., 0]
    cross = kv_override is not None
    path = attention_path(s, hd, h, kv, canonical=canonical_positions,
                          causal=causal, cached=cache is not None,
                          cross=cross, batch=b)
    attention_path_counter().inc(
        1, path=path, masked="none" if cross or not causal
        else "window" if window is not None else "causal")
    # the flash kernel takes q scaled: 1/sqrt(D) folded into RoPE's float32
    # arithmetic, before q's one cast back to the compute dtype
    q_scale = float(hd) ** -0.5 if path == "flash" else None
    q = dense(params["q"], x).reshape(b, s, h, hd)

    new_cache = None
    if kv_override is not None:                       # cross-attention
        k, v = kv_override
        kv_positions = jnp.zeros((b, k.shape[1]), jnp.int32)  # all visible
        eff_causal, eff_window = False, None
    else:
        k = dense(params["k"], x).reshape(b, s, kv, hd)
        v = dense(params["v"], x).reshape(b, s, kv, hd)
        if use_rope:
            q = apply_rope(q, positions, rope_theta, mrope_sections,
                           scale=q_scale)
            k = apply_rope(k, positions, rope_theta, mrope_sections)
        elif q_scale is not None:
            q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
        if cache is not None:
            if is_paged_cache(cache):
                new_cache = _paged_cache_write(cache, k, v, q_positions)
                if _use_paged_kernel(s, window):
                    out = _paged_attn_kernel_out(new_cache, q, q_positions)
                    out = dense(params["o"], out.reshape(b, s, h * hd))
                    return P.constrain(out, ("batch", "seq", "embed")), \
                        new_cache
                k, v, kv_positions = paged_gather(new_cache, head_dim=hd)
            else:
                new_cache = _cache_write(cache, k, v, q_positions)
                k, v = new_cache["k"], new_cache["v"]
                kv_positions = new_cache["kv_pos"]
        else:
            kv_positions = q_positions
        eff_causal, eff_window = causal, window

    q = P.constrain(q, ("batch", "seq", "heads", None))
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    if path == "flash":
        out = mha_flash(q, k, v, eff_window)
    elif path == "blockwise":
        out = mha_blockwise(q, k, v, q_positions, kv_positions,
                            eff_causal, eff_window)
    else:
        mask = _attn_mask(q_positions, kv_positions, eff_causal, eff_window)
        out = mha(q, k, v, mask)
    out = dense(params["o"], out.reshape(b, s, h * hd))
    return P.constrain(out, ("batch", "seq", "embed")), new_cache


# -------------------------------------------------------------------- MLP --
def mlp_init(key, d_model: int, d_ff: int):
    ks = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(ks[0], d_model, d_ff),
        "wi_up": dense_init(ks[1], d_model, d_ff),
        "wo": dense_init(ks[2], d_ff, d_model, scale=1.0 / jnp.sqrt(d_ff)),
    }


def mlp_apply(params, x, activation: str = "silu"):
    gate = dense(params["wi_gate"], x)
    up = dense(params["wi_up"], x)
    act = jax.nn.silu(gate) if activation == "silu" else jax.nn.gelu(gate)
    h = P.constrain(act * up, ("batch", "seq", "ff"))
    return P.constrain(dense(params["wo"], h), ("batch", "seq", "embed"))
