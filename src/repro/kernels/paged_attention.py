"""Pallas TPU kernels: paged-attention decode (one query token, K/V gathered
through the block table).

The serving decode step attends ONE new token per sequence against a KV
cache whose pages are scattered across a shared pool (``DESIGN.md
§Serving``). Materializing the gathered (B, P·ps, KV, hd) view first — the
jnp reference path — doubles the HBM traffic of the step; the kernels
instead gather each page directly into VMEM via *scalar prefetch*: the
block table lives in SMEM before the body runs, so the BlockSpec index_map
picks which physical (1, page_size, KV·hd) page of the pool to DMA for
each grid step — the same dynamic-gather pattern as ``edge_gather_mix``.

Two variants share the page-gather machinery (``ops.paged_attention_decode``
selects between them by the one-shot slab footprint; see the selection rule
there):

``paged_attention_decode`` — one-shot softmax, TWO-PHASE grid
  (the vLLM paged_attention_v1 shape, adapted to the sequential TPU grid):

  phase 0  per-page QK^T logits (MXU dots per KV head) land in a
           (P, H, ps) VMEM scratch, one page per leading index, masked
           by the context length;
  phase 1  at its first step the softmax runs ONCE over all pages
           (``ref.paged_softmax``; no online-rescale bookkeeping —
           bit-stable vs the oracle), then each step re-DMAs its V page
           and accumulates probs_page @ V_page into the (H, hd) output
           block in page order.

  Bit-identical to ``ref.paged_attention_ref`` (same per-page dot shapes,
  same one-shot softmax, same page-order f32 accumulation) — the
  short-context default and the bit-oracle for the online variant.

``paged_attention_decode_online`` — flash-style online softmax, ONE-PHASE
  grid: per page the running maximum m, running normalizer l, and the
  (H, hd) f32 accumulator are rescaled by exp(m - m_new) (FlashAttention /
  vLLM v1), so VMEM residency is bounded by ONE (H, ps) page slab plus the
  fixed (H, hd) + 2·(H, 1) carry — independent of context length. This is
  what removes the one-shot slab's VMEM ceiling (32 heads × 500k ctx × 4B
  ≈ 64 MB vs ~16 MB/core); numerics agree with the one-shot reduction to
  float tolerance (~1e-6 relative), not bitwise — the rescale order
  differs. Pages entirely beyond ctx are skipped (predicated off), so the
  online variant also does less arithmetic on short contexts in long
  tables.

Quantized KV pages (kv_bits in (8, 4)): the pools hold
``ref.kv_page_quantize`` codes (uint8; 4-bit packs two codes per byte
along head_dim) and per-(page, slot, KV-head) f32 ranges ride in
``k_scale``/``v_scale`` side-info blocks. Both kernel bodies trace
``ref.kv_page_dequantize`` on each page right after its DMA — K/V never
rematerialize in f32 in HBM, so cache reads shrink ~4x (int8) / ~8x
(int4) while the arithmetic is unchanged f32.

Work is O(ctx · H · hd) row DMAs per sequence, independent of pool size.
Unmapped (-1) and out-of-range block-table ids are clamped into the pool
here (and by the ``ops`` wrapper, whose public contract it is); their
logits are masked by ctx_len, so the junk page contributes exactly
nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels import ref as _ref

_NEG_INF = -1e30


def _load_page(page_ref, scale_ref, *, num_kv: int, head_dim: int,
               page_size: int, kv_bits: int):
    """The DMA'd page as KV per-head (ps, hd) f32 blocks. A full-precision
    page is cast and sliced along its lanes — the TPU compiler refuses a
    reshape that splits the lane axis into (KV, hd); a code pool goes
    through the traced ``ref.kv_page_dequantize`` (scale_ref is the page's
    (1, ps, KV) side-info block)."""
    if kv_bits == 32:
        page = page_ref[0].astype(jnp.float32)             # (ps, KV*hd)
        return [page[:, h * head_dim:(h + 1) * head_dim]
                for h in range(num_kv)]
    codes = page_ref[0].reshape(page_size, num_kv, -1)
    page = _ref.kv_page_dequantize(codes, scale_ref[0], kv_bits=kv_bits,
                                   head_dim=head_dim)
    return [page[:, h] for h in range(num_kv)]


def _page_logits(q_ref, k, p, ctx, *, num_kv: int, head_dim: int,
                 page_size: int, scale: float):
    """((H, ps) masked logits slab, (1, ps) validity) for page ``p``.
    Slot s of logical page p holds absolute position p*ps + s; the single
    decode query sits at position ctx-1, so causal+written masking
    collapses to slot_index < ctx."""
    q = q_ref[...]                                         # (H, hd) f32
    groups = q.shape[0] // num_kv
    idx = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = idx < ctx                                      # (1, ps)
    rows = []
    for kvh in range(num_kv):
        dots = jax.lax.dot_general(
            q[kvh * groups:(kvh + 1) * groups], k[kvh],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (G, ps)
        rows.append(dots * scale)
    slab = jnp.concatenate(rows, axis=0)                   # (H, ps)
    return jnp.where(valid, slab, _NEG_INF), valid


def _probs_dot_v(probs, v, *, num_kv: int):
    """(H, ps) probs x KV per-head (ps, hd) V -> (H, hd), per-KV-head MXU
    dots."""
    groups = probs.shape[0] // num_kv
    outs = []
    for kvh in range(num_kv):
        pg = probs[kvh * groups:(kvh + 1) * groups]        # (G, ps)
        outs.append(jax.lax.dot_general(
            pg, v[kvh], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))           # (G, hd)
    return jnp.concatenate(outs, axis=0)


def _paged_attn_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, *rest, num_kv:
                       int, head_dim: int, page_size: int, scale: float,
                       kv_bits: int):
    # bt_ref/ctx_ref are scalar-prefetch (SMEM) refs; q_ref/out_ref are
    # this sequence's (H, hd) query and output; k_ref/v_ref are the
    # (1, ps, KV*hd_store) physical page the index_map already gathered for
    # this (b, phase, p) step; ks_ref/vs_ref (quantized pools only) its
    # (1, ps, KV) ranges.
    if kv_bits == 32:
        ks_ref = vs_ref = None
        out_ref, logits_ref = rest
    else:
        ks_ref, vs_ref, out_ref, logits_ref = rest
    b = pl.program_id(0)
    phase = pl.program_id(1)
    p = pl.program_id(2)
    ctx = ctx_ref[b]
    dims = dict(num_kv=num_kv, head_dim=head_dim, page_size=page_size)

    @pl.when(phase == 0)
    def _logits():
        k = _load_page(k_ref, ks_ref, kv_bits=kv_bits, **dims)
        slab, _ = _page_logits(q_ref, k, p, ctx, scale=scale, **dims)
        logits_ref[p] = slab

    @pl.when((phase == 1) & (p == 0))
    def _softmax():
        logits_ref[...] = _ref.paged_softmax(logits_ref[...])
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(phase == 1)
    def _accumulate():
        v = _load_page(v_ref, vs_ref, kv_bits=kv_bits, **dims)
        probs = logits_ref[p]                                   # (H, ps)
        out_ref[...] += _probs_dot_v(probs, v, num_kv=num_kv)


def _paged_attn_online_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref, *rest,
                              num_kv: int, head_dim: int, page_size: int,
                              scale: float, kv_bits: int):
    # One grid phase; acc/m/l are VMEM carries across the page sweep:
    # acc (H, hd) rescaled accumulator, m (H, 1) running max, l (H, 1)
    # running normalizer. No scratch scales with pages_per_seq.
    if kv_bits == 32:
        ks_ref = vs_ref = None
        out_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref, vs_ref, out_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    ctx = ctx_ref[b]
    dims = dict(num_kv=num_kv, head_dim=head_dim, page_size=page_size)

    @pl.when(p == 0)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages entirely beyond ctx contribute nothing — skip their arithmetic
    # (their DMA still happens; the index_map is unconditional)
    @pl.when(p * page_size < ctx)
    def _page():
        k = _load_page(k_ref, ks_ref, kv_bits=kv_bits, **dims)
        slab, valid = _page_logits(q_ref, k, p, ctx, scale=scale, **dims)
        m_prev = m_ref[...]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(slab, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked slots must stay exactly zero: with m still at -inf they
        # would exp(s - m) to 1, not 0 — mask the probabilities, not just
        # the logits
        probs = jnp.where(valid, jnp.exp(slab - m_new), 0.0)  # (H, ps)
        v = _load_page(v_ref, vs_ref, kv_bits=kv_bits, **dims)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(probs, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _probs_dot_v(probs, v,
                                                           num_kv=num_kv)
        m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_ref[...]
        # ctx == 0 (inactive slot): l stays 0 -> emit zeros, not NaN
        out_ref[...] = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)


def _prep(q, k_pages, v_pages, block_tables, ctx_lens, k_scale, v_scale,
          kv_bits):
    """Shared entry validation + flattening for both kernel variants."""
    bsz, h, hd = q.shape
    num_pages, page_size, num_kv, hd_store = k_pages.shape
    assert h % num_kv == 0
    if kv_bits == 32:
        assert hd_store == hd
        assert k_scale is None and v_scale is None
    else:
        assert kv_bits in (8, 4)
        assert hd_store == (hd if kv_bits == 8 else hd // 2)
        assert k_scale is not None and v_scale is not None
        assert k_scale.shape == (num_pages, page_size, num_kv)
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, num_pages - 1)
    scale = 1.0 / float(np.sqrt(np.float32(hd)))
    kvhd = num_kv * hd_store
    k_flat = k_pages.reshape(num_pages, page_size, kvhd)
    v_flat = v_pages.reshape(num_pages, page_size, kvhd)
    q_flat = q.astype(jnp.float32)
    return (bsz, h, hd, num_pages, page_size, num_kv, kvhd, bt, scale,
            k_flat, v_flat, q_flat)


@functools.partial(jax.jit, static_argnames=("kv_bits", "interpret"))
def paged_attention_decode(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           ctx_lens: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           kv_bits: int = 32,
                           interpret: bool | None = None) -> jax.Array:
    """Single-token decode attention through a paged KV cache (one-shot
    softmax — the short-context default and bit-oracle; see the module
    docstring for the selection rule vs the online variant).

    Args:
      q: (B, H, hd) query for the one new token of each sequence (already
        rotary-embedded).
      k_pages, v_pages: (num_pages, page_size, KV, hd_store) shared pools
        (f32/bf16 values, or uint8 ``ref.kv_page_quantize`` codes when
        ``kv_bits`` < 32).
      block_tables: (B, pages_per_seq) int32 physical page ids; unmapped
        (-1) or out-of-range slots are clamped into the pool here and
        masked by ``ctx_lens``.
      ctx_lens: (B,) int32 tokens written for each sequence (the query's
        position + 1); 0 for inactive slots (output = uniform average of
        junk, callers mask it).
      k_scale, v_scale: (num_pages, page_size, KV) f32 per-entry ranges —
        required iff ``kv_bits`` in (8, 4).
      kv_bits: 32 (full precision) | 8 | 4 (quantized pools).
      interpret: interpreter mode; ``None`` decides by platform
        (``kernels.interpret_mode``).

    Returns:
      (B, H, hd) f32 attention output, bit-identical to
      ``ref.paged_attention_ref`` (same kv_bits).
    """
    (bsz, h, hd, num_pages, page_size, num_kv, kvhd, bt, scale,
     k_flat, v_flat, q_flat) = _prep(q, k_pages, v_pages, block_tables,
                                     ctx_lens, k_scale, v_scale, kv_bits)
    pages_per_seq = block_tables.shape[1]

    def qmap(b, ph, p, bt_ref, ctx_ref):
        return (b, 0, 0)

    def pagemap(b, ph, p, bt_ref, ctx_ref):
        return (bt_ref[b, p], 0, 0)

    in_specs = [
        pl.BlockSpec((None, h, hd), qmap),
        pl.BlockSpec((1, page_size, kvhd), pagemap),
        pl.BlockSpec((1, page_size, kvhd), pagemap),
    ]
    inputs = [q_flat, k_flat, v_flat]
    if kv_bits != 32:
        in_specs += [pl.BlockSpec((1, page_size, num_kv), pagemap)] * 2
        inputs += [k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, 2, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, h, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((pages_per_seq, h, page_size), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, num_kv=num_kv,
                               head_dim=hd, page_size=page_size,
                               scale=scale, kv_bits=kv_bits)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, hd), jnp.float32),
        interpret=interpret_mode(interpret),
    )(bt, ctx_lens.astype(jnp.int32), *inputs)
    return out


@functools.partial(jax.jit, static_argnames=("kv_bits", "interpret"))
def paged_attention_decode_online(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_tables: jax.Array,
                                  ctx_lens: jax.Array, *,
                                  k_scale: jax.Array | None = None,
                                  v_scale: jax.Array | None = None,
                                  kv_bits: int = 32,
                                  interpret: bool | None = None) -> jax.Array:
    """Online-softmax variant of :func:`paged_attention_decode`: same
    arguments, same masking contract, float-tolerance (not bitwise)
    agreement with ``ref.paged_attention_ref`` — VMEM scratch is ONE
    (H, hd) accumulator plus two (H, 1) carries, independent of
    ``pages_per_seq`` (the long-context variant; pinned by the
    scratch-shape test)."""
    (bsz, h, hd, num_pages, page_size, num_kv, kvhd, bt, scale,
     k_flat, v_flat, q_flat) = _prep(q, k_pages, v_pages, block_tables,
                                     ctx_lens, k_scale, v_scale, kv_bits)
    pages_per_seq = block_tables.shape[1]

    def qmap(b, p, bt_ref, ctx_ref):
        return (b, 0, 0)

    def pagemap(b, p, bt_ref, ctx_ref):
        return (bt_ref[b, p], 0, 0)

    in_specs = [
        pl.BlockSpec((None, h, hd), qmap),
        pl.BlockSpec((1, page_size, kvhd), pagemap),
        pl.BlockSpec((1, page_size, kvhd), pagemap),
    ]
    inputs = [q_flat, k_flat, v_flat]
    if kv_bits != 32:
        in_specs += [pl.BlockSpec((1, page_size, num_kv), pagemap)] * 2
        inputs += [k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, h, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((h, hd), jnp.float32),      # rescaled accumulator
            pltpu.VMEM((h, 1), jnp.float32),       # running max m
            pltpu.VMEM((h, 1), jnp.float32),       # running normalizer l
        ],
    )
    kernel = functools.partial(_paged_attn_online_kernel, num_kv=num_kv,
                               head_dim=hd, page_size=page_size,
                               scale=scale, kv_bits=kv_bits)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, hd), jnp.float32),
        interpret=interpret_mode(interpret),
    )(bt, ctx_lens.astype(jnp.int32), *inputs)
    return out
