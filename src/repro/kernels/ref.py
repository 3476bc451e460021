"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are validated against (bit-exact for
identical uniforms). Kept dependency-free of pallas so tests can diff both
implementations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import bit_schedule

_EPS = 1e-12


def quant_levels(qrange: jax.Array, safe_delta: jax.Array) -> jax.Array:
    """The top quantization level ``2^b - 1`` recovered from ``(R, Δ)``:
    ``2R/Δ`` is that integer only up to rounding, and how it rounds
    depends on how the program computing it was compiled. Rounded to the
    nearest integer it is exact, so every quantize program clips the codes
    at the same level (traced by the kernels and the oracles alike)."""
    return jnp.floor(2.0 * qrange / safe_delta + 0.5)


def stoch_quantize_ref(theta: jax.Array, q_hat_prev: jax.Array,
                       uniforms: jax.Array, delta: jax.Array,
                       qrange: jax.Array) -> jax.Array:
    """Fused quantize->dequantize (paper Eqs. 14, 15, 20).

    Args:
      theta: (N, ...) current models: (N, d) rows, or a leaf in its own
        shape.
      q_hat_prev: previous quantized models Q̂^{k-1}, shaped as ``theta``.
      uniforms: U(0,1) draws for the stochastic rounding, as ``theta``.
      delta: (N,) step sizes Δ_n^k.
      qrange: (N,) ranges R_n^k.

    Returns:
      The reconstruction Q̂^k = Q̂^{k-1} + Δ q - R 1, shaped as ``theta``.
    """
    dtype = theta.dtype
    col = (theta.shape[0],) + (1,) * (theta.ndim - 1)
    theta32 = theta.astype(jnp.float32)
    qprev32 = q_hat_prev.astype(jnp.float32)
    unif32 = uniforms.astype(jnp.float32)
    safe_delta = jnp.maximum(delta.astype(jnp.float32), _EPS).reshape(col)
    r = qrange.astype(jnp.float32).reshape(col)
    c = (theta32 - qprev32 + r) / safe_delta
    floor_c = jnp.floor(c)
    q = floor_c + (unif32 < (c - floor_c)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, quant_levels(r, safe_delta))
    return (qprev32 + safe_delta * q - r).astype(dtype)


def stoch_quantize_grouped_ref(theta: jax.Array, q_hat_prev: jax.Array,
                               uniforms: jax.Array, delta: jax.Array,
                               qrange: jax.Array,
                               group_ids: jax.Array) -> jax.Array:
    """Grouped quantize->dequantize over a packed buffer (Eqs. 14-20,
    group-wise) — ground truth for the fused kernel.

    Args:
      theta, q_hat_prev, uniforms: (N, D) packed buffers.
      delta, qrange: (N, G) per-worker per-group step sizes / ranges.
      group_ids: (D,) int32 column -> group id map.

    Returns:
      (N, D) reconstruction; column j is quantized with the side
      information of its group ``group_ids[j]``. G=1 reproduces
      :func:`stoch_quantize_ref` bit-for-bit.
    """
    dtype = theta.dtype
    theta32 = theta.astype(jnp.float32)
    qprev32 = q_hat_prev.astype(jnp.float32)
    unif32 = uniforms.astype(jnp.float32)
    delta_c = jnp.take(delta.astype(jnp.float32), group_ids, axis=1)  # (N, D)
    range_c = jnp.take(qrange.astype(jnp.float32), group_ids, axis=1)
    safe_delta = jnp.maximum(delta_c, _EPS)
    c = (theta32 - qprev32 + range_c) / safe_delta
    floor_c = jnp.floor(c)
    q = floor_c + (unif32 < (c - floor_c)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, quant_levels(range_c, safe_delta))
    return (qprev32 + safe_delta * q - range_c).astype(dtype)


def grouped_range_ref(diff: jax.Array, group_runs) -> jax.Array:
    """Per-worker per-group ``max |diff|`` over the static contiguous column
    runs of each group — the oracle for the in-kernel range reduction
    (identical reduction order, so max is bit-exact)."""
    absdiff = jnp.abs(diff)
    cols = []
    for runs in group_runs:
        parts = [jnp.max(absdiff[:, off:off + size], axis=1)
                 for off, size in runs]
        if not parts:
            parts = [jnp.zeros((diff.shape[0],), jnp.float32)]
        cols.append(parts[0] if len(parts) == 1
                    else jnp.max(jnp.stack(parts, axis=0), axis=0))
    return jnp.stack(cols, axis=1)


def stoch_quantize_grouped_fused_ref(
    theta: jax.Array, q_hat_prev: jax.Array, uniforms: jax.Array,
    bits_prev: jax.Array, range_prev: jax.Array, initialized: jax.Array,
    group_ids: jax.Array, *, group_runs, omega: float, b0: int, b_max: int,
):
    """Ground truth for ``stoch_quantize_grouped_fused``: the whole grouped
    round — range reduction over the static group runs, Eq. (18) bit
    schedule (via ``core.quantization.bit_schedule``, the same function the
    kernel traces), stochastic quantize, degenerate-group passthrough.

    Returns ``(out (N, D), range_new (N, G), bits (N, G), delta (N, G))``.
    """
    theta32 = theta.astype(jnp.float32)
    qprev32 = q_hat_prev.astype(jnp.float32)
    range_new = grouped_range_ref(theta32 - qprev32, group_runs)
    bits, delta, degen = bit_schedule(
        bits_prev.astype(jnp.float32), range_new,
        range_prev.astype(jnp.float32), initialized.astype(jnp.float32),
        omega, b0, b_max)
    out = stoch_quantize_grouped_ref(theta, q_hat_prev, uniforms, delta,
                                     range_new, group_ids)
    degen_c = jnp.take(degen, group_ids, axis=1)
    out = jnp.where(degen_c, qprev32.astype(out.dtype), out)
    return out, range_new, bits, delta.astype(jnp.float32)


# --------------------------------------------------- KV page quantization --
def _kv_page_delta(rng: jax.Array, kv_bits: int) -> jax.Array:
    """Step size Δ = 2R / (2^b - 1) for a fixed-bit page codec, via the
    SAME ``bit_schedule`` the engine's adaptive rounds use: a cache page is
    just a group whose bit width never grows (initialized=0 pins b = b0 =
    ``kv_bits``), so the codec cannot drift from the paper's Eq. (18)/(19)
    machinery."""
    zeros = jnp.zeros_like(rng)
    _, delta, _ = bit_schedule(zeros, rng, zeros, zeros,
                               0.0, kv_bits, kv_bits)
    return jnp.maximum(delta, _EPS)


def kv_page_quantize(x: jax.Array, *, kv_bits: int):
    """Encode K/V page entries to ``kv_bits``-bit codes (paper Eqs. 14/15
    with Q̂_prev = 0 and the deterministic u = 0.5 rounding draw, so a
    replayed stream re-encodes identically).

    x: (..., KV, hd) -> (codes (..., KV, hd_store) uint8, rng (..., KV)
    f32).  hd_store = hd for 8-bit; hd // 2 for 4-bit (two codes packed
    per byte along head_dim — hd must be even).  The per-token per-KV-head
    range R = max|x| is the side information; Δ is derived from it
    statically (:func:`_kv_page_delta`), so R is the ONLY float carried
    per entry."""
    if kv_bits not in (8, 4):
        raise ValueError(f"kv_bits must be 8 or 4, got {kv_bits}")
    x32 = x.astype(jnp.float32)
    rng = jnp.max(jnp.abs(x32), axis=-1)                      # (..., KV)
    delta = _kv_page_delta(rng, kv_bits)[..., None]
    c = (x32 + rng[..., None]) / delta
    floor_c = jnp.floor(c)
    q = floor_c + (0.5 < (c - floor_c)).astype(jnp.float32)   # u = 0.5
    q = jnp.clip(q, 0.0, float(2 ** kv_bits - 1)).astype(jnp.int32)
    if kv_bits == 4:
        if x.shape[-1] % 2:
            raise ValueError("4-bit KV pages need an even head_dim")
        pair = q.reshape(q.shape[:-1] + (x.shape[-1] // 2, 2))
        q = pair[..., 0] | (pair[..., 1] << 4)
    return q.astype(jnp.uint8), rng


def kv_page_dequantize(codes: jax.Array, rng: jax.Array, *, kv_bits: int,
                       head_dim: int) -> jax.Array:
    """Decode :func:`kv_page_quantize` output back to f32: x̂ = Δ·q - R
    (Eq. 20 with Q̂_prev = 0). Shared by the jnp gather path AND traced
    inside both paged-attention kernel bodies (right after each page DMA),
    so the in-kernel dequant cannot drift from this definition.

    codes: (..., KV, hd_store) uint8; rng: (..., KV) f32 ->
    (..., KV, head_dim) f32."""
    q = codes.astype(jnp.int32)
    if kv_bits == 4:
        lo, hi = q & 0xF, (q >> 4) & 0xF
        q = jnp.stack([lo, hi], axis=-1).reshape(
            q.shape[:-1] + (head_dim,))
    delta = _kv_page_delta(rng, kv_bits)[..., None]
    return delta * q.astype(jnp.float32) - rng[..., None]


def paged_softmax(logits: jax.Array) -> jax.Array:
    """Softmax of each head over all its (page, slot) logits, laid out
    (P, H, ps): max and sum run over the slots of a page, then across the
    pages. Traced both here and inside the one-shot kernel, whose scratch
    keeps pages on the leading axis (the TPU stores a page's slab there at
    a dynamic index; along the lanes it could only at multiples of 128)."""
    m = jnp.max(jnp.max(logits, axis=2, keepdims=True), axis=0,
                keepdims=True)
    e = jnp.exp(logits - m)
    return e / jnp.sum(jnp.sum(e, axis=2, keepdims=True), axis=0,
                       keepdims=True)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, block_tables: jax.Array,
                        ctx_lens: jax.Array, *,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None,
                        kv_bits: int = 32) -> jax.Array:
    """Single-token decode attention through a paged KV cache — ground
    truth for the ``paged_attention_decode`` kernel, mirroring its exact
    evaluation order (per-page QK dots, ONE softmax over all pages'
    logits, f32 V accumulation in logical page order), so identical inputs
    produce bit-identical outputs.

    q: (B, H, hd); k_pages/v_pages: (num_pages, page_size, KV, hd_store);
    block_tables: (B, P) int32 (-1 = unmapped, clamped + masked);
    ctx_lens: (B,) int32. With ``kv_bits`` in (8, 4) the pools hold
    :func:`kv_page_quantize` codes and ``k_scale``/``v_scale``
    ((num_pages, page_size, KV) f32 ranges) carry the side info — each
    page is dequantized just before its dots, exactly as the kernels do
    after the page DMA. Returns (B, H, hd) f32."""
    bsz, h, hd = q.shape
    num_pages, page_size, num_kv, _ = k_pages.shape
    groups = h // num_kv
    pages_per_seq = block_tables.shape[1]
    scale = 1.0 / float(np.sqrt(np.float32(hd)))
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, num_pages - 1)

    def page(pool, scales, pid):                           # (ps, KV, hd) f32
        if kv_bits == 32:
            return pool[pid].astype(jnp.float32)
        return kv_page_dequantize(pool[pid], scales[pid], kv_bits=kv_bits,
                                  head_dim=hd)

    def dots(a, b_mat):                                    # (G,hd)x(ps,hd)
        return jax.lax.dot_general(a, b_mat, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    outs = []
    for b in range(bsz):
        qb = q[b].astype(jnp.float32).reshape(num_kv, groups, hd)
        slabs = []
        for p in range(pages_per_seq):
            k = page(k_pages, k_scale, bt[b, p])           # (ps, KV, hd)
            rows = [dots(qb[kvh], k[:, kvh]) * scale
                    for kvh in range(num_kv)]
            slab = jnp.concatenate(rows, axis=0)           # (H, ps)
            idx = p * page_size + jnp.arange(page_size)[None, :]
            slabs.append(jnp.where(idx < ctx_lens[b], slab, -1e30))
        probs = paged_softmax(jnp.stack(slabs, axis=0))    # (P, H, ps)
        acc = jnp.zeros((h, hd), jnp.float32)
        for p in range(pages_per_seq):
            v = page(v_pages, v_scale, bt[b, p])           # (ps, KV, hd)
            pg = probs[p]
            parts = [jax.lax.dot_general(
                pg[kvh * groups:(kvh + 1) * groups], v[:, kvh],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                for kvh in range(num_kv)]
            acc = acc + jnp.concatenate(parts, axis=0)
        outs.append(acc)
    return jnp.stack(outs, axis=0)


def causal_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         window=None) -> jax.Array:
    """Causal (and ``q - k < window``) grouped-query attention over
    positions ``0..S-1`` in float32, ground truth for the flash kernel.
    q (B, S, H, D) already scaled; k, v (B, S, KV, D). Query head ``h``
    reads KV head ``h // (H / KV)``. Returns float32 (B, S, H, D)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qf = q.astype(jnp.float32).reshape(b, s, kv, h // kv, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32),
                        precision="highest")
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (pos[:, None] - pos[None, :] < window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32),
                     precision="highest")
    return out.reshape(b, s, h, d)


def bipartite_mix_ref(adjacency: jax.Array, values: jax.Array) -> jax.Array:
    """Neighbor aggregation sum_{m in N_n} v_m  =  A @ V.

    adjacency: (N, N); values: (N, d) -> (N, d).
    """
    return adjacency @ values


def edge_gather_mix_ref(values: jax.Array, nbr_table: jax.Array,
                        nbr_valid: jax.Array) -> jax.Array:
    """Sparse neighbor aggregation over a degree-padded CSR table —
    ground truth for the ``edge_gather_mix`` kernel.

    values: (N, d); nbr_table: (N, S) int32 neighbor ids (pad slots
    arbitrary); nbr_valid: (N, S) 1/0 slot validity. Returns (N, d) f32
    neighbor sums: out_n = sum_s valid[n, s] * values[nbr[n, s]].
    """
    rows = values.astype(jnp.float32)[nbr_table]          # (N, S, d)
    return jnp.einsum("nsd,ns->nd", rows,
                      nbr_valid.astype(jnp.float32))


def slstm_cell_ref(wx: jax.Array, r_w: jax.Array, fbias: jax.Array,
                   c0: jax.Array, n0: jax.Array, m0: jax.Array,
                   h0: jax.Array):
    """Sequential sLSTM cell oracle (matches models/xlstm.slstm_apply).

    wx (B,S,H,4dh); r_w (H,dh,4dh); fbias (H,dh); state (B,H,dh) each.
    Returns (hs (B,S,H,dh) f32, (c,n,m,h) final).
    """
    dh = r_w.shape[1]

    def step(carry, xt):
        c, n, m, h = carry
        rec = jnp.einsum("bhk,hkf->bhf", h, r_w)
        pre = xt.astype(jnp.float32) + rec
        i_pre, f_pre, z_pre, o_pre = jnp.split(pre, 4, axis=-1)
        f_pre = f_pre + fbias[None]
        log_f = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(log_f + m, i_pre)
        i_sc = jnp.exp(i_pre - m_new)
        f_sc = jnp.exp(log_f + m - m_new)
        c_new = f_sc * c + i_sc * jnp.tanh(z_pre)
        n_new = jnp.maximum(f_sc * n + i_sc, 1e-6)
        h_new = jax.nn.sigmoid(o_pre) * c_new / n_new
        return (c_new, n_new, m_new, h_new), h_new

    state, hs = jax.lax.scan(step, (c0, n0, m0, h0), wx.swapaxes(0, 1))
    return hs.swapaxes(0, 1), state


def censored_residual_ref(theta_hat: jax.Array, candidate: jax.Array,
                          thresholds: jax.Array) -> jax.Array:
    """(N,) transmit mask: ||candidate - theta_hat||_2 >= tau (per worker)."""
    change = jnp.sqrt(jnp.sum((candidate - theta_hat) ** 2, axis=-1))
    return (change >= thresholds).astype(theta_hat.dtype)
