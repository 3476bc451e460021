"""Jit'd public wrappers for the Pallas kernels.

Backend dispatch: every kernel entry point takes ``interpret=None``, which
``kernels.interpret_mode`` resolves from the platform — compiled by Mosaic
on a TPU, interpreted everywhere else. Interpret mode runs the kernel body
through XLA on the CPU with the same semantics, so the oracle tests in
``tests/test_kernels.py`` check the program logic; what only the TPU
compiler checks (tiling, VMEM budget) is covered by the compiles against a
described v5e in ``tests/test_chip_compile.py``.

Every wrapper bumps the obs-layer ``kernel_dispatch`` counter with the
variant it selected. The bump happens in the Python wrapper — i.e. at
trace time, once per compilation-triggering call shape, never inside the
compiled program — so tests can assert which kernel actually ran without
parsing jaxprs, and the counter provably adds zero ops to any program
(jaxpr pin in ``tests/test_obs.py``).
"""
from __future__ import annotations

import jax

from repro.kernels import bipartite_mix as _mix
from repro.kernels import stoch_quant as _quant
from repro.kernels import worker_block
from repro.obs.metrics import kernel_dispatch_counter


def _count(kernel: str, variant: str) -> None:
    kernel_dispatch_counter().inc(kernel=kernel, variant=variant)


def stoch_quantize(theta: jax.Array, q_hat_prev: jax.Array,
                   uniforms: jax.Array, delta: jax.Array,
                   qrange: jax.Array) -> jax.Array:
    _count("stoch_quantize", "flat")
    return _quant.stoch_quantize(theta, q_hat_prev, uniforms, delta, qrange)


def stoch_quantize_grouped(theta: jax.Array, q_hat_prev: jax.Array,
                           uniforms: jax.Array, delta: jax.Array,
                           qrange: jax.Array,
                           group_ids: jax.Array) -> jax.Array:
    _count("stoch_quantize", "grouped")
    return _quant.stoch_quantize_grouped(theta, q_hat_prev, uniforms, delta,
                                         qrange, group_ids)


# The single-slab fused kernel keeps one (rows, D) block of each of its
# three (N, D) inputs and of its output in VMEM, plus the f32 temporaries of
# the quantize chain. Compiled for a v5e, a 1 MiB slab still fits and 2 MiB
# (2 rows) or 4 MiB (8 rows) run out of VMEM; past this budget the D-tiled
# variant, whose VMEM use does not grow with D, takes over.
FUSED_SLAB_BYTES = 512 * 1024


def fused_slab_bytes(n: int, d: int) -> int:
    """VMEM bytes of one f32 operand slab of the single-slab fused kernel
    at an (n, d) packed buffer (rows per block, lane-padded width)."""
    return worker_block(n, _quant.BLOCK_N) * (d + (-d) % 128) * 4


def stoch_quantize_grouped_fused(theta: jax.Array, q_hat_prev: jax.Array,
                                 uniforms: jax.Array, bits_prev: jax.Array,
                                 range_prev: jax.Array,
                                 initialized: jax.Array,
                                 group_ids: jax.Array, *, group_runs,
                                 omega: float, b0: int, b_max: int):
    """Grouped quantize round with the (N, G) range reduction folded into
    the same ``pallas_call`` (no separate side-information pass).

    Selection by shape: the single-slab kernel (one read of the buffers)
    while its slab fits ``FUSED_SLAB_BYTES``, the D-tiled two-phase kernel
    (two reads, bounded VMEM) beyond. Both are bit-identical."""
    if fused_slab_bytes(*theta.shape) > FUSED_SLAB_BYTES:
        return stoch_quantize_grouped_fused_tiled(
            theta, q_hat_prev, uniforms, bits_prev, range_prev, initialized,
            group_ids, omega=omega, b0=b0, b_max=b_max)
    _count("stoch_quantize_fused", "slab")
    return _quant.stoch_quantize_grouped_fused(
        theta, q_hat_prev, uniforms, bits_prev, range_prev, initialized,
        group_ids, group_runs=group_runs, omega=omega, b0=b0, b_max=b_max)


def stoch_quantize_grouped_fused_tiled(theta, q_hat_prev, uniforms,
                                       bits_prev, range_prev, initialized,
                                       group_ids, *, omega: float, b0: int,
                                       b_max: int, block_d: int = 512):
    """Explicit entry to the D-tiled two-phase fused round."""
    _count("stoch_quantize_fused", "tiled")
    return _quant.stoch_quantize_grouped_fused_tiled(
        theta, q_hat_prev, uniforms, bits_prev, range_prev, initialized,
        group_ids, omega=omega, b0=b0, b_max=b_max, block_d=block_d)


def bipartite_mix(adjacency: jax.Array, values: jax.Array) -> jax.Array:
    _count("bipartite_mix", "dense")
    return _mix.bipartite_mix(adjacency, values)


def edge_gather_mix(values: jax.Array, nbr_table: jax.Array,
                    nbr_valid: jax.Array) -> jax.Array:
    from repro.kernels import edge_gather_mix as _edge
    _count("edge_gather_mix", "sparse")
    return _edge.edge_gather_mix(values, nbr_table, nbr_valid)


# One-shot softmax keeps a (H, pages_per_seq*page_size) f32 logits slab
# resident in VMEM; past this footprint the online-softmax variant (one
# (H, ps) page slab + fixed carries) takes over. 512 KB leaves the
# short-context default comfortably inside VMEM next to the K/V page
# blocks while switching long before the ~16 MB/core ceiling.
ONESHOT_SLAB_BYTES = 512 * 1024


def paged_attention_decode(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           k_scale=None, v_scale=None, kv_bits: int = 32):
    """Paged-attention decode with kernel selection.

    Public contract (callers pass block tables as-is): unmapped (-1) and
    out-of-range physical page ids are clamped into the pool HERE — their
    logits are masked by ``ctx_lens``, so a poisoned table slot is
    harmless through this entry point (pinned by
    ``test_ops_paged_attention_clamps_poisoned_tables``).

    Selection: the one-shot kernel (bit-exact vs ``ref.paged_attention_ref``)
    runs while its (H, P*ps) f32 logits slab fits ``ONESHOT_SLAB_BYTES``;
    beyond that the online-softmax variant bounds VMEM to one page slab.
    ``REPRO_PAGED_ATTN_ONLINE=1|0`` forces the choice either way.

    ``kv_bits`` in (8, 4) reads ``ref.kv_page_quantize`` code pools with
    ``k_scale``/``v_scale`` side info, dequantized inside the kernel.
    """
    import os

    import jax.numpy as jnp

    from repro.kernels import paged_attention as _paged
    num_pages = k_pages.shape[0]
    page_size = k_pages.shape[1]
    h = q.shape[1]
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, num_pages - 1)
    force = os.environ.get("REPRO_PAGED_ATTN_ONLINE", "")
    if force in ("0", "1"):
        online = force == "1"
    else:
        slab_bytes = h * block_tables.shape[1] * page_size * 4
        online = slab_bytes > ONESHOT_SLAB_BYTES
    _count("paged_attention_decode", "online" if online else "oneshot")
    fn = (_paged.paged_attention_decode_online if online
          else _paged.paged_attention_decode)
    return fn(q, k_pages, v_pages, bt, ctx_lens, k_scale=k_scale,
              v_scale=v_scale, kv_bits=kv_bits)


def flash_attention(q, k, v, *, window=None):
    """Causal (sliding-window where ``window`` is set) flash attention over
    canonical positions, forward and backward (``kernels/flash_attention``;
    oracle ``ref.causal_attention_ref``). ``q`` carries the ``1/sqrt(D)``
    scale."""
    from repro.kernels import flash_attention as _flash
    _count("flash_attention", "window" if window is not None else "causal")
    return _flash.causal_attention(q, k, v, window=window)


def slstm_cell(wx, r_w, fbias, c0, n0, m0, h0):
    from repro.kernels import slstm_cell as _cell
    _count("slstm_cell", "fused")
    return _cell.slstm_cell(wx, r_w, fbias, c0, n0, m0, h0)
