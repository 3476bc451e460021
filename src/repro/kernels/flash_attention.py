"""Causal, optionally sliding-window, flash attention for training-length
self-attention: the splash attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``), forward and
backward.

Scores, their running max and sum stay in VMEM (online softmax); only the
output and the per-row log-sum-exp reach HBM, and the backward pass
recomputes the scores block by block from them, in two kernels: one
accumulates dK and dV over query blocks, the other dQ over key blocks, each
in a float32 scratch rounded once to the operands' dtype. (The splash
kernel's fused backward would instead write one rounded partial of dQ per
key block and sum them.) A key block is visited only
where the mask lets some query of the query block see it: the kernel's
block map (``MaskInfo.block_mask``) is built from the layer's own mask,
``CausalMask`` or a ``LocalMask`` of ``(window - 1, 0)``, the mask
``models/layers._attn_mask`` builds from canonical positions ``0..s-1``.

Grouped-query attention as the model has it: query head ``h`` reads KV head
``h // (H / KV)`` (the kernel's own index map), with no copy of K/V per
query head. Blocks take the whole head dimension. Operands keep their dtype
(bf16 on the training path); scores, softmax statistics and accumulation are
float32. The ``1/sqrt(head_dim)`` scale is the caller's: ``q`` arrives
scaled (``models/layers.attention_apply`` folds it into RoPE's float32
arithmetic).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro import kernels

# Query and key block sizes tried, largest first: the largest that divides
# the sequence is taken by all three kernels, and scores are computed 512
# keys at a time inside a key block. Timed on one v5e chip at the danube
# cell's share (16 query and 4 KV heads of 80, 8192 positions, window 4096),
# forward plus backward, as (query, key, keys scored at a time) blocks of
# the forward and dK/dV kernels and (query, key) of the dQ kernel:
# (1024, 1024, 512) / (1024, 1024) 7.36 ms; (512, 1024, 512) with dQ
# (1024, 1024) 7.60, (512, 1024) 7.76, (512, 512) 7.76, (256, 1024) 8.05,
# (512, 2048) 8.02; all at 128 37.2. (The fused backward took 6.49-6.73 ms
# at (512, 1024, 512).) Head dims past 128 take key blocks of at most 512
# so the K/V tiles stay as large.
_BLOCK_Q = (1024, 512, 256, 128)
_BLOCK_KV = (1024, 512, 256, 128)
_KV_COMPUTE = 512


def _largest_dividing(s: int, sizes) -> Optional[int]:
    return next((b for b in sizes if s % b == 0), None)


def splash_blocks(bq: int, bkv: int) -> splash.BlockSizes:
    """``bq``-query by ``bkv``-key blocks for the forward, dK/dV and dQ
    kernels, scores computed at most ``_KV_COMPUTE`` keys at a time; dQ in
    its own kernel (module docstring)."""
    compute = min(bkv, _KV_COMPUTE)
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=compute,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=compute,
        block_q_dq=bq, block_kv_dq=bkv, use_fused_bwd_kernel=False)


def block_sizes(s: int, head_dim: int) -> Optional[splash.BlockSizes]:
    """The kernel's block sizes at sequence ``s`` and ``head_dim``, or
    ``None`` where no block of at least 128 (a lane tile) divides ``s``."""
    bq = _largest_dividing(s, _BLOCK_Q)
    kv_sizes = _BLOCK_KV if head_dim <= 128 else _BLOCK_KV[1:]
    bkv = _largest_dividing(s, kv_sizes)
    if bq is None or bkv is None:
        return None
    return splash_blocks(bq, bkv)


def _mask(s: int, window: Optional[int]):
    """The layer's mask over ``(s, s)`` canonical positions: causal, and
    ``q - k < window`` where there is a window."""
    if window is None or window >= s:
        return splash.CausalMask((s, s))
    return splash.LocalMask((s, s), (window - 1, 0), offset=0)


@functools.lru_cache(maxsize=64)
def make_kernel(s: int, heads: int, window: Optional[int],
                blocks: splash.BlockSizes, interpret: bool):
    """The splash kernel for ``heads`` query heads of one sequence. Its
    block maps are built once per shape, as concrete arrays even when the
    first call comes inside a trace, so the cache holds no tracer."""
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash.MultiHeadMask([_mask(s, window)] * heads),
            block_sizes=blocks, head_shards=1, q_seq_shards=1,
            interpret=interpret)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """``softmax(q k^T + mask) v`` over canonical positions.

    q (B, S, H, D), already carrying the ``1/sqrt(D)`` scale; k, v
    (B, S, KV, D) with KV dividing H. Returns (B, S, H, D) in ``q``'s
    dtype. ``S`` must be a multiple of a block (``block_sizes``)."""
    b, s, h, d = q.shape
    blocks = block_sizes(s, d)
    if blocks is None:
        raise ValueError(f"no flash block divides sequence {s}")
    kernel = make_kernel(s, h, window, blocks,
                         kernels.interpret_mode(interpret))
    return apply_kernel(kernel, q, k, v)


def apply_kernel(kernel, q: jax.Array, k: jax.Array,
                 v: jax.Array) -> jax.Array:
    """A ``make_kernel`` kernel over (B, S, heads, D) operands."""
    out = jax.vmap(kernel)(q.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
