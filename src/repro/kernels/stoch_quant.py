"""Pallas TPU kernel: fused stochastic quantize -> dequantize (Eqs. 14-20).

The elementwise chain

    c = (theta - q_prev + R) / Δ ; q = floor(c) + bernoulli(frac(c));
    q = clip(q, 0, round(2R/Δ)) ; out = q_prev + Δ q - R

is memory-bound (reads 3 arrays, writes 1). On TPU we tile (workers, dim)
into VMEM blocks of (BLOCK_N, BLOCK_D) with BLOCK_D a multiple of the
128-wide lane dimension so the VPU runs full vectors; Δ and R ride along as
(BLOCK_N, 1) columns broadcast across lanes. One pass, no HBM round-trips
between the four stages — on GPU this would be a thread-per-element kernel;
the TPU adaptation is lane-major blocking, not thread mapping.

Uniform draws are produced *outside* the kernel (jax.random) so the kernel
is bit-reproducible against ``ref.stoch_quantize_ref`` on every backend; a
production path could swap them for in-kernel pltpu.prng_random_bits.

Three entry points share the kernel math:

* ``stoch_quantize`` — the seed (N, d) path with per-worker scalar (Δ, R).
* ``stoch_quantize_grouped`` — the packed multi-layer path: (N, G) side
  information plus a static column->group id map, so all leaves of a
  pytree quantize in ONE ``pallas_call`` (see ``core/packing.py``). The
  (N, G) ranges are computed by the caller in a separate pass (the
  "two-pass" path, kept for benchmarks).
* ``stoch_quantize_grouped_fused`` — the two-pass path with the grouped
  range reduction *folded into the kernel*: each grid step holds a full
  (BLOCK_N, D) row block in VMEM, reduces ``max |theta - q_prev|`` per
  group over the static per-group column runs (the transpose-free slice
  trick of ``core/packing.py``), runs the Eq. (18) bit schedule in-kernel
  (tracing ``core.quantization.bit_schedule``, the same function the host
  paths use), then quantizes — one ``pallas_call``, zero separate
  side-information passes over the packed buffer. Outputs the
  reconstruction plus the (N, G) ``(R, b, Δ)`` side info the engine
  carries into the next round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import bit_schedule
from repro.kernels import interpret_mode, worker_block
from repro.kernels.ref import grouped_range_ref, quant_levels

_EPS = 1e-12
# Default VMEM tile: 8 sublanes x 512 lanes (f32: 16 KiB per operand block;
# 4 operand blocks + 1 output block ~ 80 KiB of VMEM, well under ~16 MiB).
BLOCK_N = 8
BLOCK_D = 512


def _quant_kernel(theta_ref, qprev_ref, unif_ref, delta_ref, range_ref,
                  out_ref):
    # math in f32 regardless of storage dtype (bf16 c-coordinates would
    # collapse the fine quantization levels); cast once on the way out.
    theta = theta_ref[...].astype(jnp.float32)
    qprev = qprev_ref[...].astype(jnp.float32)
    unif = unif_ref[...].astype(jnp.float32)
    delta = delta_ref[...].astype(jnp.float32)   # (BLOCK_N, 1)
    rng = range_ref[...].astype(jnp.float32)     # (BLOCK_N, 1)
    safe_delta = jnp.maximum(delta, _EPS)
    c = (theta - qprev + rng) / safe_delta
    floor_c = jnp.floor(c)
    q = floor_c + (unif < (c - floor_c)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, quant_levels(rng, safe_delta))
    out_ref[...] = (qprev + safe_delta * q - rng).astype(out_ref.dtype)


def _grouped_quant_kernel(theta_ref, qprev_ref, unif_ref, delta_ref,
                          range_ref, gid_ref, out_ref):
    """Grouped variant: (Δ, R) arrive as (BLOCK_N, G) side information plus
    a (1, BLOCK_D) column->group id row; each column's scalars are selected
    with an exact 0/1 VPU mask (no gather — Mosaic-friendly, and the select
    is bit-exact so the kernel matches ``ref.stoch_quantize_grouped_ref``
    for identical uniforms). G is static, so the select loop unrolls."""
    theta = theta_ref[...].astype(jnp.float32)
    qprev = qprev_ref[...].astype(jnp.float32)
    unif = unif_ref[...].astype(jnp.float32)
    delta = delta_ref[...].astype(jnp.float32)   # (BLOCK_N, G)
    rng = range_ref[...].astype(jnp.float32)     # (BLOCK_N, G)
    gid = gid_ref[...]                           # (1, BLOCK_D) int32
    n_groups = delta.shape[1]
    # Broadcast group scalars to columns: start from group 0 (also covers
    # the G=1 fast case with zero selects).
    delta_c = jnp.broadcast_to(delta[:, 0:1], theta.shape)
    range_c = jnp.broadcast_to(rng[:, 0:1], theta.shape)
    for g in range(1, n_groups):
        m = gid == g                             # (1, BLOCK_D)
        delta_c = jnp.where(m, delta[:, g:g + 1], delta_c)
        range_c = jnp.where(m, rng[:, g:g + 1], range_c)
    safe_delta = jnp.maximum(delta_c, _EPS)
    c = (theta - qprev + range_c) / safe_delta
    floor_c = jnp.floor(c)
    q = floor_c + (unif < (c - floor_c)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, quant_levels(range_c, safe_delta))
    out_ref[...] = (qprev + safe_delta * q - range_c).astype(out_ref.dtype)


def _broadcast_group_cols(side, gid, shape):
    """(BLOCK_N, G) per-group scalars -> (BLOCK_N, BLOCK_D) columns via the
    (1, BLOCK_D) group-id row: exact 0/1 VPU selects, no gather (the same
    Mosaic-friendly device as ``_grouped_quant_kernel``); the static G loop
    unrolls."""
    out = jnp.broadcast_to(side[:, 0:1], shape)
    for g in range(1, side.shape[1]):
        out = jnp.where(gid == g, side[:, g:g + 1], out)
    return out


def _grouped_fused_kernel(theta_ref, qprev_ref, unif_ref, bprev_ref,
                          rprev_ref, init_ref, gid_ref,
                          out_ref, range_ref, bits_ref, delta_ref,
                          *, group_runs, omega, b0, b_max):
    """Fused range+schedule+quantize body. The block is a full row slab
    (BLOCK_N, D): the per-group range reduces over the *static* contiguous
    column runs of each group (lane-axis max per run, one more max across a
    group's runs — no transpose, no gather, no second pass over HBM), the
    bit-growth schedule runs on the resulting (BLOCK_N, G) panel, and the
    quantize chain reuses the freshly computed per-column scalars while
    theta/q_prev are still resident in VMEM."""
    theta = theta_ref[...].astype(jnp.float32)
    qprev = qprev_ref[...].astype(jnp.float32)
    unif = unif_ref[...].astype(jnp.float32)
    gid = gid_ref[...]                           # (1, BLOCK_D) int32
    # the reduction traces the oracle's own helper (like bit_schedule
    # below), so kernel and oracle cannot drift apart
    range_new = grouped_range_ref(theta - qprev, group_runs)  # (BLOCK_N, G)
    bits, delta, degen = bit_schedule(
        bprev_ref[...].astype(jnp.float32), range_new,
        rprev_ref[...].astype(jnp.float32), init_ref[...].astype(jnp.float32),
        omega, b0, b_max)
    delta_c = _broadcast_group_cols(delta, gid, theta.shape)
    range_c = _broadcast_group_cols(range_new, gid, theta.shape)
    degen_c = _broadcast_group_cols(degen, gid, theta.shape)
    safe_delta = jnp.maximum(delta_c, _EPS)
    c = (theta - qprev + range_c) / safe_delta
    floor_c = jnp.floor(c)
    q = floor_c + (unif < (c - floor_c)).astype(jnp.float32)
    q = jnp.clip(q, 0.0, quant_levels(range_c, safe_delta))
    out = qprev + safe_delta * q - range_c
    # degenerate groups (nothing moved) pass the previous reconstruction
    # through untouched — folded here so the engine never re-reads (N, D)
    out_ref[...] = jnp.where(degen_c, qprev, out).astype(out_ref.dtype)
    range_ref[...] = range_new
    bits_ref[...] = bits
    delta_ref[...] = delta.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("group_runs", "omega", "b0",
                                             "b_max", "block_n", "interpret"))
def stoch_quantize_grouped_fused(
    theta: jax.Array, q_hat_prev: jax.Array, uniforms: jax.Array,
    bits_prev: jax.Array, range_prev: jax.Array, initialized: jax.Array,
    group_ids: jax.Array, *, group_runs, omega: float, b0: int, b_max: int,
    block_n: int = BLOCK_N, interpret: bool | None = None,
):
    """Grouped quantize round with the range reduction folded in: ONE
    ``pallas_call`` reads the packed buffers exactly once and emits both
    the reconstruction and the next round's (N, G) side information. The
    two-pass alternative (``core.packing.segment_maxabs`` +
    :func:`stoch_quantize_grouped`) re-reads the (N, D) buffer for the
    reduction; this entry point exists to delete that pass (DESIGN.md
    §Groups, ROADMAP "fold the grouped range reduction into the quantize
    kernel").

    Args:
      theta, q_hat_prev, uniforms: (N, D) packed buffers.
      bits_prev, range_prev, initialized: (N, G) quantizer-chain state.
      group_ids: (D,) int32 column -> group id map (kernel-side scalar
        broadcast).
      group_runs: static per-group contiguous column runs
        (``Packing.group_runs``) driving the in-kernel reduction.
      omega, b0, b_max: ``QuantConfig`` bit-schedule constants (static).

    Returns:
      ``(out (N, D), range_new (N, G), bits (N, G), delta (N, G))``,
      bit-identical to ``ref.stoch_quantize_grouped_fused_ref`` for
      identical uniforms.

    The row slab must fit VMEM on hardware (BLOCK_N * D * 4 operands);
    interpret mode has no such limit. A D-tiled two-phase grid variant is
    the recorded follow-up for LM-scale widths on real TPU (ROADMAP).
    """
    n, d = theta.shape
    n_groups = bits_prev.shape[1]
    dtype = theta.dtype
    block_n = worker_block(n, block_n)
    n_pad = (-n) % block_n
    d_pad = (-d) % 128                 # lane-align the row slab

    def pad2(x):
        return jnp.pad(x, ((0, n_pad), (0, d_pad)))

    theta_p = pad2(theta)
    qprev_p = pad2(q_hat_prev)
    unif_p = pad2(uniforms)
    # (N, G) state is padded on workers only; padded rows produce clipped
    # junk schedules and are sliced away below. Padded columns carry group
    # 0's id but are outside every static run, so they never touch the
    # reduction; their quantized values are sliced away.
    bprev_p = jnp.pad(bits_prev, ((0, n_pad), (0, 0)))
    rprev_p = jnp.pad(range_prev, ((0, n_pad), (0, 0)))
    init_p = jnp.pad(initialized, ((0, n_pad), (0, 0)))
    gid_p = jnp.pad(group_ids.astype(jnp.int32), (0, d_pad))[None, :]
    np_, dp_ = theta_p.shape

    grid = (np_ // block_n,)
    mat_spec = pl.BlockSpec((block_n, dp_), lambda i: (i, 0))
    side_spec = pl.BlockSpec((block_n, n_groups), lambda i: (i, 0))
    gid_spec = pl.BlockSpec((1, dp_), lambda i: (0, 0))
    kernel = functools.partial(_grouped_fused_kernel, group_runs=group_runs,
                               omega=omega, b0=b0, b_max=b_max)
    out, range_new, bits, delta = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat_spec, mat_spec, mat_spec, side_spec, side_spec,
                  side_spec, gid_spec],
        out_specs=(mat_spec, side_spec, side_spec, side_spec),
        out_shape=(jax.ShapeDtypeStruct((np_, dp_), dtype),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(theta_p, qprev_p, unif_p, bprev_p, rprev_p, init_p, gid_p)
    return (out[:n, :d], range_new[:n], bits[:n], delta[:n])


def _grouped_fused_tiled_kernel(theta_ref, qprev_ref, unif_ref, bprev_ref,
                                rprev_ref, init_ref, gid_ref,
                                out_ref, range_ref, bits_ref, delta_ref,
                                racc_ref, dacc_ref, gacc_ref,
                                *, n_groups, omega, b0, b_max):
    """Two-phase D-tiled fused body. The single-slab kernel above holds a
    full (BLOCK_N, D) row slab in VMEM — fine in interpret mode, impossible
    at LM-scale widths on hardware (ROADMAP). Here the grid's middle
    dimension is a phase sweep over (BLOCK_N, BLOCK_D) tiles:

      phase 0  per-tile per-group ``max |theta - q_prev|`` accumulates
               into a (BLOCK_N, G) VMEM scratch — group membership comes
               from the tile's gid row (exact 0/1 masks; max is
               order-insensitive, so the result is bit-identical to the
               slab reduction over static column runs);
      phase 1  at its first step the Eq. (18) bit schedule runs ONCE on
               the accumulated panel (side outputs written, (Δ, degen)
               parked in scratch), then every step quantizes its tile with
               the scratch scalars while re-streaming theta/q_prev.

    Two reads of the (N, D) buffers instead of one — the price of bounded
    VMEM — but still zero separate host-side passes and one pallas_call.
    """
    ph = pl.program_id(1)
    j = pl.program_id(2)
    gid = gid_ref[...]                           # (1, BLOCK_D) of this tile
    theta = theta_ref[...].astype(jnp.float32)
    qprev = qprev_ref[...].astype(jnp.float32)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        racc_ref[...] = jnp.zeros_like(racc_ref)

    @pl.when(ph == 0)
    def _reduce():
        diff = jnp.abs(theta - qprev)
        cols = []
        for g in range(n_groups):
            cols.append(jnp.max(jnp.where(gid == g, diff, 0.0), axis=1))
        racc_ref[...] = jnp.maximum(racc_ref[...],
                                    jnp.stack(cols, axis=1))

    @pl.when((ph == 1) & (j == 0))
    def _schedule():
        bits, delta, degen = bit_schedule(
            bprev_ref[...].astype(jnp.float32), racc_ref[...],
            rprev_ref[...].astype(jnp.float32),
            init_ref[...].astype(jnp.float32), omega, b0, b_max)
        range_ref[...] = racc_ref[...]
        bits_ref[...] = bits
        delta_ref[...] = delta.astype(jnp.float32)
        dacc_ref[...] = delta.astype(jnp.float32)
        gacc_ref[...] = degen.astype(jnp.float32)

    @pl.when(ph == 1)
    def _quantize():
        unif = unif_ref[...].astype(jnp.float32)
        delta_c = _broadcast_group_cols(dacc_ref[...], gid, theta.shape)
        range_c = _broadcast_group_cols(racc_ref[...], gid, theta.shape)
        degen_c = _broadcast_group_cols(gacc_ref[...], gid, theta.shape)
        safe_delta = jnp.maximum(delta_c, _EPS)
        c = (theta - qprev + range_c) / safe_delta
        floor_c = jnp.floor(c)
        q = floor_c + (unif < (c - floor_c)).astype(jnp.float32)
        q = jnp.clip(q, 0.0, quant_levels(range_c, safe_delta))
        out = qprev + safe_delta * q - range_c
        out_ref[...] = jnp.where(degen_c > 0.0, qprev,
                                 out).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_runs", "omega", "b0",
                                             "b_max", "block_n", "block_d",
                                             "interpret"))
def stoch_quantize_grouped_fused_tiled(
    theta: jax.Array, q_hat_prev: jax.Array, uniforms: jax.Array,
    bits_prev: jax.Array, range_prev: jax.Array, initialized: jax.Array,
    group_ids: jax.Array, *, group_runs=None, omega: float, b0: int,
    b_max: int, block_n: int = BLOCK_N, block_d: int = BLOCK_D,
    interpret: bool | None = None,
):
    """D-tiled two-phase variant of :func:`stoch_quantize_grouped_fused`
    for LM-scale widths: VMEM residency is O(BLOCK_N * BLOCK_D) instead of
    O(BLOCK_N * D), at the cost of streaming theta/q_prev twice (the
    two-phase grid). Same signature (``group_runs`` accepted and ignored —
    the tiled reduction masks on the gid row instead of static runs) and
    bit-identical outputs: max-reductions are order-insensitive, the
    schedule runs on an equal panel, and the quantize chain applies the
    same per-column scalars."""
    n, d = theta.shape
    n_groups = bits_prev.shape[1]
    dtype = theta.dtype
    block_n = worker_block(n, block_n)
    n_pad = (-n) % block_n
    d_pad = (-d) % block_d

    def pad2(x):
        return jnp.pad(x, ((0, n_pad), (0, d_pad)))

    theta_p = pad2(theta)
    qprev_p = pad2(q_hat_prev)
    unif_p = pad2(uniforms)
    bprev_p = jnp.pad(bits_prev, ((0, n_pad), (0, 0)))
    rprev_p = jnp.pad(range_prev, ((0, n_pad), (0, 0)))
    init_p = jnp.pad(initialized, ((0, n_pad), (0, 0)))
    # padded columns carry group 0's id but theta == q_prev == 0 there, so
    # their |diff| contributes 0 to a max over non-negative values
    gid_p = jnp.pad(group_ids.astype(jnp.int32), (0, d_pad))[None, :]
    np_, dp_ = theta_p.shape

    grid = (np_ // block_n, 2, dp_ // block_d)
    mat_spec = pl.BlockSpec((block_n, block_d), lambda i, ph, j: (i, j))
    side_spec = pl.BlockSpec((block_n, n_groups), lambda i, ph, j: (i, 0))
    gid_spec = pl.BlockSpec((1, block_d), lambda i, ph, j: (0, j))
    kernel = functools.partial(_grouped_fused_tiled_kernel,
                               n_groups=n_groups, omega=omega, b0=b0,
                               b_max=b_max)
    out, range_new, bits, delta = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat_spec, mat_spec, mat_spec, side_spec, side_spec,
                  side_spec, gid_spec],
        out_specs=(mat_spec, side_spec, side_spec, side_spec),
        out_shape=(jax.ShapeDtypeStruct((np_, dp_), dtype),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32),
                   jax.ShapeDtypeStruct((np_, n_groups), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((block_n, n_groups), jnp.float32),
            pltpu.VMEM((block_n, n_groups), jnp.float32),
            pltpu.VMEM((block_n, n_groups), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(theta_p, qprev_p, unif_p, bprev_p, rprev_p, init_p, gid_p)
    return (out[:n, :d], range_new[:n], bits[:n], delta[:n])


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def stoch_quantize_grouped(theta: jax.Array, q_hat_prev: jax.Array,
                           uniforms: jax.Array, delta: jax.Array,
                           qrange: jax.Array, group_ids: jax.Array,
                           *, block_n: int = BLOCK_N, block_d: int = BLOCK_D,
                           interpret: bool | None = None) -> jax.Array:
    """Fused grouped quantize+reconstruct: ONE ``pallas_call`` for a whole
    packed multi-leaf buffer (the per-leaf loop this replaces paid one
    kernel launch per layer).

    Args:
      theta, q_hat_prev, uniforms: (N, D) packed buffers.
      delta, qrange: (N, G) per-worker per-group step size / range — the
        full G columns ride along with every block (G is small: one entry
        per layer group, not per column).
      group_ids: (D,) int32 column -> group id map (static layout from
        ``core.packing``).
      interpret: interpreter mode; ``None`` decides by platform
        (``kernels.interpret_mode``).

    Returns:
      (N, D) reconstruction Q̂^k, bit-identical to
      ``ref.stoch_quantize_grouped_ref`` for identical uniforms.
    """
    n, d = theta.shape
    n_groups = delta.shape[1]
    dtype = theta.dtype
    block_n = worker_block(n, block_n)
    n_pad = (-n) % block_n
    d_pad = (-d) % block_d

    def pad2(x):
        return jnp.pad(x, ((0, n_pad), (0, d_pad)))

    theta_p = pad2(theta)
    qprev_p = pad2(q_hat_prev)
    unif_p = pad2(uniforms)
    # (N, G) side info is padded on workers only; padded columns read group
    # 0's scalars and are sliced away below.
    delta_p = jnp.pad(delta, ((0, n_pad), (0, 0)))
    range_p = jnp.pad(qrange, ((0, n_pad), (0, 0)))
    gid_p = jnp.pad(group_ids.astype(jnp.int32), (0, d_pad))[None, :]
    np_, dp_ = theta_p.shape

    grid = (np_ // block_n, dp_ // block_d)
    mat_spec = pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))
    side_spec = pl.BlockSpec((block_n, n_groups), lambda i, j: (i, 0))
    gid_spec = pl.BlockSpec((1, block_d), lambda i, j: (0, j))
    out = pl.pallas_call(
        _grouped_quant_kernel,
        grid=grid,
        in_specs=[mat_spec, mat_spec, mat_spec, side_spec, side_spec,
                  gid_spec],
        out_specs=mat_spec,
        out_shape=jax.ShapeDtypeStruct((np_, dp_), dtype),
        interpret=interpret_mode(interpret),
    )(theta_p, qprev_p, unif_p, delta_p, range_p, gid_p)
    return out[:n, :d]


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def stoch_quantize(theta: jax.Array, q_hat_prev: jax.Array,
                   uniforms: jax.Array, delta: jax.Array, qrange: jax.Array,
                   *, block_n: int = BLOCK_N, block_d: int = BLOCK_D,
                   interpret: bool | None = None) -> jax.Array:
    """Fused quantize+reconstruct for stacked workers.

    Args:
      theta, q_hat_prev, uniforms: (N, d).
      delta, qrange: (N,) per-worker step size / range.
      interpret: interpreter mode; ``None`` decides by platform
        (``kernels.interpret_mode``).

    Returns:
      (N, d) reconstruction Q̂^k.
    """
    n, d = theta.shape
    dtype = theta.dtype
    block_n = worker_block(n, block_n)
    n_pad = (-n) % block_n
    d_pad = (-d) % block_d

    def pad2(x):
        return jnp.pad(x, ((0, n_pad), (0, d_pad)))

    theta_p = pad2(theta)
    qprev_p = pad2(q_hat_prev)
    unif_p = pad2(uniforms)
    # delta/range keep their own (usually f32) dtype — the kernel upcasts
    # everything to f32 internally, so narrowing here would lose levels.
    delta_p = jnp.pad(delta, (0, n_pad))[:, None]
    range_p = jnp.pad(qrange, (0, n_pad))[:, None]
    np_, dp_ = theta_p.shape

    grid = (np_ // block_n, dp_ // block_d)
    mat_spec = pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))
    col_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[mat_spec, mat_spec, mat_spec, col_spec, col_spec],
        out_specs=mat_spec,
        out_shape=jax.ShapeDtypeStruct((np_, dp_), dtype),
        interpret=interpret_mode(interpret),
    )(theta_p, qprev_p, unif_p, delta_p, range_p)
    return out[:n, :d]
