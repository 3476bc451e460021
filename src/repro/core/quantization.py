"""Stochastic quantization of CQ-GGADMM (paper Sec. 5, Eqs. 14-20).

Each worker n transmits, at iteration k, the quantized *difference* between
its current model theta_n^k and its previously quantized model Q̂_n^{k-1}:

  range    R_n^k   = max_i |[theta_n^k]_i - [Q̂_n^{k-1}]_i|      (covers diff)
  step     Δ_n^k   = 2 R_n^k / (2^{b_n^k} - 1)
  coords   c_i     = (theta_i - Q̂prev_i + R) / Δ                 (Eq. 14)
  rounding q_i     = ceil(c_i) w.p. p_i = c_i - floor(c_i)        (Eq. 15/17)
                     floor(c_i) otherwise                          -> unbiased
  payload  (q, R_n^k, b_n^k)  =  b_n^k * d + b_R + b_b bits
  rebuild  Q̂_n^k  = Q̂_n^{k-1} + Δ_n^k * q - R_n^k * 1           (Eq. 20)

Convergence requires a non-increasing step size Δ_n^k <= ω Δ_n^{k-1}
(ω in (0,1)), enforced by growing the bit width per Eq. (18):

  b_n^k >= ceil( log2( 1 + (2^{b_n^{k-1}} - 1) R_n^k / (ω R_n^{k-1}) ) ).

All state is batched over a leading worker axis so the whole worker set
quantizes in one vectorized call; the elementwise hot loop optionally runs
through the Pallas kernel in ``repro.kernels``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

_EPS = 1e-12


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizerState:
    """Per-worker quantizer state, batched over a leading worker axis.

    Attributes:
      q_hat: (N, d) previously quantized model Q̂_n^{k-1} (receiver replica).
      range_prev: (N,) previous range R_n^{k-1}.
      bits_prev: (N,) previous bit-width b_n^{k-1} (float for jit friendliness).
      delta_prev: (N,) previous step size Δ_n^{k-1}.
      initialized: (N,) 0/1 flag — first iteration uses b0 directly.
    """

    q_hat: jax.Array
    range_prev: jax.Array
    bits_prev: jax.Array
    delta_prev: jax.Array
    initialized: jax.Array

    @staticmethod
    def create(n_workers: int, dim: int, b0: int = 2,
               dtype=jnp.float32) -> "QuantizerState":
        return QuantizerState(
            q_hat=jnp.zeros((n_workers, dim), dtype),
            range_prev=jnp.zeros((n_workers,), dtype),
            bits_prev=jnp.full((n_workers,), float(b0), dtype),
            delta_prev=jnp.zeros((n_workers,), dtype),
            initialized=jnp.zeros((n_workers,), dtype),
        )


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    b0: int = 2            # initial bit width
    omega: float = 0.99    # step-size contraction factor ω in (0,1)
    b_max: int = 16        # cap on per-dimension bit width
    b_overhead: int = 64   # b_R + b_b side-information bits per transmission

    def __post_init__(self):
        assert 0.0 < self.omega < 1.0
        assert 1 <= self.b0 <= self.b_max


def required_bits(bits_prev: jax.Array, range_new: jax.Array,
                  range_prev: jax.Array, omega: float,
                  initialized: jax.Array, b0: int, b_max: int) -> jax.Array:
    """Bit-growth rule of Eq. (18), vectorized over workers.

    First iteration (initialized == 0) uses b0. Degenerate ranges keep the
    previous width.
    """
    levels_prev = jnp.exp2(bits_prev) - 1.0
    ratio = range_new / jnp.maximum(omega * range_prev, _EPS)
    b_new = jnp.ceil(jnp.log2(1.0 + levels_prev * ratio))
    b_new = jnp.where(range_prev <= _EPS, bits_prev, b_new)
    b_new = jnp.where(initialized > 0, b_new, float(b0))
    return jnp.clip(b_new, 1.0, float(b_max))


def bit_schedule(bits_prev: jax.Array, range_new: jax.Array,
                 range_prev: jax.Array, initialized: jax.Array,
                 omega: float, b0: int, b_max: int,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full per-round quantizer schedule: Eq. (18) bit growth plus the step
    size Δ = 2R / (2^b - 1) and the degenerate-range flag, all elementwise
    over any (..., G) shape.

    This is the single source of truth shared by the engine's packed paths,
    the per-leaf reference loop, the jnp oracle
    (``kernels.ref.stoch_quantize_grouped_fused_ref``) and the fused Pallas
    kernel (``kernels.stoch_quant.stoch_quantize_grouped_fused``) — the
    kernel traces this very function inside its body, so the in-kernel
    schedule cannot drift from the host-side one.

    Returns ``(bits, delta, degen)``.
    """
    bits = required_bits(bits_prev, range_new, range_prev, omega,
                         initialized, b0, b_max)
    levels = jnp.exp2(bits) - 1.0
    delta = 2.0 * range_new / jnp.maximum(levels, 1.0)
    degen = range_new <= _EPS
    return bits, delta, degen


def _threefry_key_data(key: jax.Array) -> Optional[jax.Array]:
    """The uint32[2] data of a single threefry key (typed or raw), or None
    when the key draws with another generator."""
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        if str(jax.random.key_impl(key)) != "threefry2x32":
            return None
        return jax.random.key_data(key)
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return None
    return key


def uniform_rows(key: jax.Array, n: int, d: int,
                 rows: np.ndarray) -> jax.Array:
    """Rows ``rows`` of ``jax.random.uniform(key, (n, d), float32)``,
    bit for bit, computing only those rows: ``(len(rows), d)``.

    Partitionable threefry (``jax_threefry_partitionable``, the default)
    draws element ``(r, j)`` of an ``(n, d)`` array from the 64-bit counter
    ``r * d + j`` alone, so each run of consecutive rows is one range of
    counters, evaluated on its own and turned into floats exactly as
    ``jax.random.uniform`` does. Any other generator falls back to the full
    draw and a row slice."""
    rows = np.asarray(rows, np.int64)
    data = _threefry_key_data(key)
    if data is None or not jax.config.jax_threefry_partitionable:
        return jax.random.uniform(key, (n, d), jnp.float32)[rows]
    runs = np.split(rows, np.flatnonzero(np.diff(rows) != 1) + 1)
    parts = [_uniform_counters(data, int(run[0]) * d, run.size * d)
             .reshape(run.size, d) for run in runs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def uniform_columns(key: jax.Array, n: int, d: int, offset: int,
                    shape: Tuple[int, ...], block=None,
                    rows: Optional[np.ndarray] = None) -> jax.Array:
    """Columns ``offset .. offset + prod(shape[1:]) - 1`` of every row of
    ``jax.random.uniform(key, (n, d), float32)``, bit for bit, drawn in
    ``shape`` (``(n,) + leaf shape``, row-major over the leaf): a packed
    leaf's own uniforms, computed without the packed row. With ``block =
    (starts, local_shape)`` only the block of that shape at index
    ``starts`` (one start per axis, axis 0 the rows; traced values allowed)
    is drawn: a device's own part of a leaf split over devices. With
    ``rows`` (static) only those rows are drawn: ``(len(rows),) +
    shape[1:]``.

    Element ``idx`` of row ``r`` takes the counter ``r * d + offset +
    flat(idx)`` (partitionable threefry, as :func:`uniform_rows`), made of
    iotas in the block's shape. Any other generator falls back to the full
    draw and a slice."""
    starts, local = block if block is not None else ((0,) * len(shape),
                                                     tuple(shape))
    if rows is not None:
        rows = np.asarray(rows, np.int64)
        local = (rows.size,) + tuple(shape[1:])
    size = int(np.prod(shape[1:], dtype=np.int64))
    data = _threefry_key_data(key)
    if data is None or not jax.config.jax_threefry_partitionable:
        full = jax.random.uniform(key, (n, d), jnp.float32)
        leaf = full[:, offset:offset + size].reshape(shape)
        if rows is not None:
            return leaf[rows]
        return jax.lax.dynamic_slice(leaf, tuple(starts), tuple(local))
    if size >= 2 ** 32:
        raise ValueError(f"a leaf of {size} elements exceeds one 32-bit "
                         f"counter range")
    flat = jnp.zeros(local, jnp.uint32)
    stride = 1
    for axis in range(len(shape) - 1, 0, -1):
        idx = jax.lax.broadcasted_iota(jnp.uint32, local, axis)
        if block is not None:
            idx = idx + jnp.asarray(starts[axis]).astype(jnp.uint32)
        flat = flat + idx * jnp.uint32(stride)
        stride *= int(shape[axis])
    start = np.arange(n, dtype=np.uint64) * np.uint64(d) + np.uint64(offset)
    if rows is not None:
        start = start[rows]
    lo_rows = jnp.asarray((start & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    hi_rows = jnp.asarray((start >> np.uint64(32)).astype(np.uint32))
    if block is not None:
        lo_rows = jax.lax.dynamic_slice(lo_rows, (starts[0],), (local[0],))
        hi_rows = jax.lax.dynamic_slice(hi_rows, (starts[0],), (local[0],))
    col = (local[0],) + (1,) * (len(local) - 1)
    lo0, hi0 = lo_rows.reshape(col), hi_rows.reshape(col)
    lo = lo0 + flat
    hi = hi0 + (lo < lo0).astype(jnp.uint32)                  # carry
    return _counter_floats(data, hi, lo)


def _counter_floats(data: jax.Array, hi: jax.Array,
                    lo: jax.Array) -> jax.Array:
    """``jax.random.uniform``'s floats at 64-bit counters ``(hi, lo)``."""
    bits1, bits2 = threefry2x32_p.bind(data[0], data[1], hi, lo)
    mant = ((bits1 ^ bits2) >> jnp.uint32(32 - 23)) | jnp.uint32(0x3F800000)
    floats = jax.lax.bitcast_convert_type(mant, jnp.float32) - 1.0
    return jnp.maximum(0.0, floats)


_LANES = 128
_TILE = 8 * _LANES


def _uniform_counters(data: jax.Array, start: int, count: int) -> jax.Array:
    """Uniforms of threefry key ``data`` at the counters ``start ..
    start + count - 1``, flat. They are computed on an ``(m, 128)`` block
    with ``m`` a multiple of 8, whole (8, 128) tiles: a TPU lays out a
    ``(1, d)`` row one sublane in eight, and the draw's arithmetic would
    run at an eighth of the vector unit's width there."""
    m = -(-count // _TILE) * 8
    if m * _LANES >= 2 ** 32:
        raise ValueError(f"{count} counters exceed one 32-bit offset")
    offset = (jax.lax.broadcasted_iota(jnp.uint32, (m, _LANES), 0)
              * jnp.uint32(_LANES)
              + jax.lax.broadcasted_iota(jnp.uint32, (m, _LANES), 1))
    lo0 = jnp.uint32(start & 0xFFFFFFFF)
    lo = lo0 + offset
    hi = jnp.uint32(start >> 32) + (lo < lo0).astype(jnp.uint32)  # carry
    return _counter_floats(data, hi, lo).reshape(-1)[:count]


def stochastic_round(c: jax.Array, uniforms: jax.Array) -> jax.Array:
    """Eq. (15)/(17): round c up with probability frac(c), down otherwise."""
    floor_c = jnp.floor(c)
    p_up = c - floor_c
    return floor_c + (uniforms < p_up).astype(c.dtype)


def quantize_step(
    state: QuantizerState,
    theta: jax.Array,
    key: jax.Array,
    cfg: QuantConfig,
    use_kernel: bool = False,
) -> Tuple[QuantizerState, jax.Array, jax.Array, jax.Array]:
    """One full quantization round for all workers (Eqs. 14-20).

    Args:
      state: quantizer state (leading axis = workers).
      theta: (N, d) current primal variables theta_n^{k}.
      key: PRNG key for the stochastic rounding.
      cfg: quantizer hyperparameters.
      use_kernel: route the elementwise hot loop through the Pallas kernel.

    Returns:
      (new_state, q_hat_new, bits, payload_bits) where q_hat_new is the
      receiver-side reconstruction Q̂_n^k (N, d), bits is (N,) the bit widths
      b_n^k used, payload_bits is (N,) the exact transmission payload size
      b_n^k * d + overhead.
    """
    n, d = theta.shape
    diff = theta - state.q_hat
    range_new = jnp.max(jnp.abs(diff), axis=-1)  # (N,)
    bits = required_bits(state.bits_prev, range_new, state.range_prev,
                         cfg.omega, state.initialized, cfg.b0, cfg.b_max)
    levels = jnp.exp2(bits) - 1.0
    delta = 2.0 * range_new / jnp.maximum(levels, 1.0)      # Δ_n^k
    # Degenerate: nothing to transmit (diff == 0 everywhere) -> Δ=0 handled
    # by keeping q_hat unchanged below.
    uniforms = jax.random.uniform(key, theta.shape, dtype=theta.dtype)

    if use_kernel:
        from repro.kernels import ops as kernel_ops
        q_hat_new = kernel_ops.stoch_quantize(
            theta, state.q_hat, uniforms,
            delta, range_new)
    else:
        safe_delta = jnp.maximum(delta, _EPS)[:, None]
        c = (diff + range_new[:, None]) / safe_delta          # Eq. (14)
        q = stochastic_round(c, uniforms)                     # Eq. (15)
        q = jnp.clip(q, 0.0, levels[:, None])
        q_hat_new = state.q_hat + safe_delta * q - range_new[:, None]  # Eq. (20)
    q_hat_new = jnp.where((range_new <= _EPS)[:, None], state.q_hat, q_hat_new)

    new_state = QuantizerState(
        q_hat=q_hat_new,
        range_prev=jnp.where(range_new <= _EPS, state.range_prev, range_new),
        bits_prev=bits,
        delta_prev=jnp.where(range_new <= _EPS, state.delta_prev, delta),
        initialized=jnp.ones_like(state.initialized),
    )
    payload_bits = bits * float(d) + float(cfg.b_overhead)
    return new_state, q_hat_new, bits, payload_bits


def identity_quantize_step(
    state: QuantizerState, theta: jax.Array, key: jax.Array, cfg: QuantConfig,
) -> Tuple[QuantizerState, jax.Array, jax.Array, jax.Array]:
    """Unquantized pass-through with 32-bit payload accounting (GGADMM).

    The stored replica keeps the state's ``q_hat`` dtype (it may be narrowed
    via ``hat_dtype="bfloat16"``); the full-precision ``theta`` is still
    returned as the candidate, mirroring the engine's grouped version.
    """
    del key
    n, d = theta.shape
    new_state = dataclasses.replace(
        state, q_hat=theta.astype(state.q_hat.dtype),
        initialized=jnp.ones_like(state.initialized))
    bits = jnp.full((n,), 32.0, theta.dtype)
    payload_bits = jnp.full((n,), 32.0 * d, theta.dtype)
    return new_state, theta, bits, payload_bits
