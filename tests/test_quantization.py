"""Stochastic quantizer properties (paper Sec. 5, Eqs. 14-20 + (32))."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # offline: property tests skip, rest runs
    from _hypothesis_stub import given, settings, st

from repro.core.quantization import (QuantConfig, QuantizerState,
                                     identity_quantize_step, quantize_step,
                                     required_bits, stochastic_round,
                                     uniform_rows)


def _state(n, d, b0=2):
    return QuantizerState.create(n, d, b0=b0)


def test_stochastic_round_unbiased():
    c = jnp.full((20_000,), 3.3)
    u = jax.random.uniform(jax.random.PRNGKey(0), c.shape)
    q = stochastic_round(c, u)
    assert set(np.unique(np.asarray(q))) <= {3.0, 4.0}
    np.testing.assert_allclose(float(q.mean()), 3.3, atol=0.01)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 6), d=st.integers(1, 64), b0=st.integers(1, 8),
       seed=st.integers(0, 1000))
def test_error_bounded_by_step(n, d, b0, seed):
    """|Q̂ - theta| <= Δ per element => ||e||^2 <= d Δ^2 (paper Eq. 32)."""
    key = jax.random.PRNGKey(seed)
    theta = 10.0 * jax.random.normal(key, (n, d))
    state = _state(n, d, b0)
    cfg = QuantConfig(b0=b0, omega=0.99)
    new_state, q_hat, bits, payload = quantize_step(state, theta,
                                                    jax.random.fold_in(
                                                        key, 1), cfg)
    delta = np.asarray(new_state.delta_prev)
    err = np.abs(np.asarray(theta - q_hat))
    assert (err <= delta[:, None] + 1e-4 * np.abs(np.asarray(theta)).max()
            ).all()


def test_quantization_unbiased_in_expectation():
    n, d = 1, 8
    theta = jnp.asarray([[0.13, -0.7, 2.4, -3.3, 0.0, 1.01, -0.49, 5.0]])
    state = _state(n, d, b0=2)
    cfg = QuantConfig(b0=2, omega=0.99)
    reps = 3000
    acc = np.zeros((n, d))
    for i in range(reps):
        _, q_hat, _, _ = quantize_step(state, theta,
                                       jax.random.PRNGKey(i), cfg)
        acc += np.asarray(q_hat)
    mean_err = acc / reps - np.asarray(theta)
    # E[e] = 0 (Eq. 16/17); tolerance ~ Delta/sqrt(reps)
    delta = 2 * 5.0 / (2 ** 2 - 1)
    assert np.abs(mean_err).max() < 4 * delta / np.sqrt(reps) + 1e-3


def test_bit_growth_enforces_shrinking_step():
    """Δ_k <= ω Δ_{k-1} whenever a transmission happens (Eq. 18)."""
    key = jax.random.PRNGKey(0)
    n, d = 4, 32
    cfg = QuantConfig(b0=2, omega=0.9, b_max=16)
    state = _state(n, d, cfg.b0)
    theta = jax.random.normal(key, (n, d))
    deltas = []
    for k in range(12):
        theta = theta + 0.5 * jax.random.normal(jax.random.fold_in(key, k),
                                                (n, d))
        state, _, bits, _ = quantize_step(state, theta,
                                          jax.random.fold_in(key, 100 + k),
                                          cfg)
        deltas.append(np.asarray(state.delta_prev).copy())
    for k in range(1, len(deltas)):
        capped = np.asarray(
            jnp.exp2(jnp.asarray(float(cfg.b_max)))) - 1  # b_max saturation
        ok = (deltas[k] <= cfg.omega * deltas[k - 1] + 1e-7)
        # once bits saturate at b_max the contraction can no longer hold
        saturated = deltas[k] > 0
        bits_at_cap = np.asarray(state.bits_prev) >= cfg.b_max
        assert (ok | bits_at_cap).all()


def test_required_bits_first_iteration_uses_b0():
    bits = required_bits(jnp.asarray([7.0]), jnp.asarray([3.0]),
                         jnp.asarray([1.0]), 0.9, jnp.asarray([0.0]),
                         b0=2, b_max=16)
    assert float(bits[0]) == 2.0


def test_payload_accounting():
    n, d = 3, 50
    cfg = QuantConfig(b0=4, omega=0.99, b_overhead=64)
    state = _state(n, d, cfg.b0)
    theta = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    _, _, bits, payload = quantize_step(state, theta, jax.random.PRNGKey(1),
                                        cfg)
    np.testing.assert_allclose(np.asarray(payload),
                               np.asarray(bits) * d + 64)
    assert (np.asarray(payload) < 32 * d).all()   # beats full precision


def test_identity_step_respects_replica_dtype():
    """identity_quantize_step must narrow the stored replica to the state's
    q_hat dtype (hat_dtype="bfloat16" path) while the candidate keeps full
    precision — same contract as the engine's grouped version."""
    n, d = 3, 8
    state = dataclasses.replace(
        _state(n, d), q_hat=jnp.zeros((n, d), jnp.bfloat16))
    theta = jax.random.normal(jax.random.PRNGKey(0), (n, d))  # f32
    new_state, candidate, bits, payload = identity_quantize_step(
        state, theta, jax.random.PRNGKey(1), QuantConfig())
    assert new_state.q_hat.dtype == jnp.bfloat16
    assert candidate.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(new_state.q_hat),
                                  np.asarray(theta.astype(jnp.bfloat16)))
    np.testing.assert_array_equal(np.asarray(candidate), np.asarray(theta))
    assert (np.asarray(payload) == 32.0 * d).all()


def test_degenerate_zero_diff_keeps_state():
    n, d = 2, 16
    cfg = QuantConfig(b0=3)
    state = _state(n, d, cfg.b0)
    theta = jnp.zeros((n, d))
    new_state, q_hat, _, _ = quantize_step(state, theta,
                                           jax.random.PRNGKey(0), cfg)
    np.testing.assert_array_equal(np.asarray(q_hat), 0.0)
    np.testing.assert_array_equal(np.asarray(new_state.range_prev),
                                  np.asarray(state.range_prev))


# Rows of the stochastic rounding's draw (the engine's acting-rows path):
# (id, worker-leading shape, rows, key, partitionable threefry).
_RAW = jax.random.PRNGKey(11)
ROW_DRAWS = [
    ("odd-width", (3, 1001), [1], _RAW, True),
    ("even-width-contiguous", (4, 64), [1, 2], _RAW, True),
    ("non-contiguous", (5, 37), [0, 2, 4], _RAW, True),
    ("single-leaf", (6, 12, 12), [3], _RAW, True),
    # the packed width of {"w": (6, 12, 12), "b": (6, 256)}
    ("packed-multi-leaf", (6, 12 * 12 + 256), [0, 3, 5], _RAW, True),
    ("typed-threefry-key", (4, 33), [2, 3], jax.random.key(5), True),
    ("fallback-rbg-key", (4, 33), [0, 2], jax.random.key(5, impl="rbg"),
     True),
    ("fallback-not-partitionable", (4, 33), [1, 3], _RAW, False),
]


@pytest.mark.parametrize("shape,rows,key,partitionable",
                         [c[1:] for c in ROW_DRAWS],
                         ids=[c[0] for c in ROW_DRAWS])
def test_row_draw_equals_rows_of_full_draw(shape, rows, key, partitionable):
    """The engine's row draw is, bit for bit, those rows of the draw over
    all workers — jitted, as the engine runs it."""
    from repro.core import engine as E
    rows = np.asarray(rows)
    with jax.threefry_partitionable(partitionable):
        got = jax.jit(lambda k: E._uniforms(
            k, (rows.size,) + shape[1:], rows, shape[0]))(key)
        want = jax.random.uniform(key, shape, jnp.float32)[rows]
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("row,d", [(4165826, 1031), (4194304, 1031)],
                         ids=["carry-inside-row", "high-word-set"])
def test_row_draw_counters_cross_32_bits(row, d):
    """Where ``row * d + j`` reaches 2**32 the counter's high word is set,
    as partitionable threefry's 64-bit iota sets it (first row: the carry
    happens inside the row)."""
    from jax.extend.random import threefry2x32_p
    counters = np.arange(row * d, row * d + d, dtype=np.uint64)
    assert counters[-1] >= 2 ** 32
    key = jax.random.PRNGKey(3)
    b1, b2 = threefry2x32_p.bind(
        key[0], key[1], jnp.asarray((counters >> 32).astype(np.uint32)),
        jnp.asarray((counters & 0xFFFFFFFF).astype(np.uint32)))
    mant = ((np.asarray(b1) ^ np.asarray(b2)) >> 9) | 0x3F800000
    want = mant.astype(np.uint32).view(np.float32) - np.float32(1.0)
    got = uniform_rows(key, row + 1, d, np.array([row]))[0]
    np.testing.assert_array_equal(np.asarray(got), want)
