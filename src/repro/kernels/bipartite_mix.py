"""Pallas TPU kernel: bipartite neighbor aggregation  A @ V  on the MXU.

The GGADMM neighbor sum sum_{m in N_n} v_m is a (N, N) x (N, d) matmul with
a 0/1 bipartite adjacency. We tile it as a classic MXU matmul: grid
(i, j, k) over (M/bm, d/bn, N/bk); the (bm, bn) output block accumulates
A[i,k] @ V[k,j] partial products in VMEM across the k (arbitrary/sequential)
grid dimension. Block edges are MXU-aligned (multiples of 128 in the lane
dim); f32 accumulation.

For the paper-scale problems (N <= 64) this is a single block; the kernel
matters for pytree-consensus training where V is (N_workers, flat_params)
with flat_params in the billions — there the d-axis tiling is what keeps
the working set in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, worker_block

BLOCK_M = 8
BLOCK_N = 512
BLOCK_K = 128


def _mix_kernel(a_ref, v_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # full f32 precision, as the jnp mix (``core.topology``): a 0/1
    # adjacency then sums the exchanged models exactly
    out_ref[...] += jnp.dot(a_ref[...], v_ref[...],
                            preferred_element_type=out_ref.dtype,
                            precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def bipartite_mix(adjacency: jax.Array, values: jax.Array, *,
                  block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                  block_k: int = BLOCK_K,
                  interpret: bool | None = None) -> jax.Array:
    """A @ V with VMEM-tiled accumulation.

    Args:
      adjacency: (M, N) float adjacency (any weighting works; M == N for
        the full graph, M = N/w for a worker shard's row block under the
        sharded topology backend).
      values: (N, d) stacked worker vectors.

    Returns:
      (M, d) neighbor sums.
    """
    n_rows, n = adjacency.shape
    assert values.shape[0] == n
    d = values.shape[1]
    dtype = values.dtype

    block_m = worker_block(n_rows, block_m)
    block_k = worker_block(n, block_k)
    m_pad = (-n_rows) % block_m
    k_pad = (-n) % block_k
    d_pad = (-d) % block_n
    a_p = jnp.pad(adjacency.astype(dtype), ((0, m_pad), (0, k_pad)))
    v_p = jnp.pad(values, ((0, k_pad), (0, d_pad)))
    mp, kp = a_p.shape
    dp = v_p.shape[1]

    grid = (mp // block_m, dp // block_n, kp // block_k)
    out = pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), dtype),
        interpret=interpret_mode(interpret),
    )(a_p, v_p)
    return out[:n_rows, :d]
