"""Pallas TPU kernel: sparse neighbor aggregation by edge gather.

The sparse topology backend computes out_n = sum_{m in N_n} v_m from the
graph's degree-padded CSR table instead of a dense (N, N) matmul. On TPU
this is a *scalar-prefetch gather*: the neighbor ids live in SMEM before
the kernel body runs, so the BlockSpec index_map can pick which (1, bd)
row block of V to DMA for each (worker, slot) grid step — the classic
Pallas dynamic-gather pattern. The output block for worker n accumulates
its S = max_degree neighbor rows across the minor grid dimension; padded
slots multiply by a 0.0 validity scalar (also from SMEM) so they add
exactly nothing — bit-identical to the jnp oracle
(``ref.edge_gather_mix_ref``).

Work is O(N·S·d) ≈ O(E·d) row DMAs with no (N, N) operand anywhere — the
point of the sparse backend at worker counts where the adjacency matmul's
O(N²·d) MXU work (or the (N, N) buffer itself) is the bottleneck. For
paper-scale N the dense ``bipartite_mix`` MXU kernel wins; see DESIGN.md
§Topology for the crossover discussion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCK_D = 512


def _edge_gather_kernel(nbr_ref, valid_ref, v_ref, out_ref):
    # nbr_ref/valid_ref are scalar-prefetch (SMEM) refs of shape (N, S);
    # v_ref is the (1, bd) row block of V that the index_map already
    # gathered for this (worker i, slot s) step.
    i = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = valid_ref[i, s].astype(out_ref.dtype)
    out_ref[...] += w * v_ref[...]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def edge_gather_mix(values: jax.Array, nbr_table: jax.Array,
                    nbr_valid: jax.Array, *, block_d: int = BLOCK_D,
                    interpret: bool | None = None) -> jax.Array:
    """Neighbor sum over a degree-padded CSR table.

    Args:
      values: (N, d) stacked worker vectors.
      nbr_table: (N, S) int32 neighbor ids, S = max degree (pad slots may
        point anywhere in range; their contribution is zeroed).
      nbr_valid: (N, S) float 1/0 slot validity.
      interpret: interpreter mode; ``None`` decides by platform
        (``kernels.interpret_mode``).

    Returns:
      (N, d) neighbor sums, f32.
    """
    n, d = values.shape
    assert nbr_table.shape == nbr_valid.shape and nbr_table.shape[0] == n
    s = nbr_table.shape[1]
    d_pad = (-d) % block_d
    # rows as (N, 1, d): a one-row block then ends in (1, block_d), whose
    # row dimension spans the array's, as the TPU tiling requires
    v_p = jnp.pad(values.astype(jnp.float32),
                  ((0, 0), (0, d_pad)))[:, None, :]
    dp = v_p.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, dp // block_d, s),
        in_specs=[
            pl.BlockSpec((None, 1, block_d),
                         lambda i, j, s, nbr_ref, valid_ref:
                         (nbr_ref[i, s], 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_d),
                               lambda i, j, s, nbr_ref, valid_ref:
                               (i, 0, j)),
    )
    out = pl.pallas_call(
        _edge_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, dp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(nbr_table.astype(jnp.int32), nbr_valid.astype(jnp.float32), v_p)
    return out[:, 0, :d]
