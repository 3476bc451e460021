"""Pallas kernels for the paper's hot spots, their jnp oracles (``ref``)
and the dispatching wrappers (``ops``)."""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in interpret mode. ``None`` (every kernel
    entry point's default) leaves it to the platform: compiled on a TPU,
    interpreted everywhere else. The one place this is decided."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def worker_block(n: int, block: int) -> int:
    """Block height along the worker axis. Fewer rows than the block take
    the whole axis as one block (the TPU tiling accepts a block equal to
    the full dimension), so a 2-worker buffer is never padded to the
    block height — at LM width that padded copy alone would not fit HBM."""
    return n if n < block else block
