#!/usr/bin/env python3
"""Compile a cell's programs for a described (not attached) TPU v5e and
print what the compiler reckons they need, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell> \
        [--layers N]

One-chip cells compile against one device of a described ``v5e:2x2``.
``--layers`` tries another depth without editing the cell's files. Prints the arguments' and temp bytes of each program and whether
the compiler accepted it.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402

GB = 1e9


def _sds(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def report(name, lowered):
    try:
        ma = lowered.compile().memory_analysis()
    except Exception as e:                       # the compiler's verdict
        print(f"{name}: refused: {str(e).splitlines()[0][:300]}")
        return
    print(f"{name}: compiles; arguments {ma.argument_size_in_bytes / GB:.3f}"
          f" GB, outputs {ma.output_size_in_bytes / GB:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / GB:.3f} GB, aliased "
          f"{ma.alias_size_in_bytes / GB:.3f} GB")


def train(cell, one_chip):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(harness.HERE / "drivers"))
    import train as drv
    from repro.core import engine as E
    from repro.launch import train as T
    import program
    import weights
    mcfg = program.model_config(cell["config"])
    setup = T.admm_setup(mcfg, drv.trainer_args(cell, 0))
    make = weights.maker(weights.program_layout(mcfg))
    n = cell["deploy"]["workers"]

    def init():
        one = make(jax.random.PRNGKey(0))
        return E.init_state(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one),
            setup.ecfg, setup.solver)

    state = _sds(jax.eval_shape(init), one_chip)
    t = cell["traffic"]
    tok = jax.ShapeDtypeStruct((n, t["batch_per_worker"], t["seq"]),
                               jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    print(f"{cell['name']}: {mcfg.num_layers} layers, {n} workers, "
          f"{mcfg.param_count() / 1e6:.1f}M parameters per worker")
    report("round", setup.build_step().lower(
        state, {"tokens": tok, "labels": tok}, key))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int)
    args = ap.parse_args()
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(bench.root / "src"))
    if args.layers:
        cell["config"]["num_hidden_layers"] = args.layers
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell["chips"] != 1:
        raise SystemExit("four-chip cells are not rehearsed here")
    train(cell, SingleDeviceSharding(topo.devices[0]))


if __name__ == "__main__":
    main()
