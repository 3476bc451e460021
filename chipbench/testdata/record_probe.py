"""Record the small trace in ``probe_1chip.xplane.pb`` that
``tests/test_trace_reduce.py`` reads: on one TPU, three host spans
``chipbench.step``, each running ``jit_prefill_chunk`` (a 2048^3 matmul
with a tanh) and then ``jit_decode`` (one with a sin), 5 ms apart; on
several devices also an all-gather under ``chipbench.gather``.

    python3 chipbench/testdata/record_probe.py <out_dir>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@jax.jit
def prefill_chunk(x):
    return jnp.tanh(x @ x).sum()


@jax.jit
def decode(x):
    return jnp.sin(x @ x.T).mean()


def main(out):
    os.makedirs(out, exist_ok=True)
    raw = os.path.join(out, "raw")
    x = jnp.ones((2048, 2048))
    prefill_chunk(x).block_until_ready()
    decode(x).block_until_ready()
    n = len(jax.devices())
    gather = None
    if n > 1:
        mesh = Mesh(jax.devices(), ("w",))
        xs = jax.device_put(jnp.ones((n * 512, 2048)),
                            NamedSharding(mesh, P("w")))
        gather = jax.jit(lambda a: jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P())).sum())
        gather(xs).block_until_ready()
    jax.profiler.start_trace(raw)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("chipbench.step"):
            prefill_chunk(x).block_until_ready()
            time.sleep(0.002)
            decode(x).block_until_ready()
        if gather is not None:
            with jax.profiler.TraceAnnotation("chipbench.gather"):
                gather(xs).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    path = glob.glob(raw + "/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, os.path.join(out, f"probe_{n}chip.xplane.pb"))


if __name__ == "__main__":
    main(sys.argv[1])
