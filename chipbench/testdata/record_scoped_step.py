"""Record the small trace ``scoped_step_1chip.xplane.pb`` and its
program's compiled text ``scoped_step_1chip.hlo.txt.gz`` that
``tests/test_phases.py`` reads: on one TPU, three rounds of a tiny
quantized, censored CQ-GGADMM step (4 workers on a least-squares problem,
Adam over 4 local steps in a ``fori_loop``, the loss as a round metric),
each part of the round under its ``admm.*`` scope, 2 ms of host sleep
between rounds.

    PYTHONPATH=src python3 chipbench/testdata/record_scoped_step.py <out_dir>

The profiler writes the program's HLO protos and every host event into
the file; ``shrink`` then keeps what the reductions read (device events
and their names, the host's dispatch and ``chipbench.``/``repro.``
spans), on a machine with TensorFlow's XPlane protos (no chip needed):

    python3 chipbench/testdata/record_scoped_step.py shrink <in> <out>
"""
import glob
import gzip
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

N, ROWS, D = 4, 2048, 2048
LOCAL_STEPS = 4


def problem():
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (N, ROWS, D), jnp.float32)
    y = x @ jax.random.normal(kw, (D,), jnp.float32)
    return x, y


def loss(theta, x, y):
    return 0.5 * jnp.mean(jnp.square(x @ theta["w"] + theta["b"] - y))


def grad_fn(theta, batch):
    return jax.vmap(jax.grad(loss))(theta, *batch)


def loss_fn(theta, batch):
    return jnp.mean(jax.vmap(loss)(theta, *batch))


def build():
    from repro.core import engine as E
    from repro.core.censoring import CensorConfig
    from repro.core.graph import random_bipartite_graph
    from repro.core.quantization import QuantConfig
    cfg = E.EngineConfig(rho=0.5, censor=CensorConfig(tau0=0.5, xi=0.97),
                         quantize=QuantConfig(b0=4, omega=0.99))
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=LOCAL_STEPS,
                             local_lr=0.01)
    theta0 = {"w": jnp.zeros((N, D), jnp.float32),
              "b": jnp.zeros((N,), jnp.float32)}
    step = jax.jit(E.make_step(random_bipartite_graph(N, 0.6, seed=0), cfg,
                               solver,
                               extra_metrics=E.consensus_metrics(loss_fn)))
    return step, E.init_state(theta0, cfg, solver)


def main(out):
    os.makedirs(out, exist_ok=True)
    raw = os.path.join(out, "raw")
    step, state = build()
    batch = problem()
    for r in range(2):
        state, m = step(state, batch, jax.random.PRNGKey(r))
    jax.block_until_ready(state)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # keep the file small
    jax.profiler.start_trace(raw, profiler_options=opts)
    for r in range(2, 5):
        state, m = step(state, batch, jax.random.PRNGKey(r))
        jax.block_until_ready(state)
        time.sleep(0.002)
    jax.profiler.stop_trace()
    text = step.lower(state, batch, jax.random.PRNGKey(0)).compile().as_text()
    n = len(jax.devices())
    path = glob.glob(raw + "/**/*.xplane.pb", recursive=True)[0]
    trace = os.path.join(out, f"scoped_step_{n}chip.xplane.pb")
    shutil.copy(path, trace)
    with gzip.open(os.path.join(out, f"scoped_step_{n}chip.hlo.txt.gz"),
                   "wt") as f:
        f.write(text)
    shutil.rmtree(raw)
    print(f"loss {float(m['loss']):.5f}, while loops in the program: "
          f"{text.count(' while(')}, trace {os.path.getsize(trace)} bytes")


def shrink(src, dst):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = ("PjitFunction(", "chipbench.", "repro.")
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.event_metadata.clear()
        elif plane.name.startswith("/device:"):
            for md in plane.event_metadata.values():
                del md.stats[:]
        elif plane.name.startswith("/host:"):
            names = {k: md.name for k, md in plane.event_metadata.items()}
            for line in plane.lines:
                kept = [ev for ev in line.events
                        if names.get(ev.metadata_id, "").startswith(keep)]
                del line.events[:]
                line.events.extend(kept)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())
    print(f"{os.path.getsize(src)} -> {os.path.getsize(dst)} bytes")


if __name__ == "__main__":
    if sys.argv[1] == "shrink":
        shrink(sys.argv[2], sys.argv[3])
    else:
        main(sys.argv[1])
