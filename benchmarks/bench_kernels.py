"""Kernel micro-benchmark stages: Pallas vs the pure-jnp oracle, over a
shape sweep. The kernels run as the platform decides
(``kernels.interpret_mode``): compiled on a TPU, interpreted elsewhere.
On a CPU the number that matters is parity (max |diff|); the us/call
column times the interpreter there and is a kernel time only on a TPU.

``stage_shape`` wraps one (n, d) point as a campaign run (the ``kernels``
stage of campaign ``all``): results land in ``kernels.<n>x<d>`` sections
of ``BENCH_engine.json`` with parity claims in ``kernels.claims``. Timing
rides the shared discipline in ``repro.campaign.measure`` (warm-up call
blocked before the timed reps).

    PYTHONPATH=src python -m benchmarks.bench_kernels
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.campaign.measure import time_per_call
from repro.campaign.store import Claim, Record
from repro.kernels import ref
from repro.kernels.bipartite_mix import bipartite_mix
from repro.kernels.stoch_quant import stoch_quantize

SHAPES = [(8, 512), (16, 4096), (24, 16384)]


def bench_shape(n: int, d: int) -> dict:
    """Both kernels vs their oracles at one (n, d) point."""
    key = jax.random.PRNGKey(n * d)
    theta = 5 * jax.random.normal(key, (n, d))
    qprev = jnp.zeros((n, d))
    unif = jax.random.uniform(jax.random.fold_in(key, 1), (n, d))
    qrange = jnp.max(jnp.abs(theta), axis=-1)
    delta = 2.0 * qrange / 15.0
    us_k, out_k = time_per_call(stoch_quantize,
                                theta, qprev, unif, delta, qrange)
    us_r, out_r = time_per_call(jax.jit(ref.stoch_quantize_ref),
                                theta, qprev, unif, delta, qrange)
    quant_diff = float(jnp.max(jnp.abs(out_k - out_r)))

    adj = (jax.random.uniform(key, (n, n)) > 0.5).astype(jnp.float32)
    v = jax.random.normal(key, (n, d))
    us_mk, out_mk = time_per_call(bipartite_mix, adj, v)
    us_mr, out_mr = time_per_call(jax.jit(ref.bipartite_mix_ref), adj, v)
    mix_diff = float(jnp.max(jnp.abs(out_mk - out_mr)))
    return {"n": n, "d": d,
            "stoch_quant": {"us_per_call": us_k, "us_ref": us_r,
                            "max_abs_diff": quant_diff},
            "bipartite_mix": {"us_per_call": us_mk, "us_ref": us_mr,
                              "max_abs_diff": mix_diff}}


def stage_shape(n: int, d: int, ctx=None) -> Record:
    out = bench_shape(n, d)
    sq, bm = out["stoch_quant"], out["bipartite_mix"]
    print(f"stoch_quant,{n}x{d},{sq['us_per_call']:.0f},"
          f"{sq['us_ref']:.0f},{sq['max_abs_diff']:.2e}")
    print(f"bipartite_mix,{n}x{d},{bm['us_per_call']:.0f},"
          f"{bm['us_ref']:.0f},{bm['max_abs_diff']:.2e}")
    return Record(
        section=("kernels", f"{n}x{d}"), data=out,
        claims=(
            Claim(f"stoch_quant_parity_{n}x{d}",
                  sq["max_abs_diff"] <= 1e-5,
                  value=sq["max_abs_diff"], gate="<= 1e-5 vs oracle"),
            Claim(f"bipartite_mix_parity_{n}x{d}",
                  bm["max_abs_diff"] <= 1e-4,
                  value=bm["max_abs_diff"], gate="<= 1e-4 vs oracle"),),
        claims_path=("kernels", "claims"))


def main() -> int:
    """Back-compat entry: run only the kernels stage of campaign ``all``."""
    from benchmarks import campaigns
    from repro.campaign.runner import Runner
    print("# kernels: name,shape,us_per_call,us_ref,max_abs_diff")
    return Runner(campaigns.get("all"),
                  only="kernels").run().exit_code


if __name__ == "__main__":
    raise SystemExit(main())
