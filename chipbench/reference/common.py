"""What the plain references of every model family share, in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no chunking, no
remat. Sizes come from the configuration file (``cfg`` is its JSON
object); weights from a tree in the program's layout that the benchmark
itself drew from the seed (``weights.py``); nothing of the program is
imported. Matrix products go through one ``Matmul`` policy: ``"f32"``
computes them at ``highest`` precision (the reference), ``"fp8"`` rounds
both operands to float8 (e4m3, one scale per operand) first (the control:
the precision below the configurations' bfloat16 compute).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


class Matmul:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        self.mode = mode

    def _round(self, x):
        if self.mode == "f32":
            return x
        scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * scale).astype(F8).astype(jnp.float32) / scale

    def __call__(self, eq: str, a, b):
        return jnp.einsum(eq, self._round(a.astype(jnp.float32)),
                          self._round(b.astype(jnp.float32)),
                          precision=HIGHEST)


def rmsnorm(scale, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def layer_params(params, i: int, period: int):
    """The parameters of layer ``i`` from the program's stacked layout:
    ``stack/units/p<k>`` holds block ``k`` of every whole period on a
    leading axis."""
    unit = params["stack"]["units"][f"p{i % period}"]
    return jax.tree_util.tree_map(lambda x: x[i // period], unit)


def mlp(mm, p, x):
    gate = mm("bsd,df->bsf", x, p["wi_gate"]["w"])
    up = mm("bsd,df->bsf", x, p["wi_up"]["w"])
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["wo"]["w"])


def logits_of(mm, params, x, cfg):
    x = rmsnorm(params["final_norm"]["scale"], x, cfg["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, params["embed"]["table"])


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy over every position."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)
