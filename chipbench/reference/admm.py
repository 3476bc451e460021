"""Plain reference of CQ-GGADMM rounds (paper Algorithms 1-2, Eqs. 11-23),
one worker at a time, in float32.

A round: the head workers, then the tail workers, each
  1. solve locally: ``local_steps`` Adam steps (moments kept across rounds,
     bias correction counted from 1 in each solve) on
     f_n(theta) + <theta, alpha_n - rho * sum_{m in N_n} theta_hat_m>
     + rho * |N_n| / 2 * ||theta||^2;
  2. quantize the change against the last reconstruction q_hat (Eqs. 14-20:
     range R = max |theta - q_hat| over the whole model, bit width b0 in a
     worker's first round and grown by Eq. 18 after, step 2R / (2^b - 1),
     stochastic rounding with the dither drawn from the round's phase key
     over all workers' packed parameters, codes clipped to [0, 2^b - 1]);
  3. censor (Sec. 4): transmit iff ||candidate - theta_hat_n|| >= tau0 *
     xi^(k+1), and only then replace theta_hat_n;
then every dual ascends, alpha_n += rho * (|N_n| theta_hat_n - sum_{m in
N_n} theta_hat_m) (Eq. 23). Parameters are packed in the order of their
tree's leaves, one row per worker, which is how the dither maps to them.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12
B1, B2, ADAM_EPS = 0.9, 0.95, 1e-8


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray(jnp.stack([jnp.linalg.norm(x.reshape(-1))
                                 for x in jax.tree_util.tree_leaves(tree)]),
                      np.float64)


def _flat(tree):
    return jnp.concatenate([x.reshape(-1) for x in
                            jax.tree_util.tree_leaves(tree)])


def _unflat(flat, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(flat[off:off + x.size].reshape(x.shape))
        off += x.size
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.jit
def _adam(th, g, mu, nu, t, lr):
    def upd(p, gl, m, v):
        m = B1 * m + (1 - B1) * gl
        v = B2 * v + (1 - B2) * gl * gl
        step = (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t)) + ADAM_EPS)
        return p - lr * step, m, v
    out = jax.tree_util.tree_map(upd, th, g, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def _augment(g, th, v, quad):
    return jax.tree_util.tree_map(lambda a, b, c: a + c + quad * b, g, th, v)


@jax.jit
def _quantize(flat, qprev, u, bits_prev, r_prev, started, b0, omega, b_max):
    diff = flat - qprev
    r = jnp.max(jnp.abs(diff))
    grown = jnp.ceil(jnp.log2(1.0 + (2.0 ** bits_prev - 1.0) * r
                              / jnp.maximum(omega * r_prev, EPS)))
    grown = jnp.where(r_prev <= EPS, bits_prev, grown)
    bits = jnp.clip(jnp.where(started, grown, b0), 1.0, b_max)
    levels = 2.0 ** bits - 1.0
    delta = jnp.maximum(2.0 * r / jnp.maximum(levels, 1.0), EPS)
    c = (diff + r) / delta
    q = jnp.clip(jnp.floor(c) + (u < c - jnp.floor(c)), 0.0, levels)
    cand = jnp.where(r <= EPS, qprev, qprev + delta * q - r)
    return cand, bits, jnp.where(r <= EPS, r_prev, r)


def run(params0, loss_fn: Callable, batches: Sequence[Sequence[Dict]],
        keys: Sequence[jax.Array], hp: Dict, graph: Dict) -> Dict:
    """``len(batches)`` rounds from ``params0`` (every worker starts
    there). ``batches[r][n]`` is worker n's batch of round r (a dict of
    ``tokens`` and ``labels``); ``keys[r]`` the round's key; ``loss_fn(
    params, tokens, labels)``. Returns per-round losses (mean over workers
    of each one's loss on its batch after the round), transmit decisions
    and payload bits, each worker's leaf norms of its Adam first moment
    after round 1, and after the last round its leaf norms of the
    parameters' change and of the dual."""
    n = graph["workers"]
    heads = set(graph["heads"])
    nbrs: List[List[int]] = [[] for _ in range(n)]
    for a, b in graph["edges"]:
        nbrs[a].append(b)
        nbrs[b].append(a)
    rho, lr, k_steps = hp["rho"], hp["lr"], hp["local_steps"]
    grad = jax.jit(jax.grad(loss_fn))
    loss = jax.jit(loss_fn)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
    theta = [params0] * n
    hat = [zeros] * n
    alpha = [zeros] * n
    mu, nu = [zeros] * n, [zeros] * n
    qhat = [_flat(zeros)] * n
    bits_prev = [float(hp["bits"])] * n
    r_prev = [0.0] * n
    started = [False] * n
    dim = qhat[0].size
    out = {"loss": [], "tx": [], "payload_bits": []}
    for r, (batch, key) in enumerate(zip(batches, keys)):
        tx_round, bits_round = [0] * n, [0.0] * n
        tau = hp["tau0"] * hp["xi"] ** (r + 1)
        for phase_key, members in zip(jax.random.split(key),
                                      ([w for w in range(n) if w in heads],
                                       [w for w in range(n)
                                        if w not in heads])):
            dither = jax.random.uniform(phase_key, (n, dim), jnp.float32)
            for w in members:
                neigh = zeros
                for m in nbrs[w]:
                    neigh = jax.tree_util.tree_map(jnp.add, neigh, hat[m])
                v = jax.tree_util.tree_map(lambda a, b: a - rho * b,
                                           alpha[w], neigh)
                quad = rho * len(nbrs[w])
                th, m1, m2 = theta[w], mu[w], nu[w]
                for i in range(k_steps):
                    g = _augment(grad(th, batch[w]["tokens"],
                                      batch[w]["labels"]), th, v, quad)
                    th, m1, m2 = _adam(th, g, m1, m2, float(i + 1), lr)
                theta[w], mu[w], nu[w] = th, m1, m2
                cand, b, rr = _quantize(
                    _flat(th), qhat[w], dither[w], bits_prev[w], r_prev[w],
                    started[w], float(hp["bits"]), hp["omega"],
                    float(hp["b_max"]))
                qhat[w], bits_prev[w], r_prev[w] = cand, float(b), float(rr)
                started[w] = True
                change = float(jnp.linalg.norm(cand - _flat(hat[w])))
                if change >= tau:
                    hat[w] = _unflat(cand, th)
                    tx_round[w] = 1
                    bits_round[w] = float(b) * dim + hp["b_overhead"]
            del dither
        for w in range(n):
            lap = jax.tree_util.tree_map(lambda x: len(nbrs[w]) * x, hat[w])
            for m in nbrs[w]:
                lap = jax.tree_util.tree_map(jnp.subtract, lap, hat[m])
            alpha[w] = jax.tree_util.tree_map(lambda a, l: a + rho * l,
                                              alpha[w], lap)
        out["loss"].append(float(np.mean([
            float(loss(theta[w], batch[w]["tokens"], batch[w]["labels"]))
            for w in range(n)])))
        out["tx"].append(tx_round)
        out["payload_bits"].append(bits_round)
        if r == 0:
            out["mu_norms"] = np.stack([_leaf_norms(m) for m in mu])
    out["update_norms"] = np.stack([
        _leaf_norms(jax.tree_util.tree_map(jnp.subtract, theta[w], params0))
        for w in range(n)])
    out["dual_norms"] = np.stack([_leaf_norms(a) for a in alpha])
    return out
