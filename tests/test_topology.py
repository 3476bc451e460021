"""Pluggable topology backends (core/topology.py; DESIGN.md §Topology).

* All three backends (dense / sparse / sharded) agree on neighbor
  aggregation, the Laplacian dual term, and the residual reductions.
* The engine produces matching trajectories under every ``mix_backend``
  on the quickstart-style convex workload (dense stays bit-golden via the
  existing seed tests; sparse/sharded match to fp tolerance).
* The dual update rides the same backend/kernel routing as the phase
  mixes — regression for the seed bug where the dual step silently
  dropped ``use_pallas_mix``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm_baselines as ab
from repro.core import engine as E
from repro.core import topology as T
from repro.core.graph import (chain_graph, random_bipartite_graph,
                              star_graph)
from repro.core.solvers import LinearRegressionProblem
from repro.data import regression as R

N_WORKERS = 8
DIM = 12
ITERS = 40


@pytest.fixture(scope="module")
def linreg():
    data = R.synth_linear(n=240, d=DIM, seed=0)
    g = random_bipartite_graph(N_WORKERS, 0.4, seed=0)
    x, y = R.partition_uniform(data, N_WORKERS)
    return g, LinearRegressionProblem(jnp.asarray(x), jnp.asarray(y))


GRAPHS = {
    "random": lambda: random_bipartite_graph(12, 0.3, seed=7),
    "chain": lambda: chain_graph(9),
    "star": lambda: star_graph(6),
}


# ------------------------------------------------- backend equivalence ----
@pytest.mark.parametrize("graph_name", sorted(GRAPHS))
@pytest.mark.parametrize("backend", T.BACKENDS)
def test_mix_laplacian_residual_match_dense(graph_name, backend):
    g = GRAPHS[graph_name]()
    v = jnp.asarray(np.random.default_rng(1).normal(
        size=(g.n, 20)).astype(np.float32))
    adj = np.asarray(g.adjacency)
    want_mix = adj @ np.asarray(v)
    topo = T.build(g, backend)
    np.testing.assert_allclose(np.asarray(topo.mix(v)), want_mix,
                               rtol=1e-5, atol=1e-5)
    want_lap = np.asarray(g.degrees)[:, None] * np.asarray(v) - want_mix
    np.testing.assert_allclose(np.asarray(topo.laplacian(v)), want_lap,
                               rtol=1e-5, atol=1e-5)
    diffs = np.asarray(v)[:, None] - np.asarray(v)[None]
    want_res = float((adj * (diffs ** 2).sum(-1)).sum() / 2.0)
    got_res = float(topo.primal_residual(v))
    np.testing.assert_allclose(got_res, want_res, rtol=1e-4)
    # dual residual vanishes exactly at consensus (all-equal rows span
    # ker(D - A)) and is positive away from it
    consensus = jnp.broadcast_to(v[:1], v.shape)
    assert float(topo.dual_residual(topo.laplacian(consensus))) < 1e-6
    assert float(topo.dual_residual(topo.laplacian(v))) > 0.0


@pytest.mark.parametrize("backend", T.BACKENDS)
def test_tree_mix_matches_flat(backend):
    g = random_bipartite_graph(10, 0.4, seed=2)
    v = jnp.asarray(np.random.default_rng(2).normal(
        size=(g.n, 24)).astype(np.float32))
    tree = {"a": v[:, :5].reshape(g.n, 5), "b": v[:, 5:].reshape(g.n, 19)}
    topo = T.build(g, backend)
    flat = np.asarray(topo.mix(v))
    mixed = topo.mix(tree)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(mixed["a"]), np.asarray(mixed["b"])], 1),
        flat, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["dense", "sparse", "sharded"])
def test_pallas_kernel_routing(backend):
    """use_pallas_mix routes every backend's mix through its kernel
    (bipartite_mix for dense, the rectangular row-block bipartite_mix
    inside the shard_map for sharded, edge_gather_mix for sparse) with
    unchanged results."""
    g = random_bipartite_graph(10, 0.4, seed=5)
    v = jnp.asarray(np.random.default_rng(5).normal(
        size=(g.n, 30)).astype(np.float32))
    want = np.asarray(jnp.asarray(g.adjacency) @ v)
    topo = T.build(g, backend, use_pallas_mix=True)
    np.testing.assert_allclose(np.asarray(topo.mix(v)), want,
                               rtol=1e-5, atol=1e-5)


def test_build_rejects_unknown_backend():
    g = chain_graph(4)
    with pytest.raises(ValueError):
        T.build(g, "blocked")
    with pytest.raises(AssertionError):
        E.EngineConfig(mix_backend="blocked")


def test_sharded_backend_runs_under_jit():
    g = random_bipartite_graph(8, 0.5, seed=0)
    topo = T.build(g, "sharded")
    v = jnp.asarray(np.random.default_rng(0).normal(
        size=(8, 16)).astype(np.float32))
    got = jax.jit(topo.mix)(v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.asarray(g.adjacency) @ v),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_workers,width",
                         [(4, 4), (8, 4), (2, 2), (6, 2), (3, 1)])
def test_worker_devices_take_equal_row_blocks(n_workers, width):
    """Workers spread over the most devices that split them evenly: four
    chips take 4 or 8 workers whole, 2 or 6 workers on two of them, and 3
    workers on one."""
    devices = ["d0", "d1", "d2", "d3"]
    got = T.worker_devices(n_workers, devices)
    assert got == devices[:width]
    assert n_workers % len(got) == 0


# ------------------------------------------- engine trajectory parity ----
@pytest.mark.parametrize("backend", ["sparse", "sharded"])
@pytest.mark.parametrize("scheme", ["ggadmm", "cq-ggadmm"])
def test_engine_backend_matches_dense_trajectories(linreg, scheme, backend):
    """The full engine under sparse/sharded mixing reproduces the dense
    trajectories on the quickstart workload: identical censor decisions,
    matching theta / residuals to fp tolerance. The sparse mix sums the
    neighbours in another order than the dense matmul; quantized, that
    rounding becomes whole quantization steps, so there the residuals are
    held to ``CROSS_PROGRAM_RTOL`` and the bits to ``assert_bits_track``.
    (Dense itself stays bit-golden vs the frozen seed —
    tests/test_engine.py.)"""
    g, prob = linreg
    outs = {}
    for b in ("dense", backend):
        cfg = dataclasses.replace(ab.ALL_SCHEMES[scheme](rho=1.0),
                                  mix_backend=b)
        state, out = E.run(g, cfg, E.ExactSolver(prob),
                           jnp.zeros((N_WORKERS, DIM), jnp.float32),
                           ITERS, seed=3,
                           extra_metrics=E.flat_metrics(g, b))
        outs[b] = (np.asarray(out["tx_mask"]),
                   np.asarray(out["primal_residual"]),
                   np.asarray(state.theta),
                   np.asarray(out["payload_bits"]),
                   np.asarray(out["bits_per_group"]))
    np.testing.assert_array_equal(outs["dense"][0], outs[backend][0])
    np.testing.assert_allclose(outs["dense"][2], outs[backend][2],
                               rtol=1e-4, atol=1e-5)
    if scheme == "cq-ggadmm" and backend == "sparse":
        assert E.tree_rel_err(outs[backend][1],
                              outs["dense"][1]) <= E.CROSS_PROGRAM_RTOL
        assert_bits_track(outs["dense"][3:], outs[backend][3:])
        return
    np.testing.assert_allclose(outs["dense"][1], outs[backend][1],
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(outs["dense"][3], outs[backend][3],
                               rtol=1e-6)


def assert_bits_track(a, b):
    """Two programs' quantized runs, each given as ``(payload_bits (K, N),
    bits_per_group (K, N, G))``, put the same bits on the wire until their
    ranges part by rounding. A bit width is the ceil of a range ratio
    (Eq. 18), so from a shared previous width the two schedules part by at
    most one step, and the payload is the same wherever the widths are."""
    (pay_a, bits_a), (pay_b, bits_b) = a, b
    np.testing.assert_array_equal(bits_a[0], bits_b[0])
    shared_prev = bits_a[:-1] == bits_b[:-1]
    assert np.abs(bits_a[1:] - bits_b[1:])[shared_prev].max(initial=0) <= 1
    same = (bits_a == bits_b).all(axis=-1)
    np.testing.assert_array_equal(pay_a[same], pay_b[same])


def test_engine_backend_pytree_training_agrees():
    """Multi-leaf (packed-buffer) consensus training runs under every
    backend and lands on the same final state."""
    n = 6
    key = jax.random.PRNGKey(0)
    targets = {"w": 2.0 * jax.random.normal(key, (n, 8, 4)),
               "b": jax.random.normal(jax.random.fold_in(key, 1), (n, 16))}

    def grad_fn(theta, batch):
        del batch
        return jax.tree_util.tree_map(lambda t, c: t - c, theta, targets)

    g = random_bipartite_graph(n, 0.5, seed=0)
    finals = {}
    for backend in T.BACKENDS:
        solver = E.InexactSolver(grad_fn=grad_fn, local_steps=5,
                                 local_lr=0.2)
        cfg = E.EngineConfig(rho=0.5, mix_backend=backend)
        theta0 = jax.tree_util.tree_map(jnp.zeros_like, targets)
        state = E.init_state(theta0, cfg, solver)
        step = jax.jit(E.make_step(g, cfg, solver,
                                   extra_metrics=E.consensus_metrics()))
        for i in range(30):
            state, m = step(state, None, jax.random.PRNGKey(i))
        finals[backend] = (np.asarray(state.theta["w"]),
                           float(m["consensus_err"]))
    for backend in ("sparse", "sharded"):
        np.testing.assert_allclose(finals[backend][0], finals["dense"][0],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(finals[backend][1], finals["dense"][1],
                                   rtol=1e-3)


# --------------------------------------- dual-update kernel regression ----
def _count_kernel_mixes(cfg, g, prob, monkeypatch):
    from repro.kernels import ops as kernel_ops
    calls = {"n": 0}
    orig = kernel_ops.bipartite_mix

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    monkeypatch.setattr(kernel_ops, "bipartite_mix", counting)
    step = E.make_step(g, cfg, E.ExactSolver(prob))
    state = E.init_state(jnp.zeros((N_WORKERS, DIM), jnp.float32), cfg)
    step(state, None, jax.random.PRNGKey(0))
    return calls["n"]


def test_dual_update_uses_pallas_mix(linreg, monkeypatch):
    """Regression: with ``use_pallas_mix=True`` the Pallas mix kernel must
    serve the dual update too, not just the two phase mixes — the seed
    built the dual's neighbor sum with a second, flagless ``tree_mix``
    call, silently dropping the kernel routing (3 mixes per alternating
    step: head phase, tail phase, dual Laplacian)."""
    g, prob = linreg
    cfg = dataclasses.replace(ab.ALL_SCHEMES["ggadmm"](rho=1.0),
                              use_pallas_mix=True)
    assert _count_kernel_mixes(cfg, g, prob, monkeypatch) == 3


def test_dual_update_with_pallas_stays_golden(linreg):
    """Forwarding the kernel flag to the dual step must not change the
    run: identical censor decisions, duals within ``CROSS_PROGRAM_RTOL``
    of the plain matmul's (the Pallas mix sums the neighbours in another
    order, and the quantizer carries that rounding forward), and the bits
    on the wire as ``assert_bits_track`` allows."""
    g, prob = linreg
    outs = {}
    for use_kernel in (False, True):
        cfg = dataclasses.replace(ab.ALL_SCHEMES["cq-ggadmm"](rho=1.0),
                                  use_pallas_mix=use_kernel)
        state, out = E.run(g, cfg, E.ExactSolver(prob),
                           jnp.zeros((N_WORKERS, DIM), jnp.float32),
                           20, seed=3)
        outs[use_kernel] = (np.asarray(out["tx_mask"]),
                            np.asarray(state.alpha),
                            np.asarray(out["payload_bits"]),
                            np.asarray(out["bits_per_group"]))
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    assert E.tree_rel_err(outs[True][1], outs[False][1]) \
        <= E.CROSS_PROGRAM_RTOL
    assert_bits_track(outs[False][2:], outs[True][2:])


@pytest.mark.parametrize("backend,use_pallas", [
    ("dense", False), ("dense", True), ("sharded", False),
    ("sharded", True)])
def test_mix_runs_at_full_f32_precision(backend, use_pallas):
    """Every matmul the mix lowers to asks for ``Precision.HIGHEST``: a
    TPU's default f32 matmul rounds its operands to bfloat16, which would
    round every model the workers exchange (the CPU computes in f32 either
    way, so only the lowered program can show it)."""
    g = random_bipartite_graph(N_WORKERS, 0.4, seed=0)
    topo = T.build(g, backend, use_pallas_mix=use_pallas)
    text = jax.jit(topo.mix).lower(
        jnp.zeros((N_WORKERS, DIM), jnp.float32)).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots
    for ln in dots:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln
