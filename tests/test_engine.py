"""Unified consensus engine (core/engine.py).

* Golden equivalence: the engine with a one-leaf pytree and G=1 reproduces
  the frozen seed flat stepper (core/seed_reference.py) bit-for-bit on every
  algorithm variant — the refactor's no-regression proof.
* Structure invariance: splitting the flat vector into a multi-leaf pytree
  does not change deterministic trajectories.
* Layer-aware modes: G=num_leaves payload ≤ G=1 payload under heterogeneous
  per-layer range dynamics; per-group censoring silences quiet layers.
* Leaf-wise Pallas kernel routing matches the plain path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admm_baselines as ab
from repro.core import cq_ggadmm as cq
from repro.core import engine as E
from repro.core import seed_reference as ref
from repro.core.censoring import CensorConfig
from repro.core.graph import random_bipartite_graph
from repro.core.quantization import QuantConfig
from repro.core.solvers import LinearRegressionProblem
from repro.data import regression as R

N_WORKERS = 8
DIM = 12
ITERS = 50


@pytest.fixture(scope="module")
def linreg():
    data = R.synth_linear(n=240, d=DIM, seed=0)
    g = random_bipartite_graph(N_WORKERS, 0.4, seed=0)
    x, y = R.partition_uniform(data, N_WORKERS)
    return g, LinearRegressionProblem(jnp.asarray(x), jnp.asarray(y))


# ------------------------------------------------------------- golden ----
ALL_VARIANTS = ["ggadmm", "c-ggadmm", "q-ggadmm", "cq-ggadmm", "c-admm",
                "jacobian-admm"]


@pytest.mark.parametrize("scheme", ALL_VARIANTS)
def test_golden_flat_matches_seed(linreg, scheme):
    """Engine (via the cq_ggadmm adapter) == frozen seed stepper, exactly:
    same tx decisions, same payload accounting, same trajectories.

    The seed stepper charges censored workers the full payload (the metric
    bug this PR fixes), so the engine's ``payload_bits`` must equal
    ``seed payload * tx_mask`` (bits on the wire) and the engine's
    ``candidate_payload_bits`` must equal the seed's raw number."""
    g, prob = linreg
    cfg = ab.ALL_SCHEMES[scheme](rho=1.0)
    theta_star = prob.optimum()
    state_e, out_e = cq.run(g, prob, cfg, dim=DIM, iters=ITERS, seed=3,
                            theta_star=theta_star,
                            local_loss=prob.local_loss)
    state_r, out_r = ref.run(g, prob, cfg, dim=DIM, iters=ITERS, seed=3,
                             theta_star=theta_star,
                             local_loss=prob.local_loss)
    for key in ("tx_mask", "primal_residual", "objective", "dist_to_opt"):
        np.testing.assert_array_equal(out_e[key], out_r[key], err_msg=key)
    np.testing.assert_array_equal(out_e["payload_bits"],
                                  out_r["payload_bits"] * out_r["tx_mask"],
                                  err_msg="payload_bits (transmitted)")
    np.testing.assert_array_equal(out_e["candidate_payload_bits"],
                                  out_r["payload_bits"],
                                  err_msg="candidate_payload_bits")
    np.testing.assert_array_equal(np.asarray(state_e.theta),
                                  np.asarray(state_r.theta))
    np.testing.assert_array_equal(np.asarray(state_e.theta_hat),
                                  np.asarray(state_r.theta_hat))
    np.testing.assert_array_equal(np.asarray(state_e.alpha),
                                  np.asarray(state_r.alpha))
    np.testing.assert_array_equal(
        np.asarray(state_e.quant.q_hat),
        np.asarray(state_r.quant.q_hat))
    # grouped (N, 1) side info == seed scalar (N,) side info
    np.testing.assert_array_equal(
        np.asarray(state_e.quant.bits_prev[:, 0]),
        np.asarray(state_r.quant.bits_prev))


def test_golden_flat_matches_seed_with_tracing(linreg, tmp_path):
    """Tracing-ON row of the golden grid: a live REPRO_TRACE tracer during
    the engine run changes nothing — the seed equivalence still holds bit
    for bit (the obs layer is strictly host-side, DESIGN.md
    §Observability)."""
    from repro.obs import trace as obs_trace
    g, prob = linreg
    cfg = ab.ALL_SCHEMES["cq-ggadmm"](rho=1.0)
    obs_trace.enable(str(tmp_path / "trace.json"))
    try:
        state_e, out_e = cq.run(g, prob, cfg, dim=DIM, iters=ITERS, seed=3)
    finally:
        obs_trace.disable(save=False)
    state_r, out_r = ref.run(g, prob, cfg, dim=DIM, iters=ITERS, seed=3)
    for key in ("tx_mask", "primal_residual"):
        np.testing.assert_array_equal(out_e[key], out_r[key], err_msg=key)
    np.testing.assert_array_equal(out_e["payload_bits"],
                                  out_r["payload_bits"] * out_r["tx_mask"])
    np.testing.assert_array_equal(np.asarray(state_e.theta),
                                  np.asarray(state_r.theta))
    np.testing.assert_array_equal(np.asarray(state_e.quant.q_hat),
                                  np.asarray(state_r.quant.q_hat))


def test_golden_with_pallas_kernels(linreg):
    """Kernel routing flags keep the seed trajectory: the Pallas mix sums
    the neighbours in another order than the seed's matmul, so the
    residuals agree to ``CROSS_PROGRAM_RTOL`` while the censor decisions
    and the bits on the wire stay identical."""
    g, prob = linreg
    cfg = ab.ALL_SCHEMES["cq-ggadmm"](rho=1.0)
    cfg = dataclasses.replace(cfg, use_pallas_mix=True,
                              use_pallas_quant=True)
    _, out_e = cq.run(g, prob, cfg, dim=DIM, iters=12, seed=3)
    _, out_r = ref.run(g, prob, cfg, dim=DIM, iters=12, seed=3)
    np.testing.assert_array_equal(out_e["tx_mask"], out_r["tx_mask"])
    assert E.tree_rel_err(out_e["primal_residual"],
                          out_r["primal_residual"]) <= E.CROSS_PROGRAM_RTOL
    np.testing.assert_array_equal(out_e["payload_bits"],
                                  out_r["payload_bits"] * out_r["tx_mask"])


# ----------------------------------------------- pytree == flat vector ----
def _split_problem(prob, cut):
    """View the (N, d) linreg problem as a two-leaf pytree problem."""
    class SplitSolver:
        def primal_solve(self, v, rho_d, theta_init=None):
            return prob.primal_solve(v, rho_d, theta_init=theta_init)
    return SplitSolver()


def test_split_tree_matches_flat_deterministic(linreg):
    """A flat vector split into a 2-leaf pytree runs the *identical*
    deterministic trajectory (GGADMM + censoring: no randomness used)."""
    g, prob = linreg
    cfg = ab.ALL_SCHEMES["c-ggadmm"](rho=1.0)
    cut = 5

    flat0 = jnp.zeros((N_WORKERS, DIM), jnp.float32)
    tree0 = {"a": flat0[:, :cut], "b": flat0[:, cut:]}
    _, out_flat = E.run(g, cfg, E.ExactSolver(prob), flat0, ITERS, seed=3,
                        extra_metrics=E.flat_metrics(g))
    _, out_tree = E.run(g, cfg, E.ExactSolver(_split_problem(prob, cut)),
                        tree0, ITERS, seed=3,
                        extra_metrics=lambda s, b: {
                            "theta": jnp.concatenate(
                                [s.theta["a"], s.theta["b"]], axis=1)})
    np.testing.assert_array_equal(np.asarray(out_flat["tx_mask"]),
                                  np.asarray(out_tree["tx_mask"]))
    np.testing.assert_allclose(
        np.asarray(E.flat_metrics(g)(
            E.EngineState(theta=flat0, theta_hat=flat0, alpha=flat0,
                          quant=E.GroupQuantState.create(flat0, 1),
                          opt_mu=(), opt_nu=(),
                          k=jnp.zeros((), jnp.int32)), None)["theta"]),
        np.asarray(flat0))  # sanity: flatten of a flat vector is identity
    np.testing.assert_array_equal(np.asarray(out_tree["theta"][-1]),
                                  np.asarray(out_flat["theta"][-1]))


def test_adapters_share_engine_types():
    """Both seed steppers are views of the one engine."""
    from repro.core import consensus as C
    assert cq.ADMMConfig is E.EngineConfig
    assert cq.ADMMState is E.EngineState
    assert C.ConsensusState is E.EngineState


# --------------------------------------------------- layer-aware modes ----
def test_layerwise_payload_leq_whole_model_quantizer():
    """Heterogeneous per-layer range decay: per-leaf groups pay fewer bits
    than the whole-model quantizer (the slow layer no longer drags every
    coordinate up the Eq. (18) bit-growth ladder)."""
    n = 4
    key = jax.random.PRNGKey(0)
    cfg = QuantConfig(b0=4, omega=0.99, b_overhead=64)

    def make_theta(t, k):
        kw, kb = jax.random.split(k)
        return {"w": 5.0 * (0.995 ** t) * jax.random.normal(kw, (n, 128)),
                "b": 0.05 * (0.6 ** t) * jax.random.normal(kb, (n, 256))}

    totals = {}
    for groups in ("model", "leaf"):
        theta0 = make_theta(0, jax.random.PRNGKey(99))
        gids = E.resolve_groups(theta0, groups)
        state = E.GroupQuantState.create(theta0, max(gids) + 1, b0=cfg.b0)
        total = 0.0
        for t in range(40):
            theta = make_theta(t, jax.random.fold_in(key, t))
            state, _, bits, payload = E.grouped_quantize_step(
                state, theta, jax.random.fold_in(key, 1000 + t), cfg, gids)
            total += float(payload.sum())
        totals[groups] = total
    assert totals["leaf"] <= totals["model"], totals
    # and decisively so on this construction
    assert totals["leaf"] < 0.8 * totals["model"], totals


def _hetero_consensus(n=6):
    key = jax.random.PRNGKey(0)
    targets = {"w": 5.0 * jax.random.normal(key, (n, 12, 12)),
               "b": jax.random.normal(jax.random.fold_in(key, 1), (n, 256))}

    def grad_fn(theta, batch):
        del batch
        # different curvature => different per-layer convergence rates
        return {"w": 0.05 * (theta["w"] - targets["w"]),
                "b": theta["b"] - targets["b"]}

    return targets, grad_fn


def _run_engine_training(cfg, targets, grad_fn, iters=60, n=6):
    g = random_bipartite_graph(n, 0.5, seed=0)
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=10, local_lr=0.1)
    theta0 = jax.tree_util.tree_map(jnp.zeros_like, targets)
    state = E.init_state(theta0, cfg, solver)
    step = jax.jit(E.make_step(g, cfg, solver))
    total_bits = 0.0
    group_tx = None
    for i in range(iters):
        state, m = step(state, None, jax.random.PRNGKey(i))
        # payload_bits now counts only transmitted bits — no tx_mask needed
        total_bits += float(m["payload_bits"].sum())
        gt = np.asarray(m["group_tx"])
        group_tx = gt if group_tx is None else group_tx + gt
    return state, total_bits, group_tx


def test_layerwise_payload_leq_whole_model_end_to_end():
    targets, grad_fn = _hetero_consensus()
    totals = {}
    for groups in ("model", "leaf"):
        cfg = E.EngineConfig(rho=0.5, quantize=QuantConfig(b0=4, omega=0.99),
                             groups=groups)
        _, total, _ = _run_engine_training(cfg, targets, grad_fn)
        totals[groups] = total
    assert totals["leaf"] <= totals["model"], totals


def test_group_censoring_silences_quiet_layers():
    """censor_mode="group": the converged layer stops transmitting while
    the slow layer keeps going — fewer group transmissions than global."""
    targets, grad_fn = _hetero_consensus()
    tx = {}
    for mode in ("global", "group"):
        cfg = E.EngineConfig(rho=0.5, censor=CensorConfig(tau0=2.0, xi=0.97),
                             quantize=QuantConfig(b0=6, omega=0.99),
                             groups="leaf", censor_mode=mode)
        _, total, group_tx = _run_engine_training(cfg, targets, grad_fn,
                                                  iters=80)
        tx[mode] = (total, group_tx.sum())
    assert tx["group"][1] < tx["global"][1]      # fewer group transmissions
    assert tx["group"][0] < tx["global"][0]      # fewer bits on the wire


def test_group_spec_validation():
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((2, 4))}
    assert E.resolve_groups(tree, "model") == (0, 0)
    assert E.resolve_groups(tree, "leaf") == (0, 1)
    assert E.resolve_groups(tree, (0, 0)) == (0, 0)
    assert E.group_dims(tree, (0, 1)) == (3, 4)
    assert E.group_dims(tree, (0, 0)) == (7,)
    with pytest.raises(ValueError):
        E.resolve_groups(tree, (0,))             # wrong arity
    with pytest.raises(ValueError):
        E.resolve_groups(tree, (0, 2))           # non-contiguous ids


# ------------------------------------------------- payload accounting ----
@pytest.mark.parametrize("censor_mode", ["global", "group"])
@pytest.mark.parametrize("scheme", ALL_VARIANTS)
def test_censored_rounds_cost_zero_payload_flat(linreg, scheme, censor_mode):
    """Censoring's value proposition: a suppressed link costs ZERO bits.
    Every algorithm variant, both censor modes, flat (one-leaf) path."""
    g, prob = linreg
    cfg = ab.ALL_SCHEMES[scheme](rho=1.0)
    cfg = dataclasses.replace(cfg, censor_mode=censor_mode)
    _, out = cq.run(g, prob, cfg, dim=DIM, iters=ITERS, seed=3)
    tx = np.asarray(out["tx_mask"])
    payload = np.asarray(out["payload_bits"])
    candidate = np.asarray(out["candidate_payload_bits"])
    assert (payload[tx == 0] == 0).all(), scheme
    assert (payload <= candidate + 1e-6).all(), scheme
    if cfg.censor.enabled:
        assert (tx == 0).any(), f"{scheme}: censoring never triggered"
    if censor_mode == "global":
        # transmitted rounds cost exactly the candidate payload
        np.testing.assert_array_equal(payload[tx == 1], candidate[tx == 1])


@pytest.mark.parametrize("censor_mode", ["global", "group"])
def test_censored_rounds_cost_zero_payload_tree(censor_mode):
    """Same invariant on the multi-leaf packed path with per-leaf groups:
    fully censored workers pay nothing; in group mode, partially censored
    workers pay only for their transmitted groups."""
    targets, grad_fn = _hetero_consensus()
    g = random_bipartite_graph(6, 0.5, seed=0)
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=10, local_lr=0.1)
    cfg = E.EngineConfig(rho=0.5, censor=CensorConfig(tau0=5.0, xi=0.99),
                         quantize=QuantConfig(b0=6, omega=0.99),
                         groups="leaf", censor_mode=censor_mode)
    theta0 = jax.tree_util.tree_map(jnp.zeros_like, targets)
    state = E.init_state(theta0, cfg, solver)
    step = jax.jit(E.make_step(g, cfg, solver))
    saw_censored = False
    for i in range(80):
        state, m = step(state, None, jax.random.PRNGKey(i))
        tx = np.asarray(m["tx_mask"])
        payload = np.asarray(m["payload_bits"])
        candidate = np.asarray(m["candidate_payload_bits"])
        assert (payload[tx == 0] == 0).all()
        assert (payload <= candidate + 1e-4).all()
        if censor_mode == "group":
            # group-mode payload = exactly the transmitted groups' bits
            dims = np.asarray(E.group_dims(state.theta,
                                           E.resolve_groups(state.theta,
                                                            "leaf")),
                              np.float32)
            per_group = (np.asarray(m["bits_per_group"]) * dims[None, :]
                         + cfg.quantize.b_overhead)
            want = (per_group * np.asarray(m["group_tx"])).sum(-1)
            np.testing.assert_allclose(payload, want, rtol=1e-6)
        saw_censored |= bool((tx == 0).any())
    assert saw_censored, "censoring never triggered — test is vacuous"


# ------------------------------------------------------ packed fast path ----
def test_split_tree_matches_flat_quantized(linreg):
    """The packed multi-leaf path reproduces the flat seed-golden path
    bit-for-bit on full CQ-GGADMM: packing a split tree restores exactly
    the flat buffer, the G=1 segment range equals the flat max, and the
    packed uniform draw equals the flat draw."""
    g, prob = linreg
    cfg = ab.ALL_SCHEMES["cq-ggadmm"](rho=1.0)
    cut = 5
    flat0 = jnp.zeros((N_WORKERS, DIM), jnp.float32)
    tree0 = {"a": flat0[:, :cut], "b": flat0[:, cut:]}
    _, out_flat = E.run(g, cfg, E.ExactSolver(prob), flat0, ITERS, seed=3,
                        extra_metrics=E.flat_metrics(g))
    _, out_tree = E.run(g, cfg, E.ExactSolver(_split_problem(prob, cut)),
                        tree0, ITERS, seed=3,
                        extra_metrics=lambda s, b: {
                            "theta": jnp.concatenate(
                                [s.theta["a"], s.theta["b"]], axis=1)})
    np.testing.assert_array_equal(np.asarray(out_flat["tx_mask"]),
                                  np.asarray(out_tree["tx_mask"]))
    np.testing.assert_array_equal(np.asarray(out_flat["payload_bits"]),
                                  np.asarray(out_tree["payload_bits"]))
    np.testing.assert_array_equal(np.asarray(out_tree["theta"][-1]),
                                  np.asarray(out_flat["theta"][-1]))


def test_engine_fused_kernel_matches_unfused_reference_bitwise():
    """use_pallas_quant=True (one fused pallas_call over the packed buffer,
    interpret mode) vs the jnp packed oracle: identical PRNG, identical
    math => bit-for-bit equal trajectories, replicas, and payload."""
    targets, grad_fn = _hetero_consensus()
    g = random_bipartite_graph(6, 0.5, seed=0)
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=10, local_lr=0.1)
    states, totals = {}, {}
    for use_kernel in (False, True):
        cfg = E.EngineConfig(rho=0.5, quantize=QuantConfig(b0=4, omega=0.99),
                             groups="leaf", use_pallas_quant=use_kernel)
        theta0 = jax.tree_util.tree_map(jnp.zeros_like, targets)
        state = E.init_state(theta0, cfg, solver)
        step = jax.jit(E.make_step(g, cfg, solver))
        total = 0.0
        for i in range(10):
            state, m = step(state, None, jax.random.PRNGKey(i))
            total += float(m["payload_bits"].sum())
        states[use_kernel] = state
        totals[use_kernel] = total
    assert totals[True] == totals[False]
    for leaf_a, leaf_b in zip(
            jax.tree_util.tree_leaves(states[True].quant.q_hat),
            jax.tree_util.tree_leaves(states[False].quant.q_hat)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
    for leaf_a, leaf_b in zip(
            jax.tree_util.tree_leaves(states[True].theta),
            jax.tree_util.tree_leaves(states[False].theta)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
    np.testing.assert_array_equal(
        np.asarray(states[True].quant.bits_prev),
        np.asarray(states[False].quant.bits_prev))


def test_engine_pytree_kernels_match_plain():
    """Leaf-wise Pallas routing (interpret mode on CPU) reproduces the
    plain path on a multi-leaf tree."""
    targets, grad_fn = _hetero_consensus()
    outs = {}
    for use_kernel in (False, True):
        cfg = E.EngineConfig(rho=0.5, quantize=QuantConfig(b0=4, omega=0.99),
                             groups="leaf", use_pallas_mix=use_kernel,
                             use_pallas_quant=use_kernel)
        state, total, _ = _run_engine_training(cfg, targets, grad_fn,
                                               iters=10)
        outs[use_kernel] = (np.asarray(state.theta["b"]), total)
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=1e-5, atol=1e-5)
    assert outs[True][1] == outs[False][1]


# -------------------------------------------------------- acting rows ----
def _rowwise_regression(n=6):
    """Per-worker linear regression whose data rides in ``batch`` (row-wise
    ``grad_fn``, a vmap over workers): two leaves, each its own group."""
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (n, 16, 5))
    w_true = jax.random.normal(kw, (n, 5, 3))
    batch = {"x": x, "y": x @ w_true + 0.5}

    def loss(p, b):
        return jnp.mean(jnp.square(b["x"] @ p["w"] + p["b"] - b["y"]))

    def grad_fn(theta, b):
        return jax.vmap(jax.grad(loss))(theta, b)

    theta0 = {"w": jnp.zeros((n, 5, 3)), "b": jnp.zeros((n, 3))}
    return theta0, batch, grad_fn


def _masked_path(monkeypatch):
    monkeypatch.setattr(E, "_phase_rows", lambda *a: (None, None))


@pytest.mark.parametrize("participation", [False, True],
                         ids=["synchronous", "participation"])
def test_acting_rows_match_masked_path(monkeypatch, participation):
    """An alternating step that solves and quantizes only each phase's
    acting rows (non-contiguous heads and tails, Adam, data in the batch)
    matches the masked path: identical transmit decisions, payloads, bit
    widths and quantizer replicas; states equal to rounding."""
    n = 6
    g = random_bipartite_graph(n, 0.5, seed=0)
    heads = np.flatnonzero(g.head_mask)
    assert np.any(np.diff(heads) > 1)
    assert np.any(np.diff(np.flatnonzero(~g.head_mask)) > 1)
    theta0, batch, grad_fn = _rowwise_regression(n)
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=3, local_lr=0.05)
    cfg = E.EngineConfig(rho=0.5, censor=CensorConfig(tau0=0.05, xi=0.9),
                         quantize=QuantConfig(b0=3, omega=0.99),
                         groups="leaf", censor_mode="group")
    part = (jnp.asarray([1, 0, 1, 1, 1, 0], jnp.float32)
            if participation else None)

    def run():
        step = jax.jit(E.make_step(g, cfg, solver))
        state = E.init_state(theta0, cfg, solver)
        hist = []
        for i in range(5):
            args = (state, batch, jax.random.PRNGKey(i))
            state, m = step(*args, part) if participation else step(*args)
            hist.append(m)
        return state, hist

    s_rows, m_rows = run()
    _masked_path(monkeypatch)
    s_mask, m_mask = run()
    for a, b in zip(m_rows, m_mask):
        for name in ("tx_mask", "payload_bits", "bits_per_group",
                     "censor_mask", "offered_payload_bits", "group_tx"):
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]), err_msg=name)
    assert sum(float(m["tx_mask"].sum()) for m in m_rows) > 0
    for a, b in zip(jax.tree_util.tree_leaves(s_rows.quant),
                    jax.tree_util.tree_leaves(s_mask.quant)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in ("theta", "alpha", "opt_mu", "opt_nu", "theta_hat"):
        for a, b in zip(jax.tree_util.tree_leaves(getattr(s_rows, name)),
                        jax.tree_util.tree_leaves(getattr(s_mask, name))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7, err_msg=name)


def _phase_rows_read(step, state, batch):
    from repro.obs import metrics as obs_metrics
    c = obs_metrics.engine_phase_rows_counter()
    keys = [(part, path) for part in ("solve", "quantize")
            for path in ("rows", "masked")]
    before = {k: c.value(part=k[0], path=k[1]) for k in keys}
    jax.eval_shape(step, state, batch, jax.random.PRNGKey(0))
    return {k: c.value(part=k[0], path=k[1]) - before[k] for k in keys}


# (id, solver, batch?, alternating, backend, expected rows per part/path)
PHASE_ROWS = [
    ("cells", "inexact", True, True, "dense",
     {("solve", "rows"): 2, ("quantize", "rows"): 2}),
    ("no-batch", "inexact", False, True, "dense",
     {("solve", "masked"): 4, ("quantize", "rows"): 2}),
    ("exact", "exact", False, True, "dense",
     {("solve", "masked"): 4, ("quantize", "rows"): 2}),
    ("jacobian", "inexact", True, False, "dense",
     {("solve", "masked"): 2, ("quantize", "masked"): 2}),
    ("sharded", "inexact", True, True, "sharded",
     {("solve", "masked"): 4, ("quantize", "masked"): 4}),
]


@pytest.mark.parametrize("solver_kind,with_batch,alternating,backend,want",
                         [c[1:] for c in PHASE_ROWS],
                         ids=[c[0] for c in PHASE_ROWS])
def test_phase_rows_counter(solver_kind, with_batch, alternating, backend,
                            want):
    """``engine_phase_rows`` counts the rows each traced phase computes:
    the benchmark cells' shape (2 workers, one head, an inexact solver fed
    a batch) reads ``rows`` 1 + 1 for both parts; a solve without a batch
    or with an exact solver, the Jacobian step and the sharded backend
    read ``masked``, N rows a phase."""
    from repro.core.graph import pod_pair_graph
    from repro.core.solvers import LinearRegressionProblem as LRP
    n = 2
    theta0, batch, grad_fn = _rowwise_regression(n)
    if solver_kind == "exact":
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (n, 8, 4)))
        solver = E.ExactSolver(LRP(jnp.asarray(x), jnp.asarray(x[..., 0])))
        theta0 = jnp.zeros((n, 4))
    elif with_batch:
        solver = E.InexactSolver(grad_fn=grad_fn, local_steps=2)
    else:                    # a grad_fn that closes over N rows of data
        solver = E.InexactSolver(
            grad_fn=lambda theta, _: grad_fn(theta, batch), local_steps=2)
    cfg = E.EngineConfig(rho=0.5, quantize=QuantConfig(b0=3, omega=0.99),
                         alternating=alternating, mix_backend=backend)
    step = E.make_step(pod_pair_graph(), cfg, solver)
    state = E.init_state(theta0, cfg, solver)
    got = _phase_rows_read(step, state, batch if with_batch else None)
    assert got == {k: want.get(k, 0) for k in got}


# ---------------------------------------------------- exchange layout ----
def _layout_graph(name):
    """``one-edge``: the cells' two workers; ``bipartite``: six workers
    whose heads and tails are not contiguous, some of degree 2 or more."""
    from repro.core.graph import pod_pair_graph
    if name == "one-edge":
        return pod_pair_graph()
    g = random_bipartite_graph(6, 0.5, seed=0)
    assert int(np.max(g.degrees)) > 1
    return g


def _leafy_problem(n):
    """Per-worker regression over a tree of leaves of rank 2, 3 and 4
    (per worker: 5, 3 x 4 and 2 x 3 x 4), its data in the batch."""
    kx, kw = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(kx, (n, 16, 4))
    batch = {"x": x, "y": jnp.tanh(x @ jax.random.normal(kw, (n, 4, 5)))}

    def loss(p, b):
        h = b["x"] @ p["w"] + jnp.sum(p["u"], axis=0) \
            + jnp.mean(p["k"], axis=(0, 1))[None, :1]
        return jnp.mean(jnp.square(h[:, :3] @ p["v"] + p["c"] - b["y"]))

    def grad_fn(theta, b):
        return jax.vmap(jax.grad(loss))(theta, b)

    theta0 = {"c": jnp.zeros((n, 5)), "k": jnp.zeros((n, 2, 3, 4)),
              "u": jnp.zeros((n, 3, 4)), "v": jnp.zeros((n, 3, 5)),
              "w": jnp.zeros((n, 4, 4))}
    return theta0, batch, grad_fn


def _layout_reads(step, *args):
    from repro.obs import metrics as obs_metrics
    c = obs_metrics.engine_exchange_layout_counter()
    keys = [(part, layout) for part in ("quantize", "censor", "mix", "dual")
            for layout in ("leaf", "packed")]
    before = {k: c.value(part=k[0], layout=k[1]) for k in keys}
    jax.eval_shape(step, *args)
    return {k: c.value(part=k[0], layout=k[1]) - before[k] for k in keys}


@pytest.mark.parametrize("groups", ["model", "leaf"])
@pytest.mark.parametrize("graph", ["one-edge", "bipartite"])
def test_leaf_quantize_is_the_packed_round(graph, groups):
    """Each phase's acting rows, quantized leaf by leaf in each leaf's own
    shape, give bit for bit what the packed ``(N, D)`` round gives on the
    same rows: ranges, bit widths, step sizes, payload and every
    reconstructed parameter, a degenerate group's passthrough included."""
    g = _layout_graph(graph)
    n = g.n
    theta0, _, _ = _leafy_problem(n)
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 10))
    theta = jax.tree_util.tree_map(
        lambda x: jax.random.normal(next(keys), x.shape), theta0)
    q_prev = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape), theta)
    q_prev["c"] = theta["c"]                 # a group with nothing to send
    gids = E.resolve_groups(theta, groups)
    n_groups = max(gids) + 1
    state = E.GroupQuantState(
        q_hat=q_prev,
        range_prev=jnp.full((n, n_groups), 0.3, jnp.float32),
        bits_prev=jnp.full((n, n_groups), 3.0, jnp.float32),
        delta_prev=jnp.full((n, n_groups), 0.1, jnp.float32),
        initialized=jnp.ones((n, n_groups), jnp.float32))
    qcfg = QuantConfig(b0=3, omega=0.99)
    key = jax.random.PRNGKey(13)
    for rows in (np.flatnonzero(g.head_mask), np.flatnonzero(~g.head_mask)):
        args = (E.take_rows(state, rows), E.take_rows(theta, rows), key,
                qcfg, gids)
        got = jax.jit(lambda *a: E.grouped_quantize_step(
            *a, rows=rows, n_workers=n), static_argnums=(3, 4))(*args)
        want = jax.jit(lambda *a: E._grouped_quantize_step_packed(
            *a, rows=rows, n_workers=n), static_argnums=(3, 4))(*args)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if groups == "leaf":
            assert bool(jnp.all(got[0].range_prev[:, 0] == 0.3))


@pytest.mark.parametrize("graph", ["one-edge", "bipartite"])
def test_leaf_round_follows_the_packed_round(graph):
    """Three rounds with every exchange part leaf by leaf against the same
    rounds on the packed ``(N, D)`` buffer (the Pallas options, interpreted
    here): identical transmit decisions, bit widths and payloads;
    ``theta_hat`` and ``alpha`` equal where every worker has one
    neighbour (the sums are then exact), within ``CROSS_PROGRAM_RTOL``
    otherwise. ``engine_exchange_layout`` reads ``leaf`` for all four
    parts, and ``packed`` for all but the censor under the options."""
    g = _layout_graph(graph)
    theta0, batch, grad_fn = _leafy_problem(g.n)
    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=2, local_lr=0.05)
    runs, reads = {}, {}
    for layout, pallas in (("leaf", False), ("packed", True)):
        cfg = E.EngineConfig(rho=0.5, censor=CensorConfig(tau0=0.05, xi=0.9),
                             quantize=QuantConfig(b0=3, omega=0.99),
                             use_pallas_mix=pallas, use_pallas_quant=pallas)
        step = E.make_step(g, cfg, solver)
        state = E.init_state(theta0, cfg, solver)
        reads[layout] = _layout_reads(step, state, batch,
                                      jax.random.PRNGKey(0))
        step = jax.jit(step)
        hist = []
        for i in range(3):
            state, m = step(state, batch, jax.random.PRNGKey(i))
            hist.append(m)
        runs[layout] = (state, hist)
    for a, b in zip(runs["leaf"][1], runs["packed"][1]):
        for name in ("tx_mask", "bits_per_group", "payload_bits"):
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]), err_msg=name)
    assert sum(float(m["tx_mask"].sum()) for m in runs["leaf"][1]) > 0
    for part in ("theta_hat", "alpha"):
        got, want = (getattr(runs[k][0], part) for k in ("leaf", "packed"))
        if graph == "one-edge":
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert E.tree_rel_err(got, want) <= E.CROSS_PROGRAM_RTOL, part
    parts = ("quantize", "censor", "mix", "dual")
    assert reads["leaf"] == {(p, lay): int(lay == "leaf") * 2 ** (p != "dual")
                             for p in parts for lay in ("leaf", "packed")}
    assert reads["packed"] == {
        (p, lay): int(lay == ("leaf" if p == "censor" else "packed"))
        * 2 ** (p != "dual") for p in parts for lay in ("leaf", "packed")}


def _smoke_xlstm_round(pallas: bool):
    """The smoke xLSTM's round (two workers on this one device, one edge)
    lowered, with the engine's counters of exchange layouts read over its
    trace, and the packed width D of its tree."""
    from repro.configs import base
    from repro.launch import train
    from repro.obs import metrics as obs_metrics
    args = train.build_parser().parse_args([
        "--arch", "xlstm-125m", "--smoke", "--workers", "2", "--batch", "2",
        "--seq", "16", "--local-steps", "1", "--bits", "4"])
    setup = train.admm_setup(base.get_smoke_config("xlstm-125m"), args)
    assert setup.mesh is None
    ecfg = dataclasses.replace(setup.ecfg, use_pallas_mix=pallas,
                               use_pallas_quant=pallas)
    state = jax.eval_shape(lambda: E.init_state(setup.stacked_params(),
                                                ecfg, setup.solver))
    c = obs_metrics.engine_exchange_layout_counter()
    keys = [(p, lay) for p in ("quantize", "censor", "mix", "dual")
            for lay in ("leaf", "packed")]
    before = {k: c.value(part=k[0], layout=k[1]) for k in keys}
    text = jax.jit(setup.build_step(ecfg)).lower(
        state, setup.batch(0), setup.key(0)).as_text()
    reads = {k: c.value(part=k[0], layout=k[1]) - before[k] for k in keys}
    return text, reads, E.tree_dim(state.theta)


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["default", "pallas-options"])
def test_one_device_round_has_no_packed_row(pallas):
    """The smoke xLSTM round on one device holds no array whose last axis
    is the tree's whole width D: quantize, censor, mix and dual work on
    each leaf in its own shape, and ``engine_exchange_layout`` reads
    ``leaf`` for each. Under the Pallas options, which take the packed
    ``(N, D)`` buffer, the same search finds it."""
    import re
    text, reads, d = _smoke_xlstm_round(pallas)
    packed_rows = re.findall(r"[<x]%dx[a-z]\w*>" % d, text)
    parts = ("quantize", "censor", "mix", "dual")
    if not pallas:
        assert not packed_rows, packed_rows[:5]
        assert all(reads[(p, "leaf")] >= 1 and reads[(p, "packed")] == 0
                   for p in parts), reads
    else:
        assert packed_rows
        assert all(reads[(p, "packed")] >= 1 for p in parts
                   if p != "censor"), reads
