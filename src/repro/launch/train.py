"""Decentralized (CQ-GGADMM) / baseline (FSDP-Adam) LM training driver.

CPU-friendly end-to-end entry point: trains a reduced or full architecture
on the synthetic-but-learnable token stream, with the paper's censoring and
quantization live, logging loss / consensus error / transmitted bits, and
checkpointing. The workers are placed on the devices the process sees
(``worker_placement``): several workers to a device, one worker per
device, or, with more devices than workers, each worker's parameters split
over several devices by the tensor-parallel rules of
``launch/sharding.py``.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --workers 4 --steps 50 --mode admm

Campaign entry (DESIGN.md §Campaign): ``campaign_lm_run`` wraps one
consensus-LM training run as a resumable campaign stage function — the
layer-wise bits-to-loss grid (groups x censor_mode x mix_backend) and the
quantized-vs-unquantized baseline pair run as the ``lm-sweep`` campaign,
with full engine state checkpointed through the run context so a killed
sweep resumes mid-run bit-exactly:

    PYTHONPATH=src python -m repro.launch.train --campaign lm-sweep \
        [--resume] [--campaign-only lm-grid]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import npz as ckpt
from repro.configs import base
from repro.core import engine as E
from repro.core import graph as G
from repro.core import topology as topo_lib
from repro.core.censoring import CensorConfig
from repro.core.quantization import QuantConfig
from repro.data.lm import SyntheticLM, SyntheticLMConfig, model_batch
from repro.launch import compile_cache
from repro.launch import sharding as SH
from repro.models import registry
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.ledger import CommLedger
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.runtime import partitioning as P


def parse_churn(spec):
    """Parse ``--fleet-churn`` "round:leave:join[,round:leave:join...]"
    into a tuple of :class:`repro.fleet.ChurnEvent`."""
    from repro.fleet import ChurnEvent
    if not spec:
        return ()
    events = []
    for item in spec.split(","):
        parts = item.split(":")
        if len(parts) != 3:
            raise SystemExit(
                f"[train] bad --fleet-churn item {item!r} — expected "
                f"round:leave:join (e.g. '10:2:1,20:1:0')")
        try:
            events.append(ChurnEvent(round=int(parts[0]),
                                     leave=int(parts[1]),
                                     join=int(parts[2])))
        except (ValueError, AssertionError) as e:
            raise SystemExit(
                f"[train] bad --fleet-churn item {item!r}: {e}") from e
    return tuple(events)


def run_fleet(cfg, args, graph, ecfg, solver, loss_fn, params, data) -> dict:
    """Drive the consensus-LM run through FleetSim (DESIGN.md §Fleet):
    straggler timeouts fold into the censor mask, late updates land through
    the bounded-staleness buffer, churn redraws the graph and remaps state.
    With all fault knobs at their defaults every round dispatches to the
    plain synchronous engine step (the bit-identity contract pinned in
    tests/test_fleet.py; per-round keys are fold_in-derived, so the
    trajectory differs from run_admm's own loop only through its key
    schedule)."""
    from repro.fleet import FaultConfig, FleetConfig, FleetSim
    fcfg = FleetConfig(
        rounds=args.steps,
        faults=FaultConfig(participation=args.fleet_participation,
                           staleness=args.fleet_staleness,
                           stale_frac=args.fleet_stale_frac,
                           churn=parse_churn(args.fleet_churn),
                           seed=args.fleet_seed),
        graph_seed=args.seed, seed=args.seed)
    per = args.batch // args.workers

    def batch_fn(r, members):
        raw = data.worker_batch(r, len(members), per)
        return model_batch(cfg, raw, key=jax.random.PRNGKey(r))

    sim = FleetSim(args.workers, ecfg, fcfg, params, solver=solver,
                   extra_metrics=E.consensus_metrics(loss_fn),
                   batch_fn=batch_fn, graph0=graph)
    t0 = time.time()
    fs, m = sim.run()
    history = [float(x) for x in np.asarray(m["loss"])]
    total_bits = float(np.sum(m["payload_bits_total"]))
    for i in range(args.steps):
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"round {i:4d}  loss={history[i]:.4f}  "
                  f"tx={int(m['tx_count'][i])}/{int(m['n_members'][i])}  "
                  f"bits={float(m['payload_bits_total'][i]):.3e}")
    for ev in m["churn_log"]:
        print(f"[fleet] round {ev['round']}: left={ev['left']} "
              f"joined={ev['joined']} -> {ev['n_members']} members")
    print(f"[fleet] {args.steps} rounds, participation="
          f"{args.fleet_participation} staleness={args.fleet_staleness}: "
          f"final_loss={history[-1]:.4f} cum_bits={total_bits:.3e} "
          f"({(time.time() - t0) / args.steps:.2f}s/round)")
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, fs.engine.theta)
    return {"final_loss": history[-1], "history": history,
            "total_bits": total_bits,
            "n_groups": fs.engine.quant.n_groups,
            "churn_log": m["churn_log"]}


def engine_config(cfg, args) -> E.EngineConfig:
    """The ``EngineConfig`` the CLI flags describe."""
    try:
        return E.EngineConfig(
            rho=args.rho,
            censor=CensorConfig(tau0=args.tau0, xi=args.xi)
            if args.tau0 > 0 else CensorConfig(),
            quantize=QuantConfig(b0=args.bits, omega=args.omega)
            if args.quantize else None,
            groups=args.groups,
            censor_mode=args.censor_mode,
            mix_backend=args.mix_backend,
            regroup_every=args.regroup_every)
    except E.GroupSpecError as e:
        raise SystemExit(
            f"[train] bad --groups spec: {e}\n"
            f"[train] buckets available for {cfg.name}: "
            f"{registry.param_bucket_names(cfg)}") from e


def worker_placement(n_workers: int):
    """The mesh the workers' rows are placed on. Where the process sees
    more devices than workers and the workers divide them, each worker
    spans ``devices / n_workers`` of them: a ``(workers, model)`` mesh,
    each worker's parameters split over its ``model`` devices
    (``AdmmSetup.shardings``). Otherwise the ``workers`` mesh over
    ``topology.worker_devices`` — one block of worker rows per device — or
    ``None`` when that is one device, where everything stays on the
    default device. Says so when it leaves local devices idle."""
    local = jax.devices()
    if len(local) > n_workers and len(local) % n_workers == 0:
        return topo_lib.make_mesh((n_workers, len(local) // n_workers),
                                  ("workers", "model"), devices=local)
    devices = topo_lib.worker_devices(n_workers)
    n_local = len(local)
    if len(devices) < n_local:
        print(f"[train] {n_workers} workers take equal row blocks on "
              f"{len(devices)} of the {n_local} local devices")
    return None if len(devices) == 1 else topo_lib.worker_mesh(devices)


@dataclasses.dataclass
class AdmmSetup:
    """Everything one consensus-LM run is built from — model, graph,
    engine config, solver, data and worker placement — so that the CLI
    and callers that drive the same step themselves (``chip_smoke.py``)
    build it one way."""

    cfg: base.ModelConfig
    args: argparse.Namespace
    graph: object
    ecfg: E.EngineConfig
    solver: E.InexactSolver
    loss_fn: object
    data: SyntheticLM
    mesh: object = None

    def _sharding(self, x):
        """A worker-stacked array's placement by its worker axis alone
        (a batch; on a mesh without other axes, any state leaf)."""
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec("workers") if x.ndim
                             else PartitionSpec())

    @property
    def split(self) -> bool:
        """Whether each worker spans several devices (a ``model`` axis)."""
        return topo_lib.devices_per_worker(self.mesh, "workers") > 1

    def shardings(self, tree):
        """The placement of every leaf of a worker-stacked tree (parameters,
        an ``EngineState``), by its path: rows over ``workers`` and, where
        each worker spans several devices, the tensor-parallel split of
        ``launch/sharding.py`` over ``model`` (heads, MLP width, vocab)."""
        from jax.sharding import NamedSharding, PartitionSpec
        if not self.split:
            return jax.tree_util.tree_map(self._sharding, tree)

        def leaf(path, x):
            if not x.ndim:
                return NamedSharding(self.mesh, PartitionSpec())
            return NamedSharding(self.mesh, SH.param_spec(
                jax.tree_util.keystr(path), x.shape, self.mesh, self.cfg,
                worker_axis="workers"))
        return jax.tree_util.tree_map_with_path(leaf, tree)

    def _jit_placed(self, fn):
        """``fn()`` run under jit, its outputs created directly in their
        worker placement (never first gathered on one device)."""
        if self.mesh is None:
            return jax.jit(fn)()
        out = self.shardings(jax.eval_shape(fn))
        return jax.jit(fn, out_shardings=out)()

    def stacked_params(self):
        """Per-worker parameters, unplaced (to trace or ``eval_shape``):
        one shared init broadcast to every worker (the paper's
        theta_n^0 = 0 analog; workers diverge only through their local
        data)."""
        one = registry.init_params(self.cfg,
                                   jax.random.PRNGKey(self.args.seed))
        n = self.args.workers
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)

    def init_params(self):
        """Per-worker parameters, leading axis = worker."""
        return self._jit_placed(self.stacked_params)

    def init_state(self, ecfg: E.EngineConfig | None = None
                   ) -> E.EngineState:
        """A fresh engine state (the step donates the one it is given)."""
        ecfg = ecfg or self.ecfg
        return self._jit_placed(
            lambda: E.init_state(self.stacked_params(), ecfg, self.solver))

    def build_step(self, ecfg: E.EngineConfig | None = None):
        """The jitted round. It donates the state it is given: at LM
        width the input and output states cannot both be live. Where each
        worker spans several devices, the model runs under the
        tensor-parallel activation rules (``P.logical_sharding``) and the
        state leaves the round in the placement it came in. Where workers
        take whole devices, the model sees the ``workers`` mesh with no
        rule but the worker axis: the flash attention then runs inside
        ``shard_map``, each device on its own workers (GSPMD cannot
        partition its custom call)."""
        split = self.split
        specs = None
        if split:
            specs = jax.tree_util.tree_map(
                lambda s: s.spec,
                self.shardings(jax.eval_shape(self.stacked_params)))
        step = E.make_step(self.graph, ecfg or self.ecfg, self.solver,
                           extra_metrics=E.consensus_metrics(self.loss_fn,
                                                             split=split),
                           mesh=self.mesh,
                           worker_axis="workers" if self.mesh else None,
                           leaf_specs=specs)
        if self.mesh is not None:
            rules = (SH.activation_rules(self.mesh, self.cfg, batch_axes=(),
                                         worker_mode=True,
                                         worker_axis="workers")
                     if split else {"worker": "workers"})
            inner = step

            def step(state, batch, key):
                with P.logical_sharding(self.mesh, rules):
                    state, metrics = inner(state, batch, key)
                if split:
                    state = jax.lax.with_sharding_constraint(
                        state, self.shardings(state))
                return state, metrics
        return jax.jit(step, donate_argnums=0)

    def batch(self, i: int):
        """Round ``i``'s stacked per-worker batch, in worker placement
        (program span ``repro.train.input``; its child
        ``repro.train.input.generate`` times the token stream alone)."""
        a = self.args
        with obs_trace.span("input", "train", track="input"):
            with obs_trace.span("input.generate", "train", track="input"):
                raw = self.data.worker_batch(i, a.workers,
                                             a.batch // a.workers)
            b = model_batch(self.cfg, raw, key=jax.random.PRNGKey(i))
            if self.mesh is None:
                return b
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._sharding(x)), b)

    @staticmethod
    def key(i: int) -> jax.Array:
        """Round ``i``'s engine key (quantizer uniforms)."""
        return jax.random.PRNGKey(1000 + i)


def _worker_vmap(fn):
    """``jax.vmap`` over the worker axis. Under a mesh with a ``workers``
    axis (``AdmmSetup.build_step``), the mapped axis is that mesh axis
    (``spmd_axis_name``): a ``shard_map`` inside the model, the flash
    attention's, then keeps each worker on its own devices instead of
    replicating every worker on every device."""
    def mapped(*args):
        mesh = P.current_mesh()
        axis = ("workers" if mesh is not None
                and "workers" in mesh.axis_names else None)
        return jax.vmap(fn, spmd_axis_name=axis)(*args)
    return mapped


def admm_setup(cfg, args) -> AdmmSetup:
    obs_metrics.install_compile_listener()

    def grad_fn(theta, batch):
        return _worker_vmap(lambda p, b: jax.grad(
            lambda pp: registry.lm_loss(pp, cfg, b)[0])(p))(theta, batch)

    def loss_fn(theta, batch):
        return jnp.mean(_worker_vmap(
            lambda p, b: registry.lm_loss(p, cfg, b)[0])(theta, batch))

    return AdmmSetup(
        cfg=cfg, args=args,
        graph=G.worker_graph(args.workers, args.topology),
        ecfg=engine_config(cfg, args),
        solver=E.InexactSolver(grad_fn=grad_fn,
                               local_steps=args.local_steps,
                               local_lr=args.lr),
        loss_fn=loss_fn,
        data=SyntheticLM(SyntheticLMConfig(cfg.vocab_size, args.seq,
                                           seed=args.seed)),
        mesh=worker_placement(args.workers))


def run_admm(cfg, args) -> dict:
    setup = admm_setup(cfg, args)
    ecfg = setup.ecfg
    # resolve the spec against the real tree up front: a semantically
    # malformed spec (unknown/empty bucket, bad index buckets) must fail
    # here with the model's bucket vocabulary, not deep inside the jit
    try:
        cur_ids = E.resolve_groups(jax.eval_shape(setup.stacked_params),
                                   ecfg.groups)
    except E.GroupSpecError as e:
        raise SystemExit(
            f"[train] bad --groups spec for {cfg.name}: {e}\n"
            f"[train] buckets: {registry.param_buckets(cfg)}") from e
    grouper = E.AutoGrouper.from_config(ecfg)

    if args.fleet:
        if args.regroup_every:
            raise SystemExit(
                "[train] --fleet is incompatible with --regroup-every: "
                "auto regrouping re-jits the step on a schedule the fleet "
                "driver owns (churn already rebuilds it)")
        return run_fleet(cfg, args, setup.graph, ecfg, setup.solver,
                         setup.loss_fn, setup.init_params(), setup.data)
    state = setup.init_state()
    n_groups = state.quant.n_groups
    step = setup.build_step()
    # host-side observers only: the ledger reads device_get copies of the
    # metrics the step already returns, the span brackets the Python-level
    # round — neither adds an op to the jitted program (tests/test_obs.py
    # pins the jaxpr)
    ledger = CommLedger(setup.graph) if obs_trace.enabled() else None
    total_bits = 0.0
    t0 = time.time()
    history, tx_history, bits_history = [], [], []
    for i in range(args.steps):
        if grouper is not None and grouper.should_regroup(i):
            new_ids = grouper.regroup(state.theta, state.quant.q_hat)
            if new_ids != cur_ids:
                # stable-id regroup: carry conservative (R, b, Δ) per new
                # group, pin the spec to the explicit ids, re-jit the step
                state = E.EngineState(
                    theta=state.theta, theta_hat=state.theta_hat,
                    alpha=state.alpha,
                    quant=E.remap_group_state(state.quant, cur_ids,
                                              new_ids),
                    opt_mu=state.opt_mu, opt_nu=state.opt_nu, k=state.k)
                ecfg = dataclasses.replace(ecfg, groups=new_ids)
                step = setup.build_step(ecfg)
                cur_ids = new_ids
                n_groups = max(new_ids) + 1
                print(f"[train] step {i}: regrouped to G={n_groups} "
                      f"({new_ids})")
        batch = setup.batch(i)
        with obs_trace.span("round", "engine", track="rounds",
                            args={"round": i}):
            state, m = step(state, batch, setup.key(i))
            if ledger is not None:
                ledger.update(jax.device_get(m))
        bits = float(m["payload_bits"].sum())   # already tx-masked
        total_bits += bits
        mean_bits = float(np.asarray(m["bits_per_group"]).mean())
        history.append(float(m["loss"]))
        tx_history.append(int(m["tx_mask"].sum()))
        bits_history.append(bits)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss={history[-1]:.4f}  "
                  f"consensus_err={float(m['consensus_err']):.3e}  "
                  f"tx={tx_history[-1]}/{args.workers}  "
                  f"groups={n_groups}  b/group={mean_bits:.1f}  "
                  f"bits={bits:.3e}  cum_bits={total_bits:.3e}  "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, state.theta)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, state.theta)
    return {"final_loss": history[-1], "history": history,
            "tx_history": tx_history, "bits_history": bits_history,
            "total_bits": total_bits, "n_groups": n_groups}


def run_fsdp(cfg, args) -> dict:
    params = registry.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw_init(params)
    acfg = AdamWConfig(lr=args.lr)

    @jax.jit
    def step(params, opt, batch):
        (loss, metr), grads = jax.value_and_grad(
            lambda p: registry.lm_loss(p, cfg, batch), has_aux=True)(params)
        params, opt = adamw_update(grads, opt, params, acfg)
        return params, opt, loss

    data = SyntheticLM(SyntheticLMConfig(cfg.vocab_size, args.seq,
                                         seed=args.seed))
    t0 = time.time()
    history = []
    for i in range(args.steps):
        raw = data.batch(i, args.batch)
        batch = model_batch(cfg, raw, key=jax.random.PRNGKey(i))
        params, opt, loss = step(params, opt, batch)
        history.append(float(loss))
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss={float(loss):.4f}  "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, params)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, params)
    return {"final_loss": history[-1], "history": history,
            "total_bits": 0.0}


# ------------------------------------------------------- campaign entry --
def campaign_lm_run(section, quantize=True, groups="model",
                    censor_mode="global", mix_backend="dense",
                    workers=4, steps=12, batch=8, seq=64, local_steps=2,
                    arch="tinyllama-1.1b", rho=0.01, tau0=5.0, xi=0.995,
                    bits=4, omega=0.999, lr=1e-3, seed=0, ckpt_every=3,
                    compare_with=None, ctx=None):
    """One consensus-LM training run as a campaign stage function.

    Deterministic given the config (per-step PRNG keys are derived from
    the step index), and resumable: the full ``EngineState`` plus the
    loss/bits history is checkpointed through ``ctx`` every
    ``ckpt_every`` steps, so a killed campaign restarts from the last
    complete step and finishes bit-exactly where an uninterrupted run
    would. Emits the run's metrics at ``section`` of BENCH_engine.json;
    with ``compare_with`` (a section path to an earlier quantized run),
    also emits the paper's quantization-saves-bits claim against it.
    """
    from repro.campaign.runner import FatalError
    from repro.campaign.store import Claim, Record

    cfg = base.get_smoke_config(arch)
    graph = G.worker_graph(workers, "random")
    ecfg = E.EngineConfig(
        rho=rho, censor=CensorConfig(tau0=tau0, xi=xi),
        quantize=QuantConfig(b0=bits, omega=omega) if quantize else None,
        groups=groups, censor_mode=censor_mode, mix_backend=mix_backend)

    def grad_fn(theta, b):
        return jax.vmap(lambda p, bb: jax.grad(
            lambda pp: registry.lm_loss(pp, cfg, bb)[0])(p))(theta, b)

    def loss_fn(theta, b):
        return jnp.mean(jax.vmap(
            lambda p, bb: registry.lm_loss(p, cfg, bb)[0])(theta, b))

    solver = E.InexactSolver(grad_fn=grad_fn, local_steps=local_steps,
                             local_lr=lr)
    one = registry.init_params(cfg, jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (workers,) + x.shape), one)
    state = E.init_state(params, ecfg, solver)
    step = jax.jit(E.make_step(graph, ecfg, solver,
                               extra_metrics=E.consensus_metrics(loss_fn)))
    data = SyntheticLM(SyntheticLMConfig(cfg.vocab_size, seq, seed=seed))

    loss_hist = np.full(steps, np.nan)
    bits_hist = np.full(steps, np.nan)
    start = 0
    if ctx is not None:
        restored = ctx.restore({"state": state, "loss": loss_hist,
                                "bits": bits_hist})
        if restored is not None:
            tree, start = restored
            state, loss_hist, bits_hist = (tree["state"], tree["loss"],
                                           tree["bits"])
            print(f"[lm-campaign] resumed {section[-1]} at step {start}")
    for i in range(start, steps):
        raw = data.worker_batch(i, workers, batch // workers)
        b = model_batch(cfg, raw, key=jax.random.PRNGKey(i))
        state, m = step(state, b, jax.random.PRNGKey(1000 + i))
        loss_hist[i] = float(m["loss"])
        bits_hist[i] = float(m["payload_bits"].sum())   # already tx-masked
        if ctx is not None and ((i + 1) % ckpt_every == 0
                                or i == steps - 1):
            ctx.checkpoint(i + 1, {"state": state, "loss": loss_hist,
                                   "bits": bits_hist})

    label = section[-1]
    total_bits = float(np.nansum(bits_hist))
    final_loss = float(loss_hist[-1])
    out = {"arch": cfg.name, "workers": workers, "steps": steps,
           "quantize": bool(quantize), "groups": groups,
           "censor_mode": censor_mode, "mix_backend": mix_backend,
           "final_loss": final_loss, "total_bits": total_bits,
           "loss_history": [float(x) for x in loss_hist],
           "resumed_from": start}
    print(f"[lm-campaign] {label}: final_loss={final_loss:.4f} "
          f"total_bits={total_bits:.4g} (groups={groups} "
          f"censor={censor_mode} backend={mix_backend})")
    claims = [Claim(f"lm_{label}_loss_finite".replace("|", "_"),
                    bool(np.isfinite(final_loss)), value=final_loss,
                    gate="finite")]
    if compare_with is not None:
        if ctx is None:
            raise FatalError("compare_with needs a run context")
        ref = ctx.store.section(tuple(compare_with))
        if ref is None:
            raise FatalError(f"section {compare_with} missing — run the "
                             f"quantized baseline first")
        saved = 1.0 - ref["total_bits"] / max(total_bits, 1e-9)
        ok = (ref["total_bits"] < 0.5 * total_bits
              and ref["final_loss"] < final_loss + 1.0)
        print(f"claim basis: quantization saved {saved:.0%} of bits, "
              f"dloss={ref['final_loss'] - final_loss:+.3f}")
        claims.append(Claim(
            "lm_quantization_saves_bits", ok, value=saved,
            gate="quantized bits < 0.5x unquantized, loss within 1.0"))
    return Record(section=tuple(section), data=out, claims=tuple(claims),
                  claims_path=("lm_sweep", "claims"))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=base.list_architectures())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers (whole periods "
                         "of its block unit, at most the published depth); "
                         "every width stays as published")
    ap.add_argument("--mode", default="admm", choices=("admm", "fsdp"))
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--topology", default="random",
                    choices=("random", "chain", "complete"))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--tau0", type=float, default=5.0)
    ap.add_argument("--xi", type=float, default=0.995)
    ap.add_argument("--quantize", action="store_true", default=True)
    ap.add_argument("--no-quantize", dest="quantize", action="store_false")
    ap.add_argument("--groups", default="model",
                    help="quantization group spec (DESIGN.md §Groups): "
                         "'model' = paper's whole-model mode (G=1), "
                         "'leaf' = L-FGADMM per-layer ranges, "
                         "'block:attn,mlp,embed[,rest]' = named buckets "
                         "over the registry's layer names, 'auto:K' = "
                         "<= K groups clustered from per-leaf range stats "
                         "(re-clustered every --regroup-every steps)")
    ap.add_argument("--regroup-every", type=int, default=0,
                    help="for --groups auto:K — re-cluster from the "
                         "running range statistics every this many steps "
                         "(0 keeps the initial shape-balanced partition)")
    ap.add_argument("--censor-mode", default="global",
                    choices=("global", "group"),
                    help="'global' = paper's whole-model censor norm; "
                         "'group' = per-group censoring (new scenario)")
    ap.add_argument("--mix-backend", default="dense",
                    choices=("dense", "sparse", "sharded"),
                    help="topology backend for neighbor aggregation: "
                         "'dense' = (N,N) adjacency matmul, 'sparse' = "
                         "edge-list gather+segment-sum (O(E*d)), 'sharded'"
                         " = shard_map SPMD mixing over the worker axis "
                         "(DESIGN.md §Topology)")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--omega", type=float, default=0.999)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", action="store_true",
                    help="drive the admm run through FleetSim (DESIGN.md "
                         "§Fleet): straggler timeouts, bounded-staleness "
                         "delivery, churn. All knobs at defaults is "
                         "bit-identical to the plain synchronous run")
    ap.add_argument("--fleet-participation", type=float, default=1.0,
                    help="per-round P(a worker's update arrives on time)")
    ap.add_argument("--fleet-staleness", type=int, default=0,
                    help="max delivery lag (rounds) for late updates; "
                         "0 means late updates are dropped outright")
    ap.add_argument("--fleet-stale-frac", type=float, default=1.0,
                    help="P(a late update is delayed rather than dropped)")
    ap.add_argument("--fleet-churn", default="",
                    help="membership changes as round:leave:join[,...] — "
                         "e.g. '10:2:1,20:1:0'")
    ap.add_argument("--fleet-seed", type=int, default=0,
                    help="fault-schedule seed (replays the same trace)")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of this run "
                         "to PATH (same as REPRO_TRACE=PATH; strictly "
                         "host-side — compiled programs and trajectories "
                         "are unchanged, see DESIGN.md §Observability)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--campaign", default=None, metavar="NAME",
                    help="run a registered experiment campaign (e.g. "
                         "'lm-sweep') through the resumable campaign "
                         "runner instead of a single training run")
    ap.add_argument("--resume", action="store_true",
                    help="with --campaign: skip completed runs")
    ap.add_argument("--campaign-only", default=None, metavar="STAGE",
                    help="with --campaign: run one stage (plus its "
                         "incomplete dependencies)")
    return ap


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    compile_cache.enable()
    if args.trace:
        obs_trace.enable(args.trace)
    if args.campaign:
        try:
            from benchmarks import campaigns
        except ImportError as e:
            raise SystemExit(
                "[train] --campaign needs the benchmarks package on the "
                "path — run from the repo root: PYTHONPATH=src python -m "
                f"repro.launch.train --campaign {args.campaign} ({e})")
        from repro.campaign.runner import Runner
        summary = Runner(campaigns.get(args.campaign), resume=args.resume,
                         only=args.campaign_only).run()
        if args.trace:
            obs_trace.save()
        return {"campaign": args.campaign, "executed": summary.executed,
                "skipped": summary.skipped, "failed": summary.failed,
                "claim_failures": summary.claims_failed}

    cfg = base.cli_config(args.arch, smoke=args.smoke, layers=args.layers,
                          tag="train")
    print(f"[train] arch={cfg.name} mode={args.mode} workers={args.workers} "
          f"batch={args.batch} seq={args.seq} steps={args.steps}")
    if args.mode == "admm":
        assert args.batch % args.workers == 0
        out = run_admm(cfg, args)
    elif args.fleet:
        raise SystemExit("[train] --fleet only applies to --mode admm "
                         "(the fleet simulator drives the consensus "
                         "engine, not the FSDP baseline)")
    else:
        out = run_fsdp(cfg, args)
    if args.trace:
        obs_trace.save()
    return out


if __name__ == "__main__":
    main()
