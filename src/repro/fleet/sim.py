"""FleetSim: the consensus engine under injected fleet faults
(DESIGN.md §Fleet).

Runs ``core/engine.py`` through realistic decentralized-fleet scenarios —
straggler timeouts, bounded-staleness delivery, and worker churn — while
keeping the fault-free path **bit-identical** to the synchronous engine
(pinned in ``tests/test_fleet.py``). Three mechanisms:

* **Partial participation** — each round's :class:`~repro.fleet.faults.
  FaultSchedule` draw becomes the engine step's ``participation`` mask.
  Inside the engine a timed-out worker is composed into the censoring
  decision (``censoring.compose_tx_mask``): its local primal + quantizer
  chain still advance, its ``theta_hat`` replica stays stale, and it
  contributes exactly zero payload bits — the paper's own "sent nothing
  this round" semantics, reused rather than reinvented.

* **Bounded staleness** — a per-worker one-slot delivery buffer, jitted
  alongside the engine step. A *delayed* worker computes its round-r
  update on time; if the censor test passes, the engine's own committed
  reconstruction (``quant.q_hat``, exactly the value ``theta_hat`` would
  have received) and its offered payload bits are parked in the buffer
  and the worker goes dark for ``lag`` rounds (``participation = 0``
  while in flight). When the timer expires the held value lands in
  ``theta_hat`` and the held bits are charged — late bits still cost
  bits. At most one packet is in flight per worker (bounded staleness by
  construction: a worker cannot fall arbitrarily far behind its own
  transmissions).

* **Churn** — join/leave events redraw the communication graph
  (``graph.membership_graph``: fresh connected bipartite draw, head/tail
  rebalanced, CSR/edge metadata re-derived), rebuild the topology backend
  in place (``Topology.rebuild``), and remap every worker-axis row of the
  engine + buffer state: survivors carry their primal, censor reference
  (``theta_hat``), quantizer chain and optimizer moments to their new
  rows; joiners start from the survivor mean (or zeros) with a fresh
  b0-bit quantizer; duals are re-initialized in the column space of the
  *new* signed incidence matrix (``dynamic.reinit_duals`` — the Thm-3
  condition, checked by the regression tests).

The host loop (:class:`FleetSim`) drives one jitted fleet step per
membership epoch; everything per-round (fault draws, keys) is a pure
function of the config seeds, so a fleet trace is exactly replayable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dynamic as dyn_lib
from repro.core import engine as E
from repro.core import topology as topo_lib
from repro.core.graph import WorkerGraph, membership_graph
from repro.core.quantization import QuantConfig
from repro.fleet.faults import FaultConfig, FaultSchedule
from repro.obs import trace as obs_trace
from repro.obs.ledger import CommLedger

Tree = Any


# ---------------------------------------------------------------- state --
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FleetState:
    """Engine state + the bounded-staleness delivery buffer (worker axis N
    throughout). ``held_hat`` rows are only meaningful where ``timer > 0``
    (one in-flight packet per worker)."""

    engine: E.EngineState
    held_hat: Tree           # parked transmission values (theta_hat dtype)
    held_payload: jax.Array  # (N,) f32 bits to charge at delivery
    timer: jax.Array         # (N,) i32 rounds until delivery (0 = idle)


def init_fleet_state(state: E.EngineState) -> FleetState:
    n = E._flatten_worker(state.theta_hat).shape[0]
    return FleetState(
        engine=state,
        held_hat=jax.tree_util.tree_map(jnp.zeros_like, state.theta_hat),
        held_payload=jnp.zeros((n,), jnp.float32),
        timer=jnp.zeros((n,), jnp.int32),
    )


# ----------------------------------------------------------- fleet step --
def make_fleet_step(graph: WorkerGraph, cfg: E.EngineConfig,
                    solver: E.LocalSolver,
                    extra_metrics: Optional[E.MetricsFn] = None, *,
                    topology: Optional[topo_lib.Topology] = None):
    """Wrap the engine step with the staleness-buffer automaton.

    ``fstep(fleet_state, batch, key, drop, lag) -> (fleet_state, metrics)``
    with ``drop (N,) f32`` / ``lag (N,) i32`` from the fault schedule.
    This program only runs on rounds that actually carry faults —
    :class:`FleetSim` dispatches fault-free rounds straight to the plain
    synchronous engine step (bit-identity by construction; see the class
    docstring). All-zero faults through *this* program are value-identical
    but not guaranteed bit-identical: the extra (mathematically identity)
    mask arithmetic shifts XLA's fusion/FMA-contraction choices at f32-eps
    scale, which is exactly why the golden path is a dispatch decision and
    not a traced no-op.

    Metrics are the engine's, with ``payload_bits``/``tx_mask`` upgraded to
    *arrival* accounting (a delivered stale packet counts as that round's
    transmission and charges its held bits) plus the fleet diagnostics
    ``fleet_participation`` / ``fleet_start`` / ``fleet_deliver`` /
    ``fleet_timer``.
    """
    engine_step = E.make_step(graph, cfg, solver, extra_metrics,
                              topology=topology)

    def fstep(fs: FleetState, batch, key: jax.Array,
              drop: jax.Array, lag: jax.Array):
        inflight = fs.timer > 0
        start = (lag > 0) & (drop == 0) & (~inflight)
        startf = start.astype(jnp.float32)
        inflightf = inflight.astype(jnp.float32)
        # a worker is dark while dropped, buffering, or in flight
        participation = (1.0 - drop) * (1.0 - startf) * (1.0 - inflightf)

        state, m = engine_step(fs.engine, batch, key, participation)

        # buffer a delayed packet only if its censor test passed — there
        # is no transmission to delay otherwise (censor_mask is the
        # timeout-agnostic decision the engine just computed).
        started = startf * m["censor_mask"]
        held_hat = E.tree_where_worker(started, state.quant.q_hat,
                                       fs.held_hat)
        timer_dec = jnp.where(inflight, fs.timer - 1, 0)
        deliver = inflight & (timer_dec == 0)
        deliverf = deliver.astype(jnp.float32)
        timer = jnp.where(started > 0, lag, timer_dec).astype(jnp.int32)
        held_payload = jnp.where(
            started > 0, m["offered_payload_bits"],
            jnp.where(deliverf > 0, 0.0, fs.held_payload))

        # delivery: the parked value becomes the fleet-visible theta_hat
        # (used by every mix from the next phase on), late bits are charged
        theta_hat = E.tree_where_worker(deliverf, fs.held_hat,
                                        state.theta_hat)
        state = dataclasses.replace(state, theta_hat=theta_hat)

        metrics = dict(m)
        metrics["payload_bits"] = m["payload_bits"] \
            + fs.held_payload * deliverf
        metrics["tx_mask"] = jnp.minimum(m["tx_mask"] + deliverf, 1.0)
        metrics["fleet_participation"] = participation
        metrics["fleet_start"] = started
        metrics["fleet_deliver"] = deliverf
        metrics["fleet_timer"] = timer
        return FleetState(engine=state, held_hat=held_hat,
                          held_payload=held_payload, timer=timer), metrics

    return fstep


# -------------------------------------------------------- churn remapping --
def _gather_rows(x: jax.Array, idx: np.ndarray, fill) -> jax.Array:
    """Worker-axis row gather: new row i takes old row ``idx[i]``; rows
    with ``idx[i] < 0`` (joiners) take ``fill`` (scalar or broadcastable)."""
    idxj = jnp.asarray(idx, jnp.int32)
    safe = jnp.clip(idxj, 0, x.shape[0] - 1)
    out = jnp.take(x, safe, axis=0)
    mask = (idxj >= 0).reshape((len(idx),) + (1,) * (x.ndim - 1))
    return jnp.where(mask, out, jnp.asarray(fill, x.dtype))


def remap_fleet_state(fs: FleetState, idx: np.ndarray, graph: WorkerGraph,
                      cfg: E.EngineConfig, join_init: str = "mean",
                      dual_reinit: str = "zero") -> FleetState:
    """Carry fleet + engine state across a membership change.

    ``idx[i]`` is the old worker-axis row of new member i (-1 for a
    joiner). Survivors keep their primal, censor reference, quantizer
    chain, optimizer moments and any in-flight staleness packet; joiners
    get ``theta`` = survivor mean (``join_init="mean"``, warm start) or
    zeros, an all-zero ``theta_hat``/``q_hat`` (they have transmitted
    nothing), and a fresh b0-bit uninitialized quantizer. Duals are
    re-initialized in ``col(M_-)`` of the new graph per ``dual_reinit``
    (see :func:`repro.core.dynamic.reinit_duals`)."""
    st = fs.engine
    surv = np.asarray(idx)[np.asarray(idx) >= 0]
    if join_init not in ("mean", "zeros"):
        raise ValueError(f"unknown join_init {join_init!r}")

    def gather_theta(x):
        if join_init == "mean":
            fill = jnp.mean(x[jnp.asarray(surv, jnp.int32)]
                            .astype(jnp.float32), axis=0,
                            keepdims=True).astype(x.dtype)
        else:
            fill = jnp.zeros((1,) + x.shape[1:], x.dtype)
        idxj = jnp.asarray(idx, jnp.int32)
        safe = jnp.clip(idxj, 0, x.shape[0] - 1)
        out = jnp.take(x, safe, axis=0)
        mask = (idxj >= 0).reshape((len(idx),) + (1,) * (x.ndim - 1))
        return jnp.where(mask, out, fill)

    tmap = jax.tree_util.tree_map
    gather0 = lambda x: _gather_rows(x, idx, 0)  # noqa: E731
    alpha = dyn_lib.reinit_duals(tmap(gather0, st.alpha), graph,
                                 mode=dual_reinit)
    qcfg = cfg.quantize or QuantConfig()
    quant = E.GroupQuantState(
        q_hat=tmap(gather0, st.quant.q_hat),
        range_prev=_gather_rows(st.quant.range_prev, idx, 0.0),
        bits_prev=_gather_rows(st.quant.bits_prev, idx, float(qcfg.b0)),
        delta_prev=_gather_rows(st.quant.delta_prev, idx, 0.0),
        initialized=_gather_rows(st.quant.initialized, idx, 0.0),
    )
    engine = E.EngineState(
        theta=tmap(gather_theta, st.theta),
        theta_hat=tmap(gather0, st.theta_hat),
        alpha=alpha,
        quant=quant,
        opt_mu=tmap(gather0, st.opt_mu),
        opt_nu=tmap(gather0, st.opt_nu),
        k=st.k,
    )
    return FleetState(
        engine=engine,
        held_hat=tmap(gather0, fs.held_hat),
        held_payload=_gather_rows(fs.held_payload, idx, 0.0),
        timer=_gather_rows(fs.timer, idx, 0),
    )


# ------------------------------------------------------------ the harness --
@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """One fleet scenario: fault schedule + graph redraw + churn policy."""

    rounds: int
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    graph_p: float = 0.4          # density of membership_graph redraws
    graph_seed: int = 0
    join_init: str = "mean"       # "mean" | "zeros"
    dual_reinit: str = "zero"     # "zero" | "project" (Thm-3 either way)
    seed: int = 0                 # per-round PRNG key seed

    def __post_init__(self):
        assert self.rounds >= 1


class FleetSim:
    """Host-side driver: one jitted fleet step per membership epoch.

    **Golden-path dispatch.** Whether a round carries any fault is
    host-known before stepping (the fault schedule is host-side, and the
    staleness timers are shadowed on host from the previous round's
    metrics). A round with no drop, no delay and no packet in flight is
    dispatched to the *plain synchronous engine step* — the identical
    compiled program the golden arm runs — so the fault-free fleet is
    bit-identical to the synchronous engine **by construction**, not by
    hoping XLA fuses two different programs the same way (it does not:
    identity-mask arithmetic shifts FMA contraction at f32-eps). Only
    rounds that actually carry faults pay for the fault program; a mostly-
    healthy fleet runs the synchronous step most rounds and diverges only
    where physics says it must.

    Args:
      n_workers: initial fleet size.
      engine_cfg: the engine configuration (any groups/censor/quantize/
        mix_backend combination the synchronous engine accepts).
      fleet_cfg: the fault scenario.
      theta0: initial per-worker parameters, leading axis ``n_workers``.
      solver: a membership-agnostic local solver, or
      solver_factory: ``(member_gids, graph) -> LocalSolver`` rebuilt at
        every churn event (data-dependent exact solvers need this — each
        member keeps its own shard).
      extra_metrics / extra_metrics_factory: likewise for the metrics fn.
      batch_fn: ``(round, member_gids) -> batch`` for batched solvers.
      graph0: explicit initial graph (defaults to a ``membership_graph``
        epoch-0 draw) — the golden tests pass the synchronous arm's graph.
      on_churn: ``(round, graph, fleet_state) -> None`` observer hook,
        called after each membership remap (the dual column-space
        regression test lives here).
    """

    def __init__(self, n_workers: int, engine_cfg: E.EngineConfig,
                 fleet_cfg: FleetConfig, theta0: Tree, *,
                 solver: Optional[E.LocalSolver] = None,
                 solver_factory: Optional[Callable] = None,
                 extra_metrics: Optional[E.MetricsFn] = None,
                 extra_metrics_factory: Optional[Callable] = None,
                 batch_fn: Optional[Callable] = None,
                 graph0: Optional[WorkerGraph] = None,
                 on_churn: Optional[Callable] = None):
        if (solver is None) == (solver_factory is None):
            raise ValueError("pass exactly one of solver / solver_factory")
        self.engine_cfg = engine_cfg
        self.fleet_cfg = fleet_cfg
        self.theta0 = theta0
        self._solver = solver
        self._solver_factory = solver_factory
        self._extra_metrics = extra_metrics
        self._extra_metrics_factory = extra_metrics_factory
        self.batch_fn = batch_fn
        self.on_churn = on_churn
        self.schedule = FaultSchedule(fleet_cfg.faults)
        self.members: List[int] = list(range(n_workers))
        self.next_gid = n_workers
        self.epoch = 0
        self.graph = graph0 if graph0 is not None else membership_graph(
            n_workers, fleet_cfg.graph_p, fleet_cfg.graph_seed, epoch=0)
        assert self.graph.n == n_workers
        self.topo = topo_lib.build(
            self.graph, engine_cfg.mix_backend,
            use_pallas_mix=engine_cfg.use_pallas_mix)
        self.churn_log: List[Dict[str, Any]] = []
        # host shadow of the staleness timers — lets the driver know,
        # before stepping, whether any packet is in flight (it mirrors
        # fleet_timer from the previous faulted round's metrics)
        self._host_timer = np.zeros(n_workers, np.int32)
        self._rebuild_step()

    # ------------------------------------------------------- internals --
    def _make_solver(self) -> E.LocalSolver:
        if self._solver_factory is not None:
            return self._solver_factory(tuple(self.members), self.graph)
        return self._solver

    def _make_metrics(self) -> Optional[E.MetricsFn]:
        if self._extra_metrics_factory is not None:
            return self._extra_metrics_factory(tuple(self.members),
                                               self.graph, self.topo)
        return self._extra_metrics

    def _rebuild_step(self) -> None:
        self.solver = self._make_solver()
        metrics_fn = self._make_metrics()
        # the fault program AND the plain synchronous step — fault-free
        # rounds dispatch to the latter (see class docstring)
        self._step = jax.jit(make_fleet_step(
            self.graph, self.engine_cfg, self.solver, metrics_fn,
            topology=self.topo))
        self._sync_step = jax.jit(E.make_step(
            self.graph, self.engine_cfg, self.solver, metrics_fn))

    def _apply_churn(self, r: int, fs: FleetState,
                     event) -> FleetState:
        leavers = set(self.schedule.pick_leavers(r, self.members,
                                                 event.leave))
        survivors = [g for g in self.members if g not in leavers]
        joiners = list(range(self.next_gid, self.next_gid + event.join))
        self.next_gid += event.join
        new_members = survivors + joiners
        idx = np.asarray([self.members.index(g) if g in self.members
                          else -1 for g in new_members], np.int32)
        self.epoch += 1
        self.graph = membership_graph(len(new_members),
                                      self.fleet_cfg.graph_p,
                                      self.fleet_cfg.graph_seed,
                                      epoch=self.epoch)
        self.topo = self.topo.rebuild(self.graph)
        self.members = new_members
        fs = remap_fleet_state(fs, idx, self.graph, self.engine_cfg,
                               join_init=self.fleet_cfg.join_init,
                               dual_reinit=self.fleet_cfg.dual_reinit)
        self._host_timer = np.where(
            idx >= 0, self._host_timer[np.clip(idx, 0, None)], 0
        ).astype(np.int32)
        self._rebuild_step()
        self.churn_log.append({"round": r, "left": sorted(leavers),
                               "joined": joiners,
                               "n_members": len(new_members)})
        if self.on_churn is not None:
            self.on_churn(r, self.graph, fs)
        return fs

    def _trace_worker_events(self, tr, r: int, rf, host) -> None:
        """Per-worker fault instants on ``fleet/worker <gid>`` tracks:
        drop (straggler timeout), lag_start (packet parked), deliver
        (stale packet landed). Pure host-side read of the round's fault
        draw + returned metrics."""
        start = np.asarray(host["fleet_start"])
        deliver = np.asarray(host["fleet_deliver"])
        for i, gid in enumerate(self.members):
            if rf.drop[i]:
                tr.instant("drop", "fleet",
                           tr.track("fleet", f"worker {gid}"),
                           args={"round": r})
            if start[i] > 0:
                tr.instant("lag_start", "fleet",
                           tr.track("fleet", f"worker {gid}"),
                           args={"round": r, "lag": int(rf.lag[i])})
            if deliver[i] > 0:
                tr.instant("deliver", "fleet",
                           tr.track("fleet", f"worker {gid}"),
                           args={"round": r})

    # ------------------------------------------------------------- run --
    def run(self) -> Tuple[FleetState, Dict[str, Any]]:
        """Drive ``fleet_cfg.rounds`` rounds; returns the final state and
        stacked per-round metrics (ragged keys — worker-axis arrays across
        membership changes — stay python lists; scalar reductions
        ``payload_bits_total`` / ``tx_count`` / ``n_members`` are always
        dense (rounds,) arrays)."""
        fcfg = self.fleet_cfg
        state = E.init_state(self.theta0, self.engine_cfg, self.solver)
        fs = init_fleet_state(state)
        base = jax.random.PRNGKey(fcfg.seed)
        records: List[Dict[str, Any]] = []
        # host-side observers only: events/ledger read the fault schedule
        # and the metric arrays each round ALREADY returned, so a traced
        # run dispatches the identical compiled programs (pinned by the
        # tracing-ON golden row in tests/test_fleet.py)
        tr = obs_trace.tracer()
        ledger = CommLedger(self.graph, subsystem="fleet") \
            if tr is not None else None
        for r in range(fcfg.rounds):
            event = self.schedule.churn_at(r)
            if event is not None and (event.leave or event.join):
                fs = self._apply_churn(r, fs, event)
                if ledger is not None:
                    ledger.rebuild(self.graph)
                if tr is not None:
                    log = self.churn_log[-1]
                    tr.instant("churn", "fleet",
                               tr.track("fleet", "rounds"),
                               args={"round": r, "left": len(log["left"]),
                                     "joined": len(log["joined"]),
                                     "n_members": log["n_members"]})
            rf = self.schedule.round_faults(r, self.members)
            batch = self.batch_fn(r, tuple(self.members)) \
                if self.batch_fn is not None else None
            key = jax.random.fold_in(base, r)
            n = len(self.members)
            with obs_trace.span("round", "fleet", track="rounds",
                                args={"round": r, "n_members": n}):
                if (not rf.drop.any() and not rf.lag.any()
                        and not self._host_timer.any()):
                    # fault-free round, nothing in flight: the exact
                    # program of the synchronous golden arm (bit-identity
                    # contract)
                    state, m = self._sync_step(fs.engine, batch, key)
                    fs = dataclasses.replace(fs, engine=state)
                    host = jax.device_get(m)
                    host["fleet_participation"] = np.ones(n, np.float32)
                    host["fleet_start"] = np.zeros(n, np.float32)
                    host["fleet_deliver"] = np.zeros(n, np.float32)
                    host["fleet_timer"] = np.zeros(n, np.int32)
                else:
                    fs, m = self._step(fs, batch, key, jnp.asarray(rf.drop),
                                       jnp.asarray(rf.lag))
                    host = jax.device_get(m)
                    self._host_timer = np.asarray(host["fleet_timer"],
                                                  np.int32)
            if tr is not None:
                self._trace_worker_events(tr, r, rf, host)
                ledger.update(host)
            host["n_members"] = np.asarray(n, np.int32)
            records.append(host)
        metrics = stack_records(records)
        metrics["payload_bits_total"] = np.asarray(
            [float(np.sum(rec["payload_bits"])) for rec in records])
        metrics["tx_count"] = np.asarray(
            [float(np.sum(rec["tx_mask"])) for rec in records])
        metrics["churn_log"] = list(self.churn_log)
        return fs, metrics


def stack_records(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Stack per-round metric dicts into (rounds, ...) arrays; keys whose
    shape varies across rounds (worker-axis arrays across churn) stay
    lists of per-round arrays."""
    out: Dict[str, Any] = {}
    for k in records[0]:
        vals = [rec[k] for rec in records]
        if len({np.shape(v) for v in vals}) == 1:
            out[k] = np.stack([np.asarray(v) for v in vals])
        else:
            out[k] = vals
    return out


def run_synchronous(graph: WorkerGraph, cfg: E.EngineConfig,
                    solver: E.LocalSolver, theta0: Tree, rounds: int,
                    seed: int = 0,
                    extra_metrics: Optional[E.MetricsFn] = None,
                    batch_fn: Optional[Callable] = None,
                    ) -> Tuple[E.EngineState, Dict[str, Any]]:
    """The golden arm: the plain synchronous engine, driven with the SAME
    per-round key derivation as :class:`FleetSim` (``fold_in(key, round)``)
    so a fault-free fleet run is comparable bit-for-bit."""
    step = jax.jit(E.make_step(graph, cfg, solver, extra_metrics))
    state = E.init_state(theta0, cfg, solver)
    base = jax.random.PRNGKey(seed)
    records = []
    for r in range(rounds):
        batch = batch_fn(r) if batch_fn is not None else None
        state, m = step(state, batch, jax.random.fold_in(base, r))
        records.append(jax.device_get(m))
    metrics = stack_records(records)
    metrics["payload_bits_total"] = np.asarray(
        [float(np.sum(rec["payload_bits"])) for rec in records])
    return state, metrics
