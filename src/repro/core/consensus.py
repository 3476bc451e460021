"""CQ-GGADMM over model *pytrees* — decentralized training of the assigned
architectures.

Thin adapter over the unified consensus engine (``core/engine.py``; see
DESIGN.md §Engine). The paper's consensus variable theta is a flat vector;
for neural models it is the whole parameter pytree — the engine treats both
identically, and this module keeps the seed training API
(:class:`ConsensusConfig`, ``init_consensus_state``,
``make_consensus_step``) while delegating every update to the engine.

Faithfulness notes:
  * By default the censoring norm and the quantizer range are *global over
    the whole model vector*, exactly as in the paper (``groups="model"``,
    ``censor_mode="global"``). ``groups="leaf"`` opts into the L-FGADMM
    layer-wise mode (per-layer ranges and payload accounting; DESIGN.md
    §Groups).
  * The exact local argmin (Eqs. 21/22) is replaced by `local_steps` Adam
    iterations on the augmented Lagrangian g_n(theta) = f_n(theta) +
    <theta, v_n> + rho d_n / 2 ||theta||^2 — standard inexact-ADMM practice
    for non-convex f_n (recorded in DESIGN.md §5).
  * Quantizer-chain consistency under censoring: in SPMD both "sides" of a
    link share state, so the receiver replica of Q-hat_n is always in sync,
    matching the paper's error decomposition e + l (Sec. 6) bit-exactly.
  * Metrics: ``payload_bits`` counts only transmitted bits (a censored
    round costs zero); ``candidate_payload_bits`` carries the uncensored
    what-if cost (DESIGN.md §Groups, payload accounting).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine as E
from repro.core.censoring import CensorConfig
from repro.core.engine import (  # noqa: F401  (re-exported tree utils)
    GroupQuantState, tree_dim, tree_mix, tree_where_worker, tree_worker_dot,
    tree_worker_maxabs, tree_worker_sqnorm)
from repro.core.quantization import QuantConfig

Tree = Any


# -------------------------------------------------------- tree quantizer --
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TreeQuantState:
    """Legacy whole-model quantizer state (G=1 view of the engine's
    :class:`~repro.core.engine.GroupQuantState`): scalar side-information
    per worker, as in the paper (single R_n^k, b_n^k per transmission)."""

    q_hat: Tree
    range_prev: jax.Array   # (N,)
    bits_prev: jax.Array    # (N,)
    delta_prev: jax.Array   # (N,)
    initialized: jax.Array  # (N,)

    @staticmethod
    def create(theta: Tree, b0: int = 4) -> "TreeQuantState":
        g = GroupQuantState.create(theta, 1, b0=b0)
        return TreeQuantState(
            q_hat=g.q_hat, range_prev=g.range_prev[:, 0],
            bits_prev=g.bits_prev[:, 0], delta_prev=g.delta_prev[:, 0],
            initialized=g.initialized[:, 0])

    def as_grouped(self) -> GroupQuantState:
        return GroupQuantState(
            q_hat=self.q_hat, range_prev=self.range_prev[:, None],
            bits_prev=self.bits_prev[:, None],
            delta_prev=self.delta_prev[:, None],
            initialized=self.initialized[:, None])


def tree_quantize_step(
    state: TreeQuantState, theta: Tree, key: jax.Array, cfg: QuantConfig,
) -> Tuple[TreeQuantState, Tree, jax.Array, jax.Array]:
    """Whole-model stochastic quantization (Eqs. 14-20) — the engine's
    grouped quantizer with a single group."""
    group_ids = E.resolve_groups(theta, "model")
    new_g, q_hat, bits, payload = E.grouped_quantize_step(
        state.as_grouped(), theta, key, cfg, group_ids)
    new_state = TreeQuantState(
        q_hat=new_g.q_hat, range_prev=new_g.range_prev[:, 0],
        bits_prev=new_g.bits_prev[:, 0], delta_prev=new_g.delta_prev[:, 0],
        initialized=new_g.initialized[:, 0])
    return new_state, q_hat, bits[:, 0], payload


# --------------------------------------------------------- consensus step --
@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    """Hyperparameters of pytree CQ-GGADMM (adapter view of
    :class:`~repro.core.engine.EngineConfig` + the inexact local solver)."""

    rho: float = 0.01
    censor: CensorConfig = dataclasses.field(default_factory=CensorConfig)
    quantize: Optional[QuantConfig] = None
    local_steps: int = 4          # inexact-argmin Adam iterations
    local_lr: float = 1e-3
    use_adam: bool = True         # False: plain SGD (no moments; saves 2
    #                               param-sized buffers)
    hat_dtype: Optional[str] = None  # "bfloat16" stores theta_hat / q_hat
    #                               replicas at half width (paper accounting
    #                               is unchanged; only the SPMD replica
    #                               storage narrows)
    groups: E.GroupSpec = "model"    # "leaf" => L-FGADMM layer-wise mode
    censor_mode: str = "global"      # "group" => per-group censoring

    def engine_config(self) -> E.EngineConfig:
        return E.EngineConfig(
            rho=self.rho, alternating=True, censor=self.censor,
            quantize=self.quantize, groups=self.groups,
            censor_mode=self.censor_mode, hat_dtype=self.hat_dtype)

    def solver(self, grad_fn: Optional[Callable] = None) -> E.InexactSolver:
        return E.InexactSolver(grad_fn=grad_fn,
                               local_steps=self.local_steps,
                               local_lr=self.local_lr,
                               use_adam=self.use_adam)


ConsensusState = E.EngineState


def init_consensus_state(theta: Tree, cfg: ConsensusConfig) -> ConsensusState:
    return E.init_state(theta, cfg.engine_config(), cfg.solver())


def make_consensus_step(graph, cfg: ConsensusConfig,
                        grad_fn: Callable[[Tree, Any], Tree],
                        loss_fn: Optional[Callable] = None):
    """Build the jittable CQ-GGADMM training step over pytrees.

    grad_fn(theta_tree, batch) -> per-worker gradient pytree, where every
    leaf of theta_tree and batch carries a leading worker axis N.

    step(state, batch, key) -> (state, metrics).
    """
    return E.make_step(graph, cfg.engine_config(), cfg.solver(grad_fn),
                       extra_metrics=E.consensus_metrics(loss_fn))
