"""Driver for consensus-training cells: the program's CQ-GGADMM round.

Set-up builds one object, the program's jitted round (``AdmmSetup.
build_step``, i.e. ``engine.make_step``) with its engine state, from the
benchmark's weights for the seed, and drives it through its first
``REF_ROUNDS`` rounds with the program's own feed (``AdmmSetup.batch`` and
``AdmmSetup.key``); round 0 compiles. The window then drives the same step
and state on from round ``REF_ROUNDS``, keeping each round's metrics on
the device, for ``--seconds``. ``train_tokens_per_s`` counts the useful
tokens of each completed round (workers x batch x seq x local steps) over
the window's time.

After the window, with the program's state freed, the plain reference
(``reference/admm.py`` over the family's model, ``reference/<family>.py``)
replays the first rounds from the same weights and regenerated batches,
and the first rounds' readings are compared (``checks.train_checks``). A
round of the window whose loss is not finite makes the run not correct.
"""
from __future__ import annotations

import argparse
import gc
import math
import sys
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import checks  # noqa: E402
import harness  # noqa: E402
import program  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402
from reference import admm as ref_admm  # noqa: E402
from reference import synthetic_lm  # noqa: E402

REF_ROUNDS = 3          # rounds the reference follows
# Seconds of rounds the host dispatches ahead of the one it waits for, so
# that the device stays fed while the host stands still; the window is
# drained at its end, so that work counts over all of its time.
AHEAD_SECONDS = 5.0
TRACE_SECONDS = 5.0     # a --trace 1 window: traced, then it ends


def trainer_args(cell, seed: int) -> argparse.Namespace:
    t, d = cell["traffic"], cell["deploy"]
    return argparse.Namespace(
        workers=d["workers"], batch=d["workers"] * t["batch_per_worker"],
        seq=t["seq"], lr=t["lr"], rho=t["rho"],
        local_steps=t["local_steps"], tau0=t["tau0"], xi=t["xi"],
        quantize=True, bits=t["bits"], omega=t["omega"],
        groups=t["groups"], censor_mode=t["censor_mode"],
        mix_backend=d["mix_backend"], regroup_every=0, seed=seed,
        topology=d["topology"])


def useful_tokens_per_round(cell) -> int:
    t, d = cell["traffic"], cell["deploy"]
    return d["workers"] * t["batch_per_worker"] * t["seq"] * t["local_steps"]


def placed(setup, fn, *args):
    """``fn(*args)`` under jit, its outputs made directly in the program's
    worker placement (``AdmmSetup.mesh``) when it has one."""
    if setup.mesh is None:
        return jax.jit(fn)(*args)
    out = jax.tree_util.tree_map(setup._sharding, jax.eval_shape(fn, *args))
    return jax.jit(fn, out_shardings=out)(*args)


def build(cell, seed: int):
    """The program's round, its state from the benchmark's weights, and
    its feed."""
    from repro.core import engine as E
    from repro.launch import train as T
    mcfg = program.model_config(cell["config"])
    setup = T.admm_setup(mcfg, trainer_args(cell, seed))
    make = weights.maker(weights.program_layout(mcfg))
    key = harness.seed_key(seed)
    n = cell["deploy"]["workers"]

    def init(k):
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), make(k))
        return E.init_state(stacked, setup.ecfg, setup.solver)

    state = placed(setup, init, key)
    return setup, state, setup.build_step(), make, key


def first_rounds(setup, state, step, make, key):
    """Rounds 0..REF_ROUNDS-1 through the window's own call and feed, and
    the readings the reference is compared on."""
    out = {"loss": [], "tx": []}

    @jax.jit
    def change(theta, k):
        return program.leaf_norms(jax.tree_util.tree_map(
            lambda t, p: t - p[None], theta, make(k)))

    for i in range(REF_ROUNDS):
        t0 = time.perf_counter()
        state, m = step(state, setup.batch(i), setup.key(i))
        out["loss"].append(float(m["loss"]))
        out["round_s"] = time.perf_counter() - t0
        harness.log(f"round {i}: {out['round_s']:.3f} s")
        out["tx"].append([int(x) for x in np.asarray(m["tx_mask"])])
        if i == 0:
            out["mu_norms"] = np.asarray(program.leaf_norms(state.opt_mu))
    out["update_norms"] = np.asarray(change(state.theta, key))
    out["dual_norms"] = np.asarray(program.leaf_norms(state.alpha))
    return state, out


def reference(cell, seed: int, mode: str = "f32", half_batch: bool = False):
    """The reference's readings of the first rounds. ``mode="fp8"`` is the
    control; ``half_batch`` plants the fault of a loss over half of each
    batch's tokens (the first half of every row: a cell's batch may be one
    row)."""
    cfg, t, d = cell["config"], cell["traffic"], cell["deploy"]
    model = harness.load_module(
        harness.HERE / "reference" / f"{cfg['family']}.py",
        "chipbench_reference_" + cfg["family"])
    mm = model.Matmul(mode)
    params0 = jax.jit(weights.maker(weights.program_layout(
        program.model_config(cfg))))(harness.seed_key(seed))

    def loss_fn(params, tokens, labels):
        if half_batch:
            half = tokens.shape[1] // 2
            tokens, labels = tokens[:, :half], labels[:, :half]
        return model.lm_loss(params, tokens, labels, cfg, mm)

    batches = [[synthetic_lm.rows(seed, w, r, t["batch_per_worker"],
                                  t["seq"], cfg["vocab_size"])
                for w in range(d["workers"])] for r in range(REF_ROUNDS)]
    keys = [jax.random.PRNGKey(1000 + r) for r in range(REF_ROUNDS)]
    hp = dict(t, b_max=16, b_overhead=64)
    with jax.default_matmul_precision("highest"):
        return ref_admm.run(params0, loss_fn, batches, keys, hp, d["graph"])


def run(cell, *, seed: int, seconds: float, trace: bool, devices):
    setup, state, step, make, key = build(cell, seed)
    harness.log(f"built at {time.perf_counter() - harness.PROCESS_T0:.3f} s")
    state, prog = first_rounds(setup, state, step, make, key)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - harness.PROCESS_T0
    harness.log(f"setup_s {setup_s:.3f} (first {REF_ROUNDS} rounds "
                f"included, losses {prog['loss']})")

    # --trace 1: the window is the traced segment, at least two rounds
    # and TRACE_SECONDS long, drained before the profiler stops
    cap = trace_reduce.Capture() if trace else None
    limit = min(seconds, TRACE_SECONDS) if trace else seconds
    # a traced window keeps 2 rounds ahead: reading its trace takes time in
    # proportion to the rounds it holds
    ahead = 2 if trace else max(2, math.ceil(AHEAD_SECONDS
                                              / prog["round_s"]))
    losses, pending = [], deque()
    i = REF_ROUNDS
    if cap:
        cap.start()
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("chipbench.input"):
            batch = setup.batch(i)
        with jax.profiler.TraceAnnotation("chipbench.round"):
            state, m = step(state, batch, setup.key(i))
        losses.append(m["loss"])
        pending.append(m["loss"])
        if len(pending) > ahead:
            pending.popleft().block_until_ready()
        i += 1
        if time.perf_counter() - t0 >= limit and i - REF_ROUNDS >= 2:
            break
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    if cap:
        cap.stop()
    rounds = i - REF_ROUNDS
    losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(losses)))
    tokens = rounds * useful_tokens_per_round(cell)
    harness.log(f"window {window_s:.3f} s, {rounds} rounds ({ahead} "
                f"dispatched ahead), {tokens} "
                f"useful tokens, losses {losses.min():.5f}..."
                f"{losses.max():.5f}, {failed} not finite")

    device = harness.device_record(devices)
    harness.log(f"peak HBM of the fullest chip: {device['memory_peak_bytes']}"
                f" bytes")
    record = None
    if cap:
        red = cap.reduce()
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        flops = harness.load_module(
            harness.HERE / "flops" / f"{cell['config']['family']}.py",
            "chipbench_flops")
        record = {"tokens": tokens, "window_s": window_s, "rounds": rounds,
                  "chips": len(devices),
                  "flops_per_token": flops.train_flops_per_token(
                      cell["config"], cell["traffic"]["seq"]),
                  "peak_flops": harness.peaks(device["kind"])["bf16_flops"],
                  "trace": red, "breakdown": red["breakdown"]}

    del state, step, setup, m, batch, pending
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference(cell, seed)
    values = checks.train_checks(prog, ref)
    harness.log(f"reference {time.perf_counter() - t_ref:.3f} s; program "
                f"losses {prog['loss']} reference {ref['loss']}")
    values["window_nonfinite_losses"] = float(failed)
    out_checks = checks.judge(values, dict(cell["deploy"]["correct"],
                                           window_nonfinite_losses=0))
    return {"correct": checks.all_within(out_checks), "attempted": rounds,
            "failed": failed, "device": device, "checks": out_checks,
            "metrics": {"train_tokens_per_s": tokens / window_s,
                        "setup_s": setup_s},
            "record": record}
