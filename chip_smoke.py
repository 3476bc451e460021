#!/usr/bin/env python3
"""Quickest proof that the system runs on a TPU: its main paths once, at
TinyLlama-1.1B's published widths (d_model 2048, 32 heads, 4 KV heads,
d_ff 5632, vocab 32000), cut in depth only, from random weights made from
a seed.

    python3 chip_smoke.py             # one chip: train, kernels, serve
    python3 chip_smoke.py --chips 4   # four chips: sharded training only

Phases (one chip):

* train   — ``repro.launch.train.main``: CQ-GGADMM, 2 workers, 3 rounds.
            Every loss finite, every round's bits positive.
* kernels — the trainer's own step run twice from the same seed, with and
            without the Pallas kernels (fused quantize, bipartite mix),
            under a censor threshold that censors round 0: identical
            censor decisions in every round, ``theta_hat`` and the dual
            ``alpha`` within ``CROSS_PROGRAM_RTOL``, both kernels
            dispatched.
* serve   — ``repro.launch.serve.main``: the paged scheduler serves 4
            requests (prompt 128, decode 32); all finish, no page leaks.

With ``--chips 4``: 4 workers, one per chip, 3 rounds through the sharded
mix backend, against the dense backend under the same placement and seed:
identical censor decisions, losses within ``CROSS_PROGRAM_RTOL``, and
every chip holds exactly one worker row of every per-worker array.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase. Any failed check exits non-zero. The last line printed is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ARCH = "tinyllama-1.1b"
# Two workers at one layer is what the compile for a v5e fits in its
# 15.75 GiB of HBM: each worker holds six f32 copies of its parameters
# (theta, theta_hat, alpha, q_hat and Adam's two moments), and at two
# layers the step needs 17.92 GiB (19.65 GiB with the Pallas kernels).
LAYERS = 1
ROUNDS = 3
TRAIN_ARGS = ["--arch", ARCH, "--layers", str(LAYERS), "--workers", "2",
              "--steps", str(ROUNDS), "--batch", "4", "--seq", "256",
              "--log-every", "1"]
# The kernel phase censors: tau^k = tau0 * xi^k is 1e3 at round 0, above
# the norm of a first transmission (theta_hat starts at zero; the model's
# norm is a few hundred), then 1 and 1e-3, below any round's change. Round
# 0 is censored, rounds 1 and 2 transmit.
KERNEL_CENSOR = ["--tau0", "1e6", "--xi", "1e-3"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def print_peak_memory(jax) -> None:
    """Peak HBM per device: the TPU runtime counts buffers (arguments,
    outputs, live arrays) in ``peak_bytes_in_use`` and a program's temp
    space in ``peak_bytes_reserved``."""
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"  {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
              f"peak_bytes_reserved={stats.get('peak_bytes_reserved')} "
              f"bytes_limit={stats.get('bytes_limit')}")


def phase_train(jax) -> None:
    from repro.launch import train
    out = train.main(TRAIN_ARGS)
    for i, (loss, tx, bits) in enumerate(zip(
            out["history"], out["tx_history"], out["bits_history"])):
        print(f"  round {i}: loss={loss!r} tx={tx}/2 bits={bits!r}")
    check(len(out["history"]) == ROUNDS, "train ran every round")
    check(all(math.isfinite(x) for x in out["history"]), "losses finite")
    check(all(b > 0 for b in out["bits_history"]), "bits positive")


def kernel_arm(jax, setup, ecfg):
    """The trainer's step under ``ecfg``, ``ROUNDS`` rounds from the
    setup's seed, yielding each round's ``(tx_mask, theta_hat, alpha)`` on
    the host: the censor decisions, what the workers sent, and the dual,
    which sums every mix's error over the rounds."""
    import numpy as np
    state, step = setup.init_state(ecfg), setup.build_step(ecfg)
    for i in range(ROUNDS):
        state, m = step(state, setup.batch(i), setup.key(i))
        yield (np.asarray(m["tx_mask"]), jax.device_get(state.theta_hat),
               jax.device_get(state.alpha))


def phase_kernels(jax) -> None:
    import numpy as np

    from repro.configs import base
    from repro.core import engine as E
    from repro.launch import train
    from repro.obs.metrics import kernel_dispatch_counter
    cfg = base.get_config(ARCH).cut_depth(LAYERS)
    setup = train.admm_setup(cfg, train.build_parser().parse_args(
        TRAIN_ARGS + KERNEL_CENSOR))
    counter = kernel_dispatch_counter()
    ref = list(kernel_arm(jax, setup, setup.ecfg))
    check(any((r[0] == 0).any() for r in ref), "some decision censored")
    pallas = dataclasses.replace(setup.ecfg, use_pallas_quant=True,
                                 use_pallas_mix=True)
    for i, ((tx_r, hat_r, alpha_r), (tx, hat, alpha)) in enumerate(
            zip(ref, kernel_arm(jax, setup, pallas))):
        hat_err = E.tree_rel_err(hat, hat_r)
        alpha_err = E.tree_rel_err(alpha, alpha_r)
        print(f"  round {i}: tx_mask jnp={tx_r.tolist()} "
              f"pallas={tx.tolist()} theta_hat rel_err={hat_err!r} "
              f"alpha rel_err={alpha_err!r}")
        check(np.array_equal(tx, tx_r), f"round {i}: identical tx_mask")
        check(max(hat_err, alpha_err) <= E.CROSS_PROGRAM_RTOL,
              f"round {i}: theta_hat and alpha within "
              f"{E.CROSS_PROGRAM_RTOL}")
    quant = {v: counter.value(kernel="stoch_quantize_fused", variant=v)
             for v in ("slab", "tiled")}
    mix = counter.value(kernel="bipartite_mix", variant="dense")
    print(f"  kernel_dispatch: stoch_quantize_fused={quant} "
          f"bipartite_mix={mix}")
    check(sum(quant.values()) > 0, "fused quantize kernel dispatched")
    check(mix > 0, "bipartite_mix dispatched")


def phase_serve(jax) -> None:
    from repro.launch import serve
    requests, decode = 4, 32
    out = serve.main(["--arch", ARCH, "--full", "--layers", str(LAYERS),
                      "--batch", str(requests), "--requests", str(requests),
                      "--prompt-len", "128",
                      "--decode-tokens", str(decode)])
    lens = [len(out["outputs"][i]) for i in range(requests)]
    print(f"  tokens per request={lens} "
          f"final pages in use={out['final_pages_in_use']}")
    check(lens == [decode] * requests, "every request finished")
    check(out["final_pages_in_use"] == 0, "final pages in use == 0")


def phase_sharded(jax) -> None:
    import numpy as np

    from repro.configs import base
    from repro.core import engine as E
    from repro.launch import train
    n = len(jax.devices())
    argv = ["--arch", ARCH, "--layers", str(LAYERS), "--workers", str(n),
            "--steps", str(ROUNDS), "--batch", str(2 * n), "--seq", "256"]
    cfg = base.get_config(ARCH).cut_depth(LAYERS)
    runs = {}
    for backend in ("sharded", "dense"):
        setup = train.admm_setup(cfg, train.build_parser().parse_args(
            argv + ["--mix-backend", backend]))
        check(setup.mesh is not None, "workers placed on a mesh")
        state, step = setup.init_state(), setup.build_step()
        tx, loss = [], []
        for i in range(ROUNDS):
            state, m = step(state, setup.batch(i), setup.key(i))
            tx.append(np.asarray(m["tx_mask"]))
            loss.append(float(m["loss"]))
            print(f"  {backend} round {i}: loss={loss[-1]!r} "
                  f"tx_mask={tx[-1].tolist()}")
        for leaf in jax.tree_util.tree_leaves(state):
            if leaf.ndim == 0:
                continue
            rows = sorted((s.device.id, s.data.shape[0])
                          for s in leaf.addressable_shards)
            check(rows == [(d.id, 1) for d in
                           sorted(jax.devices(), key=lambda d: d.id)],
                  f"{backend}: one worker row per device, got {rows}")
        theta = jax.tree_util.tree_leaves(state.theta)[0]
        print(f"  {backend}: theta rows per device "
              f"{[(s.device.id, s.data.shape) for s in theta.addressable_shards]}")
        del state, step
        runs[backend] = (tx, loss)
    for i in range(ROUNDS):
        check(np.array_equal(runs["sharded"][0][i], runs["dense"][0][i]),
              f"round {i}: identical tx_mask")
        a, b = runs["sharded"][1][i], runs["dense"][1][i]
        check(abs(a - b) <= E.CROSS_PROGRAM_RTOL * abs(b),
              f"round {i}: loss {a!r} vs dense {b!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded training path across "
                         "four chips (and its dense comparison)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU here ({devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()

    phases = ([phase_sharded] if args.chips == 4
              else [phase_train, phase_kernels, phase_serve])
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        print(f"== {name}", flush=True)
        t0 = time.time()
        try:
            phase(jax)
        except SmokeFailure as e:
            print(f"== {name}: FAILED: {e}", flush=True)
            return 1
        print(f"== {name}: ok ({time.time() - t0:.1f} s on the chip, "
              f"compiles included)", flush=True)
        print_peak_memory(jax)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
