#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip at
the cell's own size: the program against the reference on many seeds,
and, on some of them, the control (the reference in fp8) and the planted
faults against the reference. One process, so set-up compiles once.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2

Training cells read no window: the first rounds, which the reference
follows, are driven as in a run's set-up. Prints one JSON line per seed
and reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def emit(**kw):
    print(json.dumps(kw), flush=True)


def worst_leaves(names, other, ref, top=3):
    """The leaves that set each norm gap, worst first, and the median
    leaf's gap: where a gap comes from."""
    import checks
    import numpy as np
    keep = checks.moved_leaves(ref["mu_norms"])
    out = {}
    for key, table in (("moment_gap", "mu_norms"),
                       ("update_gap", "update_norms"),
                       ("dual_gap", "dual_norms")):
        gap = checks.leaf_gaps(other[table], ref[table]).max(axis=0)
        if key != "moment_gap":
            gap = gap * keep
        order = gap.argsort()[::-1][:top]
        out[key] = {"median": float(np.median(gap)),
                    "worst": [[names[i], float(gap[i])] for i in order]}
    return out


def train(drv, cell, seed, control, names, shared):
    """One seed's readings. The round does not depend on the seed, so
    every seed drives the first seed's jitted round (``shared``), which
    is traced once."""
    import checks
    t0 = time.perf_counter()
    setup, state, step, make, key = drv.build(cell, seed)
    step = shared.setdefault("step", step)
    state, prog = drv.first_rounds(setup, state, step, make, key)
    t1 = time.perf_counter()
    del setup, state, step
    gc.collect()
    ref = drv.reference(cell, seed)
    t2 = time.perf_counter()
    emit(seed=seed, arm="program", program_s=t1 - t0, reference_s=t2 - t1,
         loss=prog["loss"], ref_loss=ref["loss"],
         **checks.train_checks(prog, ref),
         leaves=worst_leaves(names, prog, ref))
    if control:
        for arm, kw in (("control_fp8", {"mode": "fp8"}),
                        ("fault_half_batch", {"half_batch": True})):
            other = drv.reference(cell, seed, **kw)
            emit(seed=seed, arm=arm, **checks.train_checks(other, ref),
                 leaves=worst_leaves(names, other, ref))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(bench.root / "src"))
    harness.require_chips(cell["chips"])
    from repro.launch import compile_cache
    compile_cache.enable()
    drv = harness.load_module(cell["driver"], "chipbench_driver")
    import jax
    import program
    import weights
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(weights.program_layout(
                 program.model_config(cell["config"])))[0]]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    shared = {}
    for s in args.seeds.split(","):
        train(drv, cell, int(s), int(s) in control, names, shared)


if __name__ == "__main__":
    main()
