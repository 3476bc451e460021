#!/usr/bin/env python3
"""Split a training cell's round into its parts on the chip, and its
set-up into compile stages, and print them.

    python3 chipbench/phase_split.py --workload <cell> --seeds 1,2,3 \
        [--rounds 4] [--out phases-<cell>.json] [--keep DIR]

Builds the cell's round, state and feed as ``drivers/train.py`` does and
drives its first rounds (set-up; round 0 compiles). The program's compile
counter (``obs.metrics.compile_counters``) is read there: seconds of
tracing, lowering and compiling before the first window. Then, for each
seed, from that seed's weights and token stream, it runs 2 rounds and a
profiler-traced window of ``--rounds`` rounds, 2 dispatched ahead, and
reduces the trace with ``phases.phase_split`` against the round's compiled
text, fetched once by ``step.lower(...).compile().as_text()``. The compile
counter's change over each window and over that fetch is printed: a
window that compiled would not be a steady round.

Per seed it prints, in ms a round, the device self time of each part of
the round (``admm.*`` scopes), of ``unscoped`` work and of fusions that
straddle two parts; the device's busy time; the host clock's round time;
and the mean of the program's ``repro.train.input`` and
``repro.train.input.generate`` spans. One JSON object per seed goes to
stdout, the whole to ``--out``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import gzip
import json
import shutil
import sys
import time
from collections import deque
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402  (first: it stamps the process start time)
import phases  # noqa: E402
import trace_reduce  # noqa: E402

AHEAD = 2


def compile_events(before):
    """Compile events since snapshot ``before``: ``stage,fun`` -> [count,
    seconds]."""
    from repro.obs import metrics as obs_metrics
    delta = obs_metrics.REGISTRY.delta(before)
    secs = delta.get("compile_seconds", {}).get("series", {})
    return {k: [v, secs.get(k, 0.0)]
            for k, v in delta.get("compiles", {}).get("series", {}).items()
            if v}


def seed_feed(driver, cell, setup, make, seed: int):
    """The setup with seed ``seed``'s token stream, and a state from that
    seed's weights."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine as E
    from repro.data.lm import SyntheticLM, SyntheticLMConfig
    setup = dataclasses.replace(setup, data=SyntheticLM(SyntheticLMConfig(
        setup.cfg.vocab_size, setup.args.seq, seed=seed)))
    n = cell["deploy"]["workers"]

    def init(k):
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), make(k))
        return E.init_state(stacked, setup.ecfg, setup.solver)

    return setup, driver.placed(setup, init, harness.seed_key(seed))


def window(setup, state, step, rounds: int, first: int):
    """``rounds`` rounds from round ``first``, traced; returns the state,
    the trace's capture and the host clock's seconds a round."""
    import jax
    for i in range(first, first + 2):
        state, m = step(state, setup.batch(i), setup.key(i))
    jax.block_until_ready(state)
    cap = trace_reduce.Capture()
    cap.start()
    pending = deque()
    t0 = time.perf_counter()
    for i in range(first + 2, first + 2 + rounds):
        state, m = step(state, setup.batch(i), setup.key(i))
        pending.append(m["loss"])
        if len(pending) > AHEAD:
            pending.popleft().block_until_ready()
    jax.block_until_ready(state)
    round_s = (time.perf_counter() - t0) / rounds
    cap.stop()
    return state, cap, round_s


def summary(split, round_s, seed):
    r = split["rounds"]
    per = {p: 1e3 * v / r for p, v in split["phases_s"].items()}
    busy = 1e3 * split["busy_s"] / r
    spans = split["program_spans"]

    def mean_ms(name):
        v = spans.get(name)
        return 1e3 * sum(v) / len(v) if v else None

    return {"seed": seed, "rounds": r,
            "ms_per_round": per,
            "top_ops": split["top_ops"],
            "top_straddling": split["top_straddling"],
            "straddling_ms_per_round": 1e3 * split["straddling_s"] / r,
            "busy_ms_per_round": busy,
            "parts_over_busy": sum(per.values()) / busy,
            "unscoped_share": per["unscoped"] / busy,
            "host_round_ms": 1e3 * round_s,
            "input_ms": mean_ms("repro.train.input"),
            "input_generate_ms": mean_ms("repro.train.input.generate")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", default=None,
                    help="directory for the round's compiled text and each "
                         "seed's per-instruction table (phases.op_table), "
                         "from which phases.split recomputes the split")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(bench.root / "src"))
    harness.require_chips(cell["chips"])
    from repro.launch import compile_cache
    from repro.obs import metrics as obs_metrics
    compile_cache.enable()
    driver = harness.load_module(cell["driver"], "chipbench_driver")

    setup, state, step, make, key = driver.build(cell, seeds[0])
    state, _ = driver.first_rounds(setup, state, step, make, key)
    import jax
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - harness.PROCESS_T0
    stages = obs_metrics.compile_stage_seconds()
    secs = obs_metrics.REGISTRY.snapshot()["compile_seconds"]["series"]
    top = sorted(secs.items(), key=lambda kv: -kv[1])[:8]
    out = {"workload": args.workload, "setup_s": setup_s,
           "setup_stages_s": stages, "setup_top_funs_s": top, "seeds": []}
    harness.log(json.dumps({k: out[k] for k in ("setup_s", "setup_stages_s",
                                                "setup_top_funs_s")}))
    text = None
    for n, seed in enumerate(seeds):
        if n:
            del state
            gc.collect()
            setup, state = seed_feed(driver, cell, setup, make, seed)
        before = obs_metrics.REGISTRY.snapshot()
        state, cap, round_s = window(setup, state, step, args.rounds,
                                     driver.REF_ROUNDS)
        in_window = compile_events(before)
        if text is None:
            before = obs_metrics.REGISTRY.snapshot()
            text = step.lower(state, setup.batch(0),
                              setup.key(0)).compile().as_text()
            out["fetch_compile_events"] = compile_events(before)
        t0 = time.perf_counter()
        try:
            table = phases.op_table(cap.path())
        finally:
            shutil.rmtree(cap.dir, ignore_errors=True)
        split = phases.split(table, text)
        rec = summary(split, round_s, seed)
        rec["reduce_s"] = time.perf_counter() - t0
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            with gzip.open(keep / f"ops-{seed}.json.gz", "wt") as f:
                json.dump(table, f)
            if n == 0:
                with gzip.open(keep / "step.hlo.txt.gz", "wt") as f:
                    f.write(text)
        rec["window_compile_events"] = in_window
        out["seeds"].append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items() if k != "seeds"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
