#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and deployment are found by name
(``harness.Bench``). The run builds its weights and inputs from ``--seed``,
warms up every shape it uses (set-up, ``setup_s``), measures for
``--seconds``, then checks what the timed path produced against the plain
reference under ``reference/``. With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window. The last stdout line is one JSON
object; the compared numbers and their limits close stderr and the line.

There is no fallback: without a TPU (or with fewer chips than the cell
asks for) the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402  (first: it stamps the process start time)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(bench.root / "src"))
    devs = harness.require_chips(cell["chips"])
    from repro.launch import compile_cache
    compile_cache.enable()

    driver = harness.load_module(cell["driver"], "chipbench_driver")
    out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devs)

    if args.trace:
        metrics = {}
        for m in bench.per_layer(args.workload):
            value = bench.reader(m["name"]).read(out["record"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench.end_to_end(args.workload)}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if args.trace:
        result["breakdown"] = out["record"]["breakdown"]
    for name, chk in out["checks"].items():
        harness.log(f"check {name}: {chk['value']!r} limit {chk['limit']!r}")
    result["checks"] = out["checks"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchError as e:
        harness.log(f"chipbench: {e}")
        sys.exit(2)
