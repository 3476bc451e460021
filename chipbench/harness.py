"""What every cell shares: finding a cell's files by name, the device,
peaks, seeds, timing of set-up, the per-layer readers and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its pieces are
files found by name, so adding a cell, a configuration, a traffic mix or
a per-layer metric adds files and entries and edits none:

* ``configs/<config>.json``     the configuration (the entry's ``file``)
* ``traffic/<traffic>.json``    the traffic mix; its ``kind`` names the
                                driver ``drivers/<kind>.py``
* ``workloads/<cell>.json``     the cell's deployment cut and the limits
                                of its correctness check
* ``metrics/<metric>.py``       one reader per per-layer metric
* ``flops/<family>.py``         model FLOPs per token, from shapes only
* ``peaks.json``                the chip's peaks by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# The moment the process started (``run.py`` imports this first): set-up
# is timed from here, imports and compilation included.
PROCESS_T0 = time.perf_counter()


class BenchError(Exception):
    """A cell that cannot run as specified: a missing file or entry, a
    device that is not the one asked for."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (per-layer readers and drivers carry dots or
    dashes in their names, so they are not importable as packages)."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, below ``root``."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = HERE):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"no BENCHMARK.json in {self.root}")
        self.spec = load_json(path)
        self._check_layer_metrics()

    # ------------------------------------------------------------ lookup --
    def _entry(self, kind: str, name: str) -> Dict:
        for e in self.spec[kind]:
            if e["name"] == name:
                return e
        raise BenchError(f"{kind}: no entry named {name!r}")

    def workload(self, name: str) -> Dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> Dict:
        entry = self._entry("configs", name)
        return load_json(self.root / entry["file"])

    def traffic(self, name: str) -> Dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def cell(self, name: str) -> Dict:
        """Everything one run of cell ``name`` is built from."""
        w = self.workload(name)
        traffic = self.traffic(w["traffic"])
        return {"name": name, "chips": int(w["chips"]),
                "config": self.config(w["config"]),
                "traffic": traffic,
                "deploy": load_json(self.dir / "workloads" / f"{name}.json"),
                "driver": self.dir / "drivers" / f"{traffic['kind']}.py"}

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict]:
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           "chipbench_metric_" + metric.replace(".", "_"))

    def _check_layer_metrics(self) -> None:
        """Each per-layer metric moves one end-to-end metric, which every
        cell that reports it must report too."""
        e2e = {m["name"]: m for m in self.spec["end_to_end"]}
        cells = [w["name"] for w in self.spec["workloads"]]
        for m in self.spec["per_layer"]:
            moved = e2e.get(m["moves"])
            if moved is None:
                raise BenchError(f"per-layer {m['name']} moves "
                                 f"{m['moves']!r}, not an end-to-end metric")
            for c in m.get("workloads", cells):
                if c not in moved.get("workloads", cells):
                    raise BenchError(
                        f"per-layer {m['name']} is read in {c}, which does "
                        f"not report {m['moves']}")


# ------------------------------------------------------------- device --
def require_chips(chips: int):
    """The TPU devices a cell runs on; anything else is an error, never a
    fallback to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def device_record(devs) -> Dict:
    """Platform, kind and count as JAX reports them, and the peak memory
    of the fullest chip: the runtime counts live buffers as bytes in use
    and a program's temp space as reserved bytes."""
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(device_kind: str) -> Dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


# -------------------------------------------------------------- seeds --
def seed_key(seed: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)

