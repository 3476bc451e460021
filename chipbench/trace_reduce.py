"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

``reduce_file`` reads the trace with ``jax.profiler.ProfileData`` and
returns, on one clock:

* ``busy_s``: the union of the intervals in which an operation or a
  program ran on a device, averaged over the devices traced;
  ``window_s``: the span the device trace covers, from its first event to
  its last (the profiler keeps a bounded number of device events, so a
  long trace covers less than the host's);
* ``programs``: device seconds per compiled program (``jit_<name>``, from
  the modules line), averaged over devices;
* ``collective_s``: the union of collective operations' intervals
  (all-gather, all-reduce, reduce-scatter, collective-permute,
  all-to-all), averaged over devices;
* ``host_spans``: the durations of the benchmark's own ``chipbench.*``
  host annotations, by name;
* ``clock_shift_s``: what was added to the device's times to put them on
  the host's clock (``clock_shift``); ``device_busy``: the first device's
  busy intervals on that clock;
* ``breakdown``: the ten device operations (``<program>/<instruction>
  <kind> <result type>``) that took most time, and the
  ten longest idle gaps of the first device, each named by the
  ``chipbench.*`` host span that covers it (``"none"`` if none does).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Tuple

COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "chipbench."


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def program_name(module: str) -> str:
    """``jit_decode(12)`` -> ``jit_decode``."""
    return module.split("(")[0].strip()


def op_name(event_name: str) -> str:
    """An op event is named by its HLO instruction, ``%fusion.3 =
    f32[2,8]{1,0} fusion(...)``: the instruction's name, kind and result
    type without layouts (``%fusion.3 fusion f32[2,8]``), the type cut to
    60 characters."""
    head, _, rest = event_name.partition(" = ")
    kind = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    if not kind:
        return head.strip()
    shape = re.sub(r"\{[^}]*\}", "", rest[:max(kind.start() - 1, 0)])
    return f"{head.strip()} {kind.group(1)} {shape.strip()[:60]}".strip()


def clock_shift(modules, dispatches) -> float:
    """Seconds to add to device times so that no program starts before the
    host dispatched it: the device plane's clock runs some hundreds of
    microseconds to milliseconds behind the host's. ``modules``: (program,
    start) device events; ``dispatches``: (program, start) host
    ``PjitFunction(<name>)`` events; the k-th run of a program is matched
    to its k-th dispatch."""
    dev, host = defaultdict(list), defaultdict(list)
    for name, t in modules:
        dev[name.replace("jit_", "", 1)].append(t)
    for name, t in dispatches:
        host[name].append(t)
    shift = 0.0
    for name, starts in dev.items():
        for d, h in zip(sorted(starts), sorted(host.get(name, []))):
            shift = max(shift, h - d)
    return shift


def device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devs = device_planes(pd)
    if not devs:
        raise ValueError(f"{path}: no TPU device plane")
    busy_per, coll_per = [], []
    programs: Dict[str, float] = defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    first_busy: List[Tuple[float, float]] = []
    modules: List[Tuple[str, float]] = []
    dispatches: List[Tuple[str, float]] = []
    lo, hi = float("inf"), float("-inf")
    for k, plane in enumerate(devs):
        mods = _line(plane, MODULES_LINE)
        runs = sorted((ev.start_ns * 1e-9, program_name(ev.name))
                      for ev in (mods.events if mods is not None else ()))
        starts = [r[0] for r in runs]
        ops = _line(plane, OPS_LINE)
        iv = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
              for ev in (mods.events if mods is not None else ())]
        coll = []
        for ev in (ops.events if ops is not None else ()):
            s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
            iv.append((s, e))
            name = op_name(ev.name)
            j = bisect.bisect_right(starts, s) - 1
            owner = runs[j][1] if j >= 0 else "?"
            op_time[f"{owner}/{name}"] += (e - s) / len(devs)
            if COLLECTIVE.search(name):
                coll.append((s, e))
        busy = union(iv)
        if busy:
            lo, hi = min(lo, busy[0][0]), max(hi, busy[-1][1])
        busy_per.append(total(busy))
        coll_per.append(total(union(coll)))
        if k == 0:
            first_busy = busy
        for ev in (mods.events if mods is not None else ()):
            programs[program_name(ev.name)] += ev.duration_ns * 1e-9 / len(
                devs)
            if k == 0:
                modules.append((program_name(ev.name), ev.start_ns * 1e-9))
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans[ev.name].append((ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
                elif ev.name.startswith("PjitFunction("):
                    dispatches.append((ev.name[len("PjitFunction("):-1],
                                       ev.start_ns * 1e-9))
    shift = clock_shift(modules, dispatches)
    first_busy = [(s + shift, e + shift) for s, e in first_busy]
    if lo <= hi:
        lo, hi = lo + shift, hi + shift
    all_spans = [(s, e, n) for n, v in spans.items() for s, e in v]
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(first_busy,
                                                       first_busy[1:])]
    gaps.sort(reverse=True)

    def cover(t0, t1):
        mid = 0.5 * (t0 + t1)
        inside = [(e - s, n) for s, e, n in all_spans if s <= mid <= e]
        return min(inside)[1] if inside else "none"

    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy_per) / len(devs),
        "window_s": max(hi - lo, 0.0),
        "programs": dict(programs),
        "collective_s": sum(coll_per) / len(devs),
        "host_spans": {n: [e - s for s, e in v] for n, v in spans.items()},
        "clock_shift_s": shift,
        "device_busy": first_busy,
        "span_intervals": {n: v for n, v in spans.items()},
        "breakdown": {
            "device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": [[cover(a, b), g] for g, a, b in gaps[:10]]},
    }


class Capture:
    """A profiler trace of part of a run, kept in a temporary directory
    (under ``TMPDIR``) until it is reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return found[0]

    def reduce(self) -> Dict:
        try:
            red = reduce_file(self.path())
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if red["busy_s"] <= 0:
            raise ValueError("traced window has no device operation")
        return red
