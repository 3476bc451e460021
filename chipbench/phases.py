"""Device self time per part of the consensus round, from a profiler
trace (``.xplane.pb``) and the round's compiled HLO text.

The program runs each part of a round under a ``jax.named_scope``
(``admm.mix``, ``admm.solve``, ``admm.quantize``, ``admm.censor``,
``admm.commit``, ``admm.dual``, ``admm.metrics``; ``core/engine.py``),
which the compiler keeps in each instruction's ``op_name`` metadata. A
device operation in the trace is named by its instruction; this module
looks that instruction up in the compiled text and adds its **self time**
to its scope:

* self time is an operation's duration less the operations the trace
  nests inside it, so a ``while`` is not counted again in its body's
  operations;
* an instruction is attributed by its own ``op_name``, a fusion whose own
  metadata is empty by its fused root's;
* time with no ``admm.*`` scope, and every operation of another program,
  goes to ``unscoped``.

``program_spans`` holds the durations of the program's own host spans
(``repro.<subsystem>.<name>``, ``obs.trace.span``) by name.

``straddling_s`` is the self time of fusions whose body holds instructions
of more than one scope (the compiler fused across a boundary, so the whole
fusion counts for its root's scope). ``uncovered_s`` is the busy time that
no operation's self time covers (the program's own start and end on the
device).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Set, Tuple

import trace_reduce

PHASES = ("mix", "solve", "quantize", "censor", "commit", "dual", "metrics")
SCOPE = re.compile(r"\badmm\.(" + "|".join(PHASES) + r")\b")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPCODE = re.compile(r"(?:^|\s)[a-z][\w\-]*\(")
_NAME = re.compile(r"%([\w.\-]+)")
# How many instructions the search for a compiler-made instruction's part
# may visit.
_SEARCH = 4096
# The program's own host spans (``obs.trace.span``) on the profiler's trace.
PROGRAM_PREFIX = "repro."
# An event that ends this much after the one around it is still nested in
# it: the trace's times are whole nanoseconds.
SLACK_S = 1e-9


def scope_of(op_name: str) -> Optional[str]:
    """The round part an ``op_name`` belongs to, or None."""
    m = SCOPE.search(op_name)
    return m.group(1) if m else None


def _operands(line: str) -> List[str]:
    """The instructions an HLO line reads: the ``%`` names inside its
    opcode's parentheses."""
    rhs = line.split(" = ", 1)[1]
    m = _OPCODE.search(rhs)
    if not m:
        return []
    depth, j = 1, m.end()
    while j < len(rhs) and depth:
        depth += {"(": 1, ")": -1}.get(rhs[j], 0)
        j += 1
    return _NAME.findall(rhs[m.end():j])


def hlo_scopes(text: str) -> Tuple[Dict[str, Optional[str]],
                                   Dict[str, Set[str]]]:
    """``(scope, straddling)`` for a compiled module's text: each
    instruction's round part (None: none), and the fusions whose body holds
    instructions of more than one part, with those parts.

    An instruction's part is its own ``op_name``'s; a fusion with none
    takes its fused root's, or the one part its body holds. The compiler
    makes instructions of its own with no ``op_name`` (copies, the
    ``dynamic-update-slice`` chains that pack leaves into one buffer,
    async copies' ``copy-done``, fusions of those): such an instruction
    takes the part of the nearest instruction of its computation that
    reads what it writes, following readers breadth first, else of the
    nearest that it reads."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    body: Dict[str, List[str]] = defaultdict(list)
    root: Dict[str, str] = {}
    reads: Dict[str, List[str]] = {}
    comp = None
    for line in text.splitlines():
        if comp is None:
            h = _HEADER.match(line)
            if h and " = " not in line:
                comp = h.group(1)
            continue
        if line.strip() == "}":
            comp = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        body[comp].append(name)
        reads[name] = _operands(line)
        if line.lstrip().startswith("ROOT"):
            root[comp] = name
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)

    def parts(comp_name: str, seen: Set[str]) -> Set[str]:
        out: Set[str] = set()
        for n in body.get(comp_name, ()):
            if own.get(n):
                out.add(own[n])
            if n in calls and calls[n] not in seen:
                seen.add(calls[n])
                out |= parts(calls[n], seen)
        return out

    scope = dict(own)
    straddling: Dict[str, Set[str]] = {}
    for name, comp_name in calls.items():
        inside = parts(comp_name, {comp_name})
        if scope.get(name) is None:
            scope[name] = own.get(root.get(comp_name)) or (
                next(iter(inside)) if len(inside) == 1 else None)
        if len(inside) > 1:
            straddling[name] = inside

    readers: Dict[str, List[str]] = defaultdict(list)
    for comp_name, names in body.items():
        mine = set(names)
        for n in names:
            for r in reads[n]:
                if r in mine:
                    readers[r].append(n)

    def nearest(start: str, edges: Dict[str, List[str]]) -> Optional[str]:
        seen, frontier = {start}, [start]
        while frontier and len(seen) < _SEARCH:
            nxt = []
            for n in frontier:
                for m in edges.get(n, ()):
                    if m in seen:
                        continue
                    if scope.get(m):
                        return scope[m]
                    seen.add(m)
                    nxt.append(m)
            frontier = nxt
        return None

    made = [n for n, v in scope.items() if v is None]
    found = {n: nearest(n, readers) or nearest(n, reads) for n in made}
    scope.update(found)
    return scope, straddling


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[Tuple[float, float, str], float]]:
    """Each ``(start, end, name)`` event with its self seconds: its
    duration less the events nested inside it (an event that only overlaps
    another is not nested in it)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [e - s for s, e, _ in evs]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and e > evs[stack[-1]][1] + SLACK_S:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return list(zip(evs, own))


def instruction(event_name: str) -> str:
    """``%fusion.3 = f32[2]{0} fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def cut_runs(run_of: List[int], mine: Set[int]) -> Set[int]:
    """The runs in ``mine`` that hold fewer operation events than the
    fullest of them; ``run_of`` gives each event's run."""
    held = defaultdict(int)
    for j in run_of:
        held[j] += j in mine
    full = max((held[j] for j in mine), default=0)
    return {j for j in mine if held[j] < full}


def op_table(path: str, program: str = "jit_step") -> Dict:
    """What the split needs from the trace at ``path``, averaged over
    devices: ``ops``, each instruction of ``program`` -> [self seconds,
    runs, the event's label (``trace_reduce.op_name``)]; ``other_s``,
    the self seconds of every other program's operations; ``busy_s``;
    ``rounds``, the runs of ``program`` on the first device whose
    operations the trace holds in full; and ``program_spans``.

    The profiler keeps a bounded number of device events: a run of the
    program that holds fewer operation events than its fullest run was cut
    short, and counts in none of these numbers (``partial_runs``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devs = trace_reduce.device_planes(pd)
    if not devs:
        raise ValueError(f"{path}: no TPU device plane")
    n = len(devs)
    ops: Dict[str, list] = {}
    other_s = busy_s = 0.0
    rounds = partial = 0
    for k, plane in enumerate(devs):
        mods = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
        line = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        runs = sorted((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                       trace_reduce.program_name(ev.name))
                      for ev in (mods.events if mods is not None else ()))
        starts = [r[0] for r in runs]
        events = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                  for ev in (line.events if line is not None else ())]
        run_of = [bisect.bisect_right(starts, s) - 1 for s, _, _ in events]
        mine = {j for j, r in enumerate(runs) if r[2] == program}
        cut = cut_runs(run_of, mine)
        if k == 0:
            rounds, partial = len(mine) - len(cut), len(cut)
        kept = [(s, e, name) for (s, e, name), j in zip(events, run_of)
                if j not in cut]
        busy_s += trace_reduce.total(trace_reduce.union(
            [(s, e) for j, (s, e, _) in enumerate(runs) if j not in cut]
            + [(s, e) for s, e, _ in kept]))
        for (s, _, name), own in self_times(kept):
            j = bisect.bisect_right(starts, s) - 1
            if j not in mine:
                other_s += own / n
                continue
            row = ops.setdefault(instruction(name),
                                 [0.0, 0, trace_reduce.op_name(name)])
            row[0] += own / n
            row[1] += 1
    spans: Dict[str, List[float]] = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        spans[ev.name].append(ev.duration_ns * 1e-9)
    return {"ops": ops, "other_s": other_s, "busy_s": busy_s / n,
            "rounds": rounds, "partial_runs": partial,
            "program_spans": dict(spans)}


def split(table: Dict, hlo_text: str, top: int = 8) -> Dict:
    """The self seconds of ``table`` (``op_table``) per round part of the
    program whose compiled text is ``hlo_text``; ``top_ops`` lists the
    ``top`` costliest instructions of each part, ``top_straddling`` the
    costliest fusions over several parts, with their parts."""
    scope, straddling = hlo_scopes(hlo_text)
    phases: Dict[str, float] = dict.fromkeys(PHASES + ("unscoped",), 0.0)
    phases["unscoped"] = table["other_s"]
    by_part: Dict[str, list] = defaultdict(list)
    straddle = []
    for inst, (own, runs, label) in table["ops"].items():
        part = scope.get(inst) or "unscoped"
        phases[part] += own
        by_part[part].append([label, own, runs])
        if inst in straddling:
            straddle.append([label, own, sorted(straddling[inst])])
    return {"phases_s": phases,
            "straddling_s": sum(r[1] for r in straddle),
            "busy_s": table["busy_s"],
            "uncovered_s": table["busy_s"] - sum(phases.values()),
            "rounds": table["rounds"],
            "partial_runs": table.get("partial_runs", 0),
            "program_spans": table["program_spans"],
            "top_ops": {p: sorted(v, key=lambda r: -r[1])[:top]
                        for p, v in by_part.items()},
            "top_straddling": sorted(straddle, key=lambda r: -r[1])[:top]}


def phase_split(path: str, hlo_text: str, program: str = "jit_step"
                ) -> Dict:
    """``split`` of the trace at ``path`` for ``program``, whose compiled
    text is ``hlo_text``."""
    return split(op_table(path, program), hlo_text)
