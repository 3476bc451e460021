"""The phase reduction (``phases.py``) against a small trace recorded on
one TPU v5e (``testdata/scoped_step_1chip.xplane.pb``, and the compiled
text of its program, ``testdata/scoped_step_1chip.hlo.txt.gz``, both
written by ``testdata/record_scoped_step.py``): three rounds of a tiny
quantized, censored CQ-GGADMM step whose local solve is a ``while`` loop,
each part of the round under its ``admm.*`` scope. The probe of
``test_trace_reduce.py`` carries no scope."""
import gzip

import pytest

import phases
import trace_reduce

DATA = phases.__file__.rsplit("/", 1)[0] + "/testdata/"
TRACE = DATA + "scoped_step_1chip.xplane.pb"
PROBE = DATA + "probe_1chip.xplane.pb"


@pytest.fixture(scope="module")
def text():
    with gzip.open(DATA + "scoped_step_1chip.hlo.txt.gz", "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def split(text):
    return phases.phase_split(TRACE, text)


def _program_ops(path, program):
    """``(start, end, name)`` of the device ops run inside ``program``."""
    from jax.profiler import ProfileData
    plane = trace_reduce.device_planes(ProfileData.from_file(path))[0]
    mods = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
    runs = [(ev.start_ns, ev.end_ns) for ev in mods.events
            if trace_reduce.program_name(ev.name) == program]
    ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
    return [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
            for ev in ops.events
            if any(s <= ev.start_ns < e for s, e in runs)]


def test_every_part_of_the_round_has_device_time(split):
    assert split["rounds"] == 3
    for part in phases.PHASES:
        assert split["phases_s"][part] > 0, part


def test_parts_and_unscoped_add_up_to_the_busy_time(split):
    total = sum(split["phases_s"].values())
    assert total == pytest.approx(split["busy_s"], rel=0.01)
    assert split["uncovered_s"] == pytest.approx(split["busy_s"] - total)
    assert 0 <= split["straddling_s"] <= total


def test_a_while_loop_is_counted_once(text):
    ops = _program_ops(TRACE, "jit_step")
    loops = [o for o in ops if " while(" in o[2]]
    assert loops, "the recorded step keeps its local-solve loop"
    raw = sum(e - s for s, e, _ in ops)
    own = sum(t for _, t in phases.self_times(ops))
    busy = trace_reduce.total(trace_reduce.union([(s, e)
                                                  for s, e, _ in ops]))
    # the loop's events contain its body's: raw durations count it twice
    assert raw > 1.2 * busy
    assert own == pytest.approx(busy, rel=1e-6)


def test_busy_time_agrees_with_trace_reduce(split):
    for path, red in ((TRACE, split),
                      (PROBE, phases.phase_split(PROBE, "", "jit_decode"))):
        assert red["busy_s"] == pytest.approx(
            trace_reduce.reduce_file(path)["busy_s"], rel=1e-9)


def test_a_trace_without_scopes_is_all_unscoped():
    red = phases.phase_split(PROBE, "", program="jit_prefill_chunk")
    assert red["rounds"] == 3
    assert all(red["phases_s"][p] == 0 for p in phases.PHASES)
    assert red["phases_s"]["unscoped"] == pytest.approx(red["busy_s"],
                                                        rel=0.01)
    assert red["program_spans"] == {}


HLO = """HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/admm.quantize/mul"}
  ROOT %add.2 = f32[4]{0} add(%multiply.1, %param_0), metadata={op_name="jit(step)/admm.censor/add"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %exponential.3 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(step)/admm.solve/transpose(jvp(admm.solve))/exp"}
}

%fused_computation.2 (param_0.2: f32[4]) -> (f32[4], f32[4]) {
  %param_0.2 = f32[4]{0} parameter(0)
  %sine.6 = f32[4]{0} sine(%param_0.2), metadata={op_name="jit(step)/admm.dual/sin"}
  %cosine.7 = f32[4]{0} cosine(%param_0.2), metadata={op_name="jit(step)/admm.dual/cos"}
  ROOT %tuple.8 = (f32[4]{0}, f32[4]{0}) tuple(%sine.6, %cosine.7)
}

ENTRY %main.9 (x: f32[4]) -> (f32[4], (f32[4], f32[4])) {
  %x = f32[4]{0} parameter(0)
  %fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/admm.solve/exp"}
  %copy.4 = f32[4]{0} copy(%fusion.1)
  %negate.5 = f32[4]{0} negate(%copy.4), metadata={op_name="jit(step)/admm.metrics/neg"}
  %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%negate.5), kind=kLoop, calls=%fused_computation.2
  %copy.10 = f32[4]{0} copy(%x)
  %add.11 = f32[4]{0} add(%copy.10, %negate.5)
  %constant.13 = f32[] constant(0)
  ROOT %tuple.12 = (f32[4]{0}, (f32[4]{0}, f32[4]{0})) tuple(%add.11, %fusion.2)
}
"""


def test_hlo_scopes_own_then_fused_root_then_data_flow():
    scope, straddling = phases.hlo_scopes(HLO)
    assert scope["fusion"] == "censor"           # its fused root's
    assert scope["fusion.1"] == "solve"          # its own
    assert scope["negate.5"] == "metrics"
    assert scope["fusion.2"] == "dual"           # the one part of its body
    # compiler-made, no op_name: the nearest reader's part ...
    assert scope["copy.4"] == "metrics"
    assert scope["x"] == "censor"
    # ... else the nearest read one's
    assert scope["add.11"] == "metrics"
    # nothing scoped around it
    assert scope["copy.10"] is None
    assert scope["constant.13"] is None
    assert straddling == {"fusion": {"quantize", "censor"}}
    assert phases.scope_of("jit(step)/admm.dualx/y") is None


def test_self_time_subtracts_nested_events_only():
    got = dict((ev[2], t) for ev, t in phases.self_times(
        [(0, 10, "loop"), (1, 3, "a"), (3, 5, "b"), (6, 12, "after"),
         (7, 8, "c")]))
    # "after" overlaps the loop without nesting in it
    assert got == {"loop": 6, "a": 2, "b": 2, "after": 5, "c": 1}
    assert phases.instruction(
        "%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop") == \
        "fusion.3"


def test_runs_the_profiler_cut_short_are_left_out():
    # runs 0 and 2 are the program's; the trace kept 3 events of run 2
    run_of = [0] * 4 + [1] * 9 + [2] * 3 + [-1]
    assert phases.cut_runs(run_of, {0, 2}) == {2}
    assert phases.cut_runs(run_of, {0}) == set()
    assert phases.cut_runs([], set()) == set()
