"""The trace reduction against a small trace recorded on one TPU v5e
(``testdata/probe_1chip.xplane.pb``, written by
``testdata/record_probe.py``): three host spans ``chipbench.step``, each
dispatching ``jit_prefill_chunk`` then ``jit_decode`` (a 2048 x 2048 f32
matmul and its transpose product), 5 ms of host sleep between spans."""
import pytest

import trace_reduce as tr

PROBE = tr.__file__.rsplit("/", 1)[0] + "/testdata/probe_1chip.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return tr.reduce_file(PROBE)


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                               (3, 4)]
    assert tr.total(tr.union([(0, 1), (0.5, 1.5)])) == 1.5


def test_busy_is_the_union_of_device_ops(red):
    # the probe's programs run one at a time: busy time is their sum
    assert red["busy_s"] == pytest.approx(sum(red["programs"].values()),
                                          rel=0.01)
    assert 0 < red["busy_s"] < 0.1 * red["window_s"]


def test_time_per_program(red):
    p = red["programs"]
    assert set(p) == {"jit_prefill_chunk", "jit_decode"}
    # three runs each: a 2048^3 matmul with a tanh, and one with a sin
    assert p["jit_prefill_chunk"] == pytest.approx(3 * 90.04e-6, rel=0.01)
    assert p["jit_decode"] == pytest.approx(3 * 177.84e-6, rel=0.01)


def test_no_collectives_on_one_chip(red):
    assert red["collective_s"] == 0.0
    assert tr.COLLECTIVE.search(tr.op_name(
        "%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} %p), dimensions={0}"))
    assert not tr.COLLECTIVE.search(tr.op_name(
        "%fusion.1 = f32[] fusion(f32[2] %all-reduce.7), kind=kLoop"))


def test_host_spans_and_device_on_one_clock(red):
    spans = red["span_intervals"]["chipbench.step"]
    assert len(red["host_spans"]["chipbench.step"]) == 3
    # the device plane's clock runs ~1.3 ms behind the host's here
    assert 0 < red["clock_shift_s"] < 5e-3
    for s, e in red["device_busy"]:
        assert any(a <= s and e <= b for a, b in spans), (s, e)


def test_gaps_named_by_the_host_span_around_them(red):
    gaps = red["breakdown"]["idle_gaps"]
    # the two longest gaps are the host's 5 ms sleeps between spans
    assert [g[0] for g in gaps[:2]] == ["none", "none"]
    assert all(4e-3 < g[1] < 8e-3 for g in gaps[:2])
    assert {g[0] for g in gaps} <= {"none", "chipbench.step"}


def test_clock_shift_matches_kth_run_to_kth_dispatch():
    mods = [("jit_f", 1.0), ("jit_f", 2.0), ("jit_g", 1.5)]
    disp = [("f", 1.2), ("f", 2.1), ("g", 1.55)]
    assert tr.clock_shift(mods, disp) == pytest.approx(0.2)
