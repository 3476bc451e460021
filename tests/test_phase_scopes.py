"""Measurement inside the program (DESIGN.md §Observability).

* Phase scopes: the compiled program of a quantized, censored engine step
  names its instructions by the part of the round they belong to — all
  seven ``admm.*`` scopes reach the compiled HLO's ``op_name`` metadata,
  on the alternating path, on the Jacobian path, and where each phase
  solves and quantizes only its acting rows.
* Program spans: ``obs.trace.span`` puts ``repro.<subsystem>.<name>`` on
  the profiler's host plane whether or not the JSON tracer is on, and a
  balanced span in the JSON trace when it is; the trainer's input path
  (``AdmmSetup.batch``) shows as ``repro.train.input`` with its child
  ``repro.train.input.generate``.
* Compile counter: one trace, one lowering and one compile for a
  function's first call, none for its second, no lowering or compile for
  ``.lower().compile()`` after a call; nested events count once.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as E
from repro.core.censoring import CensorConfig
from repro.core.graph import random_bipartite_graph
from repro.core.quantization import QuantConfig
from repro.core.solvers import LinearRegressionProblem
from repro.data import regression as R
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.trace import validate_events

N, DIM = 6, 12
SCOPES = ("admm.mix", "admm.solve", "admm.quantize", "admm.censor",
          "admm.commit", "admm.dual", "admm.metrics")


def _rowwise_solver():
    """An inexact solver whose data rides in the batch (row-wise grad)."""
    def loss(p, b):
        pred = b["x"][:, :DIM - 4] @ p["w"] + b["x"][:, DIM - 4:] @ p["b"]
        return jnp.mean(jnp.square(pred - b["y"]))

    return E.InexactSolver(grad_fn=lambda theta, b: jax.vmap(
        jax.grad(loss))(theta, b), local_steps=2)


def _step_and_state(alternating: bool, batched: bool = False):
    data = R.synth_linear(n=N * 30, d=DIM, seed=0)
    g = random_bipartite_graph(N, 0.4, seed=0)
    x, y = R.partition_uniform(data, N)
    solver = _rowwise_solver() if batched else E.ExactSolver(
        LinearRegressionProblem(jnp.asarray(x), jnp.asarray(y)))
    cfg = E.EngineConfig(rho=1.0, censor=CensorConfig(tau0=0.5, xi=0.97),
                         quantize=QuantConfig(b0=2, omega=0.99),
                         groups="leaf", censor_mode="group",
                         alternating=alternating)
    theta0 = {"w": jnp.zeros((N, DIM - 4), jnp.float32),
              "b": jnp.zeros((N, 4), jnp.float32)}
    step = jax.jit(E.make_step(g, cfg, solver,
                               extra_metrics=E.consensus_metrics()))
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)} if batched else None
    return step, E.init_state(theta0, cfg, solver), batch


@pytest.mark.parametrize("alternating,batched",
                         [(True, False), (False, False), (True, True)],
                         ids=["alternating", "jacobian", "acting-rows"])
def test_compiled_step_carries_every_phase_scope(alternating, batched):
    """``acting-rows``: the solve and the quantize take only each phase's
    rows (an inexact solver fed a batch) and keep their scopes."""
    step, state, batch = _step_and_state(alternating, batched)
    text = step.lower(state, batch, jax.random.PRNGKey(0)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {m for n in names for m in re.findall(r"admm\.[a-z]+", n)}
    assert found == set(SCOPES)


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_program_span_reaches_profiler_and_json(tmp_path):
    with jax.profiler.trace(str(tmp_path / "off")):
        with obs_trace.span("probe", "test"):
            pass
    assert "repro.test.probe" in _host_event_names(tmp_path / "off")

    tr = obs_trace.enable(str(tmp_path / "t.json"))
    try:
        with jax.profiler.trace(str(tmp_path / "on")):
            with obs_trace.span("outer", "test", track="t", args={"i": 1}):
                with obs_trace.span("inner", "test", track="t"):
                    pass
        doc = {"traceEvents": list(tr._events)}
    finally:
        obs_trace.disable(save=False)
    assert {"repro.test.outer", "repro.test.inner"} <= _host_event_names(
        tmp_path / "on")
    assert validate_events(doc) == []
    spans = [(e["ph"], e["name"]) for e in doc["traceEvents"]
             if e["ph"] in "BE"]
    assert spans == [("B", "outer"), ("B", "inner"), ("E", "inner"),
                     ("E", "outer")]


def test_trainer_input_spans_on_profiler_host_plane(tmp_path):
    from repro.configs import base
    from repro.launch import train
    args = train.build_parser().parse_args([
        "--smoke", "--workers", "2", "--batch", "2", "--seq", "8"])
    setup = train.admm_setup(base.get_smoke_config(args.arch), args)
    setup.batch(0)                       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(setup.batch(1))
    names = _host_event_names(tmp_path)
    assert {"repro.train.input", "repro.train.input.generate"} <= names


def _deltas(before):
    d = obs_metrics.REGISTRY.delta(before)
    return ({k: v for k, v in d["compiles"]["series"].items() if v},
            {k: v for k, v in d["compile_seconds"]["series"].items() if v})


def test_compile_counter_counts_each_stage_once():
    obs_metrics.install_compile_listener()
    obs_metrics.install_compile_listener()      # idempotent

    def counted_fn(x):
        return x * 3.0 - 1.0

    f = jax.jit(counted_fn)
    x = jnp.ones((5,), jnp.float32)
    jax.block_until_ready(x)
    s0 = obs_metrics.REGISTRY.snapshot()
    jax.block_until_ready(f(x))
    counts, secs = _deltas(s0)
    for stage in ("trace", "lower", "compile"):
        assert counts[f"{stage},counted_fn"] == 1.0
        assert secs[f"{stage},counted_fn"] > 0.0

    s1 = obs_metrics.REGISTRY.snapshot()
    jax.block_until_ready(f(x))
    assert _deltas(s1) == ({}, {})

    s2 = obs_metrics.REGISTRY.snapshot()
    text = f.lower(x).compile().as_text()
    counts, secs = _deltas(s2)
    assert "multiply" in text
    # JAX's trace cache answers the trace; nothing lowers or compiles
    assert {k.split(",")[0] for k in counts} <= {"trace"}
    assert sum(secs.values()) < 1e-3


def test_compile_listener_subtracts_nested_events(tmp_path):
    listener = obs_metrics._CompileListener()
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    lower_ev = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    s0 = obs_metrics.REGISTRY.snapshot()
    tr = obs_trace.enable(str(tmp_path / "t.json"))
    try:
        # reported as JAX reports them: each event when it ends
        listener(trace_ev, 10.5, 11.0, fun_name="nested_inner")
        listener(trace_ev, 11.5, 12.0, fun_name="nested_inner")
        listener(trace_ev, 10.0, 13.0, fun_name="nested_outer")
        listener(lower_ev, 13.0, 14.0, fun_name="jit(nested_outer)")
        listener("/jax/other", 0.0, 1.0, fun_name="nested_outer")
        doc = {"traceEvents": list(tr._events)}
    finally:
        obs_trace.disable(save=False)
    counts, secs = _deltas(s0)
    assert secs == {"trace,nested_inner": 1.0, "trace,nested_outer": 2.0,
                    "lower,nested_outer": 1.0}
    assert counts == {"trace,nested_inner": 2.0, "trace,nested_outer": 1.0,
                      "lower,nested_outer": 1.0}
    assert validate_events(doc) == []
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "B"]
    assert names == ["trace nested_inner", "trace nested_inner",
                     "trace nested_outer", "lower nested_outer"]
    assert obs_metrics.compile_stage_seconds(
        obs_metrics.REGISTRY.delta(s0)) == {"trace": 3.0, "lower": 1.0,
                                            "compile": 0.0}
