"""Observability layer: structured tracing, typed metrics, and the live
communication ledger (DESIGN.md §Observability).

Three host-side-only modules:

* :mod:`repro.obs.trace`   — span/instant/counter event tracer writing
  Chrome-trace / Perfetto-loadable JSON, enabled via ``REPRO_TRACE=<path>``
  or the ``--trace`` flags on the launch/bench drivers;
* :mod:`repro.obs.metrics` — typed registry of labeled counters, gauges
  and fixed-bucket histograms with atomic snapshot/delta export (the
  scheduler's latency windows and the kernel-dispatch counters live here);
* :mod:`repro.obs.ledger`  — cumulative rounds / bits / transmit-energy /
  censoring-rate accounting over the engine's per-round metric arrays,
  streamed as trace counters.

Zero-overhead contract: nothing in this package ever adds an op to a
jitted/Pallas program — every observer consumes values the traced programs
already return on host (pinned by ``tests/test_obs.py``'s jaxpr test and
the tracing-ON golden rows in the engine/fleet/serving suites). The
engine's phase scopes (``admm.mix`` ... ``admm.metrics``, a
``jax.named_scope`` around each part of a round) are held to the same
contract: metadata in the compiled program's ``op_name``, no op.

Measurement on the profiler's clock: :func:`repro.obs.trace.span` enters
``jax.profiler.TraceAnnotation("repro.<subsystem>.<name>")`` always, and
records a JSON span only when the tracer is enabled, so a profiler trace
shows the program's own spans beside the device. The compile counter
(:func:`repro.obs.metrics.install_compile_listener`) folds JAX's trace,
lowering and compile events into ``compile_seconds{stage, fun}`` and
``compiles{stage, fun}``; a set-up's split into those stages, and a
delta of zero over a steady loop, are read from it.
"""
from repro.obs import ledger, metrics, trace  # noqa: F401
