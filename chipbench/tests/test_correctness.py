"""The comparison that decides ``correct`` fails what it must, at a size a
CPU test can hold: the control (the reference in fp8 put in the program's
place) and each fault a cell can have, planted in the timed path while
the rest of a run (set-up, window, reference, limits of the committed
workload files) goes on as on the chip. The configuration keeps its
depth, one whole period of its layer pattern, at a smaller width."""
import jax
import pytest

import checks
import harness

SEED = 2 ** 33 + 5


def _train_cell(name="xlstm-train-solve"):
    cell = harness.Bench().cell(name)
    cell["config"].update(hidden_size=64, vocab_size=512, slstm_ff=85)
    cell["traffic"].update(seq=32)
    return cell


def _driver(cell):
    return harness.load_module(cell["driver"], "drv_" + cell["name"])


def _run(cell, seconds=1.0):
    return _driver(cell).run(cell, seed=SEED, seconds=seconds, trace=False,
                             devices=jax.devices())


def test_train_control_fails():
    cell = _train_cell()
    drv = _driver(cell)
    ref = drv.reference(cell, SEED)
    control = drv.reference(cell, SEED, "fp8")
    out = checks.judge(checks.train_checks(control, ref),
                       cell["deploy"]["correct"])
    assert not checks.all_within(out), out


def test_train_state_left_unchanged_fails(monkeypatch):
    from repro.core import engine as E
    make_step = E.make_step

    def frozen(*a, **kw):
        inner = make_step(*a, **kw)

        def step(state, batch, key, participation=None):
            _, metrics = inner(state, batch, key)
            return state, metrics
        return step

    monkeypatch.setattr(E, "make_step", frozen)
    out = _run(_train_cell())
    assert out["correct"] is False
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_fails(monkeypatch):
    from repro.models import registry
    lm_loss = registry.lm_loss

    def half(params, cfg, batch, **kw):
        # the first half of every row's tokens: a cell's batch may be
        # one row
        return lm_loss(params, cfg, {k: v[:, : v.shape[1] // 2]
                                     for k, v in batch.items()}, **kw)

    monkeypatch.setattr(registry, "lm_loss", half)
    out = _run(_train_cell())
    assert out["correct"] is False
    # the parameters' change shows the fault, as on the chip
    assert out["checks"]["update_gap"]["value"] > \
        out["checks"]["update_gap"]["limit"], out["checks"]


def test_train_nonfinite_window_loss_fails(monkeypatch):
    from repro.core import engine as E
    import jax.numpy as jnp
    make_step = E.make_step

    def diverging(*a, **kw):
        inner = make_step(*a, **kw)

        def step(state, batch, key, participation=None):
            k = state.k
            state, metrics = inner(state, batch, key)
            # the window's second round and later report a NaN loss
            loss = jnp.where(k >= 4, jnp.nan, metrics["loss"])
            return state, dict(metrics, loss=loss)
        return step

    monkeypatch.setattr(E, "make_step", diverging)
    out = _run(_train_cell("xlstm-train-exchange"))
    assert out["failed"] >= 1
    assert out["checks"]["window_nonfinite_losses"]["value"] >= 1
    assert out["correct"] is False
    # the first rounds, which the reference follows, are still sound
    assert out["checks"]["loss_gap"]["value"] <= \
        out["checks"]["loss_gap"]["limit"]
