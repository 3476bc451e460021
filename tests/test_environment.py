"""The training path reads no environment variable: each switch it once
read (the mLSTM's sequential scan at training length, the sLSTM step
without remat, a sequence-parallel residual rule) changes nothing when set.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro.configs import base
from repro.launch import sharding as SH
from repro.models import registry


def _xlstm_round_text():
    """Lowered forward and backward of the smoke xLSTM (one mLSTM and one
    sLSTM block) over 16 positions, traced afresh."""
    cfg = base.get_smoke_config("xlstm-125m")
    params = jax.eval_shape(
        lambda: registry.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def loss(p, batch):
        return registry.lm_loss(p, cfg, batch)[0]

    return jax.jit(jax.value_and_grad(loss)).lower(
        params, {"tokens": tokens, "labels": tokens}).as_text()


def _danube_rules():
    """The trainer's activation rules for a worker split over two chips
    (``AdmmSetup.build_step``) on H2O-Danube-1.8B."""
    mesh = AbstractMesh((2, 2), ("workers", "model"))
    return SH.activation_rules(mesh, base.get_config("h2o-danube-1.8b"),
                               batch_axes=(), worker_mode=True,
                               worker_axis="workers")


@pytest.mark.parametrize("name,program", [
    ("REPRO_MLSTM_SCAN", _xlstm_round_text),
    ("REPRO_SLSTM_NO_REMAT", _xlstm_round_text),
    ("REPRO_SEQ_PARALLEL", _danube_rules),
])
def test_former_switch_changes_nothing(name, program, monkeypatch):
    monkeypatch.delenv(name, raising=False)
    unset = program()
    monkeypatch.setenv(name, "1")
    assert program() == unset
