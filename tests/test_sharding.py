"""Sharding policy logic on an abstract 16x16 mesh."""
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import base
from repro.launch import sharding as SH


@pytest.fixture(scope="module")
def mesh():
    return AbstractMesh((16, 16), ("data", "model"))


def test_col_parallel(mesh):
    cfg = base.get_config("tinyllama-1.1b")
    spec = SH.param_spec("['stack']['units']['p0']['attn']['q']['w']",
                         (22, 2048, 2048), mesh, cfg)
    assert spec == P(None, None, "model")


def test_row_parallel(mesh):
    cfg = base.get_config("tinyllama-1.1b")
    spec = SH.param_spec("['stack']['units']['p0']['attn']['o']['w']",
                         (22, 2048, 2048), mesh, cfg)
    assert spec == P(None, "model", None)


def test_worker_axis_prepended(mesh):
    cfg = base.get_config("tinyllama-1.1b")
    spec = SH.param_spec("['stack']['units']['p0']['attn']['q']['w']",
                         (16, 22, 2048, 2048), mesh, cfg,
                         worker_axis="data")
    assert spec == P("data", None, None, "model")


def test_nondivisible_axis_dropped(mesh):
    cfg = base.get_config("tinyllama-1.1b")
    # out dim 100 not divisible by 16 -> model axis dropped
    spec = SH.param_spec("['x']['q']['w']", (64, 100), mesh, cfg)
    assert spec == P(None, None)


def test_moe_expert_parallel_when_divisible(mesh):
    cfg = base.get_config("olmoe-1b-7b")         # 64 experts % 16 == 0
    spec = SH.param_spec("['stack']['units']['p0']['moe']['wi_gate']['w']",
                         (16, 64, 2048, 1024), mesh, cfg)
    assert spec == P(None, "model", None, None)


def test_moe_ff_tp_when_not_divisible(mesh):
    cfg = base.get_config("grok-1-314b")          # 8 experts % 16 != 0
    spec = SH.param_spec("['stack']['units']['p0']['moe']['wi_gate']['w']",
                         (64, 8, 6144, 32768), mesh, cfg)
    assert spec == P(None, None, None, "model")


def test_embed_table_vocab_sharded(mesh):
    cfg = base.get_config("tinyllama-1.1b")
    spec = SH.param_spec("['embed']['table']", (32000, 2048), mesh, cfg)
    assert spec == P("model", None)


def test_scalar_params_replicated(mesh):
    cfg = base.get_config("zamba2-7b")
    spec = SH.param_spec("['stack']['units']['p0']['mamba']['a_log']",
                         (67, 112), mesh, cfg)
    assert spec == P(None, None)


def test_activation_rules_expert_exclusive(mesh):
    cfg = base.get_config("olmoe-1b-7b")
    rules = SH.activation_rules(mesh, cfg)
    assert rules["expert"] == "model"
    assert rules["ff"] is None        # cannot both claim the model axis
    cfg2 = base.get_config("grok-1-314b")
    rules2 = SH.activation_rules(mesh, cfg2)
    assert rules2["expert"] is None
    assert rules2["ff"] == "model"
