"""The benchmark's plain reference of the dense family
(``chipbench/reference/dense.py``: float32 ``jax.numpy``, attention in
query blocks against the keys each window reaches) against the program's
model (``models/registry.py``) on the Danube smoke configuration, with
its input and output embeddings tied and untied, at a sequence longer
than the sliding window. Both compute in float32 here, so they differ by
the order of float32 sums alone."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import layers, registry
from repro.obs import metrics as obs_metrics

BENCH = Path(__file__).resolve().parent.parent / "chipbench"
SEQ = 160                         # the smoke window is 64


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def dense():
    sys.path.insert(0, str(BENCH))
    try:
        mod = _load("reference_dense", BENCH / "reference" / "dense.py")
    finally:
        sys.path.remove(str(BENCH))
    return mod


def _case(tied, seq=SEQ):
    mc = base.get_smoke_config("h2o-danube-1.8b").with_overrides(
        dtype="float32", tie_embeddings=tied)
    cfg = {"num_hidden_layers": mc.num_layers, "hidden_size": mc.d_model,
           "num_attention_heads": mc.num_heads,
           "num_key_value_heads": mc.num_kv_heads,
           "sliding_window": mc.sliding_window, "rope_theta": mc.rope_theta,
           "rms_norm_eps": mc.norm_eps, "tie_word_embeddings": tied}
    params = registry.init_params(mc, jax.random.PRNGKey(0))
    # unit norm scales would hide a misplaced norm
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(p))), x.shape)
        if "scale" in jax.tree_util.keystr(p) else x, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0,
                                mc.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    return mc, cfg, params, tokens, labels


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_reference_matches_program_logits_and_loss(dense, monkeypatch,
                                                   tied):
    monkeypatch.setattr(dense, "BLOCK_Q", 48)      # blocks cut the window
    mc, cfg, params, tokens, labels = _case(tied)
    assert ("lm_head" in params) is not tied
    mm = dense.Matmul("f32")
    with jax.default_matmul_precision("highest"):
        ref = dense.forward(params, tokens, cfg, mm)
        got, _, _ = registry.apply_model(params, mc, {"tokens": tokens})
        ref_loss = dense.lm_loss(params, tokens, labels, cfg, mm)
        loss, _ = registry.lm_loss(params, mc, {"tokens": tokens,
                                                "labels": labels})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))


def test_reference_matches_program_on_the_flash_path(dense, monkeypatch):
    """The registry's forward through the flash kernel
    (``kernels/flash_attention``, interpret mode here), reached by taking
    the platform for a TPU and lowering the blockwise threshold below the
    sequence: 256 positions, two flash blocks of 128 against the smoke
    window of 64."""
    monkeypatch.setattr(dense, "BLOCK_Q", 48)
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(layers, "MHA_BLOCKWISE_THRESHOLD", 128)
    mc, cfg, params, tokens, labels = _case(False, seq=256)
    assert mc.sliding_window < tokens.shape[1]
    mm = dense.Matmul("f32")
    paths = obs_metrics.attention_path_counter()
    before = paths.value(path="flash", masked="window")
    with jax.default_matmul_precision("highest"):
        ref = dense.forward(params, tokens, cfg, mm)
        got, _, _ = registry.apply_model(params, mc, {"tokens": tokens})
        ref_loss = dense.lm_loss(params, tokens, labels, cfg, mm)
        loss, _ = registry.lm_loss(params, mc, {"tokens": tokens,
                                                "labels": labels})
    # one trace of the scanned layer in each of the two forward passes
    assert paths.value(path="flash", masked="window") - before == 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))


def test_window_masks_keys_beyond_it(dense, monkeypatch):
    """A token more than a window back moves no logit; one inside it
    does."""
    monkeypatch.setattr(dense, "BLOCK_Q", 48)
    mc, cfg, params, tokens, _ = _case(False)
    mm = dense.Matmul("f32")
    w = mc.sliding_window
    far = tokens.at[:, 0].set((tokens[:, 0] + 1) % mc.vocab_size)
    with jax.default_matmul_precision("highest"):
        a = dense.forward(params, tokens, cfg, mm)
        b = dense.forward(params, far, cfg, mm)
    # position 0 reaches queries up to w - 1 in one layer, 2 (w - 1) in two
    reach = mc.num_layers * (w - 1)
    assert float(jnp.max(jnp.abs(a[:, reach + 1:] - b[:, reach + 1:]))) == 0
    assert float(jnp.max(jnp.abs(a[:, 1:w] - b[:, 1:w]))) > 0


def test_untied_head_is_counted():
    cfg = base.get_config("h2o-danube-1.8b")
    assert not cfg.tie_embeddings and cfg.norm_eps == 1e-5
    assert cfg.param_count() == 1_831_201_280
    tied = cfg.with_overrides(tie_embeddings=True).param_count()
    assert cfg.param_count() - tied == cfg.d_model * cfg.vocab_size
    assert "['lm_head']['w']" in registry.param_buckets(cfg)["embed"]
