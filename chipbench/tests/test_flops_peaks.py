"""FLOP counters against the compiler's own count, and the peak table."""
import json

import jax
import jax.numpy as jnp
import pytest

import harness
import program
import weights

# the configuration's depth (one whole period) at a smaller width
SMOKE = {
    "xlstm-125m": (dict(hidden_size=256, vocab_size=512, slstm_ff=341), 256),
}


def _hlo_flops(name):
    from repro.models import registry
    from repro.runtime.hlo_analysis import analyze_hlo
    over, seq = SMOKE[name]
    cfg = json.loads((harness.HERE / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    mc = program.model_config(cfg)
    params = weights.program_layout(mc)
    batch = {k: jax.ShapeDtypeStruct((2, seq), jnp.int32)
             for k in ("tokens", "labels")}

    def loss(p, b):
        return registry.lm_loss(p, mc, b)[0]

    fwd = analyze_hlo(jax.jit(loss).lower(params, batch).compile()
                      .as_text()).flops
    grad = analyze_hlo(jax.jit(jax.grad(loss)).lower(params, batch)
                       .compile().as_text()).flops
    fl = harness.load_module(harness.HERE / "flops" / f"{cfg['family']}.py",
                             "flops_" + cfg["family"])
    return cfg, fl, seq, fwd, grad


def test_xlstm_forward_bounds_compiled_dots():
    cfg, fl, seq, fwd, grad = _hlo_flops("xlstm-125m")
    ana = fl.forward_flops_per_token(cfg) * 2 * seq
    # the recurrent form is the least work; the program's chunkwise mLSTM
    # spends a little more on its intra-chunk products
    assert ana <= fwd <= 1.25 * ana
    assert 3.0 <= grad / fwd <= 4.2
    assert fl.train_flops_per_token(cfg, seq) == pytest.approx(
        3 * fl.forward_flops_per_token(cfg))


def test_peaks_by_device_kind():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")
