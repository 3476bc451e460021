"""End-to-end system tests: decentralized LM training, serving, and the
train/serve launchers (CPU-sized)."""
import jax
import numpy as np
import pytest

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


@pytest.mark.slow
def test_train_admm_end_to_end(tmp_path):
    out = train_mod.main([
        "--arch", "tinyllama-1.1b", "--smoke", "--mode", "admm",
        "--workers", "2", "--steps", "8", "--batch", "4", "--seq", "32",
        "--local-steps", "2", "--log-every", "4",
        "--ckpt-dir", str(tmp_path)])
    assert np.isfinite(out["final_loss"])
    assert out["total_bits"] > 0
    from repro.checkpoint import npz as ckpt
    assert ckpt.latest_step(tmp_path) == 8


def test_train_fsdp_end_to_end():
    out = train_mod.main([
        "--arch", "xlstm-125m", "--smoke", "--mode", "fsdp",
        "--steps", "6", "--batch", "4", "--seq", "32", "--lr", "3e-3",
        "--log-every", "3"])
    assert np.isfinite(out["final_loss"])
    # learnable synthetic stream: loss should move down from init
    assert out["history"][-1] < out["history"][0]


def test_serve_end_to_end():
    out = serve_mod.main(["--arch", "tinyllama-1.1b", "--smoke",
                          "--batch", "2", "--prompt-lens", "9,5,13",
                          "--decode-tokens", "4"])
    assert sorted(out["outputs"]) == [0, 1, 2]
    assert all(v.shape == (4,) for v in out["outputs"].values())
    assert out["final_pages_in_use"] == 0          # no page leaks


def test_serve_lockstep_baseline():
    out = serve_mod.main(["--arch", "tinyllama-1.1b", "--smoke",
                          "--engine", "lockstep", "--batch", "2",
                          "--prompt-len", "8", "--requests", "3",
                          "--decode-tokens", "4", "--sample", "temp",
                          "--temperature", "0.7"])
    assert all(v.shape == (4,) for v in out["outputs"].values())


def test_lockstep_temp_sampling_varies_across_waves():
    """Same prompt, same slot, consecutive waves: temperature sampling
    must draw fresh randomness per wave (keys carry a wave component)."""
    from repro.configs import base
    from repro.models import registry
    cfg = base.get_smoke_config("tinyllama-1.1b")
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(1, 9, dtype=np.int32)
    out = serve_mod.run_lockstep(cfg, params, [prompt] * 4, 8,
                                 sample="temp", temperature=1.5, batch=2)
    # slot 0 of wave 0 vs slot 0 of wave 1 see identical logits; only the
    # wave-keyed PRNG separates their draws
    assert out["outputs"][0].tolist() != out["outputs"][2].tolist()


def test_serve_encoder_decoder_falls_back_to_lockstep():
    """Whisper (cross-attention caches are not paged) serves through the
    lockstep engine with the encoder/cross-KV prefill wired in."""
    out = serve_mod.main(["--arch", "whisper-small", "--smoke",
                          "--batch", "2", "--prompt-len", "8",
                          "--decode-tokens", "3"])
    assert all(v.shape == (3,) for v in out["outputs"].values())


@pytest.mark.slow
def test_quantized_admm_moves_fewer_bits():
    common = ["--arch", "tinyllama-1.1b", "--smoke", "--mode", "admm",
              "--workers", "2", "--steps", "4", "--batch", "4",
              "--seq", "32", "--local-steps", "2", "--log-every", "10"]
    q = train_mod.main(common)                       # quantized by default
    f = train_mod.main(common + ["--no-quantize"])
    assert q["total_bits"] < 0.5 * f["total_bits"]


@pytest.mark.parametrize("arch,layers", [("tinyllama-1.1b", 2),
                                         ("gemma3-4b", 6),
                                         ("zamba2-7b", None)])
def test_cut_depth_keeps_every_width(arch, layers):
    """``--layers`` replaces ``num_layers`` only, in whole periods of the
    block unit, and never beyond the published depth."""
    import dataclasses
    from repro.configs import base
    cfg = base.get_config(arch)
    period = len(cfg.block_unit)
    layers = layers or period
    cut = cfg.cut_depth(layers)
    assert cut.num_layers == layers
    assert dataclasses.replace(cut, num_layers=cfg.num_layers) == cfg
    with pytest.raises(ValueError):
        cfg.cut_depth(cfg.num_layers + period)
    with pytest.raises(ValueError):
        cfg.cut_depth(0)
    if period > 1:
        with pytest.raises(ValueError):
            cfg.cut_depth(period + 1)


def test_cli_config_reports_the_cut(capsys):
    from repro.configs import base
    cfg = base.cli_config("tinyllama-1.1b", smoke=False, layers=2,
                          tag="train")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (2, 2048, 32, 4, 5632, 32000)
    assert "[train] reduced: num_layers 22->2" in capsys.readouterr().out
    assert base.cli_config("tinyllama-1.1b", smoke=False, layers=None,
                           tag="train").num_layers == 22
    with pytest.raises(SystemExit, match="bad --layers"):
        base.cli_config("tinyllama-1.1b", smoke=False, layers=23,
                        tag="train")


def test_serve_layers_cut_end_to_end(capsys):
    out = serve_mod.main(["--arch", "tinyllama-1.1b", "--smoke",
                          "--layers", "1", "--batch", "2",
                          "--prompt-len", "8", "--decode-tokens", "3"])
    assert "[serve] reduced: num_layers 2->1" in capsys.readouterr().out
    assert all(v.shape == (3,) for v in out["outputs"].values())
    assert out["final_pages_in_use"] == 0


PLACEMENT_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.configs import base
from repro.launch import train

out = {}
for backend in ("sharded", "dense"):
    args = train.build_parser().parse_args([
        "--smoke", "--workers", "4", "--batch", "4", "--seq", "16",
        "--local-steps", "1", "--mix-backend", backend])
    setup = train.admm_setup(base.get_smoke_config(args.arch), args)
    state, step = setup.init_state(), setup.build_step()
    tx, loss = [], []
    for i in range(2):
        state, m = step(state, setup.batch(i), setup.key(i))
        tx.append(np.asarray(m["tx_mask"]).tolist())
        loss.append(float(m["loss"]))
    rows = sorted({tuple(sorted((s.device.id, s.data.shape[0])
                                for s in leaf.addressable_shards))
                   for leaf in jax.tree_util.tree_leaves(state)
                   if leaf.ndim})
    out[backend] = {"tx": tx, "loss": loss, "rows": rows,
                    "mesh": setup.mesh is not None}
print(json.dumps(out))
"""


def test_sharded_training_places_one_worker_per_device():
    """With several devices, ``run_admm``'s setup spreads the workers'
    rows over a ``workers`` mesh — every per-worker array of the state
    holds one row on each device — and the sharded mix follows the dense
    one: identical censor decisions, losses within
    ``CROSS_PROGRAM_RTOL``."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    from repro.core import engine as E
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for backend in ("sharded", "dense"):
        assert out[backend]["mesh"]
        assert out[backend]["rows"] == [[[d, 1] for d in range(4)]]
    assert out["sharded"]["tx"] == out["dense"]["tx"]
    for a, b in zip(out["sharded"]["loss"], out["dense"]["loss"]):
        assert abs(a - b) <= E.CROSS_PROGRAM_RTOL * abs(b)
