"""The four-chip cell ``danube-train-ici4`` at a size a CPU test can hold
(its depth, four virtual devices, smaller widths): the FLOP counter
against a count by hand, the reference placed over the chips against the
reference on one device, and the comparison that decides ``correct``
against the control and a planted fault. The four-device runs happen in
one subprocess (``XLA_FLAGS`` must be set before JAX starts)."""
import json
import os
import subprocess
import sys

import pytest

import harness

SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = sys.argv[1:3]
import jax, numpy as np
import checks, harness
from reference import dense as ref_dense

SEED = 2 ** 33 + 7
ref_dense.BLOCK_Q = 32                  # blocks cut the window of 64


def cell():
    c = harness.Bench().cell("danube-train-ici4")
    c["config"].update(hidden_size=256, head_dim=8, intermediate_size=512,
                       vocab_size=512, sliding_window=64)
    c["traffic"].update(seq=160)
    return c


out = {}
c = cell()
drv = harness.load_module(c["driver"], "drv")
run = drv.run(c, seed=SEED, seconds=1.0, trace=False, devices=jax.devices())
out["program"] = {"correct": run["correct"],
                  "checks": {k: v["value"] for k, v in run["checks"].items()}}

placed = drv.reference(c, SEED)
one = harness.load_module(harness.HERE / "drivers" / "train.py", "one")
unplaced = one.reference(c, SEED)
out["placed_vs_unplaced"] = {
    "loss": float(np.max(np.abs(np.subtract(placed["loss"],
                                            unplaced["loss"])))),
    "tx_equal": placed["tx"] == unplaced["tx"],
    "bits_equal": placed["payload_bits"] == unplaced["payload_bits"],
    **{k: float(np.max(checks.leaf_gaps(placed[k], unplaced[k])))
       for k in ("mu_norms", "update_norms", "dual_norms")}}

control = drv.reference(c, SEED, "fp8")
out["control"] = {k: v["value"] for k, v in checks.judge(
    checks.train_checks(control, placed), c["deploy"]["correct"]).items()}
out["control_correct"] = checks.all_within(checks.judge(
    checks.train_checks(control, placed), c["deploy"]["correct"]))

from repro.models import registry
lm_loss = registry.lm_loss


def half(params, cfg, batch, **kw):
    return lm_loss(params, cfg, {k: v[:, : v.shape[1] // 2]
                                 for k, v in batch.items()}, **kw)


registry.lm_loss = half
fault = drv.run(cell(), seed=SEED, seconds=1.0, trace=False,
                devices=jax.devices())
out["half_batch"] = {"correct": fault["correct"],
                     "checks": {k: v["value"]
                                for k, v in fault["checks"].items()}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(harness.HERE),
         str(harness.ROOT / "src")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_program_is_correct_on_four_devices(four_devices):
    assert four_devices["program"]["correct"], four_devices["program"]


def test_placed_reference_reads_as_the_unplaced_one(four_devices):
    """Laying the reference's weights, rows and dither out over the chips
    moves no reading: the same decisions and payloads, and losses and
    leaf norms that differ by the order of float32 sums alone."""
    got = four_devices["placed_vs_unplaced"]
    assert got["tx_equal"] and got["bits_equal"]
    assert got["loss"] <= 1e-5
    for k in ("mu_norms", "update_norms", "dual_norms"):
        assert got[k] <= 1e-4, (k, got[k])


def test_fp8_control_fails_the_cell(four_devices):
    assert four_devices["control_correct"] is False, four_devices["control"]


def test_half_batch_fault_fails_the_cell(four_devices):
    fault = four_devices["half_batch"]
    assert fault["correct"] is False, fault


def _hand_count(d, heads, kv, hd, ff, vocab, layers, seq, window):
    """Forward FLOPs of a sequence, counted key by key."""
    per_token = (2 * d * heads * hd + 2 * 2 * d * kv * hd
                 + 2 * heads * hd * d + 3 * 2 * d * ff)
    total = seq * (layers * per_token + 2 * d * vocab)
    for t in range(seq):
        seen = min(t + 1, window) if window else t + 1
        total += layers * 2 * 2 * heads * hd * seen
    return total


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window8"])
def test_dense_flops_against_a_hand_count(window):
    fl = harness.load_module(harness.HERE / "flops" / "dense.py", "fl")
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "intermediate_size": 96, "vocab_size": 100,
           "num_hidden_layers": 3, "sliding_window": window}
    seq = 24
    want = _hand_count(64, 4, 2, 16, 96, 100, 3, seq, window)
    assert fl.forward_flops_per_token(cfg, seq) * seq == pytest.approx(want)
    assert fl.train_flops_per_token(cfg, seq) == pytest.approx(
        3 * want / seq)
    if window:
        assert fl.forward_flops_per_token(cfg, seq) < \
            fl.forward_flops_per_token(dict(cfg, sliding_window=None), seq)
