"""Workers that span several devices: with more devices than workers,
``launch/train.py`` lays a ``(workers, model)`` mesh, splits each worker's
parameters and engine state over its ``model`` devices by the
tensor-parallel rules, and the engine quantizes, censors, commits and
mixes on each device's own blocks. The round must follow the one that
keeps every worker on one device.

The multi-device runs happen in one subprocess with four virtual CPU
devices (``XLA_FLAGS`` must be set before JAX starts); its readings are
shared by the tests below."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as E

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import base
from repro.core import engine as E
from repro.core.quantization import QuantConfig
from repro.launch import train
from repro.obs import metrics as obs_metrics

out = {}
# placement by worker count on four devices
for n in (1, 2, 3, 4, 8):
    mesh = train.worker_placement(n)
    out[f"mesh{n}"] = None if mesh is None else dict(mesh.shape)

# the Danube smoke config (untied head, window 64) in float32, so that the
# two placements differ by the order of float32 sums alone
cfg = base.get_smoke_config("h2o-danube-1.8b").with_overrides(
    dtype="float32")
args = train.build_parser().parse_args([
    "--arch", "h2o-danube-1.8b", "--smoke", "--workers", "2",
    "--batch", "2", "--seq", "96", "--local-steps", "2",
    "--mix-backend", "sharded"])
split = train.admm_setup(cfg, args)
one = dataclasses.replace(split, mesh=None)
layout = obs_metrics.engine_worker_layout_counter()
runs = {}
for name, setup in (("split", split), ("one", one)):
    before = {k: layout.value(workers="2", chips_per_worker=k)
              for k in ("1", "2")}
    state, step = setup.init_state(), setup.build_step()
    tx, bits, loss = [], [], []
    for i in range(3):
        state, m = step(state, setup.batch(i), setup.key(i))
        tx.append(np.asarray(m["tx_mask"]).tolist())
        bits.append(np.asarray(m["bits_per_group"]).tolist())
        loss.append(float(m["loss"]))
    out[name] = {"tx": tx, "bits": bits, "loss": loss,
                 "layout": {k: layout.value(workers="2", chips_per_worker=k)
                            - before[k] for k in ("1", "2")}}
    runs[name] = jax.device_get(state)
    if name == "split":
        q = state.theta["stack"]["units"]["p0"]["attn"]["q"]["w"]
        o = state.opt_mu["stack"]["units"]["p0"]["attn"]["o"]["w"]
        head = state.quant.q_hat["lm_head"]["w"]
        out["specs"] = [str(x.sharding.spec) for x in (q, o, head)]
        out["shard_shapes"] = sorted({tuple(s.data.shape)
                                      for s in q.addressable_shards})
for part in ("theta", "theta_hat", "alpha"):
    out[f"rel_{part}"] = E.tree_rel_err(getattr(runs["split"], part),
                                        getattr(runs["one"], part))

# the quantize step alone, split over the devices and packed on one
theta = split.init_params()
state = E.init_state(theta, split.ecfg, split.solver)
moved = jax.tree_util.tree_map(
    lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(3), x.shape),
    theta)
qcfg = QuantConfig(b0=3, omega=0.99)
gids = E.resolve_groups(theta, "leaf")
quant = E.GroupQuantState.create(theta, max(gids) + 1, b0=3)
specs = jax.tree_util.tree_map(lambda s: s.spec,
                               split.shardings(jax.eval_shape(
                                   split.stacked_params)))
from repro.core import topology as topo_lib
topo = topo_lib.build(split.graph, "sharded", mesh=split.mesh,
                      worker_axis="workers", leaf_specs=specs)
key = jax.random.PRNGKey(11)
got = jax.jit(lambda q, t, k: E.grouped_quantize_step_split(
    q, t, k, qcfg, gids, topo))(quant, moved, key)
want = jax.jit(lambda q, t, k: E._grouped_quantize_step_packed(
    q, t, k, qcfg, gids))(*jax.device_get((quant, moved, key)))
out["quantize_equal"] = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_more_devices_than_workers_lay_a_model_axis(four_devices):
    """Two workers on four devices take two each; a worker count the
    devices do not divide, or that is not smaller, keeps the ``workers``
    mesh of ``topology.worker_devices`` (a device, or a row block a
    device)."""
    assert four_devices["mesh1"] == {"workers": 1, "model": 4}
    assert four_devices["mesh2"] == {"workers": 2, "model": 2}
    assert four_devices["mesh3"] is None
    assert four_devices["mesh4"] == {"workers": 4}
    assert four_devices["mesh8"] == {"workers": 4}


def test_split_state_follows_the_tensor_parallel_rules(four_devices):
    """q by heads (column), o by heads (row), the untied head by vocab;
    every device holds half of one worker's leaf."""
    assert four_devices["specs"] == [
        "PartitionSpec('workers', None, None, 'model')",
        "PartitionSpec('workers', None, 'model')",
        "PartitionSpec('workers', None, 'model')"]
    assert four_devices["shard_shapes"] == [[1, 2, 256, 128]]


def test_split_round_follows_one_device(four_devices):
    """Three rounds of 2 workers x 2 devices against the same rounds with
    both workers on one device: identical transmit decisions and bit
    widths; states within ``CROSS_PROGRAM_RTOL`` (the tensor-parallel
    sums are the one device's in another order)."""
    split, one = four_devices["split"], four_devices["one"]
    assert split["tx"] == one["tx"]
    assert split["bits"] == one["bits"]
    for a, b in zip(split["loss"], one["loss"]):
        assert abs(a - b) <= 1e-4 * abs(b)
    for part in ("theta", "theta_hat", "alpha"):
        assert four_devices[f"rel_{part}"] <= E.CROSS_PROGRAM_RTOL, part


def test_split_quantize_is_the_packed_round(four_devices):
    """The quantize step on each device's own blocks gives, bit for bit,
    what the packed round gives on one device: the same ranges, widths
    and payload, each parameter rounded with the same uniform."""
    assert four_devices["quantize_equal"] is True


def test_worker_layout_counter(four_devices):
    """``engine_worker_layout`` counts each trace of the round by workers
    and devices a worker spans: 2 x 2 split, 2 x 1 on one device."""
    split = four_devices["split"]["layout"]
    one = four_devices["one"]["layout"]
    assert split["1"] == 0 and split["2"] >= 1
    assert one["1"] >= 1 and one["2"] == 0


def test_one_device_placement_unchanged():
    """A process that sees one device keeps every worker on it: no mesh,
    no split, the state placed by the worker axis alone, and the round
    counted as one device a worker."""
    from repro.configs import base
    from repro.launch import train
    from repro.obs import metrics as obs_metrics
    assert len(jax.devices()) == 1
    assert train.worker_placement(2) is None
    args = train.build_parser().parse_args([
        "--smoke", "--workers", "2", "--batch", "2", "--seq", "16",
        "--local-steps", "1"])
    setup = train.admm_setup(base.get_smoke_config(args.arch), args)
    assert setup.mesh is None and not setup.split
    c = obs_metrics.engine_worker_layout_counter()
    before = c.value(workers="2", chips_per_worker="1")
    jax.eval_shape(setup.build_step(), setup.init_state(), setup.batch(0),
                   setup.key(0))
    assert c.value(workers="2", chips_per_worker="1") == before + 1


@pytest.mark.parametrize("shape,block,rows", [
    ((3, 10, 7), None, None),
    ((3, 4, 5, 6), None, None),
    ((3, 4, 5, 6), ((1, 2, 0, 3), (2, 2, 5, 3)), None),
    ((3, 810), ((2, 405), (1, 405)), None),
    ((3, 4, 5, 6), None, (1,)),
    ((3, 10, 7), None, (0, 2)),
], ids=["3d", "4d", "4d-block", "2d-block", "4d-row", "3d-rows"])
def test_uniform_columns_are_the_packed_draw(shape, block, rows):
    """A leaf's uniforms, a device's block of them, or the acting rows'
    uniforms, are bit for bit their columns of the draw over the packed
    ``(n, d)`` row."""
    from repro.core import quantization as Q
    n, d, offset = shape[0], 2000, 70
    key = jax.random.PRNGKey(7)
    full = np.asarray(jax.random.uniform(key, (n, d), jnp.float32))
    size = int(np.prod(shape[1:]))
    want = full[:, offset:offset + size].reshape(shape)
    got = np.asarray(jax.jit(lambda k: Q.uniform_columns(
        k, n, d, offset, shape, block=block,
        rows=None if rows is None else np.asarray(rows)))(key))
    if block is not None:
        want = want[tuple(slice(s, s + w) for s, w in zip(*block))]
    if rows is not None:
        want = want[list(rows)]
    np.testing.assert_array_equal(got, want)
