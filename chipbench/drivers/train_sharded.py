"""Driver for consensus-training cells whose workers span several chips:
``drivers/train.py``'s run, its state and its reference placed over all
the cell's chips.

The program's state is made directly in the program's own placement
(``AdmmSetup.shardings``: each worker's rows over its chips, split by the
tensor-parallel rules), not by the worker axis alone as
``drivers/train.py``'s ``placed`` does: a whole worker does not fit one
chip here.

The plain reference (``reference/admm.py``) keeps every worker's float32
state and an ``(N, D)`` float32 dither; for two workers of 442M
parameters that is about 25 GB, more than one chip holds. Three
placements, and nothing of its arithmetic, make it fit:

* its initial weights are laid over the cell's chips, each leaf split
  along its first axis that the chip count divides (so that a leaf
  flattened into the packed row stays split), and the reference's arrays
  derive from them;
* a leaf cut back out of the packed row (``reference/admm.py``
  ``_unflat``) is laid out as the leaf it replaces: a slice of a split row
  would otherwise be copied whole onto every chip;
* the arrays made from the round's key alone, the dither among them, are
  made on the host (``jax.default_device``) and move to the chips with
  the row they round.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import harness  # noqa: E402
import program  # noqa: E402
import weights  # noqa: E402
from reference import admm as ref_admm  # noqa: E402
from reference import synthetic_lm  # noqa: E402

base = harness.load_module(harness.HERE / "drivers" / "train.py",
                           "chipbench_driver_train")
REF_ROUNDS = base.REF_ROUNDS
build = base.build
first_rounds = base.first_rounds
trainer_args = base.trainer_args


def placed(setup, fn, *args):
    """``fn(*args)`` under jit, its outputs made directly in the program's
    placement of each leaf."""
    if setup.mesh is None:
        return jax.jit(fn)(*args)
    out = setup.shardings(jax.eval_shape(fn, *args))
    return jax.jit(fn, out_shardings=out)(*args)


def spread(layout, devices):
    """Each leaf of ``layout`` split along its first axis that
    ``len(devices)`` divides (kept whole on every device if none does)."""
    mesh = Mesh(list(devices), ("chips",))

    def leaf(x):
        axes = [None] * len(x.shape)
        for i, size in enumerate(x.shape):
            if size % len(devices) == 0:
                axes[i] = "chips"
                break
        return NamedSharding(mesh, PartitionSpec(*axes))

    return jax.tree_util.tree_map(leaf, layout)


def reference(cell, seed: int, mode: str = "f32", half_batch: bool = False):
    """``drivers/train.py``'s reference, from initial weights spread over
    the cell's chips."""
    cfg, t, d = cell["config"], cell["traffic"], cell["deploy"]
    model = harness.load_module(
        harness.HERE / "reference" / f"{cfg['family']}.py",
        "chipbench_reference_" + cfg["family"])
    mm = model.Matmul(mode)
    layout = weights.program_layout(program.model_config(cfg))
    params0 = jax.jit(weights.maker(layout), out_shardings=spread(
        layout, jax.devices()[:cell["chips"]]))(harness.seed_key(seed))

    def loss_fn(params, tokens, labels):
        if half_batch:
            half = tokens.shape[1] // 2
            tokens, labels = tokens[:, :half], labels[:, :half]
        return model.lm_loss(params, tokens, labels, cfg, mm)

    batches = [[synthetic_lm.rows(seed, w, r, t["batch_per_worker"],
                                  t["seq"], cfg["vocab_size"])
                for w in range(d["workers"])] for r in range(REF_ROUNDS)]
    keys = [jax.random.PRNGKey(1000 + r) for r in range(REF_ROUNDS)]
    hp = dict(t, b_max=16, b_overhead=64)
    unflat = ref_admm._unflat

    def unflat_in_place(flat, like):
        return jax.tree_util.tree_map(
            lambda x, y: jax.device_put(x, y.sharding), unflat(flat, like),
            like)

    ref_admm._unflat = unflat_in_place       # reference/admm.py's run calls
    try:                                     # it by name
        with jax.default_matmul_precision("highest"), \
                jax.default_device(jax.devices("cpu")[0]):
            return ref_admm.run(params0, loss_fn, batches, keys, hp,
                                d["graph"])
    finally:
        ref_admm._unflat = unflat


# drivers/train.py's run, build and first rounds call these by name
base.placed = placed
base.reference = reference


def run(cell, *, seed: int, seconds: float, trace: bool, devices):
    return base.run(cell, seed=seed, seconds=seconds, trace=trace,
                    devices=devices)
