"""The main-path Pallas kernels compile for a TPU v5e at the shapes
``chip_smoke.py`` runs: TinyLlama-1.1B's published widths, cut in depth
only, two workers. Nothing runs — the v5e is described, not attached — so
these catch what interpret mode cannot: block shapes the TPU tiling
refuses and VMEM or HBM the chip does not have.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Kernels are called with ``interpret=False``: the platform rule
(``kernels.interpret_mode``) sees the host's CPU here.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import base
from repro.core import graph as G
from repro.kernels import ops
from repro.kernels import stoch_quant as SQ
from repro.kernels.bipartite_mix import bipartite_mix
from repro.kernels.edge_gather_mix import edge_gather_mix
from repro.kernels.paged_attention import (paged_attention_decode,
                                           paged_attention_decode_online)
from repro.kernels.slstm_cell import slstm_cell
from repro.models import registry
from repro.serving import paging

ROOT = Path(__file__).resolve().parent.parent
WORKERS = 2


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but not read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def smoke():
    return _chip_smoke()


@pytest.fixture(scope="module")
def packed_width(smoke):
    """D: the packed per-worker width of the model chip_smoke trains."""
    cfg = base.get_config(smoke.ARCH).cut_depth(smoke.LAYERS)
    params = jax.eval_shape(
        lambda: registry.init_params(cfg, jax.random.PRNGKey(0)))
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_fused_quantize_compiles_at_lm_width(shape, packed_width):
    d = packed_width
    tiled = ops.fused_slab_bytes(WORKERS, d) > ops.FUSED_SLAB_BYTES
    assert tiled, "an LM-width slab cannot fit VMEM"
    kw = dict(omega=0.999, b0=4, b_max=16, interpret=False)
    mat, side = shape((WORKERS, d)), shape((WORKERS, 1))
    compiled = _compile(
        lambda *a: SQ.stoch_quantize_grouped_fused_tiled(*a, **kw),
        mat, mat, mat, side, side, side, shape((d,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    # the worker axis is not padded: no (8, D) copy of any operand
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < WORKERS * d * 4


def test_fused_quantize_slab_compiles_at_its_budget(shape):
    """The largest slab the selection rule keeps on the single-slab
    kernel fits VMEM."""
    d = ops.FUSED_SLAB_BYTES // (WORKERS * 4)
    assert ops.fused_slab_bytes(WORKERS, d) <= ops.FUSED_SLAB_BYTES
    runs = (((0, d),),)
    mat, side = shape((WORKERS, d)), shape((WORKERS, 1))
    _compile(lambda *a: SQ.stoch_quantize_grouped_fused(
        *a, group_runs=runs, omega=0.999, b0=4, b_max=16, interpret=False),
        mat, mat, mat, side, side, side, shape((d,), jnp.int32))


def test_bipartite_mix_compiles_at_lm_width(shape, packed_width):
    compiled = _compile(lambda a, v: bipartite_mix(a, v, interpret=False),
                        shape((WORKERS, WORKERS)),
                        shape((WORKERS, packed_width)))
    assert compiled.memory_analysis().temp_size_in_bytes \
        < WORKERS * packed_width * 4


def test_edge_gather_mix_compiles_at_lm_width(shape, packed_width):
    table, _ = G.chain_graph(WORKERS).neighbor_table
    _compile(lambda v, t, m: edge_gather_mix(v, t, m, interpret=False),
             shape((WORKERS, packed_width)),
             shape(table.shape, jnp.int32), shape(table.shape))


@pytest.mark.parametrize("online", [False, True])
def test_paged_attention_compiles_at_tinyllama_width(shape, smoke, online):
    cfg = base.get_config(smoke.ARCH)
    bsz, page = 4, 16
    pages_per_seq = paging.pages_needed(128 + 32, page)
    num_pages = 2 * bsz * pages_per_seq
    hd = cfg.resolved_head_dim
    assert (cfg.num_heads, cfg.num_kv_heads, hd) == (32, 4, 64)
    fn = paged_attention_decode_online if online else paged_attention_decode
    pool = shape((num_pages, page, cfg.num_kv_heads, hd), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v, bt, ctx: fn(q, k, v, bt, ctx, interpret=False),
        shape((bsz, cfg.num_heads, hd)), pool, pool,
        shape((bsz, pages_per_seq), jnp.int32), shape((bsz,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_slstm_cell_compiles_at_xlstm_width(shape):
    cfg = base.get_config("xlstm-125m")
    h = cfg.lstm_heads
    dh = cfg.d_model // h
    bsz, seq = 8, 256
    state = shape((bsz, h, dh))
    _compile(lambda *a: slstm_cell(*a, interpret=False),
             shape((bsz, seq, h, 4 * dh)), shape((h, dh, 4 * dh)),
             shape((h, dh)), state, state, state, state)


def _one_chip_xlstm_round(topo):
    """xLSTM-125M at its published widths, cut to one unit (an mLSTM and
    an sLSTM block, the least cut the registry takes), its round
    compiled as the xLSTM cells' 2 CQ-GGADMM workers (one edge, dense mix,
    1 x 128 tokens each, 1 local step) on one chip of a described v5e."""
    from repro.core import engine as E
    from repro.launch import train
    args = train.build_parser().parse_args([
        "--arch", "xlstm-125m", "--workers", "2", "--batch", "2",
        "--seq", "128", "--local-steps", "1", "--lr", "1e-4",
        "--rho", "0.01", "--bits", "4", "--omega", "0.999"])
    setup = train.admm_setup(base.get_config("xlstm-125m").cut_depth(2),
                             args)
    assert setup.mesh is None
    one_chip = SingleDeviceSharding(topo.devices[0])
    abstract = jax.eval_shape(lambda: E.init_state(
        setup.stacked_params(), setup.ecfg, setup.solver))
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        abstract)
    tok = jax.ShapeDtypeStruct((2, 1, 128), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = setup.build_step().lower(
        state, {"tokens": tok, "labels": tok}, key).compile()
    return compiled, E.tree_dim(abstract.theta)


# Arguments plus temp of the round below, compiled here: 4,739,716,608
# bytes (2,619,616,256 + 2,120,100,352) when the exchange packed every
# leaf into one (N, D) row, whose compiled text then held 481 arrays of
# last axis D; 3,255,974,400 (2,619,616,256 + 636,358,144) leaf by leaf.
PACKED_XLSTM_ROUND_BYTES = 4_739_716_608


def test_one_chip_xlstm_round_keeps_each_leaf_in_its_shape(topo):
    """The xLSTM cells' round on one chip: no array of the compiled
    program has the tree's whole width D as its last axis (no packed
    ``(N, D)`` or ``(1, D)`` row in quantize, censor, mix or dual), and
    it needs no more memory than the packed round it replaces."""
    import re
    compiled, d = _one_chip_xlstm_round(topo)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= PACKED_XLSTM_ROUND_BYTES
    rows = re.findall(r"\[[\d,]*\b%d\]" % d, compiled.as_text())
    assert not rows, rows[:5]


def _replica_groups(line):
    """The device groups of one collective in compiled HLO text, by their
    logical ids: an explicit list, an iota form, or permute pairs."""
    import re

    import numpy as np
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", line)
    if m:
        ids = np.arange(int(np.prod([int(d) for d in m.group(3).split(",")])))
        ids = ids.reshape([int(d) for d in m.group(3).split(",")])
        if m.group(4):
            ids = ids.transpose([int(p) for p in m.group(4).split(",")])
        return {frozenset(g) for g in ids.reshape(int(m.group(1)),
                                                  int(m.group(2))).tolist()}
    m = re.search(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}", line)
    if m:
        return {frozenset(int(x) for x in g.split(",") if x)
                for g in re.findall(r"\{([\d,]*)\}", m.group(1))}
    m = re.search(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}", line)
    return {frozenset(int(x) for x in p.split(","))
            for p in re.findall(r"\{(\d+,\d+)\}", m.group(1))} if m else set()


def _round(arch, workers, mesh, seq):
    """``arch`` at its published widths, cut to 1 layer, its round
    compiled as ``workers`` CQ-GGADMM workers (one sequence of ``seq``
    each) placed on ``mesh``. Returns the compiled round and the abstract
    engine state."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import engine as E
    from repro.launch import train
    cfg = base.get_config(arch).cut_depth(1)
    args = train.build_parser().parse_args([
        "--arch", arch, "--workers", str(workers), "--batch", str(workers),
        "--seq", str(seq), "--local-steps", "4", "--lr", "1e-4",
        "--rho", "0.01", "--bits", "4", "--omega", "0.999",
        "--mix-backend", "sharded"])
    setup = train.admm_setup(cfg, args)
    setup = dataclasses.replace(setup, mesh=mesh)
    abstract = jax.eval_shape(lambda: E.init_state(
        setup.stacked_params(), setup.ecfg, setup.solver))
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        abstract, setup.shardings(abstract))
    tok = jax.ShapeDtypeStruct((workers, 1, seq), jnp.int32,
                               sharding=NamedSharding(
                                   mesh, PartitionSpec("workers")))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    compiled = setup.build_step().lower(
        state, {"tokens": tok, "labels": tok}, key).compile()
    return compiled, abstract


def _danube_round(topo, seq):
    """H2O-Danube-1.8B at its published widths, cut to 1 layer, its round
    compiled as 2 CQ-GGADMM workers of 2 chips each on a described
    v5e:2x2 (the ``danube-train-ici4`` deployment) at ``seq`` positions.
    Returns the compiled round, its mesh and the abstract engine state."""
    from repro.core import topology as T
    mesh = T.make_mesh((2, 2), ("workers", "model"), devices=topo.devices)
    compiled, abstract = _round("h2o-danube-1.8b", 2, mesh, seq)
    return compiled, mesh, abstract


def _solve_lines(compiled):
    """The compiled round's instructions inside ``admm.solve``: the flash
    kernel's custom calls (op names) and the collectives (kind, result
    type, replica groups)."""
    import re
    kernels_in, collectives = [], []
    for line in compiled.as_text().splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        op = op.group(1) if op else ""
        if "admm.solve" not in op:
            continue
        if "splash_mha" in op and "pallas_call" in op:
            kernels_in.append(op)
        m = re.search(_COLLECTIVE, line)
        if m:
            collectives.append((m.group(2), m.group(1),
                                _replica_groups(line)))
    return kernels_in, collectives


@pytest.fixture
def flash_on_chip(monkeypatch):
    """The platform taken for a TPU (the described chip is not the
    process's backend) and the kernels compiled, not interpreted."""
    from repro import kernels
    from repro.models import layers
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(kernels, "interpret_mode", lambda interpret=None:
                        False)


_COLLECTIVE = (r"=\s*(\S+)\s+(all-gather|all-reduce|reduce-scatter|"
               r"collective-permute|all-to-all)(?:-start)?\(")


def test_split_workers_round_compiles_at_danube_width(topo):
    """The ``danube-train-ici4`` round at 2048 positions. Outside the local
    solve and the loss's forward pass, whose tensor-parallel collectives
    are the model's, nothing crosses a worker's two chips but scalars:
    the quantizer's ranges and the censor's norms."""
    import re
    compiled, mesh, abstract = _danube_round(topo, 2048)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9

    ids = mesh.devices.shape
    model_groups = {frozenset(r) for r in
                    jnp.arange(ids[0] * ids[1]).reshape(ids).tolist()}
    n_leaves = len(jax.tree_util.tree_leaves(abstract.theta))
    scalars, found = [], []
    for line in compiled.as_text().splitlines():
        m = re.search(_COLLECTIVE, line)
        if not m or _replica_groups(line) != model_groups:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        op = op.group(1) if op else ""
        if "admm.solve" in op or "admm.metrics/loss" in op:
            continue
        dims = re.findall(r"\[([\d,]*)\]", m.group(1))
        elements = max(int(jnp.prod(jnp.asarray(
            [int(x) for x in d.split(",") if x] or [1]))) for d in dims)
        found.append((m.group(2), m.group(1), op))
        scalars.append(elements <= n_leaves)
    assert found, "no collective over the worker's chips outside the solve"
    assert all(scalars), [f for f, ok in zip(found, scalars) if not ok]


# Arguments plus temp of the round below, per chip, compiled here: 6,813,
# 066,240 bytes (2,799,950,848 + 4,013,115,392) before the flash kernel,
# when attention ran in 512-query blocks over all 8192 keys; 6,799,474,176
# (2,799,950,848 + 3,999,523,328) with it.
BLOCKWISE_ROUND_BYTES = 6_813_066_240


def test_split_workers_round_runs_flash_attention_at_danube_length(
        topo, flash_on_chip):
    """The ``danube-train-ici4`` round at its 8192 positions, cut to 1
    layer, on a TPU: self-attention runs as the flash kernel's custom
    calls inside ``admm.solve``, each chip on its own worker's heads. No
    collective inside the solve crosses the ``workers`` axis, none is an
    all-gather (of q, k or v heads or anything else: the solve's
    collectives are the tensor-parallel all-reduces), and the round needs
    no more memory than the blockwise attention it replaces."""
    compiled, mesh, _ = _danube_round(topo, 8192)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 15.75e9 and total <= BLOCKWISE_ROUND_BYTES

    ids = mesh.devices.shape
    workers = [frozenset(r) for r in
               jnp.arange(ids[0] * ids[1]).reshape(ids).tolist()]
    kernels_in, solve_collectives = _solve_lines(compiled)
    assert any("fwd" in k for k in kernels_in)
    assert any("dkv" in k for k in kernels_in)
    assert any("dq" in k for k in kernels_in)
    assert solve_collectives
    for kind, shape, groups in solve_collectives:
        assert all(any(g <= w for w in workers) for g in groups), \
            (kind, shape, groups)
        assert kind != "all-gather", (kind, shape)


def test_worker_per_chip_round_runs_flash_attention(topo, flash_on_chip):
    """TinyLlama-1.1B at its published widths, cut to 1 layer, as 4
    workers of one chip each on a described v5e:2x2 (``chip_smoke.py
    --chips 4``'s layout) at 4096 positions, on a TPU. The model sees the
    ``workers`` mesh, so the flash kernel runs inside ``shard_map``, each
    chip on its own worker, and compiles (GSPMD cannot partition its
    custom call); the solve moves nothing between chips."""
    from repro.core import topology as T
    compiled, _ = _round("tinyllama-1.1b", 4, T.worker_mesh(topo.devices),
                         4096)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    kernels_in, solve_collectives = _solve_lines(compiled)
    assert any("fwd" in k for k in kernels_in)
    assert any("dkv" in k for k in kernels_in)
    assert any("dq" in k for k in kernels_in)
    assert not solve_collectives, solve_collectives
