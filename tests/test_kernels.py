"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exactness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # offline: property tests skip, rest runs
    from _hypothesis_stub import given, settings, st

from repro.kernels import ref
from repro.kernels.bipartite_mix import bipartite_mix
from repro.kernels.stoch_quant import stoch_quantize, stoch_quantize_grouped

SHAPES = [(1, 1), (3, 7), (8, 512), (5, 513), (24, 50), (16, 2048),
          (9, 1023)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stoch_quant_matches_ref(shape, dtype):
    n, d = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    theta = (10 * jax.random.normal(key, (n, d))).astype(dtype)
    qprev = (10 * jax.random.normal(jax.random.fold_in(key, 1),
                                    (n, d))).astype(dtype)
    unif = jax.random.uniform(jax.random.fold_in(key, 2), (n, d),
                              jnp.float32).astype(dtype)
    qrange = jnp.max(jnp.abs((theta - qprev).astype(jnp.float32)), axis=-1)
    bits = 3.0
    delta = (2.0 * qrange / (2 ** bits - 1)).astype(jnp.float32)
    got = stoch_quantize(theta, qprev, unif, delta, qrange, interpret=True)
    want = ref.stoch_quantize_ref(theta, qprev, unif, delta, qrange)
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    tol = 1e-5 + 1e-5 * np.abs(np.asarray(want, np.float32))
    if dtype == jnp.bfloat16:
        # XLA may contract the oracle's c-coordinate chain into FMAs, so a
        # coordinate landing exactly on a rounding boundary can flip by ONE
        # quantization level when inputs are stored sub-f32; allow a rare
        # single-step disagreement, never more.
        step = np.asarray(delta, np.float32)[:, None]
        flips = diff > tol
        assert (diff[flips] <= step.repeat(d, 1)[flips] * 1.001).all()
        assert flips.mean() < 5e-3, f"{flips.sum()} boundary flips"
    else:
        assert (diff <= tol).all()


def test_stoch_quant_bit_exact_f32():
    """identical uniforms => bit-identical to the oracle in f32."""
    n, d = 8, 640
    key = jax.random.PRNGKey(0)
    theta = 5 * jax.random.normal(key, (n, d))
    qprev = jnp.zeros((n, d))
    unif = jax.random.uniform(jax.random.fold_in(key, 1), (n, d))
    qrange = jnp.max(jnp.abs(theta), axis=-1)
    delta = 2.0 * qrange / 15.0
    got = np.asarray(stoch_quantize(theta, qprev, unif, delta, qrange,
                                    interpret=True))
    want = np.asarray(ref.stoch_quantize_ref(theta, qprev, unif, delta,
                                             qrange))
    np.testing.assert_array_equal(got, want)


def _grouped_inputs(n, d, g, seed):
    key = jax.random.PRNGKey(seed)
    theta = 5 * jax.random.normal(key, (n, d))
    qprev = 2 * jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    unif = jax.random.uniform(jax.random.fold_in(key, 2), (n, d))
    # contiguous group blocks of uneven width (the packed-leaf layout)
    edges = np.linspace(0, d, g + 1).astype(int)
    gids = np.zeros((d,), np.int32)
    for i in range(g):
        gids[edges[i]:edges[i + 1]] = i
    gids = jnp.asarray(gids)
    diff = jnp.abs(theta - qprev)
    qrange = jnp.stack(
        [jnp.max(jnp.where(gids[None, :] == i, diff, 0.0), axis=1)
         for i in range(g)], axis=1)                       # (N, G)
    bits = jnp.asarray(np.random.RandomState(seed).randint(2, 8, (n, g)),
                       jnp.float32)
    delta = 2.0 * qrange / (jnp.exp2(bits) - 1.0)
    return theta, qprev, unif, delta, qrange, gids


@pytest.mark.parametrize("shape_g", [(1, 1, 1), (3, 7, 2), (8, 512, 1),
                                     (5, 513, 4), (16, 2048, 8),
                                     (9, 1023, 3)])
def test_grouped_stoch_quant_bit_exact_vs_ref(shape_g):
    """The fused grouped kernel (ONE pallas_call over the packed buffer)
    equals the unfused jnp oracle bit-for-bit in interpret mode. The oracle
    runs under jit — as the engine always runs it — so both sides see the
    same XLA FMA contraction (op-by-op eager dispatch contracts the
    c-coordinate chain differently at a few ULP)."""
    n, d, g = shape_g
    theta, qprev, unif, delta, qrange, gids = _grouped_inputs(n, d, g,
                                                             seed=n * d + g)
    got = stoch_quantize_grouped(theta, qprev, unif, delta, qrange, gids,
                                 interpret=True)
    want = jax.jit(ref.stoch_quantize_grouped_ref)(theta, qprev, unif, delta,
                                                   qrange, gids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_g1_matches_ungrouped_bitwise():
    """G=1 grouped == the seed scalar-side-info kernel, bit-for-bit (the
    packed path's golden-compatibility guarantee)."""
    n, d = 8, 640
    theta, qprev, unif, delta, qrange, gids = _grouped_inputs(n, d, 1, seed=0)
    grouped = stoch_quantize_grouped(theta, qprev, unif, delta, qrange, gids,
                                     interpret=True)
    flat = stoch_quantize(theta, qprev, unif, delta[:, 0], qrange[:, 0],
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(flat))
    np.testing.assert_array_equal(
        np.asarray(ref.stoch_quantize_grouped_ref(theta, qprev, unif, delta,
                                                  qrange, gids)),
        np.asarray(ref.stoch_quantize_ref(theta, qprev, unif, delta[:, 0],
                                          qrange[:, 0])))


def test_grouped_respects_group_boundaries():
    """Columns of a degenerate-range group pass through q_prev exactly while
    other groups still quantize (no cross-group bleed in the select)."""
    n, d, g = 4, 64, 2
    key = jax.random.PRNGKey(5)
    theta = jnp.concatenate(
        [jnp.zeros((n, 32)),                   # group 0: diff == 0
         5 * jax.random.normal(key, (n, 32))], axis=1)
    qprev = jnp.zeros((n, d))
    unif = jax.random.uniform(jax.random.fold_in(key, 1), (n, d))
    gids = jnp.asarray([0] * 32 + [1] * 32, jnp.int32)
    qrange = jnp.stack([jnp.zeros((n,)),
                        jnp.max(jnp.abs(theta[:, 32:]), axis=1)], axis=1)
    delta = jnp.stack([jnp.zeros((n,)), 2.0 * qrange[:, 1] / 15.0], axis=1)
    out = np.asarray(stoch_quantize_grouped(theta, qprev, unif, delta,
                                            qrange, gids, interpret=True))
    want = np.asarray(ref.stoch_quantize_grouped_ref(theta, qprev, unif,
                                                     delta, qrange, gids))
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(out[:, :32], np.zeros((n, 32)))
    # quantized group reconstructs within one step of theta
    assert (np.abs(out[:, 32:] - np.asarray(theta[:, 32:]))
            <= np.asarray(delta[:, 1])[:, None] + 1e-6).all()


# ---------------------------------------------- fused range reduction ----
def _fused_tree_case(kind, n=6, seed=11):
    """(tree, group_ids) fixtures spanning the spec space: G=1, per-leaf,
    and a ragged block spec whose groups own non-adjacent leaf runs."""
    key = jax.random.PRNGKey(seed)
    dims = [37, 128, 13, 257, 64]
    tree = {f"l{i}": (0.5 + i) * jax.random.normal(
        jax.random.fold_in(key, i), (n, d)) for i, d in enumerate(dims)}
    gids = {"model": (0,) * 5, "leaf": tuple(range(5)),
            "ragged": (2, 0, 1, 0, 2)}[kind]
    return tree, gids


def _fused_inputs(tree, gids, dtype, seed=21):
    from repro.core import packing as P
    pk = P.make_packing(tree, gids)
    key = jax.random.PRNGKey(seed)
    n = jax.tree_util.tree_leaves(tree)[0].shape[0]
    g = pk.n_groups
    theta = P.pack(pk, tree).astype(dtype)
    qprev = (0.3 * jax.random.normal(key, theta.shape)).astype(dtype)
    unif = jax.random.uniform(jax.random.fold_in(key, 1), theta.shape)
    bits_prev = jnp.asarray(
        np.random.RandomState(seed).randint(2, 8, (n, g)), jnp.float32)
    range_prev = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2),
                                           (n, g)))
    init = (jax.random.uniform(jax.random.fold_in(key, 3), (n, g))
            > 0.3).astype(jnp.float32)
    return pk, theta, qprev, unif, bits_prev, range_prev, init


@pytest.mark.parametrize("kind", ["model", "leaf", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_range_kernel_bit_exact_vs_oracle(kind, dtype):
    """The in-kernel range reduction + bit schedule + quantize equals the
    jnp oracle bit-for-bit (all four outputs) across G in {1, leaf-count,
    ragged block} and f32/bf16 storage."""
    from repro.kernels.stoch_quant import stoch_quantize_grouped_fused
    tree, gids = _fused_tree_case(kind)
    pk, theta, qprev, unif, bprev, rprev, init = _fused_inputs(tree, gids,
                                                              dtype)
    sched = dict(group_runs=pk.group_runs, omega=0.97, b0=3, b_max=16)
    gid_cols = jnp.asarray(pk.col_group_ids)
    got = stoch_quantize_grouped_fused(theta, qprev, unif, bprev, rprev,
                                       init, gid_cols, interpret=True,
                                       **sched)
    want = jax.jit(lambda *a: ref.stoch_quantize_grouped_fused_ref(
        *a, **sched))(theta, qprev, unif, bprev, rprev, init, gid_cols)
    for g, w, name in zip(got, want, ("out", "range", "bits", "delta")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert got[0].dtype == dtype


@pytest.mark.parametrize("kind", ["model", "leaf", "ragged"])
def test_fused_range_matches_two_pass_path(kind):
    """Folding the reduction into the kernel changes the schedule of the
    program, not its values: the fused engine path equals the old
    side-info-pass path bit-for-bit, kernel and oracle alike."""
    from repro.core import engine as E
    from repro.core.quantization import QuantConfig
    tree, gids = _fused_tree_case(kind)
    cfg = QuantConfig(b0=3, omega=0.97)
    state = E.GroupQuantState.create(tree, max(gids) + 1, b0=cfg.b0)
    key = jax.random.PRNGKey(5)
    results = []
    for fn, kernel in [(E.grouped_quantize_step, False),
                       (E.grouped_quantize_step, True),
                       (E.grouped_quantize_step_twopass, False),
                       (E.grouped_quantize_step_twopass, True)]:
        results.append(fn(state, tree, key, cfg, gids, use_kernel=kernel))
    base = results[0]
    for other in results[1:]:
        for la, lb in zip(jax.tree_util.tree_leaves(base[1]),
                          jax.tree_util.tree_leaves(other[1])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        np.testing.assert_array_equal(np.asarray(base[2]),
                                      np.asarray(other[2]))
        np.testing.assert_array_equal(np.asarray(base[3]),
                                      np.asarray(other[3]))
        for fa, fb in zip(jax.tree_util.tree_leaves(base[0]),
                          jax.tree_util.tree_leaves(other[0])):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


@pytest.mark.parametrize("kind", ["model", "leaf", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_tiled_bit_parity_with_single_slab(kind, dtype):
    """The D-tiled two-phase grid variant (bounded VMEM for LM-scale
    widths) equals the single-slab fused kernel bit-for-bit on all four
    outputs — the max reduction is order-insensitive, the schedule runs on
    an equal panel, and the quantize chain applies identical scalars."""
    from repro.kernels.stoch_quant import (
        stoch_quantize_grouped_fused, stoch_quantize_grouped_fused_tiled)
    tree, gids = _fused_tree_case(kind)
    pk, theta, qprev, unif, bprev, rprev, init = _fused_inputs(tree, gids,
                                                              dtype)
    sched = dict(omega=0.97, b0=3, b_max=16)
    gid_cols = jnp.asarray(pk.col_group_ids)
    slab = stoch_quantize_grouped_fused(
        theta, qprev, unif, bprev, rprev, init, gid_cols,
        group_runs=pk.group_runs, interpret=True, **sched)
    for block_d in (128, 256):
        tiled = stoch_quantize_grouped_fused_tiled(
            theta, qprev, unif, bprev, rprev, init, gid_cols,
            block_d=block_d, interpret=True, **sched)
        for g, w, name in zip(tiled, slab, ("out", "range", "bits",
                                            "delta")):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"{name}@{block_d}")


def _fused_ops_args():
    tree, gids = _fused_tree_case("ragged")
    pk, theta, qprev, unif, bprev, rprev, init = _fused_inputs(
        tree, gids, jnp.float32)
    args = (theta, qprev, unif, bprev, rprev, init,
            jnp.asarray(pk.col_group_ids))
    return args, pk


def test_fused_tiled_env_dispatch():
    """The ops layer's explicit D-tiled entry equals its shape-selected
    single-slab dispatch bit for bit."""
    from repro.kernels import ops
    args, pk = _fused_ops_args()
    sched = dict(omega=0.97, b0=3, b_max=16)
    assert ops.fused_slab_bytes(*args[0].shape) <= ops.FUSED_SLAB_BYTES
    slab = ops.stoch_quantize_grouped_fused(*args, group_runs=pk.group_runs,
                                            **sched)
    tiled = ops.stoch_quantize_grouped_fused_tiled(*args, block_d=256,
                                                   **sched)
    for g, w in zip(tiled, slab):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fused_selection_follows_slab_bytes(monkeypatch):
    """The fused quantize takes the tiled kernel exactly when its slab
    exceeds ``FUSED_SLAB_BYTES``: small packed buffers stay on the slab,
    an LM-width buffer (TinyLlama at one layer, two workers) tiles, and
    lowering the budget under a small buffer tips it over."""
    from repro.kernels import ops
    from repro.obs.metrics import kernel_dispatch_counter
    args, pk = _fused_ops_args()
    n, d = args[0].shape
    assert ops.fused_slab_bytes(n, d) == n * (d + (-d) % 128) * 4
    assert ops.fused_slab_bytes(2, 109_582_336) > ops.FUSED_SLAB_BYTES
    assert ops.fused_slab_bytes(16, 128) == 8 * 128 * 4     # one row block
    c = kernel_dispatch_counter()
    kw = dict(group_runs=pk.group_runs, omega=0.97, b0=3, b_max=16)
    for budget, variant in ((ops.FUSED_SLAB_BYTES, "slab"),
                            (ops.fused_slab_bytes(n, d) - 1, "tiled")):
        monkeypatch.setattr(ops, "FUSED_SLAB_BYTES", budget)
        before = c.value(kernel="stoch_quantize_fused", variant=variant)
        ops.stoch_quantize_grouped_fused(*args, **kw)
        assert c.value(kernel="stoch_quantize_fused",
                       variant=variant) == before + 1


# ------------------------------------------------------ paged attention --
def _paged_attn_inputs(bsz, h, kv, hd, ps, pps, num_pages, seed=3,
                       kv_dtype=jnp.bfloat16):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (bsz, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (num_pages, ps, kv, hd)).astype(kv_dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (num_pages, ps, kv, hd)).astype(kv_dtype)
    # scattered, non-contiguous page placement + an unmapped tail
    perm = np.random.RandomState(seed).permutation(num_pages)
    bt = jnp.asarray(perm[:bsz * pps].reshape(bsz, pps), jnp.int32)
    bt = bt.at[0, pps - 1:].set(-1)
    ctx = jnp.asarray(
        np.random.RandomState(seed + 1).randint(1, (pps - 1) * ps,
                                                (bsz,)), jnp.int32)
    return q, k, v, bt, ctx


@pytest.mark.parametrize("shape", [(2, 4, 4, 16, 4, 3, 16),   # MHA
                                   (3, 8, 2, 16, 8, 4, 32),   # GQA
                                   (1, 4, 1, 32, 4, 5, 8)])   # MQA
@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_bit_exact_vs_ref(shape, kv_dtype):
    """Block-table gather kernel vs jnp oracle: identical inputs produce
    bit-identical outputs (same per-page dots, one-shot softmax, page-order
    accumulation) across MHA/GQA/MQA and pool dtypes."""
    from repro.kernels.paged_attention import paged_attention_decode
    bsz, h, kv, hd, ps, pps, num_pages = shape
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                          num_pages, kv_dtype=kv_dtype)
    got = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
    # jit the oracle so XLA applies the same FMA contractions to both
    # programs (the fused-range test's convention)
    want = jax.jit(ref.paged_attention_ref)(q, k, v, bt, ctx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_attention_matches_dense_gather():
    """The kernel agrees with gather-then-dense mha to float tolerance
    (different contraction order over the kv axis, same math)."""
    from repro.kernels.paged_attention import paged_attention_decode
    from repro.models import layers
    bsz, h, kv, hd, ps, pps, num_pages = 3, 8, 2, 16, 4, 5, 32
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                          num_pages)
    got = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
    safe = jnp.maximum(bt, 0)
    kg = jnp.take(k, safe, axis=0).reshape(bsz, pps * ps, kv, hd)
    vg = jnp.take(v, safe, axis=0).reshape(bsz, pps * ps, kv, hd)
    idx = jnp.arange(pps * ps)[None]
    kv_pos = jnp.where((idx < ctx[:, None])
                       & jnp.repeat(bt >= 0, ps, axis=1), idx, -1)
    mask = layers._attn_mask((ctx - 1)[:, None], kv_pos, True, None)
    want = layers.mha(q[:, None].astype(jnp.float32),
                      kg.astype(jnp.float32), vg.astype(jnp.float32),
                      mask)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_ignores_unmapped_and_stale_pages():
    """Entries beyond ctx_len — stale tokens in a recycled page, unmapped
    block-table slots — contribute exactly nothing: poisoning them with
    huge values does not change the output."""
    from repro.kernels.paged_attention import paged_attention_decode
    bsz, h, kv, hd, ps, pps, num_pages = 2, 4, 4, 16, 4, 3, 16
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                          num_pages)
    clean = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
    # poison every slot at-or-beyond each sequence's context length
    k2, v2 = np.asarray(k, np.float32), np.asarray(v, np.float32)
    bt_np, ctx_np = np.asarray(bt), np.asarray(ctx)
    for b in range(bsz):
        for p in range(pps):
            if bt_np[b, p] < 0:
                continue
            for s in range(ps):
                if p * ps + s >= ctx_np[b]:
                    k2[bt_np[b, p], s] = 1e4
                    v2[bt_np[b, p], s] = -1e4
    poisoned = paged_attention_decode(
        q, jnp.asarray(k2).astype(k.dtype), jnp.asarray(v2).astype(v.dtype),
        bt, ctx, interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


_PAGED_LAYOUTS = [(2, 4, 4, 16, 4, 3, 16),   # MHA
                  (3, 8, 2, 16, 8, 4, 32),   # GQA
                  (1, 4, 1, 32, 4, 5, 8)]    # MQA


@pytest.mark.parametrize("shape", _PAGED_LAYOUTS)
@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_online_matches_oneshot_ctx_matrix(shape, kv_dtype):
    """The flash-style online-softmax variant equals the one-shot kernel to
    float tolerance at every context-length edge: 1 token, one-under/exact/
    one-over a page boundary, and the full multi-page extent."""
    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_online)
    bsz, h, kv, hd, ps, pps, num_pages = shape
    q, k, v, bt, _ = _paged_attn_inputs(bsz, h, kv, hd, ps, pps, num_pages,
                                        kv_dtype=kv_dtype)
    bt = jnp.asarray(np.random.RandomState(0).permutation(num_pages)
                     [:bsz * pps].reshape(bsz, pps), jnp.int32)  # all mapped
    for c in (1, ps - 1, ps, ps + 1, pps * ps):
        ctx = jnp.full((bsz,), c, jnp.int32)
        one = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
        onl = paged_attention_decode_online(q, k, v, bt, ctx,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(onl), np.asarray(one),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"ctx={c}")


def test_paged_attention_online_matches_ref_to_1e5():
    """Direct pin against the jnp oracle (not just the one-shot kernel):
    running-max rescaling reorders the float ops, so parity is 1e-5, and
    ctx == 0 rows come back as exact zeros (l == 0 guard)."""
    from repro.kernels.paged_attention import paged_attention_decode_online
    bsz, h, kv, hd, ps, pps, num_pages = 3, 8, 2, 16, 8, 4, 32
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps, num_pages)
    got = paged_attention_decode_online(q, k, v, bt, ctx, interpret=True)
    want = jax.jit(ref.paged_attention_ref)(q, k, v, bt, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    zero = paged_attention_decode_online(q, k, v, bt,
                                         jnp.zeros((bsz,), jnp.int32),
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(zero),
                                  np.zeros((bsz, h, hd), np.float32))


def test_paged_attention_online_adversarial_max_shift():
    """Per-page K magnitudes growing 4x page over page force the running
    max to move at EVERY page (worst case for the rescale chain); the
    accumulator still lands within 1e-5 of the one-shot softmax."""
    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_online)
    bsz, h, kv, hd, ps, pps, num_pages = 2, 4, 2, 16, 4, 5, 16
    q, k, v, bt, _ = _paged_attn_inputs(bsz, h, kv, hd, ps, pps, num_pages,
                                        kv_dtype=jnp.float32)
    bt = jnp.asarray(np.arange(bsz * pps).reshape(bsz, pps), jnp.int32)
    scale = jnp.asarray(4.0) ** jnp.arange(num_pages, dtype=jnp.float32)
    k = k * scale[:, None, None, None] * 0.25
    ctx = jnp.full((bsz,), pps * ps, jnp.int32)
    one = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
    onl = paged_attention_decode_online(q, k, v, bt, ctx, interpret=True)
    np.testing.assert_allclose(np.asarray(onl), np.asarray(one),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(ctx0=st.integers(1, 20), ctx1=st.integers(1, 20),
       kv=st.sampled_from([1, 2, 4]), seed=st.integers(0, 99))
def test_paged_attention_online_oneshot_parity_property(ctx0, ctx1, kv,
                                                        seed):
    """Property sweep: arbitrary per-sequence context lengths (including
    page-boundary stragglers) keep the two kernels within 1e-5."""
    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_online)
    bsz, h, hd, ps, pps, num_pages = 2, 4, 16, 4, 5, 16
    q, k, v, bt, _ = _paged_attn_inputs(bsz, h, kv, hd, ps, pps, num_pages,
                                        seed=seed)
    bt = jnp.asarray(np.random.RandomState(seed).permutation(num_pages)
                     [:bsz * pps].reshape(bsz, pps), jnp.int32)
    ctx = jnp.asarray([ctx0, ctx1], jnp.int32)
    one = paged_attention_decode(q, k, v, bt, ctx, interpret=True)
    onl = paged_attention_decode_online(q, k, v, bt, ctx, interpret=True)
    np.testing.assert_allclose(np.asarray(onl), np.asarray(one),
                               rtol=1e-5, atol=1e-5)


def _kernel_invar_shapes(fn, *args):
    """Shapes of the pallas_call kernel-body invars inside fn's jaxpr."""
    jx = jax.make_jaxpr(fn)(*args)
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
                continue
            for v in eqn.params.values():
                for j in jax.tree_util.tree_leaves(
                        v, is_leaf=lambda x: hasattr(x, "eqns")
                        or hasattr(x, "jaxpr")):
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jx.jaxpr)
    assert found, "no pallas_call traced"
    kj = found[0].params["jaxpr"]
    return [tuple(var.aval.shape) for var in kj.invars]


def test_paged_attention_online_vmem_independent_of_context():
    """The acceptance pin for 'one page slab in VMEM': the online kernel's
    in-VMEM block + scratch shapes are IDENTICAL for pages_per_seq 2 and 8
    (only the grid grows), while the one-shot kernel's logits scratch
    visibly scales with pages_per_seq."""
    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_online)

    def shapes(entry, pps):
        bsz, h, kv, hd, ps = 2, 4, 2, 16, 4
        q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                              4 * pps)
        # drop the two scalar-prefetch operands (block table, ctx_lens):
        # those live in SMEM and legitimately scale with pages_per_seq
        return _kernel_invar_shapes(
            lambda *a: entry(*a, interpret=True), q, k, v,
            jnp.maximum(bt, 0), ctx)[2:]

    assert (shapes(paged_attention_decode_online, 2)
            == shapes(paged_attention_decode_online, 8))
    one2 = shapes(paged_attention_decode, 2)
    one8 = shapes(paged_attention_decode, 8)
    assert one2 != one8
    assert (8, 4, 4) in one8           # (pps, h, ps) logits slab grows
    # online scratch: (h, hd) accumulator + (h, 1) running max and sum
    on = shapes(paged_attention_decode_online, 8)
    assert (4, 16) in on and on.count((4, 1)) == 2


def test_ops_paged_attention_clamps_poisoned_tables(monkeypatch):
    """Satellite regression: unmapped (negative) and out-of-range slot ids
    reaching the PUBLIC ops entry are clamped into the pool before the
    kernel gather — same output as the clean table, no OOB read — under
    both the one-shot and the online kernel."""
    from repro.kernels import ops
    bsz, h, kv, hd, ps, pps, num_pages = 2, 4, 2, 16, 4, 3, 16
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                          num_pages)
    poisoned = np.asarray(bt).copy()
    poisoned[0, -1] = num_pages + 7      # out-of-range high
    poisoned[1, -1] = -9                  # unmapped / corrupt low
    for force in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN_ONLINE", force)
        clean = ops.paged_attention_decode(q, k, v, bt, ctx)
        hit = ops.paged_attention_decode(q, k, v, jnp.asarray(poisoned),
                                         ctx)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(hit),
                                      err_msg=f"online={force}")


def test_ops_paged_attention_selects_kernel_by_slab_bytes(monkeypatch):
    """ops.paged_attention_decode picks one-shot while the full logits slab
    fits the VMEM budget and switches to online-softmax beyond it;
    REPRO_PAGED_ATTN_ONLINE forces either way."""
    from repro.kernels import ops, paged_attention
    calls = []
    real_one = paged_attention.paged_attention_decode
    real_onl = paged_attention.paged_attention_decode_online
    monkeypatch.setattr(paged_attention, "paged_attention_decode",
                        lambda *a, **k: calls.append("oneshot")
                        or real_one(*a, **k))
    monkeypatch.setattr(paged_attention, "paged_attention_decode_online",
                        lambda *a, **k: calls.append("online")
                        or real_onl(*a, **k))
    monkeypatch.delenv("REPRO_PAGED_ATTN_ONLINE", raising=False)
    q, k, v, bt, ctx = _paged_attn_inputs(2, 4, 2, 16, 4, 3, 16)
    ops.paged_attention_decode(q, k, v, bt, ctx)      # tiny slab: one-shot
    monkeypatch.setattr(ops, "ONESHOT_SLAB_BYTES", 0)
    ops.paged_attention_decode(q, k, v, bt, ctx)      # over budget: online
    monkeypatch.setenv("REPRO_PAGED_ATTN_ONLINE", "0")
    ops.paged_attention_decode(q, k, v, bt, ctx)      # forced one-shot
    assert calls == ["oneshot", "online", "oneshot"]


# ------------------------------------------------- quantized KV pages ----
@pytest.mark.parametrize("kv_bits", [8, 4])
def test_kv_page_codec_roundtrip_and_paper_parity(kv_bits):
    """The page codec IS the paper quantizer (Eqs. 14/15/20) specialized to
    q_prev = 0 and the deterministic u = 0.5 draw: bit-identical to the
    stochastic_round + bit_schedule composition, within one float ulp of
    stoch_quantize_ref (whose clip ceiling 2R/delta is computed in f32
    rather than as the exact integer 2^b - 1), and reconstruction error is
    bounded by delta/2 everywhere."""
    from repro.core import quantization as Q
    x = jax.random.normal(jax.random.PRNGKey(7), (6, 5, 2, 16), jnp.float32)
    codes, rng = ref.kv_page_quantize(x, kv_bits=kv_bits)
    assert codes.dtype == jnp.uint8
    xhat = np.asarray(ref.kv_page_dequantize(codes, rng, kv_bits=kv_bits,
                                             head_dim=16))
    delta = ref._kv_page_delta(rng, kv_bits)
    err = np.abs(xhat - np.asarray(x))
    assert (err <= np.asarray(delta)[..., None] / 2 + 1e-6).all()
    c = (x + rng[..., None]) / delta[..., None]
    qq = jnp.clip(Q.stochastic_round(c, jnp.full_like(c, 0.5)), 0.0,
                  float(2 ** kv_bits - 1))
    np.testing.assert_array_equal(
        xhat, np.asarray(delta[..., None] * qq - rng[..., None]))
    flat = x.reshape(-1, 16)
    sq = ref.stoch_quantize_ref(flat, jnp.zeros_like(flat),
                                jnp.full_like(flat, 0.5),
                                delta.reshape(-1), rng.reshape(-1))
    np.testing.assert_allclose(xhat.reshape(-1, 16), np.asarray(sq),
                               rtol=0, atol=1e-6)


def test_kv_page_codec_int4_packing():
    """int4 packs two codes per byte along head_dim; unpack restores the
    exact code sequence (spot-checked against an unpacked int8-style
    requantize of the same levels)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 8), jnp.float32)
    codes, rng = ref.kv_page_quantize(x, kv_bits=4)
    assert codes.shape == (4, 3, 4)                    # hd/2 bytes
    lo = np.asarray(codes) & 0xF
    hi = (np.asarray(codes) >> 4) & 0xF
    assert lo.max() <= 15 and hi.max() <= 15
    xhat = ref.kv_page_dequantize(codes, rng, kv_bits=4, head_dim=8)
    delta = np.asarray(ref._kv_page_delta(rng, 4))[..., None]
    q = np.stack([lo, hi], axis=-1).reshape(4, 3, 8)
    np.testing.assert_array_equal(np.asarray(xhat),
                                  delta * q - np.asarray(rng)[..., None])


@pytest.mark.parametrize("kv_bits", [8, 4])
@pytest.mark.parametrize("shape", _PAGED_LAYOUTS)
def test_paged_attention_quantized_pages_vs_ref(shape, kv_bits):
    """Both kernels dequantize int8/int4-packed pages in-kernel after the
    page DMA: the one-shot kernel stays bit-identical to the (extended) jnp
    oracle, the online variant stays within 1e-5."""
    from repro.kernels.paged_attention import (paged_attention_decode,
                                               paged_attention_decode_online)
    bsz, h, kv, hd, ps, pps, num_pages = shape
    q, k, v, bt, ctx = _paged_attn_inputs(bsz, h, kv, hd, ps, pps,
                                          num_pages, kv_dtype=jnp.float32)
    kc, kr = ref.kv_page_quantize(k, kv_bits=kv_bits)
    vc, vr = ref.kv_page_quantize(v, kv_bits=kv_bits)
    want = jax.jit(lambda *a: ref.paged_attention_ref(
        *a, k_scale=kr, v_scale=vr, kv_bits=kv_bits))(q, kc, vc, bt, ctx)
    one = paged_attention_decode(q, kc, vc, bt, ctx, k_scale=kr, v_scale=vr,
                                 kv_bits=kv_bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(want))
    onl = paged_attention_decode_online(q, kc, vc, bt, ctx, k_scale=kr,
                                        v_scale=vr, kv_bits=kv_bits,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(onl), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_attention_quantized_error_tracks_bit_width():
    """Reconstruction error vs full-precision pages shrinks with more bits
    and stays small in absolute terms (int8 within ~2e-2 on unit-scale
    activations), pinning the codec wiring end-to-end."""
    from repro.kernels.paged_attention import paged_attention_decode
    q, k, v, bt, ctx = _paged_attn_inputs(2, 4, 2, 16, 4, 3, 16,
                                          kv_dtype=jnp.float32)
    full = np.asarray(paged_attention_decode(q, k, v, bt, ctx,
                                             interpret=True))
    devs = {}
    for bits in (8, 4):
        kc, kr = ref.kv_page_quantize(k, kv_bits=bits)
        vc, vr = ref.kv_page_quantize(v, kv_bits=bits)
        out = paged_attention_decode(q, kc, vc, bt, ctx, k_scale=kr,
                                     v_scale=vr, kv_bits=bits,
                                     interpret=True)
        devs[bits] = np.abs(np.asarray(out) - full).max()
    assert devs[8] < 2e-2 and devs[8] < devs[4] < 1.0


def _outer_primitives(jaxpr, out):
    """Primitive names of a jaxpr, descending into nested jaxprs (pjit,
    scan, ...) but NOT into a pallas_call's kernel body — what remains is
    the host-side traced program the acceptance claim is about."""
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            leaves = jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "eqns")
                or hasattr(x, "jaxpr"))
            for j in leaves:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _outer_primitives(inner, out)
    return out


def test_fused_path_is_single_pallas_call_no_side_pass():
    """Regression for the tentpole claim: with ``use_pallas_quant`` the
    grouped quantize traces to exactly ONE pallas_call and *zero* host-side
    reduction ops — the (N, G) min/max side-information pass is gone from
    the program. The two-pass path is the positive probe (one reduce_max
    per leaf)."""
    from repro.core import engine as E
    from repro.core.quantization import QuantConfig
    tree, gids = _fused_tree_case("ragged")
    cfg = QuantConfig(b0=3, omega=0.97)
    state = E.GroupQuantState.create(tree, max(gids) + 1, b0=cfg.b0)
    key = jax.random.PRNGKey(0)

    fused = jax.make_jaxpr(
        lambda s, t, k: E.grouped_quantize_step(s, t, k, cfg, gids,
                                                use_kernel=True))(
        state, tree, key)
    prims = _outer_primitives(fused.jaxpr, [])
    assert prims.count("pallas_call") == 1
    assert "reduce_max" not in prims, "separate side-info pass reappeared"

    twopass = jax.make_jaxpr(
        lambda s, t, k: E.grouped_quantize_step_twopass(
            s, t, k, cfg, gids, use_kernel=True))(state, tree, key)
    prims2 = _outer_primitives(twopass.jaxpr, [])
    assert prims2.count("pallas_call") == 1
    # at least one per leaf (plus cross-leaf group combines)
    assert prims2.count("reduce_max") >= len(gids)


@pytest.mark.parametrize("shape", [(2, 2, 3), (8, 8, 512), (24, 24, 50),
                                   (16, 16, 130), (5, 5, 1024)])
def test_bipartite_mix_matches_ref(shape):
    n, _, d = shape
    key = jax.random.PRNGKey(n * d)
    adj = (jax.random.uniform(key, (n, n)) > 0.5).astype(jnp.float32)
    adj = jnp.triu(adj, 1)
    adj = adj + adj.T
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    got = bipartite_mix(adj, v, interpret=True)
    want = ref.bipartite_mix_ref(adj, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 12), d=st.integers(1, 300), seed=st.integers(0, 99))
def test_bipartite_mix_property(n, d, seed):
    key = jax.random.PRNGKey(seed)
    adj = (jax.random.uniform(key, (n, n)) > 0.4).astype(jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    got = bipartite_mix(adj, v, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(adj @ v),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(2, 3), (8, 512), (10, 130), (5, 1024)])
def test_edge_gather_mix_matches_ref(shape):
    """The scalar-prefetch edge-gather kernel equals its jnp oracle and
    the dense matmul on real bipartite graphs (interpret mode)."""
    from repro.core.graph import random_bipartite_graph
    from repro.kernels.edge_gather_mix import edge_gather_mix
    n, d = shape
    n = max(n, 4)
    g = random_bipartite_graph(n, 0.5, seed=n * d)
    table, valid = g.neighbor_table
    v = jax.random.normal(jax.random.PRNGKey(d), (n, d))
    got = edge_gather_mix(v, jnp.asarray(table), jnp.asarray(valid),
                          interpret=True)
    want = ref.edge_gather_mix_ref(v, jnp.asarray(table),
                                   jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.asarray(g.adjacency) @ v),
        rtol=1e-5, atol=1e-5)


def test_edge_gather_mix_zeroes_padded_slots():
    """Padded (invalid) slots contribute exactly nothing even when their
    table entry points at a nonzero row."""
    from repro.kernels.edge_gather_mix import edge_gather_mix
    v = jnp.asarray([[1.0, 2.0], [10.0, 20.0], [100.0, 200.0]])
    table = jnp.asarray([[1, 2], [0, 2], [0, 0]], jnp.int32)
    valid = jnp.asarray([[1.0, 0.0], [1.0, 1.0], [0.0, 0.0]])
    out = np.asarray(edge_gather_mix(v, table, valid, interpret=True))
    np.testing.assert_array_equal(
        out, [[10.0, 20.0], [101.0, 202.0], [0.0, 0.0]])


def test_quant_kernel_used_inside_step():
    """quantize_step(use_kernel=True) equals the jnp path bit-for-bit."""
    from repro.core.quantization import QuantConfig, QuantizerState, \
        quantize_step
    n, d = 6, 700
    key = jax.random.PRNGKey(3)
    theta = jax.random.normal(key, (n, d))
    state = QuantizerState.create(n, d, b0=3)
    cfg = QuantConfig(b0=3, omega=0.95)
    s1, q1, b1, p1 = quantize_step(state, theta, jax.random.PRNGKey(7), cfg,
                                   use_kernel=False)
    s2, q2, b2, p2 = quantize_step(state, theta, jax.random.PRNGKey(7), cfg,
                                   use_kernel=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


@pytest.mark.parametrize("shape", [(3, 37, 2, 16), (1, 5, 1, 8),
                                   (8, 64, 4, 32)])
def test_slstm_cell_matches_ref(shape):
    """Fused sLSTM cell kernel vs the sequential-scan oracle."""
    from repro.kernels.slstm_cell import slstm_cell
    b, s, h, dh = shape
    key = jax.random.PRNGKey(b * s)
    wx = 0.5 * jax.random.normal(key, (b, s, h, 4 * dh))
    r_w = jax.random.normal(jax.random.fold_in(key, 1),
                            (h, dh, 4 * dh)) / jnp.sqrt(dh)
    fb = jnp.full((h, dh), 3.0)
    c0 = n0 = h0 = jnp.zeros((b, h, dh))
    m0 = jnp.full((b, h, dh), -1e30)
    hs_k, st_k = slstm_cell(wx, r_w, fb, c0, n0, m0, h0, block_b=2,
                            chunk_s=16, interpret=True)
    hs_r, st_r = ref.slstm_cell_ref(wx, r_w, fb, c0, n0, m0, h0)
    np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r),
                               rtol=1e-5, atol=1e-5)
    for a, b_ in zip(st_k, st_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_slstm_model_kernel_path():
    """slstm_apply(use_kernel=True) equals the scan path."""
    from repro.configs import base
    from repro.models import xlstm
    cfg = base.get_smoke_config("xlstm-125m")
    params = xlstm.slstm_init(jax.random.PRNGKey(0), cfg)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    a, _ = xlstm.slstm_apply(params, cfg, x, use_kernel=True)
    b, _ = xlstm.slstm_apply(params, cfg, x, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=1e-4, atol=1e-5)
