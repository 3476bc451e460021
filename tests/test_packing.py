"""Packed buffer view (core/packing.py): layout, roundtrip, segment
reductions, cache behavior — the substrate of the fused quantize path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing as P


def _tree(n=4):
    key = jax.random.PRNGKey(0)
    return {"a": jax.random.normal(key, (n, 3, 4)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (n, 5)),
                  "d": jax.random.normal(jax.random.fold_in(key, 2),
                                         (n, 2, 3))}}


def test_layout_metadata():
    t = _tree()
    pk = P.make_packing(t, (0, 1, 1))
    assert pk.n_leaves == 3
    assert pk.dims == (12, 5, 6)
    assert pk.offsets == (0, 12, 17)
    assert pk.dim == 23
    assert pk.n_groups == 2
    assert pk.group_dims == (12, 11)
    cols = pk.col_group_ids
    assert cols.shape == (23,) and cols.dtype == np.int32
    np.testing.assert_array_equal(cols, [0] * 12 + [1] * 11)
    assert pk.sorted_ids
    assert not P.make_packing(t, (1, 0, 1)).sorted_ids


def test_pack_unpack_roundtrip_preserves_values_and_dtypes():
    t = _tree()
    t["b"]["c"] = t["b"]["c"].astype(jnp.bfloat16)
    pk = P.make_packing(t, (0, 1, 2))
    buf = P.pack(pk, t)
    assert buf.shape == (4, 23) and buf.dtype == jnp.float32
    back = P.unpack(pk, buf)
    for orig, rt in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(back)):
        assert orig.dtype == rt.dtype and orig.shape == rt.shape
        np.testing.assert_array_equal(np.asarray(orig, np.float32),
                                      np.asarray(rt, np.float32))


def test_unpack_like_overrides_dtypes():
    t = _tree()
    pk = P.make_packing(t, (0, 0, 0))
    like = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), t)
    back = P.unpack(pk, P.pack(pk, t), like=like)
    for leaf in jax.tree_util.tree_leaves(back):
        assert leaf.dtype == jnp.bfloat16


def test_single_leaf_pack_is_reshape():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    pk = P.make_packing(x, (0,))
    np.testing.assert_array_equal(np.asarray(P.pack(pk, x)), np.asarray(x))


def test_segment_reductions_match_per_leaf():
    t = _tree()
    gids = (0, 1, 0)
    pk = P.make_packing(t, gids)
    buf = P.pack(pk, t)
    leaves = [np.asarray(x).reshape(4, -1)
              for x in jax.tree_util.tree_leaves(t)]
    want_max = np.stack(
        [np.abs(np.concatenate([leaves[0], leaves[2]], 1)).max(1),
         np.abs(leaves[1]).max(1)], axis=1)
    np.testing.assert_allclose(np.asarray(P.segment_maxabs(pk, buf)),
                               want_max, rtol=1e-6)


def test_cache_returns_same_instance():
    t = _tree()
    assert P.make_packing(t, (0, 1, 2)) is P.make_packing(t, (0, 1, 2))
    # different groups, different layout objects
    assert P.make_packing(t, (0, 0, 0)) is not P.make_packing(t, (0, 1, 2))


def test_group_arity_validated():
    with pytest.raises(ValueError):
        P.make_packing(_tree(), (0, 1))
    with pytest.raises(ValueError):
        P.make_packing((), (0,))


def test_pack_inside_jit_traces():
    t = _tree()
    pk = P.make_packing(t, (0, 1, 1))

    @jax.jit
    def f(tree):
        buf = P.pack(pk, tree)
        return P.segment_maxabs(pk, buf)

    out = f(t)
    assert out.shape == (4, 2)
