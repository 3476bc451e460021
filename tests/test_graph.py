"""Worker-graph properties (paper Assumption 1 + Appendix D identities)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # offline: property tests skip, rest runs
    from _hypothesis_stub import given, settings, st

from repro.core import graph as G


def test_chain_graph_matches_gadmm():
    g = G.chain_graph(6)
    assert g.num_edges == 5
    assert g.head_mask.tolist() == [True, False] * 3
    # every edge connects adjacent workers
    for h, t in g.edges:
        assert abs(h - t) == 1


def test_complete_bipartite():
    g = G.complete_bipartite_graph(3, 4)
    assert g.num_edges == 12
    assert g.degrees[:3].tolist() == [4.0] * 3
    assert g.degrees[3:].tolist() == [3.0] * 4


def test_star_graph():
    g = G.star_graph(5)
    assert g.degrees[0] == 4
    assert (g.degrees[1:] == 1).all()


def test_pod_pair():
    g = G.pod_pair_graph()
    assert g.n == 2 and g.num_edges == 1


@pytest.mark.parametrize("n,topology", [(2, "random"), (6, "chain"),
                                        (6, "complete"), (8, "random")])
def test_worker_graph_from_cli(n, topology):
    """The trainer's ``--workers``/``--topology`` graphs: n workers,
    bipartite and connected, with at least one head and one tail."""
    g = G.worker_graph(n, topology)
    g.validate()          # asserts bipartite + connected + identities
    assert g.n == n
    assert g.head_mask.any() and g.tail_mask.any()
    assert g.head_mask[g.edges[:, 0]].all()
    assert (~g.head_mask[g.edges[:, 1]]).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 40), p=st.floats(0.05, 0.9),
       seed=st.integers(0, 10_000))
def test_random_graph_bipartite_connected(n, p, seed):
    g = G.random_bipartite_graph(n, p, seed=seed)
    g.validate()          # asserts bipartite + connected + identities
    assert g.n == n
    assert G.is_connected(g.adjacency)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 24), seed=st.integers(0, 100))
def test_incidence_identities(n, seed):
    """D - A = M- M-^T and A = (M+ M+^T - M- M-^T)/2 (Appendix D)."""
    g = G.random_bipartite_graph(n, 0.4, seed=seed)
    m_minus, m_plus = g.signed_incidence, g.unsigned_incidence
    np.testing.assert_allclose(g.degree_matrix - g.adjacency,
                               m_minus @ m_minus.T, atol=1e-5)
    np.testing.assert_allclose(
        g.adjacency, 0.5 * (m_plus @ m_plus.T - m_minus @ m_minus.T),
        atol=1e-5)
    c = g.c_matrix
    np.testing.assert_allclose(g.adjacency, c + c.T, atol=1e-5)
    # C only has head-row -> tail-col entries (Eq. 115)
    assert c[g.tail_mask, :].sum() == 0
    assert c[:, g.head_mask].sum() == 0


def test_connectivity_ratio():
    g = G.random_bipartite_graph(20, 0.3, seed=1)
    # generator targets round(p * N(N-1)/2) edges but at least a spanning
    # tree and at most the bipartite maximum
    assert g.num_edges >= g.n - 1
    assert 0 < g.connectivity_ratio() <= 1.0


def test_density_affects_edges():
    sparse = G.random_bipartite_graph(18, 0.2, seed=0)
    dense = G.random_bipartite_graph(18, 0.4, seed=0)
    assert dense.num_edges > sparse.num_edges


# ------------------------------------------- invariants at larger N ----
# Property-style sweep over the paper's connectivity ratios (Sec. 7) at
# worker counts past the unit-test scale: bipartiteness, connectivity,
# degree/adjacency/incidence consistency, and the new edge-list/CSR
# arrays all round-tripping against the dense adjacency.
@pytest.mark.parametrize("p", [0.1, 0.3, 0.5, 1.0])
@pytest.mark.parametrize("n", [48, 96])
def test_random_graph_invariants_large(n, p):
    g = G.random_bipartite_graph(n, p, seed=int(n * 10 + p * 10))
    g.validate()   # bipartite + connected + incidence + edge/CSR identities
    a = g.adjacency
    # degrees match adjacency row sums and the CSR row lengths
    np.testing.assert_array_equal(g.degrees, a.sum(axis=1))
    np.testing.assert_array_equal(np.diff(g.csr_offsets), g.degrees)
    # at least a spanning structure, at most the bipartite maximum
    n_heads = int(g.head_mask.sum())
    assert g.n - 1 <= g.num_edges <= n_heads * (n - n_heads)
    # edge endpoints respect the head/tail split
    assert g.head_mask[g.edges[:, 0]].all()
    assert (~g.head_mask[g.edges[:, 1]]).all()


@pytest.mark.parametrize("p", [0.1, 0.3, 0.5, 1.0])
def test_edge_arrays_match_adjacency(p):
    g = G.random_bipartite_graph(64, p, seed=11)
    # every directed edge appears exactly once, dst-sorted
    rebuilt = np.zeros_like(g.adjacency)
    np.add.at(rebuilt, (g.edge_dst, g.edge_src), 1.0)
    np.testing.assert_array_equal(rebuilt, g.adjacency)
    assert (np.diff(g.edge_dst) >= 0).all()
    # CSR rows list exactly each node's neighbor set
    for node in range(0, g.n, 7):
        lo, hi = g.csr_offsets[node], g.csr_offsets[node + 1]
        want = set(np.nonzero(g.adjacency[node] > 0)[0].tolist())
        assert set(g.csr_indices[lo:hi].tolist()) == want
    # padded neighbor table covers the same sets, valid-masked
    table, valid = g.neighbor_table
    assert table.shape == (g.n, g.max_degree)
    for node in range(0, g.n, 7):
        deg = int(g.degrees[node])
        assert valid[node, :deg].all() and not valid[node, deg:].any()
        want = set(np.nonzero(g.adjacency[node] > 0)[0].tolist())
        assert set(table[node, :deg].tolist()) == want


def test_nonbipartite_rejected():
    g = G.chain_graph(4)
    bad = g.adjacency.copy()
    bad[0, 2] = bad[2, 0] = 1.0   # head-head edge
    with pytest.raises(AssertionError):
        G.WorkerGraph(n=4, edges=g.edges, head_mask=g.head_mask,
                      adjacency=bad,
                      degrees=bad.sum(1).astype(np.float32)).validate()
