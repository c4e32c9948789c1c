"""core/metrics hardening + the edge_posterior helper (ISSUE 7 satellite)."""
import numpy as np
import pytest

from repro.core import edge_posterior, roc_point, structural_hamming


def test_roc_point_basic():
    truth = np.zeros((3, 3), int)
    truth[0, 1] = truth[1, 2] = 1
    learned = np.zeros((3, 3), int)
    learned[0, 1] = 1               # one true edge
    learned[2, 0] = 1               # one spurious edge
    fp, tp = roc_point(learned, truth)
    assert tp == 0.5                # 1 of 2 true edges
    assert fp == 0.25               # 1 of 4 true non-edges


def test_roc_point_empty_inputs():
    fp, tp = roc_point(np.zeros((0, 0)), np.zeros((0, 0)))
    assert (fp, tp) == (0.0, 0.0)
    fp, tp = roc_point(np.zeros((4, 4)), np.zeros((4, 4)))   # edgeless truth
    assert (fp, tp) == (0.0, 0.0)


def test_roc_point_ignores_self_loops():
    truth = np.eye(4, dtype=int)          # only self-loops: no real edges
    learned = np.eye(4, dtype=int)
    assert roc_point(learned, truth) == (0.0, 0.0)
    # a self-loop on the learned side is not a false positive
    truth = np.zeros((3, 3), int)
    truth[0, 1] = 1
    learned = truth.copy()
    learned[2, 2] = 1
    fp, tp = roc_point(learned, truth)
    assert (fp, tp) == (0.0, 1.0)


def test_roc_point_rejects_bad_shapes():
    with pytest.raises(ValueError, match="square"):
        roc_point(np.zeros((2, 3)), np.zeros((3, 3)))
    with pytest.raises(ValueError, match="square"):
        roc_point(np.zeros(3), np.zeros((3, 3)))
    with pytest.raises(ValueError, match="differ"):
        roc_point(np.zeros((2, 2)), np.zeros((3, 3)))


def test_structural_hamming_hardened():
    assert structural_hamming(np.zeros((0, 0)), np.zeros((0, 0))) == 0
    a = np.zeros((3, 3), int)
    b = a.copy()
    b[1, 1] = 1                           # self-loop only: not a difference
    assert structural_hamming(a, b) == 0
    b[0, 2] = 1
    assert structural_hamming(a, b) == 1
    with pytest.raises(ValueError, match="differ"):
        structural_hamming(np.zeros((2, 2)), np.zeros((3, 3)))


def test_edge_posterior_hand_computed_3_nodes():
    # 4 thinned samples of a 3-node walk: edge 0->1 present in all four,
    # 1->2 in two, 2->0 in one; the diagonal picked up a stray count
    counts = np.array([[1, 4, 0],
                       [0, 0, 2],
                       [1, 0, 0]])
    p = edge_posterior(counts, 4)
    expect = np.array([[0.0, 1.0, 0.0],
                       [0.0, 0.0, 0.5],
                       [0.25, 0.0, 0.0]])
    np.testing.assert_allclose(p, expect)


def test_edge_posterior_pools_chains():
    counts = np.stack([np.full((3, 3), 2), np.full((3, 3), 4)])  # (C, n, n)
    p = edge_posterior(counts, 4)         # (2+4) / (2 chains * 4 samples)
    off = ~np.eye(3, dtype=bool)
    np.testing.assert_allclose(p[off], 0.75)
    np.testing.assert_allclose(np.diag(p), 0.0)


def test_edge_posterior_degenerate_and_invalid():
    np.testing.assert_array_equal(edge_posterior(np.zeros((3, 3)), 0),
                                  np.zeros((3, 3)))
    with pytest.raises(ValueError, match="square"):
        edge_posterior(np.zeros((2, 3)), 1)
    with pytest.raises(ValueError, match="shape"):
        edge_posterior(np.zeros(3), 1)
    with pytest.raises(ValueError, match="outside"):
        edge_posterior(np.full((2, 2), 9), 4)
    with pytest.raises(ValueError, match="outside"):
        edge_posterior(np.full((2, 2), -1), 4)


# ---------------------------------------------------------------------------
# map_dag / consensus_graph (ISSUE 10 satellite: the service query layer's
# posterior artifacts), property-tested against a brute-force oracle
# ---------------------------------------------------------------------------
from _propcheck import given, hst, settings  # noqa: E402

from repro.core.combinatorics import (candidates_to_nodes,  # noqa: E402
                                      nodes_to_candidates, rank_parent_set,
                                      unrank_parent_set)
from repro.core.metrics import consensus_graph, map_dag  # noqa: E402
from repro.core.scores import build_score_table  # noqa: E402
from repro.preprocess.sparse import prune_table  # noqa: E402


def _oracle_map_dag(table, s, pos):
    """Brute force: per child, walk EVERY global PST rank in order, keep the
    first consistent argmax (strict > — ties resolve to the lowest rank,
    the contract map_dag and the jitted scorers share)."""
    n = len(pos)
    adj = np.zeros((n, n), np.int8)
    for i in range(n):
        best, best_parents = -np.inf, np.empty(0, np.int64)
        for r in range(table.shape[1]):
            parents = candidates_to_nodes(unrank_parent_set(n - 1, s, r), i)
            if all(pos[p] < pos[i] for p in parents) and table[i, r] > best:
                best, best_parents = table[i, r], parents
        adj[best_parents, i] = 1
    return adj


def _map_score(table, s, adj):
    """Total score of a decoded structure: sum of each child's chosen
    parent-set entry (tie-insensitive quality measure)."""
    n = adj.shape[0]
    return sum(table[i, rank_parent_set(
        n - 1, s, nodes_to_candidates(np.nonzero(adj[:, i])[0], i))]
        for i in range(n))


@settings(max_examples=10, deadline=None)
@given(hst.integers(0, 10_000))
def test_map_dag_matches_bruteforce_oracle(seed):
    rng = np.random.default_rng(seed)
    n, s = int(rng.integers(3, 7)), int(rng.integers(1, 3))
    data = rng.integers(0, 2, size=(50, n)).astype(np.int8)
    st = build_score_table(data, q=2, s=s)
    pos = np.argsort(rng.permutation(n))      # pos[v] = position of node v
    table = np.asarray(st.table)
    want = _oracle_map_dag(table, s, pos)
    got = map_dag(st, pos)
    np.testing.assert_array_equal(got, want)
    # every edge respects the order, and the decode is score-optimal
    pr, ch = np.nonzero(got)
    assert np.all(pos[pr] < pos[ch])
    assert np.isclose(_map_score(table, s, got), _map_score(table, s, want))


@settings(max_examples=10, deadline=None)
@given(hst.integers(0, 10_000))
def test_map_dag_pruned_matches_dense(seed):
    rng = np.random.default_rng(seed)
    n, s = int(rng.integers(3, 7)), int(rng.integers(1, 3))
    data = rng.integers(0, 2, size=(50, n)).astype(np.int8)
    st = build_score_table(data, q=2, s=s)
    pos = np.argsort(rng.permutation(n))
    # delta wide enough to keep everything: the pruned decode must agree
    # with the dense one exactly (kept_idx is rank-ascending, so even score
    # ties break identically)
    sp = prune_table(st, delta=1e9)
    np.testing.assert_array_equal(map_dag(sp, pos), map_dag(st, pos))
    # a tight delta still yields an order-consistent DAG
    tight = map_dag(prune_table(st, delta=1.0), pos)
    pr, ch = np.nonzero(tight)
    assert np.all(pos[pr] < pos[ch])


def test_map_dag_rejects_bad_pos():
    data = np.zeros((10, 3), np.int8)
    st = build_score_table(data, q=2, s=1)
    with pytest.raises(ValueError, match="flat"):
        map_dag(st, np.zeros((2, 3), int))


def test_consensus_graph_thresholds():
    p = np.array([[0.0, 0.9, 0.5],
                  [0.2, 0.0, 0.49],
                  [1.0, 0.5, 0.0]])
    got = consensus_graph(p, 0.5)
    want = np.array([[0, 1, 1],
                     [0, 0, 0],
                     [1, 1, 0]], np.int8)
    np.testing.assert_array_equal(got, want)
    assert consensus_graph(p, 1.0).sum() == 1          # only the certain edge
    # diagonal is dropped even when probabilities sneak onto it
    q = np.eye(3) * 0.9
    assert consensus_graph(q, 0.5).sum() == 0


def test_consensus_graph_validation():
    with pytest.raises(ValueError, match="square"):
        consensus_graph(np.zeros((2, 3)), 0.5)
    with pytest.raises(ValueError, match="outside"):
        consensus_graph(np.full((2, 2), 1.5), 0.5)
    with pytest.raises(ValueError, match="threshold"):
        consensus_graph(np.zeros((2, 2)), 0.0)
    with pytest.raises(ValueError, match="threshold"):
        consensus_graph(np.zeros((2, 2)), 1.1)
