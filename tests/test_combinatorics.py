import itertools
import math

import numpy as np
import pytest
from _propcheck import given, hst, settings

from repro.core.combinatorics import (build_pst, candidates_to_nodes,
                                      n_parent_sets, nodes_to_candidates,
                                      rank_combination,
                                      rank_combinations_batch,
                                      rank_parent_set, size_offsets,
                                      unrank_combination, unrank_parent_set)


@pytest.mark.parametrize("n,s", [(6, 3), (9, 2), (12, 4)])
def test_rank_combinations_batch_matches_scalar(n, s):
    """Vectorized hockey-stick ranking == the scalar rank_parent_set, i.e.
    the identity build_pst row t ranks back to t for every t."""
    pst, sizes = build_pst(n, s)
    got = rank_combinations_batch(n, s, pst, sizes)
    np.testing.assert_array_equal(got, np.arange(pst.shape[0]))
    # and on a shuffled batch with explicit scalar cross-check
    rng = np.random.default_rng(0)
    sel = rng.choice(pst.shape[0], size=min(50, pst.shape[0]), replace=False)
    got = rank_combinations_batch(n, s, pst[sel], sizes[sel])
    want = [rank_parent_set(n, s, row[row >= 0]) for row in pst[sel]]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,k", [(5, 2), (7, 3), (8, 4), (6, 1), (4, 4)])
def test_unrank_matches_itertools(n, k):
    combos = list(itertools.combinations(range(n), k))
    for l, c in enumerate(combos):
        assert tuple(unrank_combination(n, k, l)) == c


@given(hst.integers(2, 12), hst.integers(1, 4), hst.data())
@settings(max_examples=200, deadline=None)
def test_rank_unrank_roundtrip(n, k, data):
    k = min(k, n)
    l = data.draw(hst.integers(0, math.comb(n, k) - 1))
    c = unrank_combination(n, k, l)
    assert rank_combination(n, c) == l
    assert np.all(np.diff(c) > 0)  # strictly increasing
    assert 0 <= c[0] and c[-1] < n


def test_unrank_out_of_range():
    with pytest.raises(ValueError):
        unrank_combination(5, 2, math.comb(5, 2))


@pytest.mark.parametrize("nc,s", [(6, 4), (10, 3), (5, 2), (12, 4)])
def test_pst_complete_and_ordered(nc, s):
    pst, sizes = build_pst(nc, s)
    S = n_parent_sets(nc, s)
    assert pst.shape == (S, s)
    assert sizes.shape == (S,)
    # paper's example: n=6 candidates, s=4 -> S=57
    if (nc, s) == (6, 4):
        assert S == 57
    seen = set()
    off = size_offsets(nc, s)
    for i in range(S):
        row = tuple(pst[i][pst[i] >= 0].tolist())
        assert len(row) == sizes[i]
        assert row not in seen
        seen.add(row)
        # rank is the inverse of the table position
        assert rank_parent_set(nc, s, np.asarray(row, np.int64)) == i
    # block boundaries by size
    assert np.all(np.diff(sizes) >= 0)
    for k in range(s + 1):
        assert (sizes == k).sum() == math.comb(nc, k)
        assert off[k + 1] - off[k] == math.comb(nc, k)


def _build_pst_rowloop(n_candidates, s):
    """The per-row enumeration build_pst replaced: steps through every
    combination of a size block in lexicographic order, one row at a time.
    Kept as the oracle for the vectorised build (raises where C(N, k) = 0)."""
    rows = []
    sizes = []
    for k in range(s + 1):
        if k == 0:
            rows.append(np.full((1, s), -1, dtype=np.int32))
            sizes.append(np.zeros(1, dtype=np.int32))
            continue
        block = np.empty((math.comb(n_candidates, k), s), dtype=np.int32)
        block[:] = -1
        c = np.arange(k, dtype=np.int64)
        idx = 0
        while True:
            block[idx, :k] = c
            idx += 1
            j = k - 1
            while j >= 0 and c[j] == n_candidates - k + j:
                j -= 1
            if j < 0:
                break
            c[j] += 1
            for jj in range(j + 1, k):
                c[jj] = c[jj - 1] + 1
        rows.append(block)
        sizes.append(np.full(idx, k, dtype=np.int32))
    return np.concatenate(rows, axis=0), np.concatenate(sizes)


PST_CASES = [(1, 1), (5, 2), (6, 4), (12, 4), (36, 4), (37, 4), (59, 4),
             (60, 4), (20, 6)]


def _assert_bitwise(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.int32
        assert g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("nc,s", PST_CASES)
def test_build_pst_matches_rowloop(nc, s):
    """The suffix-concatenation build is bitwise the per-row enumeration:
    rows, padding, sizes, dtypes and order."""
    _assert_bitwise(build_pst(nc, s), _build_pst_rowloop(nc, s))


def test_build_pst_fewer_candidates_than_s():
    """Where C(N, k) = 0 the size-k block is empty (the row loop raised):
    build_pst(3, 4) lists the 8 subsets of {0, 1, 2} in size order."""
    pst, sizes = build_pst(3, 4)
    want = [c for k in range(5) for c in itertools.combinations(range(3), k)]
    assert len(want) == 8 and pst.shape == (8, 4)
    assert [tuple(row[row >= 0].tolist()) for row in pst] == want
    np.testing.assert_array_equal(sizes, [len(c) for c in want])
    assert np.all(pst[np.arange(4)[None, :] >= sizes[:, None]] == -1)
    pst0, sizes0 = build_pst(0, 2)
    np.testing.assert_array_equal(pst0, [[-1, -1]])
    np.testing.assert_array_equal(sizes0, [0])


@pytest.mark.parametrize("n,s", PST_CASES)
def test_pst_without_last_column_is_candidate_pst(n, s):
    """The rows of build_pst(n, s) without column n - 1 are build_pst(n - 1,
    s), bitwise and in order: what lets the dense build enumerate once."""
    sub, ssz = build_pst(n, s)
    keep = (sub != n - 1).all(1)
    _assert_bitwise((sub[keep], ssz[keep]), build_pst(n - 1, s))


@given(hst.integers(2, 20), hst.data())
@settings(max_examples=100, deadline=None)
def test_candidate_node_mapping_bijection(n, data):
    node = data.draw(hst.integers(0, n - 1))
    cands = np.arange(n - 1)
    nodes = candidates_to_nodes(cands, node)
    assert node not in set(nodes.tolist())
    assert len(set(nodes.tolist())) == n - 1
    back = nodes_to_candidates(nodes, node)
    np.testing.assert_array_equal(back, cands)


def test_pst_memory_matches_paper_figure():
    # Paper Fig. 6(b): 60-node graph, s=4 -> ~7.99 MB PST.
    S = n_parent_sets(59, 4)
    mb = S * 4 * 4 / 2**20  # S rows x 4 int32
    assert 7.0 < mb < 9.0


@pytest.mark.parametrize("nc,s", [(7, 3), (11, 2), (10, 4)])
def test_unrank_parent_set_inverts_pst_rows(nc, s):
    """unrank_parent_set decodes EVERY global rank back to its build_pst row
    — the no-PST adjacency path (ISSUE 3 satellite) cannot drift from the
    materialized table."""
    pst, sizes = build_pst(nc, s)
    for t in range(pst.shape[0]):
        cands = unrank_parent_set(nc, s, t)
        row = pst[t][pst[t] >= 0]
        np.testing.assert_array_equal(np.sort(cands), np.sort(row))
        assert len(cands) == sizes[t]
    with pytest.raises(ValueError):
        unrank_parent_set(nc, s, pst.shape[0])
    with pytest.raises(ValueError):
        unrank_parent_set(nc, s, -1)


def test_adjacency_from_ranks_matches_pst_lookup():
    """adjacency_from_ranks == adjacency_from_best on random winning ranks."""
    from repro.core.graph import adjacency_from_best, adjacency_from_ranks

    n, s = 9, 3
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(5)
    for _ in range(20):
        best_idx = rng.integers(0, pst.shape[0], size=n)
        want = adjacency_from_best(best_idx, pst)
        got = adjacency_from_ranks(best_idx, s=s)
        np.testing.assert_array_equal(got, want)
