"""Local-score correctness: jnp chunked implementation vs a slow pure-Python
oracle computing paper Eq. 4 with math.lgamma, plus invariance properties."""
import math

import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, hst, settings

from repro.core.combinatorics import rank_parent_set
from repro.core.scores import build_score_table, score_single


def oracle_local_score(data, node, parents, q, gamma, ess):
    """Pure-Python Eq. 4 (natural log), counting with dicts. q: one arity
    for every variable or one per variable."""
    import itertools
    r = [q] * data.shape[1] if np.ndim(q) == 0 else list(q)
    k = len(parents)
    q_pa = math.prod(r[p] for p in parents)
    a_jk = ess / (q_pa * r[node])
    a_k = ess / q_pa
    counts = {}
    for row in data:
        key = tuple(int(row[p]) for p in parents)
        c = counts.setdefault(key, [0] * r[node])
        c[int(row[node])] += 1
    ls = k * math.log(gamma)
    # iterate over ALL q_pa parent configurations (zero-count ones add 0)
    for key in itertools.product(*(range(r[p]) for p in parents)):
        c = counts.get(tuple(key), [0] * r[node])
        Nk = sum(c)
        ls += math.lgamma(a_k) - math.lgamma(a_k + Nk)
        for j in range(r[node]):
            ls += math.lgamma(c[j] + a_jk) - math.lgamma(a_jk)
    return ls


@pytest.mark.parametrize("q,s", [(2, 2), (3, 3), (2, 4),
                                 ((2, 3, 4, 3, 2, 4), 2),
                                 ((4, 2, 2, 3, 4, 3), 3)])
def test_table_matches_oracle(q, s):
    rng = np.random.default_rng(1)
    n, m = 6, 200
    data = rng.integers(0, np.asarray(q), size=(m, n)).astype(np.int32)
    st = build_score_table(data, q=q, s=s, gamma=0.1, ess=1.0, chunk=13)
    table = np.asarray(st.table)
    pst = np.asarray(st.pst)
    # check a spread of entries against the oracle
    idxs = rng.choice(table.shape[1], size=min(25, table.shape[1]), replace=False)
    for i in range(n):
        for t in idxs:
            cands = pst[t][pst[t] >= 0]
            parents = [int(c + (c >= i)) for c in cands]
            want = oracle_local_score(data, i, parents, q, 0.1, 1.0)
            np.testing.assert_allclose(table[i, t], want, rtol=2e-5, atol=2e-4)


def test_score_single_matches_table():
    rng = np.random.default_rng(2)
    q, s, n, m = 3, 2, 5, 150
    data = rng.integers(0, q, size=(m, n)).astype(np.int32)
    st = build_score_table(data, q=q, s=s)
    # hash-table-equivalent lookup: rank indexes the dense table
    from repro.core.combinatorics import nodes_to_candidates
    for node, parents in [(0, [1, 3]), (2, [4]), (4, []), (1, [0, 2])]:
        cands = nodes_to_candidates(np.asarray(parents, np.int64), node)
        t = rank_parent_set(n - 1, s, cands)
        got = score_single(data, node, parents, q=q, s=s)
        np.testing.assert_allclose(np.asarray(st.table)[node, t], got,
                                   rtol=1e-5, atol=1e-5)


def test_parent_oversize_rejected():
    with pytest.raises(ValueError):
        rank_parent_set(6, 2, np.array([0, 1, 2]))


@given(hst.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_empty_parent_set_independent_of_gamma(seed):
    """|π|=0 ⇒ γ-penalty vanishes: score identical for any γ."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 2, size=(50, 4)).astype(np.int32)
    a = score_single(data, 0, [], q=2, s=2, gamma=0.1)
    b = score_single(data, 0, [], q=2, s=2, gamma=0.9)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_more_data_sharpens_true_parent():
    """With data generated by a strong edge 0 -> 1, ls(1,{0}) should beat ls(1,{})."""
    rng = np.random.default_rng(3)
    m = 2000
    x = rng.integers(0, 2, size=m)
    y = np.where(rng.random(m) < 0.9, x, 1 - x)  # y strongly depends on x
    z = rng.integers(0, 2, size=m)
    data = np.stack([x, y, z], axis=1).astype(np.int32)
    with_parent = score_single(data, 1, [0], q=2, s=2)
    without = score_single(data, 1, [], q=2, s=2)
    wrong = score_single(data, 1, [2], q=2, s=2)
    assert with_parent > without > wrong - 1e-3
    assert with_parent - without > 100  # decisive at m=2000


def test_data_range_validated():
    data = np.array([[0, 3], [1, 0]], np.int32)
    with pytest.raises(ValueError):
        build_score_table(data, q=2, s=1)


def test_prior_matrix_validated_up_front():
    """Satellite: malformed priors fail fast with a clear error instead of a
    shape blow-up mid-build."""
    rng = np.random.default_rng(9)
    data = rng.integers(0, 2, size=(40, 5)).astype(np.int32)
    with pytest.raises(ValueError, match="square"):
        build_score_table(data, q=2, s=2,
                          prior_matrix=np.full((5, 4), 0.5, np.float32))
    with pytest.raises(ValueError, match="n=5"):
        build_score_table(data, q=2, s=2,
                          prior_matrix=np.full((4, 4), 0.5, np.float32))
    with pytest.raises(ValueError, match=r"\[0, 1\]"):
        build_score_table(data, q=2, s=2,
                          prior_matrix=np.full((5, 5), 1.5, np.float32))
    bad = np.full((5, 5), 0.5, np.float32)
    bad[0, 0] = np.nan
    with pytest.raises(ValueError, match="finite"):
        build_score_table(data, q=2, s=2, prior_matrix=bad)


def test_prior_fold_matches_manual_add():
    """Batched per-node launches keep the prior fold exact: table(prior) ==
    table(none) + prior_table."""
    from repro.core.priors import prior_table

    rng = np.random.default_rng(10)
    n, q, s, m = 6, 2, 2, 120
    data = rng.integers(0, q, size=(m, n)).astype(np.int32)
    R = np.full((n, n), 0.5, np.float32)
    R[2, 0] = 0.9
    st0 = build_score_table(data, q=q, s=s)
    st1 = build_score_table(data, q=q, s=s, prior_matrix=R)
    import jax.numpy as jnp
    want = np.asarray(st0.table) + np.asarray(
        prior_table(jnp.asarray(R), st0.pst, n))
    np.testing.assert_allclose(np.asarray(st1.table), want,
                               rtol=1e-6, atol=1e-5)
