"""Preprocessing subsystem: fused pipeline vs the core/scores oracle, sparse
table semantics (lookup + pruning guarantee), planner, and disk cache."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _propcheck import given, hst, settings

from repro.core.combinatorics import build_pst, rank_parent_set
from repro.core.order_scoring import (score_order_blocked, score_order_pruned,
                                      score_order_pruned_delta)
from repro.core.scores import build_score_table
from repro.core.sharded_scoring import pad_table
from repro.preprocess import (SparseScoreTable, build_score_table_fused,
                              plan_preprocess, prune_table)
from repro.preprocess.fused import (encode_subset_codes, fused_scores_pallas,
                                    fused_scores_ref, score_luts)


def _rand_problem(rng, n, q, m):
    return rng.integers(0, q, size=(m, n)).astype(np.int32)


# ------------------------------------------------------------ fused == oracle
@given(hst.data())
@settings(max_examples=6, deadline=None)
def test_fused_matches_oracle_property(data_strategy):
    """Fused pipeline == build_score_table over random (n, q, s, m) to the
    ISSUE's 1e-4 absolute gate (bitwise on CPU by construction)."""
    rng_seed = data_strategy.draw(hst.integers(0, 2**31 - 1))
    rng = np.random.default_rng(rng_seed)
    n = data_strategy.draw(hst.integers(5, 11))
    q = data_strategy.draw(hst.integers(2, 4))
    s = data_strategy.draw(hst.integers(1, 3))
    m = data_strategy.draw(hst.integers(40, 200))
    data = _rand_problem(rng, n, q, m)
    want = np.asarray(build_score_table(data, q=q, s=s).table)
    got = np.asarray(build_score_table_fused(data, q=q, s=s).table)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_fused_matches_oracle_with_prior():
    rng = np.random.default_rng(3)
    n, q, s, m = 9, 2, 3, 150
    data = _rand_problem(rng, n, q, m)
    R = np.full((n, n), 0.5, np.float32)
    R[1, 0] = 0.95
    R[4, 2] = 0.1
    want = np.asarray(build_score_table(data, q=q, s=s, prior_matrix=R).table)
    got = np.asarray(build_score_table_fused(data, q=q, s=s,
                                             prior_matrix=R).table)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_fused_small_chunk_matches():
    """Chunking must not change values (multiple chunks per device scan)."""
    rng = np.random.default_rng(4)
    n, q, s, m = 8, 2, 2, 120
    data = _rand_problem(rng, n, q, m)
    want = np.asarray(build_score_table_fused(data, q=q, s=s).table)
    got = np.asarray(build_score_table_fused(data, q=q, s=s, chunk=7).table)
    np.testing.assert_array_equal(got, want)


def test_lgamma_f32_is_bitwise_xla_lgamma():
    """The fused kernel's lgamma (the TPU kernel lowering has none) repeats
    XLA's own expansion op for op, so on CPU it is bitwise jax.lax.lgamma:
    over the score's arguments (integer counts plus ess / q^k), a wide
    range, and the pole, negative and infinite edges."""
    from repro.preprocess.fused import lgamma_f32

    rng = np.random.default_rng(0)
    counts = np.arange(0, 2001, dtype=np.float64)
    xs = np.concatenate(
        [rng.uniform(1e-4, 2, 20_000), rng.uniform(0, 2000, 20_000)]
        + [counts + 1.0 / 3 ** k for k in range(6)]
        + [[0.0, -0.5, -3.0, -2.25, np.inf, -np.inf, np.nan]]
    ).astype(np.float32)
    want = np.asarray(jax.jit(jax.lax.lgamma)(xs))
    got = np.asarray(jax.jit(lgamma_f32)(xs))
    np.testing.assert_array_equal(got, want)


def test_fused_pallas_kernel_matches_ref():
    """Pallas fused count+score == jnp fused chunk (interpret mode), with the
    padded sample rows deliberately CORRUPTED in the child one-hot — the
    in-kernel mask must neutralise them."""
    rng = np.random.default_rng(5)
    n, q, s, m = 7, 3, 2, 100
    data = _rand_problem(rng, n, q, m)
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)],
                                          axis=1))
    sub, ssz = build_pst(n, s)
    lut_k, lut_j = score_luts(q, s, m, 1.0)
    child_oh = jax.nn.one_hot(data_ext[:, :n].reshape(-1), q,
                              dtype=jnp.float32).reshape(m, n * q)
    want = fused_scores_ref(data_ext, child_oh, jnp.asarray(sub),
                            jnp.asarray(ssz), lut_k, lut_j, q=q, s=s, n=n)
    block_m = 64
    pad = (-m) % block_m
    codes = encode_subset_codes(data_ext, jnp.asarray(sub), q).T
    codes_p = jnp.pad(codes, ((0, 0), (0, pad)), constant_values=-1)
    child_p = jnp.pad(child_oh, ((0, pad), (0, 0)), constant_values=1.0)
    got = fused_scores_pallas(codes_p, child_p, jnp.asarray(ssz), q=q, s=s,
                              n=n, ess=1.0, block_m=block_m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=0)


# ------------------------------------------------------------ sparse table
@pytest.fixture(scope="module")
def sparse_problem():
    rng = np.random.default_rng(7)
    n, q, s, m = 9, 2, 3, 250
    data = _rand_problem(rng, n, q, m)
    st = build_score_table(data, q=q, s=s)
    return st, prune_table(st, 15.0)


def test_sparse_prune_rule_exact(sparse_problem):
    """Kept set per node == {t : ls >= best - delta} + the empty set."""
    st, sp = sparse_problem
    tbl = np.asarray(st.table)
    best = tbl.max(axis=1)
    ki = np.asarray(sp.kept_idx)
    for i in range(sp.n):
        want = set(np.nonzero(tbl[i] >= best[i] - sp.delta)[0]) | {0}
        got = set(ki[i][ki[i] >= 0].tolist())
        assert got == want


def test_sparse_lookup_matches_dense_on_kept(sparse_problem):
    """Open-addressing lookup returns the exact dense score for every kept
    entry and NEG_INF for pruned ones; works under jit/vmap."""
    st, sp = sparse_problem
    tbl = np.asarray(st.table)
    ki = np.asarray(sp.kept_idx)
    for i in range(sp.n):
        idxs = ki[i][ki[i] >= 0]
        got = np.asarray(sp.lookup(np.full(len(idxs), i), idxs))
        np.testing.assert_array_equal(got, tbl[i, idxs])
        pruned = np.setdiff1d(np.arange(sp.S), idxs)[:50]
        if len(pruned):
            miss = np.asarray(sp.lookup(np.full(len(pruned), i), pruned))
            assert (miss < -1e38).all()
    # jit + vmap usability (the hot-path claim)
    f = jax.jit(jax.vmap(sp.lookup))
    nodes = jnp.asarray([0, 1, 2], jnp.int32)
    idxs = jnp.asarray([0, 0, 0], jnp.int32)
    np.testing.assert_array_equal(np.asarray(f(nodes, idxs)), tbl[:3, 0])


def test_sparse_dense_fallback_exact(sparse_problem):
    """to_dense(): bitwise-equal on kept entries, NEG_INF elsewhere."""
    st, sp = sparse_problem
    dense = np.asarray(sp.table)
    tbl = np.asarray(st.table)
    keep = tbl >= (tbl.max(1)[:, None] - sp.delta)
    keep[:, 0] = True
    np.testing.assert_array_equal(dense[keep], tbl[keep])
    assert (dense[~keep] < -1e38).all()


def test_pruning_guarantee(sparse_problem):
    """Pruned order score <= dense order score, with equality whenever each
    node's dense-consistent argmax survived pruning — and always at
    delta = +inf (exhaustive keep)."""
    st, sp = sparse_problem
    n = sp.n
    table, pst = pad_table(st.table, st.pst, 64)
    sp_inf = prune_table(st, 1e9)
    tbl = np.asarray(st.table)
    best = tbl.max(axis=1)
    rng = np.random.default_rng(11)
    for _ in range(10):
        pos = jnp.asarray(rng.permutation(n).astype(np.int32))
        d_tot, d_idx, d_ls = score_order_blocked(table, pst, pos, block=64)
        p_tot, p_idx, p_ls = score_order_pruned(sp.kept_ls, sp.kept_parents,
                                                sp.kept_idx, pos)
        assert float(p_tot) <= float(d_tot) + 1e-4
        if np.all(np.asarray(d_ls) >= best - sp.delta):
            assert float(p_tot) == float(d_tot)
            np.testing.assert_array_equal(np.asarray(p_idx),
                                          np.asarray(d_idx))
        i_tot, i_idx, _ = score_order_pruned(
            sp_inf.kept_ls, sp_inf.kept_parents, sp_inf.kept_idx, pos)
        assert float(i_tot) == float(d_tot)
        np.testing.assert_array_equal(np.asarray(i_idx), np.asarray(d_idx))


def test_pruned_delta_equals_full(sparse_problem):
    """Windowed incremental rescore == full pruned rescore, bitwise."""
    _, sp = sparse_problem
    n = sp.n
    rng = np.random.default_rng(13)
    kept = (sp.kept_ls, sp.kept_parents, sp.kept_idx)
    pos = jnp.asarray(rng.permutation(n).astype(np.int32))
    _, idx, ls = score_order_pruned(*kept, pos)
    for _ in range(10):
        # bounded-window perturbation: swap inside a window of 4 at lo
        lo = int(rng.integers(0, n - 3))
        a, b = lo + int(rng.integers(0, 4)), lo + int(rng.integers(0, 4))
        posn = np.asarray(pos).copy()
        ia, ib = np.nonzero(posn == a)[0][0], np.nonzero(posn == b)[0][0]
        posn[ia], posn[ib] = b, a
        posn = jnp.asarray(posn)
        want = score_order_pruned(*kept, posn)
        got = score_order_pruned_delta(*kept, posn, ls, idx,
                                       jnp.int32(lo), window=4)
        assert float(got[0]) == float(want[0])
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
        pos, idx, ls = posn, want[1], want[2]


# ---------------------------------------------------------------- planner
def test_planner_coverage_and_balance():
    """Every chunk lands on exactly one device; LPT keeps the cost imbalance
    within the classic 4/3 bound of the mean for these unit shapes."""
    sub, ssz = build_pst(20, 3)
    chunk = 64
    pad = (-len(ssz)) % chunk
    ssz_p = np.pad(ssz, (0, pad))
    for ndev in (1, 2, 3, 7):
        plan = plan_preprocess(ssz_p, chunk, m=100, q=2, n_devices=ndev)
        seen = sorted(c for b in plan.device_chunks for c in b)
        assert seen == list(range(plan.n_chunks))
        assert plan.imbalance <= 4 / 3 + 1e-9
        # padded lists all share one width and only repeat real ids
        widths = {len(p) for p in plan.padded_chunks}
        assert len(widths) == 1
        for b, p in zip(plan.device_chunks, plan.padded_chunks):
            assert set(p.tolist()) == set(b)


def test_planner_cost_model():
    """Costs follow the paper's q^{|pi|} * m estimate."""
    ssz = np.asarray([0, 1, 2, 2])
    plan = plan_preprocess(ssz, chunk=2, m=10, q=3, n_devices=1)
    np.testing.assert_allclose(plan.costs, [(1 + 3) * 10, (9 + 9) * 10])


# ------------------------------------------------------------------ cache
def test_cache_roundtrip_and_key_sensitivity(tmp_path):
    rng = np.random.default_rng(17)
    n, q, s, m = 7, 2, 2, 90
    data = _rand_problem(rng, n, q, m)
    d = str(tmp_path)
    st1, i1 = build_score_table_fused(data, q=q, s=s, cache_dir=d,
                                      return_info=True)
    st2, i2 = build_score_table_fused(data, q=q, s=s, cache_dir=d,
                                      return_info=True)
    assert not i1["cache_hit"] and i2["cache_hit"]
    np.testing.assert_array_equal(np.asarray(st1.table), np.asarray(st2.table))
    np.testing.assert_array_equal(np.asarray(st1.pst), np.asarray(st2.pst))
    # different hyperparameters or data must MISS
    _, i3 = build_score_table_fused(data, q=q, s=s, ess=2.0, cache_dir=d,
                                    return_info=True)
    assert not i3["cache_hit"]
    data2 = data.copy()
    data2[0, 0] ^= 1
    _, i4 = build_score_table_fused(data2, q=q, s=s, cache_dir=d,
                                    return_info=True)
    assert not i4["cache_hit"]
    # pruning reuses the dense cache entry
    sp, i5 = build_score_table_fused(data, q=q, s=s, prune_delta=5.0,
                                     cache_dir=d, return_info=True)
    assert i5["cache_hit"] and isinstance(sp, SparseScoreTable)


# ------------------------------------------------- end-to-end via bn_learn
def test_learn_structure_fused_sparse_end_to_end(tmp_path):
    """preprocess -> MCMC -> adjacency through the driver, fused + pruned +
    cached; the second run must hit the preprocessing cache."""
    from repro.launch.bn_learn import LearnConfig, learn_structure

    rng = np.random.default_rng(19)
    from repro.core import random_cpts, random_dag
    from repro.data import ancestral_sample
    adj = random_dag(rng, 8, 2, 0.4)
    cpts = random_cpts(rng, adj, 2)
    data = ancestral_sample(rng, adj, cpts, 300, 2)
    cfg = LearnConfig(q=2, s=2, iters=60, seed=1, window=4,
                      preprocess="fused", prune_delta=25.0,
                      cache_dir=str(tmp_path))
    out1 = learn_structure(data, cfg)
    assert out1["adjacency"].shape == (8, 8)
    assert not out1["preprocess_cache_hit"]
    out2 = learn_structure(data, cfg)
    assert out2["preprocess_cache_hit"]
    assert out1["score"] == out2["score"]
