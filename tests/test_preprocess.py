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
                              plan_preprocess, plan_subsets, prune_table)
from repro.preprocess.fused import (child_columns, encode_subset_codes,
                                    fused_scores_pallas, fused_scores_ref,
                                    score_luts)


def _rand_problem(rng, n, q, m):
    """(m, n) states: q is one arity, or one per column."""
    return rng.integers(0, np.asarray(q), size=(m, n)).astype(np.int32)


# seeded mixed-arity problems: (n, arities drawn from 2..4, s, m), m not a
# multiple of the 64-sample kernel block
MIXED = [(6, 2, 77, 0), (7, 3, 101, 1), (9, 2, 150, 2), (8, 3, 130, 3)]


def _mixed_problem(n, s, m, seed):
    rng = np.random.default_rng(seed)
    r = rng.integers(2, 5, size=n)
    return _rand_problem(rng, n, r, m), tuple(int(v) for v in r)


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


@pytest.mark.parametrize("q", [3, (3, 2, 4, 2, 3, 4, 2)])
def test_fused_pallas_kernel_matches_ref(q):
    """Pallas fused count+score == jnp fused chunk (interpret mode), with the
    padded sample rows deliberately CORRUPTED in the child one-hot — the
    in-kernel mask must neutralise them — at one arity and at mixed
    arities."""
    rng = np.random.default_rng(5)
    n, s, m = 7, 2, 100
    data = _rand_problem(rng, n, q, m)
    r = np.broadcast_to(np.asarray(q), (n,)).astype(np.int32)
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)],
                                          axis=1))
    sub, _ = build_pst(n, s)
    lay = plan_subsets(sub, r, len(sub), m, 1)
    Q = max(Q for Q, _, _ in lay.buckets)
    subs, qsig = jnp.asarray(lay.sub), jnp.asarray(lay.qsig)
    want = fused_scores_ref(data_ext, jnp.asarray(r), subs, qsig,
                            score_luts(lay.qsig, r, m, 1.0), Q=Q,
                            r_max=int(r.max()))
    col_child = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), r))
    state, col_r = child_columns(jnp.asarray(r), col_child)
    child_oh = (data_ext[:, col_child] == state[None, :]).astype(jnp.float32)
    sum_mat = (col_child[:, None] == jnp.arange(n)[None, :]).astype(
        jnp.float32)
    block_m = 64
    pad = (-m) % block_m
    arity_ext = jnp.asarray(np.append(r, 1))
    codes = encode_subset_codes(data_ext, subs, arity_ext).T
    codes_p = jnp.pad(codes, ((0, 0), (0, pad)), constant_values=-1)
    child_p = jnp.pad(child_oh, ((0, pad), (0, 0)), constant_values=1.0)
    got = fused_scores_pallas(codes_p, child_p, qsig, col_r[None, :], sum_mat,
                              Q=Q, ess=1.0, block_m=block_m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("n,s,m,seed", MIXED)
def test_fused_mixed_arity_matches_oracle(n, s, m, seed):
    """At arities drawn from 2..4, the fused table (jnp path, and the
    Pallas kernel in interpret mode) equals the core/scores oracle: bitwise
    on the jnp path, within the kernel gate on the Pallas one."""
    data, r = _mixed_problem(n, s, m, seed)
    want = np.asarray(build_score_table(data, q=r, s=s).table)
    got = np.asarray(build_score_table_fused(data, q=r, s=s).table)
    np.testing.assert_array_equal(got, want)
    kern = np.asarray(build_score_table_fused(data, q=r, s=s, use_pallas=True,
                                              block_m=64).table)
    np.testing.assert_allclose(kern, want, atol=1e-4, rtol=0)


def _uniform_ls_chunk(data_ext, node, pst_chunk, psize_chunk, *, q, s,
                      log_gamma, ess):
    """The one-arity oracle as it read before arities could differ: codes in
    base q, bins active by their digits, alpha from q**k. Kept to pin the
    one-arity tables bitwise to it."""
    from jax.scipy.special import gammaln
    n = data_ext.shape[1] - 1
    pcols = pst_chunk + (pst_chunk >= node)
    pcols = jnp.where(pst_chunk < 0, n, pcols)
    code = jnp.sum(data_ext[:, pcols] * q ** jnp.arange(s), axis=-1)
    counts = jnp.einsum("mcQ,mj->cQj", jax.nn.one_hot(code, q ** s),
                        jax.nn.one_hot(data_ext[:, node], q))
    k = psize_chunk.astype(jnp.float32)
    r = jnp.power(float(q), k)
    b = np.arange(q ** s)
    digits = jnp.asarray(np.stack([(b // q ** j) % q for j in range(s)], -1))
    pad_pos = jnp.arange(s)[None, :] >= psize_chunk[:, None]
    active = jnp.all(jnp.where(pad_pos[:, None, :], digits[None] == 0, True),
                     axis=-1)
    a_k = (ess / r)[:, None]
    a_jk = (ess / (r * q))[:, None, None]
    terms = active * (gammaln(a_k) - gammaln(a_k + counts.sum(-1))
                      + (gammaln(counts + a_jk) - gammaln(a_jk)).sum(-1))
    acc = terms[:, 0]
    for t in range(1, terms.shape[1]):
        acc = acc + terms[:, t]
    return k * log_gamma + acc


@pytest.mark.parametrize("q,s", [(2, 3), (3, 2), (4, 2)])
def test_uniform_arity_tables_bitwise_unchanged(q, s):
    """One arity for every variable scores exactly as before arities could
    differ: the oracle, the fused jnp path and q given as a full vector are
    all bitwise the one-arity formula."""
    rng = np.random.default_rng(10 + q)
    n, m = 7, 111
    data = _rand_problem(rng, n, q, m)
    pst, psizes = build_pst(n - 1, s)
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)],
                                          axis=1))
    f = jax.jit(_uniform_ls_chunk, static_argnames=("q", "s", "log_gamma",
                                                    "ess"))
    want = np.stack([np.asarray(f(data_ext, jnp.int32(i), jnp.asarray(pst),
                                  jnp.asarray(psizes), q=q, s=s,
                                  log_gamma=float(np.log(0.1)), ess=1.0))
                     for i in range(n)])
    for got in (build_score_table(data, q=q, s=s).table,
                build_score_table_fused(data, q=q, s=s).table,
                build_score_table_fused(data, q=[q] * n, s=s).table):
        np.testing.assert_array_equal(np.asarray(got), want)


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
        plan = plan_preprocess(2 ** ssz_p, chunk, m=100, n_devices=ndev)
        seen = sorted(c for b in plan.device_chunks for c in b)
        assert seen == list(range(plan.n_chunks))
        assert plan.imbalance <= 4 / 3 + 1e-9
        # padded lists all share one width and only repeat real ids
        widths = {len(p) for p in plan.padded_chunks}
        assert len(widths) == 1
        for b, p in zip(plan.device_chunks, plan.padded_chunks):
            assert set(p.tolist()) == set(b)


def test_planner_cost_model():
    """Costs follow the paper's q_pi * m estimate."""
    ssz = np.asarray([0, 1, 2, 2])
    plan = plan_preprocess(3 ** ssz, chunk=2, m=10, n_devices=1)
    np.testing.assert_allclose(plan.costs, [(1 + 3) * 10, (9 + 9) * 10])


@pytest.mark.parametrize("q", [3, (2, 3, 4, 2, 3, 4, 2, 3, 3, 4, 2)])
def test_subset_plan_buckets(q):
    """Every column subset lands in exactly one row, in a bucket whose Q is
    at least its q_sigma; at most MAX_BUCKETS buckets; the bin counts add
    up; at one arity the rows keep build_pst order."""
    from repro.preprocess.planner import MAX_BUCKETS
    n, s, chunk = 11, 3, 32
    r = np.broadcast_to(np.asarray(q), (n,)).astype(np.int32)
    sub, ssz = build_pst(n, s)
    lay = plan_subsets(sub, r, chunk, m=50, n_devices=2)
    assert len(lay.buckets) <= MAX_BUCKETS
    real = lay.row >= 0
    assert sorted(lay.row[real].tolist()) == list(range(len(sub)))
    np.testing.assert_array_equal(lay.sub[real], sub[lay.row[real]])
    qsig = np.prod(np.where(sub < 0, 1, r[np.maximum(sub, 0)]), axis=1)
    np.testing.assert_array_equal(lay.qsig[real], qsig[lay.row[real]])
    covered = []
    for Q, first, plan in lay.buckets:
        ids = sorted(c for b in plan.device_chunks for c in b)
        assert ids == list(range(plan.n_chunks))
        rows = slice(first * chunk, (first + plan.n_chunks) * chunk)
        assert lay.qsig[rows].max() <= Q
        covered += list(range(first, first + plan.n_chunks))
    assert covered == list(range(lay.n_chunks))
    assert lay.bins_real == int(qsig.sum())
    assert lay.bins_computed == sum(Q * p.n_chunks * chunk
                                    for Q, _, p in lay.buckets)
    if np.ndim(q) == 0:
        assert (lay.row[real] == np.arange(len(sub))).all()


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


def test_cache_keys_on_arities(tmp_path):
    """The arity vector is in the key and the manifest: one arity given as
    an int or as a full vector is the same problem, a different vector is
    a miss, and an entry is never served to a request with other arities
    even under its key."""
    from repro.preprocess.cache import cache_key, load_cached_table
    rng = np.random.default_rng(18)
    n, s, m = 6, 2, 80
    data = _rand_problem(rng, n, 2, m)
    mixed = [2] * (n - 1) + [3]
    d = str(tmp_path)
    kw = dict(s=s, gamma=0.1, ess=1.0)
    assert cache_key(data, q=2, **kw) == cache_key(data, q=[2] * n, **kw)
    assert cache_key(data, q=2, **kw) != cache_key(data, q=mixed, **kw)
    _, i1 = build_score_table_fused(data, q=2, s=s, cache_dir=d,
                                    return_info=True)
    _, i2 = build_score_table_fused(data, q=[2] * n, s=s, cache_dir=d,
                                    return_info=True)
    st3, i3 = build_score_table_fused(data, q=mixed, s=s, cache_dir=d,
                                      return_info=True)
    assert (i1["cache_hit"], i2["cache_hit"], i3["cache_hit"]) == (
        False, True, False)
    want = build_score_table(data, q=mixed, s=s).table
    np.testing.assert_array_equal(np.asarray(st3.table), np.asarray(want))
    expect = {"arity": mixed, "s": s, "m": m, "n": n, "gamma": 0.1,
              "ess": 1.0}
    assert load_cached_table(d, cache_key(data, q=2, **kw),
                             expect=expect) is None


def test_state_outside_arity_rejected():
    data = np.zeros((10, 3), np.int32)
    data[4, 2] = 2
    build_score_table_fused(data, q=[2, 2, 3], s=1)
    with pytest.raises(ValueError, match="column 2"):
        build_score_table_fused(data, q=2, s=1)
    with pytest.raises(ValueError, match="3 arities given for 4"):
        build_score_table(np.zeros((5, 4), np.int32), q=[2, 2, 2], s=1)


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
