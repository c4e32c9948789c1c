"""The BGe score for continuous data: the fused table build (jnp path and
the Pallas kernel in interpret mode) against the float64 oracle, score
equivalence, streaming-pruned against dense + prune, the order search
against exhaustive enumeration, the normal path's entry points (learner,
CLI, service job), and the disk-cache keys of the two score kinds.

Tolerances. The fused build works in float32: the moments, the Cholesky of
R_σσ and the Schur complement R_ii − R_iσ R_σσ⁻¹ R_σi, whose subtraction
loses log2(R_ii / Schur) bits, and the Schur log is weighted by ~N/2. At
these sizes (m ≤ 400, standardized columns) that leaves errors of a few
1e-6 of a node's largest |score|; the checks allow 1e-4 of it (TABLE_TOL).
The oracle is float64 numpy throughout.
"""
import itertools
import math

import numpy as np
import pytest

from repro.core.combinatorics import (build_pst, nodes_to_candidates,
                                      rank_parent_set)
from repro.core.graph import random_dag_arcs
from repro.core.scores import bge_score_single, build_score_table
from repro.data.bn_sampler import linear_gaussian_sample
from repro.data.networks import network_data
from repro.preprocess import SparseScoreTable, build_score_table_fused
from repro.preprocess.cache import cache_key

TABLE_TOL = 1e-4      # of a node's largest |score| (module docstring)
ORACLE_TOL = 1e-9     # float64 sums of a few log-determinants


def _sem(seed, n=8, arcs=10, m=300):
    rng = np.random.default_rng(seed)
    adj = random_dag_arcs(rng, n, arcs, max_parents=3)
    return adj, linear_gaussian_sample(rng, adj, m)


def _row_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float((np.abs(got - want) / np.abs(want).max(1, keepdims=True))
                 .max())


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_bge_table_matches_float64_oracle(use_pallas):
    """n = 8, s = 3: every entry of the fused table (jnp path, or the Pallas
    kernel in interpret mode) against the reference table, and sampled
    entries of that against the scalar oracle's two full slogdets."""
    _, x = _sem(4)
    n, s = x.shape[1], 3
    ref = build_score_table(x, q=None, s=s, score="bge")
    st = build_score_table_fused(x, q=None, s=s, score="bge", chunk=64,
                                 use_pallas=use_pallas, interpret=True)
    assert st.table.shape == ref.table.shape == (n, 64)
    np.testing.assert_array_equal(np.asarray(st.pst), np.asarray(ref.pst))
    assert _row_gap(st.table, ref.table) < TABLE_TOL
    pst, _ = build_pst(n - 1, s)
    for i, t in [(0, 0), (3, 17), (7, 63), (5, 40)]:
        par = [c + (c >= i) for c in pst[t] if c >= 0]
        want = bge_score_single(x, i, par)
        assert abs(float(ref.table[i, t]) - want) <= 1e-6 * abs(want)


def test_bge_hyperparameters_reach_the_table():
    _, x = _sem(5, m=200)
    a = build_score_table_fused(x, q=None, s=2, score="bge")
    b = build_score_table_fused(x, q=None, s=2, score="bge",
                                bge_alpha_mu=3.0, bge_alpha_w=20.0)
    ref = build_score_table(x, q=None, s=2, score="bge", bge_alpha_mu=3.0,
                            bge_alpha_w=20.0)
    assert _row_gap(b.table, ref.table) < TABLE_TOL
    assert _row_gap(a.table, ref.table) > 1e-3
    with pytest.raises(ValueError, match="exceed"):
        build_score_table_fused(x, q=None, s=2, score="bge", bge_alpha_w=9)


def _covered_edges(adj):
    """Edges x -> y with Pa(y) = Pa(x) + {x}: reversing one keeps the
    graph's equivalence class."""
    return [(a, b) for a, b in zip(*np.nonzero(adj))
            if set(np.nonzero(adj[:, b])[0]) ==
            set(np.nonzero(adj[:, a])[0]) | {a}]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_covered_edge_reversal_keeps_the_bge_score(seed):
    """BGe is score-equivalent: reversing a covered edge leaves the summed
    float64 oracle score equal to 1e-9 of it, and the fused table's sum
    within TABLE_TOL of the largest |score| summed."""
    adj, x = _sem(seed, n=7, arcs=9, m=400)
    n, s = 7, 3
    edges = _covered_edges(adj)
    assert edges, "the seeded DAG has no covered edge"
    st = build_score_table_fused(x, q=None, s=s, score="bge")
    table = np.asarray(st.table, np.float64)

    def sums(g):
        exact = fused = 0.0
        for i in range(n):
            par = np.nonzero(g[:, i])[0]
            exact += bge_score_single(x, i, par)
            fused += table[i, rank_parent_set(
                n - 1, s, nodes_to_candidates(par, i))]
        return exact, fused

    base = sums(adj)
    scale = np.abs(table).max(1).sum()
    for a, b in edges:
        rev = adj.copy()
        rev[a, b], rev[b, a] = 0, 1
        got = sums(rev)
        assert abs(got[0] - base[0]) <= ORACLE_TOL * abs(base[0])
        assert abs(got[1] - base[1]) <= TABLE_TOL * scale
    # a non-covered reversal changes the score (the check has teeth)
    other = [(a, b) for a, b in zip(*np.nonzero(adj))
             if (a, b) not in edges]
    if other:
        a, b = other[0]
        rev = adj.copy()
        rev[a, b], rev[b, a] = 0, 1
        assert abs(sums(rev)[0] - base[0]) > 1e-3


@pytest.mark.parametrize("delta,chunk", [(3.0, 16), (20.0, 37), (1e30, 64)])
def test_bge_streaming_equals_dense_prune(delta, chunk):
    """The streaming-pruned BGe build equals the dense build + prune in
    every stored array, as tests/test_streaming.py pins for BDeu."""
    _, x = _sem(6, n=9, arcs=12, m=150)
    dense = build_score_table_fused(x, q=None, s=3, score="bge", chunk=chunk,
                                    prune_delta=delta, streaming=False)
    stream, info = build_score_table_fused(x, q=None, s=3, score="bge",
                                           chunk=chunk, prune_delta=delta,
                                           return_info=True)
    assert isinstance(stream, SparseScoreTable) and info["streaming"]
    assert info["score"] == "bge" and info["bins_real"] is None
    for f in ("kept_idx", "kept_ls", "kept_parents", "keys", "vals"):
        np.testing.assert_array_equal(np.asarray(getattr(dense, f)),
                                      np.asarray(getattr(stream, f)), f)
    assert dense.K == stream.K


def _exhaustive_best(table, n, s):
    pst, _ = build_pst(n - 1, s)
    best = -np.inf
    for order in itertools.permutations(range(n)):
        pos = np.empty(n, int)
        pos[list(order)] = np.arange(n)
        total = 0.0
        for i in range(n):
            par = np.where(pst >= 0, pst + (pst >= i), -1)
            ok = np.all((par < 0) | (pos[np.maximum(par, 0)] < pos[i]), 1)
            total += table[i][ok].max()
        best = max(best, total)
    return best


def test_learn_structure_bge_finds_the_exhaustive_best_order():
    """n = 5: the order-MCMC's best order score equals the best over all
    120 orders of the float64 reference table, to float32 rounding."""
    from repro.learner import LearnConfig, learn_structure

    _, x = _sem(7, n=5, arcs=6, m=300)
    s = 2
    table = np.asarray(build_score_table(x, q=None, s=s, score="bge").table,
                       np.float64)
    want = _exhaustive_best(table, 5, s)
    out = learn_structure(x, LearnConfig(score="bge", s=s, iters=400,
                                         chains=2, seed=1, window=0,
                                         preprocess="fused"))
    assert abs(out["score"] - want) <= TABLE_TOL * np.abs(table).max(1).sum()


def test_bge_through_the_auto_pruned_default_and_the_cli(monkeypatch):
    """prepare_run picks the streaming-pruned build above AUTO_PRUNE_S for
    BGe as for BDeu; bn_learn --score bge learns from continuous samples."""
    from repro import learner as bl
    from repro.launch import bn_learn

    _, x = _sem(8, n=8, arcs=9, m=200)
    monkeypatch.setattr(bl, "AUTO_PRUNE_S", 10)
    out = bl.learn_structure(x, bl.LearnConfig(score="bge", s=2, iters=40,
                                               seed=2, window=4,
                                               preprocess="fused"))
    assert out["auto_pruned"] and out["adjacency"].shape == (8, 8)
    assert math.isfinite(out["score"])
    res = bn_learn.main(["--network", "synth", "--n", "6", "--arcs", "7",
                         "--score", "bge", "--s", "2", "--iters", "40",
                         "--window", "4",
                         "--samples", "200", "--preprocess", "fused",
                         "--cache-dir", ""])
    assert res["adjacency"].shape == (6, 6) and math.isfinite(res["score"])


def test_bge_service_job(tmp_path):
    """A service job whose config asks for BGe draws continuous data and
    learns through prepare_run; its admission key differs from BDeu's."""
    from repro.service import (DatasetSpec, FleetScheduler, JobManager,
                               admission_key, load_dataset, service_config)

    cfg = service_config(dict(score="bge", s=2, iters=60, chains=2,
                              check_every=20, trace_every=10, window=4,
                              stop_on_converge=False))
    data = load_dataset(DatasetSpec(network="synth", n=6, arcs=7, m=200,
                                    seed=3), cfg.q, score=cfg.score)
    assert data.dtype == np.float64 and np.allclose(data.std(0), 1.0)
    states = load_dataset(DatasetSpec(network="synth", n=6, m=200, seed=3),
                          cfg.q)
    assert admission_key(states, service_config(dict(s=2))) != \
        admission_key(states.astype(np.float64), cfg)
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=4)
    job, _ = sched.submit(data, cfg)
    sched.run()
    assert job.state == "done", job.error
    assert job.result["adjacency"].shape == (6, 6)


def test_sampler_draws_what_bn_learn_draws():
    adj, x = network_data("synth", 150, 2, 11, n_synth=12, arcs=20,
                          score="bge")
    assert adj.sum() == 20 and adj.sum(0).max() <= 4
    assert x.shape == (150, 12) and x.dtype == np.float64
    np.testing.assert_allclose(x.mean(0), 0.0, atol=1e-12)
    np.testing.assert_allclose(x.std(0), 1.0)
    with pytest.raises(ValueError, match="do not fit"):
        random_dag_arcs(np.random.default_rng(0), 5, 11, max_parents=4)


def test_cache_keys_separate_score_kinds_and_float_data():
    """BDeu and BGe never share a key; two continuous datasets with equal
    integer parts never do; BDeu refuses non-integer data instead of
    truncating it."""
    rng = np.random.default_rng(9)
    states = rng.integers(0, 3, (50, 5))
    x = states + rng.uniform(0.05, 0.95, states.shape)
    y = states + rng.uniform(0.05, 0.95, states.shape)
    kw = dict(q=3, s=2, gamma=0.1, ess=1.0)
    bdeu = cache_key(states, **kw)
    assert bdeu == cache_key(states.astype(np.float64), **kw)
    bge = {cache_key(d, score="bge", **kw) for d in (states, x, y)}
    assert len(bge) == 3 and bdeu not in bge
    assert cache_key(x, score="bge", **kw) != \
        cache_key(x, score="bge", bge_alpha_mu=2.0, **kw)
    assert cache_key(x, score="bge", **kw) == \
        cache_key(x, score="bge", bge_alpha_w=7.0, **kw)   # n + 2, the default
    for call in (lambda: cache_key(x, **kw),
                 lambda: build_score_table_fused(x, q=3, s=2),
                 lambda: build_score_table(x, q=3, s=2)):
        with pytest.raises(ValueError, match="not integers"):
            call()


def test_bge_cache_roundtrip(tmp_path):
    _, x = _sem(10, n=6, arcs=6, m=120)
    a, ia = build_score_table_fused(x, q=None, s=2, score="bge",
                                    cache_dir=str(tmp_path),
                                    return_info=True)
    b, ib = build_score_table_fused(x, q=None, s=2, score="bge",
                                    cache_dir=str(tmp_path),
                                    return_info=True)
    assert not ia["cache_hit"] and ib["cache_hit"]
    assert set(ia) == set(ib)
    np.testing.assert_array_equal(np.asarray(a.table), np.asarray(b.table))
    _, ic = build_score_table_fused(x, q=None, s=2, score="bge",
                                    bge_alpha_mu=2.0,
                                    cache_dir=str(tmp_path),
                                    return_info=True)
    assert not ic["cache_hit"]
