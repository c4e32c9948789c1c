"""Streaming pruned assembly (preprocess/streaming.py): bitwise equality
against the dense build-then-prune oracle, the no-dense-intermediate memory
guarantee, the info-schema contract, and the pruned/verified disk cache."""
import json
import os
import tracemalloc

import numpy as np
import pytest
from _propcheck import given, hst, settings

from repro.core.combinatorics import n_parent_sets
from repro.preprocess import (SparseScoreTable, build_score_table_fused,
                              build_sparse_table_streaming, prune_table)


def _rand_problem(rng, n, q, m):
    return rng.integers(0, q, size=(m, n)).astype(np.int32)


def _assert_tables_bitwise(sp_a, sp_b):
    """Every stored array identical: kept sets, packed lists, hash arrays."""
    for field in ("kept_idx", "kept_ls", "kept_parents", "keys", "vals"):
        a, b = np.asarray(getattr(sp_a, field)), np.asarray(getattr(sp_b, field))
        np.testing.assert_array_equal(a, b, err_msg=field)
    assert sp_a.max_probe == sp_b.max_probe
    assert sp_a.S == sp_b.S and sp_a.K == sp_b.K


# --------------------------------------------- streaming == dense + prune
@given(hst.data())
@settings(max_examples=6, deadline=None)
def test_streaming_matches_dense_prune_property(data_strategy):
    """Property (ISSUE 6): streaming assembly == dense-build-then-prune,
    BITWISE, over random (n, q, s, delta, chunk) — including chunk sizes
    that do not divide the subset count."""
    rng = np.random.default_rng(data_strategy.draw(hst.integers(0, 2**31 - 1)))
    n = data_strategy.draw(hst.integers(6, 11))
    q = data_strategy.draw(hst.integers(2, 4))
    s = data_strategy.draw(hst.integers(1, 3))
    m = data_strategy.draw(hst.integers(40, 150))
    deltas = [1.0, 5.0, 12.0, 1e30]
    delta = deltas[data_strategy.draw(hst.integers(0, len(deltas) - 1))]
    chunk = data_strategy.draw(hst.integers(3, 40))
    data = _rand_problem(rng, n, q, m)
    sp_dense = build_score_table_fused(data, q=q, s=s, chunk=chunk,
                                       prune_delta=delta, streaming=False)
    sp_stream = build_score_table_fused(data, q=q, s=s, chunk=chunk,
                                        prune_delta=delta)
    assert isinstance(sp_stream, SparseScoreTable)
    _assert_tables_bitwise(sp_dense, sp_stream)


@pytest.mark.parametrize("q", [2, (2, 3, 4, 3, 2, 4, 2, 3, 4)])
def test_streaming_matches_with_prior(q):
    """Bitwise streaming == dense + prune with a prior, at one arity and at
    arities 2..4 (chunks then straddle several bin-count buckets)."""
    rng = np.random.default_rng(11)
    n, s, m = 9, 3, 120
    data = rng.integers(0, np.asarray(q), size=(m, n)).astype(np.int32)
    R = np.full((n, n), 0.5, np.float32)
    R[1, 0] = 0.95
    R[4, 2] = 0.1
    sp_dense = build_score_table_fused(data, q=q, s=s, chunk=33,
                                       prior_matrix=R, prune_delta=8.0,
                                       streaming=False)
    sp_stream = build_score_table_fused(data, q=q, s=s, chunk=33,
                                        prior_matrix=R, prune_delta=8.0)
    _assert_tables_bitwise(sp_dense, sp_stream)


def test_streaming_max_keep_cap():
    """max_keep keeps each node's top-K by score (rank 0 always included);
    capped lists are a subset of the uncapped within-delta lists."""
    rng = np.random.default_rng(13)
    n, q, s, m = 8, 2, 2, 90
    data = _rand_problem(rng, n, q, m)
    full = build_score_table_fused(data, q=q, s=s, prune_delta=1e30)
    capped = build_score_table_fused(data, q=q, s=s, prune_delta=1e30,
                                     max_keep=4)
    assert capped.K <= 4 + 1                      # +1: forced rank 0
    fi, fl = np.asarray(full.kept_idx), np.asarray(full.kept_ls)
    ci, cl = np.asarray(capped.kept_idx), np.asarray(capped.kept_ls)
    for i in range(n):
        fmap = dict(zip(fi[i][fi[i] >= 0].tolist(),
                        fl[i][fi[i] >= 0].tolist()))
        kept = ci[i][ci[i] >= 0]
        assert 0 in kept.tolist()
        # capped scores are the dense scores, and (excluding the forced
        # rank 0, which sits outside the cap) they are the top non-empty ones
        scores = sorted((v for t, v in fmap.items() if t != 0), reverse=True)
        floor = scores[:4][-1]
        for t, v in zip(ci[i].tolist(), cl[i].tolist()):
            if t >= 0:
                assert fmap[t] == v
                if t != 0:
                    assert v >= floor


# ------------------------------------------------ no dense intermediate
def test_streaming_never_materialises_dense(monkeypatch):
    """The streaming path must not touch the dense assembly machinery at all
    and must keep peak host allocation well under the (n, S) table bytes."""
    from repro.preprocess import pipeline as pl

    def _boom(*a, **k):
        raise AssertionError("dense assembly invoked on the streaming path")

    monkeypatch.setattr(pl, "_rank_map", _boom)
    monkeypatch.setattr(pl, "assemble_table", _boom)

    rng = np.random.default_rng(17)
    n, q, s, m, chunk, delta = 64, 2, 3, 60, 512, 6.0
    data = _rand_problem(rng, n, q, m)
    S = n_parent_sets(n - 1, s)
    dense_bytes = n * S * 4
    # warm the jit caches outside the traced window: tracing/compilation
    # allocates MBs of Python-side jaxpr/MLIR state that has nothing to do
    # with the assembly (the trace is keyed on the static n, so warm at
    # full shape)
    build_score_table_fused(data, q=q, s=s, chunk=chunk, prune_delta=delta)
    tracemalloc.start()
    sp, info = build_score_table_fused(data, q=q, s=s, chunk=chunk,
                                       prune_delta=delta, return_info=True)
    _, traced_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert isinstance(sp, SparseScoreTable)
    assert info["streaming"] is True
    # the acceptance bound: < 25% of the dense table's n*S*4 bytes, on both
    # the self-reported assembly peak and the traced host allocations
    assert info["peak_assembly_bytes"] < 0.25 * dense_bytes, \
        (info["peak_assembly_bytes"], dense_bytes)
    assert traced_peak < 0.25 * dense_bytes, (traced_peak, dense_bytes)


def test_streaming_direct_entrypoint_info():
    rng = np.random.default_rng(19)
    data = _rand_problem(rng, 8, 2, 70)
    sp, sinfo = build_sparse_table_streaming(data, q=2, s=2, delta=6.0)
    assert isinstance(sp, SparseScoreTable)
    for k in ("peak_assembly_bytes", "n_chunks", "n_devices", "imbalance",
              "kept_entries", "K"):
        assert k in sinfo
    assert sinfo["kept_entries"] >= sp.n          # rank 0 on every node


# ------------------------------------------------------- info contract
def test_info_schema_identical_on_hit_and_miss(tmp_path):
    """Satellite bugfix: the cache-hit early return used to omit 'plan'."""
    rng = np.random.default_rng(23)
    data = _rand_problem(rng, 7, 2, 80)
    d = str(tmp_path)
    _, miss = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                      return_info=True)
    _, hit = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                     return_info=True)
    assert not miss["cache_hit"] and hit["cache_hit"]
    assert set(miss) == set(hit)
    assert "plan" in hit                     # the key the bug dropped
    # and on the pruned/streaming flavor too
    _, smiss = build_score_table_fused(data, q=2, s=2, prune_delta=4.0,
                                       cache_dir=d, return_info=True)
    _, shit = build_score_table_fused(data, q=2, s=2, prune_delta=4.0,
                                      cache_dir=d, return_info=True)
    assert set(smiss) == set(shit) == set(miss)


# ------------------------------------------------------------- cache
def test_pruned_cache_roundtrip(tmp_path):
    """Streaming runs cache the pruned representation; a second identical
    request restores it bit-for-bit, and a different delta misses."""
    rng = np.random.default_rng(29)
    data = _rand_problem(rng, 8, 2, 90)
    d = str(tmp_path)
    sp1, i1 = build_score_table_fused(data, q=2, s=2, prune_delta=5.0,
                                      cache_dir=d, return_info=True)
    sp2, i2 = build_score_table_fused(data, q=2, s=2, prune_delta=5.0,
                                      cache_dir=d, return_info=True)
    assert not i1["cache_hit"] and i2["cache_hit"]
    _assert_tables_bitwise(sp1, sp2)
    # different delta -> different kept set -> must rebuild, not hit
    _, i3 = build_score_table_fused(data, q=2, s=2, prune_delta=2.0,
                                    cache_dir=d, return_info=True)
    assert not i3["cache_hit"]


def test_cache_key_prior_shape_sensitivity():
    """Satellite bugfix: the digest must separate priors with identical
    bytes but different shapes (e.g. a transposed matrix)."""
    from repro.preprocess.cache import cache_key

    rng = np.random.default_rng(31)
    data = _rand_problem(rng, 6, 2, 40)
    R = rng.random((6, 6)).astype(np.float32)
    k1 = cache_key(data, q=2, s=2, gamma=0.1, ess=1.0, prior_matrix=R)
    k2 = cache_key(data, q=2, s=2, gamma=0.1, ess=1.0,
                   prior_matrix=np.ascontiguousarray(R.T))
    flat = np.ascontiguousarray(R.reshape(4, 9))
    k3 = cache_key(data, q=2, s=2, gamma=0.1, ess=1.0, prior_matrix=flat)
    assert len({k1, k2, k3}) == 3
    # prune_delta/max_keep key the sparse entries separately
    k4 = cache_key(data, q=2, s=2, gamma=0.1, ess=1.0, prior_matrix=R,
                   prune_delta=5.0)
    k5 = cache_key(data, q=2, s=2, gamma=0.1, ess=1.0, prior_matrix=R,
                   prune_delta=5.0, max_keep=8)
    assert len({k1, k4, k5}) == 3


def test_poisoned_cache_manifest_is_logged_miss(tmp_path, caplog):
    """Satellite bugfix: an entry whose manifest disagrees with the request
    (stale/hand-mixed cache dir) must be a logged miss, never served."""
    import logging

    rng = np.random.default_rng(37)
    data = _rand_problem(rng, 7, 2, 80)
    d = str(tmp_path)
    _, i1 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                    return_info=True)
    assert not i1["cache_hit"]
    # poison: rewrite the stored manifest to claim a different problem
    entries = os.listdir(d)
    assert len(entries) == 1
    mpath = os.path.join(d, entries[0], "step_0000000000", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["metadata"]["n"] = 999
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with caplog.at_level(logging.WARNING, logger="repro.preprocess.cache"):
        st, i2 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                         return_info=True)
    assert not i2["cache_hit"]               # mismatch = miss, rebuilt
    assert st.table.shape[0] == 7            # and the rebuild is correct
    assert any("manifest mismatch" in r.message for r in caplog.records)


# -------------------------------------------------- bn_learn auto-prune
def test_bn_learn_auto_prune_switch(monkeypatch):
    """Above the size threshold the fused driver defaults to the streaming
    pruned engine; --no-auto-prune (auto_prune=False) keeps it dense."""
    from repro import learner as bl

    rng = np.random.default_rng(41)
    n, q, s, m = 10, 2, 2, 120
    data = _rand_problem(rng, n, q, m)
    # force the threshold below this problem's S so the switch triggers
    monkeypatch.setattr(bl, "AUTO_PRUNE_S", 10)
    cfg = bl.LearnConfig(q=q, s=s, iters=30, seed=3, window=4,
                         preprocess="fused")
    out = bl.learn_structure(data, cfg)
    assert out["auto_pruned"] is True
    assert out["adjacency"].shape == (n, n)
    cfg_off = bl.LearnConfig(q=q, s=s, iters=30, seed=3, window=4,
                             preprocess="fused", auto_prune=False)
    out_off = bl.learn_structure(data, cfg_off)
    assert out_off["auto_pruned"] is False


@pytest.mark.slow
def test_streaming_n100_s4_end_to_end():
    """The ISSUE 6 acceptance gate: synthetic n = 100, s = 4 learned
    end-to-end through the streaming pruned path in bounded memory."""
    from repro.learner import LearnConfig, learn_structure

    rng = np.random.default_rng(43)
    n, q, s = 100, 2, 4
    data = _rand_problem(rng, n, q, 150)
    S = n_parent_sets(n - 1, s)
    sp, info = build_score_table_fused(data, q=q, s=s, chunk=4096,
                                       prune_delta=20.0, return_info=True)
    assert isinstance(sp, SparseScoreTable)
    assert info["streaming"] is True
    assert info["peak_assembly_bytes"] < 0.25 * n * S * 4
    cfg = LearnConfig(q=q, s=s, iters=50, seed=7, window=8,
                      preprocess="fused")
    out = learn_structure(data, cfg)
    assert out["auto_pruned"] is True
    assert out["adjacency"].shape == (n, n)
