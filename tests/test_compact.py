"""The dense assembly's order-preserving compaction (preprocess/compact.py,
the TPU's assembly; its Pallas kernel runs in interpret mode here) against
the point gather ``TI[rank_map, node]`` it replaces there, which stays the
oracle and the assembly everywhere else. Values are only moved, so the two
must agree bitwise, -inf and signed zeros included.
"""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.combinatorics import build_pst, n_parent_sets
from repro.core.graph import random_dag_arcs
from repro.data.bn_sampler import linear_gaussian_sample
from repro.preprocess import build_score_table_fused
from repro.preprocess import pipeline as pl
from repro.preprocess.compact import (compact_passes, compact_table,
                                      drop_shifts)

# (n, s): s >= n - 1 at (2, 1) and (5, 4); one pass at (2, 1)
SHAPES = [(2, 1), (5, 4), (9, 2), (11, 3), (37, 4)]


def _ti(C, n, values, seed=5):
    rng = np.random.default_rng(seed)
    TI = rng.standard_normal((C, n)).astype(np.float32)
    if values == "inf_and_zeros":
        pick = rng.random((C, n))
        TI[pick < 0.2] = -np.inf
        TI[(pick >= 0.2) & (pick < 0.4)] = -0.0
        TI[(pick >= 0.4) & (pick < 0.6)] = 0.0
    return TI


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("values", ["gaussian", "inf_and_zeros"])
@pytest.mark.parametrize("n,s", SHAPES)
def test_compaction_equals_the_rank_map_gather(n, s, values):
    """Row i of the compaction is ``TI[rank_map[i], i]`` bitwise: with a
    penalty of -0.0 (x + -0.0 is x, bit for bit) the moved values alone,
    and with ln(0.1) the whole of ``assemble_table``."""
    sub, ssz = build_pst(n, s)
    keep = (sub != n - 1).all(1)
    pst, psizes = sub[keep], ssz[keep]
    TI = jnp.asarray(_ti(len(sub), n, values))
    rank_map = pl._rank_map(n, s, pst, psizes)
    shifts = drop_shifts(n, sub)
    moved = compact_table(TI, shifts, psizes, -0.0)
    gathered = TI[rank_map, jnp.arange(n)[:, None]]
    assert moved.shape == (n, len(pst))
    np.testing.assert_array_equal(_bits(moved), _bits(gathered))
    lg = float(np.log(0.1))
    np.testing.assert_array_equal(
        _bits(compact_table(TI, shifts, psizes, lg)),
        _bits(pl.assemble_table(TI, rank_map, psizes, lg)))


@pytest.mark.parametrize("n,s,passes", [(2, 1, 1), (37, 4, 13), (60, 4, 16),
                                        (64, 4, 16)])
def test_pass_count_is_the_bits_of_the_largest_shift(n, s, passes):
    """K = bits of C - S, the subsets that hold a given column: 13 at the
    ALARM width, 16 at n = 60 and 64."""
    C, S = n_parent_sets(n, s), n_parent_sets(n - 1, s)
    assert C - S == n_parent_sets(n - 1, s - 1)
    assert compact_passes(C, S) == passes


def test_drop_shifts_count_the_dropped_subsets_before_each_kept_one():
    n, s = 7, 3
    sub, _ = build_pst(n, s)
    got = np.asarray(drop_shifts(n, sub))
    assert got.shape[0] % 8 == 0 and got.shape[1] >= len(sub)
    assert (got[n:] == -1).all() and (got[:, len(sub):] == -1).all()
    for i in range(n):
        drop = (sub == i).any(1)
        want = np.where(drop, -1, np.cumsum(drop) - drop)
        np.testing.assert_array_equal(got[i, :len(sub)], want)


def _bdeu_mixed():
    rng = np.random.default_rng(31)
    n, m = 9, 150
    r = rng.integers(2, 5, size=n)
    data = (rng.integers(0, 1 << 20, size=(m, n)) % r).astype(np.int32)
    return data, dict(q=[int(v) for v in r])


def _bge():
    rng = np.random.default_rng(32)
    adj = random_dag_arcs(rng, 8, 10, max_parents=3)
    return linear_gaussian_sample(rng, adj, 200), dict(q=None, score="bge")


@pytest.mark.parametrize("problem", [_bdeu_mixed, _bge])
def test_compacting_build_is_bitwise_the_gather_build(problem, monkeypatch):
    """A dense build that assembles by compaction (as on the TPU) is the
    default build bitwise: BDeu at mixed arities (several q_sigma buckets,
    ``TI`` filled on the host) and BGe (one bucket, ``TI`` on the device),
    each with a structure prior; the info says which assembly ran."""
    data, kw = problem()
    n, s = data.shape[1], 3
    rng = np.random.default_rng(33)
    prior = rng.uniform(0.0, 1.0, size=(n, n))
    gather, gi = build_score_table_fused(data, s=s, chunk=64,
                                         prior_matrix=prior,
                                         return_info=True, **kw)
    monkeypatch.setattr(pl, "_compacts", lambda devices, C, S: True)
    compact, ci = build_score_table_fused(data, s=s, chunk=64,
                                          prior_matrix=prior,
                                          return_info=True, **kw)
    np.testing.assert_array_equal(_bits(compact.table), _bits(gather.table))
    assert gi["assembly"] == "gather" and gi["compact_passes"] is None
    assert ci["assembly"] == "compact"
    assert ci["compact_passes"] == compact_passes(n_parent_sets(n, s),
                                                  n_parent_sets(n - 1, s))
    if kw.get("score") != "bge":
        assert len(gi["plan"]["q_buckets"]) > 1


@pytest.mark.parametrize("platform,n,compacts", [("tpu", 64, True),
                                                 ("tpu", 71, True),
                                                 ("tpu", 72, False),
                                                 ("cpu", 64, False)])
def test_assembly_is_chosen_by_platform_and_vmem(platform, n, compacts):
    """The TPU compacts while eight rows of the C subsets fit the kernel's
    VMEM budget (to n = 71 at s = 4); past that, and off the TPU, the
    build gathers."""
    C, S = n_parent_sets(n, 4), n_parent_sets(n - 1, 4)
    assert pl._compacts([SimpleNamespace(platform=platform)], C, S) is compacts
