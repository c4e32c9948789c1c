"""The dense assembly's device rank map against the host oracle.

``pipeline._rank_map`` builds the (n, S) map on the device in int32; the
oracle is the per-node loop over ``core/combinatorics.rank_combinations_batch``
(int64, numpy), written out here. Ranks and the tables built from them must
agree bitwise.
"""
import numpy as np
import jax
import pytest

from repro.core.combinatorics import build_pst, rank_combinations_batch
from repro.preprocess import build_score_table_fused
from repro.preprocess import pipeline as pl


def _oracle_rank_map(n, s, pst, psizes):
    out = np.empty((n, pst.shape[0]), np.int32)
    for i in range(n):
        cols = pst + (pst >= i)
        cols = np.where(pst < 0, -1, cols)
        out[i] = rank_combinations_batch(n, s, cols, psizes)
    return out


@pytest.mark.parametrize("n,s", [(2, 1), (5, 4), (9, 3), (16, 2), (37, 4)])
def test_device_rank_map_equals_host_oracle(n, s):
    pst, psizes = build_pst(n - 1, s)
    got = pl._rank_map(n, s, pst, psizes)
    assert isinstance(got, jax.Array)
    assert got.dtype == np.int32 and got.shape == (n, pst.shape[0])
    np.testing.assert_array_equal(np.asarray(got),
                                  _oracle_rank_map(n, s, pst, psizes))


@pytest.mark.parametrize("n,s", [(200, 4), (60, 12)])
def test_rank_map_refuses_shapes_past_int32(n, s):
    """C(201, 5) = 2,600,334,990 and C(61, 13) pass 2**31: the map raises
    before it computes anything (a stand-in PST suffices)."""
    pst = np.zeros((3, s), np.int32)
    with pytest.raises(ValueError, match=r"2\*\*31"):
        pl._rank_map(n, s, pst, np.zeros(3, np.int32))


def test_dense_table_equals_table_from_oracle_map(monkeypatch):
    """A dense build is bitwise the table assemble_table gives from the same
    TI with the host oracle's rank map."""
    rng = np.random.default_rng(23)
    n, q, s, m = 11, 3, 3, 160
    data = rng.integers(0, q, size=(m, n)).astype(np.int32)
    got = np.asarray(build_score_table_fused(data, q=q, s=s).table)
    monkeypatch.setattr(pl, "_rank_map", _oracle_rank_map)
    want = np.asarray(build_score_table_fused(data, q=q, s=s).table)
    np.testing.assert_array_equal(got, want)
