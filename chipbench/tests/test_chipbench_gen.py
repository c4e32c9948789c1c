"""The benchmark's copies of the generators draw what the program's draw,
and its plain reference agrees with the program's oracles at tiny sizes."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import gen, reference  # noqa: E402


@pytest.mark.parametrize("network,n,seed", [("alarm", 37, 3),
                                            ("synth", 60, 7),
                                            ("synth", 12, 2 ** 31 + 5)])
def test_generators_equal_the_programs(network, n, seed):
    from repro.launch.bn_learn import _network_data

    want_adj, want = _network_data(network, 300, 3, seed, n_synth=n)
    adj, data = gen.network_data(network, 300, 3, gen.dataset_rng(seed), n)
    np.testing.assert_array_equal(adj, want_adj)
    np.testing.assert_array_equal(data, want)


def test_stream_datasets_differ_and_repeat():
    a = gen.network_data("synth", 50, 3, gen.dataset_rng(9, 0), 10)[1]
    b = gen.network_data("synth", 50, 3, gen.dataset_rng(9, 1), 10)[1]
    a2 = gen.network_data("synth", 50, 3, gen.dataset_rng(9, 0), 10)[1]
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, a2)


def test_parent_sets_follow_the_programs_order():
    from repro.core.combinatorics import build_pst

    np.testing.assert_array_equal(reference.parent_sets(9, 3),
                                  build_pst(9, 3)[0])


def test_reference_table_matches_the_oracle():
    from repro.core import build_score_table

    rng = np.random.default_rng(4)
    data = rng.integers(0, 3, (120, 6)).astype(np.int32)
    want = np.asarray(build_score_table(data, q=3, s=2, gamma=0.1,
                                        ess=1.0).table)
    got = np.asarray(reference.reference_table(data, q=3, s=2, gamma=0.1,
                                               ess=1.0))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-4)
    low = np.asarray(reference.reference_table(
        data, q=3, s=2, gamma=0.1, ess=1.0, dtype=jnp.bfloat16),
        np.float32)
    assert np.max(np.abs(low - want) / np.abs(want)) > 1e-3


def test_order_best_matches_the_oracle_and_planes_round_trip():
    from repro.core.order_scoring import (build_violation_planes,
                                          score_order_ref)

    rng = np.random.default_rng(5)
    n, s = 7, 2
    psets = reference.parent_sets(n - 1, s)
    table = jnp.asarray(rng.normal(-50, 5, (n, len(psets))), jnp.float32)
    pos = jnp.asarray(rng.permutation(n), jnp.int32)
    _, want_idx, want_ls = score_order_ref(table, jnp.asarray(psets), pos)
    ls, idx, viol = reference.order_best(table, jnp.asarray(psets), pos)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(want_ls))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    pad = (-len(psets)) % 32
    planes = build_violation_planes(
        jnp.asarray(np.pad(psets, ((0, pad), (0, 0)), constant_values=-2)),
        pos)
    S = len(psets)
    np.testing.assert_array_equal(
        np.asarray(reference.unpack_counts(planes, S=S)), np.asarray(viol))
    P, W = planes.shape[1:]
    again = reference.pack_counts(viol, P=P, W=W)
    np.testing.assert_array_equal(
        np.asarray(reference.unpack_counts(again, S=S)), np.asarray(viol))
    ok = [bool(reference.consistent(jnp.asarray(psets), pos, i, idx[i]))
          for i in range(n)]
    assert all(ok)


def test_decode_graph_matches_the_programs():
    from repro.core import adjacency_from_ranks

    ranks = np.array([0, 3, 7, 12, 20, 1, 5])
    np.testing.assert_array_equal(reference.decode_graph(ranks, 2),
                                  adjacency_from_ranks(ranks, s=2))
