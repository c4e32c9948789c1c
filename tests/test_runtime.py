"""Runtime features: elastic re-meshing plans and straggler mitigation —
including the full BN path (13-leaf ChainState + telemetry trace leaves)
that the run supervisor heals through rebalance_chains."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.elastic import (accum_steps_for_batch, remesh_plan,
                                   reshard_tree)
from repro.runtime.straggler import (StragglerPolicy, best_finite_chain,
                                     rebalance_chains)


def test_remesh_plan_shrink_grows_data_axis():
    # healthy 512-chip 2-pod job
    assert remesh_plan(512, model_parallel=16, prefer_pods=2) == \
        ((2, 16, 16), ("pod", "data", "model"))
    # a pod dies: restart on 256 chips, same model parallelism
    assert remesh_plan(256, model_parallel=16) == ((16, 16), ("data", "model"))
    # odd survivor counts still factor as long as TP divides
    assert remesh_plan(192, model_parallel=16) == ((12, 16), ("data", "model"))
    with pytest.raises(ValueError):
        remesh_plan(250, model_parallel=16)


def test_accum_steps_preserve_global_batch():
    assert accum_steps_for_batch(256, 256) == 1
    assert accum_steps_for_batch(256, 128) == 2   # half the chips -> 2 steps
    with pytest.raises(ValueError):
        accum_steps_for_batch(256, 96)


def test_straggler_chain_cloning():
    from repro.core.combinatorics import build_pst, n_parent_sets
    from repro.core.mcmc import init_chain, mcmc_run
    from repro.core.order_scoring import score_order_chunked

    n, s = 8, 2
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pad = (-S) % 16
    table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=-3e38)
    pst = jnp.pad(jnp.asarray(pst), ((0, pad), (0, 0)), constant_values=-1)
    fn = functools.partial(score_order_chunked, table, pst, block=16)

    keys = jax.random.split(jax.random.key(0), 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)

    # chain 2 misses twice -> cloned from the best chain with a fresh key
    progressed = np.array([True, True, False, True])
    missed = np.zeros(4, np.int64)
    states1, missed = rebalance_chains(jax.random.key(1), states,
                                       progressed, missed,
                                       StragglerPolicy(patience=2))
    assert missed[2] == 1           # first miss: no action yet
    np.testing.assert_array_equal(np.asarray(states1.pos),
                                  np.asarray(states.pos))

    states2, missed = rebalance_chains(jax.random.key(2), states1,
                                       progressed, missed,
                                       StragglerPolicy(patience=2))
    assert missed[2] == 0           # re-seeded
    best = int(np.argmax(np.asarray(states.best_score)))
    np.testing.assert_array_equal(np.asarray(states2.pos[2]),
                                  np.asarray(states.pos[best]))
    # fresh key: the clone diverges from its source immediately
    assert not np.array_equal(
        np.asarray(jax.random.key_data(states2.key[2])),
        np.asarray(jax.random.key_data(states2.key[best])))
    # cloned chain keeps sampling fine
    st, _ = mcmc_run(states2.key[2], n, fn, 10)
    assert np.isfinite(float(st.best_score))


# ------------------------------------------------------- full BN-path heal
def _bitmask_problem():
    """Padded dense problem with the full bitmask engine closures — the
    exact per-chain state layout bn_learn's supervised path heals through
    rebalance_chains (13 ChainState leaves incl. live mask_planes)."""
    from repro.core.combinatorics import build_pst, n_parent_sets
    from repro.core.order_scoring import (build_membership_planes,
                                          build_violation_planes,
                                          delta_window,
                                          score_order_blocked,
                                          score_order_delta_bitmask)

    n, s, block = 10, 2, 32
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pad = (-S) % block
    table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=-3e38)
    pst = jnp.pad(jnp.asarray(pst), ((0, pad), (0, 0)), constant_values=-1)
    score_fn = functools.partial(score_order_blocked, table, pst, block=block)
    planes_fn = functools.partial(build_violation_planes, pst)
    cm = build_membership_planes(pst, n)
    w = delta_window(n, 4)
    assert w

    def bitmask_fn(pos, lo, prev_ls, prev_idx, pos_old, planes):
        return score_order_delta_bitmask(table, cm, pos, prev_ls, prev_idx,
                                         lo, pos_old, planes, window=w,
                                         block=block)
    return n, score_fn, planes_fn, bitmask_fn, w


def _stacked_states(n, score_fn, planes_fn, bitmask_fn, w, chains=4,
                    steps=20):
    from repro.core.mcmc import BitmaskDelta, ChainState, init_chain, mcmc_step

    keys = jax.random.split(jax.random.key(3), chains)
    states = jax.vmap(
        lambda k: init_chain(k, n, score_fn, planes_fn=planes_fn))(keys)
    assert len(ChainState._fields) == 13 and len(tuple(states)) == 13
    # drive with the REAL bitmask engine so the planes leaf is live state
    # (patched in place per accepted move), not a stale init-time cache
    step = jax.jit(jax.vmap(
        lambda s: mcmc_step(s, score_fn, BitmaskDelta(bitmask_fn), w)))
    for _ in range(steps):                     # de-trivialise every leaf
        states = step(states)
    return states


def test_rebalance_full_chain_state_keeps_caches_consistent():
    n, score_fn, planes_fn, bitmask_fn, w = _bitmask_problem()
    states = _stacked_states(n, score_fn, planes_fn, bitmask_fn, w)

    best = int(np.argmax(np.asarray(states.best_score)))
    victim = (best + 1) % 4               # stall someone other than the donor
    progressed = np.ones(4, bool)
    progressed[victim] = False
    missed = np.zeros(4, np.int64)
    out, missed, healed = rebalance_chains(
        jax.random.key(9), states, progressed, missed,
        StragglerPolicy(patience=1), return_mask=True)
    assert healed.tolist() == [c == victim for c in range(4)]
    assert missed.tolist() == [0, 0, 0, 0]

    # every leaf of the healed slot is the donor's (except the PRNG key)
    for name in ("pos", "score", "cur_idx", "best_score", "best_idx",
                 "best_pos", "accepts", "cur_ls", "mask_planes", "win_idx",
                 "adapt_err", "step"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, name))[victim],
            np.asarray(getattr(states, name))[best], err_msg=name)
    assert not np.array_equal(
        np.asarray(jax.random.key_data(out.key[victim])),
        np.asarray(jax.random.key_data(out.key[best])))

    # clone-consistency invariant: the cloned slot's derived caches describe
    # its cloned order — (score, cur_ls, cur_idx) match a fresh rescore and
    # mask_planes match a fresh plane build from the cloned positions
    sc, bi, ls = score_fn(out.pos[victim])
    np.testing.assert_array_equal(np.asarray(sc),
                                  np.asarray(out.score[victim]))
    np.testing.assert_array_equal(np.asarray(ls),
                                  np.asarray(out.cur_ls[victim]))
    np.testing.assert_array_equal(np.asarray(bi),
                                  np.asarray(out.cur_idx[victim]))
    np.testing.assert_array_equal(np.asarray(planes_fn(out.pos[victim])),
                                  np.asarray(out.mask_planes[victim]))


def test_rebalance_never_clones_from_poisoned_donor():
    from repro.runtime.faults import poison_chain_state

    n, score_fn, planes_fn, bitmask_fn, w = _bitmask_problem()
    states = _stacked_states(n, score_fn, planes_fn, bitmask_fn, w)
    # poison the would-be donor; chain 1 needs healing
    top = int(np.argmax(np.asarray(states.best_score)))
    states = poison_chain_state(states, top, "nan")
    assert best_finite_chain(states.best_score) != top
    progressed = np.ones(4, bool)
    progressed[1] = False
    out, _, healed = rebalance_chains(
        jax.random.key(2), states, progressed, np.zeros(4, np.int64),
        StragglerPolicy(patience=1), return_mask=True)
    assert healed[1]
    assert np.isfinite(np.asarray(out.score)[1])
    assert np.isfinite(np.asarray(out.best_score)[1])
    donor = best_finite_chain(states.best_score)
    np.testing.assert_array_equal(np.asarray(out.pos)[1],
                                  np.asarray(states.pos)[donor])


def test_supervisor_trace_reseed_follows_heal():
    from repro.runtime.supervisor import _reseed_trace
    from repro.telemetry import init_trace

    trace = init_trace(4, 10, n_windows=2, cap=8)
    trace = trace._replace(
        scores=trace.scores + jnp.arange(4, dtype=jnp.float32)[:, None],
        edge_counts=trace.edge_counts
        + jnp.arange(4, dtype=jnp.int32)[:, None, None])
    healed = np.array([False, True, False, False])
    out = _reseed_trace(trace, healed, donor=2)
    np.testing.assert_array_equal(np.asarray(out.scores[1]),
                                  np.asarray(trace.scores[2]))
    np.testing.assert_array_equal(np.asarray(out.scores[0]),
                                  np.asarray(trace.scores[0]))
    np.testing.assert_array_equal(np.asarray(out.edge_counts[1]),
                                  np.asarray(trace.edge_counts[2]))
    assert np.asarray(out.reseeds).tolist() == [0, 1, 0, 0]


def test_remesh_then_reshard_roundtrips_chain_leaves():
    """remesh_plan -> reshard_tree on the live platform: chain-stacked
    leaves placed with a chains-over-'data' spec survive bitwise (the
    restart path: topology-free checkpoint -> new mesh)."""
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType

    ndev = jax.device_count()
    shape, names = remesh_plan(ndev, model_parallel=1)
    assert shape == (ndev, 1) and names == ("data", "model")
    mesh = jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * 2)
    C = 2 * ndev
    tree = {"pos": np.arange(C * 6).reshape(C, 6),
            "score": np.linspace(0, 1, C)}
    specs = {"pos": P("data"), "score": P("data")}
    placed = reshard_tree(tree, specs, mesh)
    np.testing.assert_array_equal(np.asarray(placed["pos"]), tree["pos"])
    np.testing.assert_array_equal(np.asarray(placed["score"]), tree["score"])
    assert placed["pos"].sharding.mesh.shape["data"] == ndev
