"""The ISSUE 3 engine: cached consistency bitmasks ≡ recomputed masks
(bitwise, over move SEQUENCES), adaptive-window freeze, in-scan
exchange_best invariants, and restore of the extended ChainState from a
pre-tentpole checkpoint layout.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, hst, settings

from repro.core.combinatorics import build_pst, n_parent_sets
from repro.core.mcmc import (BitmaskDelta, ChainState, exchange_best,
                             exchange_step, init_chain, mcmc_run,
                             mcmc_run_adaptive, mcmc_run_chains,
                             mcmc_run_chains_adaptive, mcmc_step,
                             mcmc_step_adaptive, propose_move)
from repro.core.order_scoring import (NEG_INF, build_membership_planes,
                                      build_violation_planes, consistent_mask,
                                      pack_mask_words,
                                      planes_consistent_words,
                                      score_order_blocked,
                                      score_order_delta_bitmask,
                                      unpack_mask_words)


@functools.lru_cache(maxsize=None)
def _problem(n=12, s=3, block=64, seed=42):
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pad = (-S) % block
    table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=NEG_INF)
    pst = jnp.pad(jnp.asarray(pst), ((0, pad), (0, 0)), constant_values=-1)
    cm = build_membership_planes(pst, n)
    return table, pst, cm


def _bitmask_delta(table, cm, block, window):
    def bfn(pos, lo, prev_ls, prev_idx, pos_old, planes):
        return score_order_delta_bitmask(table, cm, pos, prev_ls, prev_idx,
                                         lo, pos_old, planes, window=window,
                                         block=block)
    return BitmaskDelta(bfn)


def test_pack_unpack_roundtrip_and_init_planes_match_masks():
    """Packed word layout (LSB-first, rank 32j+b) roundtrips, and the
    freshly-built violation planes decode to exactly consistent_mask for
    every node."""
    table, pst, _ = _problem()
    n = table.shape[0]
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, 256).astype(bool)
    np.testing.assert_array_equal(
        np.asarray(unpack_mask_words(pack_mask_words(jnp.asarray(bits)))),
        bits)
    pos = jnp.asarray(rng.permutation(n).astype(np.int32))
    planes = build_violation_planes(pst, pos)
    for i in range(n):
        want = np.asarray(consistent_mask(pst, jnp.int32(i), pos))
        got = np.asarray(unpack_mask_words(planes_consistent_words(planes[i])))
        np.testing.assert_array_equal(got, want)


@given(hst.integers(0, 2**31 - 1))
@settings(max_examples=200, deadline=None)
def test_bitmask_cache_equals_recomputed_masks(seed):
    """≥200 randomized move SEQUENCES: the incrementally-patched planes stay
    bitwise-equal to planes rebuilt from scratch, and the bitmask delta
    rescore stays bitwise-equal to a full blocked rescore — total, argmax
    parent sets, per-node scores — across 4 chained moves."""
    block = 64
    table, pst, cm = _problem(block=block)
    n = table.shape[0]
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.permutation(n).astype(np.int32))
    planes = build_violation_planes(pst, pos)
    _, idx, ls = score_order_blocked(table, pst, pos, block=block)
    key = jax.random.key(seed)
    for _ in range(4):
        key, k_mv = jax.random.split(key)
        w = int(rng.integers(2, 7))
        new_pos, lo = propose_move(k_mv, pos, window=w)
        tot, gidx, gls, win, rows = score_order_delta_bitmask(
            table, cm, new_pos, ls, idx, lo, pos, planes, window=w,
            block=block)
        new_planes = planes.at[win].set(rows)
        want = score_order_blocked(table, pst, new_pos, block=block)
        assert float(tot) == float(want[0])
        np.testing.assert_array_equal(np.asarray(gidx), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(gls), np.asarray(want[2]))
        np.testing.assert_array_equal(
            np.asarray(new_planes),
            np.asarray(build_violation_planes(pst, new_pos)))
        pos, planes, idx, ls = new_pos, new_planes, want[1], want[2]


def test_mcmc_bitmask_chain_is_bitwise_identical(padded_random_table):
    """Same key, same proposals: the bitmask-cached chain and the
    full-rescore chain traverse identical states, and the carried planes
    always describe the CURRENT order."""
    table, pst, block = padded_random_table
    n = table.shape[0]
    cm = build_membership_planes(pst, n)
    fn = functools.partial(score_order_blocked, table, pst, block=block)
    planes_fn = functools.partial(build_violation_planes, pst)

    a, _ = mcmc_run(jax.random.key(3), n, fn, 300, window=4)
    b, _ = mcmc_run(jax.random.key(3), n, fn, 300,
                    delta_fn=_bitmask_delta(table, cm, block, 4), window=4,
                    planes_fn=planes_fn)
    assert float(a.score) == float(b.score)
    assert float(a.best_score) == float(b.best_score)
    assert int(a.accepts) == int(b.accepts)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    np.testing.assert_array_equal(np.asarray(a.best_idx),
                                  np.asarray(b.best_idx))
    np.testing.assert_array_equal(np.asarray(a.cur_ls), np.asarray(b.cur_ls))
    np.testing.assert_array_equal(np.asarray(b.mask_planes),
                                  np.asarray(planes_fn(b.pos)))


def test_accept_writes_window_rows_in_place(small_problem):
    """The accept step writes back only the window's rows: the lowered
    vmapped step holds no select over the whole (C, n, P, W) plane stack,
    the compiled scan copies no such stack per iteration, and the carried
    planes still equal a from-scratch build after hundreds of steps with
    both accepts and rejects."""
    table, pst, cm, block, fn = small_problem
    n, C, iters = 12, 2, 300
    planes_fn = functools.partial(build_violation_planes, pst)
    delta = _bitmask_delta(table, cm, block, 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn, planes_fn=planes_fn))(
        jax.random.split(jax.random.key(11), C))
    step = jax.vmap(lambda s: mcmc_step(s, fn, delta, 4))
    stack = states.mask_planes.shape
    assert stack[:2] == (C, n)

    shlo = jax.jit(step).lower(states).as_text()
    tensor = "tensor<" + "x".join(map(str, stack)) + "xui32>"
    assert not [l for l in shlo.splitlines()
                if "stablehlo.select" in l and tensor in l]

    def run(st):
        return jax.lax.scan(lambda c, _: (step(c), None), st, None,
                            length=iters)[0]

    hlo = jax.jit(run, donate_argnums=0).lower(states).compile().as_text()
    result = re.compile(r"%(\S+) = u32\[" + ",".join(map(str, stack))
                        + r"\]\{[^}]*\} (\w+)\(")
    ops = [m.groups() for m in map(result.search, hlo.splitlines()) if m]
    assert ops, "the plane stack should appear in the compiled scan"
    for name, opcode in ops:
        assert opcode not in ("copy", "select") and "select" not in name, \
            (name, opcode)

    out = jax.jit(run)(states)
    accepts = np.asarray(out.accepts)
    assert ((0 < accepts) & (accepts < iters)).all(), accepts
    np.testing.assert_array_equal(np.asarray(out.mask_planes),
                                  np.asarray(jax.vmap(planes_fn)(out.pos)))


def test_adaptive_bitmask_switch_pads_window_rows(small_problem):
    """mcmc_step_adaptive over bitmask deltas of different widths pads each
    branch's rows to the widest window with the dropped id n: the carried
    planes equal a from-scratch build at EVERY iteration, and the walk is
    bitwise the full-rescore adaptive walk."""
    table, pst, cm, block, fn = small_problem
    n, C, iters, windows = 12, 2, 200, (2, 4, 6)
    planes_fn = functools.partial(build_violation_planes, pst)
    deltas = tuple(_bitmask_delta(table, cm, block, w) for w in windows)
    states = jax.vmap(lambda k: init_chain(
        k, n, fn, planes_fn=planes_fn, win_idx=len(windows) // 2))(
        jax.random.split(jax.random.key(13), C))

    def body(st, _):
        st = jax.vmap(lambda s: mcmc_step_adaptive(
            s, fn, deltas, windows, burn_in=iters // 2))(st)
        ok = jnp.all(st.mask_planes == jax.vmap(planes_fn)(st.pos))
        return st, (ok, st.win_idx)

    out, (ok, win_idx) = jax.jit(
        lambda st: jax.lax.scan(body, st, None, length=iters))(states)
    assert np.asarray(ok).all(), "carried planes drifted from rebuild"
    assert len(set(np.asarray(win_idx).ravel().tolist())) > 1, \
        "the run should visit more than one window"

    full = mcmc_run_chains_adaptive(jax.random.key(13), C, n, fn, iters,
                                    windows=windows, burn_in=iters // 2)
    got = mcmc_run_chains_adaptive(jax.random.key(13), C, n, fn, iters,
                                   windows=windows, delta_fns=deltas,
                                   planes_fn=planes_fn, burn_in=iters // 2)
    for name in ("pos", "score", "cur_idx", "cur_ls", "best_score",
                 "best_idx", "accepts", "win_idx", "adapt_err"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(full, name)), name)
    np.testing.assert_array_equal(np.asarray(got.mask_planes),
                                  np.asarray(jax.vmap(planes_fn)(got.pos)))


def test_kernel_bitmask_variant_matches_core(padded_random_table):
    """The packed-word Pallas kernel (interpret mode) == the jnp bitmask
    scorer == the gather-path blocked scorer, bitwise."""
    from repro.kernels.order_score import order_score_delta_bitmask

    table, pst, block = padded_random_table
    n = table.shape[0]
    cm = build_membership_planes(pst, n)
    rng = np.random.default_rng(7)
    pos = jnp.asarray(rng.permutation(n).astype(np.int32))
    planes = build_violation_planes(pst, pos)
    _, idx, ls = score_order_blocked(table, pst, pos, block=block)
    for seed in range(3):
        new_pos, lo = propose_move(jax.random.key(seed), pos, window=3)
        want = score_order_blocked(table, pst, new_pos, block=block)
        for use_pallas in (True, False):
            got = order_score_delta_bitmask(
                table, cm, new_pos, ls, idx, lo, pos, planes, window=3,
                block_s=block, use_pallas=use_pallas, interpret=True)
            assert float(got[0]) == float(want[0])
            np.testing.assert_array_equal(np.asarray(got[1]),
                                          np.asarray(want[1]))
            np.testing.assert_array_equal(np.asarray(got[2]),
                                          np.asarray(want[2]))
            # the fused kernel's patched plane words == from-scratch build
            patched = planes.at[got[3]].set(got[4])
            np.testing.assert_array_equal(
                np.asarray(patched),
                np.asarray(build_violation_planes(pst, new_pos)))
        pos, planes = new_pos, patched
        idx, ls = want[1], want[2]


# ------------------------------------------------- structural PST padding
def test_padded_pst_rows_are_structurally_inconsistent():
    """ISSUE 4 bugfix: pad_table/pad_for_kernel pad PST rows with the
    PAD_SET sentinel (-2), which every consistency path rejects — padded
    ranks can never reach best_idx even when the TABLE pad is 0.0 (which
    beats every real score here), where the old -1 pad (indistinguishable
    from the always-consistent empty set) handed best_idx to a padded
    rank."""
    from repro.core.order_scoring import PAD_SET, score_order_chunked
    from repro.core.sharded_scoring import pad_table
    from repro.kernels.order_score import order_score, pad_for_kernel

    from repro.core.combinatorics import build_pst, n_parent_sets

    n, s, block = 13, 3, 64
    S = n_parent_sets(n - 1, s)
    assert S % block != 0, "want a ragged pad for this test"
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    tpad, ppad = pad_table(table, jnp.asarray(pst), block)
    assert int(np.asarray(ppad)[S:].max(initial=PAD_SET)) == PAD_SET
    _, ppad_k = pad_for_kernel(table, jnp.asarray(pst), block)
    np.testing.assert_array_equal(np.asarray(ppad), np.asarray(ppad_k))
    # adversarial table pad: 0.0 beats every real entry
    tzero = jnp.pad(table, ((0, 0), (0, tpad.shape[1] - S)),
                    constant_values=0.0)
    pos = jnp.asarray(rng.permutation(n).astype(np.int32))
    for i in range(n):
        m = np.asarray(consistent_mask(ppad, jnp.int32(i), pos))
        assert not m[S:].any()
    planes = build_violation_planes(ppad, pos)
    for i in range(n):
        bits = np.asarray(unpack_mask_words(
            planes_consistent_words(planes[i])))
        assert not bits[S:].any()
    for scorer in (score_order_blocked, score_order_chunked):
        _, idx, _ = scorer(tzero, ppad, pos, block=block)
        assert int(np.max(np.asarray(idx))) < S, scorer.__name__
    _, idx, _ = order_score(tzero, ppad, pos, block_s=block, interpret=True)
    assert int(np.max(np.asarray(idx))) < S
    # bitmask delta on the adversarially-padded table also stays < S
    cm = build_membership_planes(ppad, n)
    _, idx0, ls0 = score_order_blocked(tzero, ppad, pos, block=block)
    new_pos, lo = propose_move(jax.random.key(0), pos, window=4)
    tot, gidx, _, _, _ = score_order_delta_bitmask(
        tzero, cm, new_pos, ls0, idx0, lo, pos, planes, window=4,
        block=block)
    assert int(np.max(np.asarray(gidx))) < S


# ------------------------------------------------- in-scan exchange_best
@pytest.fixture(scope="module")
def small_problem():
    table, pst, cm = _problem()
    block = 64
    fn = functools.partial(score_order_blocked, table, pst, block=block)
    return table, pst, cm, block, fn


def test_exchange_step_reseeds_worst_from_best(small_problem):
    """exchange_step: the worst chain inherits the best chain's position AND
    cache state together; everyone's best_score is monotone; keys stay
    per-slot."""
    _, _, _, _, fn = small_problem
    n = 12
    keys = jax.random.split(jax.random.key(0), 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
    # make the ranking unambiguous
    states = states._replace(best_score=jnp.asarray([3., -9., 1., 2.],
                                                    jnp.float32))
    before = np.asarray(states.best_score)
    out = jax.jit(exchange_step)(states)
    b, w = int(np.argmax(before)), int(np.argmin(before))
    np.testing.assert_array_equal(np.asarray(out.pos[w]),
                                  np.asarray(states.pos[b]))
    assert float(out.score[w]) == float(states.score[b])
    np.testing.assert_array_equal(np.asarray(out.cur_idx[w]),
                                  np.asarray(states.cur_idx[b]))
    np.testing.assert_array_equal(np.asarray(out.cur_ls[w]),
                                  np.asarray(states.cur_ls[b]))
    assert float(out.best_score[w]) == float(before[b])
    # monotone: nobody's best got worse
    assert (np.asarray(out.best_score) >= before).all()
    # PRNG keys unchanged (clones diverge immediately)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(out.key)),
        np.asarray(jax.random.key_data(states.key)))
    # untouched chains are bitwise-identical
    for c in range(4):
        if c != w:
            np.testing.assert_array_equal(np.asarray(out.pos[c]),
                                          np.asarray(states.pos[c]))


def test_mcmc_run_chains_in_scan_exchange_invariants(small_problem):
    """After a run WITH periodic exchange: every chain's (score, cur_idx,
    cur_ls, mask_planes) still describe its own pos — the re-seed copied
    caches consistently — and the final reduction returns a reproducible
    best triple."""
    table, pst, cm, block, fn = small_problem
    n = 12
    planes_fn = functools.partial(build_violation_planes, pst)

    states = mcmc_run_chains(jax.random.key(5), 4, n, fn, 120,
                             delta_fn=_bitmask_delta(table, cm, block, 4),
                             window=4,
                             exchange_every=25, planes_fn=planes_fn)
    for c in range(4):
        sc, idx, ls = fn(states.pos[c])
        assert float(sc) == float(states.score[c])
        np.testing.assert_array_equal(np.asarray(idx),
                                      np.asarray(states.cur_idx[c]))
        np.testing.assert_array_equal(np.asarray(ls),
                                      np.asarray(states.cur_ls[c]))
        np.testing.assert_array_equal(
            np.asarray(states.mask_planes[c]),
            np.asarray(planes_fn(states.pos[c])))
        assert float(states.best_score[c]) >= float(states.score[c]) - 1e-4
    bs, bi, bp = exchange_best(states)
    sc, idx, _ = fn(bp)
    assert float(sc) == float(bs)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(bi))


def test_exchange_step_degenerate_ranking_is_noop(small_problem):
    """ISSUE 4 bugfix: all-equal best_score makes argmax == argmin — the
    exchange must be a true NO-OP (guarded lax.cond), leaving EVERY leaf of
    every chain bitwise-untouched."""
    _, _, _, _, fn = small_problem
    n = 12
    keys = jax.random.split(jax.random.key(6), 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
    states = states._replace(
        best_score=jnp.zeros(4, jnp.float32),
        win_idx=jnp.asarray([0, 1, 2, 3], jnp.int32),
        adapt_err=jnp.asarray([0.1, -0.2, 0.3, -0.4], jnp.float32))
    out = jax.jit(exchange_step)(states)
    for name in ChainState._fields:
        got, want = getattr(out, name), getattr(states, name)
        if name == "key":
            got, want = jax.random.key_data(got), jax.random.key_data(want)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), name)


def test_exchange_step_keeps_adaptive_stats_per_slot(small_problem):
    """Non-degenerate exchange copies pos/caches/best_* — and ONLY those:
    win_idx, dual-averaging error, step, accept counts and PRNG keys stay
    strictly per-slot (a re-seeded chain keeps its own tuning)."""
    _, _, _, _, fn = small_problem
    n = 12
    keys = jax.random.split(jax.random.key(8), 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
    states = states._replace(
        best_score=jnp.asarray([5., -2., 0., 1.], jnp.float32),
        win_idx=jnp.asarray([3, 1, 0, 2], jnp.int32),
        adapt_err=jnp.asarray([0.5, -0.1, 0.2, 0.9], jnp.float32),
        accepts=jnp.asarray([7, 3, 9, 1], jnp.int32),
        step=jnp.asarray([10, 10, 10, 10], jnp.int32))
    out = jax.jit(exchange_step)(states)
    # the worst slot really was re-seeded...
    np.testing.assert_array_equal(np.asarray(out.pos[1]),
                                  np.asarray(states.pos[0]))
    # ...but per-slot statistics never move
    for name in ("win_idx", "adapt_err", "accepts", "step"):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(getattr(states, name)), name)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(out.key)),
        np.asarray(jax.random.key_data(states.key)))


def test_adaptive_chains_with_exchange_keep_per_slot_windows(small_problem):
    """mcmc_run_chains_adaptive + periodic in-scan exchange: the selection
    stays inside the static window set per chain, and on a FLAT table (all
    best_score equal, the degenerate ranking every round) the guarded
    exchange leaves the run bitwise-identical to exchange_every=0."""
    _, _, _, _, fn = small_problem
    n = 12
    sts = mcmc_run_chains_adaptive(jax.random.key(3), 4, n, fn, 60,
                                   windows=(2, 4), delta_fns=(None, None),
                                   burn_in=20, exchange_every=15)
    assert set(np.asarray(sts.win_idx).tolist()) <= {0, 1}
    assert np.isfinite(np.asarray(sts.adapt_err)).all()

    flat = lambda pos: (jnp.float32(0.0), jnp.zeros(n, jnp.int32),
                        jnp.zeros(n, jnp.float32))
    a = mcmc_run_chains_adaptive(jax.random.key(4), 3, n, flat, 40,
                                 windows=(2, 4), delta_fns=(None, None),
                                 burn_in=10, exchange_every=10)
    b = mcmc_run_chains_adaptive(jax.random.key(4), 3, n, flat, 40,
                                 windows=(2, 4), delta_fns=(None, None),
                                 burn_in=10, exchange_every=0)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    np.testing.assert_array_equal(np.asarray(a.win_idx),
                                  np.asarray(b.win_idx))
    np.testing.assert_array_equal(np.asarray(a.adapt_err),
                                  np.asarray(b.adapt_err))


def test_mcmc_run_chains_exchange_off_matches_legacy(small_problem):
    """exchange_every=0 keeps chains fully independent: identical to vmapped
    mcmc_run with the same keys."""
    _, _, _, _, fn = small_problem
    n = 12
    a = mcmc_run_chains(jax.random.key(2), 3, n, fn, 80, window=4)
    keys = jax.random.split(jax.random.key(2), 3)
    b, _ = jax.vmap(lambda k: mcmc_run(k, n, fn, 80, window=4))(keys)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    np.testing.assert_array_equal(np.asarray(a.best_score),
                                  np.asarray(b.best_score))


# ------------------------------------------------- adaptive move windows
def test_adaptive_window_freezes_after_burn_in(small_problem):
    """win_idx stops moving once step >= burn_in (MCMC validity: post-warmup
    samples come from ONE fixed kernel), stays inside the static set, and
    the chain's caches remain consistent with its pos."""
    _, _, _, _, fn = small_problem
    n = 12
    st, (tr_sc, tr_w) = mcmc_run_adaptive(
        jax.random.key(7), n, fn, 150, windows=(2, 4, 6),
        delta_fns=(None, None, None), burn_in=60, trace=True)
    tw = np.asarray(tr_w)
    assert set(tw.tolist()) <= {0, 1, 2}
    assert len(set(tw[60:].tolist())) == 1, "window kept adapting past burn-in"
    assert 0 < int(st.accepts) <= 150
    sc, idx, ls = fn(st.pos)
    assert float(sc) == float(st.score)
    assert float(st.best_score) >= float(np.max(np.asarray(tr_sc))) - 1e-4


def test_adaptive_flat_table_accepts_everything(small_problem):
    """On a constant table every proposal is accepted regardless of which
    window branch fired — the adaptive mixture preserves move symmetry."""
    n = 12
    fn = lambda pos: (jnp.float32(0.0), jnp.zeros(n, jnp.int32),
                      jnp.zeros(n, jnp.float32))
    st, _ = mcmc_run_adaptive(jax.random.key(9), n, fn, 100,
                              windows=(2, 4), delta_fns=(None, None),
                              burn_in=30)
    assert int(st.accepts) == 100


# ------------------------------------------------- checkpoint compatibility
def test_restore_extended_chainstate_from_pre_tentpole_checkpoint(
        tmp_path, small_problem):
    """A checkpoint written with the OLD 9-leaf ChainState layout restores
    into the extended 13-leaf state: old leaves land bitwise, new leaves keep
    the caller's freshly-initialised values (allow_missing), and the planes
    rebuilt from the restored pos let the bitmask chain continue."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    table, pst, cm, block, fn = small_problem
    n = 12
    keys = jax.random.split(jax.random.key(1), 2)
    planes_fn = functools.partial(build_violation_planes, pst)
    states = jax.vmap(
        lambda k: init_chain(k, n, fn, planes_fn=planes_fn))(keys)
    pack = lambda st: jax.tree.map(
        np.asarray, st._replace(key=jax.random.key_data(st.key)))
    full = tuple(pack(states))

    # pre-tentpole snapshot: exactly the first 9 ChainState leaves
    old_layout = full[:9]
    save_checkpoint(str(tmp_path), 7, old_layout)

    # strict restore of the 13-leaf layout must fail loudly...
    with pytest.raises(ValueError, match="mismatch"):
        restore_checkpoint(str(tmp_path), full, step=7)
    # ...allow_missing backfills the new trailing leaves from the template
    restored, meta = restore_checkpoint(str(tmp_path), full, step=7,
                                        allow_missing=True)
    assert len(meta["missing_leaves"]) == 4
    for got, want in zip(restored[:9], full[:9]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    st2 = ChainState(*[jnp.asarray(x) for x in restored])._replace(
        key=jax.random.wrap_key_data(jnp.asarray(restored[0])))
    # derived cache: rebuild planes from the restored positions and resume
    st2 = st2._replace(mask_planes=jax.vmap(planes_fn)(st2.pos))

    delta = _bitmask_delta(table, cm, block, 4)
    step = jax.jit(jax.vmap(lambda s: mcmc_step(s, fn, delta, 4)))
    for _ in range(5):
        st2 = step(st2)
    for c in range(2):
        sc, idx, ls = fn(st2.pos[c])
        assert float(sc) == float(st2.score[c])
        np.testing.assert_array_equal(np.asarray(ls),
                                      np.asarray(st2.cur_ls[c]))


def test_restore_across_engine_variants_reconciles_planes(tmp_path,
                                                          small_problem):
    """ISSUE 4 bugfix, both directions: a sharded-run snapshot (zero-size
    mask_planes placeholder) restored into the bitmask engine, and a
    full-planes snapshot restored into a placeholder engine, previously left
    a wrong-shaped planes leaf (allow_missing only backfills MISSING
    leaves). reconcile_mask_planes rebuilds the derived cache from the
    restored positions / resets the placeholder, and the chain continues
    bitwise-correctly."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.launch.bn_learn import reconcile_mask_planes

    table, pst, cm, block, fn = small_problem
    n = 12
    planes_fn = functools.partial(build_violation_planes, pst)
    keys = jax.random.split(jax.random.key(12), 2)
    with_planes = jax.vmap(
        lambda k: init_chain(k, n, fn, planes_fn=planes_fn))(keys)
    placeholder = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
    pack = lambda st: tuple(jax.tree.map(
        np.asarray, st._replace(key=jax.random.key_data(st.key))))
    unpack = lambda t: ChainState(*[jnp.asarray(x) for x in t])._replace(
        key=jax.random.wrap_key_data(jnp.asarray(t[0])))

    # direction 1: placeholder snapshot -> bitmask engine
    save_checkpoint(str(tmp_path / "a"), 1, pack(placeholder))
    restored, _ = restore_checkpoint(str(tmp_path / "a"), pack(with_planes),
                                     step=1, allow_missing=True)
    st = unpack(restored)
    assert st.mask_planes.shape == (2, 0)          # the wrong-shaped leaf
    st = reconcile_mask_planes(st, lambda p: jax.vmap(planes_fn)(p))
    assert st.mask_planes.shape == with_planes.mask_planes.shape
    np.testing.assert_array_equal(np.asarray(st.mask_planes),
                                  np.asarray(jax.vmap(planes_fn)(st.pos)))

    delta = _bitmask_delta(table, cm, block, 4)
    step = jax.jit(jax.vmap(lambda s: mcmc_step(s, fn, delta, 4)))
    for _ in range(5):
        st = step(st)
    for c in range(2):
        sc, _, ls = fn(st.pos[c])
        assert float(sc) == float(st.score[c])
        np.testing.assert_array_equal(np.asarray(ls),
                                      np.asarray(st.cur_ls[c]))
        np.testing.assert_array_equal(np.asarray(st.mask_planes[c]),
                                      np.asarray(planes_fn(st.pos[c])))

    # direction 2: full-planes snapshot -> placeholder engine
    save_checkpoint(str(tmp_path / "b"), 1, pack(with_planes))
    restored, _ = restore_checkpoint(str(tmp_path / "b"), pack(placeholder),
                                     step=1, allow_missing=True)
    st = unpack(restored)
    assert st.mask_planes.ndim == 4                # the wrong-shaped leaf
    st = reconcile_mask_planes(st, None)
    assert st.mask_planes.shape == (2, 0)
    step = jax.jit(jax.vmap(lambda s: mcmc_step(s, fn, None, 4)))
    for _ in range(3):
        st = step(st)
    for c in range(2):
        sc, _, _ = fn(st.pos[c])
        assert float(sc) == float(st.score[c])


def test_new_leaves_roundtrip_through_checkpoint(tmp_path, small_problem):
    """Forward path: the 13-leaf layout saves and strict-restores bitwise."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    _, pst, _, _, fn = small_problem
    n = 12
    planes_fn = functools.partial(build_violation_planes, pst)
    st = init_chain(jax.random.key(4), n, fn, planes_fn=planes_fn)
    pack = tuple(jax.tree.map(
        np.asarray, st._replace(key=jax.random.key_data(st.key))))
    save_checkpoint(str(tmp_path), 1, pack)
    restored, meta = restore_checkpoint(str(tmp_path), pack, step=1)
    assert "missing_leaves" not in meta
    for got, want in zip(restored, pack):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
