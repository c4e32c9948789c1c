"""ISSUE 4: the mesh-native bitmask engine — sharded-planes delta ≡
single-device bitmask delta ≡ full rescore, BITWISE, over 200 randomized
move sequences on a simulated 4-device mesh, with a checkpoint save/restore
mid-run; padded PST ranks (S % (tp·block) != 0) are structurally
inconsistent and can never reach best_idx; bn_learn --sharded runs (and
checkpoint-resumes) end to end.

Subprocess with 4 placeholder devices so the suite itself keeps seeing 1 CPU
device. The 200×2-move property runs inside ONE jitted lax.scan (a Python
loop of shard_map dispatches would pay ~seconds of dispatch overhead per
sequence); all bitwise comparisons happen host-side on the stacked results.
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.combinatorics import build_pst, n_parent_sets
    from repro.core.graph import adjacency_from_ranks
    from repro.core.mcmc import init_chain, mcmc_step, propose_move
    from repro.core.order_scoring import (build_membership_planes,
                                          build_violation_planes,
                                          consistent_mask,
                                          planes_consistent_words,
                                          score_order_delta_bitmask,
                                          unpack_mask_words)
    from repro.core.sharded_scoring import (_shard_block,
                                            make_sharded_bitmask_fns,
                                            make_sharded_planes_fn,
                                            make_sharded_score_fn, pad_table,
                                            sharded_chain_step)
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from jax.sharding import AxisType

    n, s, w, tp, block, SEQS, MOVES = 13, 3, 4, 4, 64, 200, 2
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pst = jnp.asarray(pst)
    blk = _shard_block(S, tp, block)
    assert S % (tp * blk) != 0, "want a ragged shard boundary for this test"
    tpad, ppad = pad_table(table, pst, tp * blk)
    cm = build_membership_planes(ppad, n)

    mesh = jax.make_mesh((1, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    fn = make_sharded_score_fn(table, pst, mesh, block=block)
    bfn, planes_fn = make_sharded_bitmask_fns(table, pst, mesh, window=w,
                                              block=block)

    # padded ranks are STRUCTURALLY inconsistent: every consistency
    # representation rejects them, independent of the table pad value
    pos0 = jnp.asarray(rng.permutation(n).astype(np.int32))
    for i in range(n):
        m = np.asarray(consistent_mask(ppad, jnp.int32(i), pos0))
        assert not m[S:].any(), "padded rank passed consistent_mask"
    pl0 = build_violation_planes(ppad, pos0)
    for i in range(n):
        bits = np.asarray(unpack_mask_words(planes_consistent_words(pl0[i])))
        assert not bits[S:].any(), "padded rank consistent in bit planes"

    def one_move(carry, key):
        pos, planes, ls, idx = carry
        new_pos, lo = propose_move(key, pos, window=w)
        tot_s, idx_s, ls_s, win_s, rows_s = bfn.fn(new_pos, lo, ls, idx, pos,
                                                   planes)
        tot_1, idx_1, ls_1, win_1, rows_1 = score_order_delta_bitmask(
            tpad, cm, new_pos, ls, idx, lo, pos, planes, window=w, block=blk)
        pl_s = planes.at[win_s].set(rows_s)
        pl_1 = planes.at[win_1].set(rows_1)
        tot_f, idx_f, ls_f = fn(new_pos)
        out = (tot_s, tot_1, tot_f, idx_s, idx_1, idx_f, ls_s, ls_1, ls_f,
               jnp.all(pl_s == pl_1))
        return (new_pos, pl_s, ls_s, idx_s), out

    def one_seq(_, key):
        kp, km = jax.random.split(key)
        pos = jax.random.permutation(kp, n).astype(jnp.int32)
        planes = planes_fn(pos)
        _, idx, ls = fn(pos)
        (pos_f, planes_f, _, _), outs = jax.lax.scan(
            one_move, (pos, planes, ls, idx), jax.random.split(km, MOVES))
        planes_ok = jnp.all(planes_f == planes_fn(pos_f))
        return None, outs + (planes_ok,)

    with jax.set_mesh(mesh):
        # sharded per-shard planes build == single-device build, word for word
        np.testing.assert_array_equal(np.asarray(planes_fn(pos0)),
                                      np.asarray(pl0))

        keys = jax.random.split(jax.random.key(7), SEQS)
        _, R = jax.jit(lambda ks: jax.lax.scan(one_seq, None, ks))(keys)
        (tot_s, tot_1, tot_f, idx_s, idx_1, idx_f, ls_s, ls_1, ls_f,
         pl_eq, planes_ok) = [np.asarray(r) for r in R]
        np.testing.assert_array_equal(tot_s, tot_1)   # sharded == single
        np.testing.assert_array_equal(tot_s, tot_f)   # == full rescore
        np.testing.assert_array_equal(idx_s, idx_1)
        np.testing.assert_array_equal(idx_s, idx_f)
        np.testing.assert_array_equal(ls_s, ls_1)
        np.testing.assert_array_equal(ls_s, ls_f)
        assert pl_eq.all(), "sharded patched planes != single-device planes"
        assert planes_ok.all(), "carried planes drifted from rebuild"
        assert int(idx_s.max()) < S, "padded rank leaked into best_idx"
        for row in idx_s[-1]:
            adjacency_from_ranks(row, s=s)            # decodes, never raises

        # checkpoint save/restore mid-run: positions + caches roundtrip, the
        # planes (a derived cache) are REBUILT per shard, and the continued
        # walk stays bitwise on the equivalence
        srng = np.random.default_rng(123)
        pos = jnp.asarray(srng.permutation(n).astype(np.int32))
        planes = planes_fn(pos)
        _, idx, ls = jax.jit(fn)(pos)
        ckpt = tempfile.mkdtemp()
        save_checkpoint(ckpt, 5, (np.asarray(pos), np.asarray(ls),
                                  np.asarray(idx)))
        rest, _ = restore_checkpoint(ckpt, (np.asarray(pos), np.asarray(ls),
                                            np.asarray(idx)), step=5)
        pos2, ls2, idx2 = (jnp.asarray(x) for x in rest)
        planes2 = planes_fn(pos2)
        np.testing.assert_array_equal(np.asarray(planes2),
                                      np.asarray(planes))
        new_pos, lo = propose_move(jax.random.key(9), pos2, window=w)
        got = jax.jit(bfn.fn)(new_pos, lo, ls2, idx2, pos2, planes2)
        want = jax.jit(fn)(new_pos)
        assert float(got[0]) == float(want[0])
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(
            np.asarray(planes2.at[got[3]].set(got[4])),
            np.asarray(planes_fn(new_pos)))

        # sharded_chain_step: cached-planes path == mask-recompute path ==
        # vmapped local steps, bitwise; planes always describe current order
        splanes = make_sharded_planes_fn(ppad, mesh, stacked=True)
        keys = jax.random.split(jax.random.key(2), 4)
        states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
        sm = states._replace(mask_planes=splanes(states.pos))
        sd = sl = states
        for _ in range(3):
            sm = sharded_chain_step(sm, tpad, ppad, mesh, cm, block=blk,
                                    window=w)
            sd = sharded_chain_step(sd, tpad, ppad, mesh, block=blk, window=w)
            sl = jax.vmap(lambda st: mcmc_step(st, fn, None, w))(sl)
        np.testing.assert_array_equal(np.asarray(sm.pos), np.asarray(sd.pos))
        np.testing.assert_array_equal(np.asarray(sm.pos), np.asarray(sl.pos))
        np.testing.assert_array_equal(np.asarray(sm.accepts),
                                      np.asarray(sd.accepts))
        np.testing.assert_array_equal(np.asarray(sm.cur_ls),
                                      np.asarray(sl.cur_ls))
        np.testing.assert_array_equal(np.asarray(sm.mask_planes),
                                      np.asarray(splanes(sm.pos)))
        assert (np.asarray(sm.cur_idx) < S).all()
    print("OK")
""")

LEARN_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from repro.core import random_cpts
    from repro.data.bn_sampler import ancestral_sample
    from repro.data.networks import synthetic_adjacency
    from repro.launch.bn_learn import LearnConfig, learn_structure

    rng = np.random.default_rng(0)
    adj = synthetic_adjacency(rng, 10)
    data = ancestral_sample(rng, adj, random_cpts(rng, adj, 2), 300, 2)

    cfg = LearnConfig(q=2, s=2, iters=40, chains=2, window=4, sharded=True,
                      block=64)
    out = learn_structure(data, cfg)
    assert out["sharded"] and out["mask_cache"] and out["delta_window"] == 4
    assert np.isfinite(out["score"])

    # checkpointed sharded run + resume (planes rebuilt per shard on restore)
    ckpt = tempfile.mkdtemp()
    cfg2 = LearnConfig(q=2, s=2, iters=40, chains=2, window=4, sharded=True,
                       block=64, checkpoint_dir=ckpt, checkpoint_every=20)
    a = learn_structure(data, cfg2)
    b = learn_structure(data, cfg2)       # resumes from the last snapshot
    assert np.isfinite(a["score"]) and np.isfinite(b["score"])
    assert b["score"] >= a["score"] - 1e-4
    print("OK")
""")


def test_sharded_bitmask_property_and_padded_ranks():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_bn_learn_sharded_end_to_end():
    r = subprocess.run([sys.executable, "-c", LEARN_SCRIPT],
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
