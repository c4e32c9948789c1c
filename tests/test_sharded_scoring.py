"""Multi-device order scoring == single-device oracle (subprocess with 8
placeholder devices so the suite itself keeps seeing 1 CPU device)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.combinatorics import build_pst, n_parent_sets
    from repro.core.order_scoring import score_order_ref
    from repro.core.sharded_scoring import make_sharded_score_fn, pad_table
    from repro.core.mcmc import mcmc_run

    n, s = 14, 3
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pst = jnp.asarray(pst)

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    fn = make_sharded_score_fn(table, pst, mesh, block=64)

    for seed in range(5):
        pos = jnp.asarray(np.random.default_rng(seed).permutation(n)
                          .astype(np.int32))
        with jax.set_mesh(mesh):
            sc, idx, ls = jax.jit(fn)(pos)
        sc_ref, idx_ref, ls_ref = score_order_ref(table, pst, pos)
        np.testing.assert_allclose(float(sc), float(sc_ref), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))

    # the full MCMC sampler runs on the sharded scorer unchanged
    with jax.set_mesh(mesh):
        state, _ = mcmc_run(jax.random.key(0), n, fn, 50)
    assert np.isfinite(float(state.best_score))

    # delta path: sharded incremental rescore == sharded full rescore,
    # and the delta-path chain is step-for-step identical to the full one
    from repro.core.mcmc import propose_move
    from repro.core.sharded_scoring import make_sharded_delta_fn
    dfn = make_sharded_delta_fn(table, pst, mesh, window=4, block=64)
    for seed in range(5):
        pos = jnp.asarray(np.random.default_rng(100 + seed).permutation(n)
                          .astype(np.int32))
        with jax.set_mesh(mesh):
            sc0, idx0, ls0 = jax.jit(fn)(pos)
        new_pos, lo = propose_move(jax.random.key(seed), pos, window=4)
        with jax.set_mesh(mesh):
            got = jax.jit(dfn)(new_pos, lo, ls0, idx0)
            want = jax.jit(fn)(new_pos)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    with jax.set_mesh(mesh):
        a, _ = mcmc_run(jax.random.key(1), n, fn, 40, window=4)
        b, _ = mcmc_run(jax.random.key(1), n, fn, 40, delta_fn=dfn, window=4)
    assert float(a.score) == float(b.score)
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    assert int(a.accepts) == int(b.accepts)

    # sharded_chain_step's fused delta path == per-chain full-rescore steps
    from repro.core.mcmc import init_chain, mcmc_step
    from repro.core.sharded_scoring import sharded_chain_step
    tpad, ppad = pad_table(table, pst, 4 * 64)
    keys = jax.random.split(jax.random.key(2), 8)
    with jax.set_mesh(mesh):
        states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)
        sd = sl = states
        for _ in range(3):
            sd = sharded_chain_step(sd, tpad, ppad, mesh, block=64, window=4)
            sl = jax.vmap(lambda s: mcmc_step(s, fn, None, 4))(sl)
    np.testing.assert_array_equal(np.asarray(sd.pos), np.asarray(sl.pos))
    np.testing.assert_allclose(np.asarray(sd.score), np.asarray(sl.score),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sd.accepts),
                                  np.asarray(sl.accepts))
    print("OK")
""")


def test_sharded_scoring_matches_oracle():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
