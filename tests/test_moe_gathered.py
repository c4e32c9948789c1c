"""Gathered-experts MoE == scatter-dispatch MoE (lossless capacity, 8
placeholder devices in a subprocess)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.layers import set_mesh

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    for arch in ("granite-moe-3b-a800m", "arctic-480b"):
        cfg = get_config(arch).reduced()
        # lossless capacity so both dispatch strategies drop nothing
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
        B, T = 2, 16
        m0 = Model(cfg, tp=4)
        m1 = Model(cfg, tp=4, moe_gathered=True)
        # fsdp_only flavour: batch occupies every axis, fully local dispatch
        m2 = Model(cfg, tp=4, moe_gathered=True,
                   batch_axes=("data", "model"))
        # expert-parallel a2a flavour: experts resident, tokens travel
        m3 = Model(cfg, tp=4, moe_ep=True)
        params = m0.init(jax.random.key(0))
        tok = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab)
        set_mesh(mesh)
        with jax.set_mesh(mesh):
            a, _ = jax.jit(m0.forward)(params, tok)
            b, _ = jax.jit(m1.forward)(params, tok)
            np.testing.assert_allclose(
                np.asarray(a[..., :cfg.vocab], np.float32),
                np.asarray(b[..., :cfg.vocab], np.float32),
                rtol=3e-3, atol=3e-3, err_msg=arch)
            c, _ = jax.jit(m2.forward)(params, tok)
            np.testing.assert_allclose(
                np.asarray(a[..., :cfg.vocab], np.float32),
                np.asarray(c[..., :cfg.vocab], np.float32),
                rtol=3e-3, atol=3e-3, err_msg=arch + " fsdp_only")
            e, _ = jax.jit(m3.forward)(params, tok)
            np.testing.assert_allclose(
                np.asarray(a[..., :cfg.vocab], np.float32),
                np.asarray(e[..., :cfg.vocab], np.float32),
                rtol=3e-3, atol=3e-3, err_msg=arch + " moe_ep")

            # gradients flow (train-step viability); explicit out_shardings
            # sidestep a gspmd->named conversion bug on grad-of-shard_map
            def loss(p):
                lg, _ = m1.forward(p, tok)
                return jnp.mean(lg[..., : cfg.vocab].astype(jnp.float32) ** 2)
            from jax.sharding import NamedSharding
            outs = jax.tree.map(lambda s: NamedSharding(mesh, s), m1.specs())
            g = jax.jit(jax.grad(loss), out_shardings=outs)(params)
            assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                       for x in jax.tree.leaves(g)), arch
        set_mesh(None)
    print("OK")
""")


def test_moe_gathered_matches_scatter():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, (r.stderr[-4000:], r.stdout[-500:])
    assert "OK" in r.stdout
