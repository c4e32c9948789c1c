"""Sequence-parallel RWKV stack == sequential stack (8 placeholder devices,
subprocess so the main suite keeps 1 device). Covers forward logits, the
prefill cache (state + shift tokens), and continued decode equivalence."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.layers import set_mesh

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("rwkv6-7b").reduced()
    B, T = 2, 32                      # T/tp = 8 per shard, chunk 4

    m_seq = Model(cfg, tp=4, rwkv_chunk=4)
    m_sp = Model(cfg, tp=4, rwkv_chunk=4, rwkv_sp=True)
    params = m_seq.init(jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab)

    set_mesh(mesh)
    with jax.set_mesh(mesh):
        # train-mode forward (no cache)
        a, _ = jax.jit(m_seq.forward)(params, tok)
        b, _ = jax.jit(m_sp.forward)(params, tok)
        np.testing.assert_allclose(
            np.asarray(a[..., :cfg.vocab], np.float32),
            np.asarray(b[..., :cfg.vocab], np.float32), rtol=2e-3, atol=2e-3)

        # prefill cache equivalence + continued decode
        ca = m_seq.init_cache(B, T + 4, dtype=jnp.float32)
        cb = m_sp.init_cache(B, T + 4, dtype=jnp.float32)
        la, ca = jax.jit(m_seq.prefill)(params, tok, ca)
        lb, cb = jax.jit(m_sp.prefill)(params, tok, cb)
        np.testing.assert_allclose(
            np.asarray(la[:, -1, :cfg.vocab], np.float32),
            np.asarray(lb[:, -1, :cfg.vocab], np.float32),
            rtol=2e-3, atol=2e-3)
        st_a = jax.tree.map(np.asarray, ca["layers"])
        st_b = jax.tree.map(np.asarray, cb["layers"])
        for x, y in zip(jax.tree.leaves(st_a), jax.tree.leaves(st_b)):
            np.testing.assert_allclose(x, y, rtol=2e-3, atol=2e-3)

        nxt = jnp.argmax(lb[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        da, ca = jax.jit(m_seq.decode_step)(params, ca, nxt)
        db, cb = jax.jit(m_sp.decode_step)(params, cb, nxt)
        np.testing.assert_allclose(
            np.asarray(da[..., :cfg.vocab], np.float32),
            np.asarray(db[..., :cfg.vocab], np.float32),
            rtol=2e-3, atol=2e-3)

        # gradients flow through the SP stack (train step viability)
        def loss(fn):
            def f(p):
                lg, _ = fn(p, tok)
                return jnp.mean(lg[..., : cfg.vocab].astype(jnp.float32) ** 2)
            return f
        ga = jax.grad(loss(m_seq.forward))(params)
        gb = jax.grad(loss(m_sp.forward))(params)
        leaves_a, leaves_b = jax.tree.leaves(ga), jax.tree.leaves(gb)
        err = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
                  for x, y in zip(leaves_a, leaves_b))
        assert err < 5e-2, f"grad mismatch {err}"
    print("OK")
""")


def test_rwkv_sp_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, (r.stderr[-4000:], r.stdout[-500:])
    assert "OK" in r.stdout
