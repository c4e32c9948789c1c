"""Compile every main-path Pallas kernel for a described TPU v5e chip at the
paper's real widths (n = 60, s = 4, w = 8, q = 3, m = 1000), without a chip.

Interpret mode checks numerics but not the TPU compiler's rules (block
tiling, VMEM, unsupported primitives); these compiles do. Each test asserts
the compiled HLO holds a Mosaic kernel (`tpu_custom_call`), so a kernel that
silently fell back to XLA fails here too.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.combinatorics import n_parent_sets

N, S_MAX, W, Q_ARITY, M = 60, 4, 8, 3, 1000
CHAINS = 8                                         # the MCMC vmaps over these
BLOCK_S, BLOCK_M, CHUNK = 4096, 512, 1024
S_SETS = n_parent_sets(N - 1, S_MAX)               # 489,406 parent sets
S_PAD = S_SETS + (-S_SETS) % BLOCK_S
M_PAD = M + (-M) % BLOCK_M
N_PLANES = 3                                       # violation counts 0..s


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, shardings, *shapes, chains: int = 0, in_axes=0, **static):
    """Compiled HLO of fn at these shapes; with `chains`, of fn vmapped over
    a leading chain axis of the arguments `in_axes` marks, as the
    multi-chain MCMC runs the order-score kernels (the table and PST are
    shared by every chain, the rest is per chain)."""
    if chains:
        axes = in_axes if isinstance(in_axes, tuple) else (0,) * len(shapes)
        shapes = [(shape if ax is None else (chains,) + shape, dtype)
                  for (shape, dtype), ax in zip(shapes, axes)]
        fn = jax.jit(jax.vmap(functools.partial(fn, **static),
                              in_axes=axes))
        static = {}
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=shardings)
            for shape, dtype in shapes]
    return fn.lower(*args, **static).compile().as_text()


def _order_score_window(one_chip, chains=0):
    from repro.kernels.order_score.kernel import order_score_window_pallas
    return _hlo(order_score_window_pallas, one_chip,
                ((W, S_PAD), jnp.float32), ((W,), jnp.int32),
                ((S_PAD, S_MAX), jnp.int32), ((N,), jnp.int32),
                chains=chains, in_axes=(0, 0, None, 0), block_s=BLOCK_S,
                interpret=False)


def _order_score_full(one_chip, chains=0):
    from repro.kernels.order_score.kernel import order_score_pallas
    return _hlo(order_score_pallas, one_chip,
                ((N, S_PAD), jnp.float32), ((S_PAD, S_MAX), jnp.int32),
                ((N,), jnp.int32), chains=chains, in_axes=(None, None, 0),
                block_s=BLOCK_S, interpret=False)


def _order_score_bitmask(one_chip, chains=0):
    from repro.kernels.order_score.kernel import \
        order_score_window_bitmask_pallas
    return _hlo(order_score_window_bitmask_pallas, one_chip,
                ((W, S_PAD), jnp.float32), ((W, S_PAD // 32), jnp.uint32),
                chains=chains, block_s=BLOCK_S, interpret=False)


def _order_score_bitmask_fused(one_chip, chains=0):
    from repro.kernels.order_score.kernel import \
        order_score_window_bitmask_fused_pallas
    words = S_PAD // 32
    return _hlo(order_score_window_bitmask_fused_pallas, one_chip,
                ((W, S_PAD), jnp.float32), ((W,), jnp.int32),
                ((N,), jnp.int32), ((N,), jnp.int32),
                ((W, N_PLANES, words), jnp.uint32),
                ((W, words), jnp.uint32), ((W, words), jnp.uint32),
                chains=chains, block_s=BLOCK_S, interpret=False)


def _count(one_chip):
    from repro.kernels.count.kernel import count_pallas
    return _hlo(count_pallas, one_chip,
                ((CHUNK, M_PAD), jnp.int32), ((M_PAD, Q_ARITY), jnp.float32),
                Q=Q_ARITY ** S_MAX, block_m=BLOCK_M, interpret=False)


def _fused_count_score(one_chip):
    from repro.preprocess.fused import fused_scores_pallas
    return _hlo(fused_scores_pallas, one_chip,
                ((CHUNK, M_PAD), jnp.int32),
                ((M_PAD, N * Q_ARITY), jnp.float32), ((CHUNK,), jnp.int32),
                q=Q_ARITY, s=S_MAX, n=N, ess=1.0, block_m=BLOCK_M,
                interpret=False)


@pytest.mark.parametrize("build", [
    _order_score_window, _order_score_full, _order_score_bitmask,
    _order_score_bitmask_fused, _count, _fused_count_score,
], ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, build):
    assert "tpu_custom_call" in build(one_chip)


@pytest.mark.parametrize("build", [
    _order_score_window, _order_score_full, _order_score_bitmask,
    _order_score_bitmask_fused,
], ids=lambda f: f.__name__.lstrip("_"))
def test_chain_vmapped_kernel_compiles_for_v5e(one_chip, build):
    """vmap over chains adds a squeezed leading block dim to every operand;
    the blocks must stay legal with it."""
    assert "tpu_custom_call" in build(one_chip, chains=CHAINS)
