"""Compile every main-path Pallas kernel for a described TPU v5e chip at the
paper's real widths (n = 60, s = 4, w = 8, q = 3, m = 1000), without a chip,
and the count+score kernel besides at ALARM's published arities (n = 37,
2-4 states, sum r_i = 105) in each bin-count bucket its tables plan.

The dense assembly's device rank map is compiled here too: written as an
(n, S, s) broadcast it needs 28 GB of a 16 GB chip at this size.

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
import numpy as np
import pytest

from repro.core.combinatorics import build_pst, n_parent_sets

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


def _count_score_at(one_chip, n: int, R: int, Q: int):
    from repro.preprocess.fused import fused_scores_pallas
    return _hlo(fused_scores_pallas, one_chip,
                ((CHUNK, M_PAD), jnp.int32), ((M_PAD, R), jnp.float32),
                ((CHUNK,), jnp.int32), ((1, R), jnp.float32),
                ((R, n), jnp.float32), Q=Q, ess=1.0, block_m=BLOCK_M,
                interpret=False)


def _fused_count_score(one_chip):
    return _count_score_at(one_chip, N, N * Q_ARITY, Q_ARITY ** S_MAX)


def _arities(name: str) -> np.ndarray:
    from repro.data.networks import ALARM_ARITY
    return np.asarray(ALARM_ARITY if name == "alarm" else [Q_ARITY] * N)


@pytest.mark.parametrize("name", ["paper60", "alarm"])
def test_count_score_compiles_in_every_bucket_for_v5e(one_chip, name):
    """The count+score kernel at the bin count of every bucket a table build
    plans (each is a program of its own): at the paper's n = 60, q = 3 and
    at ALARM's published arities (sum r_i = 105)."""
    from repro.preprocess.planner import plan_subsets
    r = _arities(name)
    lay = plan_subsets(build_pst(len(r), S_MAX)[0], r, CHUNK, M, 1)
    buckets = [Q for Q, _, _ in lay.buckets]
    assert len(buckets) <= 6 and buckets[-1] == np.prod(
        np.sort(r)[::-1][:S_MAX])
    for Q in buckets:
        assert "tpu_custom_call" in _count_score_at(
            one_chip, len(r), int(r.sum()), Q), Q


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


def test_rank_map_compiles_for_v5e_in_one_pass(one_chip):
    """The (n, S) int32 rank map fuses into passes that keep no intermediate
    as large as its output (an (n, S, s) layout would pad s = 4 to 128 lanes
    and run out of HBM)."""
    from repro.preprocess.pipeline import _rank_map
    args = [jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
            for shape in ((S_SETS, S_MAX), (S_SETS,))]
    mem = _rank_map.lower(N, S_MAX, *args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes, (
        mem.temp_size_in_bytes, mem.output_size_in_bytes)


def test_bge_scorer_compiles_for_v5e(one_chip):
    """The BGe scorer of a dense n = 64, s = 4 table build (the magicirri64
    configuration: 679,121 column subsets in chunks of 1024, moments padded
    to 128 lanes), as the build dispatches it: the Pallas kernel under the
    chunk gather, in one program."""
    from repro.core.combinatorics import n_parent_sets as subsets
    from repro.preprocess.bge import _run_chunks
    n, s = 64, S_MAX
    n_chunks = -(-subsets(n, s) // CHUNK)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((128, 128), jnp.float32),
                                 ((2 * (s + 1),), jnp.float32),
                                 ((n_chunks, CHUNK, s), jnp.int32),
                                 ((n_chunks,), jnp.int32))]
    compiled = _run_chunks.lower(*args, n=n, use_pallas=True,
                                 interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "bge_scores" in compiled.as_text()
