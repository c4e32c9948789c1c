"""Pallas TPU kernel for preprocessing contingency counting (beyond-paper:
the paper leaves preprocessing acceleration as future work, §VII).

N[c, k, j] = #{samples: parent-config-code == k and child-state == j} for a
batch of parent sets c. Formulated as a one-hot × one-hot matmul so the MXU
does the counting: counts_c = onehot(code_c)^T @ onehot(child), a
(Q × m) · (m × q) product per parent set. Grid streams parent sets; the
sample axis m is tiled into VMEM blocks and accumulated in the revisited
output block (sequential grid).

Codes enter as (C, 1, m) so a parent set's sample tile is a (1, block_m)
row — a legal TPU block (the unit dim spans its array dim, block_m is a
multiple of 128) — and the one-hot is built transposed, (Q, block_m), which
makes the contraction a plain (Q, BM) @ (BM, q) matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _count_kernel(codes_ref, child_oh_ref, out_ref, *, Q: int, block_m: int):
    mb = pl.program_id(1)

    @pl.when(mb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                       # (1, BM) int32, -1 = padding
    # a padded sample's code (-1) matches no bin, so its one-hot column is
    # all-zero and whatever the caller padded child_oh with contributes 0:
    # correctness does not hinge on a zero-padded child one-hot
    bins = jax.lax.broadcasted_iota(jnp.int32, (Q, block_m), 0)
    oh_t = (bins == codes).astype(jnp.float32)   # (Q, BM)
    # MXU contraction over samples: (Q, BM) @ (BM, q); 0/1 operands and
    # integer sums below 2^24 are exact at any matmul precision
    out_ref[...] += jnp.dot(oh_t, child_oh_ref[...],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("Q", "block_m", "interpret"))
def count_pallas(codes: jnp.ndarray, child_oh: jnp.ndarray, *, Q: int,
                 block_m: int = 512, interpret: bool = False) -> jnp.ndarray:
    """codes: (C, m) int32 mixed-radix parent configs (-1 = padded sample);
    child_oh: (m, q) one-hot child states. Returns (C, Q, q) f32 counts.
    m must be a multiple of block_m (pad codes with -1)."""
    C, m = codes.shape
    q = child_oh.shape[1]
    assert m % block_m == 0, "pad m to a multiple of block_m"
    grid = (C, m // block_m)
    kernel = functools.partial(_count_kernel, Q=Q, block_m=block_m)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, block_m), lambda c, mb: (c, 0, mb)),
            pl.BlockSpec((block_m, q), lambda c, mb: (mb, 0)),
        ],
        out_specs=pl.BlockSpec((None, Q, q), lambda c, mb: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, Q, q), jnp.float32),
        interpret=interpret,
    )(codes[:, None, :], child_oh)
