"""Fused count+score for one column-subset chunk (paper §III-A on-device).

The reference preprocessing (core/scores.build_score_table) materialises a
(C, Q, r_max) contingency tensor per (node, chunk) unit and re-builds the
parent-config one-hot for every node. The fused formulation exploits one
identity:

* **Count once per column subset, against every child at once.** The
  contingency counts for parent set pi of node i depend only on the *column
  set* sigma = columns(pi, i) and the child column i. Counting sigma jointly
  against the one-hot of ALL n columns — one (Q x m) @ (m x R) matmul, R =
  sum_i r_i — amortises the (m, C, Q) one-hot build over all n children, an
  ~n-fold cut in the memory traffic that dominates preprocessing.

Arities may differ per variable (core/scores.arity_vector). A subset's
configuration code is mixed-radix (core/scores.mixed_radix), so its codes
fill exactly the first q_sigma = prod_{j in sigma} r_j bins. A chunk is
computed at a static bin count Q >= every q_sigma in it (its bucket,
planner.q_buckets), and the bins past q_sigma count nothing and add +0.0.
Child i owns columns [off_i, off_i + r_i) of the child one-hot. BDeu takes
alpha_j = ess / q_sigma and alpha_jk = ess / (q_sigma r_i), so the per-subset
q_sigma and each column's child arity enter the scoring; at a uniform q
they are q^|sigma| and q, the values the scores always had.

The jnp path (:func:`fused_scores_ref`) scores through two lookup tables
(:func:`score_luts`): Eq. 4's gammaln terms depend on the counts only
through integer N in [0, m] and one alpha per q_sigma or q_sigma * r_i, so
the transcendental bulk of scoring becomes gathers.

The per-subset output is ``TI[c, i] = sum_{k active} (term_k + term_jk)`` —
everything of ls(i, pi) except the |pi|*ln(gamma) structure penalty, which the
assembly (pipeline.py) adds per PST entry. Only the (C, n) output reaches
HBM. The bin reduction is an explicitly SEQUENTIAL accumulation over the
bins so it reproduces the oracle's row-sum order: fused tables match
`local_scores_chunk` bitwise on CPU (the property tests in
tests/test_preprocess.py pin this to <= 1e-4 absolute). The Pallas kernel
evaluates gammaln (:func:`lgamma_f32`) on the counts block it just produced
in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.special import gammaln

from ..core.scores import mixed_radix

__all__ = ["child_columns", "score_luts", "fused_scores_ref",
           "fused_scores_pallas", "encode_subset_codes", "lgamma_f32"]


def child_columns(arity: jnp.ndarray, col_child: jnp.ndarray):
    """(state (R,), child arity (R,) float32) of every child one-hot column,
    given the child each column belongs to (``np.repeat(arange(n), r)``):
    child i owns columns [off_i, off_i + r_i), off = exclusive cumsum of r."""
    off = jnp.cumsum(arity) - arity
    state = jnp.arange(col_child.shape[0], dtype=jnp.int32) - off[col_child]
    return state, arity[col_child].astype(jnp.float32)


def encode_subset_codes(data_ext: jnp.ndarray, sub_chunk: jnp.ndarray,
                        arity_ext: jnp.ndarray) -> jnp.ndarray:
    """Mixed-radix configuration codes for a chunk of column subsets.

    data_ext: (m, n+1) with an appended all-zeros column; sub_chunk: (C, s)
    sorted column indices, -1 padded (padding maps to the zeros column, of
    arity 1 in ``arity_ext``, so padded digit positions contribute 0 — which
    is what makes `code < q_sigma` the exact active-bin test).
    Returns (m, C) int32.
    """
    n = data_ext.shape[1] - 1
    cols = jnp.where(sub_chunk < 0, n, sub_chunk)        # (C, s)
    strides, _ = mixed_radix(arity_ext, cols)            # (C, s)
    dcols = data_ext[:, cols]                            # (m, C, s)
    return jnp.sum(dcols * strides, axis=-1).astype(jnp.int32)


def _sequential_bin_sum(masked: jnp.ndarray) -> jnp.ndarray:
    """(C, Q, n) -> (C, n), accumulating the Q bins strictly in order — the
    same association order as the oracle's (C, Q) row sum, which is what keeps
    fused == reference at the ulp level."""
    C, _, n = masked.shape

    def step(acc, x):
        return acc + x, None

    acc, _ = jax.lax.scan(step, jnp.zeros((C, n), jnp.float32),
                          jnp.moveaxis(masked, 1, 0))
    return acc


def score_luts(qsig: np.ndarray, r: np.ndarray, m: int, ess: float):
    """(vk, lut_k, vj, lut_j): the two gammaln families of Eq. 4 tabulated
    over integer counts N in [0, m], one row per value their alpha takes.

    vk: the distinct q_sigma of ``qsig``; lut_k[i, N] = gammaln(a) -
    gammaln(a + N), a = ess / vk[i].
    vj: the distinct q_sigma * r_i; lut_j[i, N] = gammaln(N + a) -
    gammaln(a), a = ess / vj[i].

    At a uniform q the rows are |sigma| = 0..s, (s+1) x (m+1) values each.
    Built with the same f32 ops as the oracle (jax gammaln of ess over the
    exact integer q_sigma), so the tabulated values are bitwise the
    oracle's; gathered inside the jitted chunk, they keep the scores free of
    how XLA fuses a transcendental into its consumers."""
    vk = np.unique(qsig).astype(np.int64)
    vj = np.unique(vk[:, None] * np.unique(r)[None, :])
    counts = jnp.arange(m + 1, dtype=jnp.float32)[None, :]
    a_k = (ess / jnp.asarray(vk, jnp.float32))[:, None]
    a_jk = (ess / jnp.asarray(vj, jnp.float32))[:, None]
    lut_k = gammaln(a_k) - gammaln(a_k + counts)
    lut_j = gammaln(counts + a_jk) - gammaln(a_jk)
    return (jnp.asarray(vk, jnp.int32), lut_k, jnp.asarray(vj, jnp.int32),
            lut_j)


@functools.partial(jax.jit, static_argnames=("Q", "r_max"))
def fused_scores_ref(data_ext: jnp.ndarray, arity: jnp.ndarray,
                     sub_chunk: jnp.ndarray, qsig_chunk: jnp.ndarray, luts,
                     *, Q: int, r_max: int) -> jnp.ndarray:
    """Pure-jnp fused chunk: (C, n) TI for one chunk of column subsets.

    arity: (n,) int32 states per column, the largest r_max; qsig_chunk:
    (C,) each subset's q_sigma <= Q; luts: :func:`score_luts`. Every child
    gets r_max one-hot columns (states past its arity count 0 and score 0),
    so the per-child sums are one reshape. Counts are produced by one
    MXU-shaped contraction, immediately consumed by LUT gathers, and
    discarded — the only chunk output is (C, n).
    """
    vk, lut_k, vj, lut_j = luts
    C = sub_chunk.shape[0]
    n = arity.shape[0]
    arity_ext = jnp.concatenate([arity, jnp.ones((1,), arity.dtype)])
    col_child = jnp.repeat(jnp.arange(n, dtype=jnp.int32), r_max)
    state = jnp.tile(jnp.arange(r_max, dtype=jnp.int32), n)
    child_oh = (data_ext[:, col_child] == state[None, :]
                ).astype(jnp.float32)                          # (m, n*r_max)
    code = encode_subset_codes(data_ext, sub_chunk, arity_ext)       # (m, C)
    oh = jax.nn.one_hot(code, Q, dtype=jnp.float32)                  # (m, C, Q)
    counts = jnp.round(jnp.einsum("mcQ,mJ->cQJ", oh, child_oh)
                       ).astype(jnp.int32)                # (C, Q, n*r_max)
    Nk = counts[:, :, 0:r_max].sum(-1)                               # (C, Q)
    ik = jnp.searchsorted(vk, qsig_chunk)                            # (C,)
    ij = jnp.searchsorted(vj, qsig_chunk[:, None] * arity[col_child])
    term_k = lut_k[ik[:, None], Nk]                                  # (C, Q)
    term_j = lut_j[ij[:, None, :], counts]                # (C, Q, n*r_max)
    tj = term_j.reshape(C, Q, n, r_max).sum(-1)                # (C, Q, n)
    active = jnp.arange(Q)[None, :] < qsig_chunk[:, None]            # (C, Q)
    masked = active[:, :, None] * (tj + term_k[:, :, None])
    return _sequential_bin_sum(masked)                               # (C, n)


# Lanczos coefficients (g = 7, n = 9) of XLA's lgamma expansion
_LANCZOS = (676.520368121885098567009190444019,
            -1259.13921672240287047156078755283,
            771.3234287776530788486528258894,
            -176.61502916214059906584551354,
            12.507343278686904814458936853,
            -0.13857109526572011689554707,
            9.984369578019570859563e-6,
            1.50563273514931155834e-7)


def lgamma_f32(x: jnp.ndarray) -> jnp.ndarray:
    """log|Gamma(x)| built from the elementwise ops of XLA's own lgamma
    expansion, op for op and in the same order, so it is bitwise
    ``jax.lax.lgamma`` wherever both compile to the same elementwise ops (the
    CPU backend; tests/test_preprocess.py pins it). The Pallas TPU lowering
    has no lgamma primitive, so the fused kernel scores with this."""
    reflect = x < 0.5
    z = jnp.where(reflect, -x, x - 1.0)
    a = _LANCZOS[0] / (z + 1.0) + 1.0
    for i, c in enumerate(_LANCZOS[1:], start=2):
        a = a + c / (z + float(i))
    t = z + 7.5
    log_t = jnp.log1p(z / 7.5) + float(np.log(7.5))
    r = (z + 0.5 - t / log_t) * log_t
    lg = r + float((np.log(2) + np.log(np.pi)) / 2) + jnp.log(a)
    # reflection for x < 0.5: lgamma(x) = log(pi) - log|sin(pi x)| - lg
    frac = jnp.abs(x) - jnp.floor(jnp.abs(x))
    frac = jnp.where(0.5 < frac, 1.0 - frac, frac)
    log_sin = jnp.log(jnp.sin(frac * float(np.pi)))
    refl = jnp.where(jnp.isfinite(log_sin),
                     float(np.log(np.pi)) - log_sin - lg, -log_sin)
    out = jnp.where(reflect, refl, lg)
    return jnp.where(jnp.abs(x) == jnp.inf, jnp.inf, out)


def _fused_kernel(qsig_ref, codes_ref, child_oh_ref, col_r_ref, sum_mat_ref,
                  out_ref, counts_ref, *, Q: int, block_m: int, ess: float):
    """Per (subset, m-block) program: accumulate the (Q, R) counts block in
    VMEM, and on the last m-block collapse it straight to the (n,) fused
    scores — the counts never leave VMEM (the fusion the paper leaves as
    future work, §VII)."""
    c = pl.program_id(0)
    mb = pl.program_id(1)
    nmb = pl.num_programs(1)

    @pl.when(mb == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    codes = codes_ref[...]                               # (1, BM), -1 pad
    # a padded sample (-1) matches no bin: its one-hot column is all-zero, so
    # whatever the caller padded child_oh with contributes nothing
    bins = jax.lax.broadcasted_iota(jnp.int32, (Q, block_m), 0)
    oh_t = (bins == codes).astype(jnp.float32)           # (Q, BM)
    counts_ref[...] += jnp.dot(oh_t, child_oh_ref[...],
                               preferred_element_type=jnp.float32)  # (Q, R)

    @pl.when(mb == nmb - 1)
    def _score():
        counts = counts_ref[...]
        col_r = col_r_ref[...]                           # (1, R) child arity
        # q_sigma as an exact integer: Mosaic's powf is inexact (3.0 ** 2
        # reads 9.0000114 on a v5e), so no q ** |sigma| is formed here
        r = jnp.full((1, 1), qsig_ref[c], jnp.int32).astype(jnp.float32)
        a_k = ess / r                                                # (1, 1)
        a_jk = ess / (r * col_r)                                     # (1, R)
        # N_k: any one child's columns sum to it; take child 0's [0, r_0)
        lane = jax.lax.broadcasted_iota(jnp.int32, col_r.shape, 1)
        first = lane.astype(jnp.float32) < col_r[:, 0:1]
        Nk = jnp.sum(jnp.where(first, counts, 0.0), axis=-1,
                     keepdims=True)                                  # (Q, 1)
        term_k = lgamma_f32(a_k) - lgamma_f32(a_k + Nk)              # (Q, 1)
        gl = lgamma_f32(counts + a_jk) - lgamma_f32(a_jk)            # (Q, R)
        # per-child j-sum as an MXU matmul with the block-diagonal 0/1
        # (R, n) matrix (avoids an in-kernel reshape, which Mosaic
        # restricts); fp32 contraction, since gl is not representable in bf16
        tj = jnp.dot(gl, sum_mat_ref[...],
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)             # (Q, n)
        kbins = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
        active = (kbins.astype(jnp.float32) + 0.5 < r).astype(jnp.float32)
        masked = active * (tj + term_k)                              # (Q, n)
        acc = masked[0:1, :]                  # bins in order, as the oracle
        for k in range(1, Q):
            acc = acc + masked[k:k + 1, :]
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("Q", "ess", "block_m",
                                             "interpret"))
def fused_scores_pallas(codes: jnp.ndarray, child_oh: jnp.ndarray,
                        qsig_chunk: jnp.ndarray, col_r: jnp.ndarray,
                        sum_mat: jnp.ndarray, *, Q: int, ess: float = 1.0,
                        block_m: int = 512,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Pallas fused count+score. codes: (C, m) int32 subset config codes with
    -1 sample padding, each < Q; child_oh: (m, R) one-hot of all columns
    (padded rows contribute nothing); qsig_chunk: (C,) each subset's
    q_sigma; col_r: (1, R) float32 arity of each column's child; sum_mat:
    (R, n) float32, 1 where column k belongs to child i. Returns (C, n) TI.
    m must already be padded to a multiple of block_m.

    Codes enter as (C, 1, m) and the output leaves as (C, 1, n): a subset's
    tiles are then (1, block_m) and (1, n) rows, legal TPU blocks."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, m = codes.shape
    R = sum_mat.shape[0]                  # child one-hot width, sum_i r_i
    n = sum_mat.shape[1]
    assert m % block_m == 0, "pad m to a multiple of block_m (codes with -1)"
    grid = (C, m // block_m)
    kernel = functools.partial(_fused_kernel, Q=Q, block_m=block_m, ess=ess)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # subset q_sigma
            pl.BlockSpec((None, 1, block_m), lambda c, mb: (c, 0, mb)),
            pl.BlockSpec((block_m, R), lambda c, mb: (mb, 0)),
            pl.BlockSpec((1, R), lambda c, mb: (0, 0)),
            pl.BlockSpec((R, n), lambda c, mb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, n), lambda c, mb: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Q, R), jnp.float32)],
        interpret=interpret,
    )(qsig_chunk.astype(jnp.int32), codes[:, None, :], child_oh, col_r,
      sum_mat)
    return out[:, 0, :]
