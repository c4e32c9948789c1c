"""Fused count+score for one column-subset chunk (paper §III-A on-device).

The reference preprocessing (core/scores.build_score_table) materialises a
(C, q^s, q) contingency tensor per (node, chunk) unit and re-builds the
parent-config one-hot for every node. The fused formulation exploits two
identities:

* **Count once per column subset, against every child at once.** The
  contingency counts for parent set pi of node i depend only on the *column
  set* sigma = columns(pi, i) and the child column i. Counting sigma jointly
  against the one-hot of ALL n columns — one (Q x m) @ (m x n*q) matmul —
  amortises the (m, C, Q) one-hot build over all n children, an ~n-fold cut
  in the memory traffic that dominates preprocessing.

* **Scores depend on counts only through small integer marginals.** With a
  uniform arity q, Eq. 4's gammaln terms take only (s+1) x (m+1) distinct
  values: gammaln(N + alpha) for integer N in [0, m] and alpha determined by
  |pi|. The ref path replaces gammaln evaluation with two precomputed lookup
  tables (:func:`score_luts`), turning the transcendental bulk of scoring into
  gathers; the Pallas kernel evaluates gammaln (:func:`lgamma_f32`) directly
  on the (Q, n*q) counts block it just produced in VMEM — either way the
  (C, q^s, q) tensor never reaches HBM, only the (C, n) fused output does.

The per-subset output is ``TI[c, i] = sum_{k active} (term_k + term_jk)`` —
everything of ls(i, pi) except the |pi|*ln(gamma) structure penalty, which the
assembly (pipeline.py) adds per PST entry. The bin reduction is an explicitly
SEQUENTIAL accumulation over the q^s bins so it reproduces the oracle's
row-sum order: fused tables match `local_scores_chunk` bitwise on CPU (the
property tests in tests/test_preprocess.py pin this to <= 1e-4 absolute).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.special import gammaln

__all__ = ["score_luts", "fused_scores_ref", "fused_scores_pallas",
           "encode_subset_codes", "lgamma_f32"]


def score_luts(q: int, s: int, m: int, ess: float):
    """(lut_k, lut_j), each (s+1, m+1) f32: the two gammaln families of Eq. 4
    tabulated over parent-set size k (rows) and integer count N (cols).

    lut_k[k, N] = gammaln(a_k) - gammaln(a_k + N),   a_k  = ess / q^k
    lut_j[k, N] = gammaln(N + a_jk) - gammaln(a_jk), a_jk = ess / (q^k * q)

    Built with the same f32 ops as the oracle (jnp.power, jax gammaln) so the
    tabulated values are bitwise the oracle's.
    """
    ks = jnp.arange(s + 1, dtype=jnp.float32)
    r = jnp.power(float(q), ks)
    a_k = (ess / r)[:, None]
    a_jk = (ess / (r * q))[:, None]
    counts = jnp.arange(m + 1, dtype=jnp.float32)[None, :]
    lut_k = gammaln(a_k) - gammaln(a_k + counts)
    lut_j = gammaln(counts + a_jk) - gammaln(a_jk)
    return lut_k, lut_j


def encode_subset_codes(data_ext: jnp.ndarray, sub_chunk: jnp.ndarray,
                        q: int) -> jnp.ndarray:
    """Mixed-radix configuration codes for a chunk of column subsets.

    data_ext: (m, n+1) with an appended all-zeros column; sub_chunk: (C, s)
    sorted column indices, -1 padded (padding maps to the zeros column, so
    padded digit positions are the HIGH digits and contribute 0 — which is
    what makes `code < q^{|subset|}` the exact active-bin test).
    Returns (m, C) int32.
    """
    n = data_ext.shape[1] - 1
    cols = jnp.where(sub_chunk < 0, n, sub_chunk)        # (C, s)
    dcols = data_ext[:, cols]                            # (m, C, s)
    pw = q ** jnp.arange(sub_chunk.shape[1], dtype=jnp.int32)
    return jnp.sum(dcols * pw, axis=-1).astype(jnp.int32)


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


@functools.partial(jax.jit, static_argnames=("q", "s", "n"))
def fused_scores_ref(data_ext: jnp.ndarray, child_oh: jnp.ndarray,
                     sub_chunk: jnp.ndarray, ssz_chunk: jnp.ndarray,
                     lut_k: jnp.ndarray, lut_j: jnp.ndarray, *,
                     q: int, s: int, n: int) -> jnp.ndarray:
    """Pure-jnp fused chunk: (C, n) TI for one chunk of column subsets.

    child_oh: (m, n*q) one-hot of every column (built once per table).
    Counts are produced by one MXU-shaped contraction, immediately consumed
    by LUT gathers, and discarded — the only chunk output is (C, n).
    """
    C = sub_chunk.shape[0]
    Q = q ** s
    code = encode_subset_codes(data_ext, sub_chunk, q)               # (m, C)
    oh = jax.nn.one_hot(code, Q, dtype=jnp.float32)                  # (m, C, Q)
    counts = jnp.round(jnp.einsum("mcQ,mJ->cQJ", oh, child_oh)
                       ).astype(jnp.int32)                           # (C, Q, n*q)
    sz = ssz_chunk
    Nk = counts[:, :, 0:q].sum(-1)                                   # (C, Q)
    bins = jnp.arange(Q, dtype=jnp.float32)[None, :]
    active = bins + 0.5 < jnp.power(float(q), sz.astype(jnp.float32))[:, None]
    term_k = lut_k[sz[:, None], Nk]                                  # (C, Q)
    term_j = lut_j[sz[:, None, None], counts]                        # (C, Q, n*q)
    tj = term_j.reshape(C, Q, n, q).sum(-1)                          # (C, Q, n)
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


def _fused_kernel(sizes_ref, codes_ref, child_oh_ref, out_ref, counts_ref, *,
                  Q: int, q: int, n: int, block_m: int, ess: float):
    """Per (subset, m-block) program: accumulate the (Q, n*q) counts block in
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
                               preferred_element_type=jnp.float32)  # (Q, n*q)

    @pl.when(mb == nmb - 1)
    def _score():
        counts = counts_ref[...]
        szf = jnp.full((1, 1), sizes_ref[c], jnp.int32).astype(jnp.float32)
        r = jnp.power(float(q), szf)                                 # (1, 1)
        a_k = ess / r
        a_jk = ess / (r * q)
        Nk = jnp.sum(counts[:, 0:q], axis=-1, keepdims=True)         # (Q, 1)
        term_k = lgamma_f32(a_k) - lgamma_f32(a_k + Nk)              # (Q, 1)
        gl = lgamma_f32(counts + a_jk) - lgamma_f32(a_jk)            # (Q, n*q)
        # per-child j-sum as an MXU matmul with a block-diagonal 0/1 matrix
        # (avoids an in-kernel reshape, which Mosaic restricts); fp32
        # contraction, since gl is not representable in bf16
        col = jax.lax.broadcasted_iota(jnp.int32, (n * q, n), 0) // q
        tgt = jax.lax.broadcasted_iota(jnp.int32, (n * q, n), 1)
        sum_mat = (col == tgt).astype(jnp.float32)                   # (n*q, n)
        tj = jnp.dot(gl, sum_mat, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)             # (Q, n)
        kbins = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
        active = (kbins.astype(jnp.float32) + 0.5 < r).astype(jnp.float32)
        masked = active * (tj + term_k)                              # (Q, n)
        acc = masked[0:1, :]                  # bins in order, as the oracle
        for k in range(1, Q):
            acc = acc + masked[k:k + 1, :]
        out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("q", "s", "n", "ess", "block_m",
                                             "interpret"))
def fused_scores_pallas(codes: jnp.ndarray, child_oh: jnp.ndarray,
                        ssz_chunk: jnp.ndarray, *, q: int, s: int, n: int,
                        ess: float = 1.0, block_m: int = 512,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Pallas fused count+score. codes: (C, m) int32 subset config codes with
    -1 sample padding; child_oh: (m, n*q) one-hot of all columns (padded rows
    contribute nothing); ssz_chunk: (C,) subset sizes. Returns (C, n) TI.
    m must already be padded to a multiple of block_m.

    Codes enter as (C, 1, m) and the output leaves as (C, 1, n): a subset's
    tiles are then (1, block_m) and (1, n) rows, legal TPU blocks."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, m = codes.shape
    Q = q ** s
    assert m % block_m == 0, "pad m to a multiple of block_m (codes with -1)"
    grid = (C, m // block_m)
    kernel = functools.partial(_fused_kernel, Q=Q, q=q, n=n,
                               block_m=block_m, ess=ess)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # subset sizes
            pl.BlockSpec((None, 1, block_m), lambda c, mb: (c, 0, mb)),
            pl.BlockSpec((block_m, n * q), lambda c, mb: (mb, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, n), lambda c, mb: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Q, n * q), jnp.float32)],
        interpret=interpret,
    )(ssz_chunk.astype(jnp.int32), codes[:, None, :], child_oh)
    return out[:, 0, :]
