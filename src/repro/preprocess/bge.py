"""BGe local scores per column subset, for continuous data (the Gaussian
counterpart of fused.py's BDeu count+score).

For node i with parents Π (|Π| = l), N samples and a = α_w − n, the BGe
score (core/scores.py gives the closed form and the float64 oracle) is

    ls(i, Π) = l·ln γ + c(l) − ½·logdet R_ΠΠ
               − ((N + a + l + 1)/2)·log(R_ii − R_iΠ R_ΠΠ⁻¹ R_Πi)

with R = t·I + Σ_samples (x − x̄)(x − x̄)ᵀ and c(l) a constant of l alone.
As for BDeu, everything but the structure penalty depends on the child and
the *column set* σ = Π only, so one column subset is scored against every
child at once: one s×s Cholesky of R_σσ, one triangular solve against the
rows R_σ,: for all n children, and the Schur complement's log. The per-subset
output is the same (C, n) ``TI`` the BDeu kernel gives, and the assemblies
(pipeline.py dense, streaming.py pruned) read it unchanged.

A subset smaller than s is padded with identity rows: padding position j
takes column n + j of the padded moments, whose row and column are those of
the identity, so the determinant and the Schur complement are unchanged and
one program serves every subset size. Entries whose child lies in σ are
never read by the assemblies; they are written as 0.

The moments R are one (m, n)ᵀ(m, n) product on the device (span
``preprocess.moments``); the constants c(0..s) and the Schur weights are
float64 on the host. On the TPU the scorer is the Pallas kernel
:func:`bge_scores_pallas` (device op ``%bge_scores*``), which gathers the
subset's rows of R by one-hot matmuls; elsewhere :func:`bge_scores_ref`
does the same arithmetic in jnp. Both run under ``jax.named_scope
("bge_score")``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.scores import bge_log_const, bge_prior
from ..telemetry.spans import span

__all__ = ["bge_constants", "bge_scores_ref", "bge_scores_pallas",
           "bge_chunk_runner"]

_HI = jax.lax.Precision.HIGHEST
_LANES = 128


def bge_constants(N: int, n: int, s: int, alpha_mu: float,
                  alpha_w: float) -> np.ndarray:
    """(2 (s + 1),) float64: c(0..s), the closed form's constants of a
    family of l + 1 less those of its l parents, then the Schur weights
    (N + a + l + 1)/2 for l = 0..s."""
    alpha_w, t = bge_prior(alpha_mu, alpha_w, n)
    const = functools.partial(bge_log_const, N=N, n=n, t=t,
                              alpha_mu=alpha_mu, alpha_w=alpha_w)
    c = [const(l + 1) - const(l) for l in range(s + 1)]
    w = [(N + alpha_w - n + l + 1) / 2 for l in range(s + 1)]
    return np.asarray(c + w, np.float64)


def _schur_scores(entry, rows, diag, size, member, consts, s: int):
    """(C, width) TI from one subset block: ``entry(j, k)`` (C, 1) is
    R[σ_j, σ_k] (k <= j), ``rows[j]`` (C, width) the row R[σ_j, :],
    ``diag`` (1, width) R's diagonal, ``size`` (C, 1) int |σ|, ``member``
    (C, width) child ∈ σ, ``consts`` :func:`bge_constants` (indexable)."""
    L = {}
    logdet = 0.0
    for j in range(s):                                     # R_σσ = L Lᵀ
        d = entry(j, j)
        for k in range(j):
            d = d - L[j, k] * L[j, k]
        L[j, j] = jnp.sqrt(d)
        logdet = logdet + jnp.log(d)
        for i in range(j + 1, s):
            e = entry(i, j)
            for k in range(j):
                e = e - L[i, k] * L[j, k]
            L[i, j] = e / L[j, j]
    schur = diag
    y = []
    for j in range(s):                                     # L y = R_σ,:
        v = rows[j]
        for k in range(j):
            v = v - L[j, k] * y[k]
        v = v / L[j, j]
        y.append(v)
        schur = schur - v * v
    c = w = 0.0
    for l in range(s + 1):
        c = jnp.where(size == l, consts[l], c)
        w = jnp.where(size == l, consts[s + 1 + l], w)
    ti = c - 0.5 * logdet - w * jnp.log(schur)
    return jnp.where(member, 0.0, ti)


def bge_scores_ref(cols: jnp.ndarray, R: jnp.ndarray, consts: jnp.ndarray,
                   *, n: int) -> jnp.ndarray:
    """Pure-jnp scorer: (C, n) TI of column subsets ``cols`` (C, s), padding
    position j holding column n + j of the padded moments ``R``."""
    s = cols.shape[1]
    lane = jnp.arange(R.shape[0], dtype=jnp.int32)[None, :]
    rows = [R[cols[:, j]] for j in range(s)]               # (C, width)
    member = functools.reduce(jnp.logical_or,
                              [lane == cols[:, j:j + 1] for j in range(s)])
    size = jnp.sum(cols < n, axis=1, keepdims=True)
    ti = _schur_scores(lambda j, k: R[cols[:, j], cols[:, k]][:, None], rows,
                       jnp.diagonal(R)[None, :], size, member, consts, s)
    return ti[:, :n]


def _bge_kernel(consts_ref, cols_ref, r_ref, diag_ref, out_ref, *, n: int):
    """One block of subsets: the rows of R at the subset's columns by
    one-hot matmuls (exact at HIGHEST precision), then the Cholesky, the
    solve and the Schur log, with subsets on sublanes and children on
    lanes."""
    cols = cols_ref[...]                                   # (B, s)
    s = cols.shape[1]
    width = r_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols.shape[0], width), 1)
    hot = [lane == cols[:, j:j + 1] for j in range(s)]
    oh = [h.astype(jnp.float32) for h in hot]
    rows = [jnp.dot(o, r_ref[...], precision=_HI,
                    preferred_element_type=jnp.float32) for o in oh]
    member = functools.reduce(jnp.logical_or, hot)
    size = jnp.sum((cols < n).astype(jnp.int32), axis=1, keepdims=True)
    consts = [consts_ref[k] for k in range(2 * (s + 1))]
    ti = _schur_scores(
        lambda j, k: jnp.sum(rows[j] * oh[k], axis=1, keepdims=True),
        rows, diag_ref[...], size, member, consts, s)
    out_ref[...] = ti[:, :n]


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def bge_scores_pallas(cols: jnp.ndarray, R: jnp.ndarray,
                      consts: jnp.ndarray, *, n: int, block: int = 256,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Pallas scorer: (C, n) TI of column subsets ``cols`` (C, s), C a
    multiple of ``block``; ``R`` the padded moments, (width, width) with
    width a multiple of 128."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, s = cols.shape
    width = R.shape[0]
    assert C % block == 0, "pad the subsets to a multiple of block"
    diag = jnp.diagonal(R)[None, :]
    return pl.pallas_call(
        functools.partial(_bge_kernel, n=n),
        grid=(C // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),         # constants
            pl.BlockSpec((block, s), lambda c: (c, 0)),
            pl.BlockSpec((width, width), lambda c: (0, 0)),
            pl.BlockSpec((1, width), lambda c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, n), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((C, n), jnp.float32),
        name="bge_scores",
        interpret=interpret,
    )(consts, cols, R, diag)


@functools.partial(jax.jit, static_argnames=("width",))
def _moments(xc: jnp.ndarray, t: jnp.ndarray, *, width: int) -> jax.Array:
    """(width, width) padded moments: t·I + xcᵀ xc on the n×n block, the
    identity beyond it (the padding columns)."""
    n = xc.shape[1]
    R = jnp.dot(xc.T, xc, precision=_HI) + t * jnp.eye(n, dtype=jnp.float32)
    return jnp.eye(width, dtype=jnp.float32).at[:n, :n].set(R)


@functools.partial(jax.jit, static_argnames=("n", "use_pallas",
                                             "interpret"))
def _run_chunks(R, consts, subs, chunk_ids, *, n, use_pallas, interpret):
    """(U, chunk, n) TI of the planned chunks ``chunk_ids`` of ``subs``."""
    U = chunk_ids.shape[0]
    _, chunk, s = subs.shape
    cols = subs[chunk_ids]                                  # (U, chunk, s)
    with jax.named_scope("bge_score"):
        if not use_pallas:
            return jax.lax.map(
                lambda c: bge_scores_ref(c, R, consts, n=n), cols)
        total = U * chunk
        block = min(256, total + (-total) % 8)
        pad = (-total) % block
        flat = jnp.concatenate(
            [cols.reshape(total, s),
             jnp.broadcast_to(n + jnp.arange(s, dtype=jnp.int32), (pad, s))])
        ti = bge_scores_pallas(flat, R, consts, n=n, block=block,
                               interpret=interpret)
        return ti[:total].reshape(U, chunk, n)


def bge_chunk_runner(data: np.ndarray, lay, devices, *, alpha_mu: float,
                     alpha_w: float | None, use_pallas: bool,
                     interpret: bool | None):
    """run(d, ids, Q) -> (len(ids), chunk, n) TI of the planned chunks
    ``ids`` (an int32 device array on device d) for the continuous
    ``data``, with the moments and the planned subsets on each device
    (``Q``, the bucket's bin count, is 1 and unused)."""
    m, n = data.shape
    s = lay.sub.shape[1]
    alpha_w, t = bge_prior(alpha_mu, alpha_w, n)
    consts = jnp.asarray(bge_constants(m, n, s, alpha_mu, alpha_w),
                         jnp.float32)
    width = n + s
    if use_pallas:
        width += (-width) % _LANES
    # padding position j -> column n + j, a row and column of the identity
    subs = np.where(lay.sub < 0, n + np.arange(s, dtype=np.int32), lay.sub)
    subs = subs.reshape(lay.n_chunks, lay.chunk, s)
    with span("preprocess.moments"):
        # centred in float64 on the host, so an offset column loses no
        # precision in the float32 product
        xc = np.asarray(data - data.mean(0), np.float32)
        R = _moments(jax.device_put(xc, devices[0]), jnp.float32(t),
                     width=width)
    ins = [jax.device_put((R, consts, subs), dev)
           for dev in devices[:lay.n_devices]]

    def run(d, ids, Q):
        return _run_chunks(*ins[d], ids, n=n, use_pallas=use_pallas,
                           interpret=interpret)
    return run
