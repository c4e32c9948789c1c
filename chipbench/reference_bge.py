"""Plain reference BGe table for continuous data.

Like ``reference.py`` it imports nothing of the program and takes nothing
the program made. From the samples it forms the moments in float32 under
the highest matmul precision, R = t I + sum (x - xbar)(x - xbar)^T with
t = alpha_mu (alpha_w - n - 1) / (alpha_mu + 1), and scores every column
subset sigma (|sigma| = l <= s) as the parent set of every child i from two
full log-determinants, each by Gaussian elimination of its whole matrix:

    ls(i, sigma) = l ln gamma + c(l) - ((N + a + l + 1)/2) logdet R_F
                   + ((N + a + l)/2) logdet R_sigma,    F = {i} + sigma,

a = alpha_w - n, with c(l) = (1/2) log(alpha_mu / (N + alpha_mu)) +
lgamma((N + a + l + 1)/2) - lgamma((a + l + 1)/2) - (N/2) log pi +
((a + 2l + 1)/2) log t in float64: log p(d_F) - log p(d_sigma) of the
closed form (Geiger and Heckerman 2002; Kuipers, Moffa and Heckerman 2014).
The child is the first row of R_F. A subset smaller than s takes rows of
the identity in its unused places, which leave each determinant unchanged.
Subsets go in blocks of ``CHUNK`` against every child at once, and are
gathered into the program's documented parent-set order as
``reference.py`` does. ``dtype=jnp.bfloat16`` computes the same in
bfloat16: the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _gather_table, _keys, parent_sets

__all__ = ["reference_table", "constants", "row_gap"]

CHUNK = 1024        # column subsets per step (bounds device memory)


def constants(N: int, n: int, s: int, alpha_mu: float,
              alpha_w: float) -> np.ndarray:
    """(3, s + 1) float64: c(l), (N + a + l + 1)/2 and (N + a + l)/2."""
    a = alpha_w - n
    t = alpha_mu * (alpha_w - n - 1) / (alpha_mu + 1)
    ls = range(s + 1)
    return np.asarray([
        [0.5 * math.log(alpha_mu / (N + alpha_mu))
         + math.lgamma((N + a + l + 1) / 2) - math.lgamma((a + l + 1) / 2)
         - N / 2 * math.log(math.pi) + (a + 2 * l + 1) / 2 * math.log(t)
         for l in ls],
        [(N + a + l + 1) / 2 for l in ls],
        [(N + a + l) / 2 for l in ls]])


def _logdet(M):
    """log det of a positive definite matrix, given as a k x k list of
    broadcastable arrays, by Gaussian elimination of the whole matrix."""
    total = 0.0
    while M:
        p = M[0][0]
        total = total + jnp.log(p)
        M = [[M[a][b] - M[a][0] * M[0][b] / p for b in range(1, len(M))]
             for a in range(1, len(M))]
    return total


@functools.partial(jax.jit, static_argnames=("dtype",))
def _subset_scores(x, subsets, consts, t, *, dtype):
    """(U, n) score, less the structure penalty, of every column subset as
    the parent set of every child (entries whose child lies in the subset
    are never read). Each matrix entry is a (C, n), (C, 1) or (1, n)
    array over the block's subsets and the children."""
    m, n = x.shape
    s = subsets.shape[1]
    xc = x - x.mean(0)
    R = (jnp.dot(xc.T, xc, precision=jax.lax.Precision.HIGHEST)
         + t * jnp.eye(n, dtype=x.dtype))
    R = jnp.eye(n + s, dtype=x.dtype).at[:n, :n].set(R).astype(dtype)
    consts = consts.astype(dtype)
    diag = jnp.diagonal(R)[None, :n]                     # R[i, i]

    def chunk(sub):                                       # (C, s)
        cols = jnp.where(sub < 0, n + jnp.arange(s, dtype=jnp.int32), sub)
        size = (sub >= 0).sum(1)
        rows = [R[cols[:, j], :n] for j in range(s)]      # R[sigma_j, i]
        sig = [[R[cols[:, j], cols[:, k]][:, None] for k in range(s)]
               for j in range(s)]
        fam = [[diag] + rows] + [[rows[j]] + sig[j] for j in range(s)]
        c, w_fam, w_sub = consts[:, size]
        return (c[:, None] - w_fam[:, None] * _logdet(fam)
                + w_sub[:, None] * _logdet(sig))

    out = jax.lax.map(chunk, subsets.reshape(-1, CHUNK, s))
    return out.reshape(-1, n)


def reference_table(data: np.ndarray, *, s: int, gamma: float,
                    alpha_mu: float, alpha_w: float,
                    dtype=jnp.float32) -> jax.Array:
    """(n, S) BGe local scores ls(i, pi) of every node and parent set."""
    data = np.asarray(data, np.float64)
    m, n = data.shape
    base = n + 1
    if base ** (s + 1) >= 2 ** 31:
        raise ValueError(f"n = {n}, s = {s}: subset keys overflow int32")
    subsets = parent_sets(n, s)
    keys = _keys(subsets, base)
    pad = (-len(subsets)) % CHUNK
    sub_p = np.pad(subsets, ((0, pad), (0, 0)), constant_values=-1)
    t = alpha_mu * (alpha_w - n - 1) / (alpha_mu + 1)
    with jax.default_matmul_precision("highest"):
        ti = _subset_scores(jnp.asarray(data, jnp.float32),
                            jnp.asarray(sub_p),
                            jnp.asarray(constants(m, n, s, alpha_mu, alpha_w),
                                        jnp.float32),
                            jnp.float32(t), dtype=dtype)
    psets = parent_sets(n - 1, s)
    return _gather_table(ti, jnp.asarray(keys, jnp.int32), jnp.asarray(psets),
                         jnp.asarray((psets >= 0).sum(1), jnp.int32),
                         jnp.asarray(math.log(gamma), jnp.float32),
                         s=s, base=base)


def row_gap(got, want) -> float:
    """Largest |got - want| over the largest |want| of the same node's row:
    a BGe score is a log-density and crosses zero, so each entry is held to
    its node's scale; inf when got is not finite or the shapes differ."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        return float("inf")
    scale = jnp.maximum(jnp.max(jnp.abs(want), axis=1, keepdims=True), 1e-30)
    r = jnp.where(jnp.isfinite(got), jnp.abs(got - want) / scale, jnp.inf)
    return float(jnp.max(r))
