"""Plain reference BDeu table at per-variable arities.

Like ``reference.py`` it imports nothing of the program and takes nothing
the program made: it recounts every (parent configuration, child state)
cell from the samples and evaluates every log-gamma term directly, with no
kernel and no lookup table. Variable i has r_i states; a column subset
sigma has q_sigma = prod_{j in sigma} r_j configurations, coded mixed-radix
with the first column the lowest digit. The score is BDeu with
``alpha_j = ess / q_sigma`` and ``alpha_jk = ess / (q_sigma r_i)``, plus
the structure penalty ``|pi| ln gamma``, in the program's documented
parent-set order (``reference.parent_sets``).

Counts are one-hot matmuls over the samples, once per column subset against
every child at once, in float32 under the highest matmul precision (0/1
operands, so they are exact), in blocks of ``reference.CHUNK`` subsets.
Every child gets max(r) state columns; states past its own count 0 and add
0. At one arity for every variable this is ``reference.reference_table``'s
score; ``dtype=jnp.bfloat16`` computes the same in bfloat16: the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import CHUNK, _gather_table, _keys, parent_sets

__all__ = ["reference_table"]


@functools.partial(jax.jit, static_argnames=("Q", "r_max", "dtype"))
def _subset_scores(data, arity, subsets, qsig, *, Q, r_max, ess, dtype):
    """(U, n) score of every column subset as the parent set of every child
    (entries whose child lies in the subset are never read)."""
    m, n = data.shape
    child = (data[:, :, None] == jnp.arange(r_max)[None, None, :]
             ).astype(jnp.float32).reshape(m, n * r_max)
    data_ext = jnp.concatenate([data, jnp.zeros((m, 1), data.dtype)], 1)
    arity_ext = jnp.concatenate([arity, jnp.ones((1,), arity.dtype)])
    lg = jax.lax.lgamma

    def chunk(args):
        sub, qs = args                                  # (C, s), (C,)
        cols = jnp.where(sub < 0, n, sub)
        r = arity_ext[cols]
        stride = jnp.concatenate(
            [jnp.ones_like(r[:, :1]), jnp.cumprod(r[:, :-1], axis=1)], 1)
        code = (data_ext[:, cols] * stride).sum(-1)     # (m, C)
        oh = (code.T[:, None, :] ==
              jnp.arange(Q, dtype=jnp.int32)[None, :, None])
        cnt = jnp.einsum("cjm,mx->cjx", oh.astype(jnp.float32), child,
                         precision=jax.lax.Precision.HIGHEST)
        cnt = cnt.reshape(-1, Q, n, r_max).astype(dtype)  # exact counts
        qf = qs.astype(dtype)
        a_j = (ess / qf).astype(dtype)[:, None, None]              # (C, 1, 1)
        a_jk = (ess / (qf[:, None] * arity.astype(dtype)[None, :])
                ).astype(dtype)[:, None, :, None]               # (C, 1, n, 1)
        n_j = cnt.sum(-1)                                          # (C, Q, n)
        t_j = lg(a_j) - lg(a_j + n_j)
        t_jk = (lg(cnt + a_jk) - lg(a_jk)).sum(-1)
        return (t_j + t_jk).sum(1)                                 # (C, n)

    out = jax.lax.map(chunk, (subsets.reshape(-1, CHUNK, subsets.shape[1]),
                              qsig.reshape(-1, CHUNK)))
    return out.reshape(-1, n)


def reference_table(data: np.ndarray, *, q, s: int, gamma: float,
                    ess: float, dtype=jnp.float32) -> jax.Array:
    """(n, S) local scores ls(i, pi) of every node and parent set; ``q`` is
    one arity or one per variable."""
    data = np.asarray(data, np.int32)
    n = data.shape[1]
    r = np.broadcast_to(np.asarray(q, np.int32), (n,))
    base = n + 1
    if base ** (s + 1) >= 2 ** 31:
        raise ValueError(f"n = {n}, s = {s}: subset keys overflow int32")
    subsets = parent_sets(n, s)
    keys = _keys(subsets, base)
    pad = (-len(subsets)) % CHUNK
    sub_p = np.pad(subsets, ((0, pad), (0, 0)), constant_values=-1)
    qsig = np.prod(np.where(sub_p < 0, 1, r[np.maximum(sub_p, 0)]), axis=1)
    with jax.default_matmul_precision("highest"):
        ti = _subset_scores(jnp.asarray(data), jnp.asarray(r),
                            jnp.asarray(sub_p), jnp.asarray(qsig, jnp.int32),
                            Q=int(qsig.max()), r_max=int(r.max()),
                            ess=float(ess), dtype=dtype)
    psets = parent_sets(n - 1, s)
    return _gather_table(ti, jnp.asarray(keys, jnp.int32), jnp.asarray(psets),
                         jnp.asarray((psets >= 0).sum(1), jnp.int32),
                         jnp.asarray(math.log(gamma), jnp.float32),
                         s=s, base=base)
