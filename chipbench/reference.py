"""Plain reference for what the benchmark checks: BDeu local scores, the
max order score (paper Eq. 6) and the best graph under an order.

It imports nothing of the program and takes nothing the program made: it
recounts every (parent configuration, child state) cell from the samples
and evaluates every log-gamma term directly. The score is the program's
documented one (natural log, BDeu with ``alpha_jk = ess / (q^|pi| q)`` and
``alpha_j = ess / q^|pi|``, structure penalty ``|pi| ln gamma``); parent
sets are indexed in the program's documented order: sizes ascending, each
size block lexicographic over the node's candidate indices, candidate ``c``
of node ``i`` being node ``c + (c >= i)``.

Counting runs once per column subset against every child at once, in
float32 with the highest matmul precision (0/1 operands, so the counts are
exact). ``dtype=jnp.bfloat16`` computes the same in bfloat16: the control,
which a sound comparison must refuse.
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["parent_sets", "n_parent_sets", "reference_table", "order_best",
           "decode_graph", "consistent", "unpack_counts", "pack_counts",
           "rel_gap", "CHUNK"]

CHUNK = 256          # column subsets counted per step (bounds device memory)


def n_parent_sets(n_cand: int, s: int) -> int:
    return sum(math.comb(n_cand, k) for k in range(s + 1))


@functools.lru_cache(maxsize=8)
def parent_sets(n_cand: int, s: int) -> np.ndarray:
    """(S, s) int32 subsets of range(n_cand) with at most s elements, sizes
    ascending and lexicographic within a size, padded with -1."""
    rows = np.full((n_parent_sets(n_cand, s), s), -1, np.int32)
    at = 1                                         # row 0: the empty set
    for k in range(1, s + 1):
        block = np.fromiter(
            itertools.chain.from_iterable(
                itertools.combinations(range(n_cand), k)),
            np.int32).reshape(-1, k)
        rows[at:at + len(block), :k] = block
        at += len(block)
    return rows


def _keys(sets: np.ndarray, base: int) -> np.ndarray:
    """Sort keys in the enumeration order: size, then digits c+1 (0 pad)."""
    s = sets.shape[1]
    sizes = (sets >= 0).sum(1).astype(np.int64)
    digits = np.where(sets >= 0, sets.astype(np.int64) + 1, 0)
    key = sizes * base ** s
    for j in range(s):
        key = key + digits[:, j] * base ** (s - 1 - j)
    return key


@functools.partial(jax.jit, static_argnames=("q", "s", "dtype"))
def _subset_scores(data, subsets, sizes, *, q, s, ess, dtype):
    """(U, n) score of every column subset as the parent set of every child
    (entries whose child lies in the subset are never read)."""
    m, n = data.shape
    Q = q ** s
    hi = jax.lax.Precision.HIGHEST
    child = jax.nn.one_hot(data, q, dtype=jnp.float32).reshape(m, n * q)
    data_ext = jnp.concatenate([data, jnp.zeros((m, 1), data.dtype)], 1)
    pw = q ** jnp.arange(s, dtype=jnp.int32)
    lg = jax.lax.lgamma

    def chunk(args):
        sub, k = args                                   # (C, s), (C,)
        cols = jnp.where(sub < 0, n, sub)
        code = (data_ext[:, cols] * pw).sum(-1)         # (m, C)
        oh = (code.T[:, None, :] ==
              jnp.arange(Q, dtype=jnp.int32)[None, :, None])
        cnt = jnp.einsum("cjm,mx->cjx", oh.astype(jnp.float32), child,
                         precision=hi)                  # (C, Q, n*q), exact
        cnt = cnt.reshape(-1, Q, n, q).astype(dtype)
        r = jnp.power(jnp.asarray(q, dtype), k.astype(dtype))
        a_j = (ess / r).astype(dtype)[:, None, None]            # (C, 1, 1)
        a_jk = (ess / (r * q)).astype(dtype)[:, None, None, None]
        n_j = cnt.sum(-1)                                        # (C, Q, n)
        t_j = lg(a_j) - lg(a_j + n_j)
        t_jk = (lg(cnt + a_jk) - lg(a_jk)).sum(-1)
        return (t_j + t_jk).sum(1)                               # (C, n)

    out = jax.lax.map(chunk, (subsets.reshape(-1, CHUNK, s),
                              sizes.reshape(-1, CHUNK)))
    return out.reshape(-1, n)


@functools.partial(jax.jit, static_argnames=("s", "base"))
def _gather_table(ti, sub_keys, psets, psizes, log_gamma, *, s, base):
    """(n, S): each node's parent sets mapped to column subsets and looked
    up in the subset scores, plus the structure penalty."""
    n = ti.shape[1]
    dt = ti.dtype

    def node(i):
        cols = jnp.where(psets < 0, -1, psets + (psets >= i))
        key = psizes * base ** s
        for j in range(s):
            key = key + jnp.where(cols[:, j] >= 0, cols[:, j] + 1, 0) \
                * base ** (s - 1 - j)
        u = jnp.searchsorted(sub_keys, key)
        return (psizes.astype(dt) * log_gamma.astype(dt) + ti[u, i])

    return jax.lax.map(node, jnp.arange(n, dtype=jnp.int32))


def reference_table(data: np.ndarray, *, q: int, s: int, gamma: float,
                    ess: float, dtype=jnp.float32) -> jax.Array:
    """(n, S) local scores ls(i, pi) of every node and parent set."""
    data = np.asarray(data, np.int32)
    n = data.shape[1]
    base = n + 1
    if base ** (s + 1) >= 2 ** 31:
        raise ValueError(f"n = {n}, s = {s}: subset keys overflow int32")
    subsets = parent_sets(n, s)
    keys = _keys(subsets, base)
    if np.any(np.diff(keys) <= 0):
        raise AssertionError("subset enumeration is not in key order")
    pad = (-len(subsets)) % CHUNK
    sub_p = np.pad(subsets, ((0, pad), (0, 0)), constant_values=-1)
    sizes = (sub_p >= 0).sum(1).astype(np.int32)
    ti = _subset_scores(jnp.asarray(data), jnp.asarray(sub_p),
                        jnp.asarray(sizes), q=q, s=s, ess=float(ess),
                        dtype=dtype)
    psets = parent_sets(n - 1, s)
    return _gather_table(ti, jnp.asarray(keys, jnp.int32), jnp.asarray(psets),
                         jnp.asarray((psets >= 0).sum(1), jnp.int32),
                         jnp.asarray(math.log(gamma), jnp.float32),
                         s=s, base=base)


@jax.jit
def consistent(psets, pos, node, rank):
    """Whether parent set ``rank`` of ``node`` has every parent before the
    node in the order ``pos`` (ranks outside the table are not)."""
    S = psets.shape[0]
    row = psets[jnp.clip(rank, 0, S - 1)]
    par = row + (row >= node)
    ok = jnp.where(row < 0, True, pos[jnp.clip(par, 0)] < pos[node])
    return jnp.all(ok, -1) & (rank >= 0) & (rank < S)


@jax.jit
def order_best(table, psets, pos):
    """Per node, under order ``pos``: (best local score, first parent-set
    rank attaining it, (S,) count of parents that do not precede the node)."""
    n = table.shape[0]
    neg = jnp.asarray(-jnp.inf, table.dtype)

    def node(args):
        i, row = args
        par = psets + (psets >= i)
        late = (psets >= 0) & (pos[jnp.clip(par, 0)] >= pos[i])
        viol = late.sum(-1, dtype=jnp.int32)
        masked = jnp.where(viol == 0, row, neg)
        a = jnp.argmax(masked)
        return masked[a], a.astype(jnp.int32), viol

    return jax.lax.map(node, (jnp.arange(n, dtype=jnp.int32), table))


@functools.partial(jax.jit, static_argnames=("S",))
def unpack_counts(planes, *, S):
    """(n, P, W) uint32 count planes -> (n, S) int32 counts: bit b of word j
    of plane p is bit p of the count of parent set 32 j + b."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (planes[..., None] >> shifts) & jnp.uint32(1)      # (n, P, W, 32)
    weights = (jnp.uint32(1) << jnp.arange(planes.shape[1], dtype=jnp.uint32))
    counts = (bits * weights[None, :, None, None]).sum(1, dtype=jnp.uint32)
    return counts.reshape(planes.shape[0], -1)[:, :S].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("P", "W"))
def pack_counts(counts, *, P, W):
    """Inverse of unpack_counts: (n, S) counts -> (n, P, W) planes."""
    n, S = counts.shape
    c = jnp.pad(counts.astype(jnp.uint32), ((0, 0), (0, W * 32 - S)))
    c = c.reshape(n, 1, W, 32) >> jnp.arange(P, dtype=jnp.uint32)[None, :,
                                                                  None, None]
    bits = (c & jnp.uint32(1)) << jnp.arange(32, dtype=jnp.uint32)
    return bits.sum(-1, dtype=jnp.uint32)


def decode_graph(ranks: np.ndarray, s: int) -> np.ndarray:
    """(n, n) int8 adjacency (adj[p, i] = 1 for p -> i) of per-node parent-set
    ranks."""
    n = len(ranks)
    psets = parent_sets(n - 1, s)
    adj = np.zeros((n, n), np.int8)
    for i, t in enumerate(np.asarray(ranks)):
        for c in psets[int(t)]:
            if c >= 0:
                adj[c + (c >= i), i] = 1
    return adj


def rel_gap(got, want, signed: bool = False) -> float:
    """Largest |got - want| / |want| (want - got when signed: how far a
    chosen score lies below the best); inf when got is not finite or the
    shapes differ."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        return float("inf")
    d = (want - got) if signed else jnp.abs(got - want)
    r = d / jnp.maximum(jnp.abs(want), 1e-30)
    r = jnp.where(jnp.isfinite(got), r, jnp.inf)
    return float(jnp.max(r))
