"""Combinatorial machinery for parent-set enumeration (paper §V-B, Algorithm 2).

The paper indexes all subsets of at most ``s`` elements out of ``n`` candidates so
that (a) a GPU thread can *unrank* an index into its subset arithmetically
(Algorithm 2), and (b) a materialized parent-set table (PST) can replace the
arithmetic with a table read.  We implement both:

* :func:`unrank_combination` — faithful, non-recursive Algorithm 2 (lexicographic
  k-combinations of ``n`` elements).
* :func:`rank_combination` — the inverse bijection.  This is the TPU-native
  replacement for the paper's *hash table*: instead of hashing (node, parent-set)
  into a chained table, the rank IS the address into a dense ``(n, S)`` score
  table.  O(s) integer math, no pointer chasing, gatherable.
* :func:`build_pst` — the parent-set table, size-ascending then lexicographic.

Layout notes
------------
Parent sets are subsets of the ``n-1`` *candidate* indices ``{0..n-2}`` shared by
every node; candidate ``c`` of node ``i`` refers to node ``c + (c >= i)``.  PST rows
are padded to width ``s`` with ``-1``.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

__all__ = [
    "n_parent_sets",
    "size_offsets",
    "binom_table",
    "unrank_combination",
    "rank_combination",
    "rank_combinations_batch",
    "build_pst",
    "rank_parent_set",
    "unrank_parent_set",
    "candidates_to_nodes",
    "nodes_to_candidates",
]


def n_parent_sets(n_candidates: int, s: int) -> int:
    """S = sum_{j=0}^{s} C(n_candidates, j) — paper §III-B."""
    return sum(math.comb(n_candidates, j) for j in range(s + 1))


def size_offsets(n_candidates: int, s: int) -> np.ndarray:
    """Start offset of each size-k block in the PST, k = 0..s (+ total sentinel)."""
    sizes = [math.comb(n_candidates, j) for j in range(s + 1)]
    return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


@lru_cache(maxsize=None)
def binom_table(n_max: int, k_max: int) -> np.ndarray:
    """C(n, k) for 0 <= n <= n_max, 0 <= k <= k_max (int64, exact for our sizes)."""
    t = np.zeros((n_max + 1, k_max + 1), dtype=np.int64)
    t[:, 0] = 1
    for n in range(1, n_max + 1):
        for k in range(1, k_max + 1):
            t[n, k] = t[n - 1, k - 1] + t[n - 1, k]
    return t


def unrank_combination(n: int, k: int, l: int) -> np.ndarray:
    """Paper Algorithm 2: the l-th (0-based) k-combination of {0..n-1} in
    lexicographic order, non-recursive.

    The paper states it for 1-based elements and 1-based rank; we use 0-based on
    both ends (the bijection is identical up to the shift).
    """
    if not (0 <= l < math.comb(n, k)):
        raise ValueError(f"rank {l} out of range for C({n},{k})")
    comb = np.empty(k, dtype=np.int64)
    low = -1  # last chosen element (0-based); paper's `low` is the 1-based analogue
    for pos in range(k):
        remaining = k - pos
        # find the smallest next element a > low such that the number of
        # combinations starting with a covers rank l
        s = 0
        n_rest = n - (low + 1)  # candidates remaining
        acc = 0
        while True:
            s += 1
            c = math.comb(n_rest - s, remaining - 1)
            if acc + c > l:
                break
            acc += c
        comb[pos] = low + s
        l -= acc
        low = comb[pos]
    return comb


def rank_combination(n: int, comb: np.ndarray) -> int:
    """Inverse of :func:`unrank_combination` (lexicographic rank, 0-based)."""
    comb = np.asarray(comb, dtype=np.int64)
    k = len(comb)
    rank = 0
    low = -1
    for pos, a in enumerate(comb):
        remaining = k - pos
        n_rest = n - (low + 1)
        for step in range(1, int(a) - low):
            rank += math.comb(n_rest - step, remaining - 1)
        low = int(a)
    return rank


def rank_combinations_batch(n: int, s: int, rows: np.ndarray,
                            sizes: np.ndarray) -> np.ndarray:
    """Vectorized :func:`rank_parent_set` over arbitrarily-shaped batches.

    rows: (..., s) sorted element indices over {0..n-1}, padded with -1 at the
    tail; sizes: (...) set sizes. Returns (...) int64 global indices into the
    size-ascending :func:`build_pst`(n, s) ordering.

    Uses the hockey-stick identity to collapse :func:`rank_combination`'s inner
    loop:  sum_{x=a}^{b} C(n-1-x, r) = C(n-a, r+1) - C(n-1-b, r+1), so the lex
    rank of {c_0 < ... < c_{k-1}} is  sum_j [C(n-1-c_{j-1}, k-j) - C(n-c_j, k-j)]
    with c_{-1} = -1. O(s) table lookups per row, no Python per-row loop —
    this is what makes the preprocess/ assembly gather map cheap to build.
    """
    rows = np.asarray(rows, dtype=np.int64)
    sizes = np.asarray(sizes, dtype=np.int64)
    B = binom_table(n + 1, s + 1)
    off = size_offsets(n, s)
    j = np.arange(rows.shape[-1])
    valid = j < sizes[..., None]
    prev = np.concatenate(
        [np.full(rows.shape[:-1] + (1,), -1, np.int64), rows[..., :-1]],
        axis=-1)
    c = np.where(valid, rows, 0)
    p = np.where(valid, prev, 0)
    r = np.where(valid, sizes[..., None] - j, 1)      # k - j for each position
    term = (B[np.clip(n - 1 - p, 0, n), np.clip(r, 0, s + 1)]
            - B[np.clip(n - c, 0, n), np.clip(r, 0, s + 1)])
    return off[sizes] + np.where(valid, term, 0).sum(-1)


def build_pst(n_candidates: int, s: int) -> tuple[np.ndarray, np.ndarray]:
    """Parent-set table: (S, s) int32 padded with -1, and (S,) int32 sizes.

    Order: size-ascending blocks (empty set first), lexicographic within a block.
    (The paper lists size-4-first; only the block order differs — see DESIGN.md §8.)
    A size with no subsets (``n_candidates < k``) gives an empty block.

    Built by suffix concatenation, with no per-row loop: the k-subsets whose
    first element is ``a`` are ``a`` prepended to the (k-1)-subsets of
    ``{a+1..n_candidates-1}``, which are the last C(n_candidates-1-a, k-1)
    rows of the (k-1) block. The Python loop runs over sizes x first elements.
    """
    off = [int(o) for o in size_offsets(n_candidates, s)]
    pst = np.full((off[-1], s), -1, dtype=np.int32)
    for k in range(1, s + 1):
        prev_end = row = off[k]          # the (k-1) block ends where k starts
        for a in range(n_candidates - k + 1):
            t = math.comb(n_candidates - 1 - a, k - 1)
            pst[row:row + t, 0] = a
            pst[row:row + t, 1:k] = pst[prev_end - t:prev_end, :k - 1]
            row += t
    sizes = np.repeat(np.arange(s + 1, dtype=np.int32), np.diff(off))
    return pst, sizes


def rank_parent_set(n_candidates: int, s: int, parents: np.ndarray) -> int:
    """Global PST index of a candidate-index parent set (any order). The
    hash-table-equivalent lookup: table[node, rank_parent_set(...)] == ls(node, π)."""
    parents = np.sort(np.asarray(parents, dtype=np.int64))
    k = len(parents)
    if k > s:
        raise ValueError(f"parent set of size {k} exceeds limit s={s}")
    off = size_offsets(n_candidates, s)
    return int(off[k] + (rank_combination(n_candidates, parents) if k else 0))


def unrank_parent_set(n_candidates: int, s: int, rank: int) -> np.ndarray:
    """Inverse of :func:`rank_parent_set`: global PST rank -> sorted candidate
    indices. Locates the size-k block from :func:`size_offsets`, then applies
    paper Algorithm 2 within it — O(s·n) integer math, NO materialized PST.
    This is what lets the pruned representation drop the (S, s) table
    entirely (adjacency recovery decodes the ≤ n winning ranks on the fly)."""
    off = size_offsets(n_candidates, s)
    if not (0 <= rank < off[-1]):
        raise ValueError(f"rank {rank} outside [0, S={off[-1]})")
    k = int(np.searchsorted(off, rank, side="right")) - 1
    if k == 0:
        return np.empty(0, dtype=np.int64)
    return unrank_combination(n_candidates, k, int(rank) - int(off[k]))


def candidates_to_nodes(cands: np.ndarray, node: int) -> np.ndarray:
    """Map candidate indices {0..n-2} to node ids {0..n-1}\\{node}. -1 padding maps to -1."""
    cands = np.asarray(cands)
    out = cands + (cands >= node)
    return np.where(cands < 0, -1, out)


def nodes_to_candidates(nodes: np.ndarray, node: int) -> np.ndarray:
    """Inverse of :func:`candidates_to_nodes`."""
    nodes = np.asarray(nodes)
    if np.any(nodes == node):
        raise ValueError("a node cannot be its own parent")
    out = nodes - (nodes > node)
    return np.where(nodes < 0, -1, out)
