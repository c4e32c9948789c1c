"""DAG utilities: random ground-truth networks, CPTs, adjacency recovery."""
from __future__ import annotations

import numpy as np

from .combinatorics import candidates_to_nodes, unrank_parent_set
from .scores import arity_vector

__all__ = ["random_dag", "random_dag_arcs", "random_cpts", "adjacency_from_best",
           "adjacency_from_ranks", "parents_list_from_adjacency",
           "topological_order"]


def random_dag(rng: np.random.Generator, n: int, max_parents: int,
               edge_prob: float = 0.25) -> np.ndarray:
    """Random DAG adjacency (adj[m, i] = 1 ⇔ edge m → i) with ≤ max_parents."""
    order = rng.permutation(n)
    adj = np.zeros((n, n), dtype=np.int8)
    for pos in range(1, n):
        i = order[pos]
        preds = order[:pos]
        k = min(len(preds), max_parents)
        npar = rng.binomial(k, edge_prob) if k else 0
        if npar:
            for m in rng.choice(preds, size=npar, replace=False):
                adj[m, i] = 1
    return adj


def random_dag_arcs(rng: np.random.Generator, n: int, arcs: int,
                    max_parents: int = 4) -> np.ndarray:
    """Random DAG with exactly ``arcs`` edges and at most ``max_parents``
    parents per node: a random order, then the forward pairs of that order
    in random sequence, each kept while its head has room."""
    order = rng.permutation(n)
    room = np.minimum(np.arange(n), max_parents).sum()
    if not 0 <= arcs <= room:
        raise ValueError(f"{arcs} arcs do not fit {n} nodes with at most "
                         f"{max_parents} parents (at most {room})")
    tail, head = np.triu_indices(n, 1)             # positions in the order
    adj = np.zeros((n, n), dtype=np.int8)
    indeg = np.zeros(n, np.int64)
    kept = 0
    for e in rng.permutation(len(tail)):
        if kept == arcs:
            break
        a, b = order[tail[e]], order[head[e]]
        if indeg[b] < max_parents:
            adj[a, b] = 1
            indeg[b] += 1
            kept += 1
    return adj


def random_cpts(rng: np.random.Generator, adj: np.ndarray, q,
                concentration: float = 0.5) -> list[np.ndarray]:
    """Dirichlet CPTs: cpts[i] has shape (prod_{p in parents} r_p, r_i),
    (q^{|parents|}, q) at one arity q for every variable; ``q`` is that int
    or one arity per variable. Low concentration gives sharp (informative)
    conditionals."""
    r = arity_vector(q, adj.shape[0])
    return [rng.dirichlet(np.full(r[i], concentration),
                          size=int(np.prod(r[adj[:, i] != 0], dtype=np.int64)))
            for i in range(len(r))]


def topological_order(adj: np.ndarray) -> np.ndarray:
    """Kahn's algorithm; raises on cycles."""
    n = adj.shape[0]
    indeg = adj.sum(axis=0).astype(int).copy()
    queue = [i for i in range(n) if indeg[i] == 0]
    out = []
    while queue:
        v = queue.pop()
        out.append(v)
        for w in np.nonzero(adj[v])[0]:
            indeg[w] -= 1
            if indeg[w] == 0:
                queue.append(int(w))
    if len(out) != n:
        raise ValueError("graph has a cycle")
    return np.asarray(out)


def parents_list_from_adjacency(adj: np.ndarray) -> list[np.ndarray]:
    return [np.nonzero(adj[:, i])[0] for i in range(adj.shape[0])]


def adjacency_from_best(best_idx: np.ndarray, pst: np.ndarray) -> np.ndarray:
    """Recover adjacency from per-node best PST indices (the learned graph)."""
    n = len(best_idx)
    adj = np.zeros((n, n), dtype=np.int8)
    for i in range(n):
        cands = pst[int(best_idx[i])]
        for m in candidates_to_nodes(cands[cands >= 0], i):
            adj[int(m), i] = 1
    return adj


def adjacency_from_ranks(best_idx: np.ndarray, *, s: int) -> np.ndarray:
    """adjacency_from_best WITHOUT the (S, s) PST: each winning rank is
    unranked arithmetically (paper Algorithm 2). Identical output — the PST
    is built size-ascending/lexicographic, i.e. exactly in rank order — but
    usable from the pruned representation, whose footprint stays O(n·K)."""
    n = len(best_idx)
    adj = np.zeros((n, n), dtype=np.int8)
    for i in range(n):
        cands = unrank_parent_set(n - 1, s, int(best_idx[i]))
        for m in candidates_to_nodes(cands, i):
            adj[int(m), i] = 1
    return adj
