"""Synthetic experimental data from a ground-truth Bayesian network.

Ancestral (forward) sampling from Dirichlet CPTs — the paper assumes complete
multinomial data (§II). Noise injection (paper §VI, Fig. 11): each entry flips
state with probability p (for q=2 a bit flip; for q>2 a uniform re-draw among
the other states). Continuous data (the BGe score's) come from a
linear-Gaussian structural equation model instead. ``q`` is one arity for every variable or one per
variable; a parent configuration is the mixed-radix code of the parents'
states, the first parent its lowest digit.
"""
from __future__ import annotations

import numpy as np

from ..core.graph import parents_list_from_adjacency, topological_order
from ..core.scores import arity_vector

__all__ = ["ancestral_sample", "inject_noise", "linear_gaussian_sample"]


def ancestral_sample(rng: np.random.Generator, adj: np.ndarray,
                     cpts: list[np.ndarray], m: int, q) -> np.ndarray:
    """m samples (m, n) int32 from the network (adj[m, i] = 1 ⇔ m → i)."""
    n = adj.shape[0]
    r = arity_vector(q, n)
    order = topological_order(adj)
    parents = parents_list_from_adjacency(adj)
    data = np.zeros((m, n), dtype=np.int32)
    for i in order:
        ps = parents[i]
        if len(ps) == 0:
            probs = np.broadcast_to(cpts[i][0], (m, r[i]))
        else:
            code = np.zeros(m, dtype=np.int64)
            stride = 1
            for p in ps:
                code += data[:, p].astype(np.int64) * stride
                stride *= int(r[p])
            probs = cpts[i][code]
        u = rng.random((m, 1))
        data[:, i] = (probs.cumsum(axis=1) < u).sum(axis=1).clip(0, r[i] - 1)
    return data


def inject_noise(rng: np.random.Generator, data: np.ndarray, p: float,
                 q) -> np.ndarray:
    """Flip each entry with probability p (paper §VI fault-injection study):
    to the other state of a binary variable, else to a uniform draw among
    the other states."""
    flip = rng.random(data.shape) < p
    if np.ndim(q) == 0:
        if q == 2:
            return np.where(flip, 1 - data, data).astype(data.dtype)
        shift = rng.integers(1, q, size=data.shape)
        return np.where(flip, (data + shift) % q, data).astype(data.dtype)
    r = arity_vector(q, data.shape[1])[None, :]
    shift = rng.integers(1, np.maximum(r, 2), size=data.shape)
    return np.where(flip & (r > 1), (data + shift) % r,
                    data).astype(data.dtype)


def linear_gaussian_sample(rng: np.random.Generator, adj: np.ndarray,
                           m: int) -> np.ndarray:
    """m samples (m, n) float64 of a linear-Gaussian SEM on ``adj``:
    x_i = sum_p w_pi x_p + e_i, e_i ~ N(0, 1), each arc's weight drawn
    uniformly from +-[0.5, 2] (the protocol of Zheng et al. 2018,
    arXiv:1803.01422, Sec. 5); columns then standardized to mean 0 and
    variance 1, as users do with expression panels. Draws: the arcs'
    magnitudes and signs in ``np.nonzero(adj)`` order, then the noise."""
    n = adj.shape[0]
    tails, heads = np.nonzero(adj)
    w = np.zeros((n, n))
    w[tails, heads] = (rng.uniform(0.5, 2.0, len(tails))
                       * rng.choice([-1.0, 1.0], len(tails)))
    x = rng.standard_normal((m, n))
    for i in topological_order(adj):
        x[:, i] += x @ w[:, i]
    return (x - x.mean(0)) / x.std(0)
