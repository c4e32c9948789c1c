"""Seeded dataset generators of the benchmark, kept apart from the program.

Copies of the program's generators (the ALARM edge list, ``random_dag`` /
``synthetic_adjacency``, ``random_cpts`` and ``ancestral_sample``), so that
the datasets a cell is measured on do not move when the program's own data
module changes. ``network_data`` draws exactly what
``bn_learn --network {alarm,synth}`` draws for the same seed; a test pins
that equality.
"""
from __future__ import annotations

import numpy as np

__all__ = ["ALARM_NODES", "ALARM_EDGES", "alarm_adjacency", "random_dag",
           "synthetic_adjacency", "random_cpts", "ancestral_sample",
           "network_data", "dataset_rng"]

# ALARM (Beinlich et al. 1989): 37 nodes, 46 edges, as the bnlearn
# repository lists them
ALARM_NODES = [
    "HISTORY", "CVP", "PCWP", "HYPOVOLEMIA", "LVEDVOLUME", "LVFAILURE",
    "STROKEVOLUME", "ERRLOWOUTPUT", "HRBP", "HREKG", "ERRCAUTER", "HRSAT",
    "INSUFFANESTH", "ANAPHYLAXIS", "TPR", "EXPCO2", "KINKEDTUBE", "MINVOL",
    "FIO2", "PVSAT", "SAO2", "PAP", "PULMEMBOLUS", "SHUNT", "INTUBATION",
    "PRESS", "DISCONNECT", "MINVOLSET", "VENTMACH", "VENTTUBE", "VENTLUNG",
    "VENTALV", "ARTCO2", "CATECHOL", "HR", "CO", "BP",
]

ALARM_EDGES = [
    ("LVFAILURE", "HISTORY"), ("LVEDVOLUME", "CVP"), ("LVEDVOLUME", "PCWP"),
    ("HYPOVOLEMIA", "LVEDVOLUME"), ("LVFAILURE", "LVEDVOLUME"),
    ("HYPOVOLEMIA", "STROKEVOLUME"), ("LVFAILURE", "STROKEVOLUME"),
    ("ERRLOWOUTPUT", "HRBP"), ("HR", "HRBP"), ("ERRCAUTER", "HREKG"),
    ("HR", "HREKG"), ("ERRCAUTER", "HRSAT"), ("HR", "HRSAT"),
    ("ANAPHYLAXIS", "TPR"), ("ARTCO2", "EXPCO2"), ("VENTLUNG", "EXPCO2"),
    ("INTUBATION", "MINVOL"), ("VENTLUNG", "MINVOL"), ("FIO2", "PVSAT"),
    ("VENTALV", "PVSAT"), ("PVSAT", "SAO2"), ("SHUNT", "SAO2"),
    ("PULMEMBOLUS", "PAP"), ("INTUBATION", "SHUNT"), ("PULMEMBOLUS", "SHUNT"),
    ("INTUBATION", "PRESS"), ("KINKEDTUBE", "PRESS"), ("VENTTUBE", "PRESS"),
    ("MINVOLSET", "VENTMACH"), ("DISCONNECT", "VENTTUBE"),
    ("VENTMACH", "VENTTUBE"), ("INTUBATION", "VENTLUNG"),
    ("KINKEDTUBE", "VENTLUNG"), ("VENTTUBE", "VENTLUNG"),
    ("INTUBATION", "VENTALV"), ("VENTLUNG", "VENTALV"),
    ("VENTALV", "ARTCO2"), ("ARTCO2", "CATECHOL"),
    ("INSUFFANESTH", "CATECHOL"),
    ("SAO2", "CATECHOL"), ("TPR", "CATECHOL"), ("CATECHOL", "HR"),
    ("HR", "CO"), ("STROKEVOLUME", "CO"), ("CO", "BP"), ("TPR", "BP"),
]


def alarm_adjacency() -> np.ndarray:
    """(37, 37) int8 adjacency, adj[a, b] = 1 for an edge a -> b."""
    idx = {v: i for i, v in enumerate(ALARM_NODES)}
    adj = np.zeros((len(ALARM_NODES),) * 2, dtype=np.int8)
    for a, b in ALARM_EDGES:
        adj[idx[a], idx[b]] = 1
    return adj


def random_dag(rng: np.random.Generator, n: int, max_parents: int,
               edge_prob: float = 0.25) -> np.ndarray:
    """Random DAG adjacency with at most max_parents parents per node."""
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


def synthetic_adjacency(rng: np.random.Generator, n: int, *,
                        max_parents: int = 3,
                        edge_prob: float = 0.45) -> np.ndarray:
    """ALARM-like synthetic ground truth at width n."""
    return random_dag(rng, n, max_parents, edge_prob)


def random_cpts(rng: np.random.Generator, adj: np.ndarray, q: int,
                concentration: float = 0.5) -> list[np.ndarray]:
    """Dirichlet CPTs: cpts[i] has shape (q^{|parents|}, q)."""
    return [rng.dirichlet(np.full(q, concentration),
                          size=q ** int(adj[:, i].sum()))
            for i in range(adj.shape[0])]


def _topological_order(adj: np.ndarray) -> list[int]:
    """Kahn's algorithm with a LIFO queue (the program's node order)."""
    indeg = adj.sum(axis=0).astype(int)
    queue = [i for i in range(adj.shape[0]) if indeg[i] == 0]
    out = []
    while queue:
        v = queue.pop()
        out.append(v)
        for w in np.nonzero(adj[v])[0]:
            indeg[w] -= 1
            if indeg[w] == 0:
                queue.append(int(w))
    if len(out) != adj.shape[0]:
        raise ValueError("graph has a cycle")
    return out


def ancestral_sample(rng: np.random.Generator, adj: np.ndarray,
                     cpts: list[np.ndarray], m: int, q: int) -> np.ndarray:
    """m samples (m, n) int32 drawn forward through the network."""
    data = np.zeros((m, adj.shape[0]), dtype=np.int32)
    for i in _topological_order(adj):
        ps = np.nonzero(adj[:, i])[0]
        if len(ps) == 0:
            probs = np.broadcast_to(cpts[i][0], (m, q))
        else:
            code = np.zeros(m, dtype=np.int64)
            for j, p in enumerate(ps):
                code += data[:, p].astype(np.int64) * q ** j
            probs = cpts[i][code]
        u = rng.random((m, 1))
        data[:, i] = (probs.cumsum(axis=1) < u).sum(axis=1).clip(0, q - 1)
    return data


def network_data(network: str, m: int, q: int, rng: np.random.Generator,
                 n: int) -> tuple[np.ndarray, np.ndarray]:
    """(true adjacency, (m, n) samples): ``network`` is "alarm" or "synth"."""
    if network == "synth":
        adj = synthetic_adjacency(rng, n)
    elif network == "alarm":
        adj = alarm_adjacency()
    else:
        raise ValueError(f"unknown network {network!r}")
    if adj.shape[0] != n:
        raise ValueError(f"{network} has {adj.shape[0]} nodes, "
                         f"the configuration says {n}")
    return adj, ancestral_sample(rng, adj, random_cpts(rng, adj, q), m, q)


def dataset_rng(seed: int, k: int | None = None) -> np.random.Generator:
    """The generator of a cell's dataset: the seed alone for its first (as
    ``bn_learn --seed`` seeds it), (seed, k) for the k-th of a stream."""
    return np.random.default_rng(seed if k is None else [seed, k])
