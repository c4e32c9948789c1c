"""Seeded ALARM datasets at the network's published states per variable,
kept apart from the program as ``gen.py`` is.

ALARM's variables have 2 to 4 states (the bnlearn repository: 13 binary,
7 with four states, 17 with three; 509 free parameters over the 46
edges). ``network_data`` draws what ``bn_learn --network alarm --q alarm``
draws for the same seed: Dirichlet(0.5) CPTs of shape (prod r_parents,
r_i), then ancestral sampling, a parent configuration being the mixed-radix
code of the parents' states with the first parent its lowest digit. At one
arity for every variable it draws exactly what ``gen.network_data`` draws.
A test pins both equalities.
"""
from __future__ import annotations

import numpy as np

from chipbench import gen

__all__ = ["ALARM_ARITY", "random_cpts", "ancestral_sample", "network_data"]

_BINARY = {"HISTORY", "HYPOVOLEMIA", "LVFAILURE", "ERRLOWOUTPUT", "ERRCAUTER",
           "INSUFFANESTH", "ANAPHYLAXIS", "KINKEDTUBE", "FIO2", "PULMEMBOLUS",
           "SHUNT", "DISCONNECT", "CATECHOL"}
_FOUR = {"EXPCO2", "MINVOL", "PRESS", "VENTMACH", "VENTTUBE", "VENTLUNG",
         "VENTALV"}
ALARM_ARITY = [2 if v in _BINARY else 4 if v in _FOUR else 3
               for v in gen.ALARM_NODES]


def random_cpts(rng: np.random.Generator, adj: np.ndarray, r,
                concentration: float = 0.5) -> list[np.ndarray]:
    """Dirichlet CPTs: cpts[i] has shape (prod of its parents' r, r_i)."""
    r = np.asarray(r)
    return [rng.dirichlet(np.full(r[i], concentration),
                          size=int(np.prod(r[adj[:, i] != 0], dtype=np.int64)))
            for i in range(adj.shape[0])]


def ancestral_sample(rng: np.random.Generator, adj: np.ndarray,
                     cpts: list[np.ndarray], m: int, r) -> np.ndarray:
    """m samples (m, n) int32 drawn forward through the network."""
    r = np.asarray(r)
    data = np.zeros((m, adj.shape[0]), dtype=np.int32)
    for i in gen._topological_order(adj):
        ps = np.nonzero(adj[:, i])[0]
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


def network_data(network: str, m: int, r, rng: np.random.Generator,
                 n: int) -> tuple[np.ndarray, np.ndarray]:
    """(true adjacency, (m, n) samples) of ALARM with arities ``r``."""
    if network != "alarm":
        raise ValueError(f"per-variable arities are drawn for ALARM only, "
                         f"not {network!r}")
    adj = gen.alarm_adjacency()
    r = np.broadcast_to(np.asarray(r), (adj.shape[0],))
    if adj.shape[0] != n:
        raise ValueError(f"alarm has {adj.shape[0]} nodes, "
                         f"the configuration says {n}")
    return adj, ancestral_sample(rng, adj, random_cpts(rng, adj, r), m, r)
