"""Reference networks used in the paper's experiments (§VI):

* STN — the 11-node signaling transduction network from human T-cells
  (Sachs et al., Science 2005; paper ref [10]); consensus edge set.
* ALARM — the 37-node monitoring network (paper ref [17]); standard 46 edges,
  and its published states per variable (2 to 4; 509 free parameters).
* synthetic — random sparse DAGs at arbitrary n for the paper's n > 60 scale
  claim (§VI uses networks the benchmark suite ships; past ALARM size we
  generate ALARM-like ground truth instead), or with an exact arc count.

For the BGe score the samples are continuous: a linear-Gaussian SEM on the
same DAG (bn_sampler.linear_gaussian_sample) in place of the CPTs.
"""
from __future__ import annotations

import numpy as np

from ..core.graph import random_cpts, random_dag, random_dag_arcs
from .bn_sampler import ancestral_sample, linear_gaussian_sample

STN_NODES = ["Raf", "Mek", "Plcg", "PIP2", "PIP3", "Erk", "Akt", "PKA",
             "PKC", "P38", "Jnk"]

STN_EDGES = [
    ("Erk", "Akt"), ("Mek", "Erk"), ("PIP3", "PIP2"), ("PKA", "Akt"),
    ("PKA", "Erk"), ("PKA", "Jnk"), ("PKA", "Mek"), ("PKA", "P38"),
    ("PKA", "Raf"), ("PKC", "Jnk"), ("PKC", "Mek"), ("PKC", "P38"),
    ("PKC", "PKA"), ("PKC", "Raf"), ("Plcg", "PIP2"), ("Plcg", "PIP3"),
    ("Raf", "Mek"),
]

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
    ("VENTALV", "ARTCO2"), ("ARTCO2", "CATECHOL"), ("INSUFFANESTH", "CATECHOL"),
    ("SAO2", "CATECHOL"), ("TPR", "CATECHOL"), ("CATECHOL", "HR"),
    ("HR", "CO"), ("STROKEVOLUME", "CO"), ("CO", "BP"), ("TPR", "BP"),
]

# states per ALARM variable as the bnlearn repository publishes them: 13
# binary, 7 with four states, the other 17 with three
_ALARM_BINARY = {"HISTORY", "HYPOVOLEMIA", "LVFAILURE", "ERRLOWOUTPUT",
                 "ERRCAUTER", "INSUFFANESTH", "ANAPHYLAXIS", "KINKEDTUBE",
                 "FIO2", "PULMEMBOLUS", "SHUNT", "DISCONNECT", "CATECHOL"}
_ALARM_FOUR = {"EXPCO2", "MINVOL", "PRESS", "VENTMACH", "VENTTUBE",
               "VENTLUNG", "VENTALV"}
ALARM_ARITY = tuple(2 if v in _ALARM_BINARY else 4 if v in _ALARM_FOUR
                    else 3 for v in ALARM_NODES)

# named arity tables (``bn_learn --q alarm``)
ARITY_TABLES = {"alarm": ALARM_ARITY}


def parse_arity(spec):
    """``q`` from a user: an int (one arity for every variable), a sequence
    of ints, a comma list such as "2,3,3", or the name of a table in
    ``ARITY_TABLES``. Returns an int or a tuple of ints."""
    if isinstance(spec, str):
        spec = spec.strip()
        if spec in ARITY_TABLES:
            return ARITY_TABLES[spec]
        parts = [p for p in spec.split(",") if p.strip()]
        if not parts or not all(p.strip().isdigit() for p in parts):
            raise ValueError(f"arity {spec!r} is not an int, a comma list of "
                             f"ints or one of {sorted(ARITY_TABLES)}")
        spec = [int(p) for p in parts] if len(parts) > 1 else int(parts[0])
    if isinstance(spec, (int, np.integer)):
        return int(spec)
    return tuple(int(v) for v in spec)


def _adjacency(nodes: list[str], edges: list[tuple[str, str]]) -> np.ndarray:
    idx = {v: i for i, v in enumerate(nodes)}
    adj = np.zeros((len(nodes), len(nodes)), dtype=np.int8)
    for a, b in edges:
        adj[idx[a], idx[b]] = 1
    return adj


def stn_adjacency() -> np.ndarray:
    return _adjacency(STN_NODES, STN_EDGES)


def alarm_adjacency() -> np.ndarray:
    return _adjacency(ALARM_NODES, ALARM_EDGES)


def synthetic_adjacency(rng: np.random.Generator, n: int = 64, *,
                        max_parents: int = 3,
                        edge_prob: float = 0.45) -> np.ndarray:
    """ALARM-like synthetic ground truth at scale n (~1.2 parents/node at the
    defaults — the n = 64 scale-benchmark network of bn_learn/preprocess)."""
    return random_dag(rng, n, max_parents, edge_prob)


def network_data(name: str, m: int, q, seed: int, n_synth: int = 64, *,
                 arcs: int = 0, score: str = "bdeu"):
    """(ground-truth adjacency, (m, n) samples) for a named network —
    ``alarm``, ``stn`` or ``synth`` (a synthetic DAG of n_synth nodes; with
    ``arcs`` > 0, exactly that many arcs and at most 4 parents a node) —
    with seeded random CPTs at q states per variable (one int, or one per
    variable), or for ``score="bge"`` standardized linear-Gaussian samples
    (float64; q unused)."""
    rng = np.random.default_rng(seed)
    if arcs and name != "synth":
        raise ValueError(f"an arc count is drawn for synth, not {name!r}")
    if name == "synth":
        adj = (random_dag_arcs(rng, n_synth, arcs) if arcs
               else synthetic_adjacency(rng, n_synth))
    else:
        adj = {"alarm": alarm_adjacency, "stn": stn_adjacency}[name]()
    if score == "bge":
        return adj, linear_gaussian_sample(rng, adj, m)
    cpts = random_cpts(rng, adj, q)
    return adj, ancestral_sample(rng, adj, cpts, m, q)
