"""Generator of table-build traffic on continuous data: BGe score tables.

As ``table.py``: set-up draws ``datasets`` seeded datasets and builds one
more table from an extra dataset, which compiles every program a build
runs; a unit of the window is one ``build_score_table_fused(...,
score="bge")`` call, dense assembly, with no disk cache, on the next
dataset, waited for until its (n, S) table is on the device; the window
closes at the first build that completes after ``--seconds``.

The datasets are drawn here, apart from the program, as ``gen.py`` draws
the discrete ones: a DAG with the configuration's arc count and at most
``max_parents`` parents a node (a random order, then the forward pairs of
that order in random sequence, each kept while its head has room), a
linear-Gaussian SEM on it with each arc's weight uniform on +-[0.5, 2] and
N(0, 1) noise, and every column standardized. ``network_data`` draws
exactly what ``bn_learn --network synth --arcs <k> --score bge`` draws for
the same seed; a test pins that equality.

The check compares the window's last table with ``reference_bge``'s table
of the same dataset. The counters add the build's score kind.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, reference_bge
from chipbench.generators import table

UNIT = table.UNIT
STAGES = table.STAGES


def arc_dag(rng: np.random.Generator, n: int, arcs: int,
            max_parents: int) -> np.ndarray:
    """(n, n) int8 adjacency with exactly ``arcs`` arcs."""
    order = rng.permutation(n)
    tail, head = np.triu_indices(n, 1)
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
    if kept != arcs:
        raise ValueError(f"{arcs} arcs do not fit {n} nodes")
    return adj


def linear_gaussian(rng: np.random.Generator, adj: np.ndarray,
                    m: int) -> np.ndarray:
    """(m, n) float64 standardized samples of the SEM on ``adj``."""
    n = adj.shape[0]
    tails, heads = np.nonzero(adj)
    w = np.zeros((n, n))
    w[tails, heads] = (rng.uniform(0.5, 2.0, len(tails))
                       * rng.choice([-1.0, 1.0], len(tails)))
    x = rng.standard_normal((m, n))
    for i in gen._topological_order(adj):
        x[:, i] += x @ w[:, i]
    return (x - x.mean(0)) / x.std(0)


def network_data(cfg: dict, rng: np.random.Generator):
    """(true adjacency, (m, n) samples) of the configuration."""
    if cfg["network"] != "synth":
        raise ValueError(f"continuous data are drawn on a synthetic DAG, "
                         f"not {cfg['network']!r}")
    adj = arc_dag(rng, cfg["n"], cfg["arcs"], cfg["max_parents"])
    return adj, linear_gaussian(rng, adj, cfg["m"])


class Generator(table.Generator):
    def _build(self, data):
        from repro.preprocess import build_score_table_fused

        c = self.cfg
        st, info = build_score_table_fused(
            data, q=None, s=c["s"], gamma=c["gamma"], score=c["score"],
            bge_alpha_mu=c["alpha_mu"], bge_alpha_w=c["alpha_w"],
            return_info=True)
        jax.block_until_ready(st.table)
        return st, info

    def setup(self) -> None:
        c = self.cfg
        k = int(self.traffic["datasets"])
        t0 = time.perf_counter()
        self.datasets = [network_data(c, gen.dataset_rng(self.seed, i))[1]
                         for i in range(k + 1)]
        t1 = time.perf_counter()
        st, info = self._build(self.datasets.pop())       # warm: compiles
        self.phases = {"datasets_s": t1 - t0,
                       "warm_build_s": time.perf_counter() - t1}
        if st.table.shape != (c["n"], c["S"]):
            raise RuntimeError(f"table shape {st.table.shape}, config says "
                               f"{(c['n'], c['S'])}")
        self.builds = 0
        times = ("preprocess_s",) + STAGES
        self.counters = {"n": c["n"], "m": c["m"], "s": c["s"], "S": c["S"],
                         "score": info["score"], "builds": 0,
                         "traced_builds": 0,
                         **{t: 0.0 for t in times},
                         **{"traced_" + t: 0.0 for t in times}}

    # ------------------------------------------------------------- check
    def _reference(self, d: int, dtype=jnp.float32):
        c = self.cfg
        return reference_bge.reference_table(
            self.datasets[d], s=c["s"], gamma=c["gamma"],
            alpha_mu=c["alpha_mu"], alpha_w=c["alpha_w"], dtype=dtype)

    def control(self, out: dict) -> dict:
        """The reference in bfloat16, put in the program's place."""
        return {"tables": [(d, jnp.asarray(self._reference(d, jnp.bfloat16),
                                           jnp.float32))
                           for d, _ in out["tables"]]}

    def numbers(self, out: dict) -> dict:
        gap = 0.0
        for d, tbl in out["tables"]:
            gap = max(gap, reference_bge.row_gap(tbl, self._reference(d)))
        if not out["tables"]:
            gap = float("inf")
        return {"table_rel_gap": gap}
