"""The paper's own workload: Bayesian-network structure learning configs.

`BN_SIZES` mirrors the paper's Table III sweep (13..60 nodes, s=4); the two
reference networks (§VI) are STN (11 nodes) and ALARM (37 nodes).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class BNConfig:
    name: str
    n_nodes: int
    arity: int = 3            # paper's gene-expression discretization (3 states)
    max_parents: int = 4      # paper: s = 4
    gamma: float = 0.1        # structure penalty
    ess: float = 1.0          # BDeu equivalent sample size
    n_samples: int = 1000     # paper's experiments use 1,000 observations
    iterations: int = 10_000
    n_chains: int = 1
    score_block: int = 4096   # kernel tile on the parent-set axis: its
                              # packed words (block/32) fill 128 TPU lanes


CONFIG = BNConfig(name="bn-60", n_nodes=60)          # paper's headline scale
STN = BNConfig(name="bn-stn-11", n_nodes=11, arity=3, n_samples=1000)
ALARM = BNConfig(name="bn-alarm-37", n_nodes=37, arity=3, n_samples=1000)

BN_SIZES = [13, 15, 17, 20, 25, 30, 35, 40, 45, 50, 55, 60]  # Table III
