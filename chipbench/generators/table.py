"""Generator of table-build traffic: dense score tables from fresh datasets.

Set-up draws ``datasets`` seeded datasets of the configuration and builds
one more table from an extra dataset, which compiles every program a build
runs. A unit of the window is one ``build_score_table_fused`` call, with no
disk cache, on the next dataset, waited for until its (n, S) table is on
the device. The window closes at the first build that completes after
``--seconds``, so no build is cut.

The check compares the window's last table entry by entry with the plain
reference's table of the same dataset. At n = 60 a build outlasts a 10 s
window, so that is every build the window ran.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench import gen, reference

UNIT = "build"
STAGES = ("plan_s", "score_s", "assemble_s")


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int, work: str,
                 run_name: str):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.last = None                         # (dataset, table)

    def _build(self, data):
        from repro.preprocess import build_score_table_fused

        c = self.cfg
        st, info = build_score_table_fused(
            data, q=c["q"], s=c["s"], gamma=c["gamma"], ess=c["ess"],
            return_info=True)
        jax.block_until_ready(st.table)
        return st, info

    def setup(self) -> None:
        c = self.cfg
        k = int(self.traffic["datasets"])
        t0 = time.perf_counter()
        self.datasets = [
            gen.network_data(c["network"], c["m"], c["q"],
                             gen.dataset_rng(self.seed, i), c["n"])[1]
            for i in range(k + 1)]
        t1 = time.perf_counter()
        st, _ = self._build(self.datasets.pop())          # warm: compiles
        self.phases = {"datasets_s": t1 - t0,
                       "warm_build_s": time.perf_counter() - t1}
        if st.table.shape != (c["n"], c["S"]):
            raise RuntimeError(f"table shape {st.table.shape}, config says "
                               f"{(c['n'], c['S'])}")
        self.builds = 0
        times = ("preprocess_s",) + STAGES
        self.counters = {"n": c["n"], "m": c["m"], "q": c["q"], "s": c["s"],
                         "S": c["S"], "builds": 0, "traced_builds": 0,
                         **{t: 0.0 for t in times},
                         **{"traced_" + t: 0.0 for t in times}}

    def step(self) -> int:
        """One table build; returns the local scores it produced."""
        i = self.builds % len(self.datasets)
        st, info = self._build(self.datasets[i])
        self.builds += 1
        self.last = (i, st.table)
        self.counters["builds"] += 1
        self.counters["preprocess_s"] += info["preprocess_s"]
        for name in STAGES:
            self.counters[name] += info["stages"][name]
        self.last_info = info
        return int(st.table.size)

    def traced(self, units: int) -> None:
        """Count the build just run as traced (a traced run's metrics read
        the traced_ counters)."""
        c, info = self.counters, self.last_info
        c["traced_builds"] += 1
        c["traced_preprocess_s"] += info["preprocess_s"]
        for name in STAGES:
            c["traced_" + name] += info["stages"][name]

    def sync(self) -> None:
        if self.last is not None:
            jax.block_until_ready(self.last[1])

    # ------------------------------------------------------------- check
    def outputs(self) -> dict:
        return {"tables": [self.last] if self.last is not None else []}

    def control(self, out: dict) -> dict:
        """The reference in bfloat16, put in the program's place."""
        c = self.cfg
        return {"tables": [
            (d, jnp.asarray(reference.reference_table(
                self.datasets[d], q=c["q"], s=c["s"], gamma=c["gamma"],
                ess=c["ess"], dtype=jnp.bfloat16), jnp.float32))
            for d, _ in out["tables"]]}

    def numbers(self, out: dict) -> dict:
        c = self.cfg
        gap = 0.0
        for d, table in out["tables"]:
            want = reference.reference_table(
                self.datasets[d], q=c["q"], s=c["s"], gamma=c["gamma"],
                ess=c["ess"])
            gap = max(gap, reference.rel_gap(table, want))
        if not out["tables"]:
            gap = float("inf")
        return {"table_rel_gap": gap}
