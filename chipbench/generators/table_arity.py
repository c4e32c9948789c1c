"""Generator of table-build traffic at per-variable arities.

As ``table.py``: set-up draws ``datasets`` seeded datasets and builds one
more table from an extra dataset, which compiles every program a build runs
(one per bin-count bucket); a unit of the window is one
``build_score_table_fused`` call, with no disk cache, on the next dataset,
waited for until its (n, S) table is on the device; the window closes at
the first build that completes after ``--seconds``.

The datasets are ALARM's at the configuration's arities ``q`` (one per
variable), drawn by ``gen_arity``; the check compares the window's last
table with ``reference_arity``'s table of the same dataset. The counters
add the build's ``bins_real`` (the sum of q_sigma over the column subsets)
and ``bins_computed`` (chunk rows times their bucket's bin count), and its
buckets.
"""
from __future__ import annotations

import time

import jax.numpy as jnp

from chipbench import gen, gen_arity, reference, reference_arity
from chipbench.generators import table

UNIT = table.UNIT
STAGES = table.STAGES


class Generator(table.Generator):
    def setup(self) -> None:
        c = self.cfg
        k = int(self.traffic["datasets"])
        t0 = time.perf_counter()
        self.datasets = [
            gen_arity.network_data(c["network"], c["m"], c["q"],
                                   gen.dataset_rng(self.seed, i), c["n"])[1]
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
                         "builds": 0, "traced_builds": 0,
                         "bins_real": info["bins_real"],
                         "bins_computed": info["bins_computed"],
                         "q_buckets": info["plan"]["q_buckets"],
                         **{t: 0.0 for t in times},
                         **{"traced_" + t: 0.0 for t in times}}

    # ------------------------------------------------------------- check
    def _reference(self, d: int, dtype=jnp.float32):
        c = self.cfg
        return reference_arity.reference_table(
            self.datasets[d], q=c["q"], s=c["s"], gamma=c["gamma"],
            ess=c["ess"], dtype=dtype)

    def control(self, out: dict) -> dict:
        """The reference in bfloat16, put in the program's place."""
        return {"tables": [(d, jnp.asarray(self._reference(d, jnp.bfloat16),
                                           jnp.float32))
                           for d, _ in out["tables"]]}

    def numbers(self, out: dict) -> dict:
        gap = 0.0
        for d, tbl in out["tables"]:
            gap = max(gap, reference.rel_gap(tbl, self._reference(d)))
        if not out["tables"]:
            gap = float("inf")
        return {"table_rel_gap": gap}
