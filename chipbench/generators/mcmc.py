"""Generator of order-MCMC traffic: one learning job, segment by segment.

Set-up builds the score table from the seed's dataset through the fused
preprocessing (``prepare_run``), assembles the engine as the posterior
service does (``make_engine_closures`` -> ``_build_segmented``) and runs
the warm segments, which compile the segment runner. A unit of the window
is one ``RunSupervisor.advance()``: one segment of ``check_every``
iterations of every chain, then the collector's check. The iteration cap
is far beyond any window, so every segment has the same length and nothing
compiles inside the window.

The check compares, once the window has closed, with the plain reference
(``chipbench/reference.py``):

* ``table_rel_gap``: the table the chains used, every entry;
* per chain, at its final order: ``cache_rel_gap`` (cached per-node best
  scores), ``score_rel_gap`` (cached order score), ``choice_rel_gap`` (how
  far the cached parent sets score below the best consistent ones),
  ``choice_inconsistent`` (cached parent sets not consistent with the
  order) and ``plane_mismatch`` (violation counts in the consistency
  planes that differ from the order's);
* the best graph: ``best_rel_gap`` (each chain's best score against the
  reference score of its best parent sets), ``best_inconsistent`` (best
  parent sets not consistent with the best order, so not a DAG) and
  ``graph_mismatch`` (the program's decoded adjacency against the
  reference's decoding of the same ranks);
* that the window walked: ``steps_off`` (a chain's iteration count off the
  supervisor's) and ``stuck_chains`` (chains that accepted no move in the
  window).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen, reference

UNIT = "segment"


def _annotated(fn, span: str):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(span):
            return fn(*args, **kwargs)
    return wrapper


class Generator:
    def __init__(self, config: dict, traffic: dict, seed: int, work: str,
                 run_name: str):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.work, self.run_name = work, run_name
        self.sup = self.st = self._ref = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.launch.bn_learn import (LearnConfig, _build_segmented,
                                           make_engine_closures, prepare_run)

        c = self.cfg
        t0 = time.perf_counter()
        _, self.data = gen.network_data(
            c["network"], c["m"], c["q"], gen.dataset_rng(self.seed), c["n"])
        eng = c["engine"]
        lc = LearnConfig(q=c["q"], s=c["s"], gamma=c["gamma"], ess=c["ess"],
                         iters=int(self.traffic["iters"]), chains=c["chains"],
                         seed=self.seed % 2 ** 31, window=c["window"],
                         use_kernel=eng["use_kernel"],
                         mask_cache=eng["mask_cache"],
                         auto_prune=eng["auto_prune"], preprocess="fused",
                         telemetry=c["telemetry"],
                         trace_every=c["trace_every"],
                         check_every=c["check_every"], trace_dir=self.work,
                         run_name=self.run_name)
        t1 = time.perf_counter()
        st, collector, _ = prepare_run(self.data, lc)
        jax.block_until_ready(st.table)
        t2 = time.perf_counter()
        n = self.data.shape[1]
        (score_fn, window, delta_fn, planes_fn, adaptive_ws, delta_fns,
         burn_in, mask_on) = make_engine_closures(st, lc, n)
        if not mask_on or window != c["window"]:
            raise RuntimeError("the engine is not the dense bitmask delta "
                               f"engine at window {c['window']}")
        sup = _build_segmented(st, lc, jax.random.key(lc.seed), n, score_fn,
                               window, delta_fn, planes_fn, adaptive_ws,
                               delta_fns, burn_in, collector)
        # host spans around the calls into each layer, read by the trace
        # reduction to label the device's idle gaps
        sup._run_segment = _annotated(sup._run_segment, "bench.dispatch")
        if collector is not None:
            collector.check = _annotated(collector.check,
                                         "bench.collector_check")
        self.st, self.sup = st, sup
        jax.block_until_ready(sup.states)
        t3 = time.perf_counter()
        for _ in range(int(self.traffic["warm_segments"])):
            sup.advance()
        self.sync()
        self.accepts0 = np.asarray(sup.states.accepts)
        self.phases = {"dataset_s": t1 - t0, "table_s": t2 - t1,
                       "engine_s": t3 - t2,
                       "warm_s": time.perf_counter() - t3}
        self.counters = {"chains": c["chains"], "n": c["n"], "S": st.S,
                         "s": c["s"], "window": window, "steps": 0,
                         "traced_steps": 0}

    def step(self) -> int:
        """One segment; returns the chain iterations it ran."""
        before = self.sup.iters_done
        self.sup.advance()
        done = self.sup.iters_done - before
        self.counters["steps"] += done
        return done * self.cfg["chains"]

    def traced(self, steps: int) -> None:
        self.counters["traced_steps"] += steps // self.cfg["chains"]

    def sync(self) -> None:
        jax.block_until_ready(self.sup.states)

    # ------------------------------------------------------------- check
    def outputs(self) -> dict:
        """What the window produced; frees the rest of the program's state."""
        from repro.core import adjacency_from_ranks
        from repro.core.mcmc import exchange_best

        s = self.sup.states
        _, best_idx, _ = exchange_best(s)
        out = {"table": self.st.table, "pos": s.pos, "score": s.score,
               "cur_ls": s.cur_ls, "cur_idx": s.cur_idx,
               "planes": s.mask_planes, "best_score": s.best_score,
               "best_idx": s.best_idx, "best_pos": s.best_pos,
               "step": np.asarray(s.step),
               "accepts": np.asarray(s.accepts) - self.accepts0,
               "iters_done": self.sup.iters_done,
               "graph": adjacency_from_ranks(np.asarray(best_idx),
                                             s=self.cfg["s"]),
               "graph_chain": int(jnp.argmax(s.best_score))}
        self.sup = self.st = None
        return out

    def control(self, out: dict) -> dict:
        """The reference in bfloat16, put in the program's place."""
        c = self.cfg
        bf = jnp.bfloat16
        table = reference.reference_table(self.data, q=c["q"], s=c["s"],
                                          gamma=c["gamma"], ess=c["ess"],
                                          dtype=bf)
        psets = jnp.asarray(reference.parent_sets(c["n"] - 1, c["s"]))
        P, W = out["planes"].shape[-2:]
        cur = [reference.order_best(table, psets, p) for p in out["pos"]]
        best = [reference.order_best(table, psets, p)
                for p in out["best_pos"]]
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        ctrl = dict(out)
        ctrl.update(
            table=f32(table),
            score=jnp.stack([f32(ls.sum(dtype=bf)) for ls, _, _ in cur]),
            cur_ls=jnp.stack([f32(ls) for ls, _, _ in cur]),
            cur_idx=jnp.stack([i for _, i, _ in cur]),
            planes=jnp.stack([reference.pack_counts(v, P=P, W=W)
                              for _, _, v in cur]),
            best_score=jnp.stack([f32(ls.sum(dtype=bf)) for ls, _, _ in best]),
            best_idx=jnp.stack([i for _, i, _ in best]))
        ctrl["graph"] = reference.decode_graph(
            np.asarray(ctrl["best_idx"][out["graph_chain"]]), c["s"])
        return ctrl

    def numbers(self, out: dict) -> dict:
        """Every number compared, each to be held against its limit."""
        c = self.cfg
        if self._ref is None:
            self._ref = reference.reference_table(
                self.data, q=c["q"], s=c["s"], gamma=c["gamma"], ess=c["ess"])
        table = self._ref
        psets = jnp.asarray(reference.parent_sets(c["n"] - 1, c["s"]))
        S = psets.shape[0]
        nodes = jnp.arange(c["n"], dtype=jnp.int32)
        chains = range(len(out["pos"]))
        cache = score = choice = best = 0.0
        inconsistent = best_inconsistent = planes = 0
        for k in chains:
            pos = out["pos"][k]
            ls, _, viol = reference.order_best(table, psets, pos)
            cache = max(cache, reference.rel_gap(out["cur_ls"][k], ls))
            score = max(score, reference.rel_gap(out["score"][k], ls.sum()))
            idx = out["cur_idx"][k]
            ok = jax.vmap(reference.consistent, (None, None, 0, 0))(
                psets, pos, nodes, idx)
            inconsistent += int((~ok).sum())
            got = table[nodes, jnp.clip(idx, 0, S - 1)]
            choice = max(choice, reference.rel_gap(got, ls, signed=True))
            planes += int((reference.unpack_counts(out["planes"][k], S=S)
                           != viol).sum())
            bidx = out["best_idx"][k]
            ok = jax.vmap(reference.consistent, (None, None, 0, 0))(
                psets, out["best_pos"][k], nodes, bidx)
            best_inconsistent += int((~ok).sum())
            best = max(best, reference.rel_gap(
                out["best_score"][k],
                table[nodes, jnp.clip(bidx, 0, S - 1)].sum()))
        w = out["graph_chain"]
        want = reference.decode_graph(np.asarray(out["best_idx"][w]), c["s"])
        return {
            "table_rel_gap": reference.rel_gap(out["table"], table),
            "cache_rel_gap": cache,
            "score_rel_gap": score,
            "choice_rel_gap": choice,
            "choice_inconsistent": inconsistent,
            "plane_mismatch": planes,
            "best_rel_gap": best,
            "best_inconsistent": best_inconsistent,
            "graph_mismatch": int(np.abs(np.asarray(out["graph"], np.int64)
                                         - want).sum()),
            "steps_off": int(np.abs(np.asarray(out["step"], np.int64)
                                    - out["iters_done"]).max()),
            "stuck_chains": int((np.asarray(out["accepts"]) == 0).sum()),
        }

