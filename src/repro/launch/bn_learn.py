"""Command line of the structure learner (repro/learner.py):
preprocess → multi-chain order-MCMC → best-graph exchange on a named
network's seeded samples, printing the score, the ROC point against the
ground truth and the run's mode.

  python -m repro.launch.bn_learn --network alarm --iters 2000 --chains 4
  python -m repro.launch.bn_learn --network alarm --q alarm \
      --preprocess fused                         # ALARM's published arities
  python -m repro.launch.bn_learn --network synth --n 64 --s 3 \
      --preprocess fused --prune-delta 30        # fused pipeline + compression
  python -m repro.launch.bn_learn --network synth --n 64 --arcs 102 \
      --score bge --preprocess fused             # continuous data, BGe

--q is the variables' number of states: one int for all, a comma list of
one per variable, or a named table (``alarm``: ALARM's published 2-4
states per variable); the data are sampled and scored at those arities.
--score bge samples continuous data instead (a linear-Gaussian SEM on the
network's DAG, standardized columns) and scores them with the Gaussian BGe
score; --arcs gives the synthetic DAG an exact arc count.
--telemetry writes the run's convergence trace as JSONL under --trace-dir;
--supervise and --fault-plan (grammar in runtime/faults.py) turn on chain
healing and deterministic chaos between segments.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..core import roc_point
from ..data.bn_sampler import inject_noise
from ..data.networks import network_data, parse_arity
# prepare_run (unused here) is kept for the benchmark harness (chipbench/)
from ..learner import (AUTO_PRUNE_S, Engine, LearnConfig, build_engine,
                       learn_structure, prepare_run, start_run)

__all__ = ["main"]


# kept for the benchmark harness (chipbench/)
def make_engine_closures(st, cfg: LearnConfig, n: int) -> Engine:
    return build_engine(st, cfg)


# kept for the benchmark harness (chipbench/)
def _build_segmented(st, cfg: LearnConfig, key, n: int, score_fn, window,
                     delta_fn, planes_fn, adaptive_ws, delta_fns, burn_in,
                     collector):
    engine = Engine(score_fn, window, delta_fn, planes_fn, adaptive_ws,
                    delta_fns, burn_in, planes_fn is not None)
    return start_run(st, cfg, engine, collector, key)


_network_data = network_data  # kept for the benchmark harness (chipbench/)


def main(argv=None) -> dict:
    from ..runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="alarm",
                    choices=["alarm", "stn", "synth"])
    ap.add_argument("--n", type=int, default=64,
                    help="node count for --network synth")
    ap.add_argument("--samples", type=int, default=1000)
    ap.add_argument("--iters", type=int, default=1000)
    ap.add_argument("--chains", type=int, default=1)
    ap.add_argument("--q", type=parse_arity, default=2,
                    help="states per variable: an int for all, a comma "
                         "list of one per variable, or 'alarm' (ALARM's "
                         "published arities)")
    ap.add_argument("--s", type=int, default=4)
    ap.add_argument("--score", default="bdeu", choices=["bdeu", "bge"],
                    help="bdeu: discrete states at --q; bge: continuous "
                         "linear-Gaussian samples, Gaussian BGe score")
    ap.add_argument("--arcs", type=int, default=0,
                    help="--network synth: exactly this many arcs, at most "
                         "4 parents a node (0: edge probability 0.45, at "
                         "most 3 parents)")
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--window", type=int, default=8,
                    help="bounded-move window for delta rescoring (0 = full)")
    ap.add_argument("--no-mask-cache", action="store_true",
                    help="disable the cached consistency bitmasks on the "
                         "dense delta paths (debug / A-B timing)")
    ap.add_argument("--adapt-window", action="store_true",
                    help="tune the move window from the running accept rate "
                         "over a static power-of-two set; frozen after "
                         "--burn-in iterations (MCMC validity)")
    ap.add_argument("--burn-in", type=int, default=0,
                    help="adaptation horizon for --adapt-window "
                         "(0 = iters // 5)")
    ap.add_argument("--sharded", action="store_true",
                    help="run MCMC on the production-mesh path: chains DP "
                         "over 'data', score table + cached consistency "
                         "planes TP over 'model' (one shard_map program per "
                         "iteration)")
    ap.add_argument("--sharded-tp", type=int, default=0,
                    help="model-axis extent for --sharded "
                         "(0 = all visible devices)")
    ap.add_argument("--exchange-every", type=int, default=0,
                    help="> 0: in-scan cross-chain exchange period — the "
                         "best chain re-seeds the worst every this many "
                         "iterations (0 = end-only reduction)")
    ap.add_argument("--preprocess", default="reference",
                    choices=["reference", "fused"],
                    help="score-table construction: core/scores host loop or "
                         "the fused preprocess/ pipeline")
    ap.add_argument("--prune-delta", type=float, default=0.0,
                    help="> 0: hash-compress the score table, keeping per "
                         "node only parent sets within this delta of its "
                         "best (fused preprocessing only)")
    ap.add_argument("--no-auto-prune", action="store_true",
                    help="disable the automatic switch to the streaming "
                         "pruned engine above S >= %d parent sets per node "
                         "(fused preprocessing only)" % AUTO_PRUNE_S)
    ap.add_argument("--cache-dir", default="experiments/score_cache",
                    help="preprocessing disk cache directory ('' disables); "
                         "only consulted with --preprocess fused")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="convergence telemetry: in-scan chain traces + "
                         "host-side split-R̂/edge-R̂ checks, appended as "
                         "schema-versioned JSONL under --trace-dir")
    ap.add_argument("--trace-every", type=int, default=8,
                    help="telemetry tap cadence in iterations (ring writes "
                         "+ thinned posterior adjacency samples)")
    ap.add_argument("--check-every", type=int, default=0,
                    help="collector check period (0 = auto: max(64, 16 * "
                         "trace_every); checkpointed runs check at "
                         "checkpoint boundaries)")
    ap.add_argument("--stop-on-converge", action="store_true",
                    help="stop early once split-R̂ AND edge-marginal R̂ stay "
                         "below --rhat-threshold for --patience consecutive "
                         "checks (implies --telemetry)")
    ap.add_argument("--rhat-threshold", type=float, default=1.05)
    ap.add_argument("--patience", type=int, default=3)
    ap.add_argument("--emit-consensus", action="store_true",
                    help="materialize the service query layer's posterior "
                         "artifacts in the result: edge-probability matrix "
                         "(core/metrics.edge_posterior over the telemetry "
                         "edge accumulator), MAP DAG under the best order, "
                         "and the thresholded consensus graph (implies "
                         "--telemetry)")
    ap.add_argument("--consensus-threshold", type=float, default=0.5,
                    help="edge-posterior probability cut for the consensus "
                         "graph (in (0, 1])")
    ap.add_argument("--trace-dir", default="experiments/runs",
                    help="JSONL trace directory for --telemetry")
    ap.add_argument("--run-name", default="",
                    help="trace file stem ('' = timestamped)")
    ap.add_argument("--supervise", action="store_true",
                    help="fault-tolerant run supervisor: verified "
                         "checkpoint restore with quarantine/fallback, and "
                         "telemetry-driven chain healing between segments "
                         "(NaN/inf + progress guards, collector "
                         "stuck/diverged flags → straggler cloning)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic chaos spec fired at segment "
                         "boundaries (grammar in runtime/faults.py), e.g. "
                         "'corrupt@1:bitflip;crash@1:after'")
    ap.add_argument("--heal-patience", type=int, default=1,
                    help="consecutive unhealthy checks before --supervise "
                         "heals a chain (1 = the next segment boundary)")
    args = ap.parse_args(argv)

    truth, data = network_data(args.network, args.samples, args.q, args.seed,
                               n_synth=args.n, arcs=args.arcs,
                               score=args.score)
    n_nodes = truth.shape[0]
    # reject degenerate windows HERE, with a readable message, instead of
    # letting propose_move silently clamp (window > n) or trace garbage
    # (window == 1 has no in-window move) deep inside the jit
    if args.window == 1 or args.window < 0:
        ap.error(f"--window {args.window} is invalid: the bounded-move "
                 "mixture needs window >= 2 (use --window 0 for the legacy "
                 "full-rescore transposition walk)")
    if args.window > n_nodes:
        ap.error(f"--window {args.window} exceeds the network's n="
                 f"{n_nodes} nodes; pick 2 <= window <= {n_nodes} (or 0) — "
                 "oversized windows would only be silently clamped")
    if args.noise:
        if args.score == "bge":
            ap.error("--noise flips discrete states; it has no meaning for "
                     "--score bge")
        data = inject_noise(np.random.default_rng(args.seed + 1), data,
                            args.noise, args.q)
    cfg = LearnConfig(q=args.q, s=args.s, score=args.score, iters=args.iters,
                      chains=args.chains, seed=args.seed,
                      use_kernel=args.use_kernel, window=args.window,
                      mask_cache=not args.no_mask_cache,
                      adapt_window=args.adapt_window, burn_in=args.burn_in,
                      sharded=args.sharded, sharded_tp=args.sharded_tp,
                      exchange_every=args.exchange_every,
                      preprocess=args.preprocess,
                      prune_delta=args.prune_delta,
                      auto_prune=not args.no_auto_prune,
                      cache_dir=(args.cache_dir if args.preprocess == "fused"
                                 else ""),
                      checkpoint_dir=args.checkpoint_dir,
                      checkpoint_every=args.checkpoint_every,
                      telemetry=args.telemetry,
                      trace_every=args.trace_every,
                      check_every=args.check_every,
                      stop_on_converge=args.stop_on_converge,
                      rhat_threshold=args.rhat_threshold,
                      patience=args.patience,
                      emit_consensus=args.emit_consensus,
                      consensus_threshold=args.consensus_threshold,
                      trace_dir=args.trace_dir,
                      run_name=args.run_name,
                      supervise=args.supervise,
                      fault_plan=args.fault_plan,
                      heal_patience=args.heal_patience)
    out = learn_structure(data, cfg)
    fp, tp = roc_point(out["adjacency"], truth)
    out["tp_rate"], out["fp_rate"] = tp, fp
    if out["adaptive_windows"]:
        mode = f"adaptive(w∈{{{','.join(map(str, out['adaptive_windows']))}}})"
    elif out["delta_window"]:
        mode = f"delta(w={out['delta_window']})"
    else:
        mode = "full"
    if out["mask_cache"]:
        mode += "+bitmask"
    if out.get("sharded"):
        mode += f"+sharded({jax.device_count()}dev)"
    if out["exchange_every"]:
        mode += f"+exch({out['exchange_every']})"
    pre = f"pre={out['preprocess_s']:.2f}s"
    if args.preprocess == "fused":
        tags = ["fused"] + (["bge"] if args.score == "bge" else [])
        if out.get("auto_pruned"):
            tags.append("auto-pruned")
        if out["preprocess_cache_hit"]:
            tags.append("cache hit")
        pre += f" ({', '.join(tags)})"
    print(f"{args.network}: n={truth.shape[0]} S={out['S']} "
          f"score={out['score']:.2f} TP={tp:.3f} FP={fp:.4f} "
          f"{pre} "
          f"iter={out['iteration_s']:.2f}s "
          f"({out['per_iteration_s']*1e3:.2f} ms/it, {mode}, "
          f"accept={out['accept_rate']:.2f})")
    # one-line run summary: per-chain mixing at a glance
    rates = " ".join(f"{r:.2f}" for r in out["chain_accept_rates"])
    summary = f"chains: accept=[{rates}]"
    if out["window_hist"]:
        summary += f" win_hist={out['window_hist']}"
    if out["exchange_count"]:
        summary += f" exchanges={out['exchange_count']}"
    if out.get("heals"):
        events = " ".join(f"{h['chain']}<-{h['donor']}@{h['iter']}"
                          f"({h['reason']})" for h in out["heals"])
        summary += f" heals=[{events}]"
    if "consensus" in out:
        summary += (f" | consensus: {int(out['consensus'].sum())} edges "
                    f"@ p>={args.consensus_threshold:g}, "
                    f"MAP: {int(out['map_dag'].sum())} edges")
    tele = out.get("telemetry")
    if tele is not None:
        summary += (f" | R̂(score)={tele['score_rhat']:.3f} "
                    f"R̂(edges)={tele['edge_rhat']:.3f}")
        if out["stopped_early"]:
            summary += (f" — converged, stopped at "
                        f"{out['iters_run']}/{args.iters} iters")
        summary += f" → {tele['trace_path']}"
    print(summary)
    return out


if __name__ == "__main__":
    main()
