"""The structure learner as a library (the paper's full pipeline, Fig. 2):
preprocess → multi-chain order-MCMC → best-graph exchange.

One run is four calls, shared by ``learn_structure``, the posterior
service's jobs (service/jobs.py) and the benchmark harness:

* :func:`prepare_run` builds the score table — the reference host loop, or
  the fused preprocess/ pipeline with its disk cache; above
  S >= AUTO_PRUNE_S parent sets per node the fused path defaults to the
  streaming-pruned table and the O(n*K) pruned scorers — and the telemetry
  collector.
* :func:`build_engine` decides the engine kind once (pruned, sum, Pallas
  kernel or blocked XLA; bitmask-cached consistency planes or the gather
  delta) and returns the :class:`Engine`: the score closure, the delta
  closure(s) for the static move window (one per candidate window with
  ``adapt_window``) and the chain's planes function.
* :func:`start_run` initialises the vmapped chains, builds the jitted
  segment runner (core/mcmc.make_traced_segment_runner) and arms the
  RunSupervisor (runtime/supervisor.py); ``cfg.sharded`` runs the same loop
  over the production mesh (core/sharded_scoring.sharded_chain_step).
* the caller advances the supervisor until it has finished, then
  :func:`finish_run` decodes the best graph and builds the result dict.

Every walk is one loop: segments of the traced runner driven by the
supervisor. A run that needs the host only at its end (no checkpoint,
telemetry, supervision or fault plan) is one segment of ``cfg.iters``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import asdict, dataclass
from typing import Callable, NamedTuple

import jax
import numpy as np

from .core import adjacency_from_ranks, build_score_table
from .core.combinatorics import n_parent_sets
from .core.mcmc import (BitmaskDelta, exchange_best, init_chain,
                        make_traced_segment_runner, mcmc_step,
                        mcmc_step_adaptive)
from .core.metrics import consensus_graph, edge_posterior, map_dag
from .core.order_scoring import (build_membership_planes,
                                 build_violation_planes, delta_window,
                                 score_order_blocked, score_order_delta,
                                 score_order_delta_bitmask,
                                 score_order_pruned, score_order_pruned_delta,
                                 score_order_sum_cached, score_order_sum_delta)
from .data.networks import parse_arity
from .preprocess import SparseScoreTable, build_score_table_fused
from .runtime.faults import parse_fault_plan
from .runtime.supervisor import RunSupervisor, SupervisedResult

__all__ = ["LearnConfig", "Engine", "prepare_run", "build_engine",
           "start_run", "finish_run", "learn_structure",
           "adaptive_window_set", "AUTO_PRUNE_S", "AUTO_PRUNE_DELTA"]

# Above this many parent sets per node, the fused path defaults to the
# streaming-pruned engine (preprocess/streaming.py + the O(n*K) pruned
# scorers): the dense (n, S) table at S = 200k, n = 100 is ~80 MB and the
# (n, S) rank map doubles it, while the pruned table is a few MB — and at
# the n = 100, s = 4 gate (S ~ 3.9M) dense assembly is simply out of reach.
AUTO_PRUNE_S = 200_000
# Default pruning delta for the auto-switch. Kept wide (natural-log units):
# parent sets more than 20 nats below a node's per-node best contribute
# nothing to the max-scorer walk in practice, so the exactness condition
# (dense argmax survives pruning) holds at equilibrium.
AUTO_PRUNE_DELTA = 20.0


@dataclass
class LearnConfig:
    q: int | tuple = 2            # states per variable: one int for all, or
                                  # one per variable (also "2,3,..." or a
                                  # named table, see data.networks)
    s: int = 4                    # max parent-set size (paper uses 4)
    gamma: float = 0.1            # structure penalty
    ess: float = 1.0              # BDeu equivalent sample size
    iters: int = 1000
    chains: int = 1
    seed: int = 0
    block: int = 4096             # score-table streaming block
    use_kernel: bool = False      # Pallas kernel (interpret=True on CPU)
    scorer: str = "max"           # "max" (paper Eq. 6) | "sum" (baseline [5])
    window: int = 8               # bounded-move window; delta rescoring when
                                  # 2 <= window <= DELTA_CROSSOVER*n (0 = off)
    mask_cache: bool = True       # cached consistency bitmasks on the dense
                                  # delta paths (blocked + kernel)
    adapt_window: bool = False    # adaptive window set + burn-in freeze
    burn_in: int = 0              # adaptation horizon (0 = iters // 5)
    exchange_every: int = 0       # in-scan cross-chain exchange period (0 =
                                  # end-only reduction)
    checkpoint_every: int = 0     # 0 = off
    checkpoint_dir: str = ""
    sharded: bool = False         # run the MCMC on the sharded mesh path:
                                  # chains DP over 'data', score table +
                                  # cached consistency planes TP over 'model'
    sharded_tp: int = 0           # model-axis extent (0 = all devices)
    preprocess: str = "reference"  # "reference" (core/scores host loop) |
                                   # "fused" (preprocess/ pipeline)
    prune_delta: float = 0.0      # > 0: hash-compress the table, keeping per
                                  # node only parent sets within this delta
                                  # of its best (fused pipeline only)
    auto_prune: bool = True       # fused path: switch to the streaming
                                  # pruned engine (delta=AUTO_PRUNE_DELTA)
                                  # when S >= AUTO_PRUNE_S and the run is
                                  # compatible (max scorer, not sharded)
    cache_dir: str = ""           # preprocessing disk cache ("" = off)
    # --- convergence telemetry (repro.telemetry) -------------------------
    telemetry: bool = False       # in-scan taps + host collector + JSONL
    trace_every: int = 8          # tap cadence (iterations per ring write)
    check_every: int = 0          # collector check period (0 = auto:
                                  # max(64, 16 * trace_every); checkpointed
                                  # runs check at checkpoint boundaries)
    stop_on_converge: bool = False  # R̂ early stopping (implies telemetry)
    emit_consensus: bool = False  # materialize posterior artifacts in the
                                  # result dict — edge-probability matrix,
                                  # MAP DAG, thresholded consensus graph —
                                  # from the telemetry edge accumulator
                                  # (implies telemetry; the same artifacts
                                  # the service query layer serves)
    consensus_threshold: float = 0.5  # edge-posterior cut for the consensus
    rhat_threshold: float = 1.05  # both R̂s must drop below this ...
    patience: int = 3             # ... for this many consecutive checks
    trace_dir: str = "experiments/runs"  # JSONL trace directory
    run_name: str = ""            # trace file stem ("" = timestamped)
    # --- fault-tolerant run supervisor (runtime/supervisor) --------------
    supervise: bool = False       # telemetry-driven chain healing between
                                  # segments (NaN/inf + progress guards,
                                  # collector stuck/diverged flags)
    fault_plan: str = ""          # deterministic chaos spec (grammar in
                                  # runtime/faults.py), e.g.
                                  # "corrupt@1:bitflip;crash@1:after"
    heal_patience: int = 1        # consecutive unhealthy checks before a
                                  # chain is healed (1 = next boundary)

    def __post_init__(self):
        self.q = parse_arity(self.q)


class Engine(NamedTuple):
    """The closures one walk runs, from one decision on the engine kind.

    score_fn: pos -> (score, best_idx, best_ls), the full rescore.
    window: the static move window of the delta path (0 = full rescore
        every iteration, and with ``adaptive_ws``).
    delta_fn: the incremental rescore for ``window`` (a BitmaskDelta when
        the planes cache is on), or None.
    planes_fn: pos -> one chain's consistency planes, or None without the
        cache (on the sharded engine: stacked (C, n) pos -> stacked planes).
    adaptive_ws, delta_fns, burn_in: the adaptive window set, one delta
        closure per window, and the adaptation horizon (empty / 0 off).
    mask_on: the chains carry the bitmask planes cache.
    """
    score_fn: Callable
    window: int
    delta_fn: Callable | BitmaskDelta | None
    planes_fn: Callable | None
    adaptive_ws: tuple
    delta_fns: tuple
    burn_in: int
    mask_on: bool


def _padded(st, block: int):
    """(table, pst, block) with S padded to a multiple of block — shared by
    the full and delta closures so both see identical blocks. block is
    rounded up to a multiple of 32 so the packed consistency-mask words of
    the bitmask cache line up with the same block structure."""
    from .core.sharded_scoring import pad_table
    block = min(block, st.table.shape[1])
    block = block + (-block) % 32
    table, pst = pad_table(st.table, st.pst, block)
    return table, pst, block


def adaptive_window_set(n: int) -> tuple[int, ...]:
    """Static candidate windows for --adapt-window: powers of two from 2 up
    to the delta-crossover cap (each pre-traced as its own lax.switch
    branch, so the set must stay small)."""
    ws, w = [], 2
    while delta_window(n, w) == w:
        ws.append(w)
        w *= 2
    return tuple(ws) or (2,)


def build_engine(st, cfg: LearnConfig) -> Engine:
    """The :class:`Engine` over either table representation: dense
    ScoreTable (blocked XLA or Pallas kernel scorers, sum baseline) or
    preprocess.SparseScoreTable (packed pruned scorer, O(n*K)). The
    window-independent state (padded tables, membership planes) is built
    once, even when the adaptive path needs one delta closure per window."""
    n = st.n
    if cfg.adapt_window:
        if cfg.checkpoint_every and cfg.checkpoint_dir:
            raise ValueError("--adapt-window does not compose with "
                             "checkpointing yet: the dual-averaging state "
                             "would restart each segment, breaking the "
                             "burn-in freeze contract")
        windows = adaptive_window_set(n)
    else:
        w = delta_window(n, cfg.window)
        windows = (w,) if w else ()
    planes_fn = None
    if isinstance(st, SparseScoreTable):
        if cfg.scorer == "sum":
            raise ValueError(
                "the sum (logsumexp) baseline scorer needs the dense table: "
                "run without --prune-delta (pruned entries would silently "
                "drop out of the logsumexp)")
        kept = (st.kept_ls, st.kept_parents, st.kept_idx)
        score_fn = functools.partial(score_order_pruned, *kept)

        def delta_for(w):
            def sfn(pos, lo, prev_ls, prev_idx):
                return score_order_pruned_delta(*kept, pos, prev_ls, prev_idx,
                                                lo, window=w)
            return sfn
    elif cfg.scorer == "sum":
        # the Linderman et al. [5] baseline the paper improves on (§III-B);
        # the _cached variant's third output is the per-node logsumexp, so
        # the sampler's cur_ls cache feeds score_order_sum_delta
        score_fn = functools.partial(score_order_sum_cached, st.table, st.pst)

        def delta_for(w):
            def lfn(pos, lo, prev_ls, prev_idx):
                return score_order_sum_delta(st.table, st.pst, pos, prev_ls,
                                             prev_idx, lo, window=w)
            return lfn
    elif cfg.use_kernel:
        from .kernels.order_score import (BLOCK_S, order_score,
                                          order_score_delta,
                                          order_score_delta_bitmask,
                                          pad_for_kernel)
        score_fn = functools.partial(order_score, st.table, st.pst)
        if windows:
            # pre-pad once so the per-iteration call's pad is a no-op (the
            # blocked path hoists its padding the same way via _padded)
            ktable, kpst = pad_for_kernel(st.table, st.pst, BLOCK_S)
            if cfg.mask_cache:
                cm = build_membership_planes(kpst, n)
                planes_fn = functools.partial(build_violation_planes, kpst)

        def delta_for(w):
            if planes_fn is not None:
                def kbfn(pos, lo, prev_ls, prev_idx, pos_old, planes):
                    return order_score_delta_bitmask(ktable, cm, pos, prev_ls,
                                                     prev_idx, lo, pos_old,
                                                     planes, window=w)
                return BitmaskDelta(kbfn)

            def kfn(pos, lo, prev_ls, prev_idx):
                return order_score_delta(ktable, kpst, pos, prev_ls,
                                         prev_idx, lo, window=w)
            return kfn
    else:
        table, pst, block = _padded(st, cfg.block)
        score_fn = functools.partial(score_order_blocked, table, pst,
                                     block=block)
        if windows and cfg.mask_cache:
            cm = build_membership_planes(pst, n)
            planes_fn = functools.partial(build_violation_planes, pst)

        def delta_for(w):
            if planes_fn is not None:
                def bfn(pos, lo, prev_ls, prev_idx, pos_old, planes):
                    return score_order_delta_bitmask(table, cm, pos, prev_ls,
                                                     prev_idx, lo, pos_old,
                                                     planes, window=w,
                                                     block=block)
                return BitmaskDelta(bfn)

            def fn(pos, lo, prev_ls, prev_idx):
                return score_order_delta(table, pst, pos, prev_ls, prev_idx,
                                         lo, window=w, block=block)
            return fn
    delta_fns = tuple(delta_for(w) for w in windows)
    mask_on = planes_fn is not None
    if cfg.adapt_window:
        return Engine(score_fn, 0, None, planes_fn, windows, delta_fns,
                      cfg.burn_in or cfg.iters // 5, mask_on)
    window, delta_fn = (windows[0], delta_fns[0]) if windows else (0, None)
    return Engine(score_fn, window, delta_fn, planes_fn, (), (), 0, mask_on)


def prepare_run(data: np.ndarray, cfg: LearnConfig, *,
                prior_matrix: np.ndarray | None = None):
    """The preprocess + telemetry half of the pipeline, shared by
    :func:`learn_structure` and the posterior service's job manager
    (service/jobs.py): builds the score table (reference or fused pipeline,
    auto-prune switch, disk cache) and the telemetry collector.

    Returns (st, collector, pre) with pre = {"t_pre", "cache_hit",
    "auto_pruned"}."""
    n = data.shape[1]
    telem = cfg.telemetry or cfg.stop_on_converge or cfg.emit_consensus
    collector = None
    if telem:
        from .telemetry import Collector
        collector = Collector(cfg.trace_dir, run_name=cfg.run_name,
                              rhat_threshold=cfg.rhat_threshold,
                              patience=cfg.patience,
                              trace_every=cfg.trace_every)
        collector.start(config={**asdict(cfg), "n": n,
                                "m": int(data.shape[0])})
    t0 = time.time()
    cache_hit = False
    prune_delta = cfg.prune_delta if cfg.prune_delta > 0 else None
    auto_pruned = False
    if (cfg.preprocess == "fused" and prune_delta is None and cfg.auto_prune
            and not cfg.sharded and cfg.scorer == "max"
            and n_parent_sets(n - 1, cfg.s) >= AUTO_PRUNE_S):
        # default engine above the size threshold: streaming-pruned table +
        # O(n*K) pruned scorers — the dense (n, S) build is the memory wall
        prune_delta = AUTO_PRUNE_DELTA
        auto_pruned = True
    if cfg.preprocess == "fused":
        st, pre_info = build_score_table_fused(
            data, q=cfg.q, s=cfg.s, gamma=cfg.gamma, ess=cfg.ess,
            prior_matrix=prior_matrix, prune_delta=prune_delta,
            cache_dir=cfg.cache_dir or None, return_info=True)
        cache_hit = pre_info["cache_hit"]
    else:
        st = build_score_table(data, q=cfg.q, s=cfg.s, gamma=cfg.gamma,
                               ess=cfg.ess, prior_matrix=prior_matrix)
    jax.block_until_ready(st.kept_ls if isinstance(st, SparseScoreTable)
                          else st.table)
    t_pre = time.time() - t0
    if collector is not None:
        stages = (pre_info.get("stages", {})
                  if cfg.preprocess == "fused" else {})
        collector.stage("preprocess", t_pre, cache_hit=cache_hit,
                        auto_pruned=auto_pruned, **stages)
    return st, collector, {"t_pre": t_pre, "cache_hit": cache_hit,
                           "auto_pruned": auto_pruned}


def _supervise(cfg: LearnConfig, n: int, step, states, collector,
               stacked_planes_fn, *, n_windows: int = 1,
               stacked_step: bool = False) -> RunSupervisor:
    """The run loop every walk shares: telemetry taps and the in-scan
    exchange around ``step`` in one jitted segment runner, and the
    RunSupervisor that drives it, armed via ``begin``.

    Segments are checkpoint_every long when checkpointed; else the
    collector's check period when the host must see the walk (telemetry,
    supervision, a fault plan); else the whole walk is one segment."""
    trace = tap = exchange = None
    if collector is not None:
        from .telemetry import exchange_step_traced, init_trace, make_tap
        trace = init_trace(cfg.chains, n, n_windows=n_windows)
        tap = make_tap(n, cfg.s, cfg.trace_every)
        exchange = exchange_step_traced
    run_segment = make_traced_segment_runner(
        step, tap=tap, exchange=exchange,
        exchange_every=cfg.exchange_every if cfg.chains > 1 else 0,
        stacked_step=stacked_step)
    if cfg.checkpoint_every and cfg.checkpoint_dir:
        seg = cfg.checkpoint_every
    elif collector is not None or cfg.supervise or cfg.fault_plan:
        # frequent enough that --stop-on-converge reacts soon after mixing,
        # coarse enough that each segment accumulates >= 16 taps at the
        # default --trace-every 8 and re-entry cost stays negligible
        seg = cfg.check_every or max(64, 16 * cfg.trace_every)
    else:
        seg = cfg.iters
    faults = (parse_fault_plan(cfg.fault_plan, seed=cfg.seed)
              if cfg.fault_plan else None)
    sup = RunSupervisor(
        iters=cfg.iters, seg=seg, chains=cfg.chains,
        checkpoint_dir=cfg.checkpoint_dir,
        checkpoint_every=cfg.checkpoint_every,
        collector=collector, stop_on_converge=cfg.stop_on_converge,
        faults=faults, heal=cfg.supervise, heal_patience=cfg.heal_patience,
        seed=cfg.seed, planes_fn=stacked_planes_fn, cache_dir=cfg.cache_dir)
    return sup.begin(run_segment, states, trace)


def start_run(st, cfg: LearnConfig, engine: Engine, collector,
              key) -> RunSupervisor:
    """Vmapped chain init from ``split(key, chains)``, the jitted segment
    runner over ``engine``, and the RunSupervisor, armed via ``begin``:
    drive it with ``advance`` until ``finished``, then read ``result()``.
    The service's jobs start through here too, so a job interleaved with
    others walks through bitwise-identical segment boundaries to a
    standalone run."""
    n = st.n
    keys = jax.random.split(key, cfg.chains)
    states = jax.vmap(lambda k: init_chain(
        k, n, engine.score_fn, planes_fn=engine.planes_fn,
        win_idx=len(engine.adaptive_ws) // 2))(keys)
    if engine.adaptive_ws:
        # valid across segments: win_idx/adapt_err/step are ChainState
        # leaves, so the dual-averaging iterate and the burn-in freeze use
        # GLOBAL step counts no matter where segment boundaries fall
        step = lambda s: mcmc_step_adaptive(s, engine.score_fn,
                                            engine.delta_fns,
                                            engine.adaptive_ws,
                                            burn_in=engine.burn_in)
    else:
        step = lambda s: mcmc_step(s, engine.score_fn, engine.delta_fn,
                                   engine.window)
    planes = (jax.vmap(engine.planes_fn) if engine.planes_fn is not None
              else None)
    return _supervise(cfg, n, step, states, collector, planes,
                      n_windows=max(len(engine.adaptive_ws), 1))


def _sharded_mesh(st, cfg: LearnConfig):
    """The (data, model) mesh of --sharded, after checking the run fits."""
    if isinstance(st, SparseScoreTable):
        raise ValueError(
            "--sharded needs the dense (n, S) table: the pruned "
            "representation is already O(n*K) per device (drop --prune-delta)")
    if cfg.scorer == "sum":
        raise ValueError("--sharded supports the max scorer (paper Eq. 6) "
                         "only")
    if cfg.adapt_window:
        raise ValueError("--sharded does not compose with --adapt-window "
                         "yet: per-window delta closures would each need "
                         "their own shard_map branch")
    ndev = jax.device_count()
    tp = cfg.sharded_tp or ndev
    if ndev % tp:
        raise ValueError(f"--sharded-tp {tp} does not divide the "
                         f"{ndev}-device platform")
    dp = ndev // tp
    if cfg.chains % dp:
        raise ValueError(f"--chains {cfg.chains} must be divisible by the "
                         f"data-axis extent {dp}")
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _start_sharded(st, cfg: LearnConfig, mesh, collector, key):
    """(Engine, armed RunSupervisor) of the production-mesh engine
    (--sharded), under ``jax.set_mesh(mesh)``: every iteration is ONE
    shard_map program (core/sharded_scoring.sharded_chain_step) — chains DP
    over 'data', score table + cached consistency planes TP over 'model';
    per iteration only the (window,) pmax/pmin pair crosses ICI. The taps
    read only per-chain quantities the engine's pmax/pmin already
    replicated, so telemetry adds no collective traffic."""
    from .core.sharded_scoring import (_shard_block, make_sharded_planes_fn,
                                       pad_table, score_order_sharded,
                                       sharded_chain_step)

    n, tp = st.n, mesh.shape["model"]
    block = _shard_block(st.table.shape[1], tp, cfg.block)
    table, pst = pad_table(st.table, st.pst, tp * block)
    w = delta_window(n, cfg.window)
    mask_on = bool(w) and cfg.mask_cache
    cm = build_membership_planes(pst, n) if mask_on else None
    splanes_fn = (make_sharded_planes_fn(pst, mesh, stacked=True)
                  if mask_on else None)

    def score_fn(pos):
        return score_order_sharded(table, pst, pos, mesh, block=block)

    def step(stt):
        return sharded_chain_step(stt, table, pst, mesh, cm, block=block,
                                  window=cfg.window,
                                  use_kernel=cfg.use_kernel)

    keys = jax.random.split(key, cfg.chains)
    states = jax.vmap(lambda k: init_chain(k, n, score_fn))(keys)
    if mask_on:
        # per-shard plane build: each device packs its own S-shard words
        states = states._replace(mask_planes=splanes_fn(states.pos))
    engine = Engine(score_fn, w, None, splanes_fn, (), (), 0, mask_on)
    return engine, _supervise(cfg, n, step, states, collector, splanes_fn,
                              stacked_step=True)


def finish_run(cfg: LearnConfig, st, engine: Engine, res: SupervisedResult,
               *, pre: dict, t_iter: float, collector,
               sharded: bool = False) -> dict:
    """Common run epilogue: cross-chain best-graph exchange, rank-decoded
    adjacency (Algorithm 2 in reverse, which works from the O(n*K) pruned
    representation too), per-chain statistics, the result dict, and — with
    telemetry on — the final trace row. ``pre`` is :func:`prepare_run`'s."""
    states, iters_run = res.states, res.iters_run
    best_score, best_idx, best_pos = exchange_best(states)
    adj = adjacency_from_ranks(np.asarray(best_idx), s=cfg.s)
    acc = np.asarray(states.accepts)
    chain_rates = [float(a) / max(iters_run, 1) for a in acc]
    ws = engine.adaptive_ws
    if ws:
        wi = np.asarray(states.win_idx)
        win_hist = np.bincount(np.clip(wi, 0, len(ws) - 1),
                               minlength=len(ws)).tolist()
    else:
        win_hist = []
    exch = cfg.exchange_every if cfg.chains > 1 else 0
    out = {
        "adjacency": adj,
        "delta_window": engine.window,  # 0 = full rescore every iteration
        "adaptive_windows": list(ws),
        "mask_cache": engine.mask_on,
        "sharded": sharded,
        "exchange_every": cfg.exchange_every,
        "exchange_count": (iters_run // exch) if exch else 0,
        "score": float(best_score),
        "preprocess_s": pre["t_pre"],
        "preprocess_cache_hit": pre["cache_hit"],
        "auto_pruned": pre["auto_pruned"],
        "iteration_s": t_iter,
        "per_iteration_s": t_iter / max(iters_run, 1),
        "accept_rate": float(acc.sum()) / max(iters_run * max(cfg.chains, 1),
                                              1),
        "chain_accept_rates": chain_rates,
        "window_hist": win_hist,      # final per-chain win_idx histogram
        "iters_run": iters_run,
        "stopped_early": res.stopped,
        "S": st.S,
        "heals": list(res.heals),     # supervisor chain-healing events
        "telemetry": None,
    }
    if cfg.emit_consensus and res.trace is not None:
        # the service query layer's posterior artifacts, materialized here
        # for parity: standalone --emit-consensus answers must be bitwise
        # equal to what bn_serve returns for the same (data, config, seed)
        from .telemetry import drain
        snap = drain(res.trace)
        probs = edge_posterior(snap["edge_counts"], snap["edge_taps"])
        out["edge_posterior"] = probs
        out["edge_samples"] = int(snap["edge_taps"])
        out["consensus"] = consensus_graph(probs, cfg.consensus_threshold)
        out["map_dag"] = map_dag(st, np.asarray(best_pos))
    if collector is not None:
        collector.finalize(iters_run=iters_run, stopped_early=res.stopped,
                           best_score=float(best_score))
        out["telemetry"] = {
            "run": collector.run,
            "trace_path": collector.path,
            "score_rhat": collector.last.get("score_rhat", float("nan")),
            "edge_rhat": collector.last.get("edge_rhat", float("nan")),
            "converged": collector.last.get("converged", False),
            "reseeds": collector.last.get("reseeds", []),
        }
    return out


def learn_structure(data: np.ndarray, cfg: LearnConfig, *,
                    prior_matrix: np.ndarray | None = None) -> dict:
    """Full pipeline. Returns {adjacency, score, preprocess_s, iteration_s,
    per_iteration_s, accept_rate, chain_accept_rates, window_hist,
    exchange_count, iters_run, stopped_early, telemetry, ...}."""
    st, collector, pre = prepare_run(data, cfg, prior_matrix=prior_matrix)
    key = jax.random.key(cfg.seed)
    if cfg.sharded:
        mesh = _sharded_mesh(st, cfg)
        t0 = time.time()
        with jax.set_mesh(mesh):
            engine, sup = _start_sharded(st, cfg, mesh, collector, key)
            while sup.advance():
                pass
    else:
        engine = build_engine(st, cfg)
        t0 = time.time()
        sup = start_run(st, cfg, engine, collector, key)
        while sup.advance():
            pass
    res = sup.result()
    jax.block_until_ready(res.states.best_score)
    return finish_run(cfg, st, engine, res, pre=pre,
                      t_iter=time.time() - t0, collector=collector,
                      sharded=cfg.sharded)
