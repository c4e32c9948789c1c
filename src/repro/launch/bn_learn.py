"""End-to-end Bayesian-network structure learning driver (the paper's full
pipeline, Fig. 2): preprocess → multi-chain order-MCMC → best-graph exchange.

Usage (also the library entry point used by examples/ and benchmarks/):

  python -m repro.launch.bn_learn --network alarm --iters 2000 --chains 4
  python -m repro.launch.bn_learn --network alarm --q alarm \
      --preprocess fused                         # ALARM's published arities
  python -m repro.launch.bn_learn --network synth --n 64 --s 3 \
      --preprocess fused --prune-delta 30        # fused pipeline + compression

--q is the variables' number of states: one int for all, a comma list of
one per variable, or a named table (``alarm``: ALARM's published 2-4
states per variable); the data are sampled and scored at those arities.

--preprocess fused routes score-table construction through preprocess/
(count-once-per-subset + LUT scoring, ~20x the reference loop at n = 64 on
CPU) with a disk cache (--cache-dir) so repeat runs skip the stage entirely;
--prune-delta > 0 additionally hash-compresses the table to per-node score
lists, and the MCMC hot path switches to the O(n*K) pruned scorer. Above
S >= AUTO_PRUNE_S parent sets per node the fused path makes that pruned
engine the DEFAULT (delta = AUTO_PRUNE_DELTA, built streamingly with no
dense (n, S) intermediate — preprocess/streaming.py); --no-auto-prune
reverts to the dense build. That switch is what takes the driver to the
n = 100, s = 4 scale.

The per-iteration engine (ISSUE 3) defaults to the bitmask-cached delta path
on dense tables (cached consistency planes in ChainState, patched with word
ops per proposal — --no-mask-cache reverts to the gather+compare delta);
--adapt-window tunes the move window from the accept rate over a static
power-of-two set and freezes it after --burn-in; --exchange-every N runs the
cross-chain best→worst re-seed INSIDE the scan instead of only at the end.

Chains are embarrassingly parallel (DP over the data/pod mesh axes at scale,
vmap locally); the best-graph exchange at the end is the same max+argmax
reduction the scoring kernel uses, one level up. Periodic checkpointing makes
the walk restartable — a killed worker re-joins from the last snapshot (new
ChainState leaves are backfilled when restoring a pre-bitmask snapshot, and
the consistency planes are rebuilt from the restored positions; telemetry
trace leaves append after the ChainState leaves and backfill the same way).

--telemetry (ISSUE 7) threads the repro.telemetry subsystem through every
run loop: in-scan accelerator-resident taps (score/accept rings, window
histogram, thinned posterior edge counts) carried beside ChainState through
the shared segmented runner, and a host-side collector between segments
computing split-R̂ over the chain score traces and max-R̂ over cross-chain
edge marginals, appended as schema-versioned JSONL under --trace-dir.
--stop-on-converge turns the R̂ pair into an early-stopping rule (both below
--rhat-threshold for --patience consecutive checks), so long runs stop on
convergence rather than on the iteration cap.

--supervise (ISSUE 8) hands the segmented host loop — single-device,
adaptive AND sharded — to the fault-tolerant run supervisor
(runtime/supervisor.py): restores go through digest-verified checkpoints
(corrupt steps are quarantined, the run falls back to the newest step that
verifies), and between segments the supervisor folds the collector's
stuck/diverged flags plus its own NaN/inf + progress guards into
telemetry-driven chain healing (straggler cloning from the best finite
chain, planes/caches/trace leaves re-seeded together, one ``heal`` JSONL
row per event). --fault-plan injects deterministic chaos (crashes around
checkpoint writes, checkpoint/cache corruption, chain poisoning/stalls —
grammar in runtime/faults.py) so the recovery machinery is testable:
``make chaos-smoke`` asserts a crash-injected run resumes and finishes
bitwise-identical to an uninterrupted one.
"""
from __future__ import annotations

import argparse
import functools
import time
from dataclasses import asdict, dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (adjacency_from_ranks, build_score_table, mcmc_run,
                    random_cpts, roc_point)
from ..core.metrics import consensus_graph, edge_posterior, map_dag
from ..core.combinatorics import n_parent_sets
from ..core.mcmc import (BitmaskDelta, ChainState, exchange_best, init_chain,
                         make_traced_segment_runner, mcmc_run_adaptive,
                         mcmc_run_chains, mcmc_run_chains_adaptive, mcmc_step,
                         mcmc_step_adaptive)
from ..core.order_scoring import (build_membership_planes,
                                  build_violation_planes, delta_window,
                                  score_order_blocked, score_order_delta,
                                  score_order_delta_bitmask,
                                  score_order_pruned,
                                  score_order_pruned_delta,
                                  score_order_sum_cached,
                                  score_order_sum_delta)
from ..data.bn_sampler import ancestral_sample, inject_noise
from ..data.networks import (alarm_adjacency, parse_arity, stn_adjacency,
                             synthetic_adjacency)
from ..preprocess import SparseScoreTable, build_score_table_fused
from ..runtime.faults import parse_fault_plan
from ..runtime.supervisor import (N_STATE_LEAVES, RunSupervisor, pack_tree,
                                  unpack_tree)

__all__ = ["LearnConfig", "learn_structure", "make_score_fn",
           "make_delta_fn", "make_engine_closures", "prepare_run",
           "adaptive_window_set", "reconcile_mask_planes",
           "main", "AUTO_PRUNE_S", "AUTO_PRUNE_DELTA"]

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
    # --- convergence telemetry (repro.telemetry; ISSUE 7) ----------------
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
    # --- fault-tolerant run supervisor (runtime/supervisor; ISSUE 8) -----
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


def _padded(st, block: int):
    """(table, pst, block) with S padded to a multiple of block — shared by
    the full and delta closures so both see identical blocks. block is
    rounded up to a multiple of 32 so the packed consistency-mask words of
    the bitmask cache line up with the same block structure."""
    from ..core.sharded_scoring import pad_table
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


def make_score_fn(st, cfg: LearnConfig):
    """(pos) -> (score, best_idx, best_ls) closure over either table
    representation: dense ScoreTable (blocked/kernel scorers) or
    preprocess.SparseScoreTable (packed pruned scorer, O(n*K))."""
    if isinstance(st, SparseScoreTable):
        if cfg.scorer == "sum":
            raise ValueError(
                "the sum (logsumexp) baseline scorer needs the dense table: "
                "run without --prune-delta (pruned entries would silently "
                "drop out of the logsumexp)")
        return functools.partial(score_order_pruned, st.kept_ls,
                                 st.kept_parents, st.kept_idx)
    if cfg.scorer == "sum":
        # the Linderman et al. [5] baseline the paper improves on (§III-B);
        # the _cached variant's third output is the per-node logsumexp, so
        # the sampler's cur_ls cache feeds score_order_sum_delta
        return functools.partial(score_order_sum_cached, st.table, st.pst)
    if cfg.use_kernel:
        from ..kernels.order_score import order_score
        return functools.partial(order_score, st.table, st.pst)
    table, pst, block = _padded(st, cfg.block)
    return functools.partial(score_order_blocked, table, pst, block=block)


def _delta_context(st, cfg: LearnConfig):
    """(kind, tables, cm, planes_fn) — the WINDOW-INDEPENDENT state shared
    by every per-window delta closure (built once, even when the adaptive
    path needs one closure per candidate window): padded tables, membership
    planes, and the chain-cache builder. planes_fn is non-None exactly when
    the closures will be BitmaskDeltas."""
    if isinstance(st, SparseScoreTable):
        return "sparse", (st.kept_ls, st.kept_parents, st.kept_idx), None, None
    if cfg.scorer == "sum":
        return "sum", (st.table, st.pst), None, None
    if cfg.use_kernel:
        from ..kernels.order_score import BLOCK_S, pad_for_kernel

        # pre-pad once so the per-iteration call's pad is a no-op (the
        # blocked path hoists its padding the same way via _padded)
        ktable, kpst = pad_for_kernel(st.table, st.pst, BLOCK_S)
        if cfg.mask_cache:
            return "kernel", (ktable, kpst), \
                build_membership_planes(kpst, ktable.shape[0]), \
                functools.partial(build_violation_planes, kpst)
        return "kernel", (ktable, kpst), None, None
    table, pst, block = _padded(st, cfg.block)
    if cfg.mask_cache:
        return "blocked", (table, pst, block), \
            build_membership_planes(pst, table.shape[0]), \
            functools.partial(build_violation_planes, pst)
    return "blocked", (table, pst, block), None, None


def _delta_for_window(ctx, w: int):
    """Delta closure for one STATIC window w ≥ 2 over a shared
    :func:`_delta_context` — the per-window factory behind make_delta_fn and
    the adaptive window set."""
    kind, tables, cm, planes_fn = ctx
    if kind == "sparse":
        def sfn(pos, lo, prev_ls, prev_idx):
            return score_order_pruned_delta(*tables, pos, prev_ls, prev_idx,
                                            lo, window=w)
        return sfn
    if kind == "sum":
        table, pst = tables

        def lfn(pos, lo, prev_ls, prev_idx):
            return score_order_sum_delta(table, pst, pos, prev_ls, prev_idx,
                                         lo, window=w)
        return lfn
    if kind == "kernel":
        from ..kernels.order_score import (order_score_delta,
                                           order_score_delta_bitmask)

        ktable, kpst = tables
        if cm is not None:
            def kbfn(pos, lo, prev_ls, prev_idx, pos_old, planes):
                return order_score_delta_bitmask(ktable, cm, pos, prev_ls,
                                                 prev_idx, lo, pos_old,
                                                 planes, window=w)
            return BitmaskDelta(kbfn)

        def kfn(pos, lo, prev_ls, prev_idx):
            return order_score_delta(ktable, kpst, pos, prev_ls,
                                     prev_idx, lo, window=w)
        return kfn
    table, pst, block = tables
    if cm is not None:
        def bfn(pos, lo, prev_ls, prev_idx, pos_old, planes):
            return score_order_delta_bitmask(table, cm, pos, prev_ls,
                                             prev_idx, lo, pos_old, planes,
                                             window=w, block=block)
        return BitmaskDelta(bfn)

    def fn(pos, lo, prev_ls, prev_idx):
        return score_order_delta(table, pst, pos, prev_ls, prev_idx, lo,
                                 window=w, block=block)
    return fn


def make_delta_fn(st, cfg: LearnConfig):
    """(window, delta_fn, planes_fn) for the incremental per-iteration path,
    or (0, None, None) when the crossover heuristic rejects the window.
    delta_fn is a BitmaskDelta (and planes_fn builds the chain's cached
    consistency planes) on the dense max paths when cfg.mask_cache."""
    n = st.n if isinstance(st, SparseScoreTable) else st.table.shape[0]
    w = delta_window(n, cfg.window)
    if not w:
        return 0, None, None
    ctx = _delta_context(st, cfg)
    return w, _delta_for_window(ctx, w), ctx[3]


def reconcile_mask_planes(states: ChainState, planes_fn) -> ChainState:
    """Checkpoint interop across engine variants (ISSUE 4 bugfix): the
    ``mask_planes`` leaf is a DERIVED cache, and snapshots written by
    different engines disagree about its shape — sharded runs snapshot the
    zero-size placeholder, single-device bitmask runs may carry full
    (n, P, S/32) planes built under another padding, and pre-bitmask layouts
    have no leaf at all (backfilled by the checkpointer's ``allow_missing``,
    which covers MISSING leaves only, never wrong-shaped ones). Instead of
    letting a wrong-shaped restored leaf shape-mismatch the first jitted
    step, ALWAYS rebuild the cache from the restored positions when this
    engine uses it (``planes_fn``: stacked (C, n) pos -> (C, n, P, W)
    planes), and reset it to the placeholder when it doesn't."""
    if planes_fn is not None:
        return states._replace(mask_planes=planes_fn(states.pos))
    return states._replace(
        mask_planes=jnp.zeros((states.pos.shape[0], 0), jnp.uint32))


def _auto_check_every(cfg: LearnConfig) -> int:
    """Collector check period for non-checkpointed telemetry runs: frequent
    enough that --stop-on-converge reacts soon after mixing, coarse enough
    that each segment accumulates a meaningful number of taps (≥ 16 at the
    default --trace-every 8) and segment re-entry cost stays negligible."""
    return cfg.check_every or max(64, 16 * cfg.trace_every)


# checkpoint tree layout now lives with the run supervisor
# (runtime/supervisor.py); aliases kept for callers of the old names
_N_STATE_LEAVES = N_STATE_LEAVES
_pack_tree = pack_tree
_unpack_tree = unpack_tree


def _make_pack_unpack(n_chains: int):
    """Checkpoint (de)serialisation closures shared by every segmented
    driver: typed PRNG keys are not numpy-serializable, so the key leaf is
    snapshot as key data; the consistency planes are a pos-derived cache —
    snapshot a zero-size stand-in and rebuild after restore (smaller
    checkpoints, and pre-tentpole snapshots restore through the same
    path)."""
    dummy_planes = jnp.zeros((n_chains, 0), jnp.uint32)
    pack = lambda s: jax.tree.map(
        np.asarray, s._replace(key=jax.random.key_data(s.key),
                               mask_planes=dummy_planes))
    unpack = lambda t: ChainState(*t)._replace(
        key=jax.random.wrap_key_data(jnp.asarray(t[0])))
    return pack, unpack


def _make_supervisor(cfg: LearnConfig, seg: int, collector,
                     stacked_planes_fn) -> RunSupervisor:
    """One RunSupervisor per run, shared config plumbing for the
    single-device and sharded drivers."""
    pack, unpack = _make_pack_unpack(cfg.chains)
    faults = (parse_fault_plan(cfg.fault_plan, seed=cfg.seed)
              if cfg.fault_plan else None)
    return RunSupervisor(
        iters=cfg.iters, seg=seg, chains=cfg.chains,
        checkpoint_dir=cfg.checkpoint_dir,
        checkpoint_every=cfg.checkpoint_every,
        collector=collector, stop_on_converge=cfg.stop_on_converge,
        faults=faults, heal=cfg.supervise, heal_patience=cfg.heal_patience,
        seed=cfg.seed, planes_fn=stacked_planes_fn, cache_dir=cfg.cache_dir,
        pack=pack, unpack=unpack)


def _run_sharded(st, cfg: LearnConfig, key, n: int, collector=None):
    """The production-mesh MCMC path (--sharded): every iteration is ONE
    shard_map program (core/sharded_scoring.sharded_chain_step) — chains DP
    over 'data', score table + cached consistency planes TP over 'model';
    per iteration only the (window,) pmax/pmin pair crosses ICI.

    With ``collector`` (telemetry on) the walk is cut into check_every-sized
    segments carrying a TraceState beside the chain stack; the taps read
    only per-chain quantities that the engine's own pmax/pmin reduction
    already replicated, so telemetry adds ZERO collective traffic over the
    model axis — the collector drains between segments and may stop the run
    early. The host loop (verified restore, chaos injection, chain healing)
    is the shared RunSupervisor — the sharded engine gets the same fault
    tolerance as the single-device ones.
    Returns (states, delta_window, mask_on, iters_run, stopped, heals,
    trace)."""
    from ..core.sharded_scoring import (_shard_block, make_sharded_planes_fn,
                                        pad_table, score_order_sharded,
                                        sharded_chain_step)
    from .mesh import make_local_mesh

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
    mesh = make_local_mesh(dp, tp)
    block = _shard_block(st.table.shape[1], tp, cfg.block)
    table, pst = pad_table(st.table, st.pst, tp * block)
    w = delta_window(n, cfg.window)
    mask_on = bool(w) and cfg.mask_cache
    cm = build_membership_planes(pst, n) if mask_on else None
    splanes_fn = (make_sharded_planes_fn(pst, mesh, stacked=True)
                  if mask_on else None)

    def score_fn(pos):
        return score_order_sharded(table, pst, pos, mesh, block=block)

    exch = cfg.exchange_every if cfg.chains > 1 else 0
    telem = collector is not None
    trace = tap = exchange = None
    if telem:
        from ..telemetry import exchange_step_traced, init_trace, make_tap
        trace = init_trace(cfg.chains, n)
        tap = make_tap(n, cfg.s, cfg.trace_every)
        exchange = exchange_step_traced

    def step(stt):
        return sharded_chain_step(stt, table, pst, mesh, cm, block=block,
                                  window=cfg.window,
                                  use_kernel=cfg.use_kernel)

    run_segment = make_traced_segment_runner(step, tap=tap, exchange=exchange,
                                             exchange_every=exch,
                                             stacked_step=True)

    checkpointed = bool(cfg.checkpoint_every and cfg.checkpoint_dir)
    seg = cfg.checkpoint_every if checkpointed else \
        (_auto_check_every(cfg) if telem or cfg.supervise or cfg.fault_plan
         else cfg.iters)
    with jax.set_mesh(mesh):
        keys = jax.random.split(key, cfg.chains)
        states = jax.vmap(lambda k: init_chain(k, n, score_fn))(keys)
        if mask_on:
            # per-shard plane build: each device packs its own S-shard words
            states = states._replace(mask_planes=splanes_fn(states.pos))
        sup = _make_supervisor(cfg, seg, collector,
                               splanes_fn if mask_on else None)
        res = sup.run(run_segment, states, trace)
        states = res.states
        jax.block_until_ready(states.best_score)
    return (states, w, mask_on, res.iters_run, res.stopped, res.heals,
            res.trace)


def _build_segmented(st, cfg: LearnConfig, key, n: int, score_fn, window,
                     delta_fn, planes_fn, adaptive_ws, delta_fns, burn_in,
                     collector):
    """Construct (but do not drive) the segmented single-device engine:
    vmapped chain init, the jitted traced segment runner, and the armed
    RunSupervisor. Shared by :func:`_run_segmented` (one-shot CLI) and the
    posterior service's job manager (service/jobs.py) — both drive the SAME
    supervisor object, so a service job interleaved with other jobs walks
    through bitwise-identical segment boundaries to a standalone run.

    Returns the RunSupervisor, armed via ``begin`` (drive with ``advance``
    until ``finished``, then read ``result()``)."""
    telem = collector is not None
    checkpointed = bool(cfg.checkpoint_every and cfg.checkpoint_dir)
    C = cfg.chains
    keys = jax.random.split(key, C)
    wi0 = len(adaptive_ws) // 2 if adaptive_ws else 0
    states = jax.vmap(lambda k: init_chain(k, n, score_fn,
                                           planes_fn=planes_fn,
                                           win_idx=wi0))(keys)
    if adaptive_ws:
        # valid across segments: win_idx/adapt_err/step are ChainState
        # leaves, so the dual-averaging iterate and the burn-in freeze use
        # GLOBAL step counts no matter where segment boundaries fall
        step = lambda s: mcmc_step_adaptive(s, score_fn, delta_fns,
                                            adaptive_ws, burn_in=burn_in)
    else:
        step = lambda s: mcmc_step(s, score_fn, delta_fn, window)
    exch = cfg.exchange_every if C > 1 else 0
    trace = tap = exchange = None
    if telem:
        from ..telemetry import exchange_step_traced, init_trace, make_tap
        trace = init_trace(C, n, n_windows=max(len(adaptive_ws), 1))
        tap = make_tap(n, cfg.s, cfg.trace_every)
        exchange = exchange_step_traced
    run_segment = make_traced_segment_runner(step, tap=tap, exchange=exchange,
                                             exchange_every=exch)
    seg = cfg.checkpoint_every if checkpointed else _auto_check_every(cfg)

    sup = _make_supervisor(
        cfg, seg, collector,
        (jax.vmap(planes_fn) if planes_fn is not None else None))
    return sup.begin(run_segment, states, trace)


def _run_segmented(st, cfg: LearnConfig, key, n: int, score_fn, window,
                   delta_fn, planes_fn, adaptive_ws, delta_fns, burn_in,
                   collector):
    """Unified segmented driver for the single-device engines: used whenever
    the run is checkpointed, telemetry is on, or the run is supervised (the
    reasons the host must see the walk at sub-run granularity). One jitted
    segment runner carries (ChainState, TraceState) through the scan; the
    host loop between segments — verified restore, checkpoint snapshots,
    collector checks / early stop, chaos injection and chain healing — is
    the shared RunSupervisor (runtime/supervisor.py).

    Returns (stacked states, iters_run, stopped_early, heals, trace)."""
    sup = _build_segmented(st, cfg, key, n, score_fn, window, delta_fn,
                           planes_fn, adaptive_ws, delta_fns, burn_in,
                           collector)
    while sup.advance():
        pass
    res = sup.result()
    return res.states, res.iters_run, res.stopped, res.heals, res.trace


def _finish(cfg: LearnConfig, st, states, best_score, best_idx, *, window,
            adaptive_ws, mask_on, sharded, t_pre, cache_hit, auto_pruned,
            t_iter, iters_run, stopped, collector, heals=(), trace=None,
            best_pos=None) -> dict:
    """Common run epilogue: adjacency decode, per-chain statistics, the
    result dict, and — with telemetry on — the final trace row. ``states``
    may be a single un-stacked ChainState (chains == 1 fast paths) or the
    stacked multi-chain state; per-chain stats use atleast_1d either way."""
    adj = adjacency_from_ranks(np.asarray(best_idx), s=cfg.s)
    acc = np.atleast_1d(np.asarray(states.accepts))
    chain_rates = [float(a) / max(iters_run, 1) for a in acc]
    if adaptive_ws:
        wi = np.atleast_1d(np.asarray(states.win_idx))
        win_hist = np.bincount(np.clip(wi, 0, len(adaptive_ws) - 1),
                               minlength=len(adaptive_ws)).tolist()
    else:
        win_hist = []
    exch = cfg.exchange_every if cfg.chains > 1 else 0
    out = {
        "adjacency": adj,
        "delta_window": window,       # 0 = full rescore every iteration
        "adaptive_windows": list(adaptive_ws),
        "mask_cache": mask_on,
        "sharded": sharded,
        "exchange_every": cfg.exchange_every,
        "exchange_count": (iters_run // exch) if exch else 0,
        "score": float(best_score),
        "preprocess_s": t_pre,
        "preprocess_cache_hit": cache_hit,
        "auto_pruned": auto_pruned,
        "iteration_s": t_iter,
        "per_iteration_s": t_iter / max(iters_run, 1),
        "accept_rate": float(acc.sum()) / max(iters_run * max(cfg.chains, 1),
                                              1),
        "chain_accept_rates": chain_rates,
        "window_hist": win_hist,      # final per-chain win_idx histogram
        "iters_run": iters_run,
        "stopped_early": stopped,
        "S": st.S,
        "heals": list(heals),         # supervisor chain-healing events
        "telemetry": None,
    }
    if cfg.emit_consensus and trace is not None:
        # the service query layer's posterior artifacts, materialized here
        # for parity: standalone --emit-consensus answers must be bitwise
        # equal to what bn_serve returns for the same (data, config, seed)
        from ..telemetry import drain
        snap = drain(trace)
        probs = edge_posterior(snap["edge_counts"], snap["edge_taps"])
        out["edge_posterior"] = probs
        out["edge_samples"] = int(snap["edge_taps"])
        out["consensus"] = consensus_graph(probs, cfg.consensus_threshold)
        out["map_dag"] = (map_dag(st, np.asarray(best_pos))
                          if best_pos is not None else adj)
    if collector is not None:
        collector.finalize(iters_run=iters_run, stopped_early=stopped,
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


def make_engine_closures(st, cfg: LearnConfig, n: int):
    """Every closure the single-device engines need, shared by
    :func:`learn_structure` and the service job manager: (score_fn, window,
    delta_fn, planes_fn, adaptive_ws, delta_fns, burn_in, mask_on)."""
    score_fn = make_score_fn(st, cfg)
    checkpointed = bool(cfg.checkpoint_every and cfg.checkpoint_dir)
    adaptive_ws: tuple[int, ...] = ()
    delta_fns: tuple = ()
    burn_in = 0
    if cfg.adapt_window:
        if checkpointed:
            raise ValueError("--adapt-window does not compose with "
                             "checkpointing yet: the dual-averaging state "
                             "would restart each segment, breaking the "
                             "burn-in freeze contract")
        adaptive_ws = adaptive_window_set(n)
        ctx = _delta_context(st, cfg)        # shared: pads/planes built ONCE
        delta_fns = tuple(_delta_for_window(ctx, w) for w in adaptive_ws)
        window, delta_fn, planes_fn = 0, None, ctx[3]
        burn_in = cfg.burn_in or cfg.iters // 5
    else:
        window, delta_fn, planes_fn = make_delta_fn(st, cfg)
    mask_on = isinstance(delta_fn, BitmaskDelta) or \
        (cfg.adapt_window and planes_fn is not None)
    return (score_fn, window, delta_fn, planes_fn, adaptive_ws, delta_fns,
            burn_in, mask_on)


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
        from ..telemetry import Collector
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


def learn_structure(data: np.ndarray, cfg: LearnConfig, *,
                    prior_matrix: np.ndarray | None = None) -> dict:
    """Full pipeline. Returns {adjacency, score, preprocess_s, iteration_s,
    per_iteration_s, accept_rate, chain_accept_rates, window_hist,
    exchange_count, iters_run, stopped_early, telemetry, ...}."""
    n = data.shape[1]
    telem = cfg.telemetry or cfg.stop_on_converge or cfg.emit_consensus
    st, collector, pre = prepare_run(data, cfg, prior_matrix=prior_matrix)
    t_pre, cache_hit = pre["t_pre"], pre["cache_hit"]
    auto_pruned = pre["auto_pruned"]

    key = jax.random.key(cfg.seed)

    if cfg.sharded:
        t0 = time.time()
        (states, window, mask_on, iters_run, stopped, heals,
         trace) = _run_sharded(st, cfg, key, n, collector)
        t_iter = time.time() - t0
        best_score, best_idx, best_pos = exchange_best(states)
        return _finish(cfg, st, states, best_score, best_idx, window=window,
                       adaptive_ws=(), mask_on=mask_on, sharded=True,
                       t_pre=t_pre, cache_hit=cache_hit,
                       auto_pruned=auto_pruned, t_iter=t_iter,
                       iters_run=iters_run, stopped=stopped,
                       collector=collector, heals=heals, trace=trace,
                       best_pos=best_pos)

    (score_fn, window, delta_fn, planes_fn, adaptive_ws, delta_fns,
     burn_in, mask_on) = make_engine_closures(st, cfg, n)

    checkpointed = bool(cfg.checkpoint_every and cfg.checkpoint_dir)
    supervised = cfg.supervise or bool(cfg.fault_plan)
    iters_run, stopped = cfg.iters, False
    heals: list = []
    trace = None
    t0 = time.time()
    if not checkpointed and not telem and not supervised:
        # fast paths: the whole walk is ONE jitted program, no segmentation
        if cfg.adapt_window:
            if cfg.chains == 1:
                states, _ = mcmc_run_adaptive(
                    key, n, score_fn, cfg.iters, windows=adaptive_ws,
                    delta_fns=delta_fns, planes_fn=planes_fn,
                    burn_in=burn_in)
            else:
                states = mcmc_run_chains_adaptive(
                    key, cfg.chains, n, score_fn, cfg.iters,
                    windows=adaptive_ws, delta_fns=delta_fns,
                    planes_fn=planes_fn, burn_in=burn_in,
                    exchange_every=cfg.exchange_every)
        elif cfg.chains == 1:
            states, _ = mcmc_run(key, n, score_fn, cfg.iters,
                                 delta_fn=delta_fn, window=window,
                                 planes_fn=planes_fn)
        else:
            states = mcmc_run_chains(key, cfg.chains, n, score_fn, cfg.iters,
                                     delta_fn=delta_fn, window=window,
                                     exchange_every=cfg.exchange_every,
                                     planes_fn=planes_fn)
    else:
        # segmented path: checkpointing, telemetry and/or supervision need
        # the host between scan segments (snapshots, collector checks,
        # early stop, chaos injection, chain healing)
        states, iters_run, stopped, heals, trace = _run_segmented(
            st, cfg, key, n, score_fn, window, delta_fn,
            planes_fn, adaptive_ws, delta_fns, burn_in, collector)
    jax.block_until_ready(states.best_score)
    if np.asarray(states.best_score).ndim:
        best_score, best_idx, best_pos = exchange_best(states)
    else:
        best_score, best_idx = states.best_score, states.best_idx
        best_pos = states.best_pos
    t_iter = time.time() - t0

    # rank-decoded adjacency (Algorithm 2 in reverse): identical to the old
    # PST row lookup, but works from the O(n*K) pruned representation too
    return _finish(cfg, st, states, best_score, best_idx, window=window,
                   adaptive_ws=adaptive_ws, mask_on=mask_on, sharded=False,
                   t_pre=t_pre, cache_hit=cache_hit, auto_pruned=auto_pruned,
                   t_iter=t_iter, iters_run=iters_run, stopped=stopped,
                   collector=collector, heals=heals, trace=trace,
                   best_pos=best_pos)


def _network_data(name: str, m: int, q, seed: int, n_synth: int = 64):
    rng = np.random.default_rng(seed)
    if name == "synth":
        # synthetic scale-benchmark network (n defaults to 64 — past the
        # paper's headline n > 60 claim)
        adj = synthetic_adjacency(rng, n_synth)
    else:
        adj = {"alarm": alarm_adjacency, "stn": stn_adjacency}[name]()
    cpts = random_cpts(rng, adj, q)
    return adj, ancestral_sample(rng, adj, cpts, m, q)


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

    truth, data = _network_data(args.network, args.samples, args.q, args.seed,
                                n_synth=args.n)
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
        data = inject_noise(np.random.default_rng(args.seed + 1), data,
                            args.noise, args.q)
    cfg = LearnConfig(q=args.q, s=args.s, iters=args.iters,
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
        tags = ["fused"]
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
