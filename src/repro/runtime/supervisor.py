"""Fault-tolerant run supervisor: the segmented BN run loop, hardened.

Every telemetry-aware driver in launch/bn_learn already cuts the walk into
jitted segments (core/mcmc.make_traced_segment_runner) with the host in
between. :class:`RunSupervisor` owns that host loop for the single-device,
adaptive AND sharded engines, and layers four things onto it:

* **verified auto-resume** — restore goes through
  checkpoint.restore_latest_verified: per-leaf digests are re-hashed, a
  corrupt newest step is quarantined and the run falls back to the newest
  step that verifies; transient I/O retries with capped backoff ride along
  from the checkpointer.
* **deterministic fault injection** — a seeded runtime/faults.FaultPlan
  fires crashes around checkpoint writes, corrupts checkpoint leaves or
  preprocess cache entries, NaN/inf-poisons a chain's cached scores and
  stalls a chain's progress, all at segment boundaries so chaos runs stay
  bitwise-comparable to clean ones.
* **telemetry-driven chain healing** — between segments the supervisor folds
  the collector's stuck/diverged flags and its own per-chain NaN/inf +
  progress guards into runtime/straggler.rebalance_chains: a sick slot is
  re-seeded as a clone of the best finite chain with a fresh PRNG key,
  consistency planes are rebuilt for the cloned positions, the chain's
  telemetry leaves (rings, edge counts, window histogram) are re-seeded from
  the donor, and one ``heal`` row per event lands in the JSONL trace.
* **graceful degradation** — a poisoned or stalled chain never aborts the
  run: the in-scan exchange ranks non-finite scores as -inf (core/mcmc), the
  posterior edge accumulator skips non-finite chains (telemetry/taps), and
  the supervisor heals the slot at the next boundary — within one
  supervision interval.

Resume determinism: the supervisor persists its tiny host state (segment
ordinal, per-chain miss counters, progress fingerprints, stalled slots, the
collector's vote state) in the checkpoint metadata, and draws healing keys
as ``fold_in(key(seed), global_iteration)`` — so a run killed at a boundary
and auto-resumed makes byte-identical decisions to one that never died,
which is exactly what the chaos determinism gate (launch/chaos.py,
``make chaos-smoke``) asserts.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import (latest_step, restore_latest_verified,
                          save_checkpoint)
from ..core.mcmc import ChainState
from ..telemetry.spans import span
from .faults import FaultPlan
from .straggler import StragglerPolicy, best_finite_chain, rebalance_chains

__all__ = ["RunSupervisor", "SupervisedResult", "pack_tree", "unpack_tree",
           "N_STATE_LEAVES"]

logger = logging.getLogger(__name__)

N_STATE_LEAVES = len(ChainState._fields)


def pack_tree(pack, states, trace):
    """Checkpoint layout with telemetry: the ChainState leaves first (EXACTLY
    the pre-telemetry tuple when trace is None), TraceState leaves appended
    after them — so pre-telemetry snapshots restore through the
    checkpointer's ``allow_missing`` backfill (the trace leaves come back
    from the fresh template), the same schema-evolution path the pre-bitmask
    9-leaf snapshots use."""
    tree = tuple(pack(states))
    if trace is not None:
        tree = tree + tuple(np.asarray(leaf) for leaf in trace)
    return tree


def unpack_tree(unpack, restored, trace):
    """Inverse of :func:`pack_tree`: split the restored tuple back into
    (ChainState, TraceState | None)."""
    restored = tuple(jnp.asarray(leaf) for leaf in restored)
    states = unpack(restored[:N_STATE_LEAVES])
    if trace is not None:
        from ..telemetry import TraceState
        trace = TraceState(*restored[N_STATE_LEAVES:])
    return states, trace


@dataclass
class SupervisedResult:
    states: object            # stacked ChainState after the run
    trace: object             # TraceState | None
    iters_run: int
    stopped: bool             # stop-on-converge fired
    resumed_from: int | None  # checkpoint step the run resumed from
    heals: list = field(default_factory=list)   # heal event dicts


def _raw(states: ChainState) -> ChainState:
    """Typed PRNG keys are not sliceable as numpy: work on key_data."""
    return states._replace(key=jax.random.key_data(states.key))


def _chain_snapshot(states: ChainState, chain: int):
    """Host copy of one chain's slot across every leaf (stall replay)."""
    return jax.tree.map(lambda leaf: np.asarray(leaf[chain]).copy(),
                        _raw(states))


def _impose_chain(states: ChainState, chain: int, snap) -> ChainState:
    new = jax.tree.map(lambda leaf, s: leaf.at[chain].set(jnp.asarray(s)),
                       _raw(states), snap)
    return new._replace(key=jax.random.wrap_key_data(new.key))


def _reseed_trace(trace, healed: np.ndarray, donor: int):
    """Clone the donor's telemetry rows into healed slots (rings, window
    histogram, edge counts) and count the re-seed — the healed chain's
    poisoned/stalled history must not linger in R̂ or the posterior
    accumulator once the chain itself is a clone of the donor."""
    h = jnp.asarray(healed)

    def cp(leaf):
        sel = h.reshape(h.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(sel, leaf[donor][None], leaf)

    return trace._replace(
        scores=cp(trace.scores), accepts=cp(trace.accepts),
        win_hist=cp(trace.win_hist), edge_counts=cp(trace.edge_counts),
        reseeds=trace.reseeds + h.astype(trace.reseeds.dtype))


class RunSupervisor:
    """Owns the segmented host loop for one run (see module docstring).

    Parameters
    ----------
    iters, seg: total iteration budget and segment length (the supervision
        interval — checkpoint_every when checkpointed).
    collector: telemetry Collector or None; checked every boundary.
    faults: FaultPlan or None (chaos injection).
    heal: act on the health guards (--supervise); with heal=False and no
        faults the loop is behaviourally identical to the pre-supervisor
        drivers.
    planes_fn: stacked (C, n) pos -> stacked consistency planes, or None —
        used both after restore (derived-cache reconcile across engine
        variants) and after healing (cloned positions need cloned planes
        REBUILT under this engine's padding).
    pack/unpack: the driver's checkpoint (de)serialisation closures.
    """

    def __init__(self, *, iters: int, seg: int, chains: int,
                 checkpoint_dir: str = "", checkpoint_every: int = 0,
                 collector=None, stop_on_converge: bool = False,
                 faults: FaultPlan | None = None, heal: bool = False,
                 heal_patience: int = 1, seed: int = 0,
                 planes_fn=None, cache_dir: str = "",
                 pack=None, unpack=None):
        self.iters = int(iters)
        self.seg = max(int(seg), 1)
        self.chains = int(chains)
        self.checkpoint_dir = checkpoint_dir
        self.checkpointed = bool(checkpoint_every and checkpoint_dir)
        self.collector = collector
        self.stop_on_converge = bool(stop_on_converge)
        self.faults = faults if faults else None
        self.heal = bool(heal)
        self.policy = StragglerPolicy(patience=max(int(heal_patience), 1))
        self.planes_fn = planes_fn
        self.cache_dir = cache_dir
        self.pack = pack
        self.unpack = unpack
        # healing keys: decorrelated from the chain keys, derived from the
        # GLOBAL iteration so resumed runs draw identical clone keys
        self._heal_key = jax.random.fold_in(jax.random.key(int(seed)), 0x5E9)
        self._missed = np.zeros(self.chains, np.int64)
        self._prev_step: np.ndarray | None = None
        self._stalled: dict[int, object] = {}
        self._seg_done = 0
        self.heals: list[dict] = []
        # incremental-drive carry (armed by begin(), advanced by advance())
        self._run_segment = None
        self._states = self._trace = None
        self._done = 0
        self._stopped = False
        self._resumed_from: int | None = None

    # ------------------------------------------------------------ metadata
    def _state_meta(self) -> dict:
        return {"supervisor": {
            "seg_done": int(self._seg_done),
            "missed": [int(x) for x in self._missed],
            "prev_step": (None if self._prev_step is None
                          else [int(x) for x in self._prev_step]),
            "stalled": sorted(int(c) for c in self._stalled),
            "collector": (self.collector.state_dict()
                          if self.collector is not None else None),
        }}

    def _load_meta(self, metadata: dict, states: ChainState) -> None:
        sup = (metadata or {}).get("supervisor") or {}
        if not sup:
            return
        self._seg_done = int(sup.get("seg_done", self._seg_done))
        if sup.get("missed") is not None:
            self._missed = np.asarray(sup["missed"], np.int64)
        if sup.get("prev_step") is not None:
            self._prev_step = np.asarray(sup["prev_step"], np.int64)
        # a stalled chain was reverted to its snapshot BEFORE the save, so
        # the restored slot IS the snapshot — re-register it verbatim
        for c in sup.get("stalled") or []:
            self._stalled[int(c)] = _chain_snapshot(states, int(c))
        if sup.get("collector") and self.collector is not None:
            self.collector.load_state(sup["collector"])

    # ------------------------------------------------------------- restore
    def _restore(self, states, trace):
        """(states, trace, done, resumed_from): verified auto-resume."""
        if not self.checkpointed or latest_step(self.checkpoint_dir) is None:
            return states, trace, 0, None
        template = pack_tree(self.pack, states, trace)
        try:
            restored, metadata, step = restore_latest_verified(
                self.checkpoint_dir, template, allow_missing=True)
        except FileNotFoundError:
            logger.warning("no checkpoint step verified in %s — starting "
                           "from scratch", self.checkpoint_dir)
            return states, trace, 0, None
        states, trace = unpack_tree(self.unpack, restored, trace)
        states = self._reconcile_planes(states)
        self._load_meta(metadata, states)
        return states, trace, step, step

    def _reconcile_planes(self, states: ChainState) -> ChainState:
        """Derived-cache interop (mirrors launch/bn_learn
        reconcile_mask_planes): the planes leaf is rebuilt from positions
        when this engine uses the bitmask cache and reset to the zero-size
        placeholder when it does not — restored OR healed positions always
        get planes built under this engine's own padding."""
        if self.planes_fn is not None:
            return states._replace(mask_planes=self.planes_fn(states.pos))
        return states._replace(
            mask_planes=jnp.zeros((states.pos.shape[0], 0), jnp.uint32))

    # -------------------------------------------------------------- faults
    def _fire_pre_segment(self, states: ChainState) -> ChainState:
        for event in self.faults.pre_segment(self._seg_done):
            if event.kind == "poison":
                states, chain = self.faults.poison(states, event)
            elif event.kind == "stall":
                chain = self.faults.pick_chain(event, self.chains)
                logger.warning("fault: stalling chain %d from segment %d",
                               chain, self._seg_done)
                self._stalled[chain] = _chain_snapshot(states, chain)
            elif event.kind == "cache":
                if self.cache_dir:
                    self.faults.corrupt_cache(self.cache_dir, event)
                else:
                    logger.warning("fault: no cache dir — %s is a no-op",
                                   event.describe())
        return states

    def _replay_stalls(self, states: ChainState) -> ChainState:
        """A stalled chain's segment progress is thrown away every boundary
        (its snapshot is re-imposed), so from the supervisor's viewpoint the
        chain never advances — the MCMC picture of a worker whose updates
        are lost — until the progress guard heals it."""
        for chain, snap in self._stalled.items():
            states = _impose_chain(states, chain, snap)
        return states

    # ------------------------------------------------------------- healing
    def _health_guard(self, states: ChainState, rec: dict | None):
        """(progressed (C,) bool, reasons (C,) str) from the NaN/inf guard,
        the progress fingerprint, and the collector's stuck/diverged flags."""
        score = np.asarray(states.score, np.float64)
        best = np.asarray(states.best_score, np.float64)
        ls_ok = np.isfinite(np.asarray(states.cur_ls)).all(axis=1)
        finite = np.isfinite(score) & np.isfinite(best) & ls_ok
        step = np.asarray(states.step, np.int64)
        progress = (np.ones(self.chains, bool) if self._prev_step is None
                    else step != self._prev_step)
        stuck = np.zeros(self.chains, bool)
        diverged = np.zeros(self.chains, bool)
        if rec is not None:
            stuck[np.asarray(rec["stuck_chains"], int)] = True
            diverged[np.asarray(rec["diverged_chains"], int)] = True
        progressed = finite & progress & ~stuck & ~diverged
        reasons = np.where(~finite, "nonfinite",
                           np.where(~progress, "stalled",
                                    np.where(stuck, "stuck",
                                             np.where(diverged, "diverged",
                                                      ""))))
        return progressed, reasons

    def _heal(self, states, trace, rec, done: int):
        progressed, reasons = self._health_guard(states, rec)
        best_before = np.asarray(states.best_score)
        key = jax.random.fold_in(self._heal_key, done)
        states, self._missed, healed = rebalance_chains(
            key, states, progressed, self._missed, self.policy,
            return_mask=True)
        if healed.any():
            donor = best_finite_chain(best_before)
            states = self._reconcile_planes(states)
            if trace is not None:
                trace = _reseed_trace(trace, healed, donor)
            for c in np.nonzero(healed)[0]:
                event = {"iter": int(done), "chain": int(c),
                         "donor": int(donor),
                         "reason": str(reasons[c]) or "lagging"}
                self.heals.append(event)
                self._stalled.pop(int(c), None)
                logger.warning("heal: chain %d cloned from %d at iter %d "
                               "(%s)", c, donor, done, event["reason"])
                if self.collector is not None:
                    self.collector.heal(**event)
        self._prev_step = np.asarray(states.step, np.int64).copy()
        return states, trace

    # ----------------------------------------------------------------- run
    def begin(self, run_segment, states, trace) -> "RunSupervisor":
        """Arm the supervisor for incremental driving: verified auto-resume,
        then park the (states, trace) carry until :meth:`advance` is called.

        ``begin``/``advance``/``result`` split :meth:`run` into steps so a
        MULTI-JOB host loop (service/scheduler.py) can interleave segments
        of several supervised runs round-robin on one device budget; one
        call to :meth:`advance` is exactly one trip through the old while
        body, so ``run()`` — begin + advance-until-finished + result —
        is behaviourally unchanged."""
        self._run_segment = run_segment
        states, trace, done, resumed_from = self._restore(states, trace)
        self._states, self._trace = states, trace
        self._done, self._resumed_from = done, resumed_from
        self._stopped = False
        return self

    @property
    def finished(self) -> bool:
        """True once the budget is exhausted or stop-on-converge fired."""
        return self._done >= self.iters or self._stopped

    @property
    def states(self):
        """Current chain stack (valid between begin() and result())."""
        return self._states

    @states.setter
    def states(self, value):
        self._states = value

    @property
    def trace(self):
        """Current TraceState | None (valid between begin() and result())."""
        return self._trace

    @trace.setter
    def trace(self, value):
        self._trace = value

    @property
    def iters_done(self) -> int:
        return self._done

    def advance(self) -> bool:
        """Run ONE supervised segment (chaos injection, segment scan, stall
        replay, collector check, healing, checkpoint) under the ``segment``
        span. Returns True while the run has more segments to go."""
        if self.finished:
            return False
        with span("segment"):
            self._segment()
        return not self.finished

    def _segment(self) -> None:
        states, trace, done = self._states, self._trace, self._done
        if self.faults:
            states = self._fire_pre_segment(states)
        length = min(self.seg, self.iters - done)
        states, trace = self._run_segment(states, trace, jnp.int32(done),
                                          length=length)
        done += length
        if self._stalled:
            states = self._replay_stalls(states)
        rec = None
        if self.collector is not None:
            from ..telemetry import drain
            rec = self.collector.check(drain(trace), done)
        if self.heal:
            states, trace = self._heal(states, trace, rec, done)
        crash_before, corrupts, crash_after = (
            self.faults.checkpoint_events(self._seg_done)
            if self.faults else (False, [], False))
        if crash_before:
            self.faults.crash(f"before checkpoint write at iter {done}")
        if self.checkpointed:
            save_checkpoint(self.checkpoint_dir, done,
                            pack_tree(self.pack, states, trace),
                            metadata=self._state_meta())
        for event in corrupts:
            self.faults.corrupt_checkpoint(self.checkpoint_dir, event)
        if crash_after:
            self.faults.crash(f"after checkpoint write at iter {done}")
        self._seg_done += 1
        self._states, self._trace, self._done = states, trace, done
        if self.stop_on_converge and rec is not None and rec["converged"]:
            self._stopped = True

    def result(self) -> SupervisedResult:
        return SupervisedResult(states=self._states, trace=self._trace,
                                iters_run=self._done, stopped=self._stopped,
                                resumed_from=self._resumed_from,
                                heals=self.heals)

    def grow(self, extra: int) -> None:
        """Widen the per-chain host bookkeeping after an elastic fleet
        expansion (service/scheduler.expand_fleet): new slots start with a
        clean miss/progress history. The jitted segment runner recompiles
        for the new chain count on its own."""
        if extra <= 0:
            return
        self.chains += int(extra)
        self._missed = np.concatenate(
            [self._missed, np.zeros(extra, np.int64)])
        if self._prev_step is not None:
            self._prev_step = np.concatenate(
                [self._prev_step, np.full(extra, -1, np.int64)])

    def run(self, run_segment, states, trace) -> SupervisedResult:
        """Drive ``run_segment(states, trace, start, length=...)`` to the
        iteration budget (or convergence), supervised."""
        self.begin(run_segment, states, trace)
        while self.advance():
            pass
        return self.result()
