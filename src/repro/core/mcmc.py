"""Order-space Metropolis–Hastings MCMC (paper §III, Algorithm 1).

Random walk over topological orders, accepted with probability
min(1, P(≺_new)/P(≺)) — in log space, ``log u < score(≺_new) − score(≺)``.
The best graph (per-node argmax parent sets) is produced by the scorer itself
on every iteration, so the global best graph is tracked for free — no
postprocessing (paper §III-B).

Two proposal regimes:

* ``window=0`` (legacy): the paper's unbounded random transposition
  (:func:`_propose_swap`), full rescore every iteration.
* ``window=w ≥ 2``: a mixture of three SYMMETRIC bounded-window moves
  (:func:`propose_move`), drawn categorically per iteration —

    - bounded swap: positions (p, p+d), d ~ U[1, w-1];
    - single-node insertion: node at position a re-inserted at b, |a-b| < w
      (out-of-range targets degrade to a no-op, preserving symmetry);
    - window reversal: positions [p, p+len-1] reversed, len ~ U[2, w]
      (an involution, trivially symmetric).

  Every move permutes positions only inside a window of ≤ w positions
  starting at the returned ``lo``, which is what makes the incremental
  O(w·S) rescore (core/order_scoring.score_order_delta) exact. Richer move
  sets also mix better than pure transpositions (Kuipers et al. 1803.07859;
  Agrawal et al. 1803.05554). All moves are symmetric, so the acceptance
  test stays the pure score ratio.

Everything is a `lax.scan` over iterations; chains are vmapped (and sharded
over the `data`/`pod` mesh axes by the learner's sharded engine,
repro/learner.py).

Cached consistency bitmasks (ChainState.mask_planes)
----------------------------------------------------

The bitmask-cached delta path (core/order_scoring §Cached consistency
bitmasks) carries its per-node packed violation-count planes in
``ChainState.mask_planes``: shape (n, P, S/32) uint32, where P =
ceil(log2(s+1)) bit planes count, per (node, parent-set), the parents that
do NOT precede the node — bit b of word j refers to PST rank 32j+b
(LSB-first), and a set is consistent iff its count is zero across all
planes. The planes are built once at :func:`init_chain` (``planes_fn``); per
proposal the delta hands back only the ≤ window patched rows, and on accept
the sampler scatters those rows into the carried stack in place — the other
n − w rows are unchanged by construction — so the invariant "mask_planes
describes the CURRENT order" holds at every iteration without a second
stack. Paths that don't use the cache carry a zero-size placeholder and
never touch it.

Adaptive move windows (freeze after burn-in)
--------------------------------------------

:func:`mcmc_step_adaptive` tunes the move window from the running accept
rate: a SMALL STATIC set of candidate windows is pre-traced (one
`lax.switch` branch per window, each with its own delta closure, so the
delta ≡ full bitwise guarantee holds per window), and a dual-averaging
iterate in index space (Nesterov 2009, the same scheme NUTS uses for step
size) nudges the selected index toward ``target_accept``: too-high accept
rate ⇒ wider window (bigger moves), too-low ⇒ narrower. The selection is
FROZEN once ``step ≥ burn_in``: a kernel whose parameters keep adapting
forever is not a valid Markov chain (diminishing-adaptation conditions are
easy to violate), whereas adapt-then-freeze makes every post-burn-in sample
come from one fixed Metropolis kernel — the standard warm-up contract.

Convergence telemetry (segmented runs)
--------------------------------------

:func:`make_traced_segment_runner` is the multi-chain run loop (the
single-chain :func:`mcmc_run` loops stay as the walk's reference): the
scan over vmapped chains, cut into host-visible segments, optionally
carrying a telemetry ``TraceState`` (repro.telemetry.taps)
beside the chain stack and calling an in-scan tap each iteration. The host
drains the trace between segments to compute split-R̂ / edge-marginal R̂
and may stop the run early (bn_learn ``--stop-on-converge``) — runs then
terminate on CONVERGENCE, with the iteration count as the cap, instead of
the other way around. Global-iteration arithmetic keeps tap and exchange
cadences identical across segment and checkpoint-restart boundaries.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .order_scoring import inverse_permutation

__all__ = ["ChainState", "BitmaskDelta", "init_chain", "mcmc_run",
           "mcmc_run_adaptive", "mcmc_run_chains",
           "mcmc_run_chains_adaptive", "mcmc_step", "mcmc_step_adaptive",
           "propose_move", "exchange_best", "exchange_step",
           "make_traced_segment_runner", "DEFAULT_TARGET_ACCEPT"]

ScoreFn = Callable[[jnp.ndarray], tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
# pos (n,) -> (score, best_idx (n,), best_ls (n,))
DeltaFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray],
                   tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
# (new_pos (n,), lo, prev_ls (n,), prev_idx (n,)) -> same triple
#
# The sampler is representation-agnostic: both callables close over EITHER a
# dense core.scores.ScoreTable (score_order_blocked / the Pallas kernel /
# the sharded scorer) or a preprocess.SparseScoreTable (score_order_pruned,
# O(n*K)); best_idx is a global PST rank in every case, so best-graph
# tracking and adjacency recovery are identical. repro/learner.build_engine
# does the dispatch.

DEFAULT_TARGET_ACCEPT = 0.234   # classic random-walk Metropolis optimum


class BitmaskDelta(NamedTuple):
    """Marker wrapper for the EXTENDED delta contract — the bitmask-cached
    path needs the previous order and the cached planes, and hands back the
    window's patched plane rows for the sampler to write back on accept:

        fn(new_pos, lo, prev_ls, prev_idx, old_pos, planes)
            -> (score, best_idx, best_ls, win, planes_win)

    win: (w,) int32 node ids; planes_win: (w, P, W) their planes under
    new_pos. Every other row of ``planes`` is unchanged by the move.

    Wrapping (instead of widening DeltaFn) keeps every existing plain delta
    closure — pruned, sharded, kernel — working unchanged."""
    fn: Callable


class ChainState(NamedTuple):
    key: jax.Array
    pos: jax.Array          # (n,) int32 — pos[v] = position of node v in ≺
    score: jax.Array        # f32 — score of current order
    cur_idx: jax.Array      # (n,) int32 — best parent-set idx under current order
    best_score: jax.Array   # f32 — best graph score seen so far
    best_idx: jax.Array     # (n,) int32 — its parent sets
    best_pos: jax.Array     # (n,) int32 — its order
    accepts: jax.Array      # int32
    # appended LAST so positionally-named checkpoint leaves of the previous
    # 8-field layout stay aligned on restore
    cur_ls: jax.Array       # (n,) f32 — per-node best local scores (delta cache)
    # --- appended by the bitmask/adaptive engine (ISSUE 3); restore of a
    # pre-tentpole checkpoint backfills these (checkpointer allow_missing)
    mask_planes: jax.Array  # (n, P, S/32) uint32 violation planes, or (0,)
    win_idx: jax.Array      # int32 — index into the static adaptive window set
    adapt_err: jax.Array    # f32 — dual-averaging Σ(accept − target)
    step: jax.Array         # int32 — iteration counter (burn-in freeze)


def _no_planes() -> jax.Array:
    """Zero-size placeholder for paths without the bitmask cache."""
    return jnp.zeros((0,), jnp.uint32)


def init_chain(key: jax.Array, n: int, score_fn: ScoreFn,
               planes_fn: Callable[[jnp.ndarray], jax.Array] | None = None,
               win_idx: int = 0) -> ChainState:
    key, sub = jax.random.split(key)
    pos = jax.random.permutation(sub, n).astype(jnp.int32)
    score, idx, ls = score_fn(pos)
    planes = planes_fn(pos) if planes_fn is not None else _no_planes()
    return ChainState(key, pos, score, idx, score, idx, pos, jnp.int32(0), ls,
                      planes, jnp.int32(win_idx), jnp.float32(0.0),
                      jnp.int32(0))


def _propose_swap(key: jax.Array, pos: jax.Array) -> jax.Array:
    """Swap the positions of two distinct random nodes (paper §III-C)."""
    n = pos.shape[0]
    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (), 0, n)
    b = jax.random.randint(kb, (), 0, n - 1)
    b = b + (b >= a)  # distinct
    pa, pb = pos[a], pos[b]
    return pos.at[a].set(pb).at[b].set(pa)


def _propose_move_impl(key: jax.Array, pos: jax.Array, *, window: int):
    """Bounded-window move mixture. Returns (new_pos, lo) where every changed
    position lies in [lo, lo+window-1]. Requires window ≥ 2 (and n ≥ 2);
    window > n is clamped to n (callers that should refuse instead — the CLI
    — validate before tracing, the bn_learn CLI's main).

    Already-traced callers (the scan bodies) use this raw impl so the move
    inlines into the engine computation; the public `propose_move` below is
    the jitted entry point for eager callers. The branch closures below are
    rebuilt on every Python call, so an un-jitted eager call re-traces and
    re-compiles the `lax.switch` each time — thousands of such calls (the
    property tests) exhaust the JIT code-mapping budget and crash LLVM.

    Symmetry: each move's reverse is generated with the same probability
    (swap/reversal pick unordered windows; insertion draws (a, ±d) and the
    inverse is (b, ∓d), equiprobable), so Metropolis acceptance needs no
    Hastings correction.
    """
    if window < 2:
        raise ValueError(
            f"propose_move needs window >= 2, got {window}: window=1 has no "
            "in-window move (use window=0 for the legacy unbounded swap)")
    n = pos.shape[0]
    w = min(window, n)
    k_mv, k1, k2, k3 = jax.random.split(key, 4)
    order = inverse_permutation(pos)

    def swap(_):
        d = jax.random.randint(k1, (), 1, w)
        p = jax.random.randint(k2, (), 0, n - d)
        a, b = order[p], order[p + d]
        return pos.at[a].set(p + d).at[b].set(p), p

    def insert(_):
        a = jax.random.randint(k1, (), 0, n)
        d = jax.random.randint(k2, (), 1, w)
        sgn = jnp.where(jax.random.bernoulli(k3), 1, -1)
        b = a + sgn * d
        b = jnp.where((b >= 0) & (b < n), b, a)           # off-edge -> no-op
        x = order[a]
        down = ((pos > a) & (pos <= b)).astype(pos.dtype)  # a < b: shift left
        up = ((pos >= b) & (pos < a)).astype(pos.dtype)    # a > b: shift right
        new = (pos - down + up).at[x].set(b)
        return new.astype(pos.dtype), jnp.minimum(a, b)

    def reverse(_):
        ln = jax.random.randint(k1, (), 2, w + 1)
        p = jax.random.randint(k2, (), 0, n - ln + 1)
        hi = p + ln - 1
        inwin = (pos >= p) & (pos <= hi)
        return jnp.where(inwin, p + hi - pos, pos).astype(pos.dtype), p

    mv = jax.random.randint(k_mv, (), 0, 3)
    new_pos, lo = jax.lax.switch(mv, [swap, insert, reverse], None)
    return new_pos, lo.astype(jnp.int32)


propose_move = functools.partial(jax.jit,
                                 static_argnames=("window",))(_propose_move_impl)


def _propose_and_score(state: ChainState, k_prop: jax.Array,
                       score_fn: ScoreFn,
                       delta_fn: DeltaFn | BitmaskDelta | None, window: int):
    """One proposal + rescore under a STATIC window, dispatching between the
    full, plain-delta and bitmask-delta paths. Returns
    (new_pos, new_score, new_idx, new_ls, rows) where rows is the bitmask
    path's (win, planes_win) pair and None on the paths without the cache."""
    if window >= 2:
        new_pos, lo = _propose_move_impl(k_prop, state.pos, window=window)
    else:
        new_pos, lo = _propose_swap(k_prop, state.pos), jnp.int32(0)
    with jax.named_scope("order_score"):
        if isinstance(delta_fn, BitmaskDelta):
            new_score, new_idx, new_ls, win, planes_win = delta_fn.fn(
                new_pos, lo, state.cur_ls, state.cur_idx, state.pos,
                state.mask_planes)
            return new_pos, new_score, new_idx, new_ls, (win, planes_win)
        if delta_fn is not None:
            new_score, new_idx, new_ls = delta_fn(new_pos, lo, state.cur_ls,
                                                  state.cur_idx)
        else:
            new_score, new_idx, new_ls = score_fn(new_pos)
    return new_pos, new_score, new_idx, new_ls, None


@jax.named_scope("accept")
def _accept_update(state: ChainState, key, k_u, proposal) -> ChainState:
    """Shared MH accept/reject + cache/best bookkeeping."""
    new_pos, new_score, new_idx, new_ls, rows = proposal
    log_u = jnp.log(jax.random.uniform(k_u, (), minval=1e-38))
    accept = log_u < (new_score - state.score)

    pos = jnp.where(accept, new_pos, state.pos)
    score = jnp.where(accept, new_score, state.score)
    cur_idx = jnp.where(accept, new_idx, state.cur_idx)
    cur_ls = jnp.where(accept, new_ls, state.cur_ls)
    mask_planes = state.mask_planes
    if rows is not None:
        # write the window's rows back only on accept: a rejected proposal
        # aims every row at the out-of-range id n, which "drop" skips (as it
        # does the adaptive switch's padding rows). Nothing reads the old
        # stack afterwards, so XLA updates the scan carry in place.
        win, planes_win = rows
        n = mask_planes.shape[0]
        mask_planes = mask_planes.at[jnp.where(accept, win, n)].set(
            planes_win, mode="drop")

    better = accept & (new_score > state.best_score)
    return accept, ChainState(
        key=key, pos=pos, score=score, cur_idx=cur_idx, cur_ls=cur_ls,
        mask_planes=mask_planes,
        best_score=jnp.where(better, new_score, state.best_score),
        best_idx=jnp.where(better, new_idx, state.best_idx),
        best_pos=jnp.where(better, new_pos, state.best_pos),
        accepts=state.accepts + accept.astype(jnp.int32),
        win_idx=state.win_idx, adapt_err=state.adapt_err,
        step=state.step + 1,
    )


def mcmc_step(state: ChainState, score_fn: ScoreFn,
              delta_fn: DeltaFn | BitmaskDelta | None = None,
              window: int = 0) -> ChainState:
    """One MH iteration. window ≥ 2 selects the bounded-window move mixture;
    delta_fn (requires window ≥ 2) selects the incremental O(window·S)
    rescore seeded from the chain's (cur_ls, cur_idx) cache — wrapped in
    :class:`BitmaskDelta`, additionally from its cached consistency planes."""
    assert delta_fn is None or window >= 2, \
        "the delta path needs bounded-window proposals (window >= 2)"
    key, k_prop, k_u = jax.random.split(state.key, 3)
    proposal = _propose_and_score(state, k_prop, score_fn, delta_fn, window)
    _, new_state = _accept_update(state, key, k_u, proposal)
    return new_state


def mcmc_step_adaptive(state: ChainState, score_fn: ScoreFn,
                       delta_fns: tuple, windows: tuple[int, ...], *,
                       target_accept: float = DEFAULT_TARGET_ACCEPT,
                       burn_in: int = 0, da_gamma: float = 0.15,
                       da_t0: int = 10) -> ChainState:
    """One MH iteration with adaptive window selection (module docstring).

    windows: static, sorted candidate windows (each ≥ 2); delta_fns: matching
    tuple of DeltaFn/BitmaskDelta/None closures. state.win_idx picks the
    pre-traced `lax.switch` branch; while step < burn_in a dual-averaging
    iterate in index space moves win_idx toward target_accept, after that it
    is frozen (MCMC validity — adapt-then-freeze)."""
    assert len(windows) == len(delta_fns) and len(windows) >= 1
    masked = [isinstance(f, BitmaskDelta) for f in delta_fns]
    assert all(masked) or not any(masked), \
        "the bitmask cache needs a BitmaskDelta for every window"
    key, k_prop, k_u = jax.random.split(state.key, 3)
    n = state.pos.shape[0]
    w_max = min(max(windows), n)

    def branch(j):
        def go(_):
            *head, rows = _propose_and_score(state, k_prop, score_fn,
                                             delta_fns[j], windows[j])
            if rows is not None:
                # the switch's branches must agree on shapes: pad to the
                # widest window with id n (dropped by the scatter) and
                # zero rows
                win, planes_win = rows
                pad = w_max - win.shape[0]
                rows = (jnp.pad(win, (0, pad), constant_values=n),
                        jnp.pad(planes_win, ((0, pad), (0, 0), (0, 0))))
            return (*head, rows)
        return go

    idx = jnp.clip(state.win_idx, 0, len(windows) - 1)
    proposal = jax.lax.switch(idx, [branch(j) for j in range(len(windows))],
                              None)
    accept, new_state = _accept_update(state, key, k_u, proposal)

    # dual averaging in window-INDEX space: accept above target ⇒ push the
    # iterate up (wider moves), below ⇒ down; frozen once step ≥ burn_in
    t = new_state.step.astype(jnp.float32)            # 1-based after update
    adapting = new_state.step <= jnp.int32(burn_in)
    err = jnp.where(adapting,
                    state.adapt_err + (accept.astype(jnp.float32)
                                       - jnp.float32(target_accept)),
                    state.adapt_err)
    mu = jnp.float32((len(windows) - 1) / 2.0)
    x = mu + jnp.sqrt(t) / (jnp.float32(da_gamma) * (t + jnp.float32(da_t0))) \
        * err
    prop_idx = jnp.clip(jnp.round(x).astype(jnp.int32), 0, len(windows) - 1)
    win_idx = jnp.where(new_state.step < jnp.int32(burn_in), prop_idx,
                        state.win_idx)
    return new_state._replace(win_idx=win_idx, adapt_err=err)


@functools.partial(jax.jit, static_argnames=("n", "score_fn", "iters", "trace",
                                             "delta_fn", "window",
                                             "planes_fn"))
def mcmc_run(key: jax.Array, n: int, score_fn: ScoreFn, iters: int,
             trace: bool = False,
             delta_fn: DeltaFn | BitmaskDelta | None = None,
             window: int = 0, planes_fn=None):
    """Run one chain for `iters` iterations. Returns (final_state, score_trace).
    planes_fn (pos -> violation planes) is required iff delta_fn is a
    BitmaskDelta — it seeds the chain's consistency-mask cache."""
    state = init_chain(key, n, score_fn, planes_fn=planes_fn)

    def body(st, _):
        st = mcmc_step(st, score_fn, delta_fn, window)
        return st, (st.score if trace else None)

    state, tr = jax.lax.scan(body, state, None, length=iters)
    return state, tr


@functools.partial(jax.jit, static_argnames=("n", "score_fn", "iters",
                                             "windows", "delta_fns",
                                             "planes_fn", "burn_in",
                                             "target_accept", "trace"))
def mcmc_run_adaptive(key: jax.Array, n: int, score_fn: ScoreFn, iters: int, *,
                      windows: tuple[int, ...], delta_fns: tuple = None,
                      planes_fn=None, burn_in: int = None,
                      target_accept: float = DEFAULT_TARGET_ACCEPT,
                      trace: bool = False):
    """Run one chain with adaptive window selection. burn_in defaults to
    iters // 5; after it the window is frozen. Returns (final_state, trace)
    where trace (if requested) is (score (iters,), win_idx (iters,))."""
    if delta_fns is None:
        delta_fns = (None,) * len(windows)
    if burn_in is None:
        burn_in = iters // 5
    state = init_chain(key, n, score_fn, planes_fn=planes_fn,
                       win_idx=len(windows) // 2)

    def body(st, _):
        st = mcmc_step_adaptive(st, score_fn, delta_fns, windows,
                                target_accept=target_accept, burn_in=burn_in)
        return st, ((st.score, st.win_idx) if trace else None)

    state, tr = jax.lax.scan(body, state, None, length=iters)
    return state, tr


def exchange_step(states: ChainState) -> ChainState:
    """In-scan cross-chain exchange: the best chain (argmax best_score)
    re-seeds the worst chain's position/cache state — current pos, score,
    (cur_ls, cur_idx) and mask_planes are copied TOGETHER, so the re-seeded
    chain's caches describe its new order by construction, and its best_*
    triple is replaced by the donor's (≥ its own by argmin choice, keeping
    per-chain best_score monotone). PRNG keys, accept counts and adaptive
    stats stay per-slot, so the clone diverges immediately — the same
    re-seeding discipline as runtime/straggler.rebalance_chains, applied
    inside the scan instead of at the end.

    Degenerate ranking (all-equal best_score — e.g. early iterations, or a
    flat table) gives argmax == argmin: there is no information to transfer,
    so the exchange is explicitly a NO-OP instead of a self-copy — no leaf
    traffic (mask_planes can be large and mesh-sharded), and the invariant
    that win_idx / dual-averaging stats / keys / accept counts stay strictly
    per-slot holds trivially on every round.

    The ranking is NaN/inf-SAFE for graceful degradation under the run
    supervisor's fault model: a poisoned chain (non-finite best_score) ranks
    as -inf, so it is always the recipient and never the donor — one sick
    chain cannot spread through the exchange while it waits to be healed at
    the next segment boundary. On all-finite inputs the masked rank is
    bitwise the raw best_score, so healthy runs are unchanged."""
    rank = jnp.where(jnp.isfinite(states.best_score), states.best_score,
                     -jnp.inf)
    b = jnp.argmax(rank)
    w = jnp.argmin(rank)

    def copy(st: ChainState) -> ChainState:
        def mv(leaf):
            return leaf.at[w].set(leaf[b])

        return st._replace(
            pos=mv(st.pos), score=mv(st.score),
            cur_idx=mv(st.cur_idx), cur_ls=mv(st.cur_ls),
            mask_planes=mv(st.mask_planes), best_score=mv(st.best_score),
            best_idx=mv(st.best_idx), best_pos=mv(st.best_pos))

    return jax.lax.cond(b == w, lambda st: st, copy, states)


def _one_segment(states, step, iters: int, exchange_every: int):
    """The whole walk as ONE ``iters``-long segment of
    :func:`make_traced_segment_runner`: vmapped `step`, with the in-scan
    exchange every `exchange_every` iterations (none for a single chain)."""
    run = make_traced_segment_runner(
        step, exchange_every=exchange_every if states.pos.shape[0] > 1 else 0)
    return run(states, None, jnp.int32(0), length=iters)[0]


@functools.partial(jax.jit, static_argnames=("n_chains", "n", "score_fn",
                                             "iters", "delta_fn", "window",
                                             "exchange_every", "planes_fn"))
def mcmc_run_chains(key: jax.Array, n_chains: int, n: int, score_fn: ScoreFn,
                    iters: int, delta_fn: DeltaFn | BitmaskDelta | None = None,
                    window: int = 0, exchange_every: int = 0, planes_fn=None):
    """vmapped independent chains (DP axis). Returns stacked final states.

    exchange_every > 0 runs the periodic in-scan :func:`exchange_step` every
    that many iterations, instead of only reducing at the end: slow chains
    inherit the current best basin while the walk is still running — the
    paper's end-only best-graph exchange promoted to a restart heuristic.
    0 keeps fully independent chains."""
    keys = jax.random.split(key, n_chains)
    states = jax.vmap(
        lambda k: init_chain(k, n, score_fn, planes_fn=planes_fn))(keys)
    return _one_segment(
        states, lambda s: mcmc_step(s, score_fn, delta_fn, window), iters,
        exchange_every)


@functools.partial(jax.jit, static_argnames=("n_chains", "n", "score_fn",
                                             "iters", "windows", "delta_fns",
                                             "planes_fn", "burn_in",
                                             "target_accept",
                                             "exchange_every"))
def mcmc_run_chains_adaptive(key: jax.Array, n_chains: int, n: int,
                             score_fn: ScoreFn, iters: int, *,
                             windows: tuple[int, ...], delta_fns: tuple = None,
                             planes_fn=None, burn_in: int = None,
                             target_accept: float = DEFAULT_TARGET_ACCEPT,
                             exchange_every: int = 0):
    """mcmc_run_chains with per-chain adaptive window selection: each chain
    runs its own dual-averaging warm-up (adaptive stats are deliberately NOT
    copied by exchange_step, so a re-seeded chain keeps its own tuning)."""
    if delta_fns is None:
        delta_fns = (None,) * len(windows)
    if burn_in is None:
        burn_in = iters // 5
    keys = jax.random.split(key, n_chains)
    states = jax.vmap(
        lambda k: init_chain(k, n, score_fn, planes_fn=planes_fn,
                             win_idx=len(windows) // 2))(keys)
    step = lambda s: mcmc_step_adaptive(s, score_fn, delta_fns, windows,
                                        target_accept=target_accept,
                                        burn_in=burn_in)
    return _one_segment(states, step, iters, exchange_every)


def make_traced_segment_runner(step, *, tap=None, exchange=None,
                               exchange_every: int = 0,
                               stacked_step: bool = False):
    """The SEGMENTED run loop every walk shares (the single-device and
    sharded engines of repro/learner.py, :func:`mcmc_run_chains` and
    benchmarks/telemetry_bench): a jitted

        run_segment(states, trace, start, *, length) -> (states, trace)

    scanning ``length`` iterations from global iteration ``start``. The host
    calls it in a while loop, draining/analysing ``trace`` between segments
    — which is what makes stop-on-converge possible at all: the scan stays
    fully accelerator-resident, and the host only intervenes at segment
    granularity.

    * ``step``: per-chain ChainState -> ChainState (vmapped here), or — with
      ``stacked_step=True`` — a whole-stack step like
      core/sharded_scoring.sharded_chain_step (one shard_map program for all
      chains).
    * ``tap``: optional in-scan telemetry tap ``(trace, states, it) ->
      trace`` (telemetry/taps.make_tap); ``it`` is the global 1-based
      iteration, so trace cadence survives segment/restart boundaries.
      With no tap, ``trace`` is carried untouched (pass None).
    * ``exchange``: optional ``(states, trace) -> (states, trace)`` run
      every ``exchange_every`` global iterations (telemetry counts re-seeds
      via telemetry/taps.exchange_step_traced; plain runs wrap
      :func:`exchange_step`). The cadence uses the same global-iteration
      arithmetic as the checkpointed loop, so it survives restarts too.
    """
    if exchange is None:
        exchange = lambda st, tr: (exchange_step(st), tr)

    @functools.partial(jax.jit, static_argnames=("length",))
    def run_segment(states, trace, start, *, length: int):
        def body(carry, i):
            st, tr = carry
            st = step(st) if stacked_step else jax.vmap(step)(st)
            it = start + i + 1
            if tap is not None:
                with jax.named_scope("tap"):
                    tr = tap(tr, st, it)
            if exchange_every > 0:
                with jax.named_scope("exchange"):
                    st, tr = jax.lax.cond(it % exchange_every == 0,
                                          lambda c: exchange(*c),
                                          lambda c: c, (st, tr))
            return (st, tr), None

        (states, trace), _ = jax.lax.scan(body, (states, trace),
                                          jnp.arange(length))
        return states, trace

    return run_segment


def exchange_best(states: ChainState) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cross-chain best-graph reduction (max + index-resolved argmax — the same
    reduction pattern as the scoring kernel, one level up)."""
    w = jnp.argmax(states.best_score)
    return states.best_score[w], states.best_idx[w], states.best_pos[w]
