"""Accelerator-resident telemetry taps (the in-scan half of the subsystem).

Everything in this module runs INSIDE the jitted iteration scan, so it must
be (a) a pytree the scan can carry, (b) O(small) per iteration, and (c) free
of host sync. The host-side collector (collector.py) drains the state
between segments — the tap/collector split mirrors the engine's own
device/host split: per-iteration work stays resident, per-segment analysis
(R̂, spike detection, JSONL) runs on host where branching is free.

:class:`TraceState` is carried NEXT TO the sampler's ``ChainState`` (leaves
stacked over chains, like every ChainState leaf), never inside it — the
sampler's checkpoint layout is unchanged, and pre-telemetry snapshots
restore through the checkpointer's ``allow_missing`` backfill exactly like
the pre-bitmask 9-leaf snapshots did (the trace leaves are appended AFTER
the 13 ChainState leaves in the checkpoint tuple).

Per-iteration cost (why the ≤ 5% overhead gate holds): one (C, W) histogram
scatter-add every iteration, plus — only on tap iterations, every
``trace_every``-th — two (C,) ring writes and one (C, n, n) adjacency
accumulation whose parent sets are unranked ARITHMETICALLY on device
(:func:`adjacency_bits_from_ranks`, paper Algorithm 2 as fixed-depth jax
ops). Nothing is gathered from the (n, S) table and nothing crosses ICI:
every tapped quantity (score, accepts, cur_idx, win_idx) is already
per-chain and replicated after the engine's own pmax/pmin reduction, so on
the sharded path the taps add ZERO collective traffic over the
``model``/chain axes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.combinatorics import binom_table, size_offsets
from ..core.mcmc import ChainState, exchange_step
from .spans import span

__all__ = ["TraceState", "init_trace", "make_tap", "exchange_step_traced",
           "unrank_parent_sets_jax", "adjacency_bits_from_ranks", "drain",
           "DEFAULT_TRACE_CAP"]

# ring capacity: enough taps for a stable split-R̂ (128 half-length 64 per
# split half) while keeping the trace leaves tiny (C · 128 · 8 bytes)
DEFAULT_TRACE_CAP = 128


class TraceState(NamedTuple):
    """Per-chain telemetry accumulators, one scan-carried pytree.

    scores/accepts are RING buffers written every ``trace_every`` iterations
    at slot ``taps % cap`` (strided + bounded: a long run overwrites the
    oldest taps, so R̂ always sees the most recent window — old history is
    exactly what a convergence check must forget). edge_counts accumulates
    the thinned per-order argmax adjacency (the graph the max-scorer walk
    reports), the posterior edge-count accumulator behind
    ``core.metrics.edge_posterior`` and the cross-chain edge-R̂."""
    scores: jax.Array       # (C, cap) f32 — ring of tapped chain scores
    accepts: jax.Array      # (C, cap) i32 — cumulative accept count at tap
    taps: jax.Array         # i32 — total taps written (ring head = taps % cap)
    win_hist: jax.Array     # (C, W) i32 — iterations spent per window index
    edge_counts: jax.Array  # (C, n, n) i32 — adj[parent, child] sample counts
    edge_taps: jax.Array    # i32 — thinned adjacency samples accumulated
    reseeds: jax.Array      # (C,) i32 — times slot was re-seeded by exchange


def init_trace(n_chains: int, n: int, n_windows: int = 1,
               cap: int = DEFAULT_TRACE_CAP) -> TraceState:
    return TraceState(
        scores=jnp.zeros((n_chains, cap), jnp.float32),
        accepts=jnp.zeros((n_chains, cap), jnp.int32),
        taps=jnp.int32(0),
        win_hist=jnp.zeros((n_chains, max(n_windows, 1)), jnp.int32),
        edge_counts=jnp.zeros((n_chains, n, n), jnp.int32),
        edge_taps=jnp.int32(0),
        reseeds=jnp.zeros((n_chains,), jnp.int32),
    )


def unrank_parent_sets_jax(ranks: jax.Array, off: jax.Array, B: jax.Array,
                           s: int) -> jax.Array:
    """(n,) global PST ranks -> (n, s) sorted candidate indices, -1 padded.

    The jax twin of core.combinatorics.unrank_parent_set (paper Algorithm 2):
    locate the size-k block from the offsets, then pick each element with the
    hockey-stick prefix sum g(t) = C(n_rest, r) − C(n_rest − t, r) — the
    first t with g(t) > l is the paper's inner while loop collapsed into one
    vectorized compare+argmax, so the whole decode is s fixed-depth steps of
    O(m) table lookups: jit/vmap-safe, no host round-trip, exact in int32
    for every S < 2^31 (n = 100, s = 4 is S ≈ 3.9M).

    off: (s+2,) int32 size_offsets; B: (m+1, s+2) int32 binom_table over the
    m = n−1 candidates.
    """
    m = B.shape[0] - 1
    t_vec = jnp.arange(1, m + 1, dtype=jnp.int32)

    def one(rank):
        rank = rank.astype(jnp.int32)
        k = jnp.searchsorted(off, rank, side="right").astype(jnp.int32) - 1
        l0 = rank - off[k]

        def body(pos, carry):
            low, l, out = carry
            active = pos < k
            r = jnp.clip(k - pos, 0, B.shape[1] - 1)
            n_rest = m - (low + 1)
            top = B[jnp.clip(n_rest, 0, m), r]
            g = top - B[jnp.clip(n_rest - t_vec, 0, m), r]       # g(t), t>=1
            t = jnp.int32(1) + jnp.argmax(g > l).astype(jnp.int32)
            elem = low + t
            l_new = l - (top - B[jnp.clip(n_rest - (t - 1), 0, m), r])
            out = out.at[pos].set(jnp.where(active, elem, -1))
            return (jnp.where(active, elem, low),
                    jnp.where(active, l_new, l), out)

        init = (jnp.int32(-1), l0, jnp.full((s,), -1, jnp.int32))
        _, _, out = jax.lax.fori_loop(0, s, body, init)
        return out

    return jax.vmap(one)(ranks)


def adjacency_bits_from_ranks(ranks: jax.Array, off: jax.Array, B: jax.Array,
                              s: int) -> jax.Array:
    """(n,) per-node winning PST ranks -> (n, n) int32 adjacency
    adj[parent, child] — core.graph.adjacency_from_ranks as pure jax ops
    (bit-identical; pinned by tests/test_telemetry.py)."""
    n = ranks.shape[0]
    cands = unrank_parent_sets_jax(ranks, off, B, s)              # (n, s)
    child = jnp.arange(n, dtype=jnp.int32)[:, None]
    parents = jnp.where(cands >= 0, cands + (cands >= child), -1)  # node ids
    onehot = (parents[:, :, None] == jnp.arange(n, dtype=jnp.int32)) \
        & (parents[:, :, None] >= 0)                               # (n, s, n)
    return onehot.any(axis=1).T.astype(jnp.int32)    # (parent, child)


def make_tap(n: int, s: int, trace_every: int):
    """Build the in-scan tap closure: (trace, states, it) -> trace.

    ``it`` is the GLOBAL 1-based iteration index (start + i + 1 inside a
    segment scan), so the tap cadence survives segment and checkpoint-restart
    boundaries exactly like the exchange cadence does. The unranking tables
    (off, binom) are baked into the closure as constants — a few KB,
    replicated everywhere."""
    off = jnp.asarray(size_offsets(n - 1, s), jnp.int32)
    B = jnp.asarray(binom_table(n - 1, s + 1), jnp.int32)
    every = max(int(trace_every), 1)

    def tap(trace: TraceState, states: ChainState, it) -> TraceState:
        C = trace.win_hist.shape[0]
        wi = jnp.clip(states.win_idx, 0, trace.win_hist.shape[1] - 1)
        trace = trace._replace(
            win_hist=trace.win_hist.at[jnp.arange(C), wi].add(1))

        def do_tap(tr: TraceState) -> TraceState:
            slot = tr.taps % tr.scores.shape[1]
            adj = jax.vmap(
                lambda r: adjacency_bits_from_ranks(r, off, B, s))(
                    states.cur_idx)
            # graceful degradation: a poisoned chain (non-finite cached
            # score) keeps tapping its ring — diagnostics must SEE the NaN
            # to flag it — but contributes nothing to the posterior edge
            # accumulator until the supervisor heals it
            ok = jnp.isfinite(states.score).astype(adj.dtype)
            return tr._replace(
                scores=tr.scores.at[:, slot].set(states.score),
                accepts=tr.accepts.at[:, slot].set(states.accepts),
                taps=tr.taps + 1,
                edge_counts=tr.edge_counts + adj * ok[:, None, None],
                edge_taps=tr.edge_taps + 1,
            )

        return jax.lax.cond(it % every == 0, do_tap, lambda tr: tr, trace)

    return tap


def exchange_step_traced(states: ChainState,
                         trace: TraceState) -> tuple[ChainState, TraceState]:
    """core.mcmc.exchange_step + a re-seed count on the recipient slot (the
    degenerate all-equal ranking is a no-op there and counts nothing here).
    Mirrors exchange_step's NaN/inf-safe masked rank so the counted
    recipient slot matches the slot the exchange actually re-seeds."""
    rank = jnp.where(jnp.isfinite(states.best_score), states.best_score,
                     -jnp.inf)
    b = jnp.argmax(rank)
    w = jnp.argmin(rank)
    trace = trace._replace(
        reseeds=trace.reseeds.at[w].add((b != w).astype(jnp.int32)))
    return exchange_step(states), trace


def drain(trace: TraceState) -> dict:
    """Host-side snapshot: fetch every leaf as numpy, and linearise the
    score/accept rings oldest-first (valid entries only) so the collector
    sees plain (C, L) time series.

    The fetch waits for the segment that produced ``trace``; that wait is
    made explicit under the ``segment.wait`` span, so the host's own
    fetch-and-check work is told apart from waiting for the device."""
    with span("segment.wait"):
        jax.block_until_ready(trace)
    tr = jax.tree.map(np.asarray, trace)
    cap = tr.scores.shape[1]
    T = int(tr.taps)
    L = min(T, cap)
    idx = (np.arange(T - L, T) % cap) if L else np.empty(0, np.int64)
    return {
        "scores": tr.scores[:, idx],
        "accepts": tr.accepts[:, idx],
        "taps": T,
        "win_hist": tr.win_hist,
        "edge_counts": tr.edge_counts,
        "edge_taps": int(tr.edge_taps),
        "reseeds": tr.reseeds,
    }
