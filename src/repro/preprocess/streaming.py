"""Streaming pruned assembly: fused count+score chunks -> SparseScoreTable
with NO dense (n, S) intermediate (paper §III-A taken at its word).

The dense assembly (pipeline.assemble_table) materialises the full (n, S)
score table plus an (n, S) rank map before pruning — at n = 100,
s = 4 (S ≈ 3.9M) that is ~1.6 GB apiece, the memory wall that blocked the
"n >= 100 in bounded memory" gate. This module inverts the dataflow: as each
device finishes a column-subset chunk, its (chunk, n) fused scores are

1. **rank-gathered per chunk**: for every node i NOT in column subset σ, the
   candidate-space PST rank of σ is computed arithmetically
   (core/combinatorics.rank_combinations_batch on the chunk only — the
   per-chunk replacement for the (n, S) ``_rank_map``), and the full local
   score ``|σ|·ln γ + TI[σ, i] (+ prior)`` is formed with the SAME f32 ops
   as the dense assembly, so kept scores are bitwise the dense path's;
2. **merged into per-device partial candidate lists** under a GLOBAL running
   best-per-node threshold: an entry is dropped only once it falls more than
   ``delta`` below the running best, and the running best only rises, so the
   final kept set is EXACTLY ``{t : ls[i,t] >= best_i - delta} ∪ {rank 0}``
   — the same rule ``SparseScoreTable.from_dense`` applies (Scutari et al.
   1804.08137's prune-without-loss argument; Kuipers & Moffa 1803.07859's
   per-node score lists);
3. **finalised once**: the per-device partials are merged, re-thresholded
   against the final best, packed per node in ascending-rank order and
   hashed through ``SparseScoreTable.from_kept`` — the construction path
   shared with the dense oracle, so streaming == dense+prune bitwise.

Chunks are cost-sharded over devices with the existing LPT planner
(planner.py); each device's dispatches stay async with a bounded in-flight
window, so peak memory is O(n·K) merge state + O(chunk·n) per-chunk
temporaries instead of O(n·S). ``peak_assembly_bytes`` in the returned info
self-reports the high-water mark of every host allocation the assembly makes
(the tests assert it — and independently, tracemalloc — stays under 25% of
the dense table's n·S·4 bytes).

``max_keep`` optionally caps each node's list at the top-``max_keep`` scores
(ties broken toward smaller rank). The cap composes exactly with the delta
rule — an entry outside a node's running top-``max_keep`` can never re-enter
it — but the result then equals dense+prune only when no node's within-delta
set exceeds ``max_keep``.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..core.combinatorics import (build_pst, n_parent_sets,
                                  rank_combinations_batch)
from ..core.order_scoring import NEG_INF
from ..core.scores import arity_vector, as_states, bge_data
from ..telemetry.spans import span
from .planner import plan_subsets
from .sparse import SparseScoreTable

__all__ = ["build_sparse_table_streaming"]

_COMPACT_EVERY = 16      # chunks merged into a device partial between sweeps
_INFLIGHT_PER_DEV = 2    # bounded dispatch window (results buffer on device)
_RANK_BATCH = 2048       # survivors ranked per call: bounds the int64
                         # temporaries of rank_combinations_batch (~8 arrays
                         # of (_RANK_BATCH, s) each) independent of how many
                         # survivors an early, pre-threshold chunk produces


def _rank_batched(n_cand: int, s: int, rows: np.ndarray,
                  sizes: np.ndarray) -> np.ndarray:
    out = np.empty(rows.shape[0], np.int64)
    for b0 in range(0, rows.shape[0], _RANK_BATCH):
        b1 = min(b0 + _RANK_BATCH, rows.shape[0])
        out[b0:b1] = rank_combinations_batch(n_cand, s, rows[b0:b1],
                                             sizes[b0:b1])
    return out


class _DevicePartial:
    """One device's running candidate lists: flat (node, rank, ls, parents)
    triples appended per chunk, periodically compacted against the global
    running threshold. Everything is O(kept) — no per-node padding until
    finalisation."""

    def __init__(self, s: int):
        self.node: list[np.ndarray] = []       # (L,) int32
        self.rank: list[np.ndarray] = []       # (L,) int64 PST ranks
        self.ls: list[np.ndarray] = []         # (L,) f32
        self.par: list[np.ndarray] = []        # (L, s) int32 parent node ids
        self.s = s
        self.since_compact = 0

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for lst in (self.node, self.rank, self.ls,
                                        self.par) for a in lst)

    def append(self, node, rank, ls, par) -> None:
        if len(node):
            self.node.append(node)
            self.rank.append(rank)
            self.ls.append(ls)
            self.par.append(par)
        self.since_compact += 1

    def _concat(self):
        if not self.node:
            return (np.empty(0, np.int32), np.empty(0, np.int64),
                    np.empty(0, np.float32), np.empty((0, self.s), np.int32))
        return (np.concatenate(self.node), np.concatenate(self.rank),
                np.concatenate(self.ls), np.concatenate(self.par))

    def compact(self, best: np.ndarray, delta: float,
                max_keep: int | None) -> None:
        """Re-filter against the CURRENT threshold (the running best only
        rises, so this drops only entries the final rule would drop too)."""
        node, rank, ls, par = self._concat()
        keep = ls >= (best - float(delta))[node]
        node, rank, ls, par = node[keep], rank[keep], ls[keep], par[keep]
        if max_keep is not None and len(node):
            node, rank, ls, par = _cap_per_node(node, rank, ls, par,
                                                best.shape[0], max_keep)
        self.node, self.rank = [node], [rank]
        self.ls, self.par = [ls], [par]
        self.since_compact = 0


def _cap_per_node(node, rank, ls, par, n: int, max_keep: int):
    """Keep each node's top-``max_keep`` entries by score, ties toward the
    smaller rank (deterministic, so the cap composes exactly across
    compactions)."""
    order = np.lexsort((rank, -ls.astype(np.float64), node))
    node_s = node[order]
    starts = np.zeros(n + 1, np.int64)
    starts[1:] = np.cumsum(np.bincount(node_s, minlength=n))
    pos = np.arange(len(node_s)) - starts[node_s]
    keep = order[pos < max_keep]
    keep.sort()                          # restore append order (stability)
    return node[keep], rank[keep], ls[keep], par[keep]


@jax.jit
def _prior_all_jit(R: jnp.ndarray, sub_c: jnp.ndarray) -> jnp.ndarray:
    """(C, n) additive prior for a chunk of column subsets — the streaming
    counterpart of core/priors.prior_chunk, evaluated for every child node at
    once (σ already holds parent NODE ids, so no candidate shift needed)."""
    from ..core.priors import ppf_ln
    vals = ppf_ln(R[:, jnp.clip(sub_c, 0)])              # (n, C, s)
    vals = jnp.where((sub_c < 0)[None, :, :], 0.0, vals)
    return vals.sum(-1).T                                # (C, n)


def build_sparse_table_streaming(
        data: np.ndarray, *, q, s: int, gamma: float = 0.1,
        ess: float = 1.0, chunk: int = 1024, delta: float,
        prior_matrix: np.ndarray | None = None, max_keep: int | None = None,
        devices=None, use_pallas: bool = False, block_m: int = 512,
        interpret: bool | None = None, score: str = "bdeu",
        bge_alpha_mu: float = 1.0, bge_alpha_w: float | None = None):
    """(SparseScoreTable, stream_info): the fused pipeline streamed straight
    into the pruned representation. Bitwise-equal to
    ``prune_table(build_score_table_fused(...), delta)`` (kept sets, packed
    lists AND hash arrays) while never allocating an (n, S)-sized array.

    stream_info: {"peak_assembly_bytes", "n_chunks", "n_devices",
    "imbalance", "q_buckets", "bins_real", "bins_computed", "kept_entries",
    "K", "stages"} — the plan and the bin counts as the dense build reports
    them; ``stages`` breaks the
    wall-clock into {plan_s, stream_s, finalize_s} for the telemetry
    collector's stage rows. ``score`` and the BGe hyperparameters are
    build_score_table_fused's; the chunks are scored through the same
    ``pipeline.chunk_runner``.
    """
    from .pipeline import chunk_runner

    with span("preprocess.plan") as plan_span:
        if score == "bge":
            data = bge_data(data)
            m, n = data.shape
            r = np.ones(n, np.int32)
        else:
            data = as_states(data)
            m, n = data.shape
            r = arity_vector(q, n)
        S = n_parent_sets(n - 1, s)
        log_gamma = float(np.log(gamma))

        # ---- plan: identical bucketing, chunking + LPT sharding to the
        # dense pipeline
        sub = build_pst(n, s)[0]                    # subsets of ALL n columns
        if devices is None:
            devices = [jax.devices()[0]]
        lay = plan_subsets(sub, r, chunk, m, len(devices))
        del sub                      # keep only the planned copy on the host
        chunk = lay.chunk
        sub_p = lay.sub
        ssz_p = (sub_p >= 0).sum(1, dtype=np.int32)
        R = (jnp.asarray(prior_matrix, jnp.float32)
             if prior_matrix is not None else None)

        run = chunk_runner(data, r, lay, devices, score=score, ess=ess,
                           bge_alpha_mu=bge_alpha_mu,
                           bge_alpha_w=bge_alpha_w, use_pallas=use_pallas,
                           block_m=block_m, interpret=interpret)

        # ---- streaming merge state
        best = np.full(n, np.float32(NEG_INF), np.float32)  # global best
        ls0 = np.full(n, np.float32(NEG_INF), np.float32)    # empty-set scores
        partials = [_DevicePartial(s) for _ in range(lay.n_devices)]
        peak = 0

        def note_peak(tmp_bytes: int) -> None:
            nonlocal peak
            peak = max(peak, sum(p.nbytes for p in partials) + tmp_bytes)

        arange_n = np.arange(n, dtype=np.int32)

        def merge_chunk(d: int, ci: int, ti_c: np.ndarray) -> None:
            nonlocal best
            sub_c = sub_p[ci * chunk:(ci + 1) * chunk]       # (C, s) node ids
            ssz_c = ssz_p[ci * chunk:(ci + 1) * chunk]
            row_c = lay.row[ci * chunk:(ci + 1) * chunk]     # -1: padding
            # same f32 composition as assemble_table: |σ|·ln γ + TI (+ prior)
            sc = ssz_c.astype(np.float32) * np.float32(log_gamma)
            sc = sc[:, None] + ti_c                           # (C, n)
            if R is not None:
                sc = sc + np.asarray(_prior_all_jit(R, jnp.asarray(sub_c)))
            member = (sub_c[:, :, None] == arange_n[None, None, :]).any(1)
            dom = (row_c >= 0)[:, None] & ~member             # (C, n) child ok
            chunk_best = np.where(dom, sc, np.float32(NEG_INF)).max(0)
            best = np.maximum(best, chunk_best)
            keep = dom & (sc >= (best - float(delta))[None, :])
            empty = np.nonzero(row_c == 0)[0]
            if len(empty):                                # σ = ∅ lives here
                ls0[:] = sc[empty[0]]
                keep[empty[0]] = False   # rank 0 re-inserted at finalisation
            cc, ii = np.nonzero(keep)
            if len(cc):
                rows = sub_c[cc]                              # (L, s) node ids
                cand = rows - (rows > ii[:, None])
                cand = np.where(rows < 0, -1, cand)
                ranks = _rank_batched(n - 1, s, cand, ssz_c[cc])
                partials[d].append(ii.astype(np.int32), ranks,
                                   sc[cc, ii], rows.astype(np.int32))
            note_peak(ti_c.nbytes + sc.nbytes + member.nbytes + keep.nbytes
                      + 2 * len(cc) * (4 + 8 + 4 + 4 * s))
            if partials[d].since_compact >= _COMPACT_EVERY:
                partials[d].compact(best, delta, max_keep)

    # ---- dispatch: round-robin over the LPT buckets, bounded in-flight
    with span("preprocess.stream") as stream_span:
        schedule = []
        for Q, first, plan in lay.buckets:
            width = max(len(b) for b in plan.device_chunks)
            for t in range(width):
                for d, bucket in enumerate(plan.device_chunks):
                    if t < len(bucket):
                        schedule.append((d, first + bucket[t], Q))
        pending: deque = deque()
        for d, ci, Q in schedule:
            ids = jax.device_put(jnp.asarray([ci], jnp.int32), devices[d])
            pending.append((d, ci, run(d, ids, Q)))       # async dispatch
            if len(pending) >= _INFLIGHT_PER_DEV * lay.n_devices:
                dd, cc_, fut = pending.popleft()
                merge_chunk(dd, cc_, np.asarray(fut)[0])
        while pending:
            dd, cc_, fut = pending.popleft()
            merge_chunk(dd, cc_, np.asarray(fut)[0])

    # ---- one merge at the end: final threshold, pack, hash
    with span("preprocess.finalize") as finalize_span:
        node = np.concatenate([np.concatenate(p.node) if p.node else
                               np.empty(0, np.int32) for p in partials])
        rank = np.concatenate([np.concatenate(p.rank) if p.rank else
                               np.empty(0, np.int64) for p in partials])
        ls = np.concatenate([np.concatenate(p.ls) if p.ls else
                             np.empty(0, np.float32) for p in partials])
        par = np.concatenate([np.concatenate(p.par) if p.par else
                              np.empty((0, s), np.int32) for p in partials])
        keep = ls >= (best - float(delta))[node]
        node, rank, ls, par = node[keep], rank[keep], ls[keep], par[keep]
        if max_keep is not None and len(node):
            node, rank, ls, par = _cap_per_node(node, rank, ls, par, n,
                                                max_keep)
        note_peak(node.nbytes + rank.nbytes + ls.nbytes + par.nbytes)

        order = np.lexsort((rank, node))          # per node, ascending rank
        node, rank, ls, par = node[order], rank[order], ls[order], par[order]
        counts = np.bincount(node, minlength=n)
        K = int(counts.max()) + 1 if len(node) else 1    # +1: forced rank 0
        kept_idx = np.full((n, K), -1, np.int32)
        kept_ls = np.full((n, K), np.float32(NEG_INF), np.float32)
        kept_parents = np.full((n, K, s), -1, np.int32)
        kept_idx[:, 0] = 0                                   # empty set first
        kept_ls[:, 0] = ls0
        starts = np.zeros(n + 1, np.int64)
        starts[1:] = np.cumsum(counts)
        pos = np.arange(len(node)) - starts[node] + 1
        kept_idx[node, pos] = rank.astype(np.int32)
        kept_ls[node, pos] = ls
        kept_parents[node, pos] = par
        note_peak(kept_idx.nbytes + kept_ls.nbytes + kept_parents.nbytes)

        sp = SparseScoreTable.from_kept(kept_idx, kept_ls, kept_parents,
                                        q=q, s=s, delta=delta, S=S)
    bge = score == "bge"
    info = {"peak_assembly_bytes": int(peak), **lay.summary(),
            "bins_real": None if bge else lay.bins_real,
            "bins_computed": None if bge else lay.bins_computed,
            "kept_entries": int(counts.sum()) + n, "K": K,
            "stages": {"plan_s": plan_span.seconds,
                       "stream_s": stream_span.seconds,
                       "finalize_s": finalize_span.seconds}}
    return sp, info
