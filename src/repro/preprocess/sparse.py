"""Hash-compressed score table (the paper's §III-A memory-saving strategy).

The paper stores (node, parent-set) scores in a hash table because the dense
(n, S) table outgrows GPU memory. We keep the dense rank-indexed layout as
the oracle but add :class:`SparseScoreTable`: per node, only the parent sets
scoring within ``delta`` of that node's best are retained (Kuipers et al.
1803.07859's pruned per-node score lists), stored twice:

* an **open-addressing hash table** (multiplicative hashing + linear probe,
  the TPU-friendly replacement for the paper's chained buckets): O(1) point
  lookups of ls(i, pi) by PST rank, fully vectorized/jittable — usable from
  inside the order-scoring hot path;
* a **packed candidate list** (kept_ls / kept_parents / kept_idx): the
  representation core/order_scoring.score_order_pruned consumes, turning the
  per-iteration cost from O(n*S) into O(n*K) for K kept entries.

Pruning guarantee (exactness)
-----------------------------
The empty parent set is always kept, so every order has a consistent entry
per node and the pruned order score is well-defined and is always a LOWER
bound on the dense score. It is *exactly* equal whenever, for every node i,
the dense-optimal consistent parent set scores within ``delta`` of node i's
global best — in particular for delta = +inf the two scorers agree on every
order (tests/test_preprocess.py pins both properties). `to_dense()` is the
exact dense fallback: NEG_INF outside the kept set, bitwise-equal on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.order_scoring import NEG_INF

__all__ = ["SparseScoreTable", "prune_table"]

_HASH_MULT = np.uint32(0x9E3779B1)       # Fibonacci / golden-ratio hashing


def _hash(idx: np.ndarray, log2_cap: int) -> np.ndarray:
    h = (idx.astype(np.uint32) * _HASH_MULT)
    return (h >> np.uint32(32 - log2_cap)).astype(np.int64)


class SparseScoreTable:
    """Per-node pruned score lists with open-addressing lookup.

    Duck-types the parts of core.scores.ScoreTable the driver uses (`n`, `S`,
    `q`, `s`, and a `table` property materialising the exact dense fallback),
    so core/order_scoring, core/mcmc and repro/learner accept either
    representation. Deliberately does NOT keep the (S, s) PST or (S,) psizes:
    every stored array is O(n·K) — adjacency recovery decodes the winning
    ranks arithmetically instead (core.graph.adjacency_from_ranks), which is
    the paper's Algorithm 2 run in reverse and was the last O(S·s)
    hanger-on in the pruned path's memory footprint.
    """

    def __init__(self, *, keys, vals, kept_idx, kept_ls, kept_parents,
                 max_probe, q, s, delta, S, pst=None, psizes=None):
        # pst/psizes accepted (and ignored) for builder-signature stability
        del pst, psizes
        self.keys = jnp.asarray(keys)                # (n, cap) int32, -1 empty
        self.vals = jnp.asarray(vals)                # (n, cap) f32
        self.kept_idx = jnp.asarray(kept_idx)        # (n, K) int32, -1 pad
        self.kept_ls = jnp.asarray(kept_ls)          # (n, K) f32, NEG_INF pad
        self.kept_parents = jnp.asarray(kept_parents)  # (n, K, s) node ids
        self.max_probe = int(max_probe)
        self.q = q
        self.s = s
        self.delta = float(delta)
        self._S = int(S)
        self._dense = None

    # ------------------------------------------------------------ metadata
    @property
    def n(self) -> int:
        return self.keys.shape[0]

    @property
    def S(self) -> int:
        return self._S

    @property
    def K(self) -> int:
        """Packed width: max kept entries over nodes."""
        return self.kept_idx.shape[1]

    @property
    def nbytes_compressed(self) -> int:
        """Hash storage footprint (the memory the compression is about)."""
        return int(self.keys.nbytes + self.vals.nbytes)

    @property
    def compression_ratio(self) -> float:
        """Dense (n, S) f32 bytes over compressed bytes."""
        return (self.n * self.S * 4) / max(self.nbytes_compressed, 1)

    # ------------------------------------------------------------- lookups
    def lookup(self, node, idx):
        """ls(node, PST rank idx) if kept, else NEG_INF. Vectorized over
        leading dims of (node, idx); jit/vmap-safe (bounded probe window)."""
        return _hash_lookup(self.keys, self.vals, jnp.asarray(node),
                            jnp.asarray(idx), self.max_probe)

    @property
    def table(self):
        """Exact dense fallback: (n, S) f32 with NEG_INF at pruned entries.
        Materialised lazily and cached (this is the bridge that lets every
        dense-table scorer run unchanged on the compressed representation)."""
        if self._dense is None:
            dense = jnp.full((self.n, self.S), NEG_INF, jnp.float32)
            rows = jnp.arange(self.n, dtype=jnp.int32)[:, None]
            rows = jnp.broadcast_to(rows, self.kept_idx.shape)
            # pad entries (-1) are pushed out of range so mode="drop" skips
            # them (clipping could clobber rank 0 with a pad's NEG_INF)
            tgt = jnp.where(self.kept_idx >= 0, self.kept_idx, self.S)
            self._dense = dense.at[rows, tgt].set(self.kept_ls, mode="drop")
        return self._dense

    to_dense = table.fget

    # ------------------------------------------------------------- builders
    @classmethod
    def from_kept(cls, kept_idx: np.ndarray, kept_ls: np.ndarray,
                  kept_parents: np.ndarray, *, q: int, s: int, delta: float,
                  S: int):
        """Build the table from already-pruned per-node lists.

        kept_idx: (n, K) PST ranks, ASCENDING per node, -1 padded (rank 0 —
        the empty set — must be present for every node); kept_ls: (n, K) f32
        scores (NEG_INF pad); kept_parents: (n, K, s) parent NODE ids (-1
        pad). This is the single hash-construction path shared by
        :meth:`from_dense` and the streaming assembly
        (preprocess/streaming.py), so both produce bit-identical keys/vals/
        max_probe for identical kept lists — the property the
        streaming == dense+prune tests pin."""
        kept_idx = np.asarray(kept_idx, np.int32)
        kept_ls = np.asarray(kept_ls, np.float32)
        kept_parents = np.asarray(kept_parents, np.int32)
        n, K = kept_idx.shape
        cap = 1 << max(3, int(np.ceil(np.log2(2 * max(K, 1)))))
        log2_cap = int(np.log2(cap))
        keys = np.full((n, cap), -1, np.int32)
        vals = np.full((n, cap), np.float32(NEG_INF), np.float32)
        max_probe = 1
        for i in range(n):
            idxs = kept_idx[i][kept_idx[i] >= 0].astype(np.int64)
            slots = _hash(idxs, log2_cap)
            for k, (t, h) in enumerate(zip(idxs, slots)):
                probe = 1
                while keys[i, h] != -1:
                    h = (h + 1) % cap
                    probe += 1
                keys[i, h] = t
                vals[i, h] = kept_ls[i, k]
                max_probe = max(max_probe, probe)
        return cls(keys=keys, vals=vals, kept_idx=kept_idx, kept_ls=kept_ls,
                   kept_parents=kept_parents, max_probe=max_probe,
                   q=q, s=s, delta=delta, S=S)

    @classmethod
    def from_dense(cls, table, pst, psizes, *, q: int, s: int, delta: float):
        """Prune a dense (n, S) table: keep {t : ls[i,t] >= best_i - delta}
        (plus the empty set, rank 0) per node, hash the survivors."""
        del psizes                                   # layout-compat signature
        tbl = np.asarray(table)
        pst_np = np.asarray(pst)
        n, S = tbl.shape
        best = tbl.max(axis=1)
        keep = tbl >= (best[:, None] - float(delta))
        keep[:, 0] = True                            # empty set: always valid
        counts = keep.sum(axis=1)
        K = int(counts.max())
        kept_idx = np.full((n, K), -1, np.int32)
        kept_ls = np.full((n, K), np.float32(NEG_INF), np.float32)
        kept_parents = np.full((n, K, pst_np.shape[1]), -1, np.int32)
        for i in range(n):
            idxs = np.nonzero(keep[i])[0].astype(np.int64)
            kept_idx[i, :len(idxs)] = idxs
            kept_ls[i, :len(idxs)] = tbl[i, idxs]
            cands = pst_np[idxs]                     # (k, s) candidate space
            pn = cands + (cands >= i)                # -> node ids
            kept_parents[i, :len(idxs)] = np.where(cands < 0, -1, pn)
        return cls.from_kept(kept_idx, kept_ls, kept_parents, q=q, s=s,
                             delta=delta, S=S)


@functools.partial(jax.jit, static_argnames=("max_probe",))
def _hash_lookup(keys, vals, node, idx, max_probe: int):
    cap = keys.shape[1]
    log2_cap = int(np.log2(cap))
    h0 = ((idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
          >> jnp.uint32(32 - log2_cap)).astype(jnp.int32)
    probes = (h0[..., None] + jnp.arange(max_probe, dtype=jnp.int32)) % cap
    k = keys[node[..., None], probes]                # (..., P)
    hit = k == idx[..., None]
    v = vals[node[..., None], probes]
    return jnp.max(jnp.where(hit, v, NEG_INF), axis=-1)


def prune_table(st, delta: float) -> SparseScoreTable:
    """Compress a core.scores.ScoreTable (paper's memory-saving switch)."""
    return SparseScoreTable.from_dense(st.table, st.pst, st.psizes,
                                       q=st.q, s=st.s, delta=delta)
