"""Task-assignment planner (paper §III-B) for the preprocessing pipeline.

The paper assigns score-computation work to GPU blocks by *estimated cost*,
not by unit count: a parent set pi costs ~ q_pi * m (bins x samples), q_pi
= prod_{p in pi} r_p its parent configurations (q^{|pi|} at a uniform
arity). We shard at the granularity of column-subset chunks (fused.py) and
balance chunks across devices with LPT (longest-processing-time-first) greedy
scheduling — the classic 4/3-approximation to makespan, which is exactly the
imbalance the paper's Fig. 6 task table addresses.

A chunk is computed at one static bin count Q, so its subsets should have
similar q_sigma: :func:`plan_subsets` groups the column subsets by q_sigma
into at most ``MAX_BUCKETS`` buckets (one compiled program each) and pads
each chunk only to its bucket's Q.

The planner is pure (no device state): it maps a cost vector to per-device
chunk lists, so it is unit-testable at any simulated device count and is
reused by the learner (repro/learner.py) through
pipeline.build_score_table_fused with the devices of a launch/mesh mesh.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

__all__ = ["chunk_costs", "assign_chunks", "PreprocessPlan", "plan_preprocess",
           "q_buckets", "SubsetPlan", "plan_subsets", "MAX_BUCKETS"]

MAX_BUCKETS = 6     # bin-count buckets, each one compiled program


def chunk_costs(qsig: np.ndarray, chunk: int, m: int) -> np.ndarray:
    """(n_chunks,) float64 estimated cost of each subset chunk:
    sum over its rows of q_sigma * m (paper §III-B's per-set estimate).

    This is the paper's cost model, an upper envelope on the active-bin
    scoring work. The fused matmul itself is near-uniform per chunk (its
    width is the bucket's Q), so over uniform chunks LPT degrades gracefully
    toward chunk-count balance — the model matters most for the padded tail
    chunk and for mixed-size chunks at small S."""
    qsig = np.asarray(qsig)
    assert qsig.shape[0] % chunk == 0, "pad subsets to a chunk multiple"
    return qsig.reshape(-1, chunk).sum(axis=1, dtype=np.float64) * float(m)


def assign_chunks(costs: np.ndarray, n_devices: int) -> list[list[int]]:
    """LPT assignment: chunks sorted by descending cost, each placed on the
    currently least-loaded device. Returns per-device chunk-id lists (each
    list ascending, for deterministic execution order)."""
    costs = np.asarray(costs, dtype=np.float64)
    loads = np.zeros(n_devices)
    buckets: list[list[int]] = [[] for _ in range(n_devices)]
    for c in np.argsort(-costs, kind="stable"):
        d = int(np.argmin(loads))
        buckets[d].append(int(c))
        loads[d] += costs[c]
    return [sorted(b) for b in buckets]


@dataclass
class PreprocessPlan:
    """Sharding decision for one preprocessing run."""
    chunk: int
    n_chunks: int
    costs: np.ndarray                       # (n_chunks,) estimated unit costs
    device_chunks: list[list[int]]          # per-device ascending chunk ids
    padded_chunks: list[np.ndarray] = field(default_factory=list)
    # per-device ids padded (by repeating the last id) to a common length so
    # every device runs the same static-shape scan; duplicate results are
    # overwritten with identical values at assembly.

    @property
    def n_devices(self) -> int:
        return len(self.device_chunks)

    @property
    def device_loads(self) -> np.ndarray:
        return np.asarray([sum(self.costs[c] for c in b) if b else 0.0
                           for b in self.device_chunks])

    @property
    def imbalance(self) -> float:
        """max/mean device load (1.0 = perfectly balanced)."""
        loads = self.device_loads
        mean = loads.mean()
        return float(loads.max() / mean) if mean > 0 else 1.0


def plan_preprocess(qsig: np.ndarray, chunk: int, m: int,
                    n_devices: int) -> PreprocessPlan:
    """Full plan: cost model + LPT + static-shape padding, over chunks of
    rows whose q_sigma are ``qsig``.

    Every chunk id appears on exactly one device (before padding); padding
    repeats each device's last id so all scans share one trace.
    """
    costs = chunk_costs(qsig, chunk, m)
    n_chunks = costs.shape[0]
    device_chunks = assign_chunks(costs, max(1, n_devices))
    # drop devices with no work (more devices than chunks); n_chunks >= 1
    # always (the PST includes the empty set), so at least one bucket remains
    device_chunks = [b for b in device_chunks if b]
    width = max((len(b) for b in device_chunks), default=0)
    padded = [np.asarray(b + [b[-1]] * (width - len(b)), dtype=np.int32)
              for b in device_chunks]
    return PreprocessPlan(chunk=chunk, n_chunks=n_chunks, costs=costs,
                          device_chunks=device_chunks, padded_chunks=padded)


def q_buckets(qsig: np.ndarray, chunk: int) -> list[int]:
    """Ascending bin counts Q of at most ``MAX_BUCKETS`` buckets, the last
    the largest q_sigma, chosen to compute the fewest bins: a bucket of
    subsets computes Q bins for each row of its chunks, padding included,
    and each bucket beyond the first is charged one chunk at the largest Q
    for its compile and dispatch. An exact dynamic program over contiguous
    groups of the sorted distinct q_sigma values."""
    counts = np.bincount(qsig)
    vals = np.flatnonzero(counts)
    cum = np.concatenate([[0], np.cumsum(counts[vals])])
    extra = chunk * int(vals[-1])
    D = len(vals)
    # best[b][j]: least cost of the first j values in b + 1 groups
    best = np.full((MAX_BUCKETS, D + 1), np.inf)
    cut = np.zeros((MAX_BUCKETS, D + 1), np.int64)
    for j in range(1, D + 1):
        best[0, j] = vals[j - 1] * -(-cum[j] // chunk) * chunk
    for b in range(1, MAX_BUCKETS):
        for j in range(1, D + 1):
            for i in range(b, j):
                c = (best[b - 1, i] + extra
                     + vals[j - 1] * -(-(cum[j] - cum[i]) // chunk) * chunk)
                if c < best[b, j]:
                    best[b, j], cut[b, j] = c, i
    b = int(np.argmin(best[:, D]))
    out, j = [], D
    while b >= 0:
        out.append(int(vals[j - 1]))
        j, b = int(cut[b, j]), b - 1
    return out[::-1]


@dataclass
class SubsetPlan:
    """The column subsets of one table build, laid out in chunks by bucket.

    Rows are the subsets of ``build_pst(n, s)`` grouped by bucket, each
    bucket in ``build_pst`` order and padded with empty rows (``sub`` all
    -1, ``row`` -1) to a chunk multiple; ``row[t]`` is the rank in
    ``build_pst(n, s)`` of the subset at row t. ``buckets`` holds (Q, id
    of the bucket's first chunk, plan over the bucket's chunks); chunk
    ``first + c`` is the plan's c."""
    chunk: int
    sub: np.ndarray                          # (n_chunks * chunk, s) int32
    qsig: np.ndarray                         # (n_chunks * chunk,) int32
    row: np.ndarray                          # (n_chunks * chunk,) int32
    buckets: list[tuple[int, int, PreprocessPlan]]

    @property
    def n_chunks(self) -> int:
        return self.sub.shape[0] // self.chunk

    @property
    def n_devices(self) -> int:
        return max(p.n_devices for _, _, p in self.buckets)

    @property
    def bins_real(self) -> int:
        """sum of q_sigma over the real subsets: the bins that can count."""
        return int(self.qsig[self.row >= 0].astype(np.int64).sum())

    @property
    def bins_computed(self) -> int:
        """sum over chunks of chunk x Q: the bins the kernel computes."""
        return sum(Q * p.n_chunks * self.chunk for Q, _, p in self.buckets)

    def summary(self) -> dict:
        loads = np.zeros(self.n_devices)
        for _, _, p in self.buckets:
            loads[:p.n_devices] += p.device_loads
        return {"n_chunks": self.n_chunks, "n_devices": self.n_devices,
                "imbalance": float(loads.max() / loads.mean()),
                "q_buckets": [Q for Q, _, _ in self.buckets]}


def plan_subsets(sub: np.ndarray, r: np.ndarray, chunk: int, m: int,
                 n_devices: int) -> SubsetPlan:
    """Bucket, chunk and shard the column subsets ``sub`` ((C, s), -1
    padded, as ``build_pst(n, s)`` lists them) of columns with arities
    ``r``. Within a bucket the subsets keep their ``build_pst`` order. Host
    memory stays a few O(C) int32 arrays (the streaming assembly's bound)."""
    r_ext = np.append(np.asarray(r, np.int32), np.int32(1))
    qsig = np.ones(len(sub), np.int32)
    for col in sub.T:                        # padding -1 -> arity 1
        qsig *= r_ext[col]
    chunk = min(chunk, len(qsig))
    Qs = q_buckets(qsig, chunk)
    lows = [0] + Qs[:-1]
    sizes = [int(np.count_nonzero((qsig > lo) & (qsig <= Q)))
             for lo, Q in zip(lows, Qs)]
    total = sum(k + (-k) % chunk for k in sizes)
    out_sub = np.full((total, sub.shape[1]), -1, np.int32)
    out_q = np.ones(total, np.int32)
    out_row = np.full(total, -1, np.int32)
    buckets = []
    at = 0
    for Q, lo in zip(Qs, lows):
        idx = np.flatnonzero((qsig > lo) & (qsig <= Q))
        width = len(idx) + (-len(idx)) % chunk
        rows = slice(at, at + len(idx))      # unbuffered: idx is in range
        np.take(sub, idx, axis=0, out=out_sub[rows], mode="clip")
        np.take(qsig, idx, out=out_q[rows], mode="clip")
        out_row[rows] = idx
        plan = plan_preprocess(out_q[at:at + width], chunk, m, n_devices)
        buckets.append((Q, at // chunk, plan))
        at += width
    return SubsetPlan(chunk=chunk, sub=out_sub, qsig=out_q, row=out_row,
                      buckets=buckets)
