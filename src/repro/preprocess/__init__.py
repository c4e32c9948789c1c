"""GPU-resident preprocessing subsystem (paper §III-A / §III-B).

The paper splits structure learning into a *preprocessing* stage — compute
every local score ls(i, pi) for |pi| <= s and store it in a hash table
(§III-A) — and an MCMC stage that only reads the table (§III-B). After PR 1
made the MCMC iteration O(window*S), preprocessing became the end-to-end
wall-clock bottleneck (the paper's own future work, §VII: move counting onto
the accelerator). This package is that stage, organised by paper section:

==================  =========================================================
module              paper mapping
==================  =========================================================
fused.py            §III-A counting + Eq. 4 scoring fused into one pass:
                    each column subset is counted ONCE against all n children
                    (one matmul) and scored in-register (in-VMEM gammaln in
                    the Pallas kernel), so the (C, Q, r) contingency tensor
                    never reaches HBM. Arities may differ per variable.
planner.py          §III-B task assignment: work units weighted by the
                    paper's q_pi*m cost estimate and LPT-balanced across
                    devices (the GPU-block task table, promoted to a mesh);
                    column subsets bucketed by q_sigma so a chunk pads only
                    to its bucket's bin count.
sparse.py           §III-A memory-saving strategy: per-node score lists
                    pruned to a delta of the node's best, stored in an
                    open-addressing hash table (the paper's chained hash
                    buckets, TPU-vectorized) + packed lists for the
                    order-scoring hot path, with an exact dense fallback.
streaming.py        §III-A taken at its word: fused chunks rank-gathered
                    chunk-locally and merged straight into the pruned
                    SparseScoreTable — peak memory O(n·K + chunk·n), no
                    (n, S) dense table or rank map ever materialised
                    (bitwise-equal to dense+prune). The engine behind
                    prune_delta runs; reaches n = 100, s = 4.
cache.py            preprocessing disk cache keyed on (data, arities, s, ess,
                    gamma, prior [+ prune_delta/max_keep for pruned
                    entries]); manifests verified on restore: repeated
                    bn_learn runs skip the stage, never get a wrong table.
pipeline.py         the driver: cache -> plan -> fused pass -> dense
                    rank-gather assembly (the rank IS the hash address) or
                    streaming-pruned assembly -> cache store.
compact.py          the dense assembly on the TPU: each node's row is its
                    column of the per-subset output with the subsets that
                    hold the node dropped, compacted in VMEM by log-step
                    passes (bitwise the rank-gather).
==================  =========================================================

core/scores.build_score_table remains the oracle; tests/test_preprocess.py
pins fused == oracle to <= 1e-4 absolute (bitwise on CPU) and
benchmarks/preprocess_bench.py tracks the >= 3x n = 64 speedup gate.
"""
from .fused import fused_scores_pallas, fused_scores_ref
from .pipeline import assemble_table, build_score_table_fused
from .planner import (PreprocessPlan, assign_chunks, chunk_costs,
                      plan_preprocess, plan_subsets)
from .sparse import SparseScoreTable, prune_table
from .streaming import build_sparse_table_streaming

__all__ = [
    "build_score_table_fused", "assemble_table",
    "build_sparse_table_streaming",
    "fused_scores_ref", "fused_scores_pallas",
    "PreprocessPlan", "plan_preprocess", "plan_subsets", "assign_chunks",
    "chunk_costs",
    "SparseScoreTable", "prune_table",
]
