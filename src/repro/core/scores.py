"""Bayesian-Dirichlet local scores in log space (paper Eq. 3/4) and the
precomputed score table (the paper's "hash table", §III-A).

``ls(i, π) = |π|·ln γ + Σ_k [ lnΓ(α_k) − lnΓ(α_k + N_k)
                              + Σ_j ( lnΓ(N_jk + α_jk) − lnΓ(α_jk) ) ]``

with BDeu hyperparameters ``α_jk = ess / (r_i · q)``, ``α_k = ess / r_i``,
``r_i = q^{|π|}``.  Natural log internally (the paper's log10 is a constant
factor that cancels in Metropolis–Hastings ratios; priors are rescaled to
match — see priors.py).

Counting N_jk is formulated as one-hot × one-hot matmuls so the hot loop is
MXU work on TPU (see kernels/count for the Pallas version; this module is the
pure-jnp oracle and the default CPU path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln

from .combinatorics import build_pst, n_parent_sets

__all__ = ["count_parent_child", "local_scores_chunk", "build_score_table",
           "ScoreTable", "validate_prior_matrix"]


def count_parent_child(data_ext: jnp.ndarray, node: int | jnp.ndarray,
                       parent_cols: jnp.ndarray, q: int, s: int) -> jnp.ndarray:
    """Contingency counts N[c, parent_config, child_state] for a chunk of parent sets.

    data_ext: (m, n+1) int32 — data with an appended all-zeros column so padded
      parents (mapped to column n) contribute digit 0.
    parent_cols: (C, s) int32 column indices into data_ext (already node-mapped,
      padding -> n).
    Returns (C, q**s, q) float32 counts.
    """
    m = data_ext.shape[0]
    cols = data_ext[:, parent_cols]                      # (m, C, s)
    pw = (q ** jnp.arange(s, dtype=jnp.int32))           # (s,)
    code = jnp.sum(cols * pw, axis=-1)                   # (m, C)
    Q = q ** s
    oh_code = jax.nn.one_hot(code, Q, dtype=jnp.float32)         # (m, C, Q)
    oh_child = jax.nn.one_hot(data_ext[:, node], q, dtype=jnp.float32)  # (m, q)
    # MXU-shaped contraction over samples
    return jnp.einsum("mcQ,mj->cQj", oh_code, oh_child)


def _bin_digits(q: int, s: int) -> np.ndarray:
    """(q**s, s) digit decomposition of each parent-config bin index, base q."""
    Q = q ** s
    b = np.arange(Q, dtype=np.int64)
    return np.stack([(b // q ** j) % q for j in range(s)], axis=-1)


@functools.partial(jax.jit, static_argnames=("q", "s", "use_pallas"))
def local_scores_chunk(data_ext: jnp.ndarray, node: jnp.ndarray,
                       pst_chunk: jnp.ndarray, psize_chunk: jnp.ndarray,
                       *, q: int, s: int,
                       log_gamma: float, ess: float,
                       use_pallas: bool = False) -> jnp.ndarray:
    """ls(node, π) for a chunk of parent sets. pst_chunk: (C, s) candidate idx, -1 pad.

    use_pallas=True routes the counting matmul through kernels/count
    (count_contingency, interpret mode off-TPU) instead of the pure-jnp
    einsum — same (C, Q, q) contract, MXU-tiled on real hardware."""
    n = data_ext.shape[1] - 1
    # candidate -> node column; padding -> the zeros column n
    pcols = pst_chunk + (pst_chunk >= node)
    pcols = jnp.where(pst_chunk < 0, n, pcols)
    if use_pallas:
        from ..kernels.count import count_contingency  # late: kernels layer
        counts = count_contingency(data_ext, data_ext[:, node], pcols,
                                   q=q, s=s)                      # (C, Q, q)
    else:
        counts = count_parent_child(data_ext, node, pcols, q, s)  # (C, Q, q)

    k = psize_chunk.astype(jnp.float32)                                # (C,)
    r = jnp.power(float(q), k)                                         # q^{|π|}
    alpha_jk = ess / (r * q)                                           # (C,)
    alpha_k = ess / r

    digits = jnp.asarray(_bin_digits(q, s))                            # (Q, s)
    pad_pos = jnp.arange(s)[None, :] >= psize_chunk[:, None]           # (C, s)
    # bin active iff every padded position has digit 0
    active = jnp.all(jnp.where(pad_pos[:, None, :], digits[None] == 0, True),
                     axis=-1)                                          # (C, Q)

    Nk = counts.sum(-1)                                                # (C, Q)
    a_k = alpha_k[:, None]
    a_jk = alpha_jk[:, None, None]
    term_k = gammaln(a_k) - gammaln(a_k + Nk)                          # (C, Q)
    term_jk = (gammaln(counts + a_jk) - gammaln(a_jk)).sum(-1)         # (C, Q)
    terms = active * (term_k + term_jk)                                # (C, Q)
    # bins summed strictly in order (XLA may reassociate a row reduction):
    # the fused preprocessing sums them in the same order, bit for bit
    acc = terms[:, 0]
    for b in range(1, terms.shape[1]):
        acc = acc + terms[:, b]
    return k * log_gamma + acc


class ScoreTable:
    """Dense (n, S) local-score table + its PST. The TPU-native 'hash table'."""

    def __init__(self, table: jnp.ndarray, pst: np.ndarray, psizes: np.ndarray,
                 q: int, s: int):
        self.table = table          # (n, S) float32
        self.pst = jnp.asarray(pst)        # (S, s) int32, -1 padded
        self.psizes = jnp.asarray(psizes)  # (S,) int32
        self.q = q
        self.s = s

    @property
    def n(self) -> int:
        return self.table.shape[0]

    @property
    def S(self) -> int:
        return self.table.shape[1]


def validate_prior_matrix(prior_matrix, n: int) -> None:
    """Up-front prior_matrix check with actionable errors: must be a square
    (n, n) interface matrix with entries in [0, 1] (paper §IV). Catching this
    here beats a shape error surfacing mid-way through a chunked build."""
    if prior_matrix is None:
        return
    R = np.asarray(prior_matrix)
    if R.ndim != 2 or R.shape[0] != R.shape[1]:
        raise ValueError("prior_matrix must be square (n, n); got shape "
                         f"{R.shape}")
    if R.shape[0] != n:
        raise ValueError(f"prior_matrix is {R.shape[0]}x{R.shape[0]} but the "
                         f"data has n={n} variables")
    if not np.all(np.isfinite(R)) or R.min() < 0.0 or R.max() > 1.0:
        raise ValueError("prior_matrix entries must be finite confidences "
                         f"in [0, 1]; got range [{R.min()}, {R.max()}]")


@functools.partial(jax.jit, static_argnames=("q", "s", "use_pallas"))
def _node_scores_batched(data_ext, node, pst_chunks, psz_chunks, R, *,
                         q: int, s: int, log_gamma: float, ess: float,
                         use_pallas: bool):
    """All chunks of one node in a single device program (a lax.map over the
    stacked (nc, chunk, s) PST) — one launch per node instead of one per
    (node, chunk), so the host never blocks between chunks."""
    from .priors import prior_chunk  # late import to avoid cycle

    def body(args):
        pst_c, psz_c = args
        ls = local_scores_chunk(data_ext, node, pst_c, psz_c, q=q, s=s,
                                log_gamma=log_gamma, ess=ess,
                                use_pallas=use_pallas)
        if R is not None:
            ls = ls + prior_chunk(R, node, pst_c)
        return ls

    return jax.lax.map(body, (pst_chunks, psz_chunks)).reshape(-1)


def build_score_table(data: np.ndarray, *, q: int, s: int,
                      gamma: float = 0.1, ess: float = 1.0,
                      chunk: int = 1024,
                      prior_matrix: np.ndarray | None = None,
                      use_pallas: bool = False) -> ScoreTable:
    """Preprocessing (paper §III-A): all local scores for |π| <= s.

    data: (m, n) integer states in [0, q). Optionally folds the pairwise prior
    (paper §IV) into the table — priors are per-(node, parent-set) additive
    constants, so baking them in preserves Eq. 9 exactly.

    Chunk launches are batched per node (_node_scores_batched); the Python
    loop only runs over nodes and never syncs on a device result — the single
    block happens when the caller first reads the stacked table. This is the
    reference path; preprocess/pipeline.build_score_table_fused is the fast
    one (same table).
    """
    data = np.asarray(data, dtype=np.int32)
    m, n = data.shape
    if np.any(data < 0) or np.any(data >= q):
        raise ValueError(f"data states must lie in [0, {q})")
    validate_prior_matrix(prior_matrix, n)
    S = n_parent_sets(n - 1, s)
    pst, psizes = build_pst(n - 1, s)
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)], axis=1))
    log_gamma = float(np.log(gamma))

    # stack chunks to a uniform width (pad rows are all -1 / size 0: they
    # score as the empty set and are sliced off below)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    pst_chunks = jnp.asarray(
        np.pad(pst, ((0, pad), (0, 0)), constant_values=-1)
        .reshape(-1, chunk, s))
    psz_chunks = jnp.asarray(
        np.pad(psizes, (0, pad)).reshape(-1, chunk))
    R = None if prior_matrix is None else jnp.asarray(prior_matrix, jnp.float32)
    rows = [_node_scores_batched(data_ext, jnp.int32(i), pst_chunks,
                                 psz_chunks, R, q=q, s=s,
                                 log_gamma=log_gamma, ess=ess,
                                 use_pallas=use_pallas)[:S]
            for i in range(n)]
    table = jnp.stack(rows)
    return ScoreTable(table, pst, psizes, q, s)


def score_single(data: np.ndarray, node: int, parent_nodes: list[int], *,
                 q: int, s: int, gamma: float = 0.1, ess: float = 1.0) -> float:
    """Scalar oracle for tests: ls(node, parents as *node ids*)."""
    from .combinatorics import nodes_to_candidates
    data = np.asarray(data, np.int32)
    m, n = data.shape
    cands = np.sort(nodes_to_candidates(np.asarray(parent_nodes, np.int64), node))
    row = np.full((1, s), -1, np.int32)
    row[0, : len(cands)] = cands
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)], 1))
    ls = local_scores_chunk(data_ext, jnp.int32(node), jnp.asarray(row),
                            jnp.asarray([len(cands)], jnp.int32), q=q, s=s,
                            log_gamma=float(math.log(gamma)), ess=ess)
    return float(ls[0])
