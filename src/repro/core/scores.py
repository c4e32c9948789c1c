"""Bayesian-Dirichlet local scores in log space (paper Eq. 3/4) and the
precomputed score table (the paper's "hash table", §III-A).

``ls(i, π) = |π|·ln γ + Σ_k [ lnΓ(α_k) − lnΓ(α_k + N_k)
                              + Σ_j ( lnΓ(N_jk + α_jk) − lnΓ(α_jk) ) ]``

with BDeu hyperparameters ``α_jk = ess / (q_π · r_i)``, ``α_k = ess / q_π``,
where variable ``i`` has ``r_i`` states and ``q_π = Π_{p∈π} r_p`` is the
number of parent configurations (``q^{|π|}`` when every variable has ``q``
states).  Natural log internally (the paper's log10 is a constant factor
that cancels in Metropolis–Hastings ratios; priors are rescaled to match —
see priors.py).

Arities: every entry point takes ``q``, one int (every variable has ``q``
states) or one int per variable; :func:`arity_vector` turns either into the
one int32 arity vector ``r`` the code works with.  A parent configuration is
a mixed-radix code with per-column strides (:func:`mixed_radix`), so the
codes of a parent set fill exactly the first ``q_π`` bins.

Counting N_jk is formulated as one-hot × one-hot matmuls so the hot loop is
MXU work on TPU (see kernels/count for the Pallas version; this module is the
pure-jnp oracle and the default CPU path).

Continuous data take the Gaussian BGe score instead (``score="bge"``):
:func:`bge_score_single` is its float64 oracle and
``build_score_table(score="bge")`` its reference table.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln

from .combinatorics import build_pst, n_parent_sets

__all__ = ["arity_vector", "check_states", "as_states", "mixed_radix",
           "max_bins", "count_parent_child", "local_scores_chunk",
           "build_score_table", "ScoreTable", "validate_prior_matrix",
           "SCORES", "check_score", "bge_prior", "bge_data",
           "bge_moments", "bge_log_const",
           "bge_log_marginal", "bge_score_single"]

SCORES = ("bdeu", "bge")     # discrete states | continuous (Gaussian) data


def arity_vector(q, n: int) -> np.ndarray:
    """(n,) int32 states per variable from ``q``: one int for every variable,
    or a sequence of n ints."""
    r = np.asarray(q)
    if r.dtype.kind not in "iu":
        raise ValueError(f"arities must be integers, got {q!r}")
    if r.ndim == 0:
        r = np.full(n, int(r))
    if r.shape != (n,):
        raise ValueError(f"{r.size} arities given for {n} variables")
    if r.min() < 1:
        raise ValueError(f"every variable needs at least one state: {q!r}")
    return r.astype(np.int32)


def check_states(data: np.ndarray, r: np.ndarray) -> None:
    """Raise unless every state of column i lies in [0, r_i)."""
    bad = (data < 0) | (data >= r[None, :])
    if bad.any():
        i = int(np.nonzero(bad.any(0))[0][0])
        raise ValueError(f"data states must lie in [0, r_i): column {i} "
                         f"has a state outside [0, {int(r[i])})")


def as_states(data) -> np.ndarray:
    """(m, n) int32 states of discrete data; raises on a value that is not
    an integer (continuous data take ``score="bge"``), so no measurement is
    truncated to a state in silence."""
    arr = np.asarray(data)
    if arr.dtype.kind not in "iub":
        if arr.dtype.kind != "f" or not np.all(np.isfinite(arr)) or \
                np.any(arr != np.round(arr)):
            raise ValueError("BDeu scores discrete states: the data hold "
                             "values that are not integers (score "
                             "continuous data with score='bge')")
    return arr.astype(np.int32)


def check_score(score: str) -> str:
    if score not in SCORES:
        raise ValueError(f"score {score!r} is not one of {SCORES}")
    return score


def mixed_radix(arity_ext: jnp.ndarray, cols: jnp.ndarray):
    """(strides (..., s), q_σ (...)) int32 of column sets ``cols`` (..., s),
    padding mapped to the appended zeros column whose arity is 1:
    stride_j = Π_{l<j} r[cols_l], so a set's configuration codes fill
    exactly [0, q_σ) and its active bins are the first q_σ (q**j and q**|σ|
    at a uniform q)."""
    r = arity_ext[cols]
    strides = jnp.concatenate(
        [jnp.ones_like(r[..., :1]), jnp.cumprod(r[..., :-1], axis=-1)], -1)
    return strides, strides[..., -1] * r[..., -1]


def max_bins(r, s: int) -> int:
    """Largest q_σ over sets of at most s columns: the product of the s
    largest arities (q**s at a uniform q)."""
    return math.prod(sorted(r, reverse=True)[:s])


def count_parent_child(data_ext: jnp.ndarray, node: int | jnp.ndarray,
                       parent_cols: jnp.ndarray, q, s: int) -> jnp.ndarray:
    """Contingency counts N[c, parent_config, child_state] for a chunk of parent sets.

    data_ext: (m, n+1) int32 — data with an appended all-zeros column so padded
      parents (mapped to column n) contribute digit 0.
    parent_cols: (C, s) int32 column indices into data_ext (already node-mapped,
      padding -> n).
    q: one arity for every variable (an int) or a tuple of one per variable.
    Returns (C, Q, r_max) float32 counts, Q = :func:`max_bins` and r_max the
    largest arity (q**s and q at a uniform q); states past the child's own
    arity count 0.
    """
    n = data_ext.shape[1] - 1
    r = q if isinstance(q, tuple) else (q,) * n
    arity_ext = jnp.asarray(r + (1,), jnp.int32)
    strides, _ = mixed_radix(arity_ext, parent_cols)     # (C, s)
    cols = data_ext[:, parent_cols]                      # (m, C, s)
    code = jnp.sum(cols * strides, axis=-1)              # (m, C)
    oh_code = jax.nn.one_hot(code, max_bins(r, s),
                             dtype=jnp.float32)          # (m, C, Q)
    oh_child = jax.nn.one_hot(data_ext[:, node], max(r),
                              dtype=jnp.float32)         # (m, r_max)
    # MXU-shaped contraction over samples
    return jnp.einsum("mcQ,mj->cQj", oh_code, oh_child)


@functools.partial(jax.jit, static_argnames=("q", "s", "use_pallas"))
def local_scores_chunk(data_ext: jnp.ndarray, node: jnp.ndarray,
                       pst_chunk: jnp.ndarray, psize_chunk: jnp.ndarray,
                       *, q, s: int,
                       log_gamma: float, ess: float,
                       use_pallas: bool = False) -> jnp.ndarray:
    """ls(node, π) for a chunk of parent sets. pst_chunk: (C, s) candidate idx, -1 pad.
    q: one arity for every variable (an int) or a tuple of one per variable.

    use_pallas=True routes the counting matmul through kernels/count
    (count_contingency, interpret mode off-TPU) instead of the pure-jnp
    einsum — same (C, Q, q) contract, MXU-tiled on real hardware; that
    kernel counts one arity for every variable."""
    n = data_ext.shape[1] - 1
    q = q if isinstance(q, tuple) else (q,) * n
    arity_ext = jnp.asarray(q + (1,), jnp.int32)
    # candidate -> node column; padding -> the zeros column n
    pcols = pst_chunk + (pst_chunk >= node)
    pcols = jnp.where(pst_chunk < 0, n, pcols)
    if use_pallas:
        if len(set(q)) > 1:
            raise ValueError("kernels/count counts one arity for every "
                             "variable; use the einsum oracle")
        from ..kernels.count import count_contingency  # late: kernels layer
        counts = count_contingency(data_ext, data_ext[:, node], pcols,
                                   q=q[0], s=s)                   # (C, Q, q)
    else:
        counts = count_parent_child(data_ext, node, pcols, q, s)  # (C, Q, r_max)

    k = psize_chunk.astype(jnp.float32)                                # (C,)
    _, qsig = mixed_radix(arity_ext, pcols)                            # q_π
    r_pa = qsig.astype(jnp.float32)                                    # exact
    alpha_jk = ess / (r_pa * arity_ext[node].astype(jnp.float32))      # (C,)
    alpha_k = ess / r_pa
    # the codes of a parent set fill its first q_π bins
    active = jnp.arange(counts.shape[1])[None, :] < qsig[:, None]      # (C, Q)

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
                 q, s: int):
        self.table = table          # (n, S) float32
        self.pst = jnp.asarray(pst)        # (S, s) int32, -1 padded
        self.psizes = jnp.asarray(psizes)  # (S,) int32
        self.q = q                  # int, or one arity per variable
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
                         q: tuple, s: int, log_gamma: float, ess: float,
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


def build_score_table(data: np.ndarray, *, q, s: int,
                      gamma: float = 0.1, ess: float = 1.0,
                      chunk: int = 1024,
                      prior_matrix: np.ndarray | None = None,
                      use_pallas: bool = False, score: str = "bdeu",
                      bge_alpha_mu: float = 1.0,
                      bge_alpha_w: float | None = None) -> ScoreTable:
    """Preprocessing (paper §III-A): all local scores for |π| <= s.

    data: (m, n) integer states, column i in [0, r_i) for the arities ``q``
    (one int, or one per variable). Optionally folds the pairwise prior
    (paper §IV) into the table — priors are per-(node, parent-set) additive
    constants, so baking them in preserves Eq. 9 exactly.

    Chunk launches are batched per node (_node_scores_batched); the Python
    loop only runs over nodes and never syncs on a device result — the single
    block happens when the caller first reads the stacked table. This is the
    reference path; preprocess/pipeline.build_score_table_fused is the fast
    one (same table).

    ``score="bge"`` scores continuous (m, n) data with the BGe score instead
    (``q`` and ``ess`` unused): float64 numpy from full log-determinants,
    :func:`bge_log_marginal` of every family minus that of its parents.
    """
    if check_score(score) == "bge":
        return _bge_table(data, s=s, gamma=gamma, prior_matrix=prior_matrix,
                          alpha_mu=bge_alpha_mu, alpha_w=bge_alpha_w)
    data = as_states(data)
    m, n = data.shape
    r = arity_vector(q, n)
    check_states(data, r)
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
                                 psz_chunks, R, q=tuple(r.tolist()), s=s,
                                 log_gamma=log_gamma, ess=ess,
                                 use_pallas=use_pallas)[:S]
            for i in range(n)]
    table = jnp.stack(rows)
    return ScoreTable(table, pst, psizes, q, s)


def score_single(data: np.ndarray, node: int, parent_nodes: list[int], *,
                 q, s: int, gamma: float = 0.1, ess: float = 1.0) -> float:
    """Scalar oracle for tests: ls(node, parents as *node ids*)."""
    from .combinatorics import nodes_to_candidates
    data = np.asarray(data, np.int32)
    m, n = data.shape
    cands = np.sort(nodes_to_candidates(np.asarray(parent_nodes, np.int64), node))
    row = np.full((1, s), -1, np.int32)
    row[0, : len(cands)] = cands
    data_ext = jnp.asarray(np.concatenate([data, np.zeros((m, 1), np.int32)], 1))
    ls = local_scores_chunk(data_ext, jnp.int32(node), jnp.asarray(row),
                            jnp.asarray([len(cands)], jnp.int32),
                            q=tuple(arity_vector(q, n).tolist()), s=s,
                            log_gamma=float(math.log(gamma)), ess=ess)
    return float(ls[0])


# ---------------------------------------------------------------- BGe score
# The Gaussian BGe score (Geiger and Heckerman 2002, with the correction of
# Kuipers, Moffa and Heckerman 2014, Ann. Statist. 42:1689) for continuous
# data, with bnlearn's default hyperparameters: alpha_mu = 1, alpha_w = n + 2
# and the prior mean nu = the sample mean. For a set Y of k variables,
# with N samples and a = alpha_w - n,
#
#   log p(d_Y) = (k/2) log(alpha_mu / (N + alpha_mu))
#                + log Gamma_k((N + a + k)/2) - log Gamma_k((a + k)/2)
#                - (k N / 2) log pi + ((a + k)/2) log det T_YY
#                - ((N + a + k)/2) log det R_YY,
#
# T = t I, t = alpha_mu (alpha_w - n - 1) / (alpha_mu + 1) (the prior
# covariance is the identity), R = T + sum_samples (x - xbar)(x - xbar)^T
# (the (nu - xbar) term of R vanishes at nu = xbar), Gamma_k the
# multivariate gamma function; ls(i, pi) = |pi| ln gamma + log p(d_{pi+i})
# - log p(d_pi).

def bge_prior(alpha_mu: float, alpha_w: float | None,
              n: int) -> tuple[float, float]:
    """(alpha_w, t) of the prior, alpha_w = n + 2 when None, checked:
    alpha_mu > 0 and alpha_w > n + 1 (t > 0)."""
    if not alpha_mu > 0:
        raise ValueError(f"bge_alpha_mu must be positive, got {alpha_mu}")
    alpha_w = float(n + 2 if alpha_w is None else alpha_w)
    if alpha_w <= n + 1:
        raise ValueError(f"bge_alpha_w must exceed n + 1 = {n + 1}, got "
                         f"{alpha_w}")
    return alpha_w, alpha_mu * (alpha_w - n - 1) / (alpha_mu + 1)


def bge_data(data) -> np.ndarray:
    """(m, n) float64 continuous data, checked finite with m >= 2."""
    arr = np.asarray(data, np.float64)
    if arr.ndim != 2 or arr.shape[0] < 2:
        raise ValueError(f"BGe needs (m >= 2, n) samples, got {arr.shape}")
    if not np.all(np.isfinite(arr)):
        raise ValueError("BGe data must be finite")
    return arr


def bge_moments(data, alpha_mu: float = 1.0,
                alpha_w: float | None = None) -> tuple[np.ndarray, float]:
    """(R (n, n) float64, t): the posterior scale matrix at nu = xbar."""
    x = bge_data(data)
    _, t = bge_prior(alpha_mu, alpha_w, x.shape[1])
    xc = x - x.mean(0)
    return t * np.eye(x.shape[1]) + xc.T @ xc, t


def bge_log_const(k: int, *, N: int, n: int, t: float, alpha_mu: float,
                   alpha_w: float) -> float:
    """log p(d_Y) of |Y| = k variables less its log det R_YY term (the
    pi^(k(k-1)/4) of the two multivariate gammas cancels)."""
    a = alpha_w - n
    return (k / 2 * math.log(alpha_mu / (N + alpha_mu))
            + sum(math.lgamma((N + a + k + 1 - j) / 2)
                  - math.lgamma((a + k + 1 - j) / 2) for j in range(1, k + 1))
            - k * N / 2 * math.log(math.pi) + (a + k) / 2 * k * math.log(t))


def bge_log_marginal(R: np.ndarray, cols, *, N: int, n: int, t: float,
                     alpha_mu: float, alpha_w: float) -> float:
    """log p(d_Y) of the columns ``cols`` (the formula above), from the
    full log-determinant of R_YY."""
    k = len(cols)
    if k == 0:
        return 0.0
    sign, logdet = np.linalg.slogdet(R[np.ix_(cols, cols)])
    if sign <= 0:
        raise ValueError("R_YY is not positive definite")
    return (bge_log_const(k, N=N, n=n, t=t, alpha_mu=alpha_mu,
                           alpha_w=alpha_w)
            - (N + alpha_w - n + k) / 2 * logdet)


def bge_score_single(data, node: int, parent_nodes, *, gamma: float = 0.1,
                     alpha_mu: float = 1.0,
                     alpha_w: float | None = None) -> float:
    """Scalar float64 oracle: ls(node, parents as node ids) of continuous
    ``data`` under BGe, log p(d_{parents + node}) - log p(d_parents) from
    two full log-determinants (not through a Schur complement).

    Departures from the 2014 closed form: none. Its free choices are fixed
    as bnlearn fixes them: nu = the sample mean (so R has no (nu - xbar)
    term) and T = t I; the structure penalty |pi| ln gamma is the
    program's, added to the score as for BDeu."""
    R, t = bge_moments(data, alpha_mu, alpha_w)
    N, n = np.shape(data)
    kw = dict(N=N, n=n, t=t, alpha_mu=float(alpha_mu),
              alpha_w=bge_prior(alpha_mu, alpha_w, n)[0])
    parents = sorted(int(p) for p in parent_nodes)
    return (len(parents) * math.log(gamma)
            + bge_log_marginal(R, parents + [node], **kw)
            - bge_log_marginal(R, parents, **kw))


def _bge_table(data, *, s: int, gamma: float, prior_matrix,
               alpha_mu: float, alpha_w: float | None) -> ScoreTable:
    """The reference BGe table: per node and parent-set size, batched
    float64 slogdets of every family and every parent set."""
    R, t = bge_moments(data, alpha_mu, alpha_w)
    N, n = np.shape(data)
    validate_prior_matrix(prior_matrix, n)
    alpha_w = bge_prior(alpha_mu, alpha_w, n)[0]
    a = alpha_w - n
    pst, psizes = build_pst(n - 1, s)
    const = functools.partial(bge_log_const, N=N, n=n, t=t,
                              alpha_mu=float(alpha_mu), alpha_w=alpha_w)

    table = np.zeros((n, len(pst)), np.float64)
    for i in range(n):
        for l in range(s + 1):
            rows = np.nonzero(psizes == l)[0]
            par = pst[rows, :l] + (pst[rows, :l] >= i)          # node ids
            fam = np.concatenate([par, np.full((len(rows), 1), i)], 1)
            ld_fam = np.linalg.slogdet(R[fam[:, :, None], fam[:, None, :]])[1]
            ld_par = (np.linalg.slogdet(R[par[:, :, None], par[:, None, :]])[1]
                      if l else 0.0)
            table[i, rows] = (l * math.log(gamma)
                              + const(l + 1) - (N + a + l + 1) / 2 * ld_fam
                              - const(l) + (N + a + l) / 2 * ld_par)
    out = jnp.asarray(table, jnp.float32)
    if prior_matrix is not None:
        from .priors import prior_table
        out = out + prior_table(jnp.asarray(prior_matrix, jnp.float32),
                                jnp.asarray(pst), n)
    return ScoreTable(out, pst, psizes, None, s)
