"""The fused preprocessing pipeline: cache -> plan -> fused count+score ->
assemble (dense OR streaming-pruned) -> cache store.

Replaces core/scores.build_score_table's host-side double loop (n nodes x
S/chunk chunks, one device round-trip each) with:

1. one fused count+score pass per column-subset chunk (fused.py) — all n
   children of a chunk are scored by a single contraction;
2. cost-balanced chunk sharding across devices (planner.py, paper §III-B);
3. one of two assemblies:

   * **dense** (``prune_delta=None``, or ``streaming=False``): a single
     jitted scan per device, then a gather
     ls(i, pi) = |pi|*ln(gamma) + TI[rank(columns(pi, i)), i] using the
     vectorized combination ranking (core/combinatorics) — the rank IS the
     hash (paper §III-A). The (n, S) rank map is built on the device from
     the (S, s) PST by one jitted int32 computation, so only the PST and
     its sizes cross from the host. Materialises the (n, S) table (plus
     the (n, S) device rank map), which is the memory wall at n >= 100;
   * **streaming** (``prune_delta`` set — the default engine for pruned
     tables, streaming.py): per-chunk dispatch whose (chunk, n) output is
     rank-gathered chunk-locally and merged into per-node within-delta
     candidate lists under a global running best, going straight into the
     pruned SparseScoreTable. Peak memory O(n·K + chunk·n); NO dense (n, S)
     table or rank map ever exists. Bitwise-equal to dense+prune
     (tests/test_streaming.py pins it).

4. a disk cache (cache.py) keyed on (data, arities, s, ess, gamma, prior).
   Dense runs cache the dense table (one entry serves every delta);
   streaming runs cache the pruned representation under a key that
   additionally includes (prune_delta, max_keep) — "always cache the DENSE table" is no longer
   possible at streaming scale. Pruned lookups try sparse first, then fall
   back to pruning a dense entry, then build. Every restore is
   manifest-verified (wrong arities/s/m/n/... is a logged miss, never a
   wrong-shape table).

``q`` is one arity for every variable or one per variable; the table is
the same either way for ``q = [q] * n``. Column subsets are counted in
chunks of similar q_sigma (planner.plan_subsets), each at its bucket's bin
count Q.

``score`` is a choice about the data: ``"bdeu"`` scores integer states
(non-integer data is refused), ``"bge"`` scores continuous data with the
Gaussian BGe score (bge.py; ``q`` and ``ess`` unused). Both assemblies meet
the score at one seam, :func:`chunk_runner`: a function of a planned chunk
that returns its (chunk, n) ``TI``. BGe has no q_sigma buckets, so its
chunks come out in ``build_pst(n, s)`` order and the dense assembly keeps
``TI`` on the device.

The dense result is bitwise-compatible with build_score_table on CPU (the
oracle's reduction order is reproduced deliberately; see fused.py) at a
fraction of the wall clock — benchmarks/preprocess_bench.py measures >= 3x
at n = 64 and ~10x at ALARM size, which is what makes n > 60 end-to-end
practical; the streaming path extends reach to n = 100, s = 4 (S ~ 3.9M)
where the dense intermediate alone is ~1.6 GB.

With ``return_info=True`` the info dict has the SAME schema on cache hit and
miss: {cache_hit, n, S, score, plan, preprocess_s, streaming,
peak_assembly_bytes, bins_real, bins_computed, stages}; ``score`` is the
score kind. ``plan`` is None on
a cache hit (no sharding was planned), a {n_chunks, n_devices, imbalance,
q_buckets} dict otherwise; ``bins_real`` (sum of q_sigma over the column
subsets) and ``bins_computed`` (sum over chunks of chunk x Q) count the
kernel's useful and computed bins, None on a cache hit and for BGe;
``peak_assembly_bytes`` is None unless the streaming assembly ran.
``stages`` breaks ``preprocess_s`` into per-stage wall-clock seconds
(plan_s/score_s/assemble_s on the dense path, plan_s/stream_s/finalize_s
streaming, cache_load_s/cache_store_s around the disk cache; BGe's
``preprocess.moments`` lies inside score_s or plan_s) — the
telemetry collector (repro/learner.prepare_run) emits them as stage rows.
Each is the ``.seconds`` of a telemetry span (``preprocess.build`` gives
``preprocess_s``; ``preprocess.plan``/``score``/``assemble``, the last split
into ``preprocess.rank_map`` and ``preprocess.gather``), so a profiler trace
shows the same stretches on the device's clock.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.combinatorics import build_pst, n_parent_sets, size_offsets
from ..core.scores import (ScoreTable, arity_vector, as_states, bge_data,
                           bge_prior, check_score, check_states,
                           validate_prior_matrix)
from ..telemetry.spans import span
from .cache import (cache_key, load_cached_sparse, load_cached_table,
                    store_cached_sparse, store_cached_table)
from .fused import (child_columns, encode_subset_codes, fused_scores_pallas,
                    fused_scores_ref, score_luts)
from .planner import plan_subsets
from .sparse import SparseScoreTable, prune_table

__all__ = ["build_score_table_fused", "assemble_table", "chunk_runner"]


def _binom(x: jnp.ndarray, r: jnp.ndarray, s: int) -> jnp.ndarray:
    """C(x, r) elementwise for int32 x >= 0 and 1 <= r <= s, in closed form:
    C(x, k + 1) = C(x, k) (x - k) / (k + 1) divides exactly, and a factor
    x - k < 0 only meets C(x, k) = 0. No table read, so XLA fuses it into
    plain vector arithmetic (gathers from a binomial table do not fuse on
    the TPU and keep (n, S) temporaries)."""
    c = out = x
    for k in range(1, s):
        c = c * (x - k) // (k + 1)
        out = jnp.where(r == k + 1, c, out)
    return out


@functools.partial(jax.jit, static_argnames=("n", "s"))
def _rank_map(n: int, s: int, pst, psizes) -> jax.Array:
    """(n, S) int32 on the device: rank_map[i, t] = rank (in the
    size-ascending subset enumeration over the n columns) of the column set
    of PST row t for node i. Candidate->column mapping is monotone, so digit
    order is preserved and the subset's config bins line up with the PST
    entry's.

    The same hockey-stick formula as
    core/combinatorics.rank_combinations_batch, which stays the host oracle
    (tests/test_rank_map.py pins the two bitwise): one (n, S) int32 term per
    PST position, so no intermediate is wider than the output. int32 is
    exact while every value formed stays below 2**31, which n and s alone
    decide; past that this raises at trace time.

    Dense-assembly only — the streaming path computes the INVERSE map chunk
    by chunk (streaming.py) and never materialises this array."""
    largest = max([math.comb(n + 1, s + 1) + n_parent_sets(n, s)]
                  + [k * math.comb(n, k) for k in range(2, s + 1)])
    if largest >= 2 ** 31:
        raise ValueError(
            f"the int32 rank map is exact only below 2**31: at n={n}, s={s}, "
            f"C(n+1, s+1) + the size offsets, or a binomial's product "
            f"k*C(n, k), reaches {largest}")
    off = size_offsets(n, s).tolist()
    rank = jnp.zeros((n,) + psizes.shape, jnp.int32)
    for k in range(1, s + 1):
        rank = jnp.where(psizes == k, off[k], rank)
    node = jnp.arange(n, dtype=jnp.int32)[:, None]
    prev = jnp.int32(-1)
    for j in range(s):
        valid = j < psizes
        r = jnp.where(valid, psizes - j, 1)
        col = pst[:, j] + (pst[:, j] >= node).astype(jnp.int32)
        term = (_binom(n - 1 - jnp.where(valid, prev, 0), r, s)
                - _binom(n - jnp.where(valid, col, 0), r, s))
        rank = rank + jnp.where(valid, term, 0)
        prev = col
    return rank


def assemble_table(TI: jnp.ndarray, rank_map: jax.Array, psizes: np.ndarray,
                   log_gamma: float) -> jnp.ndarray:
    """(n, S) table from the fused per-subset output: a pure gather."""
    n = TI.shape[1]
    kfac = jnp.asarray(np.asarray(psizes, np.float32)) * jnp.float32(log_gamma)
    rm = jnp.asarray(rank_map)
    return kfac[None, :] + TI[rm, jnp.arange(n, dtype=jnp.int32)[:, None]]


@functools.partial(jax.jit, static_argnames=("Q", "r_max", "ess",
                                             "use_pallas", "block_m",
                                             "interpret"))
def _run_device(data_ext, arity, col_child, subs, qsigs, luts, chunk_ids, *,
                Q, r_max, ess, use_pallas, block_m, interpret):
    """One device's share of one bin-count bucket: a single jitted scan over
    its chunk ids -> stacked (U, C, n) TI. ``arity`` (n,) holds the columns'
    states, ``col_child`` (R,) the child of each child one-hot column,
    ``qsigs`` each subset's q_sigma <= Q, ``luts`` the jnp path's
    fused.score_luts. Module-level so the trace is
    compiled once per problem shape and bucket, not once per build call. The
    streaming assembly reuses it with (1,)-shaped chunk_ids (one trace
    serves all chunks of a bucket)."""
    m = data_ext.shape[0]
    arity_ext = jnp.concatenate([arity, jnp.ones((1,), arity.dtype)])
    if use_pallas:
        n = arity.shape[0]
        state, col_r = child_columns(arity, col_child)
        child_oh = (data_ext[:, col_child] == state[None, :]
                    ).astype(jnp.float32)                         # (m, R)
        child_p = jnp.pad(child_oh, ((0, (-m) % block_m), (0, 0)))
        sum_mat = (col_child[:, None] == jnp.arange(n)[None, :]
                   ).astype(jnp.float32)                          # (R, n)

    def body(_, ci):
        sub_c = subs[ci]
        qsig_c = qsigs[ci]
        if use_pallas:
            codes = encode_subset_codes(data_ext, sub_c, arity_ext).T  # (C, m)
            codes = jnp.pad(codes, ((0, 0), (0, (-m) % block_m)),
                            constant_values=-1)
            ti = fused_scores_pallas(codes, child_p, qsig_c, col_r[None, :],
                                     sum_mat, Q=Q, ess=ess, block_m=block_m,
                                     interpret=interpret)
        else:
            ti = fused_scores_ref(data_ext, arity, sub_c, qsig_c, luts, Q=Q,
                                  r_max=r_max)
        return None, ti

    _, TI = jax.lax.scan(body, None, chunk_ids)
    return TI


def _device_inputs(data: np.ndarray, r: np.ndarray, lay, luts, device):
    """The arrays every ``_run_device`` call of one build reads, on
    ``device``: data with the zeros column, arities, the child of each child
    one-hot column, the planned (n_chunks, chunk, ...) subsets and q_sigma,
    and the score LUTs."""
    m, n = data.shape
    data_ext = np.concatenate([data, np.zeros((m, 1), np.int32)], axis=1)
    col_child = np.repeat(np.arange(n, dtype=np.int32), r)
    shape = (lay.n_chunks, lay.chunk)
    return jax.device_put((data_ext, r, col_child,
                           lay.sub.reshape(shape + (-1,)),
                           lay.qsig.reshape(shape), luts), device)


def chunk_runner(data: np.ndarray, r: np.ndarray, lay, devices, *,
                 score: str, ess: float, bge_alpha_mu: float,
                 bge_alpha_w: float | None, use_pallas: bool, block_m: int,
                 interpret):
    """The seam both assemblies share: ``run(d, ids, Q)`` -> (len(ids),
    chunk, n) ``TI`` of the planned chunks ``ids`` (an int32 array on device
    d) of bin-count bucket Q, after the inputs every call of the build
    reads are on the devices. BDeu counts and scores (fused.py), BGe
    factors the moments (bge.py)."""
    if score == "bge":
        from .bge import bge_chunk_runner
        return bge_chunk_runner(data, lay, devices, alpha_mu=bge_alpha_mu,
                                alpha_w=bge_alpha_w, use_pallas=use_pallas,
                                interpret=interpret)
    luts = score_luts(lay.qsig, r, data.shape[0], ess)
    ins = [_device_inputs(data, r, lay, luts, dev)
           for dev in devices[:lay.n_devices]]
    r_max = int(r.max())

    def run(d, ids, Q):
        # one program per bin-count bucket (at most MAX_BUCKETS), compiled
        # once per problem shape and reused by every build
        return _run_device(*ins[d], ids, Q=Q, r_max=r_max, ess=ess,
                           use_pallas=use_pallas, block_m=block_m,
                           interpret=interpret)
    return run


def build_score_table_fused(data: np.ndarray, *, q, s: int,
                            gamma: float = 0.1, ess: float = 1.0,
                            chunk: int = 1024,
                            prior_matrix: np.ndarray | None = None,
                            prune_delta: float | None = None,
                            max_keep: int | None = None,
                            streaming: bool | None = None,
                            cache_dir: str | None = None,
                            mesh=None, devices=None,
                            use_pallas: bool | None = None,
                            block_m: int = 512,
                            interpret: bool | None = None,
                            return_info: bool = False, score: str = "bdeu",
                            bge_alpha_mu: float = 1.0,
                            bge_alpha_w: float | None = None):
    """Drop-in replacement for core/scores.build_score_table (same table, same
    PST ordering) via the fused pipeline. Returns a ScoreTable — or a
    SparseScoreTable when ``prune_delta`` is set — and, with
    ``return_info=True``, an info dict with a schema that is IDENTICAL on
    cache hit and miss (see module docstring). ``q`` is one arity for every
    variable (an int) or a sequence of one per variable.

    ``streaming`` selects the assembly when ``prune_delta`` is set: None
    (default) and True stream chunks straight into the pruned table with no
    dense (n, S) intermediate; False forces the dense build-then-prune path
    (the oracle the streaming tests compare against). ``max_keep``
    optionally caps each node's kept list at its top-``max_keep`` scores
    (streaming path only).

    ``mesh``/``devices`` pick the accelerators to shard chunks over
    (launch/mesh meshes work directly); default is the first local device.
    ``use_pallas`` defaults to True on TPU, False elsewhere (the jnp fused
    path is the fast CPU path; the kernel is the fast TPU path).

    ``score="bge"`` scores continuous ``data`` with the BGe score, its
    hyperparameters ``bge_alpha_mu`` and ``bge_alpha_w`` (None: n + 2);
    ``q`` and ``ess`` are then unused. The BDeu default refuses data that
    is not integer.
    """
    with span("preprocess.build") as build:
        if check_score(score) == "bge":
            data = bge_data(data)
            m, n = data.shape
            r = np.ones(n, np.int32)          # no bins: one bucket, in order
            bge_alpha_w = bge_prior(bge_alpha_mu, bge_alpha_w, n)[0]
            q = None
            kind = {"score": "bge", "alpha_mu": float(bge_alpha_mu),
                    "alpha_w": bge_alpha_w}
        else:
            data = as_states(data)
            m, n = data.shape
            r = arity_vector(q, n)
            check_states(data, r)
            kind = {"score": "bdeu", "arity": r.tolist(), "ess": float(ess)}
        validate_prior_matrix(prior_matrix, n)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        if streaming is None:
            streaming = prune_delta is not None
        streaming = bool(streaming) and prune_delta is not None

        S = n_parent_sets(n - 1, s)
        # "stages" is the per-stage wall-clock breakdown of preprocess_s —
        # the telemetry collector's stage rows (repro/learner) read it
        info: dict = {"cache_hit": False, "n": n, "S": S, "score": score,
                      "plan": None,
                      "preprocess_s": None, "streaming": streaming,
                      "peak_assembly_bytes": None, "bins_real": None,
                      "bins_computed": None, "stages": {}}
        log_gamma = float(np.log(gamma))
        expect = {**kind, "s": s, "m": m, "n": n, "gamma": float(gamma)}
        scoring = dict(score=score, ess=ess, bge_alpha_mu=bge_alpha_mu,
                       bge_alpha_w=bge_alpha_w)
        if devices is None:
            devices = (list(np.asarray(mesh.devices).flat) if mesh is not None
                       else [jax.devices()[0]])

        # ---- cache lookups: sparse (exact delta/max_keep) first, then dense
        key = skey = None
        sp = dense = None
        if cache_dir:
            key = cache_key(data, q=q, s=s, gamma=gamma,
                            prior_matrix=prior_matrix, **scoring)
            if prune_delta is not None:
                skey = cache_key(data, q=q, s=s, gamma=gamma,
                                 prior_matrix=prior_matrix,
                                 prune_delta=prune_delta, max_keep=max_keep,
                                 **scoring)
                hit = load_cached_sparse(cache_dir, skey, expect=expect)
                if hit is not None:
                    kept_idx, kept_ls, kept_parents, _ = hit
                    sp = SparseScoreTable.from_kept(
                        kept_idx, kept_ls, kept_parents,
                        q=q, s=s, delta=prune_delta, S=S)
            if sp is None:
                dense = load_cached_table(cache_dir, key, expect=expect)
                if dense is not None:
                    info["streaming"] = False
            info["cache_hit"] = sp is not None or dense is not None

        if info["cache_hit"]:
            pass                               # the table came from disk
        elif streaming:
            # ---- streaming assembly: chunks -> pruned table, no dense
            # intermediate
            from .streaming import build_sparse_table_streaming
            sp, sinfo = build_sparse_table_streaming(
                data, q=q, s=s, gamma=gamma, chunk=chunk,
                delta=prune_delta, prior_matrix=prior_matrix,
                max_keep=max_keep, devices=devices, use_pallas=use_pallas,
                block_m=block_m, interpret=interpret, **scoring)
            info["plan"] = {k: sinfo[k] for k in ("n_chunks", "n_devices",
                                                  "imbalance", "q_buckets")}
            for k in ("peak_assembly_bytes", "bins_real", "bins_computed"):
                info[k] = sinfo[k]
            info["stages"].update(sinfo.get("stages", {}))
        else:
            dense = _build_dense(data, r, s=s, chunk=chunk,
                                 log_gamma=log_gamma,
                                 prior_matrix=prior_matrix, devices=devices,
                                 use_pallas=use_pallas, block_m=block_m,
                                 interpret=interpret, info=info, **scoring)
    info["preprocess_s"] = build.seconds

    if info["cache_hit"]:
        info["stages"]["cache_load_s"] = build.seconds
    elif cache_dir:
        with span("preprocess.cache_store") as store:
            if sp is not None:
                store_cached_sparse(
                    cache_dir, skey, np.asarray(sp.kept_idx),
                    np.asarray(sp.kept_ls), np.asarray(sp.kept_parents),
                    metadata={**expect, "prune_delta": float(prune_delta),
                              "max_keep": max_keep, "S": S})
            else:
                table, pst, psizes = dense
                store_cached_table(cache_dir, key, np.asarray(table), pst,
                                   psizes, metadata={**expect,
                                                     "kind": "dense"})
        info["stages"]["cache_store_s"] = store.seconds
    if sp is not None:
        return (sp, info) if return_info else sp

    table, pst, psizes = dense
    st = ScoreTable(jnp.asarray(table), np.asarray(pst), np.asarray(psizes),
                    q, s)
    if prune_delta is not None:
        with span("preprocess.prune") as prune:
            st = prune_table(st, prune_delta)
        if not info["cache_hit"]:
            info["stages"]["prune_s"] = prune.seconds
    return (st, info) if return_info else st


def _build_dense(data: np.ndarray, r: np.ndarray, *, s: int, chunk: int,
                 log_gamma: float, prior_matrix, devices, use_pallas: bool,
                 block_m: int, interpret, info: dict, **scoring):
    """(table, pst, psizes) by the dense assembly: plan the column-subset
    chunks, score them on the devices, rank-gather the (n, S) table. Fills
    ``info["plan"]``, the bin counts and the plan_s/score_s/assemble_s
    stages."""
    m, n = data.shape
    with span("preprocess.plan") as plan_span:
        sub, ssz = build_pst(n, s)               # subsets of ALL n columns
        # the candidate PST, build_pst(n - 1, s): the subsets without column
        # n - 1, whose restriction keeps the size-then-lexicographic order
        keep = (sub != n - 1).all(1)
        pst, psizes = sub[keep], ssz[keep]

        # plan: column subsets, bucketed by q_sigma, chunked + cost-sharded
        # (paper §III-B)
        lay = plan_subsets(sub, r, chunk, m, len(devices))
        info["plan"] = lay.summary()
        if scoring["score"] == "bdeu":
            info.update(bins_real=lay.bins_real,
                        bins_computed=lay.bins_computed)

    with span("preprocess.score") as score_span:
        # execute: per device, one jitted scan over its chunks of a bucket
        run = chunk_runner(data, r, lay, devices, use_pallas=use_pallas,
                           block_m=block_m, interpret=interpret, **scoring)
        per_dev = []
        for d, dev in enumerate(devices[:lay.n_devices]):
            for Q, first, plan in lay.buckets:
                if d >= plan.n_devices:
                    continue
                ids = plan.padded_chunks[d] + first
                out = run(d, jax.device_put(jnp.asarray(ids), dev), Q)
                per_dev.append((ids, out))                # async dispatch

        if len(per_dev) == 1:
            # one bucket on one device: the chunks are in build_pst(n, s)
            # order already, so TI stays on the device
            TI = per_dev[0][1].reshape(-1, n)[:len(sub)]
        else:
            chunk = lay.chunk
            TI = np.zeros((lay.n_chunks * chunk, n), np.float32)
            for ids, out in per_dev:
                out = np.asarray(out)                      # (U, C, n) sync
                for u, ci in enumerate(ids):               # dupes: same data
                    TI[ci * chunk:(ci + 1) * chunk] = out[u]
            # back to build_pst(n, s) order: the rank map's subset ranks
            where = np.empty(len(sub), np.int32)
            where[lay.row[lay.row >= 0]] = np.nonzero(lay.row >= 0)[0]
            TI = jnp.asarray(TI)[jnp.asarray(where)]

    with span("preprocess.assemble") as assemble_span:
        # assemble: rank-gather + structure penalty (+ prior)
        with span("preprocess.rank_map"):
            rmap = _rank_map(n, s, pst, psizes)
        with span("preprocess.gather"):
            table = assemble_table(TI, rmap, psizes, log_gamma)
            if prior_matrix is not None:
                from ..core.priors import prior_table
                table = table + prior_table(
                    jnp.asarray(prior_matrix, jnp.float32),
                    jnp.asarray(pst), n)
    info["stages"].update(plan_s=plan_span.seconds,
                          score_s=score_span.seconds,
                          assemble_s=assemble_span.seconds)
    return table, pst, psizes
