"""Preprocessing disk cache: skip score-table construction on repeat runs.

Keyed on everything the table depends on — a SHA-256 over the data bytes,
the per-variable arity vector and the scoring hyperparameters (s, ess,
gamma, prior matrix INCLUDING its shape/dtype), and for BGe the score kind,
the float64 data bytes and alpha_mu, alpha_w in place of the states and
arities — so a second `bn_learn` invocation with identical inputs
restores the table instead of recomputing it. Storage rides
checkpoint/checkpointer: atomic publish (write-to-temp + rename) means a
killed run can never leave a readable-but-corrupt cache entry, and entries
are plain .npy + manifest.

Two entry kinds now coexist (the "always caches the DENSE table" contract
died with the streaming assembly — at n = 100, s = 4 the dense table is the
1.6 GB intermediate the streaming path exists to avoid):

* **dense** entries (``cache_key`` without ``prune_delta``): the (n, S)
  table + PST. One entry serves every --prune-delta setting, since pruning
  from dense is cheap. Written only by the dense pipeline path.
* **sparse** entries (``cache_key`` with ``prune_delta``): the pruned
  SparseScoreTable arrays (kept_idx / kept_ls / kept_parents), O(n·K) on
  disk. Written by the streaming path; ``prune_delta`` (and the optional
  ``max_keep`` cap) is part of the digest because the kept set depends on
  it. The pipeline's lookup order is sparse -> dense (prune on the fly) ->
  build.

Restores are **verified against the request**: every entry stores a manifest
(arity, s, m, n, gamma, ess, kind, ...) and ``load_cached_*`` takes an
``expect`` mapping — any mismatch (stale format, hand-mixed cache dirs,
truncated copies) is treated as a logged miss instead of being served as a
silently wrong-shape table. The checkpointer additionally digests every
array at write time (sha256 in the manifest) and re-verifies on restore, so
a truncated or bit-flipped cached .npy degrades to the same logged
miss-and-rebuild instead of feeding garbage scores into the walk — which is
exactly what the supervisor's ``cache@K`` chaos fault exercises.
"""
from __future__ import annotations

import hashlib
import logging
import os

import numpy as np

from ..checkpoint import latest_step, restore_checkpoint, save_checkpoint
from ..core.scores import (arity_vector, as_states, bge_data, bge_prior,
                           check_score)

__all__ = ["cache_key", "load_cached_table", "store_cached_table",
           "load_cached_sparse", "store_cached_sparse"]

_FORMAT = "preprocess-v3"     # bump to invalidate every cached table

logger = logging.getLogger(__name__)


def cache_key(data: np.ndarray, *, q, s: int, gamma: float, ess: float,
              prior_matrix: np.ndarray | None = None,
              prune_delta: float | None = None,
              max_keep: int | None = None, score: str = "bdeu",
              bge_alpha_mu: float = 1.0,
              bge_alpha_w: float | None = None) -> str:
    """Hex digest identifying one preprocessing problem instance. ``q`` is
    one arity or one per variable; the digest holds the arity vector, so an
    int q and the same arity for every variable share a key, and two
    different vectors never do. BDeu data must be integer states (as the
    build refuses anything else); BGe entries are keyed on the float64
    data bytes, the score kind and alpha_mu, alpha_w (``q`` and ``ess``
    unused), so no BDeu entry and no other continuous dataset shares one.

    ``prune_delta``/``max_keep`` enter the digest only when set — they key
    the PRUNED (sparse) entries, whose kept set depends on both; dense
    entries are delta-independent and keep the delta-free key."""
    h = hashlib.sha256()
    h.update(_FORMAT.encode())
    if check_score(score) == "bge":
        arr = np.ascontiguousarray(bge_data(data))
        alpha_w = bge_prior(bge_alpha_mu, bge_alpha_w, arr.shape[1])[0]
        h.update(repr(("bge", arr.shape, s, float(gamma),
                       float(bge_alpha_mu), alpha_w)).encode())
    else:
        arr = np.ascontiguousarray(as_states(data))
        h.update(repr((arr.shape, s, float(gamma), float(ess))).encode())
        h.update(arity_vector(q, arr.shape[1]).tobytes())
    h.update(arr.tobytes())
    if prior_matrix is not None:
        R = np.ascontiguousarray(np.asarray(prior_matrix, np.float32))
        # shape/dtype in the digest: R.tobytes() alone collides e.g. a
        # transposed or reshaped prior with the original (satellite bugfix)
        h.update(repr((R.shape, str(R.dtype))).encode())
        h.update(R.tobytes())
    if prune_delta is not None:
        h.update(repr(("pruned", float(prune_delta), max_keep)).encode())
    return h.hexdigest()[:24]


def _entry_dir(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, key)


def _manifest_ok(meta: dict, expect: dict | None, entry: str) -> bool:
    """True iff every expected manifest field matches. A missing or
    mismatching field means the entry was written by an older format or a
    different problem — log and treat as a miss (never serve it)."""
    if not expect:
        return True
    for field, want in expect.items():
        got = meta.get(field, None)
        if got != want:
            logger.warning(
                "preprocess cache: manifest mismatch at %s (%s: stored %r, "
                "requested %r) — ignoring entry", entry, field, got, want)
            return False
    return True


def load_cached_table(cache_dir: str, key: str,
                      expect: dict | None = None):
    """(table, pst, psizes) numpy arrays, or None on miss.

    ``expect`` maps manifest fields (arity, s, m, n, gamma, ess, ...) to the
    values the caller is requesting; a stored manifest that disagrees is a
    logged miss (satellite bugfix: never serve a wrong-shape table)."""
    entry = _entry_dir(cache_dir, key)
    if latest_step(entry) is None:
        return None
    tree_like = (np.zeros(0, np.float32), np.zeros(0, np.int32),
                 np.zeros(0, np.int32))
    try:
        (table, pst, psizes), meta = restore_checkpoint(entry, tree_like,
                                                        step=0)
    except Exception as exc:                      # corrupt / truncated entry
        logger.warning("preprocess cache: unreadable entry at %s (%s) — "
                       "ignoring", entry, exc)
        return None
    if not _manifest_ok(dict(meta or {}), expect, entry):
        return None
    return np.asarray(table), np.asarray(pst), np.asarray(psizes)


def store_cached_table(cache_dir: str, key: str, table, pst, psizes,
                       metadata: dict | None = None) -> str:
    meta = dict(metadata or {})
    meta.setdefault("kind", "dense")
    tree = (np.asarray(table, np.float32), np.asarray(pst, np.int32),
            np.asarray(psizes, np.int32))
    return save_checkpoint(_entry_dir(cache_dir, key), 0, tree,
                           metadata=meta)


def load_cached_sparse(cache_dir: str, key: str,
                       expect: dict | None = None):
    """(kept_idx, kept_ls, kept_parents, meta) or None on miss. The same
    manifest verification as :func:`load_cached_table` applies."""
    entry = _entry_dir(cache_dir, key)
    if latest_step(entry) is None:
        return None
    tree_like = (np.zeros(0, np.int32), np.zeros(0, np.float32),
                 np.zeros(0, np.int32))
    try:
        (kept_idx, kept_ls, kept_parents), meta = restore_checkpoint(
            entry, tree_like, step=0)
    except Exception as exc:
        logger.warning("preprocess cache: unreadable entry at %s (%s) — "
                       "ignoring", entry, exc)
        return None
    meta = dict(meta or {})
    if meta.get("kind") != "sparse" or not _manifest_ok(meta, expect, entry):
        return None
    return (np.asarray(kept_idx), np.asarray(kept_ls),
            np.asarray(kept_parents), meta)


def store_cached_sparse(cache_dir: str, key: str, kept_idx, kept_ls,
                        kept_parents, metadata: dict | None = None) -> str:
    meta = dict(metadata or {})
    meta["kind"] = "sparse"
    tree = (np.asarray(kept_idx, np.int32),
            np.asarray(kept_ls, np.float32),
            np.asarray(kept_parents, np.int32))
    return save_checkpoint(_entry_dir(cache_dir, key), 0, tree,
                           metadata=meta)
