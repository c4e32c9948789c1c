"""Public jit'd wrapper: pads, dispatches kernel vs oracle, returns the
(score, best_idx, best_ls) contract used by core.mcmc."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (BLOCK_S, NEG_INF, order_score_pallas,
                     order_score_window_bitmask_fused_pallas,
                     order_score_window_pallas)
from .ref import order_score_ref

__all__ = ["order_score", "order_score_delta", "order_score_delta_bitmask",
           "pad_for_kernel"]


def pad_for_kernel(table: jnp.ndarray, pst: jnp.ndarray, block_s: int):
    """Pad S to a multiple of block_s: scores with NEG_INF (never win) AND
    parent sets with the PAD_SET row sentinel (-2, structurally inconsistent
    in every consistency check) — padded ranks can't reach best_idx even if a
    caller pads the table with something other than NEG_INF."""
    from ...core.order_scoring import PAD_SET

    S = table.shape[1]
    pad = (-S) % block_s
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=NEG_INF)
        pst = jnp.pad(pst, ((0, pad), (0, 0)), constant_values=PAD_SET)
    return table, pst


@functools.partial(jax.jit,
                   static_argnames=("block_s", "use_pallas", "interpret"))
def order_score(table: jnp.ndarray, pst: jnp.ndarray, pos: jnp.ndarray, *,
                block_s: int = BLOCK_S, use_pallas: bool = True,
                interpret: bool | None = None):
    """Score an order (paper Eq. 6). Returns (score, best_idx (n,), best_ls (n,))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas:
        tbl, ps = pad_for_kernel(table, pst, block_s)
        val, idx = order_score_pallas(tbl, ps, pos, block_s=block_s,
                                      interpret=interpret)
    else:
        val, idx = order_score_ref(table, pst, pos)
    return val.sum(), idx, val


@functools.partial(jax.jit, static_argnames=("window", "block_s", "use_pallas",
                                             "interpret"))
def order_score_delta(table: jnp.ndarray, pst: jnp.ndarray, pos: jnp.ndarray,
                      prev_ls: jnp.ndarray, prev_idx: jnp.ndarray,
                      lo: jnp.ndarray, *, window: int,
                      block_s: int = BLOCK_S,
                      use_pallas: bool = True, interpret: bool | None = None):
    """Kernel-path incremental rescore (core/order_scoring.py docstring):
    recomputes only the `window` nodes at positions [lo, lo+window-1] of the
    proposed order via the windowed Pallas kernel, splices them into the
    cached (prev_ls, prev_idx). Same (score, best_idx, best_ls) contract —
    bitwise-consistent with the full `order_score` path (same tiles, same
    fold, same tie-break)."""
    from ...core.order_scoring import splice_window, window_nodes

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = table.shape[0]
    w = min(window, n)
    tbl, ps = pad_for_kernel(table, pst, block_s)
    win = window_nodes(pos, lo, w)
    rows = tbl[win]
    if use_pallas:
        val, idx = order_score_window_pallas(rows, win, ps, pos,
                                             block_s=block_s,
                                             interpret=interpret)
    else:
        from ...core.order_scoring import _score_nodes_blocked
        val, idx = _score_nodes_blocked(rows, win, ps, pos,
                                        block=min(block_s, tbl.shape[1]))
    return splice_window(prev_ls, prev_idx, win, val, idx)


@functools.partial(jax.jit, static_argnames=("window", "block_s", "use_pallas",
                                             "interpret"))
def order_score_delta_bitmask(table: jnp.ndarray, cm: jnp.ndarray,
                              pos: jnp.ndarray, prev_ls: jnp.ndarray,
                              prev_idx: jnp.ndarray, lo: jnp.ndarray,
                              pos_old: jnp.ndarray, planes: jnp.ndarray, *,
                              window: int, block_s: int = BLOCK_S,
                              use_pallas: bool = True,
                              interpret: bool | None = None):
    """Kernel-path bitmask-cached rescore, now ONE fused Pallas kernel
    (order_score_window_bitmask_fused_pallas): the cached violation-plane
    words are read into VMEM once, patched with the membership/ripple-carry
    word ops, and the masked max+argmax folds in the same pass — the XLA
    word-op patch + separate scoring-kernel round trip through HBM is gone,
    and the PST leaves the per-iteration hot path entirely. table must
    already be padded to a block_s multiple (pad_for_kernel), with cm/planes
    built on the padded shape. Same extended contract as core's
    score_order_delta_bitmask: (total, best_idx, best_ls, win, planes_win)."""
    from ...core.order_scoring import (_score_nodes_blocked_bitmask,
                                      planes_consistent_words, splice_window,
                                      update_window_planes, window_nodes)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, S = table.shape
    assert S % block_s == 0, "pad table with pad_for_kernel first"
    w = min(window, n)
    win = window_nodes(pos, lo, w)
    rows = table[win]
    if use_pallas:
        n_cand = cm.shape[0]
        cm_lo = cm[jnp.clip(win, 0, n_cand - 1)]        # row when x < i
        cm_hi = cm[jnp.clip(win - 1, 0, n_cand - 1)]    # row when x > i
        val, idx, new_planes_win = order_score_window_bitmask_fused_pallas(
            rows, win, pos_old, pos, planes[win], cm_lo, cm_hi,
            block_s=block_s, interpret=interpret)
    else:
        new_planes_win = update_window_planes(cm, pos_old, pos, win,
                                              planes[win])
        words = planes_consistent_words(new_planes_win)
        val, idx = _score_nodes_blocked_bitmask(rows, words,
                                                block=min(block_s, S))
    tot, best_idx, best_ls = splice_window(prev_ls, prev_idx, win, val, idx)
    return tot, best_idx, best_ls, win, new_planes_win
