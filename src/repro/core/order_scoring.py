"""Order scoring (paper Eq. 6): score(≺) = Σ_i max_{π_i consistent with ≺} ls(i, π_i).

This is the hot loop the paper puts on the GPU. Three interchangeable paths:

* :func:`score_order_ref` — pure-jnp oracle (chunked over S);
* kernels/order_score — the Pallas TPU kernel (same contract);
* :func:`score_order_sharded` — the multi-device version: the parent-set axis is
  sharded over the ``model`` mesh axis and reduced with pmax + index-resolved
  pmin — the paper's thread→block→global reduction tree promoted to
  lane→block→device→ICI (DESIGN.md §2).

Contract: given table (n, S), pst (S, s), psizes (S,), pos (n,) (pos[v] =
position of node v in ≺), return (total_score, best_idx (n,), best_ls (n,))
where best_idx[i] is the PST index of the argmax parent set — i.e. the best
graph consistent with the order, produced *during* scoring (no postprocessing,
paper §III-B).

Incremental (delta) scoring
---------------------------

:func:`score_order_delta` is the per-iteration fast path of the MCMC sampler.
A bounded-window move (core/mcmc.py: adjacent/bounded swap, single-node
insertion, window reversal) permutes only the positions in ``[lo, lo+w-1]``.
A node whose position is OUTSIDE that window keeps its exact predecessor set
(the whole window lies on one side of it), so its consistency masks — and
therefore its cached (best_ls, best_idx) — are unchanged. Only the ≤ w nodes
occupying the window need rescoring: O(w·S) work instead of O(n·S).

Delta contract: given the proposal's NEW ``pos``, the PREVIOUS order's
``(prev_ls, prev_idx)`` and the window start ``lo`` (clipped internally to
``[0, n-window]`` — clipping only widens the recompute set, which is safe
because rescoring an unaffected node reproduces its cached value bitwise),
return the same ``(total, best_idx, best_ls)`` triple, *exactly* equal to a
full rescore: the window nodes go through the same `_score_nodes_blocked`
inner loop (same blocks, same first-wins tie-break) and the total is
``best_ls.sum()`` (same reduction order as the full path).

Crossover heuristic: the delta path wins only while ``window`` is small
relative to n; :func:`delta_window` returns 0 (meaning "use the full blocked
path") when ``window < 2`` or ``window > DELTA_CROSSOVER · n``. The decision
is static (window and n are trace-time constants), so no lax.cond is paid —
and under vmap over chains no dead full-rescore branch is materialized.

Cached consistency bitmasks (the accelerator-resident fast path)
----------------------------------------------------------------

Even the delta path above recomputes its window masks from scratch: per PST
block it gathers a ``(blk, s)`` slab of parent positions and compares against
the child's position — O(w·S·s) gather+compare work per proposal. That mask
is *almost entirely reusable*: a bounded-window move changes, for a window
node i, only the precedence of the ≤ w other window nodes (everything outside
the window keeps its side of i — see the delta contract). So we cache the
mask and patch it with word ops:

* **membership planes** (:func:`build_membership_planes`, order-independent,
  built ONCE): ``cm[c]`` is a packed (S/32,)-word bitmask with bit t set iff
  candidate c appears in parent set t — LSB-first within each uint32 word,
  word j covering PST ranks [32j, 32j+31].
* **violation-count planes** (:func:`build_violation_planes`, carried in
  ``ChainState.mask_planes``): per node, ``ceil(log2(s+1))`` packed bit-plane
  words holding, per parent set, the COUNT of parents that do not precede the
  node (0 ⇔ consistent). Counts — not booleans — because an OR of violators
  is not invertible, while a counter supports exact ±1 updates via a packed
  ripple-carry (:func:`_planes_add`/:func:`_planes_sub`).

Per proposal, :func:`score_order_delta_bitmask` patches the ≤ w window nodes'
planes with one plane-add/-sub per (node, moved-parent) pair — O(w²·S/32)
word ops — and derives the boolean mask as ``~(V₀|V₁|…)``, replacing the
O(w·S·s) gather+compare entirely. The masked max+argmax then runs over the
same blocks with the same first-wins tie-break as `_score_nodes_blocked`, so
the result is bitwise-identical to a full `score_order_blocked` rescore.
It returns only the window's patched rows; on accept the sampler writes
them back into the chain cache in place (core/mcmc.py), preserving the
invariant that ``mask_planes`` always describes the CURRENT order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = jnp.float32(-3.0e38)

__all__ = ["consistent_mask", "score_order_ref", "score_order_chunked",
           "score_order_blocked", "score_order_sum", "score_order_sum_cached",
           "score_order_sum_delta", "score_order_delta",
           "score_order_delta_bitmask", "score_order_pruned",
           "score_order_pruned_delta", "delta_window", "inverse_permutation",
           "window_nodes", "splice_window", "DELTA_CROSSOVER", "NEG_INF",
           "PAD_SET",
           "MASK_WORD_BITS", "mask_plane_count", "pack_mask_words",
           "unpack_mask_words", "build_membership_planes",
           "build_violation_planes", "planes_consistent_words",
           "update_window_planes"]

DELTA_CROSSOVER = 0.5   # delta pays off while window ≤ this fraction of n

# PST pad-ROW sentinel. A real parent-set row uses -1 for its unused trailing
# slots (the empty set is all -1), which every consistency check treats as
# vacuously satisfied. Rows appended purely to pad S to a block/shard multiple
# must NOT inherit that meaning — a -1-padded row is indistinguishable from
# the (always-consistent) empty set and scores as a real candidate, leaving
# only the NEG_INF table pad between a padded rank and best_idx. Padding rows
# with PAD_SET instead makes them STRUCTURALLY inconsistent in every path
# (gather, bitmask, kernel): best_idx can never name a rank ≥ S no matter how
# the table was padded.
PAD_SET = -2


def delta_window(n: int, window: int, crossover: float = DELTA_CROSSOVER) -> int:
    """Static crossover decision: the window to use for the delta path, or 0
    to mean "rescore everything with the blocked full path"."""
    if window < 2 or window > max(2, int(n * crossover)):
        return 0
    return min(window, n)


def inverse_permutation(pos: jnp.ndarray) -> jnp.ndarray:
    """order (n,) with order[p] = node at position p (inverse of pos)."""
    n = pos.shape[0]
    return jnp.zeros((n,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))


def window_nodes(pos: jnp.ndarray, lo: jnp.ndarray, window: int) -> jnp.ndarray:
    """(window,) ids of the nodes occupying positions [lo, lo+window-1],
    with lo clipped into [0, n-window] (clipping only widens the recompute
    set — safe, see the delta contract above)."""
    n = pos.shape[0]
    lo = jnp.clip(lo.astype(jnp.int32), 0, n - window)
    return jax.lax.dynamic_slice_in_dim(inverse_permutation(pos), lo, window)


def splice_window(prev_ls: jnp.ndarray, prev_idx: jnp.ndarray,
                  win: jnp.ndarray, ls_w: jnp.ndarray, idx_w: jnp.ndarray):
    """Scatter freshly-rescored window results into the cached per-node
    arrays and return the (total, best_idx, best_ls) contract triple. The
    ONE splice used by every delta path (blocked, kernel, sharded), so the
    bitwise delta≡full guarantee lives in a single place."""
    best_ls = prev_ls.at[win].set(ls_w)
    best_idx = prev_idx.at[win].set(idx_w)
    return best_ls.sum(), best_idx, best_ls


def consistent_mask(pst: jnp.ndarray, node: jnp.ndarray,
                    pos: jnp.ndarray) -> jnp.ndarray:
    """(C,) bool — parent set consistent with order: all parents precede node.

    pst: (C, s) candidate indices (-1 = empty slot, PAD_SET = pad row —
    structurally inconsistent); node: scalar; pos: (n,).
    """
    pnode = pst + (pst >= node)                       # (C, s) node ids
    ppos = pos[jnp.clip(pnode, 0)]                    # (C, s)
    ok = jnp.where(pst < 0, pst > PAD_SET, ppos < pos[node])
    return jnp.all(ok, axis=-1)


@functools.partial(jax.jit, static_argnames=())
def score_order_ref(table: jnp.ndarray, pst: jnp.ndarray,
                    pos: jnp.ndarray):
    """Unchunked oracle. table: (n, S); pst: (S, s); pos: (n,)."""
    n, S = table.shape

    def per_node(i, row):
        mask = consistent_mask(pst, i, pos)
        masked = jnp.where(mask, row, NEG_INF)
        idx = jnp.argmax(masked)
        return masked[idx], idx

    best_ls, best_idx = jax.vmap(per_node)(jnp.arange(n), table)
    return best_ls.sum(), best_idx.astype(jnp.int32), best_ls


@functools.partial(jax.jit, static_argnames=())
def score_order_sum(table: jnp.ndarray, pst: jnp.ndarray, pos: jnp.ndarray):
    """The BASELINE the paper argues against (§III-B): Linderman et al.'s
    sum-based order score  Σ_i log Σ_{π consistent} exp ls(i, π).

    Needs exp/log per parent set (the paper's first objection), does NOT
    produce the best graph (a postprocessing pass — one max-scorer call — is
    required, the paper's third objection), and the best graph may not be
    consistent with the best order (second objection; demonstrated in
    benchmarks/baseline_sum.py). Same contract as score_order_ref, but
    best_idx/best_ls come from the embedded max pass (the postprocessing)."""
    n, S = table.shape

    def per_node(i, row):
        mask = consistent_mask(pst, i, pos)
        masked = jnp.where(mask, row, NEG_INF)
        total = jax.scipy.special.logsumexp(masked)
        idx = jnp.argmax(masked)
        return total, masked[idx], idx

    tot, best_ls, best_idx = jax.vmap(per_node)(jnp.arange(n), table)
    return tot.sum(), best_idx.astype(jnp.int32), best_ls


def _sum_nodes(rows: jnp.ndarray, node_ids: jnp.ndarray, pst: jnp.ndarray,
               pos: jnp.ndarray):
    """Per-node logsumexp over consistent sets + embedded argmax, for an
    ARBITRARY node subset — the single inner loop shared by the cached-full
    and the delta sum paths (the same sharing that makes the max paths'
    delta ≡ full guarantee bitwise)."""
    def per_node(i, row):
        masked = jnp.where(consistent_mask(pst, i, pos), row, NEG_INF)
        return jax.scipy.special.logsumexp(masked), jnp.argmax(masked)

    lse, idx = jax.vmap(per_node)(node_ids, rows)
    return lse, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=())
def score_order_sum_cached(table: jnp.ndarray, pst: jnp.ndarray,
                           pos: jnp.ndarray):
    """score_order_sum restated so the sampler's per-node cache works for it:
    the third output is the PER-NODE LOGSUMEXP vector (it sums to the score,
    which is what ChainState.cur_ls must satisfy for splice_window to keep
    the running total exact) instead of the max-pass best_ls. best_idx stays
    the embedded argmax (the postprocessing pass, paper §III-B objection 3)."""
    n = pos.shape[0]
    lse, idx = _sum_nodes(table, jnp.arange(n), pst, pos)
    return lse.sum(), idx, lse


@functools.partial(jax.jit, static_argnames=("window",))
def score_order_sum_delta(table: jnp.ndarray, pst: jnp.ndarray,
                          pos: jnp.ndarray, prev_lse: jnp.ndarray,
                          prev_idx: jnp.ndarray, lo: jnp.ndarray, *,
                          window: int):
    """Incremental companion of score_order_sum_cached: a bounded-window
    move leaves every out-of-window node's consistency mask — hence its
    logsumexp — untouched, so only the window nodes' running logsumexp needs
    recomputing, spliced through the same splice_window as every max-path
    delta. O(window·S) per move; makes benchmarks/baseline_sum.py a
    like-for-like incremental-vs-incremental comparison."""
    n = pos.shape[0]
    w = min(window, n)
    win = window_nodes(pos, lo, w)
    lse_w, idx_w = _sum_nodes(table[win], win, pst, pos)
    return splice_window(prev_lse, prev_idx, win, lse_w, idx_w)


def _score_nodes_blocked(rows: jnp.ndarray, node_ids: jnp.ndarray,
                         pst: jnp.ndarray, pos: jnp.ndarray, *, block: int):
    """Block-outer/node-inner masked max+argmax for an ARBITRARY node subset.

    rows: (k, S) score-table rows for node_ids; node_ids: (k,) actual node
    ids (the candidate→node shift depends on them); pos: (n,) the full
    position vector. Returns (best_ls (k,), best_idx (k,)).

    This is the single inner loop shared by the full blocked path
    (node_ids = arange(n)) and the delta path (node_ids = the moved window),
    so both produce bitwise-identical values and identical first-block /
    first-index tie-breaking.
    """
    k, S = rows.shape
    n = pos.shape[0]
    nb = S // block
    # Candidate c maps to node c + (c >= i), so a parent's position is either
    # pos[c] or pos[c+1]: gather BOTH once per block (node-independent) and
    # pick per node with an elementwise select — no per-(node, block) gather.
    pos_ext = jnp.concatenate([pos, jnp.zeros((1,), pos.dtype)])

    def body(carry, b):
        bmax, barg = carry                                # (k,), (k,)
        tbl = jax.lax.dynamic_slice_in_dim(rows, b * block, block, axis=1)
        psl = jax.lax.dynamic_slice_in_dim(pst, b * block, block, axis=0)
        safe = jnp.clip(psl, 0)
        ppos_lo = pos_ext[safe]                           # (blk, s) c -> c
        ppos_hi = pos_ext[jnp.minimum(safe + 1, n)]       # (blk, s) c -> c+1

        def per_node(i, row):
            ppos = jnp.where(psl >= i, ppos_hi, ppos_lo)
            ok = jnp.where(psl < 0, psl > PAD_SET, ppos < pos[i])
            masked = jnp.where(jnp.all(ok, axis=-1), row, NEG_INF)
            a = jnp.argmax(masked)
            return masked[a], a

        v, a = jax.vmap(per_node)(node_ids, tbl)          # (k,), (k,)
        better = v > bmax
        return (jnp.where(better, v, bmax),
                jnp.where(better, a + b * block, barg)), None

    (best_ls, best_idx), _ = jax.lax.scan(
        body, (jnp.full((k,), NEG_INF), jnp.zeros((k,), jnp.int32)),
        jnp.arange(nb))
    return best_ls, best_idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block",))
def score_order_blocked(table: jnp.ndarray, pst: jnp.ndarray,
                        pos: jnp.ndarray, *, block: int = 4096):
    """Same contract as score_order_chunked, restructured block-OUTER /
    node-INNER (§Perf hillclimb #3): the PST block is loaded once and the
    consistency masks for ALL n nodes are computed against it while it is
    hot, so HBM traffic drops from n·(S·4 + S·s·4) to n·S·4 + S·s·4 —
    ~(s+1)/(1+s/n)× less. This is exactly the Pallas kernel's revisiting-grid
    order (grid (S/blk, n), PST block index depends on dim 0 only)."""
    n, S = table.shape
    assert S % block == 0, "pad S to a multiple of block"
    best_ls, best_idx = _score_nodes_blocked(table, jnp.arange(n), pst, pos,
                                             block=block)
    return best_ls.sum(), best_idx, best_ls


@functools.partial(jax.jit, static_argnames=("window", "block"))
def score_order_delta(table: jnp.ndarray, pst: jnp.ndarray, pos: jnp.ndarray,
                      prev_ls: jnp.ndarray, prev_idx: jnp.ndarray,
                      lo: jnp.ndarray, *, window: int, block: int = 4096):
    """Incremental rescore after a bounded-window move (module docstring).

    pos is the PROPOSED order; (prev_ls, prev_idx) are the per-node caches of
    the order it was proposed from; lo is the first position the move could
    have touched. Recomputes only the `window` nodes occupying positions
    [lo, lo+window-1] under the new order — O(window·S) vs O(n·S) — and
    returns (total, best_idx (n,), best_ls (n,)) exactly equal to
    score_order_blocked(table, pst, pos, block=block)."""
    n, S = table.shape
    assert S % block == 0, "pad S to a multiple of block"
    w = min(window, n)
    win = window_nodes(pos, lo, w)                        # (w,) node ids
    rows = table[win]                                     # (w, S)
    ls_w, idx_w = _score_nodes_blocked(rows, win, pst, pos, block=block)
    return splice_window(prev_ls, prev_idx, win, ls_w, idx_w)


# --------------------------------------------------------------------------
# Cached consistency bitmasks (module docstring §Cached consistency bitmasks)
# --------------------------------------------------------------------------

MASK_WORD_BITS = 32


def mask_plane_count(s: int) -> int:
    """Bit planes needed to count 0..s violating parents per set."""
    return max(1, int(s).bit_length())


def pack_mask_words(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., S) bool/int -> (..., S/32) uint32, LSB-first (bit b of word j is
    PST rank 32j+b). S must be a multiple of 32."""
    S = bits.shape[-1]
    assert S % MASK_WORD_BITS == 0, "pad S to a multiple of 32"
    w = jnp.left_shift(jnp.uint32(1),
                       jnp.arange(MASK_WORD_BITS, dtype=jnp.uint32))
    grouped = bits.reshape(bits.shape[:-1] + (-1, MASK_WORD_BITS))
    return jnp.sum(jnp.where(grouped != 0, w, jnp.uint32(0)), axis=-1,
                   dtype=jnp.uint32)


def unpack_mask_words(words: jnp.ndarray) -> jnp.ndarray:
    """(..., W) uint32 -> (..., 32W) bool — inverse of pack_mask_words."""
    shifts = jnp.arange(MASK_WORD_BITS, dtype=jnp.uint32)
    bits = jnp.right_shift(words[..., None], shifts) & jnp.uint32(1)
    return (bits != 0).reshape(words.shape[:-1] + (-1,))


def build_membership_planes(pst, n: int) -> jnp.ndarray:
    """(n-1, S/32) uint32: cm[c] bit t ⇔ candidate c ∈ parent set t.

    Order-independent — built once per table (host loop over the s PST
    columns, O(S·s)); -1 padding never sets a bit. Membership lives in the
    shared CANDIDATE space: child i reads node x's plane at cm[x - (x > i)].
    """
    pst_np = np.asarray(pst)
    S, s = pst_np.shape
    assert S % MASK_WORD_BITS == 0, "pad S to a multiple of 32"
    mem = np.zeros((max(n - 1, 1), S), dtype=bool)
    for col in range(s):
        v = pst_np[:, col]
        ok = v >= 0
        mem[v[ok], np.nonzero(ok)[0]] = True
    w = (np.uint64(1) << np.arange(MASK_WORD_BITS, dtype=np.uint64))
    grouped = mem.reshape(mem.shape[0], -1, MASK_WORD_BITS).astype(np.uint64)
    words = (grouped * w).sum(axis=-1).astype(np.uint32)
    return jnp.asarray(words)


@jax.jit
def build_violation_planes(pst: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """(n, P, S/32) uint32 violation-count bit planes for order `pos` — the
    from-scratch builder (init_chain / checkpoint-restore / the oracle the
    incremental updates are tested against). O(n·S·s), one full-rescore's
    worth of mask work, paid once."""
    n = pos.shape[0]
    P = mask_plane_count(pst.shape[1])

    def per_node(i):
        pnode = pst + (pst >= i)
        ppos = pos[jnp.clip(pnode, 0)]
        # PAD_SET entries count as permanent violations: pad rows carry count
        # s forever (membership planes never touch them), so padded ranks are
        # structurally inconsistent in the bitmask path too
        viol = jnp.sum(((pst >= 0) & (ppos >= pos[i])) | (pst <= PAD_SET),
                       axis=-1, dtype=jnp.int32)               # (S,)
        planes = [pack_mask_words((viol >> p) & 1) for p in range(P)]
        return jnp.stack(planes)                               # (P, S/32)

    # lax.map keeps the peak temporary at O(S) instead of O(n·S)
    return jax.lax.map(per_node, jnp.arange(n, dtype=jnp.int32))


def _planes_add(planes: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """Add 1 to the packed counters at the positions set in `bits` —
    ripple-carry over the P planes. planes: (P, W); bits: (W,)."""
    out, carry = [], bits
    for p in range(planes.shape[0]):
        v = planes[p]
        out.append(v ^ carry)
        carry = v & carry
    return jnp.stack(out)


def _planes_sub(planes: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """Subtract 1 at the positions set in `bits` (ripple borrow)."""
    out, borrow = [], bits
    for p in range(planes.shape[0]):
        v = planes[p]
        out.append(v ^ borrow)
        borrow = (~v) & borrow
    return jnp.stack(out)


def planes_consistent_words(planes: jnp.ndarray) -> jnp.ndarray:
    """(..., P, W) count planes -> (..., W) packed consistency mask:
    bit set ⇔ violation count is zero ⇔ parent set consistent."""
    acc = planes[..., 0, :]
    for p in range(1, planes.shape[-2]):
        acc = acc | planes[..., p, :]
    return ~acc


def update_window_planes(cm: jnp.ndarray, pos_old: jnp.ndarray,
                         pos_new: jnp.ndarray, win: jnp.ndarray,
                         planes_win: jnp.ndarray) -> jnp.ndarray:
    """Patch the window nodes' violation planes from order pos_old to
    pos_new. Exactness rests on the delta contract: for a window node i, the
    only parents whose side of i can change are the other window nodes, so
    one plane-add/-sub per (i, x) pair — O(w²·S/32) word ops — reproduces
    build_violation_planes(pst, pos_new)[win] bitwise.

    cm: (n-1, S/32) membership planes; win: (w,) node ids occupying the
    window under BOTH orders (moves permute within the window);
    planes_win: (w, P, S/32) the cached planes rows for `win` under pos_old.
    """
    n_cand = cm.shape[0]

    def per_node(i, planes_i):
        pi_old, pi_new = pos_old[i], pos_new[i]

        def body(planes_i, x):
            was = pos_old[x] > pi_old
            now = pos_new[x] > pi_new
            cand = jnp.clip(x - (x > i).astype(x.dtype), 0, n_cand - 1)
            row = cm[cand]                       # (S/32,) membership of x
            zero = jnp.zeros_like(row)
            # x == i gives was == now, so both updates degrade to no-ops
            planes_i = _planes_add(planes_i, jnp.where(now & ~was, row, zero))
            planes_i = _planes_sub(planes_i, jnp.where(was & ~now, row, zero))
            return planes_i, None

        planes_i, _ = jax.lax.scan(body, planes_i, win)
        return planes_i

    return jax.vmap(per_node)(win, planes_win)


def _score_nodes_blocked_bitmask(rows: jnp.ndarray, mask_words: jnp.ndarray,
                                 *, block: int):
    """`_score_nodes_blocked` with the consistency mask read from packed
    words instead of recomputed from (blk, s) position gathers. Same block
    order, same first-wins fold — bitwise-identical given an identical mask.

    rows: (k, S); mask_words: (k, S/32). Returns (best_ls (k,), best_idx (k,)).
    """
    k, S = rows.shape
    assert S % block == 0 and block % MASK_WORD_BITS == 0
    nb = S // block
    bw = block // MASK_WORD_BITS

    def body(carry, b):
        bmax, barg = carry
        tbl = jax.lax.dynamic_slice_in_dim(rows, b * block, block, axis=1)
        wrd = jax.lax.dynamic_slice_in_dim(mask_words, b * bw, bw, axis=1)
        ok = unpack_mask_words(wrd)                           # (k, blk)
        masked = jnp.where(ok, tbl, NEG_INF)
        a = jnp.argmax(masked, axis=1)
        v = jnp.take_along_axis(masked, a[:, None], axis=1)[:, 0]
        better = v > bmax
        return (jnp.where(better, v, bmax),
                jnp.where(better, a.astype(jnp.int32) + b * block, barg)), None

    (best_ls, best_idx), _ = jax.lax.scan(
        body, (jnp.full((k,), NEG_INF), jnp.zeros((k,), jnp.int32)),
        jnp.arange(nb))
    return best_ls, best_idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "block"))
def score_order_delta_bitmask(table: jnp.ndarray, cm: jnp.ndarray,
                              pos: jnp.ndarray, prev_ls: jnp.ndarray,
                              prev_idx: jnp.ndarray, lo: jnp.ndarray,
                              pos_old: jnp.ndarray, planes: jnp.ndarray, *,
                              window: int, block: int = 4096):
    """Bitmask-cached incremental rescore (module docstring): patch the
    window nodes' cached violation planes with word ops, score them against
    the packed mask, splice. No per-proposal (blk, s) position gathers — the
    PST is not even an argument. Returns the usual (total, best_idx (n,),
    best_ls (n,)) contract triple PLUS the window's node ids (w,) and their
    patched (w, P, S/32) plane rows, which the sampler writes back on
    accept."""
    n, S = table.shape
    assert S % block == 0, "pad S to a multiple of block"
    w = min(window, n)
    win = window_nodes(pos, lo, w)                            # (w,) node ids
    new_planes_win = update_window_planes(cm, pos_old, pos, win, planes[win])
    words = planes_consistent_words(new_planes_win)           # (w, S/32)
    ls_w, idx_w = _score_nodes_blocked_bitmask(table[win], words, block=block)
    tot, best_idx, best_ls = splice_window(prev_ls, prev_idx, win, ls_w, idx_w)
    return tot, best_idx, best_ls, win, new_planes_win


def _score_nodes_pruned(kept_ls: jnp.ndarray, kept_parents: jnp.ndarray,
                        kept_idx: jnp.ndarray, node_ids: jnp.ndarray,
                        pos: jnp.ndarray):
    """Masked max+argmax over per-node PRUNED candidate lists (the sparse
    hot path — O(K) per node instead of O(S)).

    kept_ls: (k, K) scores (NEG_INF pad); kept_parents: (k, K, s) parent NODE
    ids (-1 pad — already node-mapped at build, unlike the shared PST);
    kept_idx: (k, K) global PST ranks (the contract's best_idx space).
    Rows align with node_ids. Returns (best_ls (k,), best_idx (k,)).
    """
    def per_node(i, ls_row, par_row, idx_row):
        ppos = pos[jnp.clip(par_row, 0)]                     # (K, s)
        ok = jnp.where(par_row < 0, True, ppos < pos[i])
        masked = jnp.where(jnp.all(ok, axis=-1), ls_row, NEG_INF)
        a = jnp.argmax(masked)                               # first-wins ties
        return masked[a], idx_row[a]

    best_ls, best_idx = jax.vmap(per_node)(node_ids, kept_ls, kept_parents,
                                           kept_idx)
    return best_ls, best_idx.astype(jnp.int32)


@jax.jit
def score_order_pruned(kept_ls: jnp.ndarray, kept_parents: jnp.ndarray,
                       kept_idx: jnp.ndarray, pos: jnp.ndarray):
    """score_order over a preprocess.SparseScoreTable's packed arrays — the
    same (score, best_idx, best_ls) contract as score_order_blocked, with
    best_idx in the global PST rank space.

    Exactness: equals the dense scorer whenever each node's dense-consistent
    argmax survived pruning (always true for delta = +inf; the empty set is
    always kept so the result is defined for every order). See
    preprocess/sparse.py for the guarantee statement and its tests."""
    n = pos.shape[0]
    best_ls, best_idx = _score_nodes_pruned(kept_ls, kept_parents, kept_idx,
                                            jnp.arange(n, dtype=jnp.int32),
                                            pos)
    return best_ls.sum(), best_idx, best_ls


@functools.partial(jax.jit, static_argnames=("window",))
def score_order_pruned_delta(kept_ls: jnp.ndarray, kept_parents: jnp.ndarray,
                             kept_idx: jnp.ndarray, pos: jnp.ndarray,
                             prev_ls: jnp.ndarray, prev_idx: jnp.ndarray,
                             lo: jnp.ndarray, *, window: int):
    """Incremental companion of score_order_pruned: O(window*K) per move,
    spliced through the same splice_window as every other delta path so
    delta == full holds bitwise within the pruned representation."""
    n = pos.shape[0]
    w = min(window, n)
    win = window_nodes(pos, lo, w)
    ls_w, idx_w = _score_nodes_pruned(kept_ls[win], kept_parents[win],
                                      kept_idx[win], win, pos)
    return splice_window(prev_ls, prev_idx, win, ls_w, idx_w)


@functools.partial(jax.jit, static_argnames=("block",))
def score_order_chunked(table: jnp.ndarray, pst: jnp.ndarray,
                        pos: jnp.ndarray, *, block: int = 4096):
    """Same contract, streaming S in blocks (bounded working set; mirrors the
    kernel's VMEM tiling). S must be padded to a multiple of `block` by the
    caller (pad table with NEG_INF)."""
    n, S = table.shape
    assert S % block == 0, "pad S to a multiple of block"
    nb = S // block

    def per_node(i, row):
        def body(carry, b):
            bmax, barg = carry
            sl = jax.lax.dynamic_slice_in_dim(row, b * block, block)
            psl = jax.lax.dynamic_slice_in_dim(pst, b * block, block, axis=0)
            mask = consistent_mask(psl, i, pos)
            masked = jnp.where(mask, sl, NEG_INF)
            a = jnp.argmax(masked)
            v = masked[a]
            better = v > bmax
            return (jnp.where(better, v, bmax),
                    jnp.where(better, a + b * block, barg)), None

        (bmax, barg), _ = jax.lax.scan(body, (NEG_INF, jnp.int32(0)),
                                       jnp.arange(nb))
        return bmax, barg

    best_ls, best_idx = jax.vmap(per_node)(jnp.arange(n), table)
    return best_ls.sum(), best_idx.astype(jnp.int32), best_ls
