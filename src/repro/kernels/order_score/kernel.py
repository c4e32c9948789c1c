"""Pallas TPU kernels for order scoring (paper §V-B/§V, Eq. 6).

Grid (S/BLK,): one program per parent-set block, and ALL window rows ride
that program as the sublane axis of a (w, BLK) tile. Consistency is evaluated
lane-parallel on the VPU; each step folds the block max + first-wins argmax
into a persistent (w, 1) accumulator — the paper's thread →
shared-memory-tree → global reduction (Fig. 7) becomes lane-reduction →
sequential-grid accumulation. The cross-device level (pmax/pmin over the
`model` axis) lives in core/sharded_scoring.py.

TPU block rules shape every layout here: the last two dims of each block are
multiples of (8, 128) or span the whole array, so

* row tiles are (w, BLK) — w is the whole array dim;
* the PST enters transposed as (s, S), so its tile is (s, BLK);
* packed consistency words are (w_pad, BLK/32): BLK must be a multiple of
  4096 on the chip (BLK/32 lanes a multiple of 128), and the bitmask
  wrappers pad w to a multiple of 8 so the bit-major slices below stay
  sublane-aligned;
* per-row scalars (node ids, positions) come in as (w, 1) columns, and
  what a loop indexes by a dynamic or per-slot scalar lives in SMEM as a
  (1, k) row, which stays a legal block when the MCMC vmaps the kernel over
  chains (the batch dim is squeezed in front of it).

Two §Perf tricks mirrored from the winning jnp scorer:

* select-instead-of-gather: candidate c maps to node c + (c ≥ i), so a
  parent's position is either pos[c] or pos[c+1]; BOTH are materialized
  node-independently ONCE per block (a loop over the n positions in SMEM —
  TPU vector memory dislikes dynamic gathers), and each row then needs only
  an elementwise select.
* the per-row work is an (s, BLK) compare/select + a BLK-lane max — exactly
  the compare/assign-only inner loop the paper argues for (§III-B).

Bitmask variants and the fused plane-patch kernel
-------------------------------------------------

`_order_score_window_bitmask_kernel` consumes PACKED consistency words
(core/order_scoring §Cached consistency bitmasks) instead of recomputing the
mask from PST gathers. `_order_score_window_bitmask_fused_kernel` goes one
step further and is the production bitmask path: the cached violation-plane
words are read into VMEM, the membership/ripple-carry patch for the ≤ w
moved window nodes is applied, the packed consistency mask derived, and the
masked max+argmax folded — ONE kernel, one VMEM pass, with the patched words
emitted as an output for adoption on accept. Contract:

    (rows (w, S), node_ids (w,), pos_old (n,), pos_new (n,),
     planes_win (w, P, S/32), cm_lo (w, S/32), cm_hi (w, S/32))
        -> (best_val (w,), best_idx (w,), patched_planes (w, P, S/32))

Rank 32j+b is bit b of word j (LSB-first). Unpacking words into rank order
in-kernel would interleave lanes, which Mosaic cannot do cheaply, so the
wrappers hand both bitmask kernels the score rows BIT-MAJOR: a
(32·w_pad, S/32) array whose row b·w_pad + r holds rank 32j+b of window row
r at column j. Bit b of a (w_pad, BLK/32) word tile then masks the aligned
slice rows [b·w_pad, (b+1)·w_pad) of the score tile directly.

Same fold and first-wins tie-break in every kernel, so the three variants
are bitwise-interchangeable.

Plane-sharding layout (core/sharded_scoring): on a mesh, the plane word
axis is S-sharded over `model` right alongside the table — word j of a
device's (n, P, shard/32) slice covers global ranks 32·(shard_start/32 + j)
…, so each device patches and scores only its own words (this kernel runs
per shard inside shard_map) and only the (w,) pmax/pmin pair crosses ICI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.order_scoring import PAD_SET

NEG_INF = -3.0e38
# parent-set block of every order-score kernel and of the padding that
# matches it: on the chip the packed words of a block (BLOCK_S / 32) must
# fill whole 128-lane rows
BLOCK_S = 4096
_INT_MAX = 2**31 - 1
_SUBLANES = 8


def _init_acc(val_ref, idx_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        val_ref[...] = jnp.full(val_ref.shape, NEG_INF, val_ref.dtype)
        idx_ref[...] = jnp.zeros(idx_ref.shape, idx_ref.dtype)


def _fold(val_ref, idx_ref, lmax, larg, block_s: int):
    """Fold this block's (w, 1) max and its lowest local rank into the
    running accumulators. Strict `>` keeps the earlier block on ties, so the
    global index is the first maximal rank — jnp.argmax's tie-break."""
    b = pl.program_id(0)
    cur = val_ref[...]
    better = lmax > cur
    val_ref[...] = jnp.where(better, lmax, cur)
    idx_ref[...] = jnp.where(better, larg + b * block_s, idx_ref[...])


def _order_score_window_kernel(pos_ref, nid_ref, mypos_ref, pst_ref,
                               rows_ref, val_ref, idx_ref, *, block_s: int,
                               n: int, s: int):
    """The one gather-path scoring body: rows r of the (w, BLK) tile belong
    to node nid[r] at position mypos[r]. The full path is the special case
    nid = arange(n); the delta path passes the w moved window nodes —
    identical tiles, fold and tie-break by construction."""
    _init_acc(val_ref, idx_ref)
    pst = pst_ref[...]                            # (s, BLK) candidate ids
    safe = jnp.maximum(pst, 0)
    nxt = jnp.minimum(safe + 1, n - 1)

    def gather(k, carry):                         # lo = pos[c], hi = pos[c+1]
        lo, hi = carry
        pk = pos_ref[0, k]
        return jnp.where(safe == k, pk, lo), jnp.where(nxt == k, pk, hi)

    zero = jnp.zeros_like(pst)
    lo, hi = jax.lax.fori_loop(0, n, gather, (zero, zero))

    nid = nid_ref[...]                            # (w, 1)
    my_pos = mypos_ref[...]                       # (w, 1)
    consistent = None
    for j in range(s):
        pj = pst[j:j + 1, :]                      # (1, BLK)
        ppos = jnp.where(pj >= nid, hi[j:j + 1, :], lo[j:j + 1, :])
        neg = pj < 0                              # empty slot or pad row
        ok = ((neg & (pj > PAD_SET))
              | (jnp.logical_not(neg) & (ppos < my_pos)))
        consistent = ok if consistent is None else consistent & ok

    masked = jnp.where(consistent, rows_ref[...], NEG_INF)   # (w, BLK)
    lmax = jnp.max(masked, axis=1, keepdims=True)
    rank = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    larg = jnp.min(jnp.where(masked == lmax, rank, _INT_MAX), axis=1,
                   keepdims=True)
    _fold(val_ref, idx_ref, lmax, larg, block_s)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def order_score_window_pallas(rows: jnp.ndarray, node_ids: jnp.ndarray,
                              pst: jnp.ndarray, pos: jnp.ndarray, *,
                              block_s: int = BLOCK_S,
                              interpret: bool = False):
    """(w, S) gathered rows, (w,) node ids, (S, s) pst, (n,) pos ->
    (best_val (w,), best_idx (w,)). S must be a multiple of block_s."""
    w, S = rows.shape
    n = pos.shape[0]
    s = pst.shape[1]
    assert S % block_s == 0, "pad S to a multiple of block_s"
    node_ids = node_ids.astype(jnp.int32)
    kernel = functools.partial(_order_score_window_kernel, block_s=block_s,
                               n=n, s=s)
    val, idx = pl.pallas_call(
        kernel,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # pos
            pl.BlockSpec((w, 1), lambda b: (0, 0)),             # node ids
            pl.BlockSpec((w, 1), lambda b: (0, 0)),             # their pos
            pl.BlockSpec((s, block_s), lambda b: (0, b)),       # PST^T tile
            pl.BlockSpec((w, block_s), lambda b: (0, b)),       # row tile
        ],
        out_specs=[
            pl.BlockSpec((w, 1), lambda b: (0, 0)),             # running max
            pl.BlockSpec((w, 1), lambda b: (0, 0)),             # running arg
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w, 1), jnp.float32),
            jax.ShapeDtypeStruct((w, 1), jnp.int32),
        ],
        interpret=interpret,
    )(pos.astype(jnp.int32)[None, :], node_ids[:, None],
      pos[node_ids][:, None].astype(jnp.int32), pst.T, rows)
    return val[:, 0], idx[:, 0]


def _pad_rows(x: jnp.ndarray, w_pad: int, value) -> jnp.ndarray:
    return jnp.pad(x, ((0, w_pad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


def _bit_major(rows: jnp.ndarray, w_pad: int) -> jnp.ndarray:
    """(w, S) score rows -> (32·w_pad, S/32): row b·w_pad + r, column j
    holds rank 32j+b of row r (the packed-word bit order, see module
    docstring); padded rows score NEG_INF."""
    w, S = rows.shape
    rows = _pad_rows(rows, w_pad, NEG_INF)
    return (rows.reshape(w_pad, S // 32, 32).transpose(2, 0, 1)
            .reshape(32 * w_pad, S // 32))


def _fold_words(words, rows_ref, val_ref, idx_ref, *, block_s: int,
                w_pad: int):
    """Masked max + first-wins argmax of a bit-major score tile under packed
    consistency words (w_pad, BLK/32), folded into the accumulators."""
    shape = words.shape
    masked = []
    for bit in range(32):
        on = (words & jnp.uint32(1 << bit)) != 0
        masked.append(jnp.where(on, rows_ref[bit * w_pad:(bit + 1) * w_pad, :],
                                NEG_INF))
    lmax = jnp.max(functools.reduce(jnp.maximum, masked), axis=1,
                   keepdims=True)
    j32 = jax.lax.broadcasted_iota(jnp.int32, shape, 1) * 32
    first = functools.reduce(jnp.minimum, [
        jnp.where(m == lmax, j32 + bit, _INT_MAX)
        for bit, m in enumerate(masked)])
    larg = jnp.min(first, axis=1, keepdims=True)
    _fold(val_ref, idx_ref, lmax, larg, block_s)


def _order_score_window_bitmask_kernel(mask_ref, rows_ref, val_ref, idx_ref,
                                       *, block_s: int, w_pad: int):
    """Bitmask-consuming variant of the window kernel: consistency arrives as
    PACKED uint32 words (core/order_scoring §Cached consistency bitmasks)
    streamed through VMEM alongside the score tile — (BLK/32) words per row
    instead of the PST tile and its position selects. The per-row work
    collapses to bit test + select + fold: no gathers, no per-node compares —
    the paper's compare/assign-only inner loop (§III-B) taken one step
    further."""
    _init_acc(val_ref, idx_ref)
    _fold_words(mask_ref[...], rows_ref, val_ref, idx_ref, block_s=block_s,
                w_pad=w_pad)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def order_score_window_bitmask_pallas(rows: jnp.ndarray,
                                      mask_words: jnp.ndarray, *,
                                      block_s: int = BLOCK_S,
                                      interpret: bool = False):
    """(w, S) gathered rows + (w, S/32) packed consistency words ->
    (best_val (w,), best_idx (w,)). S must be a multiple of block_s and
    block_s a multiple of 32. The PST never enters the kernel — masks were
    patched on the host side of the cache (update_window_planes)."""
    w, S = rows.shape
    assert S % block_s == 0, "pad S to a multiple of block_s"
    assert block_s % 32 == 0, "packed words need block_s % 32 == 0"
    bw = block_s // 32
    w_pad = -(-w // _SUBLANES) * _SUBLANES
    kernel = functools.partial(_order_score_window_bitmask_kernel,
                               block_s=block_s, w_pad=w_pad)
    val, idx = pl.pallas_call(
        kernel,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((w_pad, bw), lambda b: (0, b)),        # mask words
            pl.BlockSpec((32 * w_pad, bw), lambda b: (0, b)),   # bit-major
        ],
        out_specs=[
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),         # running max
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),         # running arg
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((w_pad, 1), jnp.int32),
        ],
        interpret=interpret,
    )(_pad_rows(mask_words, w_pad, 0), _bit_major(rows, w_pad))
    return val[:w, 0], idx[:w, 0]


def _order_score_window_bitmask_fused_kernel(
        nid_s, po_s, pn_s, nid_ref, po_ref, pn_ref, planes_ref, cmlo_ref,
        cmhi_ref, rows_ref, val_ref, idx_ref, new_planes_ref, *,
        block_s: int, w: int, w_pad: int, n_planes: int):
    """ONE kernel for the whole bitmask-cached proposal rescore: read the
    OLD violation-plane words, apply the membership/ripple-carry patch for
    the ≤ w moved window nodes, derive the packed consistency mask, and fold
    the masked max+argmax — all in the same VMEM pass over the (BLK) tile.
    The patched words are emitted as a third output so the sampler can adopt
    them on accept. Replaces the XLA word-op patch (`update_window_planes`)
    + separate scoring kernel (`_order_score_window_bitmask_kernel`) pair:
    the plane words are read ONCE instead of written to HBM and re-read.

    All window rows i patch together along the sublane axis: for each moved
    node x (a static loop — w is small), row i takes x's candidate row as
    cmhi[x] when node x > node i and cmlo[x] otherwise — the same
    select-instead-of-gather trick as the position kernel. x's ids and
    positions are SMEM scalars; row i's are (w_pad, 1) columns."""
    _init_acc(val_ref, idx_ref)
    nid_i = nid_ref[...]                          # (w_pad, 1)
    po_i = po_ref[...]
    pn_i = pn_ref[...]
    planes = [planes_ref[p] for p in range(n_planes)]   # (w_pad, BLK/32) each
    zero = jnp.zeros_like(planes[0])
    for x in range(w):
        was = po_s[0, x] > po_i                   # x after row i's node
        now = pn_s[0, x] > pn_i                   # ... after the move
        row = jnp.where(nid_s[0, x] > nid_i, cmhi_ref[x:x + 1, :],
                        cmlo_ref[x:x + 1, :])
        add = jnp.where(now & jnp.logical_not(was), row, zero)
        sub = jnp.where(was & jnp.logical_not(now), row, zero)
        carry = add                               # ripple-carry +1
        for p in range(n_planes):
            planes[p], carry = planes[p] ^ carry, planes[p] & carry
        borrow = sub                              # ripple-borrow -1
        for p in range(n_planes):
            planes[p], borrow = planes[p] ^ borrow, (~planes[p]) & borrow
    for p in range(n_planes):
        new_planes_ref[p] = planes[p]
    words = ~functools.reduce(jnp.bitwise_or, planes)   # violations == 0
    _fold_words(words, rows_ref, val_ref, idx_ref, block_s=block_s,
                w_pad=w_pad)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def order_score_window_bitmask_fused_pallas(
        rows: jnp.ndarray, node_ids: jnp.ndarray, pos_old: jnp.ndarray,
        pos_new: jnp.ndarray, planes_win: jnp.ndarray, cm_lo: jnp.ndarray,
        cm_hi: jnp.ndarray, *, block_s: int = BLOCK_S,
        interpret: bool = False):
    """Fused plane-patch + masked-argmax (see the fused kernel docstring).

    rows: (w, S) gathered table rows for the window nodes; node_ids: (w,);
    pos_old/pos_new: (n,) previous/proposed orders; planes_win: (w, P, S/32)
    the CACHED plane rows under pos_old; cm_lo/cm_hi: (w, S/32) membership
    rows cm[clip(node)] / cm[clip(node-1)] (the two possible candidate rows
    of each window node). Returns (best_val (w,), best_idx (w,),
    patched_planes (w, P, S/32)). S must be a multiple of block_s, block_s a
    multiple of 32. Grid (S/BLK,): ALL w window rows ride one kernel
    invocation, exactly like the gather-window kernel."""
    w, S = rows.shape
    n_planes, W = planes_win.shape[1], planes_win.shape[2]
    assert S % block_s == 0, "pad S to a multiple of block_s"
    assert block_s % 32 == 0, "packed words need block_s % 32 == 0"
    assert W * 32 == S, "planes words must cover S"
    bw = block_s // 32
    w_pad = -(-w // _SUBLANES) * _SUBLANES
    node_ids = node_ids.astype(jnp.int32)
    po = pos_old[node_ids].astype(jnp.int32)
    pn = pos_new[node_ids].astype(jnp.int32)
    nid_c, po_c, pn_c = (_pad_rows(v[:, None], w_pad, 0)
                         for v in (node_ids, po, pn))

    kernel = functools.partial(_order_score_window_bitmask_fused_kernel,
                               block_s=block_s, w=w, w_pad=w_pad,
                               n_planes=n_planes)
    val, idx, new_planes = pl.pallas_call(
        kernel,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # node ids
            pl.BlockSpec(memory_space=pltpu.SMEM),              # their pos_old
            pl.BlockSpec(memory_space=pltpu.SMEM),              # their pos_new
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),         # ... columns
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),
            pl.BlockSpec((n_planes, w_pad, bw), lambda b: (0, 0, b)),  # planes
            pl.BlockSpec((w_pad, bw), lambda b: (0, b)),        # cm (x < i)
            pl.BlockSpec((w_pad, bw), lambda b: (0, b)),        # cm (x > i)
            pl.BlockSpec((32 * w_pad, bw), lambda b: (0, b)),   # bit-major
        ],
        out_specs=[
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),         # running max
            pl.BlockSpec((w_pad, 1), lambda b: (0, 0)),         # running arg
            pl.BlockSpec((n_planes, w_pad, bw), lambda b: (0, 0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((w_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_planes, w_pad, W), jnp.uint32),
        ],
        interpret=interpret,
    )(node_ids[None, :], po[None, :], pn[None, :], nid_c, po_c, pn_c,
      _pad_rows(planes_win, w_pad, 0).transpose(1, 0, 2),
      _pad_rows(cm_lo, w_pad, 0), _pad_rows(cm_hi, w_pad, 0),
      _bit_major(rows, w_pad))
    return val[:w, 0], idx[:w, 0], new_planes[:, :w].transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def order_score_pallas(table: jnp.ndarray, pst: jnp.ndarray, pos: jnp.ndarray,
                       *, block_s: int = BLOCK_S,
                       interpret: bool = False):
    """(n, S) table, (S, s) pst, (n,) pos -> (best_val (n,), best_idx (n,)).

    S must be a multiple of block_s (pad table with NEG_INF, pst with -1).
    The full score IS the windowed kernel with node_ids = arange(n) — one
    kernel body, so full and delta can never diverge on masking/tie-break.
    """
    n = table.shape[0]
    return order_score_window_pallas(table, jnp.arange(n, dtype=jnp.int32),
                                     pst, pos, block_s=block_s,
                                     interpret=interpret)
