"""Multi-device order scoring: the paper's two-level GPU reduction (threads →
shared-memory tree, Fig. 7) promoted one level up to devices → ICI.

The parent-set axis S is sharded over the ``model`` mesh axis (the paper's
"assign h blocks per node, split P_{π_i} over threads" becomes "split the
score-table columns over devices"); each device computes a local masked
max+argmax over its shard (VPU work — on TPU via the Pallas kernel, here via
the chunked oracle), then:

  global max   = pmax  over 'model'              (the paper's tree reduction)
  global argmax= pmin  over 'model' of (idx where local==global else +inf)
                 — deterministic tie-break, exactly the role of the
                 thread-id tracking in the paper's Fig. 7.

MCMC chains ride the ``data``/``pod`` axes unchanged (independent chains =
pure DP), so the whole sampler is one shard_map program on the production
mesh — scoring is TP, chains are DP, and the only cross-device traffic per
iteration is the (n,)-vector pmax/pmin pair — or (window,) on the delta path.

Sharded consistency planes (the mesh-native bitmask engine)
-----------------------------------------------------------

The bitmask-cached delta engine (core/order_scoring §Cached consistency
bitmasks) is S-sharded right along with the table: each device holds its own
``(n, P, shard/32)`` slice of ``ChainState.mask_planes`` (word j of the local
slice covers GLOBAL PST ranks [32·(my·shard/32 + j), …] — the word axis is
just the rank axis divided by 32, so the table's shard boundaries are plane
word boundaries as long as the shard size is a multiple of 32, which
:func:`_shard_block` guarantees). Everything about the cache is
device-local:

* **build** — :func:`make_sharded_planes_fn` runs ``build_violation_planes``
  per shard inside the shard_map region (init / checkpoint restore), each
  device packing only its own S-shard's words;
* **patch** — ``update_window_planes`` runs on the local words (membership
  planes are sharded ``P(None, model)`` like the table, candidate axis
  replicated);
* **score** — the masked max+argmax folds over the local words
  (``_score_nodes_blocked_bitmask`` here, the fused plane-patch + masked
  argmax Pallas kernel ``order_score_window_bitmask_fused_pallas`` on TPU),
  and only then does the usual (w,) pmax/pmin pair cross ICI.

The planes themselves NEVER cross ICI: the per-iteration collective payload
of the bitmask delta path is identical to the plain delta path's — two
(window,) vectors per chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mcmc import BitmaskDelta
from .order_scoring import (MASK_WORD_BITS, NEG_INF, PAD_SET,
                            _score_nodes_blocked,
                            _score_nodes_blocked_bitmask,
                            build_membership_planes, build_violation_planes,
                            delta_window, planes_consistent_words,
                            score_order_blocked, score_order_chunked,
                            splice_window, update_window_planes, window_nodes)

__all__ = ["score_order_sharded", "make_sharded_score_fn",
           "make_sharded_delta_fn", "make_sharded_bitmask_fns",
           "make_sharded_planes_fn", "pad_table", "sharded_chain_step"]

INT_MAX = jnp.int32(2**31 - 1)


def pad_table(table, pst, mult: int):
    """Pad S to a multiple of `mult` (device count × block). Scores pad with
    NEG_INF; PST rows pad with the PAD_SET sentinel (-2), which every
    consistency path treats as structurally inconsistent — a padded rank can
    never reach best_idx, independent of the table pad value."""
    S = table.shape[1]
    pad = (-S) % mult
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=NEG_INF)
        pst = jnp.pad(pst, ((0, pad), (0, 0)), constant_values=PAD_SET)
    return table, pst


def _shard_block(S: int, tp: int, block: int) -> int:
    """Shared block rounding for every sharded maker: bounded by the shard
    size, floored at one packed word (32 ranks) and rounded up to the word
    multiple so the packed consistency-mask layout tiles the shard exactly."""
    block = min(block, max((S + tp - 1) // tp, MASK_WORD_BITS))
    return block + (-block) % MASK_WORD_BITS


def _local_score(table_l, pst_l, pos, offset, block: int,
                 blocked: bool = True):
    """Masked max+argmax over this device's S-shard. Returns (n,), (n,) with
    argmax as a GLOBAL PST index (offset by the shard's start).

    blocked=True uses the block-outer/node-inner scorer (§Perf hillclimb:
    the PST block is read once for all nodes instead of once per node)."""
    fn = score_order_blocked if blocked else score_order_chunked
    _, idx_l, ls_l = fn(table_l, pst_l, pos,
                        block=min(block, table_l.shape[1]))
    return ls_l, idx_l + offset


def score_order_sharded(table, pst, pos, mesh, *, axis: str = "model",
                        block: int = 4096):
    """Same contract as score_order_chunked, S sharded over `axis`.

    table: (n, S) already padded so S % mesh.shape[axis] == 0.
    Under jit with the table sharded P(None, axis) this is one shard_map
    region; the collective payload is 2 × (n,) per call.
    """
    n, S = table.shape
    tp = mesh.shape[axis]
    shard = S // tp
    in_specs = (P(None, axis), P(axis, None), P(None))
    out_specs = (P(), P(None), P(None))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def go(table_l, pst_l, pos):
        my = jax.lax.axis_index(axis)
        ls_l, idx_l = _local_score(table_l, pst_l, pos, my * shard, block)
        ls_g = jax.lax.pmax(ls_l, axis)                       # Fig. 7, level 2
        cand = jnp.where(ls_l >= ls_g, idx_l, INT_MAX)
        idx_g = jax.lax.pmin(cand, axis)                      # id resolution
        return ls_g.sum(), idx_g, ls_g

    return go(table, pst, pos)


def _pmax_pmin(ls_l, idx_l, axis: str):
    """The Fig. 7 level-2 reduction: global max + deterministic index
    resolution (smallest global rank among the tied shards)."""
    ls_g = jax.lax.pmax(ls_l, axis)
    cand = jnp.where(ls_l >= ls_g, idx_l, INT_MAX)
    idx_g = jax.lax.pmin(cand, axis)
    return ls_g, idx_g


def _local_delta(table_l, pst_l, pos, lo, offset, *, window: int, block: int,
                 axis: str):
    """Device-local window rescore + the same pmax/pmin reduction, but on
    (window,)-vectors instead of (n,) — the delta path's collective payload
    shrinks with the window too. Returns (win_nodes, ls_g, idx_g)."""
    win = window_nodes(pos, lo, window)
    ls_l, idx_l = _score_nodes_blocked(table_l[win], win, pst_l, pos,
                                       block=min(block, table_l.shape[1]))
    ls_g, idx_g = _pmax_pmin(ls_l, idx_l + offset, axis)
    return win, ls_g, idx_g


def _local_bitmask_delta(table_l, cm_l, pos, lo, offset, pos_old, planes_l, *,
                         window: int, block: int, axis: str,
                         use_kernel: bool = False,
                         interpret: bool | None = None):
    """Device-local bitmask-cached window rescore: patch the local plane
    words, fold the masked max over the local shard, reduce the (w,) pair
    over ICI. planes_l: (n, P, shard/32) — this device's slice of the chain's
    cached violation planes; the window's patched rows (w, P, shard/32) are
    returned for the sampler to write back on accept and never leave the
    device. Returns (win, ls_g, idx_g, planes_win).

    use_kernel=True routes patch+score through the ONE fused Pallas kernel
    (order_score_window_bitmask_fused_pallas); the default runs the same
    word ops in XLA (`update_window_planes` + `_score_nodes_blocked_bitmask`)
    — bitwise-identical by construction."""
    win = window_nodes(pos, lo, window)
    rows = table_l[win]
    planes_win = planes_l[win]
    if use_kernel:
        from ..kernels.order_score.kernel import \
            order_score_window_bitmask_fused_pallas

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        n_cand = cm_l.shape[0]
        cm_lo = cm_l[jnp.clip(win, 0, n_cand - 1)]
        cm_hi = cm_l[jnp.clip(win - 1, 0, n_cand - 1)]
        ls_l, idx_l, new_win = order_score_window_bitmask_fused_pallas(
            rows, win, pos_old, pos, planes_win, cm_lo, cm_hi,
            block_s=min(block, rows.shape[1]), interpret=interpret)
    else:
        new_win = update_window_planes(cm_l, pos_old, pos, win, planes_win)
        words = planes_consistent_words(new_win)
        ls_l, idx_l = _score_nodes_blocked_bitmask(
            rows, words, block=min(block, rows.shape[1]))
    ls_g, idx_g = _pmax_pmin(ls_l, idx_l + offset, axis)
    return win, ls_g, idx_g, new_win


def make_sharded_planes_fn(pst, mesh, *, axis: str = "model",
                           stacked: bool = True):
    """Violation-plane builder that runs PER SHARD inside the shard_map
    region — each device packs only its own S-shard's words, so neither the
    build (init / checkpoint restore) nor any later patch moves plane words
    across ICI.

    pst: the PADDED (S, s) table (same padding as the scoring closures).
    stacked=True: (C, n) chain-stacked positions -> (C, n, P, S/32) planes
    sharded (chains over the data axes, words over `axis`); stacked=False:
    one (n,) position -> (n, P, S/32) (init_chain's planes_fn contract)."""
    dax = tuple(a for a in mesh.axis_names if a != axis)
    pos_spec = P(dax, None) if stacked else P(None)
    out_spec = (P(dax, None, None, axis) if stacked
                else P(None, None, axis))

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(pos_spec, P(axis, None)),
                       out_specs=out_spec, check_vma=False)
    def build(pos, pst_l):
        if stacked:
            return jax.vmap(lambda p: build_violation_planes(pst_l, p))(pos)
        return build_violation_planes(pst_l, pos)

    return lambda pos: build(pos, pst)


def sharded_chain_step(states, table, pst, mesh, cm=None, *,
                       axis: str = "model", block: int = 4096,
                       window: int = 0, use_kernel: bool = False):
    """One MCMC iteration for ALL chains on the production mesh, as a single
    shard_map program: chains are DP over the pod/data axes, the score table
    is TP over `axis`. Per iteration the cross-device traffic is the (n,)
    pmax/pmin pair per chain — or (window,) on the delta path.

    states: ChainState with a leading chains dim C divisible by the data-axes
    extent. table must be padded (pad_table) to axis_size × block.
    window ≥ 2 (and ≤ DELTA_CROSSOVER·n, else it degrades to the full path)
    enables bounded-window proposals + incremental O(window·S/tp) rescoring
    per device.

    cm (the (n-1, S/32) membership planes, padded like the table) switches
    the delta path to the sharded bitmask engine: states.mask_planes must
    then carry the (C, n, P, S/32) cached violation planes (seeded by
    :func:`make_sharded_planes_fn`), S-sharded over `axis` alongside the
    table — each device patches and scores its own plane words and only the
    (w,) pmax/pmin pair crosses ICI. Without cm (or with the zero-size
    placeholder in states.mask_planes) the delta path recomputes window
    masks from per-shard position gathers.
    """
    from .mcmc import mcmc_step

    n, S = table.shape
    tp = mesh.shape[axis]
    shard = S // tp
    w = delta_window(n, window)
    mask = cm is not None and bool(w) and states.mask_planes.ndim == 4
    dax = tuple(a for a in mesh.axis_names if a != axis)
    st_specs = jax.tree.map(lambda _: P(dax), states)
    if mask:
        st_specs = st_specs._replace(mask_planes=P(dax, None, None, axis))
    in_specs = (st_specs, P(None, axis), P(axis, None))
    operands = (states, table, pst)
    if mask:
        in_specs += (P(None, axis),)
        operands += (cm,)
    out_specs = st_specs

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def go(states_l, table_l, pst_l, *rest):
        my = jax.lax.axis_index(axis)

        def score_fn(pos):
            ls_l, idx_l = _local_score(table_l, pst_l, pos, my * shard, block)
            ls_g, idx_g = _pmax_pmin(ls_l, idx_l, axis)
            return ls_g.sum(), idx_g, ls_g

        delta_fn = None
        if mask:
            cm_l = rest[0]

            def bitmask_fn(pos, lo, prev_ls, prev_idx, pos_old, planes_l):
                win, ls_g, idx_g, planes_win = _local_bitmask_delta(
                    table_l, cm_l, pos, lo, my * shard, pos_old, planes_l,
                    window=w, block=block, axis=axis, use_kernel=use_kernel)
                tot, bi, bl = splice_window(prev_ls, prev_idx, win, ls_g,
                                            idx_g)
                return tot, bi, bl, win, planes_win

            delta_fn = BitmaskDelta(bitmask_fn)
        elif w:
            def delta_fn(pos, lo, prev_ls, prev_idx):
                win, ls_g, idx_g = _local_delta(
                    table_l, pst_l, pos, lo, my * shard, window=w,
                    block=block, axis=axis)
                return splice_window(prev_ls, prev_idx, win, ls_g, idx_g)

        return jax.vmap(lambda s: mcmc_step(s, score_fn, delta_fn, w))(states_l)

    # jit even when called eagerly: an eager shard_map reports the zero-size
    # mask_planes placeholder as replicated against its P(data) out_spec and
    # fails; under jit it is partitioned like every other chain leaf (an
    # outer jit, as in the segment runner, inlines this one)
    return jax.jit(go)(*operands)


def make_sharded_score_fn(table, pst, mesh, *, axis: str = "model",
                          block: int = 4096):
    """Closure with the (n,)-contract used by core.mcmc — the drop-in
    multi-device replacement for make_score_fn."""
    tp = mesh.shape[axis]
    block = _shard_block(table.shape[1], tp, block)
    table, pst = pad_table(table, pst, tp * block)

    def fn(pos):
        return score_order_sharded(table, pst, pos, mesh, axis=axis,
                                   block=block)
    return fn


def make_sharded_delta_fn(table, pst, mesh, *, window: int,
                          axis: str = "model", block: int = 4096):
    """Delta-path companion of make_sharded_score_fn (same padding rules, so
    the two are bitwise-consistent). Returns a DeltaFn with the core.mcmc
    contract, or None when the crossover heuristic rejects the window. This
    is the mask-RECOMPUTE variant; :func:`make_sharded_bitmask_fns` is the
    cached-planes engine."""
    n = table.shape[0]
    w = delta_window(n, window)
    if not w:
        return None
    tp = mesh.shape[axis]
    block = _shard_block(table.shape[1], tp, block)
    table, pst = pad_table(table, pst, tp * block)
    shard = table.shape[1] // tp
    in_specs = (P(None, axis), P(axis, None), P(None), P(), P(None), P(None))
    out_specs = (P(), P(None), P(None))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def go(table_l, pst_l, pos, lo, prev_ls, prev_idx):
        my = jax.lax.axis_index(axis)
        win, ls_g, idx_g = _local_delta(table_l, pst_l, pos, lo, my * shard,
                                        window=w, block=block, axis=axis)
        return splice_window(prev_ls, prev_idx, win, ls_g, idx_g)

    def fn(pos, lo, prev_ls, prev_idx):
        return go(table, pst, pos, lo, prev_ls, prev_idx)
    return fn


def make_sharded_bitmask_fns(table, pst, mesh, *, window: int,
                             axis: str = "model", block: int = 4096,
                             use_kernel: bool = False):
    """(delta_fn, planes_fn) for the mesh-native bitmask engine, padded with
    the same rules as make_sharded_score_fn so the three closures are
    bitwise-consistent:

    * delta_fn: a :class:`BitmaskDelta` with the extended per-chain contract
      ``fn(new_pos, lo, prev_ls, prev_idx, old_pos, planes) -> (score,
      best_idx, best_ls, win, planes_win)`` where planes is the chain's
      (n, P, S/32) cache and planes_win the window's (w, P, S/32) patched
      rows, both S-sharded over `axis` (win is replicated) — plane words
      stay on their device; the collective payload is the (w,) pmax/pmin
      pair.
    * planes_fn: (n,) pos -> freshly-built sharded planes (init_chain's
      ``planes_fn`` contract / checkpoint-restore rebuild), built per shard
      inside shard_map.

    Returns (None, None) when the crossover heuristic rejects the window."""
    n = table.shape[0]
    w = delta_window(n, window)
    if not w:
        return None, None
    tp = mesh.shape[axis]
    block = _shard_block(table.shape[1], tp, block)
    table, pst = pad_table(table, pst, tp * block)
    shard = table.shape[1] // tp
    cm = build_membership_planes(pst, n)
    planes_fn = make_sharded_planes_fn(pst, mesh, axis=axis, stacked=False)

    in_specs = (P(None, axis), P(None, axis), P(None), P(), P(None), P(None),
                P(None), P(None, None, axis))
    out_specs = (P(), P(None), P(None), P(None), P(None, None, axis))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def go(table_l, cm_l, pos, lo, prev_ls, prev_idx, pos_old, planes_l):
        my = jax.lax.axis_index(axis)
        win, ls_g, idx_g, planes_win = _local_bitmask_delta(
            table_l, cm_l, pos, lo, my * shard, pos_old, planes_l,
            window=w, block=block, axis=axis, use_kernel=use_kernel)
        tot, bi, bl = splice_window(prev_ls, prev_idx, win, ls_g, idx_g)
        return tot, bi, bl, win, planes_win

    def fn(pos, lo, prev_ls, prev_idx, pos_old, planes):
        return go(table, cm, pos, lo, prev_ls, prev_idx, pos_old, planes)

    return BitmaskDelta(fn), planes_fn
