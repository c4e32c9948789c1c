"""The dense assembly on the TPU: an order-preserving compaction of ``TI``.

Row i of the dense (n, S) table is column i of the (C, n) per-subset
output ``TI`` (subsets of all n columns in ``build_pst(n, s)`` order) with
the subsets that contain column i dropped: the candidate->column
relabelling ``c + (c >= i)`` is monotone, so the kept subsets come in the
order of node i's parent sets. The point gather ``TI[rank_map, node]``
(``pipeline.assemble_table``) reads one scattered float per entry, which
the TPU serves at a small share of its HBM bandwidth; this module moves
whole rows instead.

A kept entry's shift is the number of dropped subsets before it
(:func:`drop_shifts`). The compaction runs K = ``compact_passes(C, S)``
passes, low bit first: pass k moves every kept entry whose shift has bit k
set 2**k to the left, carrying its shift. Two kept entries a < b sit at
least ``shift(b) - shift(a) + 1`` apart, and after the bits below k are
spent they still do, so no pass makes them meet. Values are only selected,
never combined, so the table is bitwise the gather's (-inf and signed zeros
included).

The kernel takes eight node rows at a time, ``TI``'s transpose in its own
layout (nodes on sublanes, subsets on lanes, so a move of 2**k >= 128 is a
whole number of vregs): it reads the rows' values and shifts from HBM
once, runs all K passes in VMEM, adds the structure penalty and writes
the rows' first S entries once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["drop_shifts", "compact_passes", "compact_fits", "compact_table"]

SUB, LANES = 8, 128
STEP = 8 * LANES                # lanes one step of a pass's loop covers
VMEM_BUDGET = 100 * 2 ** 20     # of the 128 MiB of VMEM on a v5e


def _groups(n: int) -> int:
    """Node rows in whole groups of eight."""
    return -(-n // SUB) * SUB


def _cols(C: int) -> int:
    """Lanes that hold C subsets in whole loop steps."""
    return -(-C // STEP) * STEP


def compact_passes(C: int, S: int) -> int:
    """Passes of the compaction: the bits of the largest shift, C - S."""
    return (C - S).bit_length()


def _reach(C: int, S: int) -> int:
    """Lanes a pass reads past the last step: its longest move, >= 128."""
    return max(LANES, 1 << (compact_passes(C, S) - 1))


def _vmem_bytes(C: int, S: int) -> int:
    """VMEM of one group: values and shifts with their reach, and the
    output block."""
    return SUB * 4 * (2 * (_cols(C) + _reach(C, S)) + S)


def compact_fits(C: int, S: int) -> bool:
    """Whether a group of eight rows of C subsets fits the VMEM budget (to
    about C = 1M, n = 71 at s = 4)."""
    return _vmem_bytes(C, S) <= VMEM_BUDGET


@functools.partial(jax.jit, static_argnames=("n",))
def drop_shifts(n: int, sub) -> jax.Array:
    """(n rounded up to 8, C rounded up to 1024) int32 on the device: for
    node i and column subset c of the (C, s) ``build_pst(n, s)`` table
    (padded with -1), -1 where c contains column i (no parent set of node
    i), else how many subsets before c do: how far the compaction moves
    entry c of row i to the left. The padding reads -1."""
    C = sub.shape[0]
    node = jnp.arange(_groups(n), dtype=jnp.int32)[:, None]
    drop = sub[:, 0] == node
    for j in range(1, sub.shape[1]):
        drop = drop | (sub[:, j] == node)
    drop = drop.astype(jnp.int32)
    shifts = jnp.where((drop == 1) | (node >= n), -1,
                       jnp.cumsum(drop, axis=1) - drop)
    return jnp.pad(shifts, ((0, 0), (0, _cols(C) - C)), constant_values=-1)


def _compact_kernel(vals_hbm, sh_hbm, out_ref, v_s, s_s, sem, *,
                    cols: int, passes: int, S: int, firsts: tuple,
                    log_gamma: float):
    """One group of eight node rows: copy values and shifts into VMEM
    (shifts past the rows read -1, so nothing moves in from there), run
    every pass in place, low bit first, and write the first S entries plus
    the structure penalty out. In place is safe: a step writes its lanes
    after reading them and lanes beyond, which no earlier step of the pass
    wrote."""
    rows = pl.ds(pl.multiple_of(pl.program_id(0) * SUB, SUB), SUB)
    copies = [pltpu.make_async_copy(src.at[rows, :], dst.at[:, pl.ds(0, cols)],
                                    sem.at[j])
              for j, (src, dst) in enumerate(((vals_hbm, v_s), (sh_hbm, s_s)))]
    for c in copies:
        c.start()
    reach = s_s.shape[1] - cols
    s_s[:, pl.ds(cols, reach)] = jnp.full((SUB, reach), -1, jnp.int32)
    for c in copies:
        c.wait()

    for k in range(passes):
        d = 1 << k

        def step(t, carry, k=k, d=d):
            at = pl.multiple_of(t * STEP, STEP)
            cur_v = v_s[:, pl.ds(at, STEP)]
            cur_s = s_s[:, pl.ds(at, STEP)]
            if d < LANES:          # within a vreg: rotate one vreg more
                src_v = pltpu.roll(v_s[:, pl.ds(at, STEP + LANES)],
                                   STEP + LANES - d, 1)[:, :STEP]
                src_s = pltpu.roll(s_s[:, pl.ds(at, STEP + LANES)],
                                   STEP + LANES - d, 1)[:, :STEP]
            else:                  # whole vregs
                src_v = v_s[:, pl.ds(at + d, STEP)]
                src_s = s_s[:, pl.ds(at + d, STEP)]
            move = (src_s >= 0) & ((src_s >> k) & 1 == 1)
            stay = (cur_s >= 0) & ((cur_s >> k) & 1 == 0)
            v_s[:, pl.ds(at, STEP)] = jnp.where(move, src_v, cur_v)
            s_s[:, pl.ds(at, STEP)] = jnp.where(
                move, src_s, jnp.where(stay, cur_s, -1))
            return carry
        jax.lax.fori_loop(0, cols // STEP, step, 0)

    def write(at, width):
        # |pi| ln(gamma) from the entry's place: the PST is size-ascending
        # and parent sets of size k start at firsts[k - 1]
        lane = at + jax.lax.broadcasted_iota(jnp.int32, (SUB, width), 1)
        size = sum(((lane >= f).astype(jnp.int32) for f in firsts),
                   jnp.zeros((SUB, width), jnp.int32))
        out_ref[:, pl.ds(at, width)] = (
            size.astype(jnp.float32) * jnp.float32(log_gamma)
            + v_s[:, pl.ds(at, width)])

    if S // STEP:
        def body(t, carry):
            write(pl.multiple_of(t * STEP, STEP), STEP)
            return carry
        jax.lax.fori_loop(0, S // STEP, body, 0)
    if S % STEP:
        write(S - S % STEP, S % STEP)


@functools.partial(jax.jit,
                   static_argnames=("S", "firsts", "log_gamma", "interpret"))
def _compact(TI, shifts, *, S: int, firsts: tuple, log_gamma: float,
             interpret: bool):
    C, n = TI.shape
    groups, cols = shifts.shape
    passes = compact_passes(C, S)
    with jax.named_scope("assemble"):
        vals = jnp.pad(TI.T, ((0, groups - n), (0, cols - C)))
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            functools.partial(_compact_kernel, cols=cols, passes=passes, S=S,
                              firsts=firsts, log_gamma=log_gamma),
            grid=(groups // SUB,),
            in_specs=[hbm, hbm],
            out_specs=pl.BlockSpec((SUB, S), lambda g: (g, 0),
                                   pipeline_mode=pl.Buffered(1)),
            out_shape=jax.ShapeDtypeStruct((n, S), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((SUB, cols + _reach(C, S)), jnp.float32),
                pltpu.VMEM((SUB, cols + _reach(C, S)), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_bytes(C, S) + 4 * 2 ** 20),
            name="compact_rows",
            interpret=interpret,
        )(vals, shifts)


def compact_table(TI, shifts: jax.Array, psizes: np.ndarray,
                  log_gamma: float, *, interpret: bool | None = None):
    """(n, S) f32 table ``|pi| ln(gamma) + rows``: row i holds the entries
    of ``TI[:, i]`` (``TI`` (C, n)) that ``shifts`` (:func:`drop_shifts`)
    keeps, in order; ``psizes`` (S,) the parent-set sizes, ascending as
    the PST orders them.
    Bitwise ``pipeline.assemble_table`` of the same ``TI``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    psizes = np.asarray(psizes)
    firsts = tuple(int(f) for f in np.searchsorted(
        psizes, np.arange(1, int(psizes[-1]) + 1)))
    return _compact(jnp.asarray(TI), shifts, S=len(psizes), firsts=firsts,
                    log_gamma=float(log_gamma), interpret=interpret)
