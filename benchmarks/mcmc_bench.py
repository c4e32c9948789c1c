"""Per-iteration MCMC cost: PR-1 gather-delta engine vs the bitmask-cached
engine (ISSUE 3 tentpole gate: >= 2x at n = 64, window = 8, dense path),
plus the SHARDED pair (ISSUE 4 gate: the mesh-native bitmask delta path
>= 2x the per-shard mask-recompute path at n = 64, window = 8 on a simulated
4-device mesh — `--sharded`).

Both engines run the REAL sampler (mcmc_run / sharded_chain_step, identical
keys hence identical proposals) over the same synthetic dense tables at
n ∈ {16, 37, 64} — n = 37 is the paper's CPU/GPU crossover point, n = 64 its
headline "n > 60" scale. The PR-1 baseline recomputes each window node's
consistency mask from (blk, s) position gathers every proposal
(core/order_scoring.score_order_delta); the bitmask engine patches cached
packed violation planes with word ops (score_order_delta_bitmask). The two
paths are asserted BITWISE-equal on a shared prefix before anything is
timed.

  PYTHONPATH=src python benchmarks/mcmc_bench.py [--smoke] [--iters N] [--s K]
  PYTHONPATH=src python benchmarks/mcmc_bench.py --sharded [--smoke]

Emits experiments/bench/BENCH_mcmc[_sharded].json (per-iteration wall ms per
engine), mirrored to the repo root as BENCH_mcmc[_sharded].json.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys

# --sharded simulates a small device mesh on the host platform; the flag must
# land before the FIRST jax import (jax locks the device count at init)
if "--sharded" in sys.argv and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax
import jax.numpy as jnp
import numpy as np

try:
    from .common import emit, timeit
except ImportError:                      # run as a plain script
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import emit, timeit

from repro.core.combinatorics import build_pst, n_parent_sets
from repro.core.mcmc import BitmaskDelta, mcmc_run
from repro.core.order_scoring import (NEG_INF, build_membership_planes,
                                      build_violation_planes, delta_window,
                                      score_order_blocked, score_order_delta,
                                      score_order_delta_bitmask)

WINDOW = 8
GATE_N = 64
GATE_SPEEDUP = 2.0


def make_problem(n: int, s: int, block: int, seed: int = 0):
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pad = (-S) % block
    table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=NEG_INF)
    pst = jnp.pad(jnp.asarray(pst), ((0, pad), (0, 0)), constant_values=-1)
    return table, pst, S


def bench_size(n: int, s: int, iters: int, block: int = 4096) -> dict:
    table, pst, S = make_problem(n, s, block)
    block = min(block, table.shape[1])
    w = delta_window(n, WINDOW)
    assert w, f"n={n} too small for window {WINDOW}"
    score_fn = functools.partial(score_order_blocked, table, pst, block=block)

    def delta_fn(pos, lo, prev_ls, prev_idx):
        return score_order_delta(table, pst, pos, prev_ls, prev_idx, lo,
                                 window=w, block=block)

    cm = build_membership_planes(pst, n)
    planes_fn = functools.partial(build_violation_planes, pst)

    def bitmask_fn(pos, lo, prev_ls, prev_idx, pos_old, planes):
        return score_order_delta_bitmask(table, cm, pos, prev_ls, prev_idx,
                                         lo, pos_old, planes, window=w,
                                         block=block)
    bitmask = BitmaskDelta(bitmask_fn)

    def run_pr1():
        st, _ = mcmc_run(jax.random.key(0), n, score_fn, iters,
                         delta_fn=delta_fn, window=w)
        return st.score

    def run_bitmask():
        st, _ = mcmc_run(jax.random.key(0), n, score_fn, iters,
                         delta_fn=bitmask, window=w, planes_fn=planes_fn)
        return st.score

    # same key + same proposals: the engines must agree bitwise before we
    # time them (never time a bug)
    a, _ = mcmc_run(jax.random.key(1), n, score_fn, min(iters, 50),
                    delta_fn=delta_fn, window=w)
    b, _ = mcmc_run(jax.random.key(1), n, score_fn, min(iters, 50),
                    delta_fn=bitmask, window=w, planes_fn=planes_fn)
    assert float(a.score) == float(b.score), "bitmask != gather delta"
    np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
    np.testing.assert_array_equal(np.asarray(a.cur_ls), np.asarray(b.cur_ls))

    t_pr1 = timeit(run_pr1)
    t_bit = timeit(run_bitmask)
    return {
        "n": n, "S": S, "window": w, "iters": iters,
        "pr1_delta_ms_per_it": t_pr1 / iters * 1e3,
        "bitmask_ms_per_it": t_bit / iters * 1e3,
        "speedup": t_pr1 / t_bit,
    }


def bench_sharded(n: int, s: int, iters: int, block: int = 1024) -> dict:
    """Sharded pair on the simulated mesh: sharded_chain_step with the
    S-sharded cached planes (cm passed) vs the per-shard mask-RECOMPUTE
    delta path (no cm) — identical keys, identical proposals, asserted
    bitwise-equal on a shared prefix before timing. Chains ride a trivial
    data axis; the table, membership planes and violation planes are TP over
    'model'; per iteration only the (w,) pmax/pmin pair crosses the mesh."""
    from repro.core.mcmc import init_chain
    from repro.core.order_scoring import build_membership_planes
    from repro.core.sharded_scoring import (_shard_block,
                                            make_sharded_planes_fn,
                                            pad_table, score_order_sharded,
                                            sharded_chain_step)
    from repro.launch.mesh import make_local_mesh

    tp = jax.device_count()
    mesh = make_local_mesh(1, tp)
    S = n_parent_sets(n - 1, s)
    pst_np, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    blk = _shard_block(S, tp, block)
    table, pst = pad_table(table, jnp.asarray(pst_np), tp * blk)
    w = delta_window(n, WINDOW)
    assert w, f"n={n} too small for window {WINDOW}"
    cm = build_membership_planes(pst, n)
    planes_fn = make_sharded_planes_fn(pst, mesh, stacked=True)

    def score_fn(pos):
        return score_order_sharded(table, pst, pos, mesh, block=blk)

    @functools.partial(jax.jit, static_argnames=("length", "mask"))
    def run(states, *, length, mask):
        def body(st, _):
            return sharded_chain_step(st, table, pst, mesh,
                                      cm if mask else None, block=blk,
                                      window=w), None
        states, _ = jax.lax.scan(body, states, None, length=length)
        return states

    with jax.set_mesh(mesh):
        states = jax.vmap(lambda k: init_chain(k, n, score_fn))(
            jax.random.split(jax.random.key(0), 1))
        sm = states._replace(mask_planes=planes_fn(states.pos))

        # same key + same proposals: the engines must agree bitwise before
        # we time them (never time a bug)
        a = run(states, length=min(iters, 30), mask=False)
        b = run(sm, length=min(iters, 30), mask=True)
        np.testing.assert_array_equal(np.asarray(a.pos), np.asarray(b.pos))
        np.testing.assert_array_equal(np.asarray(a.cur_ls),
                                      np.asarray(b.cur_ls))
        assert (np.asarray(b.cur_idx) < S).all(), \
            "padded rank leaked into best_idx"

        t_rec = timeit(lambda: run(states, length=iters, mask=False).score)
        t_bit = timeit(lambda: run(sm, length=iters, mask=True).score)
    return {
        "n": n, "S": S, "window": w, "iters": iters, "devices": tp,
        "recompute_ms_per_it": t_rec / iters * 1e3,
        "bitmask_ms_per_it": t_bit / iters * 1e3,
        "speedup": t_rec / t_bit,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes/iters — CI wiring check, seconds")
    ap.add_argument("--iters", type=int, default=0,
                    help="override iterations per timed run")
    ap.add_argument("--s", type=int, default=3, help="max parent-set size")
    ap.add_argument("--sharded", action="store_true",
                    help="benchmark the sharded pair on a simulated "
                         "4-device mesh (mask recompute vs cached planes)")
    args = ap.parse_args(argv)

    if args.smoke:
        sizes, iters = [16], args.iters or 30
    else:
        sizes, iters = [16, 37, 64], args.iters or 300

    if args.sharded:
        iters = args.iters or (30 if args.smoke else 200)
        rows = [bench_sharded(n, args.s, iters) for n in sizes]
        emit("BENCH_mcmc_sharded", rows)
        if not args.smoke:
            last = rows[-1]
            print(f"\nn={last['n']}: sharded bitmask delta path is "
                  f"{last['speedup']:.2f}x the per-shard mask-recompute path "
                  f"on {last['devices']} devices "
                  f"(gate >= {GATE_SPEEDUP:g}x at n={GATE_N})")
            if last["n"] == GATE_N and last["speedup"] < GATE_SPEEDUP:
                raise SystemExit(
                    f"FAIL: {last['speedup']:.2f}x < {GATE_SPEEDUP:g}x gate")
        return rows

    rows = [bench_size(n, args.s, iters) for n in sizes]
    emit("BENCH_mcmc", rows)
    if not args.smoke:
        last = rows[-1]
        print(f"\nn={last['n']}: bitmask-cached engine is "
              f"{last['speedup']:.2f}x the PR-1 gather-delta engine "
              f"(gate >= {GATE_SPEEDUP:g}x at n={GATE_N})")
        if last["n"] == GATE_N and last["speedup"] < GATE_SPEEDUP:
            raise SystemExit(
                f"FAIL: {last['speedup']:.2f}x < {GATE_SPEEDUP:g}x gate")
    return rows


if __name__ == "__main__":
    main()
