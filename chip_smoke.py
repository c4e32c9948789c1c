"""Smoke test of the BN structure learner's main path on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the sharded path only

Runs at the paper's own width (§VI / Table III): n = 60 variables with
q = 3 states, at most s = 4 parents, m = 1000 samples drawn from a seeded
synthetic DAG (the data of ``bn_learn --network synth``). On one chip:

(a) builds the dense score table with the fused Pallas count+score kernel
    and checks it against the jnp table build on the same chip;
(b) learns with ``learn_structure`` on that table, bitmask delta engine,
    once on the XLA path and once on the fused Pallas kernel path: the two
    must agree bitwise, and the result must find true edges;
(c) runs the default fused path, which at this S is the streaming-pruned
    engine: its table must equal the pruned dense table bitwise;
(d) serves two ALARM-width jobs plus a duplicate through the posterior
    service: the duplicate must dedup and every job must finish ``done``.

With ``--four-chips`` it runs only ``bn_learn --sharded`` (table and planes
S-sharded over four chips), with and without the kernel, and checks both
against a single-device run of the same seed, bitwise.

It refuses to run without a TPU and never falls back to the CPU. The times
it prints are smoke set-up times, not benchmark numbers. Its last line is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
WORK = os.path.join(ROOT, "experiments", "cache", "chip_smoke")

import numpy as np  # noqa: E402

from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

# the fused kernel evaluates lgamma with its own elementwise ops, the jnp
# table build with XLA's: on the chip the two may differ in the last bits of
# each term, and a table entry sums ~q^s * n*q of them (on a TPU v5e at
# this cell: max relative difference 3.1e-6, max absolute 1.5e-3)
TABLE_RTOL, TABLE_ATOL = 1e-5, 1e-3


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def _require(ok, what) -> None:
    """Raise SmokeFailure unless ok (explicit: asserts vanish under -O)."""
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Cell:
    n: int = 60
    q: int = 3
    s: int = 4
    m: int = 1000
    seed: int = 0
    chains: int = 8
    window: int = 8
    iters: int = 2000
    serve_m: int = 1000       # ALARM samples per service job
    serve_iters: int = 600


PAPER = Cell()


@contextmanager
def timed(label: str):
    t0 = time.perf_counter()
    yield
    print(f"chip_smoke: {label}: {time.perf_counter() - t0:.1f} s "
          "(smoke set-up time, not a benchmark number)", flush=True)


def _mosaic(fn, *args, **static) -> bool:
    """Whether fn, compiled as the program would compile it, holds a Pallas
    TPU kernel — interpret mode cannot hide behind a passing result."""
    return "tpu_custom_call" in fn.lower(*args, **static).compile().as_text()


def _learn_config(cell: Cell, work: str, run_name: str, **kw):
    from repro.launch.bn_learn import LearnConfig
    return LearnConfig(q=cell.q, s=cell.s, iters=cell.iters,
                       chains=cell.chains, window=cell.window, seed=cell.seed,
                       preprocess="fused", telemetry=True,
                       trace_dir=os.path.join(work, "runs"),
                       run_name=run_name, **kw)


def preprocess(cell: Cell, data, cache: str, require_mosaic: bool = True):
    """(a) Dense table through the fused Pallas count+score kernel, stored
    in `cache` for (b); checked against the jnp table build."""
    import jax
    import jax.numpy as jnp

    from repro.preprocess import build_score_table_fused
    from repro.preprocess.fused import fused_scores_pallas

    with timed("(a) Pallas count+score build"):
        st, info = build_score_table_fused(data, q=cell.q, s=cell.s,
                                           use_pallas=True, cache_dir=cache,
                                           return_info=True)
        got = np.asarray(st.table)
    _require(not info["cache_hit"], "the kernel build must not be a hit")
    with timed("(a) jnp build"):
        want = np.asarray(build_score_table_fused(
            data, q=cell.q, s=cell.s, use_pallas=False).table)
    _require(got.shape == want.shape == (cell.n, st.S), got.shape)
    _require(np.isfinite(got).all(), "non-finite score-table entries")
    diff = float(np.max(np.abs(got - want)))
    print(f"chip_smoke: (a) table {got.shape}: max |pallas - jnp| = {diff!r} "
          f"(max |entry| {float(np.max(np.abs(want)))!r})", flush=True)
    np.testing.assert_allclose(got, want, rtol=TABLE_RTOL, atol=TABLE_ATOL)
    if require_mosaic:
        block_m, chunk = 512, 1024       # build_score_table_fused defaults
        m_pad = cell.m + (-cell.m) % block_m
        R = cell.n * cell.q
        _require(_mosaic(
            fused_scores_pallas,
            jax.ShapeDtypeStruct((chunk, m_pad), jnp.int32),
            jax.ShapeDtypeStruct((m_pad, R), jnp.float32),
            jax.ShapeDtypeStruct((chunk,), jnp.int32),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
            jax.ShapeDtypeStruct((R, cell.n), jnp.float32),
            Q=cell.q ** cell.s), "count+score kernel not compiled")
    return st


def learn_dense(cell: Cell, data, truth, st, cache: str, work: str,
                require_mosaic: bool = True) -> dict:
    """(b) XLA and fused-kernel bitmask engines on the dense table: equal
    best score and best parent sets, finite, with true edges found."""
    from repro.core import roc_point
    from repro.launch.bn_learn import learn_structure

    outs = {}
    for name, kernel in (("xla", False), ("kernel", True)):
        cfg = _learn_config(cell, work, f"dense_{name}", auto_prune=False,
                            cache_dir=cache, use_kernel=kernel)
        with timed(f"(b) learn_structure, {name} path"):
            out = learn_structure(data, cfg)
        _require(out["preprocess_cache_hit"],
                 "(b) must learn on (a)'s table")
        _require(out["mask_cache"] and out["delta_window"] == cell.window,
                 "(b) must run the bitmask delta engine")
        _require(out["iters_run"] == cell.iters, out["iters_run"])
        _require(np.isfinite(out["score"]), out["score"])
        fp, tp = roc_point(out["adjacency"], truth)
        print(f"chip_smoke: (b) {name}: score={out['score']!r} TP={tp:.3f} "
              f"FP={fp:.4f} accept={out['accept_rate']:.3f}", flush=True)
        _require(tp > 0, f"{name} path found no true edge")
        outs[name] = out
    x, k = outs["xla"], outs["kernel"]
    _require(x["score"] == k["score"], (x["score"], k["score"]))
    _require(np.array_equal(x["adjacency"], k["adjacency"]),
             "XLA and kernel paths chose different best parent sets")
    _require(x["chain_accept_rates"] == k["chain_accept_rates"],
             "XLA and kernel chains accepted different moves")
    print("chip_smoke: (b) XLA and kernel runs agree: same best score, "
          "best parent sets and per-chain accept counts", flush=True)
    if require_mosaic:
        _check_kernel_step_compiles(cell, st)
    return x


def _check_kernel_step_compiles(cell: Cell, st):
    """The per-iteration kernel step of (b), as learn_structure builds it,
    compiles to a Pallas TPU kernel."""
    import jax.numpy as jnp

    from repro.core.order_scoring import (build_membership_planes,
                                          build_violation_planes)
    from repro.kernels.order_score import (BLOCK_S,
                                           order_score_delta_bitmask,
                                           pad_for_kernel)

    table, pst = pad_for_kernel(st.table, st.pst, BLOCK_S)
    pos = jnp.arange(cell.n, dtype=jnp.int32)
    ls = jnp.zeros((cell.n,), jnp.float32)
    idx = jnp.zeros((cell.n,), jnp.int32)
    _require(_mosaic(order_score_delta_bitmask, table,
                     build_membership_planes(pst, cell.n), pos, ls, idx,
                     jnp.int32(0), pos, build_violation_planes(pst, pos),
                     window=cell.window), "order-score kernel not compiled")


def learn_pruned(cell: Cell, data, st, dense_score: float, work: str):
    """(c) The default fused path: at S >= AUTO_PRUNE_S the streaming
    pruned table, built through the Pallas kernel as the default path
    builds it on the chip, checked bitwise against the pruned dense table
    (as the streaming tests do), then learned on end to end from the cache
    that build filled."""
    from repro.launch.bn_learn import (AUTO_PRUNE_DELTA, AUTO_PRUNE_S,
                                       learn_structure)
    from repro.preprocess import build_score_table_fused, prune_table

    _require(st.S >= AUTO_PRUNE_S, (st.S, AUTO_PRUNE_S))
    cache = os.path.join(work, "pruned")
    with timed("(c) streaming pruned build"):
        sp, info = build_score_table_fused(
            data, q=cell.q, s=cell.s, prune_delta=AUTO_PRUNE_DELTA,
            use_pallas=True, cache_dir=cache, return_info=True)
    _require(info["streaming"] and not info["cache_hit"], info)
    want = prune_table(st, AUTO_PRUNE_DELTA)
    for field in ("kept_idx", "kept_ls", "kept_parents", "keys", "vals"):
        np.testing.assert_array_equal(np.asarray(getattr(sp, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)
    _require((sp.max_probe, sp.S, sp.K) == (want.max_probe, want.S, want.K),
             "streaming table shape differs from the pruned dense one")
    print(f"chip_smoke: (c) streaming table == pruned dense table, bitwise "
          f"(K={sp.K} of S={sp.S})", flush=True)
    with timed("(c) learn_structure, default fused path"):
        out = learn_structure(data, _learn_config(cell, work, "pruned",
                                                  cache_dir=cache))
    _require(out["auto_pruned"] and out["preprocess_cache_hit"], out)
    _require(np.isfinite(out["score"]), out["score"])
    print(f"chip_smoke: (c) pruned engine score={out['score']!r} "
          f"(dense engine {dense_score!r})", flush=True)


def serve(cell: Cell, work: str):
    """(d) Two ALARM-width jobs and a duplicate through the posterior
    service, in process; every job must finish done and every response
    validate."""
    from repro.launch.serve_smoke import (fetch_artifacts, shutdown,
                                          start_server, submit_and_wait)

    config = {"q": cell.q, "s": cell.s, "iters": cell.serve_iters,
              "chains": 4, "check_every": 200, "trace_every": 10,
              "seed": 11, "stop_on_converge": True, "patience": 1,
              "preprocess": "fused"}
    specs = [{"network": "alarm", "m": cell.serve_m, "seed": 3},
             {"network": "alarm", "m": cell.serve_m, "seed": 4}]
    _, base, thread = start_server(os.path.join(work, "service"))
    with timed("(d) service jobs"):
        states = submit_and_wait(base, specs, config)
    for jid in states:
        art = fetch_artifacts(base, jid)
        _require(art["posterior"]["n"] == 37, art["posterior"]["n"])
        _require(np.isfinite(art["map"]["score"]), art["map"]["score"])
    print(f"chip_smoke: (d) every service job done: {sorted(states)}",
          flush=True)
    shutdown(base, thread)


def one_chip(cell: Cell, truth, data, work: str,
             require_mosaic: bool = True):
    cache = os.path.join(work, "dense")
    st = preprocess(cell, data, cache, require_mosaic)
    dense = learn_dense(cell, data, truth, st, cache, work, require_mosaic)
    learn_pruned(cell, data, st, dense["score"], work)
    serve(cell, work)


def four_chips(cell: Cell, data, work: str, tp: int = 4):
    """bn_learn --sharded (table and planes S-sharded over `tp` chips),
    without and with the kernel, against a single-device run: bitwise."""
    from repro.launch.bn_learn import learn_structure
    from repro.preprocess import build_score_table_fused

    cache = os.path.join(work, "dense")
    with timed("Pallas count+score build"):
        build_score_table_fused(data, q=cell.q, s=cell.s, use_pallas=True,
                                cache_dir=cache)
    with timed("learn_structure, single device"):
        ref = learn_structure(data, _learn_config(
            cell, work, "single", auto_prune=False, cache_dir=cache))
    _require(not ref["sharded"] and np.isfinite(ref["score"]), ref["score"])
    for kernel in (False, True):
        name = f"sharded over {tp} chips, {'kernel' if kernel else 'XLA'}"
        with timed(f"learn_structure, {name}"):
            out = learn_structure(data, _learn_config(
                cell, work, f"sharded_{int(kernel)}", auto_prune=False,
                cache_dir=cache, sharded=True, sharded_tp=tp,
                use_kernel=kernel))
        _require(out["sharded"] and out["mask_cache"], out)
        print(f"chip_smoke: {name}: score={out['score']!r} "
              f"(single device {ref['score']!r})", flush=True)
        _require(out["score"] == ref["score"], (out["score"], ref["score"]))
        _require(np.array_equal(out["adjacency"], ref["adjacency"]),
                 "sharded run chose different best parent sets")
        _require(out["chain_accept_rates"] == ref["chain_accept_rates"],
                 "sharded chains accepted different moves")
    print(f"chip_smoke: sharded == single device, bitwise, with and without "
          f"the kernel", flush=True)


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path over four chips and "
                         "compare it with a single-device run")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.bn_learn import _network_data

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    shutil.rmtree(WORK, ignore_errors=True)
    cell = PAPER
    truth, data = _network_data("synth", cell.m, cell.q, cell.seed,
                                n_synth=cell.n)
    if args.four_chips:
        four_chips(cell, data, WORK)
    else:
        one_chip(cell, truth, data, WORK)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
