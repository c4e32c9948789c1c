"""Posterior-service scheduling overhead: K jobs run back-to-back as
standalone ``learn_structure`` calls vs interleaved through the
FleetScheduler (ISSUE 10 gate: concurrent scheduling keeps >= 90% of the
sequential AGGREGATE iters/sec at n = 32).

Both sides run the SAME jobs — same data, same config, same seeds — through
the same engine builders, so the only difference is who drives the segment
loop: the in-process while-loop, or the round-robin scheduler tick. The
scheduler adds per-segment host work (job bookkeeping, slot accounting) and
loses locality by alternating jitted runners; the gate caps that tax at 10%
of aggregate throughput. Per-job artifacts are asserted bitwise-equal
between the two drivers before anything is timed (never time a bug).

  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--iters N]

Rows land in BENCH_mcmc.json (mode="serve", variant="sequential" |
"concurrent") beside the engine / telemetry / supervisor rows, mirrored to
the repo root.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

try:
    from .common import emit
except ImportError:                      # run as a plain script
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import emit

from repro.learner import learn_structure
from repro.service import (DatasetSpec, FleetScheduler, JobManager,
                           load_dataset, service_config)

JOBS = 2
GATE_N = 32
GATE_RATIO = 0.90               # concurrent >= 90% of sequential iters/sec


def _configs(n: int, iters: int):
    """The K job payloads: same size, different data + walk seeds, telemetry
    cadence fixed so segment boundaries match between drivers."""
    cfg = dict(iters=iters, chains=4, window=8, trace_every=10,
               check_every=max(iters // 4, 10), stop_on_converge=False,
               exchange_every=50)
    out = []
    for k in range(JOBS):
        c = service_config(dict(cfg, seed=11 + k))
        data = load_dataset(DatasetSpec(network="synth", n=n, m=200,
                                        seed=3 + k), c.q)
        out.append((data, c))
    return out

def _sequential(jobs):
    t0 = time.perf_counter()
    results = [learn_structure(data, cfg) for data, cfg in jobs]
    return results, time.perf_counter() - t0


def _concurrent(jobs, tmpdir: str):
    man = JobManager(run_dir=tmpdir)
    sched = FleetScheduler(man, slots=sum(c.chains for _, c in jobs))
    t0 = time.perf_counter()
    handles = [sched.submit(data, cfg)[0] for data, cfg in jobs]
    sched.run()
    dt = time.perf_counter() - t0
    for h in handles:
        assert h.state == "done", f"{h.id}: {h.state} {h.error}"
    return [h.result for h in handles], dt


def bench_size(n: int, iters: int, tmpdir: str) -> list[dict]:
    jobs = _configs(n, iters)
    # warmup = correctness pass: both drivers must produce bitwise-identical
    # artifacts per job (and it absorbs compilation for the timed runs)
    seq, t_seq = _sequential(jobs)
    con, t_con = _concurrent(jobs, tmpdir)
    for k, (a, b) in enumerate(zip(seq, con)):
        for key in ("edge_posterior", "map_dag", "consensus"):
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]),
                err_msg=f"job {k}: {key} diverged between drivers")
        assert float(a["score"]) == float(b["score"]), f"job {k}: score"
    # timed passes (compiled caches warm for both drivers)
    _, t_seq = _sequential(jobs)
    _, t_con = _concurrent(jobs, tmpdir + "_timed")
    total_iters = JOBS * iters
    chains = jobs[0][1].chains
    base = {"n": n, "iters": iters, "chains": chains, "window": 8,
            "mode": "serve", "jobs": JOBS}
    return [
        {**base, "variant": "sequential", "wall_s": t_seq,
         "agg_iters_per_s": total_iters / t_seq},
        {**base, "variant": "concurrent", "wall_s": t_con,
         "agg_iters_per_s": total_iters / t_con,
         "ratio_vs_sequential": t_seq / t_con},
    ]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes/iters — CI wiring check, seconds")
    ap.add_argument("--iters", type=int, default=0,
                    help="override iterations per job")
    args = ap.parse_args(argv)

    if args.smoke:
        sizes, iters = [12], args.iters or 80
    else:
        sizes, iters = [12, GATE_N], args.iters or 600

    import tempfile
    rows = []
    for n in sizes:
        rows += bench_size(n, iters, tempfile.mkdtemp(prefix="serve_bench_"))
    emit("BENCH_mcmc", rows)
    if not args.smoke:
        last = rows[-1]
        ratio = last["ratio_vs_sequential"]
        print(f"\nn={last['n']}: concurrent scheduling keeps "
              f"{ratio * 100:.1f}% of sequential aggregate iters/sec "
              f"(gate >= {GATE_RATIO * 100:g}% at n={GATE_N})")
        if last["n"] == GATE_N and ratio < GATE_RATIO:
            raise SystemExit(f"FAIL: {ratio * 100:.1f}% < "
                             f"{GATE_RATIO * 100:g}% throughput gate")
    return rows


if __name__ == "__main__":
    main()
