#!/usr/bin/env python3
"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 chipbench/control.py --workload <name> --seeds 1 2 3 ... \\
        --seconds <s> [--control-seeds 3]

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, then every number the check compares, read twice: once
for what the program produced (the lower readings) and, on the first
``--control-seeds`` seeds, once for the control, the plain reference
computed in bfloat16 put in the program's place (the upper readings). One
JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.run import BenchError, Cell, use_cache  # noqa: E402


def readings(cell: Cell, seed: int, seconds: float, control: bool) -> dict:
    load = cell.load(seed)
    load.setup()
    t0 = time.perf_counter()
    units = 0
    while True:
        load.step()
        units += 1
        if time.perf_counter() - t0 >= seconds:
            break
    load.sync()
    out = load.outputs()
    gc.collect()
    row = {"seed": seed, "units": units, "program": load.numbers(out)}
    if control:
        row["control"] = load.numbers(load.control(out))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    try:
        cell.devices()
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    use_cache()
    for k, seed in enumerate(args.seeds):
        row = readings(cell, seed, args.seconds, k < args.control_seeds)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
