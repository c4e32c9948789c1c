"""Shared benchmark utilities: timing, result collection, CSV emission.

``save`` MERGES by row config instead of overwriting: each BENCH_*.json is a
perf trajectory, and the n = 16 CI smoke must land BESIDE the n = 64 gate
rows, never on top of them (the pre-fix writer clobbered the whole file, so
every smoke run erased the gate evidence). Rows are keyed by their
configuration fields (CONFIG_KEYS: n, S, window, devices, ...); a new row
replaces the old row with the SAME config and appends otherwise.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.runtime.compile_cache import use_compile_cache

# every benchmark imports this module first: reuse compiled programs across
# runs (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
use_compile_cache()

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "bench")
# every BENCH_*.json is mirrored to the repo root so the perf trajectory is
# machine-readable without digging into experiments/ (CI and make bench-*
# rely on this)
ROOT_DIR = os.path.join(os.path.dirname(__file__), "..")

# The identity of a benchmark row: every field that selects WHAT was
# measured (problem size, engine knobs, topology), none that reports HOW it
# went (timings, speedups). Fields absent from a row are simply not part of
# its key, so differently-shaped benches coexist in one file.
CONFIG_KEYS = ("n", "q", "s", "m", "S", "iters", "chains", "window",
               "devices", "n_devices", "tp", "dp", "chunk", "block",
               "mode", "variant", "scorer", "delta", "prune_delta",
               "max_keep", "backend", "flip_p")


_HOST_META: dict | None = None


def host_meta() -> dict:
    """Cached machine identity stamped into every bench row by :func:`save`:
    reading a trajectory later, a 1-vCPU CI smoke and a multi-core gate box
    must be tellable apart. Deliberately NOT in CONFIG_KEYS — the host
    describes where a measurement ran, not what was measured, so merge
    identity is unchanged."""
    global _HOST_META
    if _HOST_META is None:
        from repro.telemetry import host_meta as _hm
        _HOST_META = _hm()
    return _HOST_META


def timeit(fn, *args, warmup: int = 1, reps: int = 3) -> float:
    """Median wall seconds of fn(*args) with jax sync."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _row_key(row) -> str:
    """Hashable config identity of one row. Non-dict payload entries (or
    rows with no config field at all) key on their full JSON text — they
    merge by exact identity, which degrades to append-if-changed."""
    if isinstance(row, dict):
        cfg = {k: row[k] for k in CONFIG_KEYS if k in row}
        if cfg:
            return json.dumps(cfg, sort_keys=True, default=float)
    return json.dumps(row, sort_keys=True, default=float)


def merge_rows(existing: list, new: list) -> list:
    """Existing rows with same-config rows replaced by their new
    measurement and genuinely new configs appended (stable order: existing
    first, new appended in their given order)."""
    out = list(existing)
    index = {_row_key(r): i for i, r in enumerate(out)}
    for row in new:
        k = _row_key(row)
        if k in index:
            out[index[k]] = row
        else:
            index[k] = len(out)
            out.append(row)
    return out


def _load_rows(path: str) -> list:
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            prev = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []          # unreadable trajectory: start over, don't crash
    return prev if isinstance(prev, list) else [prev]


def save(name: str, payload) -> None:
    """Merge ``payload`` (a list of row dicts) into the named trajectory
    file(s) by row config — never wholesale-overwrite (see module
    docstring)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    rows = payload if isinstance(payload, list) else [payload]
    rows = [({**r, "host": host_meta()} if isinstance(r, dict)
             and "host" not in r else r) for r in rows]
    dirs = [RESULTS_DIR] + ([ROOT_DIR] if name.startswith("BENCH_") else [])
    for d in dirs:
        path = os.path.join(d, f"{name}.json")
        merged = merge_rows(_load_rows(path), rows)
        with open(path, "w") as f:
            json.dump(merged, f, indent=1, default=float)


def emit(name: str, rows: list[dict]) -> None:
    """Print a compact aligned table and persist JSON."""
    if not rows:
        print(f"[{name}] no rows")
        return
    cols = list(rows[0])
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows))
              for c in cols}
    print(f"\n== {name} ==")
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))
    save(name, rows)


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)
