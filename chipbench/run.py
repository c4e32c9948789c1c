#!/usr/bin/env python3
"""Chip benchmark of the Bayesian-network structure learner.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and last ``checks``, every number compared beside its
limit. The same lines go to standard error, as its last lines.

Everything a cell needs is found by name: the configuration file that
``BENCHMARK.json`` names, ``traffic/<traffic>.json`` (whose ``generator``
names the general generator in ``generators/``), ``limits/<workload>.json``,
``metrics/<metric>.py`` for each per-layer metric, and ``peaks.json``. So a
new cell, configuration, traffic mix or metric is new files and entries,
not an edit.

A run: refuses anything but a TPU listed in ``peaks.json``; generates its
dataset from ``--seed``; sets up and warms every program the window runs
(set-up time, ``setup_s``, is process start to window open); measures for
``--seconds``, closing at the first unit boundary after it; reads the peak
device memory; then checks what the window produced against the plain
reference (``reference.py``). With ``--trace 1`` the profiler records the
start of the window and the per-layer metrics are read from that trace.

Besides the keys the contract reads, the result line carries what explains
a run: the set-up phases, the generator's counters, the units' host-clock
durations (``units``: ``excess_s`` is the time units took beyond the
median unit), the process's CPU time in the window (``host``), and the
compile-cache entries left unwritten.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# The persistent compile cache keeps no entry over this size. Such a
# program carries a large constant: the segment runner carries its
# dataset's score table, so it differs with every seed. Written, it would
# be found again only by a later run of the same seed, and set-up would
# depend on which seeds ran before in the checkout.
MAX_ENTRY_BYTES = 4 * 2 ** 20
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The cell cannot run here; nothing is printed on standard output."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_chips(need: int, peaks: dict):
    """(devices, peaks of their kind) or BenchError: a TPU listed in the
    peaks table, with at least ``need`` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        raise BenchError(f"needs {need} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return devices, peaks[kind]


def use_cache(path: str | None = None,
              max_entry: int = MAX_ENTRY_BYTES) -> list:
    """Turn JAX's persistent compile cache on where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), or at
    ``path``, writing no entry over ``max_entry`` bytes. Returns the list
    to which each entry left out is added as [module, bytes]."""
    from jax._src import compilation_cache, lru_cache

    skipped: list = []

    class SmallEntries(lru_cache.LRUCache):
        def put(self, key: str, val: bytes) -> None:
            if len(val) > max_entry:
                skipped.append([key.split("-", 1)[0], len(val)])
            else:
                super().put(key, val)

    if path is None:
        from repro.runtime.compile_cache import use_compile_cache

        path = use_compile_cache()
    else:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    compilation_cache._cache = SmallEntries(path, max_size=-1)
    return skipped


def _device_ids(devices) -> list:
    """The profiler's /device:TPU:<k> plane number of each device."""
    return [d.id if getattr(d, "local_hardware_id", None) is None
            else d.local_hardware_id for d in devices]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Cell:
    """One entry of ``BENCHMARK.json``'s workloads with every file it names."""

    def __init__(self, workload: str, root: str = ROOT):
        for path in (os.path.join(root, "src"), root):
            if path not in sys.path:
                sys.path.insert(0, path)
        self.name, self.root = workload, root
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        bench = os.path.join(root, self.spec["paths"][0])
        self.bench = bench
        self.wl = _by_name(self.spec["workloads"], workload, "workload")
        self.config = _load_json(os.path.join(root, _by_name(
            self.spec["configs"], self.wl["config"], "config")["file"]))
        self.traffic = _load_json(os.path.join(
            bench, "traffic", self.wl["traffic"] + ".json"))
        self.limits = _load_json(os.path.join(bench, "limits",
                                              workload + ".json"))
        self.peaks = _load_json(os.path.join(bench, "peaks.json"))
        kind = self.traffic["generator"]
        self.generators = _load_module(
            os.path.join(BENCH, "generators", kind + ".py"),
            "chipbench_generator_" + kind)
        self.work = os.path.join(root, ".chipbench_work", workload)

    def devices(self, find_chip: bool = True):
        """(the cell's devices, their peaks); BenchError off a listed TPU."""
        import jax

        chips = int(self.wl["chips"])
        if find_chip:
            devices, peak = find_chips(chips, self.peaks)
        else:
            devices = jax.devices()
            peak = next(v for v in self.peaks.values() if isinstance(v, dict))
        return devices[:chips], peak

    def load(self, seed: int):
        """The cell's traffic generator for one seed."""
        os.makedirs(self.work, exist_ok=True)
        return self.generators.Generator(self.config, self.traffic, seed,
                                         self.work, f"{self.name}-{seed}")


def _reader_path(bench: str, metric: str) -> str:
    """``metrics/<metric>.py``; where there is none, the reader of the
    quantity the metric splits by cell: ``device_idle.mcmc`` is read by
    ``metrics/device_idle.py``."""
    path = os.path.join(bench, "metrics", metric + ".py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(bench, "metrics", metric.split(".")[0] + ".py")
    return path


def per_layer_metrics(cell: Cell, reduced, counters: dict,
                      peak: dict) -> dict:
    """Each per-layer metric of the cell, read by ``metrics/<name>.py`` from
    the reduced trace and the generator's counters; a reader that finds
    nothing to read returns None, and its metric is left out."""
    out = {}
    for m in cell.spec["per_layer"]:
        if not _applies(m, cell.name):
            continue
        reader = _load_module(_reader_path(cell.bench, m["name"]),
                              "chipbench_metric_" + m["name"])
        value = reader.read(reduced, counters, cell.config, peak)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, find_chip: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    cell = Cell(workload, root)
    spec, traffic, limits = cell.spec, cell.traffic, cell.limits
    devices, peak = cell.devices(find_chip)
    skipped = use_cache() if find_chip else []

    import jax

    compiles = {"window": 0, "open": False}

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT and compiles["open"]:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    load = cell.load(seed)
    t_setup = time.perf_counter()
    load.setup()

    # ---------------------------------------------------------- the window
    trace_dir = os.path.join(cell.work, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_for = traffic.get("trace_seconds")
    tracing = bool(trace)
    window_span = None
    gc.collect()
    gc.freeze()               # set-up's objects are not scanned in the window
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    compiles["open"] = True
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    units = done = 0
    spans = []                             # (start, seconds) of each unit
    while True:
        t_unit = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + cell.generators.UNIT):
            n = load.step()
        units += 1
        done += n
        now = time.perf_counter()
        spans.append((t_unit - t_open, now - t_unit))
        if tracing:
            load.traced(n)
            if trace_for is not None and now - t_open >= trace_for:
                load.sync()
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
        if now - t_open >= seconds:
            break
    load.sync()
    t_close = time.perf_counter()
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    gc.unfreeze()
    compiles["open"] = False
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    # ------------------------------------------------------ after the window
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}
    t_check = time.perf_counter()
    out = load.outputs()
    gc.collect()
    numbers = load.numbers(out)
    del out
    check_s = time.perf_counter() - t_check
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise BenchError(f"no limit for {name!r} in "
                             f"limits/{workload}.json")
        if not math.isfinite(value):
            value = None                   # JSON has no inf or nan
        checks[name] = {"value": value, "limit": limits[name]}
    correct = all(_within(c["value"], c["limit"]) for c in checks.values())

    metrics = {}
    result = {"correct": correct, "attempted": units,
              "failed": 0 if correct else 1}
    if trace:
        from chipbench.trace_reduce import reduce_trace

        red = reduce_trace(trace_dir, _device_ids(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        metrics = per_layer_metrics(cell, red, load.counters, peak)
        result["breakdown"] = red.breakdown()
    else:
        for m in spec["end_to_end"]:
            if not _applies(m, workload):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == traffic["rate_metric"]:
                value = done / (t_close - t_open)
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["setup_phases"] = {"start_s": t_setup - T_START,
                              **load.phases}
    result["counters"] = load.counters
    durs = sorted(d for _, d in spans)
    median = durs[len(durs) // 2]
    result["units"] = {
        "count": len(spans), "median_s": median, "max_s": durs[-1],
        "excess_s": sum(d - median for d in durs if d > median),
        "slowest": [list(u) for u in sorted(spans, key=lambda u: -u[1])[:5]]}
    result["host"] = {"user_s": usage1.ru_utime - usage0.ru_utime,
                      "system_s": usage1.ru_stime - usage0.ru_stime}
    result["cache_skipped"] = skipped
    result["check_s"] = check_s
    result["window_compiles"] = compiles["window"]
    result["checks"] = checks
    return result


def _within(value, limit) -> bool:
    return value is not None and value <= limit


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    if result["window_compiles"]:
        print(f"chipbench: {result['window_compiles']} compilation(s) inside "
              "the measured window", file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if _within(c["value"], c["limit"]) else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
