"""Convergence telemetry: accelerator-resident taps + host-side collector.

Long MCMC runs used to terminate on a fixed iteration count with no
visibility into whether the posterior had mixed (the paper's §V convergence
caveat). This package splits observability the same way the engine splits
work:

* **In-scan taps** (:mod:`taps`, device): a :class:`~taps.TraceState` pytree
  carried beside ``ChainState`` through every run loop — downsampled
  per-chain score/accept ring buffers, a per-iteration window histogram, a
  thinned posterior edge-count accumulator (parent sets unranked
  arithmetically on device), and per-slot exchange re-seed counts. O(small)
  per iteration, no host sync, no extra collectives on the sharded path.
* **Host collector** (:mod:`collector`): drains the taps between jitted
  segments, computes split-R̂ on score traces and max-R̂ over cross-chain
  edge marginals (:mod:`rhat`, the Kuipers–Moffa concordance criterion),
  flags stuck/diverged chains with rolling-median/MAD spike detection, and
  appends schema-versioned JSONL rows (:mod:`schema`) under
  ``experiments/runs/``.
* **Spans** (:mod:`spans`, host): ``span(name)`` marks the host layers'
  boundaries (preprocessing stages, each supervised segment, the drain's
  wait) on the profiler's clock and times the preprocessing stages; a
  compile counter files every backend compile under the innermost span.

The R̂ stopping rule (``bn_learn --stop-on-converge``): both R̂ statistics
below ``--rhat-threshold`` for ``--patience`` consecutive checks stops the
run early — convergence, not the iteration cap, decides run length.
``python -m repro.telemetry.validate`` re-validates emitted trace files
(CI runs it after an end-to-end telemetry smoke).
"""
from .collector import Collector, host_meta
from .rhat import edge_rhat, median_outliers, split_rhat
from .schema import SCHEMA, read_rows, validate_row, write_rows
from .spans import span
from .taps import (DEFAULT_TRACE_CAP, TraceState, adjacency_bits_from_ranks,
                   drain, exchange_step_traced, init_trace, make_tap,
                   unrank_parent_sets_jax)

__all__ = [
    "Collector", "host_meta", "edge_rhat", "median_outliers", "split_rhat",
    "SCHEMA", "read_rows", "validate_row", "write_rows", "DEFAULT_TRACE_CAP",
    "TraceState", "adjacency_bits_from_ranks", "drain",
    "exchange_step_traced", "init_trace", "make_tap",
    "unrank_parent_sets_jax", "span", "spans",
]
