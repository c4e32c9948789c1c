"""The span recorder (telemetry/spans.py) on the CPU.

* with no profiler session nothing is recorded, and ``.seconds`` is set;
* under ``jax.profiler.start_trace`` nested spans record counts and seconds,
  children bounded by their parent;
* a backend compile is counted under the innermost open span, or ``""``;
* a dense or streaming build's ``info["stages"]`` are its spans' seconds,
  and the supervisor's ``segment`` / ``segment.wait`` spans bound each other;
* the spans land on the profiler's host line, nested, beside the window
  span, so the benchmark's trace reduction can hand idle time to them.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.preprocess import build_score_table_fused
from repro.telemetry import init_trace, span, spans
from repro.runtime.supervisor import RunSupervisor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DENSE_SPANS = ("preprocess.build", "preprocess.plan", "preprocess.score",
               "preprocess.assemble", "preprocess.rank_map",
               "preprocess.gather")


@pytest.fixture
def profiled(tmp_path):
    """A profiler session around the test, and a clean record."""
    spans.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def _data(n=7, m=120, q=2, seed=0):
    return np.random.default_rng(seed).integers(0, q, (m, n), dtype=np.int32)


def test_nothing_recorded_without_a_profiler():
    spans.reset()
    with span("idle.outer") as outer:
        with span("idle.inner") as inner:
            time.sleep(0.002)
    assert inner.seconds >= 0.002 and outer.seconds >= inner.seconds
    assert spans.snapshot()["spans"] == {}


def test_nested_spans_recorded_under_a_profiler(profiled):
    with span("outer") as outer:
        for _ in range(2):
            with span("inner"):
                time.sleep(0.002)
    rec = spans.snapshot()["spans"]
    assert rec["outer"] == {"count": 1, "s": outer.seconds}
    assert rec["inner"]["count"] == 2
    assert 0.004 <= rec["inner"]["s"] <= rec["outer"]["s"]


def test_compile_counted_under_innermost_span():
    spans.reset()
    with span("compile.outer"):
        with span("compile.inner"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(13)).block_until_ready()
    jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(17)).block_until_ready()
    comp = spans.snapshot()["compile"]
    assert comp["compile.inner"]["count"] >= 1
    assert comp["compile.inner"]["s"] > 0
    assert comp[""]["count"] >= 1
    assert "compile.outer" not in comp
    assert spans.snapshot()["spans"] == {}       # compiles need no profiler


def test_dense_stages_are_the_spans_seconds(profiled):
    _, info = build_score_table_fused(_data(), q=2, s=2, return_info=True)
    rec = spans.snapshot()["spans"]
    assert {k: rec[k]["count"] for k in DENSE_SPANS} == dict.fromkeys(
        DENSE_SPANS, 1)
    stages = info["stages"]
    assert set(stages) == {"plan_s", "score_s", "assemble_s"}
    assert stages["plan_s"] == rec["preprocess.plan"]["s"]
    assert stages["score_s"] == rec["preprocess.score"]["s"]
    assert stages["assemble_s"] == rec["preprocess.assemble"]["s"]
    assert info["preprocess_s"] == rec["preprocess.build"]["s"]
    assert (rec["preprocess.rank_map"]["s"] + rec["preprocess.gather"]["s"]
            <= stages["assemble_s"])
    assert sum(stages.values()) <= info["preprocess_s"]


def test_streaming_stages_are_the_spans_seconds(profiled):
    _, info = build_score_table_fused(_data(), q=2, s=2, prune_delta=3.0,
                                      return_info=True)
    rec = spans.snapshot()["spans"]
    stages = info["stages"]
    assert set(stages) == {"plan_s", "stream_s", "finalize_s"}
    for stage in ("plan", "stream", "finalize"):
        assert stages[stage + "_s"] == rec["preprocess." + stage]["s"]
    assert info["preprocess_s"] == rec["preprocess.build"]["s"]
    assert sum(stages.values()) <= info["preprocess_s"]


def test_segment_spans_and_runner_compile(profiled):
    class Collector:
        def check(self, drained, done):
            assert drained["taps"] == 0
            return None

    @jax.jit
    def run_segment(states, trace, start, *, length):
        return states + length + start, trace

    sup = RunSupervisor(iters=12, seg=4, chains=2, collector=Collector())
    sup.begin(run_segment, jnp.zeros(2, jnp.int32), init_trace(2, 3))
    while sup.advance():
        pass
    rec = spans.snapshot()
    assert rec["spans"]["segment"]["count"] == 3
    assert rec["spans"]["segment.wait"]["count"] == 3
    wait, seg = rec["spans"]["segment.wait"], rec["spans"]["segment"]
    assert 0 < wait["s"] <= seg["s"]
    assert rec["compile"]["segment"]["s"] > 0
    assert np.asarray(sup.states).tolist() == [24, 24]


def test_spans_nest_on_the_window_line(tmp_path):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench.trace_reduce import (WINDOW_SPAN, find_xplane,
                                        reduce_events)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            build_score_table_fused(_data(seed=1), q=2, s=2)
    finally:
        jax.profiler.stop_trace()
    planes = jax.profiler.ProfileData.from_file(find_xplane(str(tmp_path)))
    host = None
    for plane in planes.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if any(n == WINDOW_SPAN for n, _, _ in evs):
                host = evs
    assert host is not None
    by_name = {n: (s, e) for n, s, e in host}
    window = by_name[WINDOW_SPAN]
    chain = [window] + [by_name[n] for n in ("preprocess.build",
                                              "preprocess.assemble",
                                              "preprocess.rank_map")]
    for (s0, e0), (s1, e1) in zip(chain, chain[1:]):
        assert s0 <= s1 and e1 <= e0
    # a device idle through the window: the rank map is where it waited
    red = reduce_events([{"ops": [], "modules": []}], host)
    assert red.gap_ns["preprocess.rank_map"] > 0
