"""The trace reduction on hand-built traces: busy union, self times of
nested ops, idle time by host event, module sums, the window clip, and
the choice of the cell's own device planes."""
import os
import sys
from types import SimpleNamespace as NS

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from chipbench.trace_reduce import (reduce_events, short_name,  # noqa: E402
                                    trace_events)

WHILE = "%while.9 = (s32[]) while(s32[] %t), condition=%c, body=%b"
FUSION = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
KERNEL = "%fused_scores_pallas.3 = f32[4,1,60]{2,1,0} custom-call(...)"


def _device():
    # a loop [100, 300) holding two ops, then a lone kernel [500, 600)
    return {"ops": [(WHILE, 100, 300), (FUSION, 120, 170),
                    (FUSION, 200, 260), (KERNEL, 500, 600)],
            "modules": [("jit_run_segment(123)", 100, 300),
                        ("jit_gather(9)", 500, 600)]}


def _host():
    return [("bench.window", 50, 700),
            ("bench.segment", 60, 690),
            ("np.asarray(jax.Array)", 280, 480),
            ("bench.collector_check", 620, 690)]


def test_short_names():
    assert short_name(WHILE) == "%while.9"
    assert short_name(KERNEL) == "%fused_scores_pallas.3"
    assert short_name("jit_run_segment(6329505471132494722)") == \
        "jit_run_segment"


def test_busy_union_and_idle_share():
    red = reduce_events([_device()], _host())
    assert red.window_ns == 650
    assert red.busy_ns == 200 + 100
    assert red.idle_share == pytest.approx(1 - 300 / 650)


def test_nested_ops_count_self_time():
    red = reduce_events([_device()], _host())
    assert red.op_self_ns["%while.9"] == 200 - 50 - 60
    assert red.op_self_ns["%fusion.3"] == 110
    assert red.op_total_ns["%while.9"] == 200
    assert red.op_total_ns["%fused_scores_pallas.3"] == 100
    assert sum(red.op_self_ns.values()) == red.busy_ns


def test_gaps_go_to_the_innermost_host_event():
    red = reduce_events([_device()], _host())
    # gaps [50, 100), [300, 500), [600, 700): [50, 60) and [690, 700) are
    # under the window span alone; np.asarray covers [300, 480) and the
    # collector check [620, 690); bench.segment the rest
    assert red.gap_ns == {"bench.window": 20, "bench.segment": 80,
                          "np.asarray(jax.Array)": 180,
                          "bench.collector_check": 70}
    assert sum(red.gap_ns.values()) == red.window_ns - red.busy_ns
    bd = red.breakdown()
    assert bd["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                  pytest.approx(180e-9)]
    assert bd["device_ops"][0][0] == "%fusion.3"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_modules_and_window_clip():
    host = [("bench.window", 150, 550)]
    red = reduce_events([_device()], host)
    # ops clipped to [150, 550): loop 150..300, fusions 150..170 and
    # 200..260, kernel 500..550
    assert red.busy_ns == 150 + 50
    assert red.op_total_ns["%fused_scores_pallas.3"] == 50
    # a module counts where it starts inside the window
    assert red.module_ns == {"jit_gather": 100}


def test_busy_is_averaged_over_devices():
    idle = {"ops": [], "modules": []}
    red = reduce_events([_device(), idle], _host())
    assert red.devices == 2
    assert red.busy_ns == 150


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        reduce_events([_device()], [("bench.segment", 0, 10)])


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=a, end_ns=b)
                            for n, a, b in evs])
        for ln, evs in lines.items()])


def test_only_the_cells_device_planes_are_read():
    dev = _device()
    planes = [_plane("/device:TPU:0", {"XLA Ops": dev["ops"],
                                       "XLA Modules": dev["modules"]}),
              _plane("/device:TPU:1", {"XLA Ops": [], "XLA Modules": []}),
              _plane("/device:TPU:0 SparseCore", {"XLA Ops": []}),
              _plane("/host:CPU", {"python": [("other", 0, 5)],
                                   "main": _host()})]
    red = reduce_events(*trace_events(planes, [0]))
    assert red.devices == 1
    assert red.busy_ns == 300
    assert red.idle_share == pytest.approx(1 - 300 / 650)
    both = reduce_events(*trace_events(planes, [0, 1]))
    assert both.devices == 2 and both.busy_ns == 150
    with pytest.raises(ValueError, match="device planes"):
        trace_events(planes, [2])
