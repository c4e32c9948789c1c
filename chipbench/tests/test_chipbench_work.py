"""The per-layer metrics' work functions against hand counts at tiny sizes,
and each reader on a hand-built reduced trace."""
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import pytest  # noqa: E402

from chipbench.trace_reduce import Reduced  # noqa: E402

METRICS = os.path.join(os.path.dirname(HERE), "metrics")
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_order_step_bytes_by_hand():
    m = _reader("order_step_roofline")
    # S = 40 sets, s = 3 (P = 2 planes), window 2, one chain:
    # rows 2*40*4 = 320; planes read and written 2*2*2*ceil(40/32)*4 = 64;
    # membership rows 2*2*4 = 16
    assert m.bytes_per_iteration(1, 40, 3, 2) == 320 + 64 + 16
    # s = 4 needs 3 planes (counts 0..4); chains multiply
    assert m.bytes_per_iteration(3, 64, 4, 8) == 3 * (
        8 * 64 * 4 + 2 * 8 * 3 * 2 * 4 + 8 * 2 * 4)


def test_order_step_roofline_reads_module_time():
    m = _reader("order_step_roofline")
    red = Reduced(window_ns=1e9, busy_ns=1e9, devices=1,
                  module_ns={"jit_run_segment": 4e9})
    counters = {"chains": 1, "S": 40, "s": 3, "window": 2,
                "traced_steps": 10}
    # least 400 B / 10 B/s = 40 s per iteration, measured 0.4 s: 10000 %
    assert m.read(red, counters, {}, PEAK) == pytest.approx(
        100 * 40 / 0.4)
    assert m.read(Reduced(1.0, 1.0, 1), counters, {}, PEAK) is None


def test_count_score_work_by_hand():
    m = _reader("count_score_roofline")
    # n = 3 columns, s = 1: the empty set (1 bin) and 3 singletons (q bins)
    n, mm, q = 3, 5, 2
    flops = 1 * 2 * 1 * mm * n * q + 3 * 2 * q * mm * n * q
    nbytes = 4 * (mm + n) * 4
    assert m.work_per_table(n, mm, q, 1) == (flops, nbytes)


def test_count_score_roofline_sums_kernel_events():
    m = _reader("count_score_roofline")
    red = Reduced(1e9, 1e9, 1, op_total_ns={
        "%fused_scores_pallas.3": 1e9, "%fused_scores_pallas.4": 1e9,
        "%fusion.1": 5e9})
    counters = {"n": 3, "m": 5, "q": 2, "s": 1, "traced_builds": 2}
    flops, nbytes = m.work_per_table(3, 5, 2, 1)
    least = 2 * max(flops / 100.0, nbytes / 10.0)
    assert m.read(red, counters, {}, PEAK) == pytest.approx(100 * least / 2)
    assert m.read(Reduced(1e9, 1e9, 1), counters, {}, PEAK) is None


def test_assembly_share_and_idle():
    share = _reader("assembly_host_share")
    counters = {"traced_preprocess_s": 8.0, "traced_plan_s": 1.0,
                "traced_assemble_s": 3.0}
    assert share.read(None, counters, {}, PEAK) == pytest.approx(50.0)
    assert share.read(None, {"traced_preprocess_s": 0.0}, {}, PEAK) is None
    red = Reduced(window_ns=200.0, busy_ns=150.0, devices=1)
    assert _reader("device_idle").read(red, {}, {}, PEAK) == \
        pytest.approx(25.0)
