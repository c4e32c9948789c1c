"""The continuous-data cell on the CPU at a tiny size: its sampler draws
what the program's draws, its reference agrees with the program's float64
oracle, its reader counts the configuration's work, the generator builds
the configured shape, and a sound run is ``correct`` while the control and
each planted fault are not. One fault weights the Schur log by
(N + a + l)/2 in place of (N + a + l + 1)/2: every score moves by half a
log, which a check of signs or of the best parent set would miss."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import gen, reference_bge  # noqa: E402
from chipbench.generators import table_bge  # noqa: E402
from chipbench.tests.test_chipbench_run import _env  # noqa: E402

TINY = {"n": 8, "arcs": 9, "s": 2, "m": 200, "S": 29, "alpha_w": 10.0}


def _config(**over):
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "magicirri64.json")))
    return dict(base, **over)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_bge_sampler_equals_the_programs(seed):
    from repro.data.networks import network_data

    cfg = _config()
    want_adj, want = network_data("synth", cfg["m"], 2, seed, n_synth=64,
                                  arcs=102, score="bge")
    adj, data = table_bge.network_data(cfg, gen.dataset_rng(seed))
    np.testing.assert_array_equal(adj, want_adj)
    np.testing.assert_array_equal(data, want)
    assert adj.sum() == 102 and adj.sum(0).max() <= 4
    assert data.shape == (1000, 64)


def test_bge_reference_matches_the_oracle():
    """float32 reference against the program's float64 oracle: a few 1e-6
    of a node's largest |score| (float32 moments and eliminations); the
    bfloat16 control is off by more than 1e-3."""
    from repro.core import build_score_table

    cfg = _config(**TINY)
    _, x = table_bge.network_data(cfg, gen.dataset_rng(11))
    want = np.asarray(build_score_table(x, q=None, s=3, score="bge").table)
    kw = dict(s=3, gamma=0.1, alpha_mu=1.0, alpha_w=10.0)
    got = reference_bge.reference_table(x, **kw)
    assert reference_bge.row_gap(got, want) < 2e-5
    low = reference_bge.reference_table(x, dtype=jnp.bfloat16, **kw)
    assert reference_bge.row_gap(low, want) > 1e-3
    assert reference_bge.row_gap(got[:, :5], want) == float("inf")
    assert reference_bge.row_gap(got.at[2, 3].set(jnp.nan), want) == \
        float("inf")


def test_bge_roofline_counts_the_configurations_work():
    from chipbench.metrics import bge_score_roofline as m
    from chipbench.trace_reduce import Reduced

    # n = 3 columns, s = 1: the empty set (no work, 3 scores) and three
    # singletons: 1/3 + 3 + 6 FLOPs and 4 (3 + 1) bytes each
    assert m.work_per_table(3, 1) == (3 * (1 / 3 + 3 + 6), 4 * 3 + 3 * 16)
    flops, nbytes = m.work_per_table(64, 4)
    assert nbytes == 4 * sum(
        c * (64 + k) for k, c in enumerate([1, 64, 2016, 41664, 635376]))
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    cfg = {"n": 64, "s": 4}
    red = Reduced(window_ns=1e9, busy_ns=1e9, devices=1,
                  op_total_ns={"%bge_scores.3": 1e9, "%bge_scores.4": 1e9,
                               "%fusion.2": 7e9})
    counters = {"traced_builds": 2}
    least = 2 * max(flops / 1e12, nbytes / 1e9)
    assert m.read(red, counters, cfg, peak) == pytest.approx(
        100.0 * least / 2.0)
    assert m.read(Reduced(1e9, 1e9, 1), counters, cfg, peak) is None
    assert m.read(red, {}, cfg, peak) is None


@pytest.fixture(scope="module")
def bge_root(tmp_path_factory):
    """A checkout-like root holding one tiny continuous-data table cell,
    tinyg.table: n = 8, 9 arcs, s = 2, m = 200."""
    root = tmp_path_factory.mktemp("bgebench")
    bench = root / "bench"
    for sub in ("traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "configs" / "tinyg.json").write_text(
        json.dumps(_config(name="tinyg", **TINY)))
    shutil.copy(os.path.join(BENCH, "limits",
                             "magicirri64.preprocess.json"),
                bench / "limits" / "tinyg.table.json")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["paths"] = ["bench"]
    spec["configs"] = [{"name": "tinyg", "source": "test",
                        "file": "bench/configs/tinyg.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tinyg.table", "config": "tinyg",
                          "traffic": "table_stream_bge", "chips": 1,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tinyg.table"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


SCRIPT = textwrap.dedent('''
    import json, sys
    import repro.preprocess
    import repro.preprocess.bge as bge
    from chipbench import run as R
    from chipbench.control import readings

    root, workload = sys.argv[1], sys.argv[2]
    real_load = R.Cell.load
    real_build = repro.preprocess.build_score_table_fused
    real_consts = bge.bge_constants

    def half_samples(d):
        def build(data, **kw):
            return real_build(data[: data.shape[0] // 2], **kw)
        repro.preprocess.build_score_table_fused = build

    def altered_entry(d):
        def build(data, **kw):
            st, info = real_build(data, **kw)
            st.table = st.table.at[1, 3].add(1.0)
            return st, info
        repro.preprocess.build_score_table_fused = build

    def schur_weight(d):
        def consts(N, n, s, alpha_mu, alpha_w):
            c = real_consts(N, n, s, alpha_mu, alpha_w).copy()
            c[s + 1:] -= 0.5                  # (N + a + l)/2
            return c
        bge.bge_constants = consts

    for name, fault in [("sound", None), ("half_samples", half_samples),
                        ("altered_entry", altered_entry),
                        ("schur_weight", schur_weight)]:
        def load(self, seed, fault=fault):
            d = real_load(self, seed)
            setup = d.setup
            def planted():
                setup()
                if fault:
                    fault(d)
            d.setup = planted
            return d
        R.Cell.load = load
        res = R.run(workload, 2 ** 31 + 11, 0.3, False, root=root,
                    find_chip=False)
        repro.preprocess.build_score_table_fused = real_build
        bge.bge_constants = real_consts
        print(json.dumps({"case": name, "correct": res["correct"],
                          "failed": res["failed"],
                          "metrics": sorted(res["metrics"]),
                          "counters": res["counters"],
                          "checks": res["checks"]}), flush=True)
    R.Cell.load = real_load
    cell = R.Cell(workload, root)
    cell.devices(False)
    row = readings(cell, 5, 0.3, True)
    ok = all(v is not None and v <= cell.limits[k]
             for k, v in row["control"].items())
    sound = all(v <= cell.limits[k] for k, v in row["program"].items())
    print(json.dumps({"case": "control", "correct": ok,
                      "program_correct": sound}), flush=True)
''')


def test_bge_cell_sound_run_passes_control_and_faults_fail(bge_root):
    script = bge_root / "drive.py"
    script.write_text(SCRIPT)
    proc = subprocess.run([sys.executable, str(script), str(bge_root),
                           "tinyg.table"], cwd=str(bge_root), env=_env(),
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = {r["case"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    sound = rows["sound"]
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    assert sound["metrics"] == ["setup_s", "table_scores_per_s"]
    assert sound["counters"]["score"] == "bge"
    assert sound["counters"]["S"] == 29 and sound["counters"]["builds"] >= 1
    for fault in ("half_samples", "altered_entry", "schur_weight"):
        assert not rows[fault]["correct"], (fault, rows[fault]["checks"])
        assert rows[fault]["failed"] == 1
    assert rows["control"]["program_correct"]
    assert not rows["control"]["correct"]
