"""The mixed-arity cell on the CPU at a tiny size: its sampler draws what
the program's draws, its reference agrees with the program's oracle and
with the one-arity reference, its readers count the configuration's work,
and a sound run is ``correct`` while the control and each planted fault are
not."""
import json
import os
import shutil
import subprocess
import sys

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

from chipbench import gen, gen_arity, reference, reference_arity  # noqa: E402
from chipbench.tests.test_chipbench_run import SCRIPT, _env  # noqa: E402


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_arity_sampler_equals_the_programs(seed):
    from repro.data.networks import ALARM_ARITY
    from repro.launch.bn_learn import _network_data

    assert list(ALARM_ARITY) == gen_arity.ALARM_ARITY
    want_adj, want = _network_data("alarm", 300, ALARM_ARITY, seed)
    adj, data = gen_arity.network_data("alarm", 300, gen_arity.ALARM_ARITY,
                                       gen.dataset_rng(seed), 37)
    np.testing.assert_array_equal(adj, want_adj)
    np.testing.assert_array_equal(data, want)
    # one arity for every variable draws what the one-arity copy draws
    _, one = gen_arity.network_data("alarm", 300, 3, gen.dataset_rng(seed),
                                    37)
    np.testing.assert_array_equal(
        one, gen.network_data("alarm", 300, 3, gen.dataset_rng(seed), 37)[1])


def test_alarm_arities_give_the_published_parameter_count():
    r = np.asarray(gen_arity.ALARM_ARITY)
    adj = gen.alarm_adjacency()
    params = sum((r[i] - 1) * np.prod(r[adj[:, i] != 0])
                 for i in range(len(r)))
    assert (params, r.sum(), adj.sum(0).max()) == (509, 105, 4)


def test_arity_reference_matches_the_oracle_and_the_one_arity_reference():
    from repro.core import build_score_table

    rng = np.random.default_rng(6)
    r = (2, 4, 3, 2, 4, 3, 2)
    data = rng.integers(0, np.asarray(r), (150, 7)).astype(np.int32)
    want = np.asarray(build_score_table(data, q=r, s=3, gamma=0.1,
                                        ess=1.0).table)
    got = np.asarray(reference_arity.reference_table(data, q=r, s=3,
                                                     gamma=0.1, ess=1.0))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-4)
    low = np.asarray(reference_arity.reference_table(
        data, q=r, s=3, gamma=0.1, ess=1.0, dtype=jnp.bfloat16), np.float32)
    assert np.max(np.abs(low - want) / np.abs(want)) > 1e-3
    one = rng.integers(0, 3, (120, 6)).astype(np.int32)
    np.testing.assert_allclose(
        np.asarray(reference_arity.reference_table(one, q=3, s=2, gamma=0.1,
                                                   ess=1.0)),
        np.asarray(reference.reference_table(one, q=3, s=2, gamma=0.1,
                                             ess=1.0)),
        rtol=2e-6, atol=1e-4)


def test_readers_count_the_configurations_work():
    from chipbench.metrics import (arity_pad_share, count_score_roofline,
                                   mixed_count_roofline)
    from chipbench.trace_reduce import Reduced

    assert mixed_count_roofline.work_per_table([3] * 60, 1000, 4) == \
        count_score_roofline.work_per_table(60, 1000, 3, 4)
    flops, nbytes = mixed_count_roofline.work_per_table(
        gen_arity.ALARM_ARITY, 1000, 4)
    assert flops == 2 * 1000 * 105 * 4_420_072
    assert nbytes == 74_519 * (1000 + 37) * 4
    cfg = {"n": 37, "m": 1000, "s": 4, "q": gen_arity.ALARM_ARITY}
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    red = Reduced(window_ns=1e9, busy_ns=1e9, devices=1,
                  op_total_ns={"%fused_scores_pallas.7": 2e9})
    counters = {"traced_builds": 2}
    assert mixed_count_roofline.read(red, counters, cfg, peak) == \
        pytest.approx(100.0 * 2 * flops / 1e12 / 2.0)
    idle = Reduced(window_ns=1e9, busy_ns=0.0, devices=1)
    assert mixed_count_roofline.read(idle, counters, cfg, peak) is None
    assert arity_pad_share.read(red, {"bins_real": 3, "bins_computed": 4},
                                cfg, peak) == pytest.approx(25.0)
    assert arity_pad_share.read(red, counters, cfg, peak) is None


@pytest.fixture(scope="module")
def arity_root(tmp_path_factory):
    """A checkout-like root holding one tiny mixed-arity table cell,
    tinya.table: ALARM at its arities with s = 2, m = 200."""
    root = tmp_path_factory.mktemp("aritybench")
    bench = root / "bench"
    for sub in ("traffic", "metrics", "limits"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    (bench / "configs").mkdir()
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "alarm37arity.json")))
    (bench / "configs" / "tinya.json").write_text(
        json.dumps(dict(base, name="tinya", s=2, m=200, S=667)))
    shutil.copy(os.path.join(BENCH, "limits",
                             "alarm37arity.preprocess.json"),
                bench / "limits" / "tinya.table.json")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["paths"] = ["bench"]
    spec["configs"] = [{"name": "tinya", "source": "test",
                        "file": "bench/configs/tinya.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tinya.table", "config": "tinya",
                          "traffic": "table_stream_arity", "chips": 1,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tinya.table"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_arity_cell_sound_run_passes_control_and_faults_fail(arity_root):
    script = arity_root / "drive.py"
    script.write_text(SCRIPT)
    proc = subprocess.run([sys.executable, str(script), str(arity_root),
                           "tinya.table"], cwd=str(arity_root), env=_env(),
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = {r["case"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    sound = rows["sound"]
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    assert sound["metrics"] == ["setup_s", "table_scores_per_s"]
    for fault in ("half_samples", "altered_entry"):
        assert not rows[fault]["correct"], (fault, rows[fault]["checks"])
        assert rows[fault]["failed"] == 1
    assert rows["control"]["program_correct"]
    assert not rows["control"]["correct"]
