"""The harness end to end on the CPU at a tiny size.

* ``run.py`` refuses to run, and prints no result, without a TPU;
* a cell, its configuration, traffic, limits and a per-layer metric are
  found by name from files alone (a throwaway cell in a temporary root);
* the compile cache writes no entry over its size limit;
* a sound run is ``correct``; the control (the reference in bfloat16 in the
  program's place) and each fault the cells can have, planted under the
  timed path, come out not correct.

The runs skip the harness's look for a chip; everything else is the run.
"""
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

import pytest  # noqa: E402

TINY = {"network": "synth", "n": 8, "q": 2, "s": 2, "m": 200, "S": 29,
        "chains": 2, "window": 2, "trace_every": 2, "check_every": 16}
TINY_TABLE = dict(TINY, n=10, S=46)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src")])
    return env


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-like root holding only throwaway cells: tiny.mcmc and
    tiny.table, plus a per-layer metric of its own."""
    root = tmp_path_factory.mktemp("tinybench")
    bench = root / "bench"
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    (bench / "configs").mkdir()
    (bench / "limits").mkdir()
    base = json.load(open(os.path.join(BENCH, "configs", "paper60.json")))
    for name, over in (("tiny", TINY), ("tinyt", TINY_TABLE)):
        (bench / "configs" / f"{name}.json").write_text(
            json.dumps(dict(base, name=name, **over)))
    shutil.copy(os.path.join(BENCH, "limits", "paper60.mcmc.json"),
                bench / "limits" / "tiny.mcmc.json")
    shutil.copy(os.path.join(BENCH, "limits", "paper60.preprocess.json"),
                bench / "limits" / "tiny.table.json")
    (bench / "metrics" / "tiny_probe.py").write_text(textwrap.dedent("""
        def read(trace, counters, config, peak):
            return 2.0 * counters["x"] if "x" in counters else None
        """))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["paths"] = ["bench"]
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny", "tinyt")]
    spec["workloads"] = [
        {"name": "tiny.mcmc", "config": "tiny", "traffic": "mcmc_steady",
         "chips": 1, "why": "test"},
        {"name": "tiny.table", "config": "tinyt", "traffic": "table_stream",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            mcmc = any(w.endswith(".mcmc") for w in m["workloads"])
            m["workloads"] = ["tiny.mcmc" if mcmc else "tiny.table"]
    spec["per_layer"].append(
        {"name": "tiny_probe", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "chain_iters_per_s", "workloads": ["tiny.mcmc"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_run_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "paper60.mcmc", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_cell_and_metric_found_by_name(tiny_root):
    from chipbench.run import Cell, per_layer_metrics
    from chipbench.trace_reduce import Reduced

    cell = Cell("tiny.mcmc", str(tiny_root))
    assert cell.config["n"] == 8 and cell.traffic["generator"] == "mcmc"
    assert "stuck_chains" in cell.limits
    red = Reduced(window_ns=100.0, busy_ns=60.0, devices=1,
                  module_ns={"jit_run_segment": 50.0})
    counters = {"x": 4, "chains": 2, "S": 29, "s": 2, "window": 2,
                "traced_steps": 5}
    got = per_layer_metrics(cell, red, counters, {"hbm_bytes_per_s": 1e9})
    assert got["tiny_probe"] == {"value": 8.0, "unit": "%"}
    assert got["device_idle.mcmc"]["value"] == pytest.approx(40.0)
    assert "order_step_roofline" in got
    del counters["x"]
    assert "tiny_probe" not in per_layer_metrics(cell, red, counters,
                                                 {"hbm_bytes_per_s": 1e9})


CACHE_SCRIPT = textwrap.dedent('''
    import json, os, sys
    import numpy as np
    import jax, jax.numpy as jnp
    from chipbench.run import use_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    skipped = use_cache(sys.argv[1], max_entry=200_000)
    table = np.random.default_rng(0).normal(size=400_000).astype(np.float32)
    jax.jit(lambda x: x + jnp.asarray(table))(jnp.ones(400_000))
    jax.jit(lambda x: 2 * x)(jnp.ones(4)).block_until_ready()
    print(json.dumps({"skipped": skipped, "sizes": [
        os.path.getsize(os.path.join(sys.argv[1], f))
        for f in os.listdir(sys.argv[1])]}))
''')


def test_cache_leaves_out_programs_with_a_large_constant(tmp_path):
    proc = subprocess.run([sys.executable, "-c", CACHE_SCRIPT,
                           str(tmp_path / "cache")], cwd=ROOT, env=_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert [name for name, _ in got["skipped"]] == ["jit__lambda"]
    assert got["skipped"][0][1] > 1_000_000
    assert got["sizes"] and max(got["sizes"]) <= 200_000


SCRIPT = textwrap.dedent('''
    import json, sys
    import jax, jax.numpy as jnp
    import repro.preprocess
    from chipbench import run as R
    from chipbench.control import readings

    root, workload = sys.argv[1], sys.argv[2]
    real_load = R.Cell.load
    real_build = repro.preprocess.build_score_table_fused

    def segment_fault(make):
        def plant(d):
            d.sup._run_segment = make(d.sup._run_segment)
        return plant

    def unchanged(seg):
        return lambda states, trace, start, *, length: (states, trace)

    def half_chains(seg):
        def run(states, trace, start, *, length):
            new, trace = seg(states, trace, start, length=length)
            h = states.pos.shape[0] // 2
            keep = lambda a, b: a.at[:h].set(b[:h])
            fields = ("pos", "score", "cur_idx", "cur_ls", "mask_planes",
                      "best_score", "best_idx", "best_pos", "accepts", "step")
            return new._replace(**{f: keep(getattr(new, f), getattr(states, f))
                                   for f in fields}), trace
        return run

    def altered_score(seg):
        def run(states, trace, start, *, length):
            new, trace = seg(states, trace, start, length=length)
            return new._replace(cur_ls=new.cur_ls.at[0, 0].add(1.0)), trace
        return run

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

    CASES = {"mcmc": [("sound", None),
                      ("unchanged", segment_fault(unchanged)),
                      ("half_chains", segment_fault(half_chains)),
                      ("altered_score", segment_fault(altered_score))],
             "table": [("sound", None), ("half_samples", half_samples),
                       ("altered_entry", altered_entry)]}

    for name, fault in CASES[workload.split(".")[1]]:
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
        print(json.dumps({"case": name, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": sorted(res["metrics"]),
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


@pytest.mark.parametrize("workload,rate,faults", [
    ("tiny.mcmc", "chain_iters_per_s",
     ["unchanged", "half_chains", "altered_score"]),
    ("tiny.table", "table_scores_per_s", ["half_samples", "altered_entry"]),
])
def test_sound_run_passes_control_and_faults_fail(tiny_root, workload, rate,
                                                  faults):
    script = tiny_root / "drive.py"
    script.write_text(SCRIPT)
    proc = subprocess.run([sys.executable, str(script), str(tiny_root),
                           workload], cwd=str(tiny_root), env=_env(),
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = {r["case"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    sound = rows["sound"]
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    assert sound["attempted"] >= 1
    assert sound["metrics"] == sorted([rate, "setup_s"])
    for fault in faults:
        assert not rows[fault]["correct"], (fault, rows[fault]["checks"])
        assert rows[fault]["failed"] == 1
    assert rows["control"]["program_correct"]
    assert not rows["control"]["correct"]
