"""Convergence telemetry (ISSUE 7): R̂ diagnostics, the in-scan taps, the
JSONL trace schema, checkpoint compatibility and the end-to-end
--telemetry / --stop-on-converge driver path."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adjacency_from_ranks
from repro.core.combinatorics import (binom_table, n_parent_sets,
                                      size_offsets, unrank_parent_set)
from repro.telemetry import (SCHEMA, Collector, TraceState,
                             adjacency_bits_from_ranks, drain, edge_rhat,
                             init_trace, make_tap, median_outliers,
                             read_rows, split_rhat, unrank_parent_sets_jax,
                             validate_row, write_rows)
from repro.telemetry.validate import validate_file


# ------------------------------------------------------------------- R-hat
def test_split_rhat_identical_chains_near_one():
    rng = np.random.default_rng(0)
    base = rng.normal(0.0, 1.0, 256)
    traces = np.stack([base + rng.normal(0, 1e-3, 256) for _ in range(4)])
    r = split_rhat(traces)
    assert np.isfinite(r) and r < 1.05


def test_split_rhat_shifted_chain_large():
    rng = np.random.default_rng(1)
    traces = rng.normal(0.0, 1.0, (4, 256))
    traces[0] += 50.0                       # one chain stuck in another mode
    assert split_rhat(traces) > 2.0


def test_split_rhat_detects_within_chain_drift():
    # split-R̂'s whole point vs plain R̂: halves of ONE drifting chain
    # disagree, so identical-but-drifting chains still flag
    t = np.linspace(0.0, 10.0, 256)[None, :].repeat(4, axis=0)
    assert split_rhat(t) > 1.5


def test_split_rhat_degenerate():
    assert np.isnan(split_rhat(np.zeros((4, 2))))        # too short
    assert split_rhat(np.zeros((4, 64))) == 1.0          # frozen, agreeing
    frozen = np.zeros((2, 64))
    frozen[1] = 3.0                                      # frozen, disjoint
    assert split_rhat(frozen) == np.inf


def test_edge_rhat_concordant_vs_discordant():
    n, T = 6, 200
    rng = np.random.default_rng(2)
    p = rng.uniform(0.2, 0.8, (n, n))
    conc = np.stack([rng.binomial(T, p) for _ in range(4)])
    r_conc, _ = edge_rhat(conc, T)
    assert np.isfinite(r_conc) and r_conc < 1.2

    disc = conc.copy()
    disc[0, 1, 2] = 0
    disc[1, 1, 2] = T                       # chains disagree on edge 1->2
    r_disc, mat = edge_rhat(disc, T)
    assert r_disc > 1.5
    assert mat[1, 2] == r_disc              # the disagreeing edge is the max


def test_edge_rhat_degenerate():
    r, _ = edge_rhat(np.zeros((1, 4, 4)), 10)            # single chain
    assert np.isnan(r)
    r, _ = edge_rhat(np.zeros((3, 4, 4)), 0)             # no samples yet
    assert np.isnan(r)


def test_median_outliers():
    vals = np.array([1.0, 1.1, 0.9, 1.0, 8.0])
    out = median_outliers(vals, 4.0)
    assert out.tolist() == [False, False, False, False, True]
    # floor suppresses flags when all-chain spread is absolutely tiny
    assert not median_outliers(np.array([1.0, 1.0, 1.0001]), 4.0,
                               floor=0.02).any()


# ------------------------------------------------- device-side unranking
@pytest.mark.parametrize("n,s", [(6, 3), (12, 4), (20, 2)])
def test_unrank_jax_matches_host_oracle(n, s):
    S = n_parent_sets(n - 1, s)
    rng = np.random.default_rng(n * 100 + s)
    ranks = rng.integers(0, S, 64).astype(np.int32)
    off = jnp.asarray(size_offsets(n - 1, s), jnp.int32)
    B = jnp.asarray(binom_table(n - 1, s + 1), jnp.int32)
    got = np.asarray(unrank_parent_sets_jax(jnp.asarray(ranks), off, B, s))
    for r, row in zip(ranks, got):
        want = unrank_parent_set(n - 1, s, int(r))
        want = np.pad(np.asarray(want, np.int32), (0, s - len(want)),
                      constant_values=-1)
        np.testing.assert_array_equal(row, want)


@pytest.mark.parametrize("n,s", [(8, 3), (16, 4)])
def test_adjacency_bits_matches_adjacency_from_ranks(n, s):
    S = n_parent_sets(n - 1, s)
    rng = np.random.default_rng(7)
    ranks = rng.integers(0, S, n).astype(np.int32)
    off = jnp.asarray(size_offsets(n - 1, s), jnp.int32)
    B = jnp.asarray(binom_table(n - 1, s + 1), jnp.int32)
    got = np.asarray(adjacency_bits_from_ranks(jnp.asarray(ranks), off, B, s))
    want = adjacency_from_ranks(ranks, s=s)
    np.testing.assert_array_equal(got, np.asarray(want, got.dtype))


# --------------------------------------------------------------- the taps
def _fake_states(n_chains, n, score, accepts, ranks, win_idx=0):
    from repro.core.mcmc import ChainState
    C = n_chains
    return ChainState(
        key=jax.random.split(jax.random.key(0), C),
        pos=jnp.zeros((C, n), jnp.int32),
        score=jnp.full((C,), score, jnp.float32),
        cur_idx=jnp.broadcast_to(jnp.asarray(ranks, jnp.int32), (C, n)),
        best_score=jnp.full((C,), score, jnp.float32),
        best_idx=jnp.zeros((C, n), jnp.int32),
        best_pos=jnp.zeros((C, n), jnp.int32),
        accepts=jnp.full((C,), accepts, jnp.int32),
        cur_ls=jnp.zeros((C, n), jnp.float32),
        mask_planes=jnp.zeros((C, 0), jnp.uint32),
        win_idx=jnp.full((C,), win_idx, jnp.int32),
        adapt_err=jnp.zeros((C,), jnp.float32),
        step=jnp.zeros((C,), jnp.int32),
    )


def test_tap_cadence_and_ring_wrap():
    n, s, C, cap = 6, 2, 2, 4
    tap = make_tap(n, s, trace_every=3)
    trace = init_trace(C, n, cap=cap)
    for it in range(1, 19):
        st = _fake_states(C, n, float(it), it, np.zeros(n, np.int32))
        trace = tap(trace, st, jnp.int32(it))
    # 18 iterations / every 3 = 6 taps into a cap-4 ring
    assert int(trace.taps) == 6
    assert int(trace.edge_taps) == 6
    snap = drain(trace)
    assert snap["scores"].shape == (C, 4)
    # oldest-first linearisation: taps at iterations 9, 12, 15, 18 survive
    np.testing.assert_allclose(snap["scores"][0], [9.0, 12.0, 15.0, 18.0])
    # win_hist counts EVERY iteration, not just taps
    assert snap["win_hist"].sum() == 18 * C


def test_tap_accumulates_edge_counts():
    n, s = 8, 3
    S = n_parent_sets(n - 1, s)
    rng = np.random.default_rng(3)
    ranks = rng.integers(0, S, n).astype(np.int32)
    tap = make_tap(n, s, trace_every=1)
    trace = init_trace(2, n)
    st = _fake_states(2, n, -1.0, 0, ranks)
    trace = tap(trace, st, jnp.int32(1))
    trace = tap(trace, st, jnp.int32(2))
    adj = np.asarray(adjacency_from_ranks(ranks, s=s))
    for c in range(2):
        np.testing.assert_array_equal(np.asarray(trace.edge_counts[c]),
                                      adj * 2)


# ----------------------------------------------------------- JSONL schema
def test_schema_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rows = [
        {"schema": SCHEMA, "kind": "meta", "run": "r1", "config": {"n": 8},
         "host": {"backend": "cpu"}},
        {"schema": SCHEMA, "kind": "stage", "run": "r1",
         "stage": "preprocess", "seconds": 0.25},
        {"schema": SCHEMA, "kind": "segment", "run": "r1", "iter": 64,
         "taps": 8, "score_mean": -10.0, "score_rhat": float("nan"),
         "edge_rhat": float("inf"), "accept_rates": [0.4, 0.5],
         "stuck_chains": [], "diverged_chains": [], "converge_hits": 0,
         "converged": False},
        {"schema": SCHEMA, "kind": "final", "run": "r1", "iters_run": 64,
         "stopped_early": False, "score_rhat": 1.01, "edge_rhat": 1.02},
    ]
    write_rows(path, rows)
    back = read_rows(path)
    assert len(back) == 4
    assert np.isnan(back[2]["score_rhat"])          # nan/inf survive JSON
    assert back[2]["edge_rhat"] == float("inf")
    info = validate_file(path)
    assert info["run"] == "r1"
    assert info["kinds"] == {"meta": 1, "stage": 1, "segment": 1, "final": 1}

    with pytest.raises(ValueError, match="missing required field"):
        validate_row({"schema": SCHEMA, "kind": "final", "run": "r1"})
    with pytest.raises(ValueError, match="schema"):
        validate_row({"schema": "bn-telemetry/v0", "kind": "meta"})
    with pytest.raises(ValueError, match="kind"):
        validate_row({"schema": SCHEMA, "kind": "mystery"})


def test_validate_file_rejects_misordered(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": SCHEMA, "kind": "stage", "run": "r",
                            "stage": "x", "seconds": 1.0}) + "\n")
    with pytest.raises(ValueError, match="first row"):
        validate_file(path)


def test_collector_emits_valid_trace(tmp_path):
    col = Collector(str(tmp_path), run_name="unit", rhat_threshold=1.1,
                    patience=2, min_taps=4)
    col.start({"n": 6, "iters": 100})
    col.stage("preprocess", 0.1, plan_s=0.02)
    rng = np.random.default_rng(0)
    base = rng.normal(-50, 1.0, 64)
    snap = {
        "scores": np.stack([base + rng.normal(0, 1e-3, 64)
                            for _ in range(3)]),
        "accepts": np.tile(np.arange(1, 65, dtype=np.int64), (3, 1)),
        "taps": 64, "win_hist": np.ones((3, 1), np.int64),
        "edge_counts": np.tile(rng.binomial(64, 0.5, (6, 6)), (3, 1, 1)),
        "edge_taps": 64, "reseeds": np.zeros(3, np.int64),
    }
    rec1 = col.check(snap, 512)
    assert not rec1["converged"]            # patience 2: one hit is not enough
    rec2 = col.check(snap, 1024)
    assert rec2["converged"]
    col.finalize(iters_run=1024, stopped_early=True)
    info = validate_file(col.path)
    assert info["kinds"] == {"meta": 1, "stage": 1, "segment": 2, "final": 1}


def test_collector_restart_truncates_stale_trace(tmp_path):
    """Reusing a run name (re-run CI smoke, retried acceptance run) must
    truncate the old trace — appending a second meta/final pair would fail
    the single-run validation contract."""
    for _ in range(2):
        col = Collector(str(tmp_path), run_name="reused", min_taps=4)
        col.start({"n": 4})
        col.finalize(iters_run=10, stopped_early=False)
    info = validate_file(col.path)
    assert info["kinds"] == {"meta": 1, "final": 1}


def test_collector_flags_stuck_chain(tmp_path):
    col = Collector(str(tmp_path), run_name="stuck", min_taps=4)
    C, L = 6, 32
    scores = np.random.default_rng(1).normal(-50, 0.5, (C, L))
    accepts = np.tile(np.arange(1, L + 1) * 10, (C, 1))
    accepts[2] = 0                          # chain 2 accepts nothing
    snap = {"scores": scores, "accepts": accepts, "taps": L,
            "win_hist": np.ones((C, 1), np.int64),
            "edge_counts": np.zeros((C, 4, 4), np.int64), "edge_taps": L,
            "reseeds": np.zeros(C, np.int64)}
    rec = col.check(snap, 320)
    assert 2 in rec["stuck_chains"]


# ------------------------------------------- checkpoint schema evolution
def test_old_13_leaf_checkpoint_backfills_trace_leaves(tmp_path):
    """A snapshot written by a pre-telemetry run (exactly the 13 ChainState
    leaves) restores into the telemetry layout: chain leaves land bitwise,
    the appended TraceState leaves keep the fresh template's values
    (allow_missing backfill) — same schema-evolution path as the 9->13 leaf
    upgrade."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.core.mcmc import ChainState
    from repro.runtime.supervisor import pack_tree, unpack_tree

    n, C = 6, 2
    fn = lambda pos: (jnp.float32(-1.0), jnp.zeros(n, jnp.int32),
                      jnp.zeros(n, jnp.float32))
    from repro.core.mcmc import init_chain
    states = jax.vmap(lambda k: init_chain(k, n, fn))(
        jax.random.split(jax.random.key(5), C))

    # pre-telemetry snapshot: trace=None -> exactly the 13-leaf layout
    old = pack_tree(states, None)
    assert len(old) == len(ChainState._fields) == 13
    save_checkpoint(str(tmp_path), 3, old)

    trace = init_trace(C, n)
    template = pack_tree(states, trace)
    assert len(template) == 13 + len(TraceState._fields)
    with pytest.raises(ValueError, match="mismatch"):
        restore_checkpoint(str(tmp_path), template, step=3)
    restored, meta = restore_checkpoint(str(tmp_path), template, step=3,
                                        allow_missing=True)
    assert len(meta["missing_leaves"]) == len(TraceState._fields)
    st2, tr2 = unpack_tree(restored, trace)
    np.testing.assert_array_equal(np.asarray(st2.pos), np.asarray(states.pos))
    assert int(tr2.taps) == 0               # backfilled fresh trace
    assert tr2.edge_counts.shape == (C, n, n)


def test_checkpoint_roundtrip_with_trace(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.core.mcmc import init_chain
    from repro.runtime.supervisor import pack_tree, unpack_tree

    n, C = 6, 2
    fn = lambda pos: (jnp.float32(-1.0), jnp.zeros(n, jnp.int32),
                      jnp.zeros(n, jnp.float32))
    states = jax.vmap(lambda k: init_chain(k, n, fn))(
        jax.random.split(jax.random.key(6), C))
    trace = init_trace(C, n)._replace(taps=jnp.int32(5),
                                      reseeds=jnp.asarray([1, 2], jnp.int32))
    save_checkpoint(str(tmp_path), 9, pack_tree(states, trace))
    restored, _ = restore_checkpoint(
        str(tmp_path), pack_tree(states, init_trace(C, n)), step=9,
        allow_missing=True)
    _, tr2 = unpack_tree(restored, init_trace(C, n))
    assert int(tr2.taps) == 5
    np.testing.assert_array_equal(np.asarray(tr2.reseeds), [1, 2])


# ------------------------------------------------------------- end to end
def _synth_data(m=300, n=8, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, size=(m, n)).astype(np.int8)


def test_learn_structure_telemetry_end_to_end(tmp_path):
    from repro.learner import LearnConfig, learn_structure

    cfg = LearnConfig(q=2, s=2, iters=300, chains=3, seed=0, window=4,
                      telemetry=True, trace_every=4, check_every=100,
                      trace_dir=str(tmp_path), run_name="e2e",
                      exchange_every=50)
    out = learn_structure(_synth_data(), cfg)
    assert out["iters_run"] == 300 and not out["stopped_early"]
    assert len(out["chain_accept_rates"]) == 3
    assert out["exchange_count"] == 6
    tele = out["telemetry"]
    assert tele is not None and np.isfinite(tele["score_rhat"])
    info = validate_file(os.path.join(str(tmp_path), "e2e.jsonl"))
    assert info["kinds"]["segment"] == 3    # 300 iters / check_every 100
    assert info["kinds"]["final"] == 1
    # segment rows carry the in-run iteration axis
    iters = [r["iter"] for r in read_rows(tele["trace_path"])
             if r["kind"] == "segment"]
    assert iters == [100, 200, 300]


def test_learn_structure_stop_on_converge(tmp_path):
    from repro.learner import LearnConfig, learn_structure

    cfg = LearnConfig(q=2, s=2, iters=2000, chains=4, seed=0, window=4,
                      stop_on_converge=True, trace_every=4, check_every=100,
                      patience=2, rhat_threshold=1.2,
                      trace_dir=str(tmp_path), run_name="conv",
                      exchange_every=50)
    out = learn_structure(_synth_data(), cfg)
    # flat posterior (random data, tiny n): chains mix almost immediately,
    # so the run must stop WELL before the iteration cap
    assert out["stopped_early"] and out["iters_run"] < 2000
    assert out["telemetry"]["converged"]
    rows = read_rows(out["telemetry"]["trace_path"])
    assert rows[-1]["kind"] == "final" and rows[-1]["stopped_early"]


def test_learn_structure_telemetry_resumes_from_plain_checkpoint(tmp_path):
    """Driver-level schema evolution: a checkpointed run WITHOUT telemetry
    leaves 13-leaf snapshots; re-running the same config WITH telemetry
    resumes from them (trace leaves backfilled) and completes."""
    from repro.learner import LearnConfig, learn_structure

    ck = str(tmp_path / "ck")
    data = _synth_data()
    cfg = LearnConfig(q=2, s=2, iters=100, chains=2, seed=0, window=4,
                      checkpoint_dir=ck, checkpoint_every=50)
    learn_structure(data, cfg)
    cfg2 = LearnConfig(q=2, s=2, iters=200, chains=2, seed=0, window=4,
                      checkpoint_dir=ck, checkpoint_every=50,
                      telemetry=True, trace_every=4,
                      trace_dir=str(tmp_path), run_name="resume")
    out = learn_structure(data, cfg2)
    assert out["iters_run"] == 200
    info = validate_file(os.path.join(str(tmp_path), "resume.jsonl"))
    assert info["kinds"]["final"] == 1


@pytest.mark.parametrize("chains,adapt", [(1, False), (1, True),
                                           (2, False), (2, True)])
def test_telemetry_does_not_change_the_walk(chains, adapt):
    """The taps are observers: the same config with and without telemetry
    must land on the identical best score, adjacency and accept rates —
    one chain or several, fixed or adaptive window. STN samples, not flat
    random data, so that walks from different keys tell apart."""
    from repro.data.networks import network_data
    from repro.learner import LearnConfig, learn_structure

    _, data = network_data("stn", 300, 2, 1)
    base = dict(q=2, s=2, iters=150, chains=chains, seed=0, window=4,
                exchange_every=30, adapt_window=adapt)
    plain = learn_structure(data, LearnConfig(**base))
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        tapped = learn_structure(
            data, LearnConfig(**base, telemetry=True, trace_every=4,
                              check_every=50, trace_dir=td))
    assert plain["score"] == tapped["score"]
    np.testing.assert_array_equal(plain["adjacency"], tapped["adjacency"])
    assert plain["chain_accept_rates"] == tapped["chain_accept_rates"]


def test_learn_structure_walk_is_the_core_chain_loop():
    """The library's run loop is pinned to the core multi-chain loop: three
    exchanging chains through learn_structure land on the best score and
    graph of a direct mcmc_run_chains call over the same engine."""
    from repro.core import adjacency_from_ranks
    from repro.core.mcmc import exchange_best, mcmc_run_chains
    from repro.data.networks import network_data
    from repro.learner import (LearnConfig, build_engine, learn_structure,
                               prepare_run)

    _, data = network_data("stn", 300, 2, 1)
    cfg = LearnConfig(q=2, s=2, iters=120, chains=3, seed=1, window=4,
                      exchange_every=25)
    out = learn_structure(data, cfg)
    st, _, _ = prepare_run(data, cfg)
    eng = build_engine(st, cfg)
    assert eng.mask_on and eng.window == 4
    states = mcmc_run_chains(jax.random.key(cfg.seed), cfg.chains, st.n,
                             eng.score_fn, cfg.iters, delta_fn=eng.delta_fn,
                             window=eng.window,
                             exchange_every=cfg.exchange_every,
                             planes_fn=eng.planes_fn)
    best_score, best_idx, _ = exchange_best(states)
    assert out["score"] == float(best_score)
    assert out["chain_accept_rates"] == [
        int(a) / cfg.iters for a in np.asarray(states.accepts)]
    np.testing.assert_array_equal(
        out["adjacency"], adjacency_from_ranks(np.asarray(best_idx), s=cfg.s))
    assert out["exchange_count"] == 4
