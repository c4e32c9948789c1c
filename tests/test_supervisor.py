"""Fault-tolerant run supervisor (ISSUE 8): verified checkpoints, chaos
injection, telemetry-driven chain healing, and the crash-resume determinism
contract."""
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, CheckpointCorruptError,
                              io_retry, latest_step, quarantine_step,
                              restore_checkpoint, restore_latest_verified,
                              save_checkpoint)
from repro.runtime.faults import (FaultEvent, InjectedCrash,
                                  corrupt_checkpoint_dir, parse_fault_plan,
                                  poison_chain_state)


# ------------------------------------------------------------- fault plans
def test_fault_plan_grammar():
    plan = parse_fault_plan(
        "crash@2:before; corrupt@1:leaf=leaf_3:truncate,"
        "poison@0:chain=1:inf;stall@3;cache@2:delete", seed=7)
    kinds = [(e.kind, e.segment) for e in plan.events]
    # sorted by (segment, kind order)
    assert kinds == [("poison", 0), ("corrupt", 1), ("crash", 2),
                     ("cache", 2), ("stall", 3)]
    ev = {e.kind: e for e in plan.events}
    assert ev["crash"].mode == "before"
    assert ev["corrupt"].leaf == "leaf_3" and ev["corrupt"].mode == "truncate"
    assert ev["poison"].chain == 1 and ev["poison"].mode == "inf"
    assert ev["cache"].mode == "delete"
    assert plan.pre_segment(0) == [ev["poison"]]
    assert plan.checkpoint_events(2) == (True, [], False)
    assert plan.checkpoint_events(1) == (False, [ev["corrupt"]], False)
    # defaults
    d = parse_fault_plan("crash@0;corrupt@0;poison@0;cache@0")
    modes = {e.kind: e.mode for e in d.events}
    assert modes == {"crash": "after", "corrupt": "bitflip",
                     "poison": "nan", "cache": "truncate"}
    assert not parse_fault_plan("")          # empty spec -> falsy plan
    assert not parse_fault_plan("  ;  ")


def test_fault_plan_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault_plan("explode@1")
    with pytest.raises(ValueError, match="integer segment"):
        parse_fault_plan("crash@soon")
    with pytest.raises(ValueError, match="bad option"):
        parse_fault_plan("crash@1:sideways")


def test_fault_plan_seeded_choices_are_deterministic(tmp_path):
    d = str(tmp_path / "ck")
    tree = tuple(np.arange(8, dtype=np.float32) + i for i in range(5))
    save_checkpoint(d, 1, tree)
    import shutil
    picked = []
    for _ in range(2):
        plan = parse_fault_plan("corrupt@0:bitflip", seed=123)
        picked.append(os.path.basename(
            plan.corrupt_checkpoint(d, plan.events[0])))
        shutil.rmtree(d)                 # pristine files for the next round
        save_checkpoint(d, 1, tree)
    assert picked[0] == picked[1]       # same seed -> same target leaf


def test_poison_chain_state_hits_cached_scores_only():
    class S:
        pass
    score = jnp.zeros(4)
    cur_ls = jnp.zeros((4, 3))
    best = jnp.ones(4)
    from collections import namedtuple
    St = namedtuple("St", "score cur_ls best_score pos")
    st = St(score, cur_ls, best, jnp.arange(4))
    out = poison_chain_state(st, 2, "inf")
    assert np.isinf(np.asarray(out.score)[2])
    assert np.isinf(np.asarray(out.cur_ls)[2]).all()
    assert np.isfinite(np.asarray(out.score)[[0, 1, 3]]).all()
    np.testing.assert_array_equal(np.asarray(out.pos), np.arange(4))


# --------------------------------------------- verified checkpoint restore
def _tree():
    return (np.arange(6, dtype=np.float32),
            np.arange(12, dtype=np.int32).reshape(3, 4))


def test_digest_verify_quarantine_and_fallback(tmp_path):
    d = str(tmp_path / "ck")
    t1 = _tree()
    t2 = tuple(a + 1 for a in t1)
    save_checkpoint(d, 10, t1)
    save_checkpoint(d, 20, t2)
    # corrupt the newest step's first leaf
    rng = np.random.default_rng(0)
    corrupt_checkpoint_dir(d, rng, leaf="leaf_0", mode="bitflip")
    with pytest.raises(CheckpointCorruptError, match="digest mismatch"):
        restore_checkpoint(d, t1, step=20)
    # verified restore falls back to step 10 and quarantines step 20
    tree, meta, step = restore_latest_verified(d, t1)
    assert step == 10
    np.testing.assert_array_equal(tree[0], t1[0])
    assert os.path.isdir(os.path.join(d, "corrupt_step_0000000020"))
    assert latest_step(d) == 10            # quarantined dirs are invisible
    # all steps corrupt -> FileNotFoundError (start from scratch)
    corrupt_checkpoint_dir(d, rng, leaf="leaf_1", mode="truncate")
    with pytest.raises(FileNotFoundError):
        restore_latest_verified(d, t1)


def test_quarantine_name_collision(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, 5, _tree())
    q1 = quarantine_step(d, 5)
    save_checkpoint(d, 5, _tree())
    q2 = quarantine_step(d, 5)
    assert q1 != q2 and os.path.isdir(q1) and os.path.isdir(q2)


def test_truncated_leaf_detected_without_digests(tmp_path):
    # even a pre-digest snapshot (manifest without 'digests') must not
    # restore a truncated array silently: np.load fails -> corrupt error
    d = str(tmp_path / "ck")
    save_checkpoint(d, 1, _tree())
    man = os.path.join(d, "step_0000000001", "manifest.json")
    with open(man) as f:
        m = json.load(f)
    del m["digests"]
    with open(man, "w") as f:
        json.dump(m, f)
    leaf = os.path.join(d, "step_0000000001", "leaf_1.npy")
    with open(leaf, "r+b") as f:
        f.truncate(os.path.getsize(leaf) // 2)
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, _tree(), step=1)


def test_io_retry_backs_off_then_succeeds():
    calls = []

    def flaky():
        calls.append(time.monotonic())
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert io_retry(flaky, what="flaky", backoff_s=0.01) == "ok"
    assert len(calls) == 3
    # non-OSError is NOT retried
    def boom():
        calls.append(None)
        raise ValueError("logic bug")
    calls.clear()
    with pytest.raises(ValueError):
        io_retry(boom, what="boom", backoff_s=0.01)
    assert len(calls) == 1


def test_async_checkpointer_surfaces_writer_errors(tmp_path, monkeypatch):
    ck = AsyncCheckpointer(str(tmp_path / "ck"), keep=2)
    import repro.checkpoint.checkpointer as mod

    def raising_save(*a, **k):
        raise OSError("disk on fire")

    monkeypatch.setattr(mod, "save_checkpoint", raising_save)
    ck.save(1, _tree())
    # the failure happened on the writer thread; the NEXT call must surface
    # it instead of silently leaving a hole in the trajectory
    with pytest.raises(OSError, match="disk on fire"):
        ck.wait()
    # the error is consumed once raised; writes work again after the patch
    monkeypatch.undo()
    ck.save(2, _tree())
    ck.wait()
    assert latest_step(str(tmp_path / "ck")) == 2
    # ... and save() itself re-raises a pending writer failure
    monkeypatch.setattr(mod, "save_checkpoint", raising_save)
    ck.save(3, _tree())
    with pytest.raises(OSError, match="disk on fire"):
        ck.save(4, _tree())
    monkeypatch.undo()


# --------------------------------------------------- NaN/inf-safe exchange
def test_exchange_step_never_donates_from_poisoned_chain():
    from repro.core.combinatorics import build_pst, n_parent_sets
    from repro.core.mcmc import exchange_step, init_chain
    from repro.core.order_scoring import score_order_chunked
    import functools

    n, s = 8, 2
    S = n_parent_sets(n - 1, s)
    pst, _ = build_pst(n - 1, s)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(-40, 8, (n, S)).astype(np.float32))
    pad = (-S) % 16
    table = jnp.pad(table, ((0, 0), (0, pad)), constant_values=-3e38)
    pst = jnp.pad(jnp.asarray(pst), ((0, pad), (0, 0)), constant_values=-1)
    fn = functools.partial(score_order_chunked, table, pst, block=16)
    keys = jax.random.split(jax.random.key(0), 4)
    states = jax.vmap(lambda k: init_chain(k, n, fn))(keys)

    # poison the would-be donor: the masked rank must re-route the exchange
    donor = int(np.argmax(np.asarray(states.best_score)))
    poisoned = poison_chain_state(states, donor, "nan")
    out = jax.jit(exchange_step)(poisoned)
    # poisoned chain ranks -inf -> it is the RECIPIENT: its pos/caches are
    # overwritten by the best remaining finite chain
    finite = np.isfinite(np.asarray(poisoned.best_score))
    best_left = int(np.argmax(np.where(finite,
                                       np.asarray(poisoned.best_score),
                                       -np.inf)))
    np.testing.assert_array_equal(np.asarray(out.pos[donor]),
                                  np.asarray(states.pos[best_left]))
    assert np.isfinite(np.asarray(out.best_score)).all()

    # and on all-finite inputs the masked rank is bitwise the old behaviour
    clean = jax.jit(exchange_step)(states)
    w = int(np.argmin(np.asarray(states.best_score)))
    b = int(np.argmax(np.asarray(states.best_score)))
    np.testing.assert_array_equal(np.asarray(clean.pos[w]),
                                  np.asarray(states.pos[b]))


def test_best_finite_chain():
    from repro.runtime.straggler import best_finite_chain
    assert best_finite_chain(np.array([1.0, 5.0, 3.0])) == 1
    assert best_finite_chain(np.array([1.0, np.nan, 3.0])) == 2
    assert best_finite_chain(np.array([np.inf, 2.0, np.nan])) == 1
    assert best_finite_chain(np.array([np.nan, np.nan])) in (0, 1)


# --------------------------------------------- supervised-run determinism
def _bn_data(n=10, m=160):
    rng = np.random.default_rng(0)
    return rng.integers(0, 2, size=(m, n)).astype(np.int8)


def _cfg(tmp_path, name, **over):
    from repro.learner import LearnConfig
    base = dict(q=2, s=2, iters=64, chains=4, seed=5, window=4,
                exchange_every=8, check_every=32,
                trace_dir=str(tmp_path / "traces"), run_name=name)
    base.update(over)
    return LearnConfig(**base)


def test_supervised_run_matches_plain_bitwise(tmp_path):
    from repro.learner import learn_structure
    data = _bn_data()
    o1 = learn_structure(data, _cfg(tmp_path, "plain", telemetry=True))
    o2 = learn_structure(data, _cfg(tmp_path, "sup", telemetry=True,
                                    supervise=True))
    assert o1["score"] == o2["score"]
    np.testing.assert_array_equal(o1["adjacency"], o2["adjacency"])
    assert o1["chain_accept_rates"] == o2["chain_accept_rates"]
    assert o2["heals"] == []


def test_crash_resume_is_bitwise_identical(tmp_path):
    from repro.learner import learn_structure
    data = _bn_data()
    ref = learn_structure(data, _cfg(
        tmp_path, "ref", supervise=True, checkpoint_every=32,
        checkpoint_dir=str(tmp_path / "ck_ref")))
    ckd = str(tmp_path / "ck")
    with pytest.raises(InjectedCrash):
        learn_structure(data, _cfg(
            tmp_path, "crash", supervise=True, checkpoint_every=32,
            checkpoint_dir=ckd,
            fault_plan="corrupt@0:bitflip;crash@0:after"))
    # resume: crash/corrupt events not re-armed (the arm-once discipline)
    res = learn_structure(data, _cfg(
        tmp_path, "resume", supervise=True, checkpoint_every=32,
        checkpoint_dir=ckd))
    assert ref["score"] == res["score"]
    np.testing.assert_array_equal(ref["adjacency"], res["adjacency"])
    assert ref["chain_accept_rates"] == res["chain_accept_rates"]
    # the corrupt step was quarantined on restore
    assert any(d.startswith("corrupt_step_") for d in os.listdir(ckd))


def test_poison_healed_within_one_interval(tmp_path):
    from repro.learner import learn_structure
    data = _bn_data()
    out = learn_structure(data, _cfg(
        tmp_path, "heal", telemetry=True, supervise=True, exchange_every=0,
        fault_plan="poison@1:chain=2:nan"))
    assert [h["chain"] for h in out["heals"]] == [2]
    h = out["heals"][0]
    assert h["reason"] == "nonfinite" and h["iter"] == 64
    assert np.isfinite(out["score"])
    # the heal row landed in the JSONL trace and the file still validates
    from repro.telemetry.validate import validate_file
    info = validate_file(out["telemetry"]["trace_path"])
    assert info["kinds"].get("heal") == 1


def test_stall_healed_by_progress_guard(tmp_path):
    from repro.learner import learn_structure
    data = _bn_data()
    out = learn_structure(data, _cfg(
        tmp_path, "stall", supervise=True, iters=96,
        fault_plan="stall@0:chain=1"))
    assert any(h["chain"] == 1 and h["reason"] == "stalled"
               for h in out["heals"])
    assert np.isfinite(out["score"])


def test_graceful_degradation_without_heal(tmp_path):
    # poisoned chain, NO --supervise: the run must still complete with a
    # finite best score (NaN-safe exchange + finite-guarded accumulators)
    from repro.learner import learn_structure
    data = _bn_data()
    out = learn_structure(data, _cfg(
        tmp_path, "degrade", telemetry=True,
        fault_plan="poison@1:chain=2:nan"))
    assert np.isfinite(out["score"])
    assert out["heals"] == []


# ------------------------------------------------------ cache chaos fault
def test_truncated_cache_entry_degrades_to_rebuild(tmp_path, caplog):
    import logging
    from repro.preprocess import build_score_table_fused
    from repro.runtime.faults import corrupt_cache_dir

    data = _bn_data(n=7, m=120)
    d = str(tmp_path / "cache")
    _, i1 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                    return_info=True)
    assert not i1["cache_hit"]
    assert corrupt_cache_dir(d, np.random.default_rng(0),
                             mode="truncate") is not None
    with caplog.at_level(logging.WARNING, logger="repro.preprocess.cache"):
        st2, i2 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                          return_info=True)
    assert not i2["cache_hit"]              # corrupt entry = logged miss
    assert any("ignoring" in r.message for r in caplog.records)
    # the rebuild repaired the entry in place: third call hits again
    _, i3 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                    return_info=True)
    assert i3["cache_hit"]
    # delete mode nukes the whole entry -> plain miss
    assert corrupt_cache_dir(d, np.random.default_rng(1),
                             mode="delete") is not None
    _, i4 = build_score_table_fused(data, q=2, s=2, cache_dir=d,
                                    return_info=True)
    assert not i4["cache_hit"]


# --------------------------------------------- concurrent writers (ISSUE 10)
def test_checkpoint_concurrent_writers_second_wins(tmp_path, monkeypatch):
    """Two writers racing on the SAME entry (deduped service jobs sharing a
    cache/checkpoint dir) must each stage in a private tmp dir, and the
    second publisher must win WHOLE — never a mixed tree. The interleave is
    forced deterministically: writer A is paused at its publish point while
    writer B stages and publishes, then A publishes over B."""
    import repro.checkpoint.checkpointer as cp
    d = str(tmp_path / "ck")
    tree_a = (np.full(4, 1.0), np.arange(3))
    tree_b = (np.full(4, 2.0), np.arange(3) * 10)
    real_replace = os.replace
    raced = []

    def racing_replace(src, dst):
        if dst.endswith("step_0000000007") and not raced:
            raced.append(True)
            save_checkpoint(d, 7, tree_b)     # B lands while A is mid-publish
        return real_replace(src, dst)

    monkeypatch.setattr(cp.os, "replace", racing_replace)
    save_checkpoint(d, 7, tree_a)             # A staged first, published last
    restored, _ = restore_checkpoint(d, tree_a)   # digests must all verify
    np.testing.assert_array_equal(restored[0], tree_a[0])
    np.testing.assert_array_equal(restored[1], tree_a[1])
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_checkpoint_resave_same_step_overwrites(tmp_path):
    """Re-saving a step (the single-writer race with one's own past self)
    replaces the published tree atomically instead of ENOTEMPTY-failing."""
    d = str(tmp_path / "ck")
    save_checkpoint(d, 3, (np.zeros(5),))
    save_checkpoint(d, 3, (np.ones(5),))
    restored, _ = restore_checkpoint(d, (np.empty(5),))
    np.testing.assert_array_equal(restored[0], np.ones(5))
    assert latest_step(d) == 3
