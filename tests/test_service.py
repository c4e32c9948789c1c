"""Posterior-service invariants: admission/dedup, multi-job scheduling
determinism, slot reclamation, elastic expansion, and the response schema.

The load-bearing property is bitwise parity: a job advanced segment-by-
segment inside a multi-job FleetScheduler pack must produce artifacts
identical to a standalone ``learn_structure`` run of the same
(data, config, seed) — interleaving may only change WHEN segments run.
"""
import json
import os

import numpy as np
import pytest

from repro.launch.bn_learn import learn_structure
from repro.service import (DatasetSpec, FleetScheduler, JobManager,
                           admission_key, error_response, job_response,
                           load_dataset, materialize, service_config,
                           validate_response)
from repro.service.scheduler import expand_fleet


def _cfg(**kw):
    base = dict(iters=240, chains=3, seed=5, check_every=80, trace_every=10,
                window=6, stop_on_converge=False)
    base.update(kw)
    return service_config(base)


@pytest.fixture(scope="module")
def dataset():
    cfg = _cfg()
    return load_dataset(DatasetSpec(network="synth", n=7, m=120, seed=2),
                        cfg.q)


# --------------------------------------------------------------- admission
def test_service_config_invariants():
    cfg = _cfg()
    assert cfg.telemetry and cfg.emit_consensus
    with pytest.raises(ValueError, match="unknown config field"):
        service_config({"not_a_field": 1})


def test_admission_key_separates_run_config(dataset):
    a = admission_key(dataset, _cfg())
    assert a == admission_key(dataset, _cfg())
    assert a != admission_key(dataset, _cfg(seed=6))
    assert a != admission_key(dataset, _cfg(iters=241))
    assert a != admission_key(dataset[:100], _cfg())
    # presentation-only fields must NOT split dedup
    assert a == admission_key(dataset, _cfg(run_name="other",
                                            trace_dir="/elsewhere"))


def test_admission_key_separates_arities(dataset):
    """The arity vector is part of the job's identity: one arity given as an
    int or as a full vector is the same job, another vector is not."""
    n = dataset.shape[1]
    a = admission_key(dataset, _cfg(q=2))
    assert a == admission_key(dataset, _cfg(q=[2] * n))
    assert a == admission_key(dataset, _cfg(q=",".join(["2"] * n)))
    assert a != admission_key(dataset, _cfg(q=[2] * (n - 1) + [3]))


def test_job_at_published_alarm_arities(tmp_path):
    """A service job on ALARM sampled and scored at its published 2-4
    states per variable runs to its posterior."""
    from repro.data.networks import ALARM_ARITY
    cfg = _cfg(q="alarm", s=2, iters=60, chains=2, check_every=20)
    assert cfg.q == ALARM_ARITY
    data = load_dataset(DatasetSpec(network="alarm", m=200, seed=4), cfg.q)
    assert (data.max(0) < np.asarray(ALARM_ARITY)).all()
    assert data.max() == 3                       # four-state variables
    man = JobManager(run_dir=str(tmp_path))
    sched = FleetScheduler(man, slots=4)
    job, _ = sched.submit(data, cfg)
    sched.run()
    assert job.state == "done", job.error
    arts = materialize(job)
    assert np.asarray(arts["posterior"]["edge_probs"]).shape == (37, 37)


def test_dedup_attaches_to_same_job(dataset, tmp_path):
    man = JobManager(run_dir=str(tmp_path))
    j1, d1 = man.submit(dataset, _cfg())
    j2, d2 = man.submit(dataset, _cfg())
    j3, d3 = man.submit(dataset, _cfg(seed=9))
    assert (d1, d2, d3) == (False, True, False)
    assert j1 is j2 and j1.attached == 2
    assert j3.id != j1.id


def test_oversized_job_fails_admission(dataset, tmp_path):
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=2)
    job, deduped = sched.submit(dataset, _cfg(chains=3))
    assert not deduped and job.state == "failed"
    assert "chain slots" in job.error
    assert not sched.pending and not sched.active


# ------------------------------------------------------------- determinism
def test_concurrent_jobs_bitwise_equal_standalone(dataset, tmp_path):
    """Two jobs interleaved through the scheduler == each run alone."""
    cfgs = [_cfg(seed=5), _cfg(seed=9, iters=160)]
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=6)
    handles = [sched.submit(dataset, c)[0] for c in cfgs]
    sched.run()
    for job, cfg in zip(handles, cfgs):
        assert job.state == "done", job.error
        ref = learn_structure(dataset, cfg)
        np.testing.assert_array_equal(np.asarray(job.result["edge_posterior"]),
                                      np.asarray(ref["edge_posterior"]))
        np.testing.assert_array_equal(np.asarray(job.result["map_dag"]),
                                      np.asarray(ref["map_dag"]))
        np.testing.assert_array_equal(np.asarray(job.result["consensus"]),
                                      np.asarray(ref["consensus"]))
        assert float(job.result["score"]) == float(ref["score"])


# ------------------------------------------------------------- scheduling
def test_finished_job_slots_reclaimed(dataset, tmp_path):
    """A short job retires early; its slots admit the queued third job."""
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=6)
    short, _ = sched.submit(dataset, _cfg(seed=5, iters=160))
    long_, _ = sched.submit(dataset, _cfg(seed=9, iters=400))
    queued, _ = sched.submit(dataset, _cfg(seed=13, iters=80))
    sched.step()
    assert queued.state == "queued" and sched.slots_used == 6
    admitted = False
    for _ in range(100):
        alive = sched.step()
        if not admitted and queued.state != "queued":
            # a single-segment job can start AND finish inside one tick, so
            # observe the admission via the state leaving "queued"
            admitted = True
            assert short.state == "done", \
                "queued job admitted before any slots were freed"
        if not alive:
            break
    assert admitted, "queued job never admitted into reclaimed slots"
    assert {short.state, long_.state, queued.state} == {"done"}


def test_converged_job_stops_early(dataset, tmp_path):
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=4)
    job, _ = sched.submit(dataset, _cfg(
        iters=4000, chains=4, check_every=100, stop_on_converge=True,
        patience=1, rhat_threshold=1.5))
    sched.run()
    assert job.state == "done"
    assert job.result["iters_run"] < 4000, "never converged in 4000 iters"
    assert sched.slots_used == 0 and not sched.active


def test_elastic_expansion_completes(dataset, tmp_path):
    sched = FleetScheduler(JobManager(run_dir=str(tmp_path)), slots=4,
                           elastic=True)
    short, _ = sched.submit(dataset, _cfg(seed=5, iters=80, chains=2))
    grown, _ = sched.submit(dataset, _cfg(seed=9, iters=400, chains=2))
    sched.run()
    assert short.state == "done" and grown.state == "done"
    assert grown.extra_chains > 0, "idle slots were never cloned into"
    C = grown.cfg.chains + grown.extra_chains
    tele = grown.result["telemetry"]
    assert len(tele["reseeds"]) == C
    assert np.asarray(grown.result["edge_posterior"]).shape == (7, 7)


def test_expand_fleet_noop_when_not_running(dataset, tmp_path):
    job, _ = JobManager(run_dir=str(tmp_path)).submit(dataset, _cfg())
    assert expand_fleet(job, 2) == 0 and job.extra_chains == 0


# ------------------------------------------------------------------ query
def test_responses_validate_and_persist(dataset, tmp_path):
    man = JobManager(run_dir=str(tmp_path))
    sched = FleetScheduler(man, slots=4)
    job, _ = sched.submit(dataset, _cfg())
    validate_response(job_response(job))          # queued is a valid state
    with pytest.raises(LookupError):
        materialize(job)                          # artifacts gated on done
    sched.run()
    arts = materialize(job)
    for resp in arts.values():
        validate_response(resp)
        assert resp["job_id"] == job.id
    n = job.data.shape[1]
    assert np.asarray(arts["posterior"]["edge_probs"]).shape == (n, n)
    persisted = os.path.join(str(tmp_path), "jobs", job.id, "result.json")
    with open(persisted) as f:
        doc = json.load(f)
    assert doc["posterior"]["edge_probs"] == arts["posterior"]["edge_probs"]
    with pytest.raises(ValueError, match="missing required field"):
        validate_response({"schema": "bn-service/v1", "kind": "job"})
    validate_response(error_response("nope"))
