"""Posterior service: long-running BN structure learning with queryable
posteriors.

The package splits the server into the classic three layers:

* :mod:`repro.service.jobs`      — admission, content-addressed dedup, and
  the per-job engine (the same ``prepare_run``/``build_engine``/
  ``start_run`` path of repro/learner.py that standalone ``bn_learn``
  uses, so service answers are bitwise-comparable to one-shot runs).
* :mod:`repro.service.scheduler` — packs jobs onto a chain-slot budget,
  advancing each active job one supervised segment per tick with optional
  elastic fleet cloning into idle slots.
* :mod:`repro.service.query`     — materialized, stamped, schema-validated
  posterior / MAP / consensus responses (:mod:`repro.service.schema`).

The HTTP front end lives in :mod:`repro.launch.bn_serve`; the offline
artifact reader in :mod:`repro.launch.bn_query`.
"""
from .jobs import (DatasetSpec, Job, JobManager, admission_key,
                   load_dataset, service_config)
from .query import (consensus_response, error_response, job_response,
                    map_response, materialize, posterior_response)
from .scheduler import FleetScheduler, expand_fleet
from .schema import SCHEMA, validate_response

__all__ = [
    "SCHEMA", "validate_response",
    "DatasetSpec", "Job", "JobManager", "admission_key", "load_dataset",
    "service_config",
    "FleetScheduler", "expand_fleet",
    "job_response", "posterior_response", "map_response",
    "consensus_response", "materialize", "error_response",
]
