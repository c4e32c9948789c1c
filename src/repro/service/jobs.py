"""Job manager: admission, dedup, and the per-job learning engine.

A JOB is one dataset-learning request — (data, LearnConfig) — run as a
vmapped fleet of chains through the SAME code path a standalone
``learn_structure`` run takes (repro/learner.py): ``prepare_run``
(preprocess + disk cache + collector), ``build_engine`` (scorer/delta/plane
closures) and ``start_run`` (vmapped init, jitted traced segment runner,
armed RunSupervisor). Because the engine construction is shared, a job advanced
segment-by-segment by the multi-job scheduler produces BITWISE-identical
posterior artifacts to a one-shot run of the same (data, config, seed):
the interleaving only changes *when* each segment executes on the host,
never the segment boundaries or any PRNG stream.

Admission rides the preprocess cache's content key: two requests with
identical (data, score kind, arity vector or BGe hyperparameters, s, ess,
gamma, prior, pruning) AND identical
run-affecting config (iters, chains, seed, windows, telemetry cadence, …)
hash to the same job id, so the second request ATTACHES to the in-flight
or completed job instead of recomputing — the dedup layer the ROADMAP's
"millions of users" story needs. Requests that share only the dataset
fingerprint still share the preprocess disk cache entry (the score table
is built once); requests differing in any run-affecting field are distinct
jobs.

Job lifecycle: ``queued`` (admitted, engine not built) → ``running``
(engine compiled, advancing one supervised segment per scheduler tick) →
``done`` (artifacts materialized + persisted to the job's run directory
for the offline ``bn_query`` CLI) or ``failed`` (exception captured).
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, replace

import numpy as np

from ..core.scores import as_states, bge_data
from ..learner import (LearnConfig, build_engine, finish_run, prepare_run,
                       start_run)
from ..preprocess.cache import cache_key
from .query import job_response, materialize

__all__ = ["DatasetSpec", "Job", "JobManager", "admission_key",
           "load_dataset", "service_config"]

# run-affecting LearnConfig fields folded into the admission key beside the
# preprocess content key. Anything that can change the walk or its artifacts
# belongs here; presentation-only fields (trace_dir, run_name, cache_dir,
# checkpoint paths) deliberately do not — two users asking the same question
# from different directories are the SAME job.
_RUN_FIELDS = ("iters", "chains", "seed", "window", "mask_cache",
               "adapt_window", "burn_in", "exchange_every", "scorer",
               "use_kernel", "block", "preprocess", "auto_prune",
               "trace_every", "check_every", "stop_on_converge",
               "rhat_threshold", "patience", "consensus_threshold",
               "supervise", "heal_patience")


@dataclass(frozen=True)
class DatasetSpec:
    """What to learn on: a named generator network, a synthetic DAG, or a
    file-backed sample matrix (``.npy``, rows = samples: integer states
    for BDeu, measurements for BGe)."""
    network: str = "stn"     # alarm | stn | synth | file
    n: int = 16              # node count (network == "synth")
    arcs: int = 0            # exact arc count (network == "synth"; 0 = off)
    m: int = 300             # samples to draw (generator networks)
    seed: int = 0            # data-generation seed
    noise: float = 0.0       # label-noise fraction (generator networks)
    path: str = ""           # network == "file": .npy sample matrix


def load_dataset(spec: DatasetSpec, q, score: str = "bdeu") -> np.ndarray:
    """Materialise the sample matrix for one dataset spec — the same
    generators the ``bn_learn`` CLI uses, so a service job and a standalone
    run of the same spec see byte-identical data. ``q``: states per
    variable, one int or one per variable (``LearnConfig.q``); ``score``
    (``LearnConfig.score``): "bge" draws or loads continuous data, "bdeu"
    integer states, refusing a file whose values are not integers."""
    if spec.network == "file":
        data = np.load(spec.path, allow_pickle=False)
        if data.ndim != 2:
            raise ValueError(f"dataset file {spec.path} must hold a 2-D "
                             f"(samples, nodes) matrix, got {data.shape}")
        return bge_data(data) if score == "bge" else as_states(data)
    from ..data.bn_sampler import inject_noise
    from ..data.networks import network_data
    _, data = network_data(spec.network, spec.m, q, spec.seed,
                           n_synth=spec.n, arcs=spec.arcs, score=score)
    if spec.noise:
        if score == "bge":
            raise ValueError("noise flips discrete states; a BGe dataset "
                             "takes none")
        data = inject_noise(np.random.default_rng(spec.seed + 1), data,
                            spec.noise, q)
    return data


def service_config(overrides: dict | None = None, **kw) -> LearnConfig:
    """LearnConfig with the service invariants applied: telemetry is always
    on (the posterior artifacts come from the edge accumulator),
    ``emit_consensus`` materializes them, and stop-on-converge lets the
    scheduler reclaim a converged job's slots early. Callers may override
    anything else; unknown keys are rejected (they would silently change
    nothing but still alter the admission hash a client expects)."""
    fields = {f for f in LearnConfig.__dataclass_fields__}
    merged = {**(overrides or {}), **kw}
    unknown = set(merged) - fields
    if unknown:
        raise ValueError(f"unknown config field(s): {sorted(unknown)}")
    merged.setdefault("chains", 4)
    merged.setdefault("stop_on_converge", True)
    merged["telemetry"] = True
    merged["emit_consensus"] = True
    return LearnConfig(**merged)


def admission_key(data: np.ndarray, cfg: LearnConfig,
                  prior_matrix: np.ndarray | None = None) -> str:
    """Content-addressed job id: the preprocess cache key (data, the score
    kind, the per-variable arity vector of ``cfg.q`` or the BGe
    hyperparameters, s, ess, gamma, prior, pruning)
    extended with every run-affecting config field.
    Identical requests — however many users submit them — collapse to one
    id, which is the admission/dedup contract."""
    prune_delta = cfg.prune_delta if cfg.prune_delta > 0 else None
    base = cache_key(data, q=cfg.q, s=cfg.s, gamma=cfg.gamma, ess=cfg.ess,
                     prior_matrix=prior_matrix, prune_delta=prune_delta,
                     score=cfg.score, bge_alpha_mu=cfg.bge_alpha_mu,
                     bge_alpha_w=cfg.bge_alpha_w)
    run = repr(tuple(getattr(cfg, f) for f in _RUN_FIELDS))
    h = hashlib.sha256((base + run).encode()).hexdigest()[:16]
    return f"job-{h}"


class Job:
    """One admitted dataset-learning request (see module docstring)."""

    def __init__(self, job_id: str, data: np.ndarray, cfg: LearnConfig, *,
                 run_dir: str = "",
                 prior_matrix: np.ndarray | None = None):
        self.id = job_id
        self.data = data
        self.cfg = cfg
        self.prior_matrix = prior_matrix
        self.run_dir = run_dir
        self.state = "queued"
        self.deduped = False          # set on the response for re-submits
        self.attached = 1             # requests collapsed onto this job
        self.error = ""
        self.result: dict | None = None
        self.sup = None               # armed RunSupervisor once running
        self.extra_chains = 0         # elastic expansion beyond cfg.chains
        self.submitted_at = time.time()
        self._st = self._collector = self._pre = self._engine = None
        self._t0 = 0.0

    @property
    def n(self) -> int:
        return int(self.data.shape[1])

    @property
    def chains(self) -> int:
        """Device slots this job occupies (grows under elastic cloning)."""
        return self.cfg.chains + self.extra_chains

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """Build + compile the engine (the expensive admission step — the
        scheduler calls it only once slots are available)."""
        import jax
        cfg = self.cfg
        self._st, self._collector, self._pre = prepare_run(
            self.data, cfg, prior_matrix=self.prior_matrix)
        self._engine = build_engine(self._st, cfg)
        self._t0 = time.time()
        self.sup = start_run(self._st, cfg, self._engine, self._collector,
                             jax.random.key(cfg.seed))
        self.state = "running"

    def advance(self) -> bool:
        """One supervised segment; True while more remain. Exceptions mark
        the job failed instead of taking the server down."""
        try:
            return self.sup.advance()
        except Exception as exc:              # noqa: BLE001 — job isolation
            self.state = "failed"
            self.error = f"{type(exc).__name__}: {exc}"
            return False

    def finish(self) -> dict:
        """Materialise the result dict (identical to what a standalone
        ``learn_structure`` call returns, artifacts included), persist the
        query artifacts for ``bn_query``, and retire the job."""
        self.result = finish_run(
            self.cfg, self._st, self._engine, self.sup.result(),
            pre=self._pre, t_iter=time.time() - self._t0,
            collector=self._collector)
        self.state = "done"
        self._st = self._engine = None        # free the table
        if self.run_dir:
            self._persist()
        return self.result

    def _persist(self) -> None:
        """Write the job's validated artifact responses to its run
        directory — the offline surface ``bn_query`` reads. Write-to-temp +
        atomic replace, same discipline as the checkpointer."""
        d = os.path.join(self.run_dir, self.id)
        os.makedirs(d, exist_ok=True)
        doc = {"job": job_response(self), **materialize(self)}
        tmp = os.path.join(d, f".result.{os.getpid()}.tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(d, "result.json"))


class JobManager:
    """Admission + registry. Thread-compatible: the HTTP front end only
    touches it under the server lock; all jax work happens on the scheduler
    thread."""

    def __init__(self, *, run_dir: str = "experiments/service",
                 cache_dir: str = ""):
        self.run_dir = run_dir
        self.cache_dir = cache_dir
        self.jobs: dict[str, Job] = {}

    def submit(self, data: np.ndarray, cfg: LearnConfig, *,
               prior_matrix: np.ndarray | None = None) -> tuple[Job, bool]:
        """Admit one request. Returns (job, deduped): an identical request
        attaches to the existing in-flight/completed job (same id, no
        recompute) — that is the whole point of content-addressed ids."""
        job_id = admission_key(data, cfg, prior_matrix)
        job = self.jobs.get(job_id)
        if job is not None:
            job.attached += 1
            return job, True
        # the job owns its trace + cache wiring; these fields are NOT part
        # of the admission hash, so forcing them here cannot split dedup
        cfg = replace(cfg, run_name=job_id,
                      trace_dir=os.path.join(self.run_dir, "traces"),
                      cache_dir=self.cache_dir)
        job = Job(job_id, data, cfg, run_dir=os.path.join(self.run_dir,
                                                          "jobs"),
                  prior_matrix=prior_matrix)
        self.jobs[job_id] = job
        return job, False

    def get(self, job_id: str) -> Job | None:
        return self.jobs.get(job_id)
