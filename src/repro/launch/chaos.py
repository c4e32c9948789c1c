"""Chaos harness: prove the fault-tolerant run supervisor end to end.

This is the executable form of the robustness acceptance gate (run as
``make chaos-smoke`` in CI):

* **crash-resume determinism** — a run with a mid-walk injected crash
  (``--fault-plan "corrupt@1:bitflip;crash@1:after"``) is resumed by
  re-invoking WITHOUT the crash events (the arm-once discipline from
  runtime/faults.py: the process died, so the resume invocation simply
  doesn't re-arm the crash) and must finish BITWISE-IDENTICAL — best score,
  adjacency, per-chain accept counts — to an uninterrupted run with the
  same seeds. The corrupt event additionally forces the restore through the
  quarantine + fallback path: the newest checkpoint fails digest
  verification, is renamed ``corrupt_step_*``, and the run falls back to
  the previous verified step.
* **heal-within-one-interval** — a NaN-poisoned chain and a stalled chain
  are both healed at the next supervision boundary (one ``heal`` row each
  in the JSONL trace), and the run still finishes with a finite score.
* **trace hygiene** — every emitted JSONL trace re-validates against
  ``bn-telemetry/v1`` (repro.telemetry.validate), including the traces of
  crashed and resumed runs.

The gate runs on the single-device engine in-process and on the sharded
engine in a subprocess (XLA_FLAGS must force the multi-device CPU platform
BEFORE jax imports, so the sharded leg re-executes this module with
``--leg sharded``).

Usage::

    python -m repro.launch.chaos                # full gate (~1 min on CPU)
    python -m repro.launch.chaos --skip-sharded # single-device legs only
"""
from __future__ import annotations

import argparse
import os
import sys


def _build_cfg(workdir: str, name: str, **overrides):
    from ..learner import LearnConfig
    base = dict(q=2, s=2, iters=96, chains=4, seed=3, window=4,
                exchange_every=16, trace_every=4, check_every=32,
                telemetry=True, supervise=True, checkpoint_every=32,
                preprocess="reference",
                trace_dir=os.path.join(workdir, "traces"), run_name=name)
    base.update(overrides)
    return LearnConfig(**base)


def _data(n: int = 12, m: int = 200):
    import numpy as np
    rng = np.random.default_rng(0)
    return rng.integers(0, 2, size=(m, n)).astype(np.int8)


def _fingerprint(out: dict):
    return (out["score"], out["adjacency"].tolist(),
            out["chain_accept_rates"])


def _validate_traces(workdir: str) -> int:
    from ..telemetry.validate import validate_file
    tdir = os.path.join(workdir, "traces")
    count = 0
    for f in sorted(os.listdir(tdir)):
        if f.endswith(".jsonl"):
            info = validate_file(os.path.join(tdir, f))
            print(f"  trace {f}: {info['rows']} rows "
                  f"{sorted(info['kinds'].items())}")
            count += 1
    return count


def _crash_resume_leg(workdir: str, *, sharded: bool = False) -> None:
    """Crash + corrupt mid-run, auto-resume, compare bitwise to clean."""
    from ..runtime.faults import InjectedCrash
    from ..learner import learn_structure
    tag = "sharded" if sharded else "single"
    data = _data()
    ref = learn_structure(data, _build_cfg(
        workdir, f"{tag}_ref", sharded=sharded,
        checkpoint_dir=os.path.join(workdir, f"ck_{tag}_ref")))
    ckd = os.path.join(workdir, f"ck_{tag}_chaos")
    try:
        learn_structure(data, _build_cfg(
            workdir, f"{tag}_crash", sharded=sharded, checkpoint_dir=ckd,
            fault_plan="corrupt@1:bitflip;crash@1:after"))
        raise AssertionError("fault plan did not crash the run")
    except InjectedCrash as e:
        print(f"  [{tag}] crashed as planned: {e}")
    # resume: same config, crash/corrupt events NOT re-armed
    res = learn_structure(data, _build_cfg(
        workdir, f"{tag}_resume", sharded=sharded, checkpoint_dir=ckd))
    quarantined = [d for d in sorted(os.listdir(ckd))
                   if d.startswith("corrupt_step_")]
    assert quarantined, "corrupt checkpoint was not quarantined"
    print(f"  [{tag}] quarantined: {quarantined}")
    assert _fingerprint(ref) == _fingerprint(res), (
        f"[{tag}] resumed run diverged from the uninterrupted reference: "
        f"{_fingerprint(ref)} != {_fingerprint(res)}")
    print(f"  [{tag}] crash+corrupt resume bitwise-identical "
          f"(score {res['score']:.4f}) OK")


def _heal_leg(workdir: str) -> None:
    """Poisoned + stalled chains healed within one supervision interval."""
    import numpy as np
    from ..learner import learn_structure
    data = _data()
    # exchange_every=0: with the in-scan exchange on, the poisoned chain is
    # re-seeded INSIDE the scan (the NaN-safe exchange always makes it the
    # recipient) before the supervisor ever sees the NaN — that's graceful
    # degradation, but this leg wants the supervisor's own guard exercised
    out = learn_structure(data, _build_cfg(
        workdir, "heal", checkpoint_every=0, exchange_every=0,
        fault_plan="poison@1:chain=2:nan;stall@0:chain=1"))
    heals = out["heals"]
    print(f"  heals: {heals}")
    healed = {h["chain"] for h in heals}
    assert {1, 2} <= healed, f"expected chains 1 and 2 healed, got {healed}"
    # "within one supervision interval": the fault lands before segment k,
    # the heal must be logged at the boundary after segment k (check_every
    # iterations later, segments are 32 iters here)
    for h in heals:
        if h["chain"] == 2:
            # poisoned before segment 1 -> healed at boundary 64, and by the
            # supervisor's own NaN/inf guard
            assert h["iter"] == 64 and h["reason"] == "nonfinite", h
        if h["chain"] == 1:
            assert h["iter"] == 64, h   # stall detected at the 2nd boundary
    assert np.isfinite(out["score"]), "healed run must still converge"
    print(f"  heal-within-one-interval OK (score {out['score']:.4f})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workdir", default="",
                    help="scratch dir ('' = a fresh temp dir)")
    ap.add_argument("--skip-sharded", action="store_true",
                    help="skip the forced-multi-device sharded leg")
    ap.add_argument("--leg", default="all", choices=["all", "sharded"],
                    help="internal: 'sharded' runs only the sharded leg "
                         "(expects XLA_FLAGS to pre-force 4 CPU devices)")
    args = ap.parse_args(argv)

    workdir = args.workdir
    if not workdir:
        import tempfile
        workdir = tempfile.mkdtemp(prefix="chaos_")
    os.makedirs(os.path.join(workdir, "traces"), exist_ok=True)

    if args.leg == "sharded":
        _crash_resume_leg(workdir, sharded=True)
        return 0

    print(f"chaos harness (workdir {workdir})")
    print("[1/4] single-device crash+corrupt resume")
    _crash_resume_leg(workdir, sharded=False)
    print("[2/4] chain healing (poison + stall)")
    _heal_leg(workdir)
    if args.skip_sharded:
        print("[3/4] sharded leg SKIPPED (--skip-sharded)")
    else:
        print("[3/4] sharded crash+corrupt resume (subprocess, 4 devices)")
        import subprocess
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.chaos", "--leg", "sharded",
             "--workdir", workdir], env=env)
        if proc.returncode:
            print("sharded leg FAILED", file=sys.stderr)
            return proc.returncode
    print("[4/4] re-validating emitted JSONL traces")
    n = _validate_traces(workdir)
    assert n >= 4, f"expected >= 4 traces, found {n}"
    print("chaos harness: ALL LEGS PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
