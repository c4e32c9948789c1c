"""serve_smoke: end-to-end CI gate for the bn_serve posterior service.

    PYTHONPATH=src python -m repro.launch.serve_smoke

Starts the HTTP server IN-PROCESS on an ephemeral port, then exercises the
whole service contract over real HTTP:

1. submits two small synthetic datasets, one of them twice — the duplicate
   must come back with the SAME job id and ``deduped: true``;
2. polls job status to completion (per-job stop-on-converge may retire a
   job early; its slots must be reclaimed);
3. fetches posterior / MAP / consensus artifacts and validates every
   response against the ``bn-service/v1`` schema;
4. asserts each job's artifacts are BITWISE-equal to a standalone
   ``learn_structure`` run of the same (data, config, seed) — the service's
   core determinism promise (JSON float64 round-trips exactly, so the
   HTTP hop cannot blur the comparison);
5. checks the offline ``bn_query`` CLI reads the persisted artifacts back;
6. shuts the server down cleanly via POST /v1/shutdown.

Exit code 0 = every gate passed. Runs on CPU in well under a minute.
"""
from __future__ import annotations

import json
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

__all__ = ["main", "start_server", "submit_and_wait", "fetch_artifacts",
           "shutdown"]

_POLL_TIMEOUT = 300.0      # seconds until we declare the service hung


def _http(method: str, url: str, payload: dict | None = None) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def start_server(run_dir: str, slots: int = 16):
    """BNServer on an ephemeral localhost port, serving from a daemon
    thread. Returns (server, base_url, thread)."""
    from ..launch.bn_serve import BNServer

    srv = BNServer(("127.0.0.1", 0), slots=slots, run_dir=run_dir)
    host, port = srv.server_address[:2]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, f"http://{host}:{port}", t


def submit_and_wait(base: str, specs: list[dict], config: dict,
                    timeout: float = _POLL_TIMEOUT) -> dict[str, dict]:
    """Submit every dataset spec plus an exact duplicate of the first, check
    the duplicate dedups onto the first job, poll until every job settles,
    and check each settled job is ``done`` — the scheduler catches job
    exceptions by design, so a clean return proves nothing; the state does.
    Every response is schema-validated. Returns {job_id: final status}."""
    from ..service import validate_response

    health = _http("GET", f"{base}/v1/health")
    validate_response(health)
    assert health["state"] == "up", health

    jobs = [_http("POST", f"{base}/v1/jobs",
                  {"dataset": s, "config": config}) for s in specs]
    dup = _http("POST", f"{base}/v1/jobs",
                {"dataset": specs[0], "config": config})
    for j in jobs + [dup]:
        validate_response(j)
    assert not any(j["deduped"] for j in jobs), jobs
    assert dup["deduped"] and dup["job_id"] == jobs[0]["job_id"], \
        f"dedup broken: {dup['job_id']} vs {jobs[0]['job_id']}"
    assert dup["attached"] == 2, dup
    ids = [j["job_id"] for j in jobs]
    assert len(set(ids)) == len(ids), ids
    print(f"serve_smoke: dedup OK ({dup['job_id']} attached twice)")

    deadline = time.time() + timeout
    states: dict[str, dict] = {}
    while time.time() < deadline:
        states = {i: _http("GET", f"{base}/v1/jobs/{i}") for i in ids}
        if all(s["state"] in ("done", "failed") for s in states.values()):
            break
        time.sleep(0.5)
    for i, s in states.items():
        validate_response(s)
        assert s["state"] == "done", f"job {i}: {s}"
    print(f"serve_smoke: {len(ids)} jobs done "
          f"(iters_done={[states[i]['iters_done'] for i in ids]}, "
          f"converged={[states[i]['converged'] for i in ids]})")
    return states


def fetch_artifacts(base: str, jid: str) -> dict[str, dict]:
    """The posterior, MAP and consensus (default and 0.25 cut) responses of
    one finished job, each schema-validated."""
    from ..service import validate_response

    out = {
        "posterior": _http("GET", f"{base}/v1/jobs/{jid}/posterior"),
        "map": _http("GET", f"{base}/v1/jobs/{jid}/map"),
        "consensus": _http("GET", f"{base}/v1/jobs/{jid}/consensus"),
        "consensus_lo": _http(
            "GET", f"{base}/v1/jobs/{jid}/consensus?threshold=0.25"),
    }
    for r in out.values():
        validate_response(r)
    assert out["consensus_lo"]["threshold"] == 0.25
    assert np.asarray(out["consensus_lo"]["adjacency"]).sum() >= \
        np.asarray(out["consensus"]["adjacency"]).sum()
    return out


def shutdown(base: str, thread: threading.Thread) -> None:
    """POST /v1/shutdown and wait for the serving thread to stop."""
    from ..service import validate_response

    validate_response(_http("POST", f"{base}/v1/shutdown"))
    thread.join(timeout=60)
    assert not thread.is_alive(), "server thread did not stop"


def main(argv=None) -> int:
    from ..learner import learn_structure
    from ..launch.bn_query import load_result
    from ..service import load_dataset, service_config
    from ..service.jobs import DatasetSpec

    run_dir = tempfile.mkdtemp(prefix="serve_smoke_")
    _, base, t = start_server(run_dir)
    print(f"serve_smoke: server up at {base}, run_dir={run_dir}")

    config = {"iters": 400, "chains": 3, "check_every": 100,
              "trace_every": 10, "seed": 11, "stop_on_converge": True,
              "patience": 1}
    specs = [
        {"network": "synth", "n": 8, "m": 150, "seed": 3},
        {"network": "synth", "n": 10, "m": 150, "seed": 4},
    ]
    ids = list(submit_and_wait(base, specs, config))

    # --- slots reclaimed once everything finished
    health = _http("GET", f"{base}/v1/health")
    assert health["slots_used"] == 0 and health["active"] == 0, health

    # --- artifacts: schema-valid AND bitwise-equal to standalone runs
    for spec, jid in zip(specs, ids):
        art = fetch_artifacts(base, jid)
        post, mapr, cons = art["posterior"], art["map"], art["consensus"]
        cfg = service_config(config)
        data = load_dataset(DatasetSpec(**spec), cfg.q, score=cfg.score)
        ref = learn_structure(data, cfg)
        same = {
            "posterior": np.array_equal(np.asarray(post["edge_probs"]),
                                        np.asarray(ref["edge_posterior"])),
            "map": np.array_equal(np.asarray(mapr["adjacency"]),
                                  np.asarray(ref["map_dag"])),
            "consensus": np.array_equal(np.asarray(cons["adjacency"]),
                                        np.asarray(ref["consensus"])),
            "score": mapr["score"] == float(ref["score"]),
        }
        assert all(same.values()), f"job {jid} diverged: {same}"
        print(f"serve_smoke: {jid} bitwise-equal to standalone "
              f"(n={post['n']}, edge_samples={post['edge_samples']})")

    # --- offline CLI reads the persisted artifacts back
    for jid in ids:
        doc = load_result(run_dir, jid)
        assert doc["job"]["state"] == "done"
    print("serve_smoke: bn_query round-trip OK")

    shutdown(base, t)
    print("serve_smoke: clean shutdown — PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
