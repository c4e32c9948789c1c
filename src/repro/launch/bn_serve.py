"""bn_serve: the long-running BN posterior service over local HTTP.

    PYTHONPATH=src python -m repro.launch.bn_serve --port 8787 \
        --slots 64 --run-dir experiments/service

Architecture: ONE driver thread owns every jax operation — it builds
engines and advances each active job one supervised segment per scheduler
tick (``FleetScheduler.step``). The stdlib ThreadingHTTPServer front end
never touches the device; handlers only enqueue dataset specs and read
materialized results under the server lock. That split keeps request
latency independent of segment latency and sidesteps jax's
single-host-thread dispatch model entirely.

Endpoints (all JSON, schema ``bn-service/v1`` — repro/service/schema.py):

    POST /v1/jobs                    {"dataset": {...DatasetSpec fields},
                                      "config": {...LearnConfig overrides}}
                                     -> job response (dedup-aware: an
                                        identical request returns the SAME
                                        job id with deduped=true)
    GET  /v1/jobs                    -> list of job responses
    GET  /v1/jobs/<id>               -> job status
    GET  /v1/jobs/<id>/posterior     -> (n, n) edge-probability matrix
    GET  /v1/jobs/<id>/map           -> MAP DAG + score
    GET  /v1/jobs/<id>/consensus[?threshold=t]
                                     -> thresholded consensus adjacency
    GET  /v1/health                  -> liveness + scheduler occupancy
    POST /v1/shutdown                -> drain and stop cleanly

Every artifact response is stamped with job id, iterations done, R̂ status
and heal/reseed counts. Artifacts are also persisted to
``<run_dir>/jobs/<id>/result.json`` for the offline ``bn_query`` CLI, so
the server can be stopped and its answers remain queryable.
"""
from __future__ import annotations

import argparse
import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..service import (DatasetSpec, FleetScheduler, JobManager,
                       consensus_response, error_response, job_response,
                       load_dataset, map_response, posterior_response,
                       service_config, validate_response)
from ..service.schema import SCHEMA

__all__ = ["BNServer", "main"]

logger = logging.getLogger(__name__)

# driver idle sleep when nothing is active (seconds); ticks are back-to-back
# while jobs are running
_IDLE_SLEEP = 0.05


class BNServer(ThreadingHTTPServer):
    """ThreadingHTTPServer + scheduler driver thread (module docstring)."""

    daemon_threads = True

    def __init__(self, addr, *, slots: int = 64, elastic: bool = False,
                 run_dir: str = "experiments/service", cache_dir: str = ""):
        super().__init__(addr, _Handler)
        self.manager = JobManager(run_dir=run_dir, cache_dir=cache_dir)
        self.scheduler = FleetScheduler(self.manager, slots=slots,
                                        elastic=elastic)
        self.lock = threading.Lock()
        self.stopping = threading.Event()
        self._driver = threading.Thread(target=self._drive, daemon=True,
                                        name="bn-serve-driver")
        self._driver.start()

    # ---------------------------------------------------------- driver loop
    def _drive(self) -> None:
        """The ONLY thread that touches jax: tick the scheduler until asked
        to stop, then drain in-flight jobs so no work is lost."""
        while not self.stopping.is_set():
            with self.lock:
                busy = self.scheduler.step()
            if not busy:
                time.sleep(_IDLE_SLEEP)
        with self.lock:                      # drain: finish active jobs
            while self.scheduler.active and self.scheduler.step():
                pass

    def shutdown_clean(self) -> None:
        self.stopping.set()
        self._driver.join(timeout=600)
        self.shutdown()

    # ------------------------------------------------------------- handlers
    def submit(self, payload: dict) -> dict:
        spec = DatasetSpec(**payload.get("dataset", {}))
        cfg = service_config(payload.get("config", {}))
        data = load_dataset(spec, cfg.q)
        with self.lock:
            job, deduped = self.scheduler.submit(data, cfg)
            return job_response(job, deduped=deduped)

    def health(self) -> dict:
        with self.lock:
            resp = {"schema": SCHEMA, "kind": "health",
                    "state": "stopping" if self.stopping.is_set() else "up",
                    "jobs": len(self.manager.jobs),
                    "active": len(self.scheduler.active),
                    "pending": len(self.scheduler.pending),
                    "slots": self.scheduler.slots,
                    "slots_used": self.scheduler.slots_used}
        validate_response(resp)
        return resp


class _Handler(BaseHTTPRequestHandler):
    server: BNServer

    def log_message(self, fmt, *args):        # route through logging, quiet
        logger.debug("%s " + fmt, self.address_string(), *args)

    def _send(self, code: int, doc: dict) -> None:
        body = json.dumps(doc).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _job(self, job_id: str):
        with self.server.lock:
            return self.server.manager.get(job_id)

    def do_GET(self) -> None:               # noqa: N802 — http.server API
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        try:
            if parts == ["v1", "health"]:
                return self._send(200, self.server.health())
            if parts == ["v1", "jobs"]:
                with self.server.lock:
                    jobs = list(self.server.manager.jobs.values())
                    return self._send(
                        200, {"schema": SCHEMA, "kind": "job_list",
                              "jobs": [job_response(j) for j in jobs]})
            if len(parts) in (3, 4) and parts[:2] == ["v1", "jobs"]:
                job = self._job(parts[2])
                if job is None:
                    return self._send(404, error_response(
                        f"unknown job {parts[2]!r}"))
                if len(parts) == 3:
                    return self._send(200, job_response(job))
                artifact = parts[3]
                with self.server.lock:
                    if artifact == "posterior":
                        return self._send(200, posterior_response(job))
                    if artifact == "map":
                        return self._send(200, map_response(job))
                    if artifact == "consensus":
                        q = parse_qs(url.query)
                        t = q.get("threshold", [None])[0]
                        return self._send(200, consensus_response(
                            job, None if t is None else float(t)))
                return self._send(404, error_response(
                    f"unknown artifact {artifact!r} (posterior|map|"
                    "consensus)"))
            return self._send(404, error_response(f"no route {url.path!r}"))
        except LookupError as exc:          # artifact requested too early
            return self._send(409, error_response(str(exc)))
        except Exception as exc:            # noqa: BLE001 — server stays up
            logger.exception("GET %s failed", self.path)
            return self._send(500, error_response(
                f"{type(exc).__name__}: {exc}"))

    def do_POST(self) -> None:              # noqa: N802 — http.server API
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        try:
            if parts == ["v1", "shutdown"]:
                self._send(200, {"schema": SCHEMA, "kind": "shutdown",
                                 "state": "stopping"})
                # shut down from another thread: shutdown() blocks until
                # serve_forever exits, which can't happen inside a handler
                threading.Thread(target=self.server.shutdown_clean,
                                 daemon=True).start()
                return
            if parts == ["v1", "jobs"]:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
                return self._send(202, self.server.submit(payload))
            return self._send(404, error_response(f"no route {url.path!r}"))
        except (TypeError, ValueError, KeyError, OSError) as exc:
            return self._send(400, error_response(
                f"{type(exc).__name__}: {exc}"))
        except Exception as exc:            # noqa: BLE001 — server stays up
            logger.exception("POST %s failed", self.path)
            return self._send(500, error_response(
                f"{type(exc).__name__}: {exc}"))


def main(argv=None) -> None:
    from ..runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8787)
    ap.add_argument("--slots", type=int, default=64,
                    help="chain-slot budget shared by all active jobs")
    ap.add_argument("--elastic", action="store_true",
                    help="clone chains into idle slots (breaks standalone "
                         "bitwise parity for the grown job)")
    ap.add_argument("--run-dir", default="experiments/service")
    ap.add_argument("--cache-dir", default="",
                    help="preprocess disk cache shared across jobs")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    srv = BNServer((args.host, args.port), slots=args.slots,
                   elastic=args.elastic, run_dir=args.run_dir,
                   cache_dir=args.cache_dir)
    host, port = srv.server_address[:2]
    logger.info("bn_serve listening on http://%s:%d (slots=%d)",
                host, port, args.slots)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.shutdown_clean()


if __name__ == "__main__":
    main()
