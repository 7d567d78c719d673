"""Ops plane: the live in-process telemetry HTTP endpoint.

The reference engine ships a runtime HTTP service for live metrics and
profiling (pprof flamegraph + heap endpoints, auron/src/http/mod.rs:
25-108; plus a Spark UI tab). Our stack had every data plane — the
process registry, the scheduler, the memmgr ledger, the mesh fault
domain, the flight recorder — but only as per-query file exports or
in-process snapshots. This module is the scrape surface that makes a
LIVE process operable:

- ``GET /metrics``  — the registry's Prometheus text exposition
  (``obs/registry.render_prometheus``), conformance-pinned;
- ``GET /healthz``  — ok-vs-degraded verdict assembled from the
  watchdog stall counters, scheduler occupancy, memmgr pressure and
  the mesh plane's quarantine ledger;
- ``GET /queries``  — the live query table (id, running|queued, wall so
  far, tasks done/total, per-query memory vs quota, program-cache
  hits) across every scheduler in the process;
- ``GET /flight``   — the always-on flight recorder's ring as JSONL
  (``?query=<id>`` filters, ``?last=N`` tails).

One server per process, REFCOUNTED: every Session (and AuronServer)
built while ``auron.ops.enabled`` is on acquires it; the last close
releases and stops it. ``auron.ops.port`` 0 binds an ephemeral port,
logged at startup and surfaced as ``Session.ops_address`` / the
AuronServer ``ops_port`` stat. Handlers are read-only and best-effort:
a scrape can never mutate engine state, and a failing collector answers
500 instead of wedging the socket.
"""

from __future__ import annotations

import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

logger = logging.getLogger("auron_tpu.ops")


# ---------------------------------------------------------------------------
# collectors (read-only views over the process's planes)
# ---------------------------------------------------------------------------

def health() -> dict:
    """The /healthz body: per-plane state plus an overall verdict.
    ``degraded`` (not dead — the process is still serving) when mesh
    devices sit in quarantine or a memmgr runs past 90% of budget."""
    reasons: list[str] = []
    out: dict = {"status": "ok"}
    try:
        from auron_tpu.runtime import watchdog
        out["watchdog"] = watchdog.stats()
    except Exception:   # pragma: no cover - collectors best-effort
        out["watchdog"] = None
    try:
        from auron_tpu.runtime import scheduler
        out["scheduler"] = scheduler.aggregate_states()
    except Exception:   # pragma: no cover
        out["scheduler"] = None
    try:
        from auron_tpu.memmgr import manager as _mgr
        statuses = _mgr.aggregate_status()
        out["memmgr"] = statuses
        for st in statuses:
            if st["total"] > 0 and st["used"] / st["total"] > 0.9:
                reasons.append(
                    f"memory_pressure:{st['used']}/{st['total']}")
    except Exception:   # pragma: no cover
        out["memmgr"] = None
    try:
        from auron_tpu.parallel import mesh as _mesh
        plane = _mesh.current_plane()
        if plane is not None:
            st = plane.stats()
            out["mesh"] = st
            if st.get("quarantined"):
                reasons.append(
                    f"mesh_quarantined:{st['quarantined']}")
        else:
            out["mesh"] = None
    except Exception:   # pragma: no cover
        out["mesh"] = None
    if reasons:
        out["status"] = "degraded"
        out["reasons"] = reasons
    return out


def queries() -> dict:
    """The /queries body: live table + per-scheduler admission stats
    (the same table the serving STATS frame answers)."""
    from auron_tpu.runtime import scheduler
    table = scheduler.aggregate_query_table()
    admission: dict = {}
    for s in list(scheduler._SCHEDULERS):
        st = s.stats()
        ent = admission.setdefault(
            s.name, {"admitted": 0, "rejected": 0, "dequeued": 0})
        for k in ent:
            ent[k] += st[k]
    out = {"queries": table, "admission": admission}
    try:
        from auron_tpu.cache import aot as _aot
        from auron_tpu.cache import result_cache as _rcache
        out["cache"] = _rcache.get_cache().stats()
        out["aot"] = _aot.last_stats()
        # warm inventory for the fleet router's affinity routing: the
        # plan fingerprints this process can serve from its result
        # cache without executing anything
        out["warm_plan_fps"] = _rcache.get_cache().warm_plan_fps()
    except Exception:   # pragma: no cover - cache plane optional
        pass
    try:
        from auron_tpu import config as _cfg
        from auron_tpu.runtime import journal as _jrn
        jdir = _cfg.get_config().get(_cfg.JOURNAL_DIR)
        if jdir:
            # failover inventory: which journaled queries under the
            # (fleet-shared) journal dir could a survivor RESUME
            out["resume_inventory"] = _jrn.resume_inventory(jdir)
    except Exception:   # pragma: no cover - journal plane optional
        pass
    return out


# ---------------------------------------------------------------------------
# HTTP plumbing
# ---------------------------------------------------------------------------

class _OpsHandler(BaseHTTPRequestHandler):
    #: stop http.server from logging every scrape to stderr
    def log_message(self, fmt, *args):   # noqa: D102 - stdlib override
        pass

    def _reply(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_json(self, obj, code: int = 200) -> None:
        self._reply(code, json.dumps(obj, indent=2,
                                     default=str).encode(),
                    "application/json")

    def do_GET(self):   # noqa: N802 - stdlib casing
        url = urlparse(self.path)
        q = parse_qs(url.query)
        try:
            self._route(url.path.rstrip("/") or "/", q)
        except BrokenPipeError:   # pragma: no cover - client went away
            pass
        except Exception as e:   # noqa: BLE001 — scrape must not wedge
            logger.exception("ops endpoint %s failed", self.path)
            try:
                self._reply(500, f"{type(e).__name__}: {e}".encode(),
                            "text/plain; charset=utf-8")
            except OSError:   # pragma: no cover
                pass

    def _route(self, path: str, q: dict) -> None:
        self._count(path)
        if path == "/metrics":
            from auron_tpu.obs import registry
            body = registry.get_registry().render_prometheus().encode()
            self._reply(200, body,
                        "text/plain; version=0.0.4; charset=utf-8")
        elif path == "/healthz":
            # degraded still answers 200 (the process IS serving —
            # degraded-vs-ok is the body's verdict, not liveness)
            self._reply_json(health())
        elif path == "/queries":
            self._reply_json(queries())
        elif path == "/flight":
            from auron_tpu.obs import flight_recorder
            query_id = (q.get("query") or [None])[0]
            last = q.get("last")
            body = flight_recorder.recorder().dump_jsonl(
                query_id=query_id,
                last=int(last[0]) if last else None).encode()
            self._reply(200, body, "application/x-ndjson")
        elif path == "/":
            self._reply_json({
                "service": "auron ops endpoint",
                "endpoints": ["/metrics", "/healthz", "/queries",
                              "/flight"]})
        else:
            self._reply(404, f"no such endpoint {path!r}\n".encode(),
                        "text/plain; charset=utf-8")

    #: the fixed label vocabulary of the scrape counter — unknown
    #: paths bucket under "other", or a port scanner looping over
    #: unique URLs would mint one counter instrument per URL (the
    #: classic Prometheus cardinality leak)
    _KNOWN_PATHS = frozenset(
        ("/metrics", "/healthz", "/queries", "/flight", "/"))

    @classmethod
    def _count(cls, path: str) -> None:
        try:
            from auron_tpu.obs import registry
            if registry.enabled():
                label = path if path in cls._KNOWN_PATHS else "other"
                registry.get_registry().counter(
                    "auron_ops_scrapes_total", path=label).inc()
        except Exception:   # pragma: no cover - telemetry best-effort
            pass


class OpsServer:
    """One process's ops endpoint (ThreadingHTTPServer on a daemon
    thread). ``address`` is the BOUND (host, port) — the ephemeral-port
    discovery surface.

    ``handler_cls`` swaps the route table (the fleet router serves its
    federated views through the same plumbing); ``context`` is exposed
    to handlers as ``self.server.context``."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 handler_cls=None, context=None):
        self._httpd = ThreadingHTTPServer((host, port),
                                          handler_cls or _OpsHandler)
        self._httpd.daemon_threads = True
        self._httpd.context = context
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> tuple:
        return self._httpd.server_address[:2]

    @property
    def port(self) -> int:
        return self.address[1]

    def start(self) -> "OpsServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="auron-ops-server")
        self._thread.start()
        logger.info("ops endpoint listening on http://%s:%d "
                    "(/metrics /healthz /queries /flight)",
                    *self.address)
        return self

    def stop(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:   # pragma: no cover - teardown best-effort
            logger.exception("ops endpoint shutdown failed")
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# ---------------------------------------------------------------------------
# process-wide refcounted singleton (Session / AuronServer lifecycle)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_SERVER: Optional[OpsServer] = None
_REFS = 0


def ensure_started(config=None) -> Optional[OpsServer]:
    """Acquire the process ops endpoint when ``auron.ops.enabled`` is
    on (None otherwise): the first acquirer binds and starts it —
    ``auron.ops.port``, 0 = ephemeral — and every acquirer must pair
    with one :func:`release`. Idempotent across Sessions: they share
    the one server."""
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    if not conf.get(cfg.OPS_ENABLED):
        return None
    global _SERVER, _REFS
    with _LOCK:
        if _SERVER is None:
            try:
                _SERVER = OpsServer(
                    port=int(conf.get(cfg.OPS_PORT))).start()
            except OSError:
                # a taken fixed port must not fail Session construction
                # — the ops plane is observability, never availability
                logger.exception("could not bind the ops endpoint")
                return None
        _REFS += 1
        return _SERVER


def release() -> None:
    """Drop one acquisition; the last release stops the server (the
    Session.close() clean-shutdown contract)."""
    global _SERVER, _REFS
    with _LOCK:
        if _REFS == 0:
            return
        _REFS -= 1
        if _REFS > 0 or _SERVER is None:
            return
        server, _SERVER = _SERVER, None
    server.stop()


def current() -> Optional[OpsServer]:
    with _LOCK:
        return _SERVER
