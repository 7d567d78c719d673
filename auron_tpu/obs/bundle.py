"""Post-mortem failure bundles: one self-contained diagnostic directory
per classified query failure.

When a production query is shed, misses its deadline, stalls out, loses
its mesh, or trips over a corrupt journal, the operator's question is
always the same: *what was the process doing in the seconds before?*
Every plane that can answer already exists — the flight recorder's ring,
the scheduler/memmgr/mesh stats, the stall reports, the
metric tree — but each lives somewhere else and most are gone once the
process moves on. This module freezes them together at the unwind:

``bundle_<query_id>/``
    ``bundle.json``        manifest: schema, query id, outcome, error
    ``flight.jsonl``       flight-recorder dump (the failing query's
                           events with its neighbors interleaved — the
                           neighbor causing the pressure is evidence)
    ``explain.txt``        the query's plan tree WITH the metrics its
                           completed tasks mirrored (obs/metric_tree)
    ``metrics.prom``       registry exposition at failure time
    ``scheduler.json``     admission stats + live query table
    ``memmgr.json``        per-manager status (per-query ledgers)
    ``mesh.json``          mesh plane fault ledger (when armed)
    ``journal.json``       the query's journal state (when journaled)
    ``config.json``        resolved config snapshot + trace_salt
    ``stall_report_*.json``copied from auron.trace.dir (when present)

Triggering: ``maybe_write`` is called from the executor/serving unwind
(Session's admission scope, the serving handler) with the terminal
exception; only CLASSIFIED failures bundle — ``classify`` maps
MemoryExhausted, DeadlineExceeded, TaskStalled, MeshUnavailable and
JournalCorrupt/JournalInvalidated to an outcome tag and everything else
(plain cancels, admission sheds, unclassified crashes — tracebacks
already serve those) to None.

Retention: ``auron.bundle.max_bundles`` with oldest-first eviction, so
a crash loop can never fill the disk. Every artifact write is
best-effort and individually guarded — a failing diagnostic must never
shadow the query's own classified error.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import shutil
import time
from typing import Optional

logger = logging.getLogger("auron_tpu.ops")

SCHEMA_VERSION = 1

#: outcome tag per bundle-eligible classified-failure class (order
#: matters: DeadlineExceeded IS-A QueryCancelled and MeshUnavailable
#: IS-A DeviceExecutionError — most-derived first)
_BUNDLE_CLASSES = (
    ("MemoryExhausted", "memory_exhausted"),
    ("DeadlineExceeded", "deadline"),
    ("TaskStalled", "stalled"),
    ("MeshUnavailable", "mesh_unavailable"),
    ("JournalCorrupt", "journal_corrupt"),
    ("JournalInvalidated", "journal_invalidated"),
)


def classify(exc) -> Optional[str]:
    """Outcome tag when ``exc`` is a bundle-eligible classified failure,
    else None (no bundle: plain cancels are the caller's verdict,
    admission sheds never held resources, unclassified crashes carry a
    traceback)."""
    if exc is None:
        return None
    from auron_tpu import errors
    for cls_name, tag in _BUNDLE_CLASSES:
        cls = getattr(errors, cls_name, None)
        if cls is not None and isinstance(exc, cls):
            return tag
    return None


def armed(config=None) -> bool:
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    return bool(conf.get(cfg.BUNDLE_ENABLED))


def bundle_dir(config=None) -> str:
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    d = conf.get(cfg.BUNDLE_DIR)
    if not d:
        import tempfile
        d = os.path.join(tempfile.gettempdir(), "auron-bundles")
    return d


def list_bundles(dir_path: str) -> list[str]:
    """Bundle directories under ``dir_path``, oldest first."""
    entries = [p for p in glob.glob(os.path.join(dir_path, "bundle_*"))
               if os.path.isdir(p)]
    entries.sort(key=lambda p: (os.path.getmtime(p), p))
    return entries


def maybe_write(exc, token=None, config=None, scheduler=None,
                mem_manager=None) -> Optional[str]:
    """Write one post-mortem bundle for a classified failure; returns
    the bundle path, or None when disarmed / not bundle-eligible.
    NEVER raises — the caller is an unwind path re-raising the query's
    own classified error."""
    try:
        if not armed(config):
            return None
        outcome = classify(exc)
        if outcome is None:
            return None
        return _write(exc, outcome, token=token, config=config,
                      scheduler=scheduler, mem_manager=mem_manager)
    except Exception:   # noqa: BLE001 — diagnostics must not shadow
        logger.exception("post-mortem bundle write failed")
        return None


def _write(exc, outcome: str, token=None, config=None, scheduler=None,
           mem_manager=None) -> str:
    root = bundle_dir(config)
    os.makedirs(root, exist_ok=True)
    qid = getattr(token, "query_id", "") or "unknown"
    name = f"bundle_{qid}"
    path = os.path.join(root, name)
    n = 2
    while os.path.exists(path):   # recycled id (cross-process dir)
        path = os.path.join(root, f"{name}_{n}")
        n += 1
    # stage on a dot-prefixed temp dir + rename: the eviction scan and
    # the chaos audit must never observe a half-written bundle
    tmp = os.path.join(root, f".{os.path.basename(path)}.part")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)

    def art(filename: str, producer) -> None:
        """One guarded artifact: a failing collector costs its file,
        never the bundle."""
        try:
            body = producer()
            if body is None:
                return
            with open(os.path.join(tmp, filename), "w") as f:
                f.write(body)
        except Exception:   # noqa: BLE001
            logger.exception("bundle artifact %s failed", filename)

    art("bundle.json", lambda: json.dumps({
        "schema_version": SCHEMA_VERSION,
        "query_id": qid,
        "outcome": outcome,
        "error_type": type(exc).__name__,
        "error": str(exc)[:2000],
        "reason": getattr(token, "reason", None),
        "site": getattr(exc, "site", None),
        "tasks_done": getattr(token, "tasks_done", 0),
        "tasks_total": getattr(token, "tasks_total", 0),
        "created_wall": time.time(),
        "pid": os.getpid(),
    }, indent=2, default=str))
    art("flight.jsonl", _flight_dump)
    art("ledger.json", lambda: _ledger_json(token))
    art("explain.txt", lambda: _explain_text(token))
    art("metrics.prom", _metrics_text)
    art("scheduler.json", lambda: _scheduler_json(scheduler))
    art("memmgr.json", lambda: _memmgr_json(mem_manager))
    art("mesh.json", _mesh_json)
    art("journal.json", lambda: _journal_json(token))
    art("config.json", lambda: _config_json(config))
    _copy_stall_reports(tmp, config)
    os.replace(tmp, path)
    _evict(root, config)
    try:
        from auron_tpu.obs import registry
        if registry.enabled():
            registry.get_registry().counter(
                "auron_bundles_written_total", outcome=outcome).inc()
    except Exception:   # pragma: no cover - telemetry best-effort
        pass
    logger.warning("post-mortem bundle written: %s (%s: %s)", path,
                   type(exc).__name__, str(exc)[:200])
    return path


def write_fleet_death(dead_name: str, dead_health, dead_queries,
                      router_stats, timeline: str,
                      config=None) -> Optional[str]:
    """Fleet failure bundle: one directory per liveness-confirmed
    replica death, written by the ROUTER (the only process that saw
    the whole story):

    ``bundle_fleet_death_<replica>/``
        ``bundle.json``            manifest (kind=fleet_death)
        ``routing_timeline.jsonl`` the router's flight ring — route /
                                   forward / death / failover events
        ``replica_health.json``    the dead replica's LAST scraped
                                   /healthz body (its final state)
        ``replica_queries.json``   its last /queries table
        ``router_stats.json``      router counters + fleet snapshot

    The survivor's recovery record (``failover.json``) is appended via
    :func:`add_artifact` once failover lands — recovery happens AFTER
    the death, so the bundle is sealed first. NEVER raises; returns
    the bundle path or None (disarmed / write failure)."""
    try:
        if not armed(config):
            return None
        root = bundle_dir(config)
        os.makedirs(root, exist_ok=True)
        safe = str(dead_name).replace(":", "_").replace("/", "_")
        name = f"bundle_fleet_death_{safe}"
        path = os.path.join(root, name)
        n = 2
        while os.path.exists(path):   # the same replica can die twice
            path = os.path.join(root, f"{name}_{n}")
            n += 1
        tmp = os.path.join(root, f".{os.path.basename(path)}.part")
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)

        def art(filename: str, producer) -> None:
            try:
                body = producer()
                if body is None:
                    return
                with open(os.path.join(tmp, filename), "w") as f:
                    f.write(body)
            except Exception:   # noqa: BLE001
                logger.exception("bundle artifact %s failed", filename)

        art("bundle.json", lambda: json.dumps({
            "schema_version": SCHEMA_VERSION,
            "kind": "fleet_death",
            "replica": dead_name,
            "outcome": "replica_death",
            "created_wall": time.time(),
            "pid": os.getpid(),
        }, indent=2, default=str))
        art("routing_timeline.jsonl", lambda: timeline or None)
        art("replica_health.json",
            lambda: (json.dumps(dead_health, indent=2, default=str)
                     if dead_health else None))
        art("replica_queries.json",
            lambda: (json.dumps(dead_queries, indent=2, default=str)
                     if dead_queries else None))
        art("router_stats.json",
            lambda: json.dumps(router_stats, indent=2, default=str))
        os.replace(tmp, path)
        _evict(root, config)
        try:
            from auron_tpu.obs import registry
            if registry.enabled():
                registry.get_registry().counter(
                    "auron_bundles_written_total",
                    outcome="replica_death").inc()
        except Exception:   # pragma: no cover - telemetry best-effort
            pass
        logger.warning("fleet death bundle written: %s (replica %s)",
                       path, dead_name)
        return path
    except Exception:   # noqa: BLE001 — diagnostics must not shadow
        logger.exception("fleet death bundle write failed")
        return None


def add_artifact(path: str, filename: str, body: str) -> bool:
    """Append one artifact to an ALREADY-sealed bundle (the router's
    ``failover.json``: the survivor's recovery record lands after the
    death bundle was written). Best-effort, never raises."""
    try:
        if not path or not os.path.isdir(path):
            return False
        with open(os.path.join(path, filename), "w") as f:
            f.write(body)
        return True
    except Exception:   # noqa: BLE001
        logger.exception("bundle add_artifact %s failed", filename)
        return False


# -- artifact producers (each individually guarded by art()) ----------------

def _ledger_json(token) -> Optional[str]:
    """The failing query's cost ledger (serving stashes it on the
    cancel token at finalize — ``outcome=failed`` partial costs are
    exactly what a post-mortem wants)."""
    led = getattr(token, "cost_ledger", None)
    if not isinstance(led, dict):
        return None
    return json.dumps(led, indent=2, default=str)


def _flight_dump() -> str:
    from auron_tpu.obs import flight_recorder
    return flight_recorder.recorder().dump_jsonl()


def _explain_text(token) -> Optional[str]:
    tree = getattr(token, "plan_tree", None)
    if tree is None:
        return None
    from auron_tpu.obs import metric_tree as mt
    return mt.render(tree)


def _metrics_text() -> str:
    from auron_tpu.obs import registry
    return registry.get_registry().render_prometheus()


def _scheduler_json(scheduler) -> str:
    from auron_tpu.runtime import scheduler as sched_mod
    body = {"table": sched_mod.aggregate_query_table()}
    if scheduler is not None:
        body["stats"] = scheduler.stats()
    else:
        body["states"] = sched_mod.aggregate_states()
    return json.dumps(body, indent=2, default=str)


def _memmgr_json(mem_manager) -> Optional[str]:
    if mem_manager is not None:
        statuses = [mem_manager.status()]
    else:
        from auron_tpu.memmgr import manager as _mgr
        statuses = _mgr.aggregate_status()
    return json.dumps(statuses, indent=2, default=str)


def _mesh_json() -> Optional[str]:
    from auron_tpu.parallel import mesh as _mesh
    plane = _mesh.current_plane()
    if plane is None:
        return None
    return json.dumps(plane.stats(), indent=2, default=str)


def _journal_json(token) -> Optional[str]:
    jr = getattr(token, "journal", None)
    if jr is None:
        return None
    body = {}
    for attr in ("stem", "path", "scope", "num_partitions",
                 "query_id"):
        v = getattr(jr, attr, None)
        if v is not None:
            body[attr] = v
    try:
        from auron_tpu.runtime import journal as jrn
        body["stats"] = jrn.last_stats()
    except Exception:   # pragma: no cover - stats optional
        pass
    return json.dumps(body, indent=2, default=str)


def _config_json(config) -> str:
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    resolved = {}
    for opt in cfg.options():
        try:
            resolved[opt.key] = conf.get(opt.key)
        except Exception:   # pragma: no cover - env parse failure
            resolved[opt.key] = "<unresolvable>"
    return json.dumps({"resolved": resolved,
                       "trace_salt": list(cfg.trace_salt())},
                      indent=2, default=str)


def _copy_stall_reports(tmp: str, config, limit: int = 8) -> None:
    """Copy recent stall reports from auron.trace.dir (the watchdog
    writes them there) — best-effort, bounded."""
    try:
        from auron_tpu import config as cfg
        conf = config if config is not None else cfg.get_config()
        tdir = conf.get(cfg.TRACE_DIR)
        if not tdir or not os.path.isdir(tdir):
            return
        reports = sorted(
            glob.glob(os.path.join(tdir, "stall_report_*.json")),
            key=os.path.getmtime)[-limit:]
        for p in reports:
            shutil.copy(p, os.path.join(tmp, os.path.basename(p)))
    except Exception:   # noqa: BLE001
        logger.exception("bundle stall-report copy failed")


def _evict(root: str, config) -> None:
    """Oldest-first retention: keep at most auron.bundle.max_bundles."""
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    keep = int(conf.get(cfg.BUNDLE_MAX_BUNDLES))
    if keep <= 0:
        return
    entries = list_bundles(root)
    for victim in entries[:-keep] if len(entries) > keep else []:
        shutil.rmtree(victim, ignore_errors=True)


def read_manifest(path: str) -> dict:
    """Load one bundle's manifest (tools/ops_report.py, chaos audit)."""
    with open(os.path.join(path, "bundle.json")) as f:
        return json.load(f)
