"""Replica health snapshots: the router's view of one engine process.

The router never holds live references into a replica — it decides over
immutable ``ReplicaSnapshot`` values assembled from the PR 14 ops
surfaces (/healthz + /queries) on a bounded-staleness poll loop.  The
split here is deliberate and test-facing:

- ``scrape_replica`` is the ONLY function that touches the network
  (stdlib urllib against the replica's ops port);
- ``snapshot_from_bodies`` / ``unreachable`` are pure functions from
  scraped JSON bodies to a snapshot, so every routing decision in
  ``fleet/routing.py`` is unit-testable from literal dicts without a
  single socket (tests/test_fleet.py).
"""

from __future__ import annotations

import json
import urllib.request
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ReplicaSnapshot:
    """One replica's scraped state at one poll instant (immutable)."""

    #: "host:port" of the replica's SERVING socket — the routing key
    name: str
    host: str
    port: int
    #: did the scrape succeed at all?
    ok: bool
    #: the /healthz verdict: ok | degraded | unreachable
    status: str
    #: live query occupancy from the /queries table
    running: int = 0
    queued: int = 0
    #: admission counters (cumulative) — the shed history
    admitted: int = 0
    rejected: int = 0
    #: worst memmgr used/total ratio across the replica's managers
    mem_frac: float = 0.0
    #: warm plan fingerprints (result-cache inventory) — affinity keys
    warm_fps: frozenset = field(default_factory=frozenset)
    #: resumable journal stems visible to this replica (dead owners)
    resume_stems: tuple = ()
    #: time.monotonic() of the scrape (staleness accounting)
    scraped_at: float = 0.0

    @property
    def occupancy(self) -> int:
        return self.running + self.queued

    def fresh(self, now: float, staleness_s: float) -> bool:
        """Is this snapshot recent enough to route on?"""
        return self.ok and (now - self.scraped_at) <= staleness_s


def unreachable(name: str, host: str, port: int,
                scraped_at: float) -> ReplicaSnapshot:
    """The snapshot of a replica whose scrape failed: never routed to,
    but kept in the table so staleness/recovery is observable."""
    return ReplicaSnapshot(name=name, host=host, port=port, ok=False,
                           status="unreachable", scraped_at=scraped_at)


def snapshot_from_bodies(name: str, host: str, port: int,
                         health: dict, queries: dict,
                         scraped_at: float) -> ReplicaSnapshot:
    """Pure assembly of a snapshot from the two scraped JSON bodies.

    Tolerant by construction: every field degrades to a neutral value
    when absent (an older replica, a partially-failed collector) — a
    routing decision must never crash on a scrape-shape surprise."""
    running = queued = 0
    for row in queries.get("queries") or []:
        state = row.get("state")
        if state == "running":
            running += 1
        elif state == "queued":
            queued += 1
    admitted = rejected = 0
    for ent in (queries.get("admission") or {}).values():
        if isinstance(ent, dict):
            admitted += int(ent.get("admitted", 0))
            rejected += int(ent.get("rejected", 0))
    mem_frac = 0.0
    for st in health.get("memmgr") or []:
        total = st.get("total") or 0
        if total > 0:
            mem_frac = max(mem_frac, st.get("used", 0) / total)
    stems = tuple(
        ent["stem"] for ent in queries.get("resume_inventory") or []
        if not ent.get("owner_alive") and not ent.get("claimed")
        and "stem" in ent)
    return ReplicaSnapshot(
        name=name, host=host, port=port, ok=True,
        status=health.get("status", "ok"),
        running=running, queued=queued,
        admitted=admitted, rejected=rejected,
        mem_frac=mem_frac,
        warm_fps=frozenset(queries.get("warm_plan_fps") or ()),
        resume_stems=stems,
        scraped_at=scraped_at)


def scrape_text(host: str, ops_port: int, path: str = "/metrics",
                timeout_s: float = 2.0) -> str:
    """Fetch one ops endpoint body as raw text — the router federates
    each replica's /metrics exposition verbatim (re-labeling happens at
    render time, ``registry.render_federated``). Raises OSError on an
    unreachable endpoint; the poll loop treats that as a missed scrape,
    not a death."""
    with urllib.request.urlopen(
            f"http://{host}:{ops_port}{path}",
            timeout=timeout_s) as resp:
        return resp.read().decode("utf-8", "replace")


def scrape_replica(host: str, ops_port: int,
                   timeout_s: float = 2.0) -> tuple[dict, dict]:
    """Fetch (/healthz body, /queries body) from a replica's ops
    endpoint.  Raises OSError/ValueError on an unreachable or
    malformed endpoint — the poll loop maps that to ``unreachable``."""
    bodies = []
    for path in ("/healthz", "/queries"):
        with urllib.request.urlopen(
                f"http://{host}:{ops_port}{path}",
                timeout=timeout_s) as resp:
            bodies.append(json.loads(resp.read().decode()))
    return bodies[0], bodies[1]
