"""Serving fleet: a router/coordinator over replicated AuronServers.

- ``fleet.snapshot`` — immutable replica health snapshots (scrape +
  pure parsers over the PR 14 ops bodies);
- ``fleet.routing``  — pure decisions: least-loaded order, warm
  affinity, spill-over backoff, failover action, shed verdicts;
- ``fleet.router``   — the I/O: a wire-compatible front that routes,
  spills sheds over, and fails dead replicas' queries over to
  survivors (journal RESUME or guarded re-execution);
- ``fleet.replica``  — the subprocess harness the fleet tooling boots
  real replicas with.

A plain ``AuronClient`` pointed at the router sees one server; the
wire protocol is unchanged.
"""

from auron_tpu.fleet.router import FleetRouter
from auron_tpu.fleet.replica import FleetHarness, ReplicaProc, \
    spawn_replica, tpu_chip_env
from auron_tpu.fleet.snapshot import ReplicaSnapshot, \
    snapshot_from_bodies, unreachable
from auron_tpu.fleet import routing

__all__ = [
    "FleetRouter", "FleetHarness", "ReplicaProc", "spawn_replica",
    "tpu_chip_env",
    "ReplicaSnapshot", "snapshot_from_bodies", "unreachable",
    "routing",
]
