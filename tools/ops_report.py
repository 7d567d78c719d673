"""Render a post-mortem failure bundle — or a live ops-endpoint poll —
into a human post-mortem.

    python tools/ops_report.py <bundle_dir>          # post-mortem
    python tools/ops_report.py --url http://h:port   # live poll
    python tools/ops_report.py --dir <bundles_root>  # inventory table

The bundle mode prints the failure's identity (query, outcome, error,
site), the flight-recorder event timeline leading up to it (the
failing query's events flagged, neighbors interleaved), the scheduler /
memmgr / mesh state at failure time, and the explain-with-metrics tree
when the bundle carries one. The live mode polls /healthz, /queries and
/metrics and prints the same shape for a process that is still up.

``--url`` understands BOTH endpoint flavors: a replica's ops endpoint
(PR 14) and the fleet router's (``auron.fleet.ops_port``) — the
/healthz body's ``role`` key picks the renderer. Against a router it
prints the merged fleet query table (each row tagged with its replica)
and the per-replica health/occupancy table, dead replicas labeled
``down``. Fleet death bundles (``bundle_fleet_death_*``) render their
routing timeline, the dead replica's last scraped state, and the
survivor's failover record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_ts(us: float) -> str:
    return f"{us / 1e6:10.3f}s"


def _fmt_attrs(attrs: dict, limit: int = 6) -> str:
    items = list(attrs.items())[:limit]
    return " ".join(f"{k}={v}" for k, v in items)


def render_timeline(events: list[dict], query_id: str = "",
                    tail: int = 60) -> list[str]:
    """The failure's event timeline: last ``tail`` events, the failing
    query's rows marked with '>' so the cause reads at a glance."""
    lines = [f"  {'':1} {'ts':>11} {'cat':<9} {'event':<28} "
             f"{'query':<12} attrs"]
    for ev in events[-tail:]:
        mark = ">" if query_id and ev.get("query") == query_id else " "
        lines.append(
            f"  {mark} {_fmt_ts(ev.get('ts_us', 0.0))} "
            f"{ev.get('cat', '?'):<9} {ev.get('name', '?'):<28} "
            f"{(ev.get('query') or '-'):<12} "
            f"{_fmt_attrs(ev.get('attrs') or {})}")
    return lines


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError:
        return None


def render_fleet_death(path: str, mf: dict) -> str:
    """A fleet death bundle: the router's routing/failover timeline,
    the dead replica's last scraped state, and (when recovery landed)
    the survivor's failover record."""
    from auron_tpu.obs import flight_recorder as flight
    out = [
        f"fleet death bundle: {path}",
        f"  replica   : {mf.get('replica')}",
        f"  outcome   : {mf.get('outcome')}",
        f"  router pid: {mf.get('pid')}   created: "
        f"{mf.get('created_wall')}",
    ]
    fo = _load_json(os.path.join(path, "failover.json"))
    if fo:
        out.append(f"  recovery  : {fo.get('action')} on "
                   f"{fo.get('survivor')} after "
                   f"{fo.get('latency_s')}s")
    else:
        out.append("  recovery  : (no failover record — nothing was "
                   "in flight, or recovery failed)")
    health = _load_json(os.path.join(path, "replica_health.json"))
    if health:
        out.append("")
        out.append(f"dead replica's last scraped health: "
                   f"status={health.get('status')}"
                   + (f" reasons={health.get('reasons')}"
                      if health.get("reasons") else ""))
    queries = _load_json(os.path.join(path, "replica_queries.json"))
    if queries:
        rows = queries.get("queries") or []
        out.append(f"dead replica's last query table "
                   f"({len(rows)} rows):")
        for row in rows:
            out.append(f"  {row.get('query'):<12} "
                       f"{row.get('state'):<8} "
                       f"wall={row.get('wall_s')}s")
    tl = os.path.join(path, "routing_timeline.jsonl")
    if os.path.exists(tl):
        events = flight.read_jsonl(tl)
        out.append("")
        out.append(f"routing timeline ({len(events)} router events):")
        out.extend(render_timeline(events))
    stats = _load_json(os.path.join(path, "router_stats.json"))
    if stats:
        out.append("")
        out.append(f"router counters: {stats.get('router')}")
    return "\n".join(out) + "\n"


def render_bundle(path: str) -> str:
    from auron_tpu.obs import bundle as bundle_mod
    from auron_tpu.obs import flight_recorder as flight
    mf = bundle_mod.read_manifest(path)
    if mf.get("kind") == "fleet_death":
        return render_fleet_death(path, mf)
    qid = mf.get("query_id", "?")
    out = [
        f"post-mortem bundle: {path}",
        f"  query     : {qid}",
        f"  outcome   : {mf.get('outcome')}",
        f"  error     : {mf.get('error_type')}: {mf.get('error')}",
        f"  site      : {mf.get('site') or '-'}",
        f"  progress  : {mf.get('tasks_done')}/{mf.get('tasks_total')} "
        f"tasks",
        f"  pid       : {mf.get('pid')}   created: "
        f"{mf.get('created_wall')}",
    ]
    led = _load_json(os.path.join(path, "ledger.json"))
    if led:
        out.append(f"  cost      : device={led.get('device_s')}s "
                   f"host={led.get('host_total_s')}s "
                   f"wall={led.get('wall_s')}s "
                   f"rows={led.get('rows')} "
                   f"spill={_g(led, 'spill', 'bytes')}B "
                   f"shuffle={_g(led, 'shuffle', 'bytes')}B "
                   f"retries={_g(led, 'retries', 'transient_retries')}")
    flight_path = os.path.join(path, "flight.jsonl")
    if os.path.exists(flight_path):
        events = flight.read_jsonl(flight_path)
        out.append("")
        out.append(f"event timeline ({len(events)} recorded; "
                   f"'>' = the failing query):")
        out.extend(render_timeline(events, query_id=qid))
    sched = _load_json(os.path.join(path, "scheduler.json"))
    if sched:
        out.append("")
        out.append("scheduler at failure:")
        for row in sched.get("table", []):
            out.append(
                f"  {row.get('query'):<12} {row.get('state'):<8} "
                f"wall={row.get('wall_s')}s "
                f"tasks={row.get('tasks_done')}/{row.get('tasks_total')}"
                f" mem={row.get('mem_used_bytes', '-')}"
                f"/{row.get('mem_quota_bytes', '-')}")
        if "stats" in sched:
            st = sched["stats"]
            out.append(f"  admitted={st.get('admitted')} "
                       f"rejected={st.get('rejected')} "
                       f"dequeued={st.get('dequeued')}")
    mem = _load_json(os.path.join(path, "memmgr.json"))
    if mem:
        out.append("")
        out.append("memmgr at failure:")
        for st in mem:
            out.append(f"  used={st.get('used')}/{st.get('total')} "
                       f"consumers={st.get('num_consumers')} "
                       f"spills={st.get('num_spills')} "
                       f"queries={st.get('queries')}")
    mesh = _load_json(os.path.join(path, "mesh.json"))
    if mesh:
        out.append("")
        out.append(f"mesh plane: {json.dumps(mesh, default=str)[:500]}")
    stalls = sorted(p for p in os.listdir(path)
                    if p.startswith("stall_report_"))
    for p in stalls:
        rep = _load_json(os.path.join(path, p)) or {}
        out.append(f"stall report {p}: last_site="
                   f"{rep.get('last_site', '?')}")
    explain = os.path.join(path, "explain.txt")
    if os.path.exists(explain):
        out.append("")
        out.append("explain (metrics from completed tasks):")
        with open(explain) as f:
            out.extend("  " + ln.rstrip() for ln in f)
    return "\n".join(out) + "\n"


def _g(d: dict, *keys, default="-"):
    """Nested dict get for report rows (missing keys render '-')."""
    for k in keys:
        if not isinstance(d, dict) or k not in d:
            return default
        d = d[k]
    return d


def render_fleet_live(url: str, get, health: dict) -> str:
    """The router flavor of the live poll: per-replica health /
    occupancy (dead replicas labeled ``down``), the merged fleet query
    table, router counters, and the federated metrics' outcome view."""
    fleet = json.loads(get("/fleet/queries"))
    out = [f"live fleet poll: {url}",
           f"  status : {health.get('status')}  replicas "
           f"{health.get('replicas_live')}/"
           f"{health.get('replicas_total')} live"]
    rt = health.get("router") or {}
    out.append(f"  router : routed={rt.get('routed')} "
               f"spillovers={rt.get('spillovers')} "
               f"deaths={rt.get('replica_deaths')} "
               f"failovers={rt.get('failovers_resume')}+"
               f"{rt.get('failovers_reexecute')}")
    out.append("")
    out.append("replicas:")
    for label, rep in sorted((fleet.get("replicas") or {}).items()):
        out.append(f"  {label:<4} {rep.get('name'):<22} "
                   f"{rep.get('status'):<12} "
                   f"running={rep.get('running')} "
                   f"queued={rep.get('queued')} "
                   f"pid={rep.get('pid')}")
    out.append("")
    out.append("fleet queries (merged):")
    rows = fleet.get("queries") or []
    if not rows:
        out.append("  (idle)")
    for row in rows:
        out.append(f"  {row.get('replica'):<4} "
                   f"{row.get('query'):<12} {row.get('state'):<8} "
                   f"wall={row.get('wall_s')}s "
                   f"tasks={row.get('tasks_done')}/"
                   f"{row.get('tasks_total')}")
    from auron_tpu.obs import registry as obs_registry
    fams = obs_registry.parse_prometheus(get("/metrics").decode())
    up = fams.get("auron_fleet_replica_up")
    if up:
        out.append("")
        out.append("federated reachability (auron_fleet_replica_up):")
        for name, labels, value in up["samples"]:
            out.append(f"  {labels.get('replica'):<22} "
                       f"{'up' if value else 'DOWN'}")
    dur = fams.get("auron_query_duration_seconds")
    if dur:
        out.append("")
        out.append("fleet query outcomes (per replica):")
        for name, labels, value in dur["samples"]:
            if name.endswith("_count"):
                out.append(f"  replica={labels.get('replica', '-'):<4} "
                           f"outcome={labels.get('outcome'):<10} "
                           f"count={value:g}")
    return "\n".join(out) + "\n"


def render_live(url: str) -> str:
    import urllib.request

    def get(path: str) -> bytes:
        with urllib.request.urlopen(url.rstrip("/") + path,
                                    timeout=10) as r:
            return r.read()

    health = json.loads(get("/healthz"))
    if health.get("role") == "router":
        return render_fleet_live(url, get, health)
    queries = json.loads(get("/queries"))
    out = [f"live ops poll: {url}",
           f"  status : {health.get('status')}"
           + (f"  reasons: {health.get('reasons')}"
              if health.get("reasons") else "")]
    sched = health.get("scheduler") or {}
    for name, st in sched.items():
        out.append(f"  scheduler[{name}]: running={st.get('running')} "
                   f"queued={st.get('queued')}")
    out.append("")
    out.append("live queries:")
    rows = queries.get("queries", [])
    if not rows:
        out.append("  (idle)")
    for row in rows:
        out.append(f"  {row.get('query'):<12} {row.get('state'):<8} "
                   f"wall={row.get('wall_s')}s "
                   f"tasks={row.get('tasks_done')}/"
                   f"{row.get('tasks_total')}")
    out.append("")
    out.append("recent flight events:")
    events = [json.loads(ln) for ln in
              get("/flight?last=30").decode().splitlines() if ln]
    out.extend(render_timeline(events, tail=30))
    from auron_tpu.obs import registry as obs_registry
    fams = obs_registry.parse_prometheus(get("/metrics").decode())
    dur = fams.get("auron_query_duration_seconds")
    if dur:
        out.append("")
        out.append("query outcomes (auron_query_duration_seconds):")
        for name, labels, value in dur["samples"]:
            if name.endswith("_count"):
                out.append(f"  outcome={labels.get('outcome'):<10} "
                           f"count={value:g}")
    return "\n".join(out) + "\n"


def render_inventory(root: str) -> str:
    from auron_tpu.obs import bundle as bundle_mod
    out = [f"bundle inventory: {root}"]
    entries = bundle_mod.list_bundles(root)
    if not entries:
        out.append("  (no bundles)")
    for p in entries:
        try:
            mf = bundle_mod.read_manifest(p)
            out.append(f"  {os.path.basename(p):<28} "
                       f"{mf.get('outcome'):<18} "
                       f"{mf.get('error_type')}: "
                       f"{(mf.get('error') or '')[:60]}")
        except Exception as e:   # noqa: BLE001 — inventory best-effort
            out.append(f"  {os.path.basename(p):<28} <unreadable: {e}>")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bundle", nargs="?",
                    help="path to one bundle_<query_id>/ directory")
    ap.add_argument("--url", help="live ops endpoint "
                                  "(http://host:port) to poll instead")
    ap.add_argument("--dir", help="bundles root: print the inventory "
                                  "table")
    args = ap.parse_args(argv)
    if args.url:
        print(render_live(args.url), end="")
    elif args.dir:
        print(render_inventory(args.dir), end="")
    elif args.bundle:
        print(render_bundle(args.bundle), end="")
    else:
        ap.error("give a bundle directory, --url, or --dir")
    return 0


if __name__ == "__main__":
    sys.exit(main())
