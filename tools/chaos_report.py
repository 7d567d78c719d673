"""Chaos-battery sweep report: injected faults vs recovery outcome.

Runs the chaos harness (auron_tpu/it/chaos.py) across N seeds for every
(scenario, fault plan) pair of the battery and prints a site-by-site
table: how many faults each plan injected, how many runs recovered to
bit-identical output, how many surfaced a classified ``AuronError`` —
and, the failure buckets, how many diverged silently (``mismatch``) or
crashed unclassified. A non-zero exit means the robustness contract
broke somewhere in the sweep; the failing (plan, seed) pairs replay
exactly via ``auron.faults.plan`` / ``auron.faults.seed``.

    python tools/chaos_report.py                   # default 8 seeds
    python tools/chaos_report.py --seeds 32
    python tools/chaos_report.py --scenario spill_sort

The last stdout line is one JSON record (same driver contract as
bench.py / compile_report.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the battery's (scenario, plan) pairs — one per site/kind with traffic
PLANS = [
    ("rss_pipeline", "rss.write:io_error@0.2"),
    ("rss_pipeline", "rss.write:corrupt@0.3"),
    ("rss_pipeline", "rss.flush:io_error@0.4"),
    ("rss_pipeline", "rss.commit:fatal@0.5"),
    ("rss_pipeline", "rss.fetch:corrupt@0.1"),
    ("rss_pipeline", "rss.fetch:io_error@0.3"),
    ("spill_sort", "spill.write:io_error@0.3"),
    ("spill_sort", "spill.write:corrupt@0.4"),
    ("spill_sort", "spill.read:io_error@0.4"),
    ("spill_sort", "spill.read:corrupt@0.15"),
    ("agg_pipeline", "device.compute:io_error@0.3"),
    ("agg_pipeline", "device.compute:fatal@0.5"),
    ("agg_pipeline", "program.build:io_error@0.2"),
    ("agg_pipeline", "device.compute:io_error@0.2;rss.fetch:corrupt@0.1"),
    # Chaos 2.0 lifecycle battery (PR 8): cancel races, stall-watchdog
    # hangs, forced memory-pressure sheds
    ("lifecycle_pipeline", "cancel.race:cancel@0.3"),
    ("lifecycle_pipeline", "task.hang:hang@0.15"),
    ("lifecycle_pipeline", "memmgr.deny:deny@0.5"),
    ("lifecycle_pipeline", "cancel.race:cancel@0.2;task.hang:hang@0.1"),
    # concurrency battery (the [serving] scheduler plane): admission
    # denies + forced memory pressure against racing queries
    ("overload", "sched.admit:deny@0.5"),
    ("overload", "memmgr.deny:deny@0.4"),
    ("overload", "sched.admit:deny@0.3;memmgr.deny:deny@0.3"),
    # mesh fault domain (ISSUE 12): per-round device losses recover by
    # route demotion (identical, not merely classified), hangs drive
    # the straggler defense, gang-door cancels dequeue cleanly
    ("mesh_pipeline", "mesh.all_to_all:io_error@0.3"),
    ("mesh_pipeline", "mesh.all_to_all:fatal@0.5"),
    ("mesh_pipeline", "mesh.all_to_all:hang@0.15"),
    ("mesh_pipeline", "mesh.gang:cancel@0.5"),
    ("mesh_pipeline",
     "mesh.all_to_all:io_error@0.2;device.compute:io_error@0.1"),
    # crash-safe query journal (ISSUE 13): write/commit faults must
    # DEGRADE journaling (journal.disable), never the query — every run
    # identical, no journal file left behind
    ("journal_pipeline", "journal.write:io_error@0.3"),
    ("journal_pipeline", "journal.write:fatal@0.5"),
    ("journal_pipeline", "journal.commit:io_error@0.5"),
    ("journal_pipeline",
     "journal.write:io_error@0.2;rss.write:io_error@0.2"),
    # serving fleet (ISSUE 19): a replica SIGKILLed mid-query every run
    # (the scenario's own drill) PLUS seeded faults on the router's own
    # sites — routing errors and forward-leg breaks must end in a
    # spill-over, a failover, or a classified verdict, with the shared
    # journal dir clean after teardown
    ("fleet_failover", "fleet.route:io_error@0.25"),
    ("fleet_failover", "fleet.forward:io_error@0.25"),
    ("fleet_failover",
     "fleet.route:io_error@0.15;fleet.forward:io_error@0.15"),
]


def lifecycle_summary() -> dict:
    """Process-level lifecycle telemetry accumulated over the sweep:
    cancel-to-unwind latency percentiles per kind (the registry
    histogram the acceptance gate reads), stall detections, and
    degradation-ladder rung counts."""
    out = {"cancel_latency_s": {}, "stall_detections": 0,
           "pressure_rungs": {}, "admission_sheds": {}}
    try:
        from auron_tpu.obs import registry as obs_registry
        snap = obs_registry.get_registry().snapshot()
        for key, val in snap.items():
            if key.startswith("auron_cancel_latency_seconds"):
                kind = key.split('kind="')[1].rstrip('"}') \
                    if 'kind="' in key else "all"
                out["cancel_latency_s"][kind] = {
                    "count": val["count"],
                    "p50": round(val["p50"], 4),
                    "p99": round(val["p99"], 4)}
            elif key.startswith("auron_memmgr_pressure_total"):
                rung = key.split('rung="')[1].rstrip('"}') \
                    if 'rung="' in key else "?"
                out["pressure_rungs"][rung] = int(val)
            elif key.startswith("auron_sched_rejected_total"):
                reason = key.split('reason="')[1].rstrip('"}') \
                    if 'reason="' in key else "?"
                out["admission_sheds"][reason] = int(val)
    except Exception:
        pass
    try:
        from auron_tpu.runtime import watchdog
        out["stall_detections"] = watchdog.stall_totals()
    except Exception:
        pass
    return out


def mesh_summary() -> dict:
    """Mesh-recovery telemetry accumulated over the sweep: route
    demotions by reason (device_loss vs straggler), device-loss
    quarantines, straggler detections and stall verdicts the round
    guard downgraded to slow rounds — the fault domain's ledger
    alongside the per-(plan, seed) contract table."""
    out = {"demotions": {}, "quarantines": 0, "stragglers": 0,
           "rounds_forgiven": 0, "device_losses": 0}
    try:
        from auron_tpu.obs import registry as obs_registry
        snap = obs_registry.get_registry().snapshot()
        for key, val in snap.items():
            if key.startswith("auron_mesh_demotions_total"):
                reason = key.split('reason="')[1].rstrip('"}') \
                    if 'reason="' in key else "?"
                out["demotions"][reason] = int(val)
            elif key.startswith("auron_mesh_quarantines_total"):
                out["quarantines"] = int(val)
            elif key.startswith("auron_mesh_stragglers_total"):
                out["stragglers"] = int(val)
    except Exception:
        pass
    try:
        from auron_tpu.runtime import watchdog
        out["rounds_forgiven"] = watchdog.mesh_rounds_forgiven()
    except Exception:
        pass
    try:
        from auron_tpu.parallel import mesh as mesh_mod
        plane = mesh_mod._PLANE[1]
        if plane is not None:
            out["device_losses"] = plane.device_losses
    except Exception:
        pass
    return out


def run_sweep(seeds: int, scenario_filter: str | None) -> dict:
    from auron_tpu.it import chaos

    rows = []
    failures = []
    sites: dict = {}
    with tempfile.TemporaryDirectory(prefix="chaos_report_") as d:
        scenarios = {name: factory(os.path.join(d, name))
                     for name, factory in chaos.SCENARIOS.items()}
        for scen_name, plan in PLANS:
            if scenario_filter and scen_name != scenario_filter:
                continue
            agg = {"identical": 0, "classified": 0, "mismatch": 0,
                   "unclassified": 0}
            injected = 0
            leaked = 0
            for seed in range(1, seeds + 1):
                o = chaos.run_chaos(scenarios[scen_name], plan, seed)
                agg[o.status] += 1
                injected += sum(sum(v.values())
                                for v in o.injected.values())
                leaked += len(o.leaks)
                # site→span correlation, aggregated across the sweep:
                # each injected site accumulates the recovery spans its
                # faults triggered (trace ids make single runs
                # replayable/inspectable)
                for site, c in o.correlation.items():
                    s = sites.setdefault(
                        site, {"injected": 0, "recovery": {}, "runs": 0})
                    s["injected"] += c["injected"]
                    s["runs"] += 1
                    for name, n in c["recovery"].items():
                        s["recovery"][name] = \
                            s["recovery"].get(name, 0) + n
                if not o.ok:
                    failures.append({
                        "scenario": scen_name, "plan": plan, "seed": seed,
                        "status": o.status, "error_type": o.error_type,
                        "error": o.error, "leaks": o.leaks,
                        "trace_id": o.trace_id})
            rows.append({"scenario": scen_name, "plan": plan,
                         "injected": injected, "leaked": leaked, **agg})
    return {"seeds": seeds, "rows": rows, "failures": failures,
            "sites": sites, "lifecycle": lifecycle_summary(),
            "mesh": mesh_summary()}


def print_table(report: dict) -> None:
    w_plan = max(len(r["plan"]) for r in report["rows"])
    hdr = (f"{'scenario':13s} {'fault plan':{w_plan}s} {'inj':>5s} "
           f"{'ident':>5s} {'class':>5s} {'mism':>4s} {'uncls':>5s} "
           f"{'leak':>4s}")
    print(hdr)
    print("-" * len(hdr))
    for r in report["rows"]:
        print(f"{r['scenario']:13s} {r['plan']:{w_plan}s} "
              f"{r['injected']:>5d} {r['identical']:>5d} "
              f"{r['classified']:>5d} {r['mismatch']:>4d} "
              f"{r['unclassified']:>5d} {r['leaked']:>4d}")
    total = {k: sum(r[k] for r in report["rows"])
             for k in ("injected", "identical", "classified", "mismatch",
                       "unclassified", "leaked")}
    print("-" * len(hdr))
    print(f"{'TOTAL':13s} {'':{w_plan}s} {total['injected']:>5d} "
          f"{total['identical']:>5d} {total['classified']:>5d} "
          f"{total['mismatch']:>4d} {total['unclassified']:>5d} "
          f"{total['leaked']:>4d}")
    sites = report.get("sites") or {}
    if sites:
        print()
        print("site -> recovery-span correlation "
              "(fault events linked to the recovery they triggered)")
        w_site = max(len(s) for s in sites)
        for site in sorted(sites):
            s = sites[site]
            rec = ", ".join(f"{k}x{v}"
                            for k, v in sorted(s["recovery"].items())) \
                or "-"
            print(f"  {site:{w_site}s}  injected={s['injected']:<5d} "
                  f"runs={s['runs']:<4d} recovery: {rec}")
    life = report.get("lifecycle") or {}
    if life.get("cancel_latency_s") or life.get("stall_detections") \
            or life.get("pressure_rungs") or life.get("admission_sheds"):
        print()
        print("lifecycle (cancel latency / stalls / pressure rungs / "
              "admission sheds)")
        for kind, p in sorted(life.get("cancel_latency_s", {}).items()):
            print(f"  cancel->unwind [{kind:9s}]  n={p['count']:<4d} "
                  f"p50={p['p50']*1000:.1f}ms p99={p['p99']*1000:.1f}ms")
        print(f"  stall detections: {life.get('stall_detections', 0)}")
        rungs = ", ".join(f"{k}x{v}" for k, v in
                          sorted(life.get("pressure_rungs", {}).items())) \
            or "-"
        print(f"  degradation rungs taken: {rungs}")
        sheds = ", ".join(f"{k}x{v}" for k, v in
                          sorted(life.get("admission_sheds", {}).items())) \
            or "-"
        print(f"  admission sheds: {sheds}")
    m = report.get("mesh") or {}
    if m.get("demotions") or m.get("quarantines") or m.get("stragglers") \
            or m.get("rounds_forgiven"):
        print()
        print("mesh recovery (route demotions / quarantines / "
              "straggler defense)")
        dem = ", ".join(f"{k}x{v}" for k, v in
                        sorted(m.get("demotions", {}).items())) or "-"
        print(f"  route demotions by reason: {dem}")
        print(f"  device-loss quarantines: {m.get('quarantines', 0)} "
              f"(losses recorded: {m.get('device_losses', 0)})")
        print(f"  straggler rounds: {m.get('stragglers', 0)} "
              f"(stall verdicts forgiven as slow rounds: "
              f"{m.get('rounds_forgiven', 0)})")
    for f in report["failures"]:
        print(f"CONTRACT BROKEN: {f['scenario']} plan={f['plan']!r} "
              f"seed={f['seed']} trace={f.get('trace_id', 0)} -> "
              f"{f['status']} ({f['error_type']}: {f['error']}) "
              f"leaks={f['leaks']}")


def run_crash(kill_points=None) -> dict:
    """The subprocess crash sweep (auron_tpu/it/chaos.run_crash_sweep):
    a child Session SIGKILLed at every journal stage boundary of the
    two-exchange crash query, the parent resuming each time. Reported
    like the seeded battery: identical-or-classified, zero leaks."""
    from auron_tpu.it import chaos
    outs = chaos.run_crash_sweep(kill_points=kill_points)
    rows = [{"kill_point": o.kill_point, "child_rc": o.child_rc,
             "status": o.status, "error_type": o.error_type,
             "maps_skipped": o.maps_skipped,
             "maps_recomputed": o.maps_recomputed,
             "bytes_reused": o.bytes_reused,
             "resume_wall_s": round(o.resume_wall_s, 3),
             "leaks": o.leaks} for o in outs]
    return {"rows": rows, "ok": all(o.ok for o in outs)}


def print_crash(report: dict) -> None:
    hdr = (f"{'kill@':>5s} {'rc':>4s} {'status':>10s} {'skip':>5s} "
           f"{'recomp':>6s} {'bytes reused':>13s} {'resume s':>8s} "
           f"{'leaks':>5s}")
    print("crash sweep (child SIGKILLed at every journal boundary, "
          "parent resumes)")
    print(hdr)
    print("-" * len(hdr))
    for r in report["rows"]:
        print(f"{r['kill_point']:>5d} {r['child_rc']:>4d} "
              f"{r['status']:>10s} {r['maps_skipped']:>5d} "
              f"{r['maps_recomputed']:>6d} {r['bytes_reused']:>13,d} "
              f"{r['resume_wall_s']:>8.3f} {len(r['leaks']):>5d}")
    for r in report["rows"]:
        if r["status"] not in ("identical", "classified", "completed") \
                or r["leaks"]:
            print(f"CONTRACT BROKEN: kill@{r['kill_point']} -> "
                  f"{r['status']} ({r.get('error_type')}) "
                  f"leaks={r['leaks']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seeds", type=int, default=8,
                    help="seeds per (scenario, plan) pair")
    ap.add_argument("--scenario", choices=["rss_pipeline", "spill_sort",
                                           "agg_pipeline",
                                           "mesh_pipeline",
                                           "lifecycle_pipeline",
                                           "overload",
                                           "journal_pipeline",
                                           "fleet_failover"],
                    default=None)
    ap.add_argument("--crash", action="store_true",
                    help="run the subprocess crash sweep (SIGKILL at "
                         "every journal stage boundary + resume) "
                         "instead of the seeded fault battery")
    args = ap.parse_args(argv)

    if args.crash:
        report = run_crash()
        print_crash(report)
        print(json.dumps({"crash_points": len(report["rows"]),
                          "crash_rows": report["rows"],
                          "crash_contract_ok": report["ok"]}))
        return 0 if report["ok"] else 1

    report = run_sweep(args.seeds, args.scenario)
    print_table(report)
    ok = not report["failures"]
    print(json.dumps({"chaos_seeds": report["seeds"],
                      "chaos_runs": sum(
                          sum(r[k] for k in ("identical", "classified",
                                             "mismatch", "unclassified"))
                          for r in report["rows"]),
                      "chaos_injected": sum(r["injected"]
                                            for r in report["rows"]),
                      "chaos_sites": report.get("sites") or {},
                      "chaos_lifecycle": report.get("lifecycle") or {},
                      "chaos_mesh": report.get("mesh") or {},
                      "chaos_contract_ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
