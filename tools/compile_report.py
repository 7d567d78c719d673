"""Per-query XLA program-build report from the central program registry.

For each query of a TPC-DS / TPC-H suite run, prints the programs BUILT
(central registry, auron_tpu/runtime/programs.py), the registry cache
hits, and the raw backend compiles + seconds (utils/compile_stats) —
the numbers behind PERF.md's compile-economics section and the
whole-stage-fusion acceptance gate.

    python tools/compile_report.py --suite tpcds --scale 0.05
    python tools/compile_report.py --fusion off          # unfused baseline
    python tools/compile_report.py --compare             # both, fresh
                                                         # process each,
                                                         # prints the delta

``--compare`` runs the suite twice in CHILD processes (one per fusion
setting) so neither run warms the other's kernel caches, then reports
total builds and the fused-vs-unfused reduction — the ISSUE 2 acceptance
check (builds drop >= 30% on the CI-scale gate).

The last stdout line of a single run is one JSON record, so drivers and
--compare can parse totals without scraping the table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_report(suite: str, scale: float, names, data_dir=None) -> dict:
    import tempfile
    import time

    from auron_tpu.runtime import programs
    from auron_tpu.utils import compile_stats

    if suite == "tpcds":
        from auron_tpu.it.tpcds import generate
        from auron_tpu.it.tpcds_queries import QUERIES
    else:
        from auron_tpu.it.tpch import generate
        from auron_tpu.it.tpch_queries import QUERIES
    from auron_tpu.frontend.session import Session

    data_dir = data_dir or tempfile.mkdtemp(prefix="compile_report_")
    tables = generate(data_dir, scale=scale)

    rows = []
    t_start = compile_stats.snapshot()
    p_start = programs.totals()
    print(f"{'query':>6}  {'builds':>6}  {'hits':>6}  {'compiles':>8}  "
          f"{'compile_s':>9}  {'wall_s':>7}")
    for q in QUERIES:
        if names and q.name not in names:
            continue
        compile_stats.maybe_clear()
        c0 = compile_stats.snapshot()
        p0 = programs.totals()
        err = None
        t0 = time.perf_counter()
        try:
            q.run(Session(), tables)
        except Exception as e:   # noqa: BLE001 — report, don't abort
            err = f"{type(e).__name__}: {e}"
        wall = time.perf_counter() - t0
        cd = compile_stats.delta(c0)
        pd = programs.delta(p0)
        rows.append({"query": q.name, "builds": pd.builds,
                     "hits": pd.hits, "compiles": cd.count,
                     "compile_s": round(cd.seconds, 2),
                     "wall_s": round(wall, 2), "error": err})
        line = (f"{q.name:>6}  {pd.builds:>6}  {pd.hits:>6}  "
                f"{cd.count:>8}  {cd.seconds:>9.2f}  {wall:>7.2f}")
        if err:
            line += f"  ERROR {err[:80]}"
        print(line, flush=True)
    td = compile_stats.delta(t_start)
    pdt = programs.delta(p_start)
    from auron_tpu import config as cfg
    sites = {k: v for k, v in programs.snapshot().items() if v["builds"]}
    # hash-table subsystem attribution: every hashtable.* compile site
    # (agg_step/agg_grow/agg_export/build/probe/grow/join_index)
    # rides the central registry like any other builder — break its share
    # out so hash-path compile costs are visible at a glance (the
    # aggregation's other programs are ops.agg.batch_reduce / state_merge
    # / emit / passthrough)
    ht_sites = {k: v for k, v in sites.items()
                if k.startswith("hashtable.")}
    gcfg = cfg.get_config()
    summary = {
        "suite": suite, "scale": scale,
        "queries": len(rows),
        "fusion": gcfg.get(cfg.FUSION_ENABLED),
        "hashtable": gcfg.get(cfg.HASHTABLE_ENABLED),
        "program_builds": pdt.builds,
        "program_hits": pdt.hits,
        "hashtable_builds": sum(v["builds"] for v in ht_sites.values()),
        "backend_compiles": td.count,
        "compile_seconds": round(td.seconds, 2),
        "sites": sites,
        "hashtable_sites": ht_sites,
        "per_query": rows,
    }
    print(f"total: {pdt.builds} program builds, {pdt.hits} hits, "
          f"{td.count} backend compiles, {td.seconds:.1f}s compiling")
    if ht_sites:
        per = ", ".join(f"{k.split('.', 1)[1]}={v['builds']}"
                        for k, v in sorted(ht_sites.items()))
        print(f"hashtable sites: {summary['hashtable_builds']} builds "
              f"({per})")
    return summary


def _compare(args) -> int:
    """A/B in fresh child processes, auron.fusion.enabled off vs on;
    gate: program builds drop >= 30% (the ISSUE 2 acceptance check)."""
    import subprocess
    results = {}
    for setting in ("false", "true"):
        env = dict(os.environ)
        env["AURON_CONF_FUSION_ENABLED"] = setting
        cmd = [sys.executable, os.path.abspath(__file__),
               "--suite", args.suite, "--scale", str(args.scale)]
        if args.queries:
            cmd += ["--queries", args.queries]
        if args.data:
            cmd += ["--data", args.data]
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if proc.returncode != 0 or not proc.stdout.strip():
            sys.stderr.write(proc.stderr)
            print(f"AURON_CONF_FUSION_ENABLED={setting} child failed "
                  f"rc={proc.returncode}")
            return 1
        results[setting] = json.loads(proc.stdout.strip().splitlines()[-1])
    off, on = results["false"], results["true"]
    drop = 1.0 - (on["program_builds"] / max(1, off["program_builds"]))
    print(f"unfused: {off['program_builds']} builds, "
          f"{off['compile_seconds']}s compiling")
    print(f"fused:   {on['program_builds']} builds, "
          f"{on['compile_seconds']}s compiling")
    print(f"program-build reduction: {drop:.1%} "
          f"({'meets' if drop >= 0.30 else 'BELOW'} the >=30% gate)")
    print(json.dumps({"unfused_builds": off["program_builds"],
                      "fused_builds": on["program_builds"],
                      "reduction": round(drop, 4)}))
    return 0 if drop >= 0.30 else 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--suite", default="tpcds", choices=["tpcds", "tpch"])
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--queries", default="",
                    help="comma-separated query names (default: all)")
    ap.add_argument("--data", default=None,
                    help="reuse/create the dataset in this directory")
    ap.add_argument("--fusion", default=None, choices=["on", "off"],
                    help="override auron.fusion.enabled for this run")
    ap.add_argument("--compare", action="store_true",
                    help="A/B auron.fusion.enabled off vs on (fresh "
                         "process per setting) and print the delta")
    args = ap.parse_args(argv)
    if args.compare:
        return _compare(args)
    if args.fusion is not None:
        from auron_tpu import config as cfg
        cfg.get_config().set("auron.fusion.enabled", args.fusion == "on")
    names = [n.strip() for n in args.queries.split(",") if n.strip()] or None
    summary = run_report(args.suite, args.scale, names, data_dir=args.data)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
