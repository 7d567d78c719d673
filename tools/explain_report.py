"""EXPLAIN ANALYZE report: run a suite query, print the annotated plan.

Runs one (or every) TPC-DS / TPC-H query through the engine with a
mirrored metric tree (obs/metric_tree.py — the positional
update_metric_node walk of the reference, rt.rs:302-308) and prints
each plan node annotated with what actually happened: elapsed_compute,
output_rows/batches, spill and shuffle counters, dispatch decisions.

    python tools/explain_report.py --suite tpcds --query q3
    python tools/explain_report.py --suite tpcds --scale 0.02 --query all

Each suite Query collects internally, so the tool captures the query's
top-level DataFrame by hooking Session.execute, then re-runs it under
``explain(analyze=True)``.

The last stdout line is one JSON record (driver contract shared with
bench.py / compile_report.py): per-query node counts plus the
zero-metric audit (plan nodes whose elapsed_compute or output_rows
stayed zero — the acceptance gate wants none on a served query).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def analyze_query(session, q, tables) -> dict:
    """Capture the query's top-level DataFrame (the LAST Session.execute
    — the final .collect()) and re-run it with a mirrored metric tree."""
    from auron_tpu.obs import metric_tree as mt

    captured = {}
    original = session.execute

    def capturing_execute(df):
        captured["df"] = df
        return original(df)

    session.execute = capturing_execute
    try:
        q.run(session, tables)
    finally:
        session.execute = original
    df = captured.get("df")
    if df is None:
        raise RuntimeError(f"{q.name}: no DataFrame execution captured")
    op = session.plan_physical(df)
    tree, table = mt.explain_analyze(
        op, num_partitions=df.num_partitions,
        mem_manager=session.mem_manager, config=session.config)
    zero = [n.op_repr for n in tree.walk()
            if not n.metrics.get("elapsed_compute")
            or not n.metrics.get("output_rows")]
    return {"render": mt.render(tree), "totals": mt.totals(tree),
            "rows": table.num_rows, "zero_metric_nodes": zero}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--suite", default="tpcds", choices=["tpcds", "tpch"])
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--query", default="q3", help="query name, or 'all'")
    ap.add_argument("--data", default=None,
                    help="reuse/create the dataset in this directory")
    args = ap.parse_args(argv)

    import tempfile

    if args.suite == "tpcds":
        from auron_tpu.it.tpcds import generate
        from auron_tpu.it.tpcds_queries import QUERIES
    else:
        from auron_tpu.it.tpch import generate
        from auron_tpu.it.tpch_queries import QUERIES
    from auron_tpu.frontend.session import Session

    data_dir = args.data or tempfile.mkdtemp(prefix="explain_report_")
    tables = generate(data_dir, scale=args.scale)
    names = None if args.query == "all" else {args.query}

    out = []
    for q in QUERIES:
        if names and q.name not in names:
            continue
        try:
            res = analyze_query(Session(), q, tables)
        except Exception as e:   # noqa: BLE001 — report, don't abort
            out.append({"query": q.name,
                        "error": f"{type(e).__name__}: {e}"})
            print(f"== {q.name}: ERROR {str(e)[:200]}")
            continue
        print(f"== {q.name} ({res['rows']} rows) ==")
        print(res["render"], end="")
        t = res["totals"]
        print(f"-- nodes={t['nodes']} elapsed={t['elapsed_compute_ms']}ms "
              f"rows={t['output_rows']} "
              f"zero_metric_nodes={len(res['zero_metric_nodes'])}")
        out.append({"query": q.name, "nodes": t["nodes"],
                    "elapsed_compute_ms": t["elapsed_compute_ms"],
                    "rows": res["rows"],
                    "zero_metric_nodes": res["zero_metric_nodes"]})
    print(json.dumps({"suite": args.suite, "scale": args.scale,
                      "queries": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
