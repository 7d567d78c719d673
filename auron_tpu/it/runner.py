"""Integration-harness runner.

CLI analogue of the reference's auron-it Main (reference:
dev/auron-it/.../Main.scala:60-128, flags --auron-only/--result-check):

    python -m auron_tpu.it.runner [--scale 1.0] [--queries q01,q03] [--data DIR]

Runs on the ambient jax platform (a TPU where one is visible;
``JAX_PLATFORMS=cpu`` for the CPU mesh) and prints it in the summary
line. Exit code 0 iff every query's result matches the pandas oracle.
"""

from __future__ import annotations

import sys
import tempfile
import time

from auron_tpu.it.comparator import ComparisonResult, QueryResultComparator
from auron_tpu.it.queries import QUERIES
from auron_tpu.it.tpcds_data import generate, load_pandas


def _fresh_session():
    from auron_tpu.frontend.session import Session
    return Session()


def run_query(query, tables, pd_tables,
              comparator=None) -> ComparisonResult:
    comparator = comparator or QueryResultComparator()
    session = _fresh_session()
    t0 = time.perf_counter()
    try:
        got = query.run(session, tables)
    except Exception as e:  # a crash is a FAIL with the error recorded
        import traceback
        return ComparisonResult(query.name, False, 0,
                                error=traceback.format_exc(limit=8))
    elapsed = time.perf_counter() - t0
    expected = query.expected(pd_tables)
    res = comparator.compare(query.name, got, expected)
    res.elapsed_s = round(elapsed, 3)
    return res


def run_all(data_dir=None, scale: float = 1.0, names=None,
            verbose: bool = True) -> list[ComparisonResult]:
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="auron_it_")
    tables = generate(data_dir, scale=scale)
    pd_tables = load_pandas(tables)
    results = []
    for q in QUERIES:
        if names and q.name not in names and q.name.split("_")[0] not in names:
            continue
        res = run_query(q, tables, pd_tables)
        results.append(res)
        if verbose:
            took = getattr(res, "elapsed_s", None)
            suffix = f" ({took}s)" if took is not None else ""
            print(res.report() + suffix, flush=True)
    return results


def defloat_decimals(tbl):
    """Cast decimal columns to float64 so engine decimals (exact,
    Spark-typed) and the Acero oracle's mixed decimal/float outputs
    compare under the double tolerance. Money sums at TPC-DS scale stay
    within float64's 2^53 exact-integer range."""
    import pyarrow as pa
    cols = []
    for i, f in enumerate(tbl.schema):
        c = tbl.column(i)
        if pa.types.is_decimal(f.type):
            c = c.cast(pa.float64())
        cols.append(c)
    return pa.table({f.name: c for f, c in zip(tbl.schema, cols)})


def _run_suite(queries, tables, arrow, comparator, names=None,
               verbose: bool = True, budget_note: bool = True):
    """Shared per-query loop: fresh session, compile attribution, oracle
    diff, verbose report, suite compile-budget summary."""
    from auron_tpu.utils import compile_stats
    results = []
    suite_start = compile_stats.snapshot()
    clears_start = compile_stats.clears()
    for q in queries:
        if names and q.name not in names:
            continue
        compile_stats.maybe_clear()   # bound live programs per process
        session = _fresh_session()
        t0 = time.perf_counter()
        c0 = compile_stats.snapshot()
        try:
            got = q.run(session, tables)
        except Exception:
            import traceback
            results.append(ComparisonResult(
                q.name, False, 0, error=traceback.format_exc(limit=8)))
            if verbose:
                print(results[-1].report(), flush=True)
            continue
        elapsed = time.perf_counter() - t0
        cd = compile_stats.delta(c0)
        expected = q.oracle(arrow)
        res = comparator.compare(q.name, defloat_decimals(got),
                                 defloat_decimals(expected))
        res.elapsed_s = round(elapsed, 3)
        res.compiles = cd.count
        res.compile_s = round(cd.seconds, 3)
        results.append(res)
        if verbose:
            print(res.report() + f" ({res.elapsed_s}s, "
                  f"{cd.count} compiles {res.compile_s}s)", flush=True)
    total = compile_stats.delta(suite_start)
    if verbose and budget_note:
        wall = sum(getattr(r, "elapsed_s", 0) or 0 for r in results)
        n_clears = compile_stats.clears() - clears_start
        note = ("a second run in this process should compile ~0"
                if n_clears == 0 else
                f"{n_clears} cache clears hit the auron.max_live_programs "
                "ceiling, so warm reruns recompile cleared kernels")
        print(f"compile budget: {total.count} XLA programs, "
              f"{total.seconds:.1f}s compiling / {wall:.1f}s total "
              f"({note})", flush=True)
    return results


def run_tpcds(data_dir=None, scale: float = 1.0, names=None,
              verbose: bool = True) -> list[ComparisonResult]:
    """The real-schema TPC-DS gate: 99 genuine TPC-DS query shapes over a
    scale-1.0 = 1M-fact-row dataset, diffed against the pyarrow/Acero
    oracle (reference gate: .github/workflows/tpcds-reusable.yml:70-83)."""
    from auron_tpu.it.tpcds import generate, load_arrow
    from auron_tpu.it.tpcds_queries import QUERIES as TQ
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="auron_tpcds_")
    tables = generate(data_dir, scale=scale)
    arrow = load_arrow(tables)
    return _run_suite(TQ, tables, arrow,
                      QueryResultComparator(double_rel_tol=1e-7,
                                            double_abs_tol=1e-6),
                      names=names, verbose=verbose)


def run_tpch(data_dir=None, scale: float = 1.0, names=None,
             verbose: bool = True) -> list[ComparisonResult]:
    """TPC-H q1/q3/q5/q6/q9/q18 (incl. the BASELINE.md join-heavy
    targets) vs pandas
    oracles."""
    from auron_tpu.it.tpch import generate, load_arrow
    from auron_tpu.it.tpch_queries import QUERIES as HQ
    if data_dir is None:
        data_dir = tempfile.mkdtemp(prefix="auron_tpch_")
    tables = generate(data_dir, scale=scale)
    arrow = load_arrow(tables)
    return _run_suite(HQ, tables, arrow,
                      QueryResultComparator(double_rel_tol=1e-7,
                                            double_abs_tol=1e-5),
                      names=names, verbose=verbose)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--suite", default="synth",
                    choices=["synth", "tpcds", "tpch"],
                    help="synth: the synthetic-star queries; tpcds: the "
                         "real-schema TPC-DS battery (see tpcds_queries) "
                         "vs the Acero oracle; "
                         "tpch: q1/q3/q5/q6/q9/q18 incl. the BASELINE targets")
    ap.add_argument("--queries", default="",
                    help="comma-separated names (q01 or full name)")
    ap.add_argument("--data", default=None,
                    help="reuse/create dataset in this directory")
    args = ap.parse_args(argv)
    names = [n.strip() for n in args.queries.split(",") if n.strip()] or None
    if args.suite == "tpcds":
        results = run_tpcds(data_dir=args.data, scale=args.scale,
                            names=names)
    elif args.suite == "tpch":
        results = run_tpch(data_dir=args.data, scale=args.scale,
                           names=names)
    else:
        results = run_all(data_dir=args.data, scale=args.scale, names=names)
    if not results:
        print(f"no queries matched --queries {args.queries!r} in suite "
              f"{args.suite!r} — nothing ran", file=sys.stderr)
        return 2
    failed = [r for r in results if not r.ok]
    import jax
    dev = jax.devices()[0]
    print(f"{len(results) - len(failed)}/{len(results)} queries passed "
          f"(platform={dev.platform} kind={dev.device_kind} "
          f"devices={len(jax.devices())})")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
