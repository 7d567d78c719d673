"""Concurrent-serving load report: drive M clients against AuronServer.

The measurement half of the [serving] scheduler plane: spin an
in-process ``AuronServer`` (admission control armed via the
``auron.sched.*`` knobs), hammer it with M concurrent clients x R
requests each, and print the admission/shed/latency table the PERF.md
"Concurrent serving" section quotes:

- serial baseline wall vs concurrent wall → the aggregate-vs-serial
  throughput ratio (the ROADMAP gate asks >= ~0.8x of serial);
- admission outcomes: ok / rejected-by-reason / cancelled, straight
  from the server scheduler's registry-independent counters;
- latency p50/p99 of successful requests and the scheduler's observed
  queue-wait p50/p99;
- an overload arm: clients sized at 2x the concurrency + queue budget
  MUST produce rejections (shed-not-crash) — the report fails loudly
  when overload produced zero sheds, because that means the admission
  door was not actually exercised.

    python tools/load_report.py                      # defaults
    python tools/load_report.py --clients 8 --requests 4 \
        --max-concurrent 2 --queue-depth 2

``--repeat N`` switches to the WARM-PATH measurement (the PR 16 cache
acceptance figure): the same task driven N times cold (cache disabled,
every run executes fully) and N times warm (cache enabled, first run
populates, the rest hit), reporting cold/warm latency p50s, their
ratio, a bit-identical check of cached-vs-fresh results, and the
server's cache counters from ``AuronClient.stats()``.
``--expect-speedup X`` makes a warm-p50 speedup under X exit nonzero:

    python tools/load_report.py --repeat 10 --expect-speedup 10

``--fleet N`` switches to the FLEET measurement (the serving-fleet
acceptance figure): N subprocess replicas behind an in-process
``FleetRouter``, each replica throttled to one concurrent query +
one queue slot so admission capacity — the thing replication buys —
is the measured resource.  The same concurrent burst is driven twice
(once at fleet size 1, once at N, with one replica SIGKILLed
mid-burst) and the report gates on:

- zero UNCLASSIFIED client errors (every request ends in a result or
  a structured AdmissionRejected — replica death included);
- every successful result bit-identical to the baseline table
  (journal-backed failover must not change bytes);
- aggregate admitted throughput >= ``--expect-scale`` x the
  single-replica run (default 2.5);
- a clean shared journal dir after the dead-owner sweep (a resumable
  journal nobody failed over = a dropped query).

    python tools/load_report.py --fleet 3

The last stdout line is one JSON record (the bench.py/chaos_report.py
driver contract)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _dataset(root: str, rows: int):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(7)
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 64, rows), pa.int64()),
        "v": pa.array(rng.normal(size=rows), pa.float64())})
    path = os.path.join(root, "load.parquet")
    pq.write_table(tbl, path)
    return path


def _task_bytes(path: str):
    from auron_tpu.ir import pb
    col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
    plan = pb.PlanNode(agg=pb.AggNode(
        child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(files=[path])),
        mode="complete", group_exprs=[col(0)],
        aggs=[pb.AggFunctionP(fn="sum", arg=col(1)),
              pb.AggFunctionP(fn="count", arg=col(1))]))
    return pb.TaskDefinition(plan=plan, task_id=1).SerializeToString()


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(p * len(sorted_vals)),
                           len(sorted_vals) - 1)]


def _drive(addr, task, n_requests, outcomes, lock, ledgers=None):
    from auron_tpu.runtime.serving import AuronClient
    client = AuronClient(*addr, timeout_s=120)
    for _ in range(n_requests):
        t0 = time.perf_counter()
        try:
            _tbl, metrics = client.execute(task)
            kind = "ok"
            if ledgers is not None and isinstance(metrics, dict) \
                    and isinstance(metrics.get("cost_ledger"), dict):
                with lock:
                    ledgers.append(metrics["cost_ledger"])
        except RuntimeError as e:
            kind = ("rejected" if "AdmissionRejected" in str(e)
                    else "error")
        except Exception:   # noqa: BLE001 — tally, don't crash the driver
            kind = "error"
        with lock:
            outcomes.append((kind, time.perf_counter() - t0))


def run_load(clients: int, requests: int, max_concurrent: int,
             queue_depth: int, rows: int) -> dict:
    from auron_tpu import config as cfg
    from auron_tpu.runtime.serving import AuronServer
    conf = cfg.get_config()
    conf.set(cfg.SCHED_MAX_CONCURRENT, max_concurrent)
    conf.set(cfg.SCHED_QUEUE_DEPTH, queue_depth)
    root = tempfile.mkdtemp(prefix="auron_load_")
    try:
        path = _dataset(root, rows)
        task = _task_bytes(path)
        srv = AuronServer()
        srv.serve_background()
        try:
            lock = threading.Lock()
            # warm compiles so the serial/concurrent comparison is fair
            warm: list = []
            _drive(srv.address, task, 1, warm, lock)
            if warm[0][0] != "ok":
                raise SystemExit("load_report: warmup request failed")

            # serial baseline: the same total request count, one at a
            # time through one client
            serial: list = []
            t0 = time.perf_counter()
            _drive(srv.address, task, clients * requests, serial, lock)
            serial_wall = time.perf_counter() - t0
            serial_ok = sum(1 for k, _ in serial if k == "ok")

            # concurrent storm
            before = srv.scheduler.stats()
            outcomes: list = []
            ledgers: list = []
            threads = [threading.Thread(
                target=_drive,
                args=(srv.address, task, requests, outcomes, lock,
                      ledgers),
                daemon=True) for _ in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            wedged = 0
            for t in threads:
                t.join(300)
                if t.is_alive():
                    wedged += 1
            conc_wall = time.perf_counter() - t0
            st = srv.scheduler.stats()

            oks = sorted(lat for k, lat in outcomes if k == "ok")
            n_ok = len(oks)
            n_rej = sum(1 for k, _ in outcomes if k == "rejected")
            # tally against the EXPECTED request count: a wedged
            # client's missing outcomes register as errors — a dropped
            # thread must fail the report, not flatter its table
            n_err = clients * requests - n_ok - n_rej
            # aggregate throughput ratio: completed requests per second,
            # concurrent vs serial (rejected requests completed NOTHING
            # — shedding must not flatter the ratio)
            serial_rps = serial_ok / serial_wall if serial_wall else 0.0
            conc_rps = n_ok / conc_wall if conc_wall else 0.0
            return {
                "clients": clients,
                "requests_per_client": requests,
                "max_concurrent": max_concurrent,
                "queue_depth": queue_depth,
                "input_rows": rows,
                "serial": {"ok": serial_ok,
                           "wall_s": round(serial_wall, 3),
                           "req_per_sec": round(serial_rps, 2)},
                "concurrent": {
                    "ok": n_ok, "rejected": n_rej, "error": n_err,
                    "wall_s": round(conc_wall, 3),
                    "req_per_sec": round(conc_rps, 2),
                    "latency_p50_s": round(_pct(oks, 0.50), 4),
                    "latency_p99_s": round(_pct(oks, 0.99), 4),
                },
                "throughput_ratio_vs_serial": round(
                    conc_rps / serial_rps, 3) if serial_rps else 0.0,
                "sched": {
                    "rejected_by_reason": {
                        k: v - before["rejected_by_reason"].get(k, 0)
                        for k, v in st["rejected_by_reason"].items()},
                    "dequeued_by_reason": st["dequeued_by_reason"],
                    "queue_wait_p50_s": st["queue_wait_p50_s"],
                    "queue_wait_p99_s": st["queue_wait_p99_s"],
                },
                "wedged_clients": wedged,
                "server_stats": dict(srv.stats),
                # per-query cost ledgers off the DONE frames, folded
                # into fleet-scale totals (obs/ledger.fold)
                "cost": _fold_ledgers(ledgers),
            }
        finally:
            srv.shutdown()
    finally:
        conf.unset(cfg.SCHED_MAX_CONCURRENT)
        conf.unset(cfg.SCHED_QUEUE_DEPTH)
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def run_repeat(repeats: int, rows: int) -> dict:
    """Warm-path A/B: the same task N times with the result cache OFF
    (cold — every run executes the full pipeline) then N times with it
    ON (warm — the first run populates, the rest are served from
    cache). Cold/warm p50s and their ratio are the PERF.md "Warm-path
    serving" figures; the bit-identical check and the server's cache
    counters prove the warm runs actually came from the cache rather
    than a faster execution."""
    from auron_tpu import config as cfg
    from auron_tpu.cache.result_cache import get_cache
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    conf = cfg.get_config()
    cache = get_cache()
    root = tempfile.mkdtemp(prefix="auron_repeat_")
    try:
        path = _dataset(root, rows)
        task = _task_bytes(path)
        srv = AuronServer()
        srv.serve_background()
        try:
            client = AuronClient(*srv.address, timeout_s=120)
            # cold phase: cache off; one unmeasured warmup first so the
            # cold p50 measures execution, not first-compile
            conf.set(cfg.CACHE_ENABLED, False)
            client.execute(task)
            cold_lat: list = []
            cold_tbl = None
            for _ in range(repeats):
                t0 = time.perf_counter()
                cold_tbl, _ = client.execute(task)
                cold_lat.append(time.perf_counter() - t0)
            # warm phase: cache on, starting empty; the first request
            # misses and populates, the measured N all hit
            conf.set(cfg.CACHE_ENABLED, True)
            cache.clear(reset_counters=True)
            fresh_tbl, _ = client.execute(task)
            warm_lat: list = []
            warm_tbl, hit_flags = None, []
            for _ in range(repeats):
                t0 = time.perf_counter()
                warm_tbl, metrics = client.execute(task)
                warm_lat.append(time.perf_counter() - t0)
                hit_flags.append(bool(metrics.get("cache_hit")))
            identical = (warm_tbl.equals(fresh_tbl)
                         and warm_tbl.equals(cold_tbl))
            stats = client.stats()
            cold_sorted, warm_sorted = sorted(cold_lat), sorted(warm_lat)
            cold_p50 = _pct(cold_sorted, 0.50)
            warm_p50 = _pct(warm_sorted, 0.50)
            return {
                "mode": "repeat",
                "repeats": repeats,
                "input_rows": rows,
                "cold": {"p50_s": round(cold_p50, 4),
                         "p99_s": round(_pct(cold_sorted, 0.99), 4)},
                "warm": {"p50_s": round(warm_p50, 4),
                         "p99_s": round(_pct(warm_sorted, 0.99), 4),
                         "cache_hits": sum(hit_flags)},
                "speedup_x": round(cold_p50 / warm_p50, 1)
                if warm_p50 > 0 else 0.0,
                "bit_identical": identical,
                "cache": stats.get("cache", {}),
            }
        finally:
            srv.shutdown()
    finally:
        conf.unset(cfg.CACHE_ENABLED)
        cache.clear(reset_counters=True)
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def _fold_ledgers(ledgers: list) -> dict:
    from auron_tpu.obs import ledger as ledger_mod
    return ledger_mod.fold(ledgers)


def _fleet_burst(harness, task, clients: int, requests: int,
                 kill_index=None, kill_after_s: float = 0.0):
    """Drive ``clients`` x ``requests`` through the harness's router,
    optionally SIGKILLing one replica mid-burst.  Returns (outcomes,
    wall_s, tables) where outcomes are ("ok"|"rejected"|"error", lat)
    tuples — "rejected" strictly means a structured AdmissionRejected
    verdict, anything else non-ok is an UNCLASSIFIED error."""
    lock = threading.Lock()
    outcomes: list = []
    tables: list = []
    error_samples: list = []
    # all clients pass the gate together: admission capacity is the
    # measured resource, so the burst must actually be simultaneous
    # (thread start stagger on a small host would smuggle refill
    # capacity into the "one replica" baseline)
    barrier = threading.Barrier(clients)

    ledgers: list = []

    def drive():
        client = harness.client(timeout_s=120)
        barrier.wait(timeout=60)
        for _ in range(requests):
            t0 = time.perf_counter()
            try:
                tbl, metrics = client.execute(task)
                kind = "ok"
                with lock:
                    tables.append(tbl)
                    if isinstance(metrics, dict) and isinstance(
                            metrics.get("cost_ledger"), dict):
                        ledgers.append(metrics["cost_ledger"])
            except Exception as e:   # noqa: BLE001 — tally, don't crash
                kind = ("rejected" if "AdmissionRejected" in str(e)
                        else "error")
                if kind == "error":
                    with lock:
                        if len(error_samples) < 3:
                            error_samples.append(
                                str(e).replace("\n", " | ")[:300])
            with lock:
                outcomes.append((kind, time.perf_counter() - t0))

    threads = [threading.Thread(target=drive, daemon=True)
               for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    if kill_index is not None:
        time.sleep(kill_after_s)
        # prefer a replica that is actually busy so the kill lands on
        # an in-flight conversation (the failover surface under test)
        harness.router._poll_once()
        busy = kill_index
        for i, rep in enumerate(harness.router._replicas):
            if rep.snapshot.running or rep.snapshot.queued:
                busy = i
                break
        harness.kill_replica(busy)
    wedged = 0
    for t in threads:
        t.join(300)
        if t.is_alive():
            wedged += 1
    wall = time.perf_counter() - t0
    return outcomes, wall, tables, wedged, error_samples, ledgers


def _journal_orphans(journal_dir: str) -> list:
    """Artifacts left in the shared journal dir after the dead-owner
    sweep: every one is a query failover dropped on the floor."""
    import glob as globmod

    from auron_tpu.runtime import journal as jrn
    jrn.sweep_orphans(journal_dir, force=True)
    leftovers = []
    for pat in ("*.journal", "*.part", "*.claim"):
        leftovers.extend(os.path.basename(p) for p in globmod.glob(
            os.path.join(journal_dir, pat)))
    rss = os.path.join(journal_dir, "rss")
    if os.path.isdir(rss):
        leftovers.extend("rss/" + n for n in os.listdir(rss))
    return sorted(leftovers)


def run_fleet(n: int, clients: int, requests: int, rows: int,
              chip_per_replica: bool = False) -> dict:
    from auron_tpu.fleet import FleetHarness, tpu_chip_env
    root = tempfile.mkdtemp(prefix="auron_fleet_load_")
    # throttle each replica to 1 running + 1 queued query: on a small
    # host the fleet's win is ADMISSION capacity (more replicas admit
    # more of the same burst), and this makes that the measured axis
    env_extra = {"AURON_CONF_SCHED_MAX_CONCURRENT": "1",
                 "AURON_CONF_SCHED_QUEUE_DEPTH": "1"}

    # on a TPU host every replica needs a chip of its own; this process
    # (router + clients) never touches jax, so it holds none
    def chips(k):
        return ([tpu_chip_env(i) for i in range(k)]
                if chip_per_replica else None)
    try:
        path = _dataset(root, rows)
        task = _task_bytes(path)
        jdir_one = os.path.join(root, "journal_one")
        jdir_n = os.path.join(root, "journal_n")
        os.makedirs(jdir_one)
        os.makedirs(jdir_n)

        with FleetHarness(1, journal_dir=jdir_one, env_extra=env_extra,
                          replica_env=chips(1)) as h1:
            warm: list = []
            lock = threading.Lock()
            _drive(h1.address, task, 1, warm, lock)
            if warm[0][0] != "ok":
                raise SystemExit("fleet report: warmup failed")
            base_tbl, _ = h1.client(timeout_s=120).execute(task)
            out1, wall1, _tbls1, wedged1, errs1, _led1 = _fleet_burst(
                h1, task, clients, requests)
            stats1 = h1.router.stats_dict()

        with FleetHarness(n, journal_dir=jdir_n, env_extra=env_extra,
                          replica_env=chips(n)) as hn:
            _drive(hn.address, task, 1, [], lock)   # warm compiles
            outn, walln, tblsn, wedgedn, errsn, ledn = _fleet_burst(
                hn, task, clients, requests, kill_index=0,
                kill_after_s=1.0)
            statsn = hn.router.stats_dict()

        orphans = (_journal_orphans(jdir_one)
                   + _journal_orphans(jdir_n))

        def tally(outcomes, total):
            ok = sum(1 for k, _ in outcomes if k == "ok")
            rej = sum(1 for k, _ in outcomes if k == "rejected")
            return ok, rej, total - ok - rej

        total = clients * requests
        ok1, rej1, err1 = tally(out1, total)
        okn, rejn, errn = tally(outn, total)
        rps1 = ok1 / wall1 if wall1 else 0.0
        rpsn = okn / walln if walln else 0.0
        identical = all(t.equals(base_tbl) for t in tblsn)
        lat = statsn.get("failover_latency_s") or []
        return {
            "mode": "fleet",
            "replicas": n,
            "clients": clients,
            "requests_per_client": requests,
            "input_rows": rows,
            "one": {"ok": ok1, "rejected": rej1, "error": err1,
                    "wall_s": round(wall1, 3),
                    "req_per_sec": round(rps1, 2),
                    "wedged": wedged1},
            "fleet": {"ok": okn, "rejected": rejn, "error": errn,
                      "wall_s": round(walln, 3),
                      "req_per_sec": round(rpsn, 2),
                      "wedged": wedgedn},
            "admitted_scale_x": round(okn / ok1, 2) if ok1 else 0.0,
            "throughput_scale_x": round(rpsn / rps1, 2) if rps1
            else 0.0,
            "bit_identical": identical,
            "failover": {
                "deaths": statsn["router"]["replica_deaths"],
                "resumes": statsn["router"]["failovers_resume"],
                "reexecutes": statsn["router"]["failovers_reexecute"],
                "latency_p50_s": round(_pct(lat, 0.50), 4),
                "latency_p99_s": round(_pct(lat, 0.99), 4),
            },
            "router": statsn["router"],
            "journal_orphans": orphans,
            "error_samples": errs1 + errsn,
            # folded per-query cost ledgers from the fleet burst's DONE
            # frames — fleet.hops/failover facts stamped by the router
            "cost": _fold_ledgers(ledn),
        }
    finally:
        import shutil
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=None,
                    help="concurrent client threads (default 8; "
                         "fleet mode: 4 x N)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per client (default 3; fleet "
                         "mode: 1 — a single simultaneous round "
                         "measures admission capacity, not refill "
                         "dynamics)")
    ap.add_argument("--max-concurrent", type=int, default=2,
                    help="auron.sched.max_concurrent for the run")
    ap.add_argument("--queue-depth", type=int, default=2,
                    help="auron.sched.queue_depth for the run")
    ap.add_argument("--rows", type=int, default=None,
                    help="rows in the driven aggregation (default "
                         "200k; fleet mode: 3M — query time must "
                         "dwarf burst stagger so admission capacity, "
                         "not thread scheduling, decides outcomes)")
    ap.add_argument("--expect-shed", action="store_true",
                    help="fail (exit 1) when the overload produced ZERO "
                         "rejections — the admission door went untested")
    ap.add_argument("--repeat", type=int, default=0, metavar="N",
                    help="warm-path mode: drive the same task N times "
                         "cold (cache off) and N times warm (cache on) "
                         "and report the p50 speedup instead of the "
                         "concurrency table")
    ap.add_argument("--expect-speedup", type=float, default=None,
                    metavar="X",
                    help="with --repeat: fail (exit 1) when the warm "
                         "p50 speedup is under X or the cached results "
                         "are not bit-identical")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet mode: N subprocess replicas behind a "
                         "router, one SIGKILLed mid-burst; reports "
                         "admitted-throughput scale vs one replica, "
                         "failover latency, and journal cleanliness")
    ap.add_argument("--chip-per-replica", action="store_true",
                    help="with --fleet on a TPU host: confine replica "
                         "i to chip i (fleet.tpu_chip_env); without it "
                         "every replica inherits the whole host")
    ap.add_argument("--expect-scale", type=float, default=2.5,
                    metavar="X",
                    help="with --fleet: fail (exit 1) when aggregate "
                         "admitted throughput is under X x the one-"
                         "replica run (default 2.5)")
    args = ap.parse_args(argv)

    if args.fleet > 0:
        rep = run_fleet(args.fleet,
                        args.clients or 4 * args.fleet,
                        args.requests or 1,
                        args.rows or 3_000_000,
                        chip_per_replica=args.chip_per_replica)
        o, f, fo = rep["one"], rep["fleet"], rep["failover"]
        print(f"fleet report: {args.fleet} replicas, "
              f"{rep['clients']} clients x "
              f"{rep['requests_per_client']} req, one replica "
              "SIGKILLed mid-burst")
        print(f"  one replica : {o['ok']} ok / {o['rejected']} "
              f"rejected / {o['error']} error in {o['wall_s']}s "
              f"({o['req_per_sec']} req/s)")
        print(f"  fleet       : {f['ok']} ok / {f['rejected']} "
              f"rejected / {f['error']} error in {f['wall_s']}s "
              f"({f['req_per_sec']} req/s)")
        print(f"  admitted scale: {rep['admitted_scale_x']}x ; "
              f"throughput scale: {rep['throughput_scale_x']}x")
        print(f"  failover: {fo['deaths']} death(s), {fo['resumes']} "
              f"resumed / {fo['reexecutes']} re-executed, "
              f"p50/p99 {fo['latency_p50_s']}s / {fo['latency_p99_s']}s")
        print(f"  bit-identical results: {rep['bit_identical']} ; "
              f"journal orphans: {len(rep['journal_orphans'])}")
        cost = rep.get("cost") or {}
        if cost.get("queries"):
            print(f"  cost ledgers: {cost['queries']} queries on "
                  f"{cost.get('devices')}, "
                  f"device {cost['device_s']}s / host "
                  f"{cost['host_total_s']}s, "
                  f"{cost['rows']} rows, "
                  f"{cost['replica_hops']} replica hop(s), "
                  f"{cost['failovers']} failed-over, "
                  f"{cost['cache_hits']} cache hit(s)")
        rc = 0
        if f["error"] or f["wedged"] or o["error"] or o["wedged"]:
            print(f"  FAIL: {f['error'] + o['error']} request(s) died "
                  f"UNCLASSIFIED / {f['wedged'] + o['wedged']} "
                  "wedged — replica death leaked to a client")
            rc = 1
        if not rep["bit_identical"]:
            print("  FAIL: a failed-over result differs from the "
                  "baseline table")
            rc = 1
        if rep["journal_orphans"]:
            print(f"  FAIL: journal orphans left behind: "
                  f"{rep['journal_orphans']}")
            rc = 1
        if rep["throughput_scale_x"] < args.expect_scale \
                and rep["admitted_scale_x"] < args.expect_scale:
            print(f"  FAIL: admitted throughput scaled "
                  f"{rep['throughput_scale_x']}x (admitted "
                  f"{rep['admitted_scale_x']}x) < expected "
                  f"{args.expect_scale}x")
            rc = 1
        print(json.dumps(rep))
        return rc

    if args.repeat > 0:
        rep = run_repeat(args.repeat, args.rows or 200_000)
        c, w = rep["cold"], rep["warm"]
        print(f"repeat report: {args.repeat} runs cold vs warm "
              f"({rep['input_rows']} rows)")
        print(f"  cold p50/p99: {c['p50_s']}s / {c['p99_s']}s "
              f"(cache disabled)")
        print(f"  warm p50/p99: {w['p50_s']}s / {w['p99_s']}s "
              f"({w['cache_hits']}/{args.repeat} served from cache)")
        print(f"  speedup: {rep['speedup_x']}x ; bit-identical: "
              f"{rep['bit_identical']}")
        print(f"  server cache stats: {rep['cache']}")
        rc = 0
        if not rep["bit_identical"]:
            print("  FAIL: cached result differs from the fresh run")
            rc = 1
        if w["cache_hits"] < args.repeat:
            print(f"  FAIL: only {w['cache_hits']}/{args.repeat} warm "
                  "runs hit the cache — the warm path did not engage")
            rc = 1
        if args.expect_speedup is not None \
                and rep["speedup_x"] < args.expect_speedup:
            print(f"  FAIL: speedup {rep['speedup_x']}x < expected "
                  f"{args.expect_speedup}x")
            rc = 1
        print(json.dumps(rep))
        return rc

    rep = run_load(args.clients or 8, args.requests or 3,
                   args.max_concurrent, args.queue_depth,
                   args.rows or 200_000)
    c, s = rep["concurrent"], rep["serial"]
    print(f"load report: {args.clients} clients x {args.requests} req, "
          f"max_concurrent={args.max_concurrent} "
          f"queue_depth={args.queue_depth}")
    print(f"  serial    : {s['ok']} ok in {s['wall_s']}s "
          f"({s['req_per_sec']} req/s)")
    print(f"  concurrent: {c['ok']} ok / {c['rejected']} rejected / "
          f"{c['error']} error in {c['wall_s']}s "
          f"({c['req_per_sec']} req/s)")
    print(f"  throughput ratio vs serial: "
          f"{rep['throughput_ratio_vs_serial']}x")
    print(f"  latency p50/p99: {c['latency_p50_s']}s / "
          f"{c['latency_p99_s']}s ; queue wait p50/p99: "
          f"{rep['sched']['queue_wait_p50_s']}s / "
          f"{rep['sched']['queue_wait_p99_s']}s")
    print(f"  sheds by reason: {rep['sched']['rejected_by_reason']}")
    cost = rep.get("cost") or {}
    if cost.get("queries"):
        print(f"  cost ledgers: {cost['queries']} queries on "
              f"{cost.get('devices')}, "
              f"device {cost['device_s']}s / host "
              f"{cost['host_total_s']}s, shuffle "
              f"{cost['shuffle_bytes']}B, spill {cost['spill_bytes']}B")
    rc = 0
    if args.expect_shed and c["rejected"] == 0:
        print("  FAIL: overload produced no rejections — admission "
              "control untested at this load")
        rc = 1
    if c["error"]:
        print(f"  FAIL: {c['error']} requests died UNCLASSIFIED "
              "(neither DONE nor AdmissionRejected)")
        rc = 1
    print(json.dumps(rep))
    return rc


if __name__ == "__main__":
    sys.exit(main())
