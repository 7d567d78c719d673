"""Perf regression gate: fresh q01 bench vs the checked-in baseline.

q01 CPU throughput decayed 276k → 108k rows/s across the driver's
round 3→5 captures and nobody noticed until they were read side by
side. This gate makes that trajectory a failing exit code: it takes a
fresh ``bench.py`` record (or one from a file/stdin), looks up the
platform's floor in ``tools/perf_baseline.json`` (CPU floors only —
a tpu record has none until the ledger supplies it), applies the
tolerance
(CLI > ``auron.perf_gate.tolerance_pct`` > baseline default, sized to
this container's measured wall-clock variance), and exits nonzero on a
regression past it.

    python tools/perf_gate.py --run                # runs bench.py
    python tools/perf_gate.py --bench-json rec.json
    python bench.py | python tools/perf_gate.py --bench-json -

Exit codes: 0 pass, 1 regression, 2 unusable record (bench errored or
the platform has no baseline). The last stdout line is one JSON record
(the bench.py / chaos_report.py driver contract) carrying the verdict
AND the bench record's host/device ``profile`` section, so a failing
gate arrives WITH the attribution that explains where the time went.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_HERE = os.path.dirname(os.path.abspath(__file__))
_DEFAULT_BASELINE = os.path.join(_HERE, "perf_baseline.json")


def load_baseline(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def fresh_bench_record(timeout_s: int = 1800) -> dict:
    """Run bench.py (one process; this one stays off jax, so the child
    gets the chip) and parse its one-JSON-line contract."""
    repo = os.path.dirname(_HERE)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=timeout_s, cwd=repo)
    lines = [ln for ln in (proc.stdout or "").strip().splitlines()
             if ln.strip()]
    if not lines:
        raise SystemExit(
            f"bench.py produced no output (rc={proc.returncode}); "
            f"stderr tail: {(proc.stderr or '')[-500:]}")
    return json.loads(lines[-1])


def resolve_tolerance(cli_pct, baseline: dict) -> float:
    if cli_pct is not None:
        return float(cli_pct)
    try:
        from auron_tpu import config as cfg
        conf = cfg.get_config()
        # honor an explicit override — a programmatic AuronConfig.set
        # (the documented top of the resolution order) or the env
        # binding; otherwise prefer the baseline file so the floor and
        # its tolerance travel together in one reviewed artifact
        opt = cfg._REGISTRY[cfg.PERF_GATE_TOLERANCE_PCT]
        with conf._lock:
            session_set = cfg.PERF_GATE_TOLERANCE_PCT in conf._overrides
        if session_set or os.environ.get(opt.env_var) is not None:
            return float(conf.get(cfg.PERF_GATE_TOLERANCE_PCT))
    except Exception:
        pass
    return float(baseline.get("default_tolerance_pct", 50.0))


def evaluate(record: dict, baseline: dict, tolerance_pct: float,
             tolerance_pinned: bool = False) -> dict:
    """Pure gate verdict from a bench record + baseline (the unit the
    mechanics tests drive with synthetic records).

    Two floors per platform: the flagship KERNEL metric
    (``record["value"]``, the historical rows/s headline) under the
    platform's ``tolerance_pct`` (falling back to the resolved default),
    and — when both the baseline entry and the record carry one — the
    q01 OPERATOR-PIPELINE floor (``profile.pipeline_rows_per_sec``, the
    end-to-end number the pipelined-execution work moves) under its own
    tighter tolerance. Either floor failing fails the gate. The
    pipeline floor only applies when the record's profile scale matches
    the baseline's (batch-size/scale experiments must not trip it)."""
    if "error" in record and record.get("value") is None:
        return {"perf_gate": "unusable",
                "reason": f"bench errored: {record['error']}"}
    platform = record.get("platform", "")
    entry = baseline.get("platforms", {}).get(platform)
    if entry is None:
        return {"perf_gate": "unusable",
                "reason": f"no baseline for platform {platform!r}"}
    value = float(record.get("value", 0.0))
    base = float(entry["rows_per_sec"])
    # per-platform tolerance override (the tightened CPU floor) unless
    # the caller pinned one explicitly (CLI --tolerance-pct)
    entry_tol = entry.get("tolerance_pct")
    eff_tol = (float(entry_tol)
               if entry_tol is not None and not tolerance_pinned
               else tolerance_pct)
    floor = base * (1.0 - eff_tol / 100.0)
    verdict = {
        "perf_gate": "pass" if value >= floor else "fail",
        "metric": baseline.get("metric"),
        "platform": platform,
        "value_rows_per_sec": round(value, 1),
        "baseline_rows_per_sec": round(base, 1),
        "floor_rows_per_sec": round(floor, 1),
        "tolerance_pct": eff_tol,
        "delta_vs_baseline_pct": round((value - base) / base * 100.0, 2),
    }
    pentry = entry.get("pipeline")
    if pentry:
        prof = record.get("profile")
        pscale = pentry.get("scale")
        has_value = isinstance(prof, dict) \
            and bool(prof.get("pipeline_rows_per_sec"))
        if has_value and pscale is not None \
                and float(prof.get("scale", -1)) != float(pscale):
            # batch-size / scale experiments must not trip the floor,
            # but the skip is RECORDED, never silent
            verdict["pipeline"] = {
                "verdict": "skipped",
                "reason": f"profile scale {prof.get('scale')} != "
                          f"baseline scale {pscale}",
            }
        elif not has_value:
            # the baseline expects a pipeline number and the record
            # can't produce one (bench profile errored, or throughput
            # collapsed to 0) — exactly the silent-decay mode the
            # floor exists to catch: fail loudly
            verdict["pipeline"] = {
                "verdict": "missing",
                "reason": "record carries no usable "
                          "profile.pipeline_rows_per_sec "
                          + (f"(profile_error: {record['profile_error']})"
                             if record.get("profile_error") else ""),
            }
            verdict["perf_gate"] = "fail"
        else:
            pval = float(prof["pipeline_rows_per_sec"])
            pbase = float(pentry["rows_per_sec"])
            ptol = float(pentry.get("tolerance_pct", eff_tol))
            pfloor = pbase * (1.0 - ptol / 100.0)
            verdict["pipeline"] = {
                "verdict": "pass" if pval >= pfloor else "fail",
                "value_rows_per_sec": round(pval, 1),
                "baseline_rows_per_sec": round(pbase, 1),
                "floor_rows_per_sec": round(pfloor, 1),
                "tolerance_pct": ptol,
                "delta_vs_baseline_pct": round(
                    (pval - pbase) / pbase * 100.0, 2),
            }
            if pval < pfloor:
                verdict["perf_gate"] = "fail"
    # SPMD mesh floor: the virtual 8-device CPU mesh q01 scaling figure
    # (bench.bench_mesh). Gated whenever the record carries a mesh
    # section; a bench that TRIED and failed records mesh_error and
    # FAILS (the silent-decay hole stays closed for every fresh bench);
    # a single-device run says it skipped the sweep, and records
    # predating the mesh bench skip with the skip recorded.
    mentry = baseline.get("platforms", {}).get("mesh")
    if mentry:
        mrec = record.get("mesh")
        if isinstance(mrec, dict) and mrec.get("skipped"):
            verdict["mesh"] = {"verdict": "skipped",
                               "reason": str(mrec["skipped"])}
        elif isinstance(mrec, dict) and mrec.get("mesh_rows_per_sec"):
            mscale = mentry.get("scale")
            mdev = int(mentry.get("devices", 8))
            if mscale is not None \
                    and float(mrec.get("scale", -1)) != float(mscale):
                verdict["mesh"] = {
                    "verdict": "skipped",
                    "reason": f"mesh scale {mrec.get('scale')} != "
                              f"baseline scale {mscale}",
                }
            elif int(mrec.get("devices", 0)) != mdev:
                verdict["mesh"] = {
                    "verdict": "skipped",
                    "reason": f"mesh devices {mrec.get('devices')} != "
                              f"baseline devices {mdev}",
                }
            elif mrec.get("mesh_demoted") or (mrec.get(
                    "route_demoted_by_devices") or {}).get(
                    str(mrec.get("devices", 0)), 0):
                # a run whose rounds demoted to host mid-exchange
                # measured the RECOVERY path, not the mesh: it must
                # neither fail the floor (the demotion worked as
                # designed) nor pass it (host throughput is not a mesh
                # figure) — recorded and reported, never miscounted
                verdict["mesh"] = {
                    "verdict": "skipped",
                    "reason": "mesh rounds demoted to host mid-run "
                              "(recovery path measured, not the mesh)",
                    "value_rows_per_sec": round(
                        float(mrec["mesh_rows_per_sec"]), 1),
                    "route_demoted": mrec.get(
                        "route_demoted_by_devices"),
                    "route_mix": mrec.get("route_mix_by_devices"),
                }
            else:
                mval = float(mrec["mesh_rows_per_sec"])
                mbase = float(mentry["rows_per_sec"])
                mtol = float(mentry.get("tolerance_pct", eff_tol))
                mfloor = mbase * (1.0 - mtol / 100.0)
                verdict["mesh"] = {
                    "verdict": "pass" if mval >= mfloor else "fail",
                    "value_rows_per_sec": round(mval, 1),
                    "baseline_rows_per_sec": round(mbase, 1),
                    "floor_rows_per_sec": round(mfloor, 1),
                    "tolerance_pct": mtol,
                    "delta_vs_baseline_pct": round(
                        (mval - mbase) / mbase * 100.0, 2),
                    "scaling_factor": mrec.get("scaling_factor"),
                    "route_all_to_all": mrec.get(
                        "route_all_to_all_by_devices"),
                    "route_mix": mrec.get("route_mix_by_devices"),
                }
                if mval < mfloor:
                    verdict["perf_gate"] = "fail"
        elif record.get("mesh_error"):
            verdict["mesh"] = {
                "verdict": "missing",
                "reason": f"mesh bench errored: {record['mesh_error']}",
            }
            verdict["perf_gate"] = "fail"
        elif "mesh" in record:
            # a mesh section WITHOUT a usable value (interrupted child,
            # renamed key) is the silent-decay mode, not a pre-mesh
            # record — fail loudly like the pipeline floor's zero case
            verdict["mesh"] = {
                "verdict": "missing",
                "reason": "mesh section carries no usable "
                          "mesh_rows_per_sec",
            }
            verdict["perf_gate"] = "fail"
        else:
            verdict["mesh"] = {
                "verdict": "skipped",
                "reason": "record carries no mesh section "
                          "(predates the mesh bench)",
            }
    # carry the forensics along: a failing gate should arrive WITH the
    # host/device attribution
    if isinstance(record.get("profile"), dict):
        verdict["profile"] = record["profile"]
    return verdict


def scrape_ops_metrics(port: int, host: str = "127.0.0.1") -> dict:
    """One STRICT ops-endpoint scrape (the ops-plane gate's unit):
    fetch ``/metrics``, run it through the conformance parser
    (obs/registry.parse_prometheus — ValueError on any text-format
    violation), and verify the SLO family
    ``auron_query_duration_seconds`` is being exposed. Returns the
    parsed families."""
    import urllib.request

    from auron_tpu.obs import registry as obs_registry
    with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    fams = obs_registry.parse_prometheus(text)
    if "auron_query_duration_seconds" not in fams:
        raise ValueError(
            "auron_query_duration_seconds absent from /metrics — the "
            "per-query SLO surface is gone")
    return fams


def run_ops_gate(tables) -> dict:
    """Ops-plane smoke gate: boot a Session with the telemetry endpoint
    on (ephemeral port), scrape ``/metrics`` in a loop WHILE q01 runs,
    and fail loudly when any scrape is unparseable, the SLO histogram
    is missing, or the endpoint never answered. Returns
    ``{"ops_gate": "pass"|"fail", "ops_scrapes": n, "ops_error": ...}``."""
    import threading

    from auron_tpu import config as cfg
    from auron_tpu.frontend.session import Session
    from auron_tpu.it.queries import q01_dataframe
    conf = cfg.get_config()
    conf.set(cfg.OPS_ENABLED, True)
    conf.set(cfg.OPS_PORT, 0)
    errors: list = []
    scrapes = [0]
    try:
        s = Session()
        try:
            if s.ops_address is None:
                return {"ops_gate": "fail", "ops_scrapes": 0,
                        "ops_error": "ops endpoint did not start "
                                     "(auron.ops.enabled was on)"}
            port = s.ops_address[1]
            stop = threading.Event()

            def scraper():
                while not stop.is_set():
                    try:
                        scrape_ops_metrics(port)
                        scrapes[0] += 1
                    except Exception as e:   # noqa: BLE001 — verdict
                        errors.append(f"{type(e).__name__}: {e}")
                        return
                    stop.wait(0.002)

            th = threading.Thread(target=scraper, daemon=True)
            th.start()
            q01_dataframe(s, tables).collect()   # scraped mid-flight
            stop.set()
            th.join(10)
            try:
                # final post-run scrape: the family must be present
                # and parseable AFTER the query observed its outcome
                scrape_ops_metrics(port)
                scrapes[0] += 1
            except Exception as e:   # noqa: BLE001 — verdict
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            s.close()
    finally:
        conf.unset(cfg.OPS_ENABLED)
        conf.unset(cfg.OPS_PORT)
    out = {"ops_gate": "pass" if not errors and scrapes[0] else "fail",
           "ops_scrapes": scrapes[0]}
    if errors:
        out["ops_error"] = errors[0]
    elif not scrapes[0]:
        out["ops_error"] = "ops endpoint answered no scrape"
    return out


def run_lint_gate() -> dict:
    """graftlint arm of the smoke gate: the contract checker
    (auron_tpu/analysis, ANALYSIS.md) must hold on HEAD. Fails LOUDLY
    when the baseline file is missing or unparseable (a deleted/garbage
    baseline would otherwise let every frozen violation pass as 'new
    code clean'), when baseline entries have gone stale en masse (the
    file no longer describes this tree), or when unbaselined
    violations/parse errors exist. Returns
    ``{"lint_gate": "pass"|"fail", "lint_new": n, ...}``."""
    from auron_tpu.analysis import core
    path = core.default_baseline_path()
    if not os.path.exists(path):
        return {"lint_gate": "fail", "lint_new": -1,
                "lint_error": f"lint baseline missing: {path} — run "
                              f"python -m auron_tpu.analysis "
                              f"--update-baseline"}
    try:
        baseline = core.load_baseline(path)
    except (ValueError, json.JSONDecodeError, OSError) as e:
        return {"lint_gate": "fail", "lint_new": -1,
                "lint_error": f"lint baseline unreadable: {e}"}
    result = core.analyze()
    new, old, stale = core.apply_baseline(result.violations, baseline)
    out = {"lint_gate": "pass", "lint_new": len(new),
           "lint_baselined": len(old), "lint_stale": len(stale),
           "lint_suppressed": result.suppressed,
           "lint_files": result.files_scanned}
    entries = len(baseline.get("entries", ()))
    if result.parse_errors:
        out["lint_gate"] = "fail"
        out["lint_error"] = (f"{len(result.parse_errors)} files failed "
                             f"to parse: {result.parse_errors[0]}")
    elif new:
        out["lint_gate"] = "fail"
        v = new[0]
        out["lint_error"] = (f"{len(new)} unbaselined violations, "
                             f"first: {v.file}:{v.line} {v.rule} "
                             f"{v.message}")
    elif entries and len(stale) * 2 > entries:
        # over half the frozen entries match nothing in this tree: the
        # baseline is from another world (mass rename/refactor) and
        # 'pass' would be vacuous — regenerate it deliberately
        out["lint_gate"] = "fail"
        out["lint_error"] = (f"lint baseline is stale: {len(stale)} of "
                             f"{entries} entries match nothing — "
                             f"regenerate with --update-baseline")
    return out


def run_cache_gate(tables, smoke: dict) -> dict:
    """Warm-path cache arm (the serving-plane cache, cache/result_cache):
    with ``auron.cache.*`` armed, the SAME q01 re-submitted through one
    Session must come back from the result cache — bit-identical and at
    least ``smoke.cache_speedup_floor_x`` times faster than the fresh
    run — and a fresh Session's AOT warmer (``auron.cache.aot_top_n``)
    must replay the recorded plan with zero silent errors. A repeat
    submission that never hits, a non-identical cached result, a
    speedup under the floor, an erroring warmer, or a warmer that
    warmed NOTHING all fail loudly. Returns
    ``{"cache_gate": "pass"|"fail", "cache_speedup_x": ..., ...}``."""
    import shutil
    import time

    from auron_tpu import config as cfg
    from auron_tpu.cache import aot as _aot
    from auron_tpu.cache.result_cache import get_cache
    from auron_tpu.frontend.session import Session
    from auron_tpu.it.queries import q01_dataframe

    floor_x = float(smoke.get("cache_speedup_floor_x", 5.0))
    conf = cfg.get_config()
    cache = get_cache()
    # naming auron.xla_cache_dir arms the AOT inventory under it — and
    # moves the compile cache there unless the environment placed it.
    # A fixed corner of the cache the process already uses, so the
    # gate's compiles stay warm across runs; only its inventory is
    # scratch, and the binding is restored after
    from auron_tpu.utils import xla_cache
    aot_root = os.path.join(xla_cache.cache_dir(conf), "perf_gate")
    aot_plans = os.path.join(aot_root, "aot_plans")
    shutil.rmtree(aot_plans, ignore_errors=True)
    conf.set(cfg.CACHE_ENABLED, True)
    conf.set(cfg.XLA_CACHE_DIR, aot_root)
    try:
        cache.clear(reset_counters=True)
        s = Session()
        try:
            t0 = time.perf_counter()
            fresh = q01_dataframe(s, tables).collect()
            fresh_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            cached = q01_dataframe(s, tables).collect()
            cached_s = time.perf_counter() - t0
        finally:
            s.close()
        st = cache.stats()
        speedup = fresh_s / cached_s if cached_s > 0 else float("inf")
        out = {
            "cache_gate": "pass",
            "cache_speedup_x": round(speedup, 1),
            "cache_speedup_floor_x": floor_x,
            "cache_fresh_s": round(fresh_s, 4),
            "cache_hit_s": round(cached_s, 4),
            "cache_hits": st["hits"],
        }
        if not st["hits"]:
            out["cache_gate"] = "fail"
            out["cache_error"] = (
                "repeat submission never hit the result cache (0 hits "
                "recorded) — the warm path did not engage")
        elif not cached.equals(fresh):
            out["cache_gate"] = "fail"
            out["cache_error"] = ("cached q01 result is not bit-identical "
                                  "to the fresh run")
        elif speedup < floor_x:
            out["cache_gate"] = "fail"
            out["cache_error"] = (
                f"repeat-query speedup {speedup:.1f}x < floor "
                f"{floor_x:.0f}x (warm-path serving gate)")
        # AOT arm: the fresh run above recorded its plan in the
        # inventory; a NEW Session with the warmer armed must replay it
        # cleanly (errors are collected, never raised — exactly the
        # silent-failure mode this arm exists to catch)
        conf.set(cfg.CACHE_AOT_TOP_N, 2)
        try:
            cache.clear(reset_counters=True)
            Session().close()
        finally:
            conf.unset(cfg.CACHE_AOT_TOP_N)
        # the warmer runs on a background thread since Fusion 2.0's
        # overlap work; close() joins it, but join explicitly anyway —
        # this arm must gate the FINAL summary, not an in-flight one
        _aot.wait(timeout=120.0)
        aot = _aot.last_stats()
        out["aot_warmed"] = aot["warmed"]
        out["aot_errors"] = len(aot["errors"])
        out["aot_overlapped_ms"] = aot.get("overlapped_ms", 0.0)
        if aot["errors"]:
            out["cache_gate"] = "fail"
            out["cache_error"] = (
                f"AOT warmer errored silently: {aot['errors'][0]}")
        elif not aot["warmed"]:
            out["cache_gate"] = "fail"
            out["cache_error"] = (
                "AOT warmer warmed nothing — the recorded q01 plan "
                "never reached the inventory")
        return out
    finally:
        conf.unset(cfg.CACHE_ENABLED)
        conf.unset(cfg.XLA_CACHE_DIR)
        cache.clear(reset_counters=True)
        xla_cache.bind(conf)
        shutil.rmtree(aot_plans, ignore_errors=True)


def run_fusion_gate() -> dict:
    """Fusion 2.0 map-side-combine arm: on the dup-heavy grouped agg
    (bench.bench_fusion2 — a tiny-key-domain multi-partition group-by)
    the combine stage must have merged groups before the exchange:
    ``combine_rows_in > combine_rows_out > 0``. A run whose byte counter
    reads zero (the exchange's live-bytes ledger went dark), or whose
    combine shipped as many rows as it took (the fold silently
    disengaged — the seeded-regression mode this arm exists to catch),
    fails loudly rather than gating a vacuous measurement. Returns
    ``{"fusion_gate": "pass"|"fail", "combine_rows_in": ...}``."""
    from bench import bench_fusion2
    try:
        r = bench_fusion2()
    except Exception as e:   # noqa: BLE001 — verdict, not a crash
        return {"fusion_gate": "fail",
                "fusion_error": f"{type(e).__name__}: {e}"}
    nbytes = int(r.get("combine_shuffle_bytes", 0))
    rows_in = int(r.get("combine_rows_in", 0))
    rows_out = int(r.get("combine_rows_out", 0))
    out = {
        "fusion_gate": "pass",
        "combine_shuffle_bytes": nbytes,
        "combine_rows_in": rows_in,
        "combine_rows_out": rows_out,
        "fusion2_rows_per_sec": r.get("fusion2_rows_per_sec", 0.0),
    }
    if not nbytes:
        out["fusion_gate"] = "fail"
        out["fusion_error"] = (
            "shuffle byte counter reads zero — the exchange's "
            "live-bytes ledger went dark, nothing to gate")
    elif not rows_in > rows_out > 0:
        out["fusion_gate"] = "fail"
        out["fusion_error"] = (
            f"the combine stage took {rows_in:,} rows and shipped "
            f"{rows_out:,} — map-side combine silently disengaged")
    return out


def run_fleet_gate(smoke: dict) -> dict:
    """Serving-fleet arm (the replicated-AuronServer plane): TWO real
    replica subprocesses behind an in-process ``FleetRouter``; a query
    is driven through the router and the replica that picked it up is
    SIGKILLed mid-flight. The gate holds when the client still receives
    the bit-identical table (journal RESUME on the survivor, or guarded
    re-execution — either is a legitimate failover), exactly one
    replica death is recorded, and the detect-to-done failover latency
    stays under ``smoke.fleet_failover_ceiling_s`` — an idle survivor
    has free capacity, so a slow failover here is router overhead, not
    admission queueing. Returns ``{"fleet_gate": "pass"|"fail",
    "fleet_failover_s": ..., ...}``."""
    import tempfile
    import threading
    import time

    ceiling = float(smoke.get("fleet_failover_ceiling_s", 10.0))
    out: dict = {"fleet_gate": "pass",
                 "fleet_failover_ceiling_s": ceiling}
    root = None
    try:
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from auron_tpu.fleet.replica import FleetHarness
        from auron_tpu.ir import pb
        from auron_tpu.utils.envsafe import require_shareable_device

        require_shareable_device("the fleet gate")
        root = tempfile.mkdtemp(prefix="auron_fleet_gate_")
        rng = np.random.default_rng(19)
        n = 600_000
        path = os.path.join(root, "fleet.parquet")
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 64, n), pa.int64()),
            "v": pa.array(rng.normal(size=n), pa.float64())}), path)
        col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
        plan = pb.PlanNode(agg=pb.AggNode(
            child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(
                files=[path])),
            mode="complete", group_exprs=[col(0)],
            aggs=[pb.AggFunctionP(fn="sum", arg=col(1)),
                  pb.AggFunctionP(fn="count", arg=col(1))]))
        task = pb.TaskDefinition(plan=plan,
                                 task_id=1).SerializeToString()

        with FleetHarness(2) as h:
            warm, _ = h.client(timeout_s=120).execute(task)
            box: dict = {}

            def drive() -> None:
                try:
                    tbl, _ = h.client(timeout_s=120).execute(task)
                    box["table"] = tbl
                except BaseException as e:   # noqa: BLE001 — verdict below
                    box["err"] = e

            t = threading.Thread(target=drive, daemon=True)
            t.start()
            victim = None
            deadline = time.monotonic() + 10.0
            while victim is None and t.is_alive() \
                    and time.monotonic() < deadline:
                h.router._poll_once()
                for i in range(len(h.replicas)):
                    snap = h.router._replicas[i].snapshot
                    if snap is not None and snap.occupancy > 0:
                        victim = i
                        break
                if victim is None:
                    time.sleep(0.05)
            if victim is not None and h.replicas[victim].alive():
                h.kill_replica(victim)
            t.join(timeout=120)
            stats = h.router.stats_dict()
            r = stats["router"]
            out["fleet_deaths"] = r["replica_deaths"]
            out["fleet_failover_kind"] = (
                "resume" if r["failovers_resume"]
                else "reexecute" if r["failovers_reexecute"] else "none")
            lats = stats.get("failover_latency_s") or []
            out["fleet_failover_s"] = round(lats[0], 3) if lats else None
            if t.is_alive():
                out["fleet_gate"] = "fail"
                out["fleet_error"] = ("the killed query never "
                                      "completed or classified (wedged)")
            elif victim is None:
                out["fleet_gate"] = "fail"
                out["fleet_error"] = ("no replica ever showed the query "
                                      "running — nothing was killed, "
                                      "nothing gated")
            elif "err" in box:
                out["fleet_gate"] = "fail"
                out["fleet_error"] = (f"failover surfaced an error to "
                                      f"the client: "
                                      f"{type(box['err']).__name__}: "
                                      f"{str(box['err'])[:200]}")
            elif not box["table"].equals(warm):
                out["fleet_gate"] = "fail"
                out["fleet_error"] = ("failed-over query's table is "
                                      "not bit-identical to the warm "
                                      "pass")
            elif r["replica_deaths"] != 1:
                out["fleet_gate"] = "fail"
                out["fleet_error"] = (f"expected exactly one recorded "
                                      f"replica death, saw "
                                      f"{r['replica_deaths']}")
            elif out["fleet_failover_kind"] == "none":
                out["fleet_gate"] = "fail"
                out["fleet_error"] = ("no failover recorded — the "
                                      "query survived without one "
                                      "(kill landed too late?)")
            elif lats and lats[0] >= ceiling:
                out["fleet_gate"] = "fail"
                out["fleet_error"] = (
                    f"failover took {lats[0]:.2f}s >= ceiling "
                    f"{ceiling:.0f}s against an IDLE survivor — "
                    f"router overhead, not admission queueing")
    except Exception as e:   # noqa: BLE001 — verdict, not a crash
        return {"fleet_gate": "fail",
                "fleet_failover_ceiling_s": ceiling,
                "fleet_error": f"{type(e).__name__}: {e}"}
    finally:
        if root is not None:
            import shutil
            shutil.rmtree(root, ignore_errors=True)
    return out


def obs_fleet_verdict(base_s: float, obs_s: float, smoke: dict, *,
                      ledgers_on: int, ledgers_off: int,
                      queries: int) -> dict:
    """Pure verdict for the fleet-observability overhead arm (the unit
    the seeded-regression test drives with synthetic walls): ``base_s``
    is the best observed wall with trace propagation + the cost ledger
    OFF, ``obs_s`` with both ON, over the same ``queries``-query batch.
    The A/B must be HONEST to gate anything: the on-arm must have
    produced a cost ledger on every query (an idle ledger would measure
    nothing) and the off-arm must have produced none (a knob that no
    longer disengages would measure the feature against itself)."""
    limit = float(smoke.get("obs_fleet_overhead_pct_max", 2.0))
    out: dict = {"obs_fleet_gate": "pass",
                 "obs_fleet_overhead_pct_max": limit,
                 "obs_fleet_queries": queries,
                 "obs_fleet_base_s": round(base_s, 4),
                 "obs_fleet_obs_s": round(obs_s, 4),
                 "obs_fleet_ledgers": ledgers_on}
    if not (base_s > 0.0) or not (obs_s > 0.0):
        out["obs_fleet_gate"] = "fail"
        out["obs_fleet_error"] = (
            "overhead measurement went dark (non-positive wall) — "
            "nothing to gate")
        return out
    overhead = (obs_s - base_s) / base_s * 100.0
    out["obs_fleet_overhead_pct"] = round(overhead, 3)
    if ledgers_on < queries:
        out["obs_fleet_gate"] = "fail"
        out["obs_fleet_error"] = (
            f"cost ledger engaged on only {ledgers_on}/{queries} "
            f"on-arm queries — the overhead measured an idle ledger")
    elif ledgers_off:
        out["obs_fleet_gate"] = "fail"
        out["obs_fleet_error"] = (
            f"off-arm still produced {ledgers_off} cost ledger(s) — "
            f"auron.ledger.enabled no longer disengages, the A/B "
            f"measured the feature against itself")
    elif overhead >= limit:
        out["obs_fleet_gate"] = "fail"
        out["obs_fleet_error"] = (
            f"trace-propagation + cost-ledger overhead "
            f"{overhead:.2f}% >= {limit:.0f}% of the serving wall "
            f"(fleet-observability gate)")
    return out


def run_obs_fleet_gate(smoke: dict) -> dict:
    """Fleet-observability overhead arm (ISSUE 20): the cross-process
    trace plumbing (KIND_TRACE prefix frame + wire_scope adoption) and
    the per-query cost ledger both sit on the serving hot path, so this
    arm runs the SAME grouped-agg through one in-process AuronServer
    with tracing on in BOTH arms and only ``auron.trace.propagate`` +
    ``auron.ledger.enabled`` toggled between them. Best-of-3
    interleaved passes per arm (min wall over a 4-query batch) against
    ``smoke.obs_fleet_overhead_pct_max``; verdict mechanics live in
    ``obs_fleet_verdict``."""
    import tempfile
    import time

    try:
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from auron_tpu import config as cfg
        from auron_tpu.ir import pb
        from auron_tpu.runtime.serving import AuronClient, AuronServer

        root = tempfile.mkdtemp(prefix="auron_obs_gate_")
        try:
            rng = np.random.default_rng(20)
            n = 120_000
            path = os.path.join(root, "obs.parquet")
            pq.write_table(pa.table({
                "k": pa.array(rng.integers(0, 32, n), pa.int64()),
                "v": pa.array(rng.normal(size=n), pa.float64())}), path)
            col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
            plan = pb.PlanNode(agg=pb.AggNode(
                child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(
                    files=[path])),
                mode="complete", group_exprs=[col(0)],
                aggs=[pb.AggFunctionP(fn="sum", arg=col(1)),
                      pb.AggFunctionP(fn="count", arg=col(1))]))
            task = pb.TaskDefinition(plan=plan,
                                     task_id=1).SerializeToString()

            conf = cfg.get_config()
            conf.set(cfg.TRACE_ENABLED, True)
            srv = AuronServer()
            srv.serve_background()
            try:
                client = AuronClient(*srv.address, timeout_s=120)
                passes, batch = 3, 4

                def arm(obs_on: bool) -> "tuple[float, int]":
                    conf.set(cfg.TRACE_PROPAGATE, obs_on)
                    conf.set(cfg.LEDGER_ENABLED, obs_on)
                    led = 0
                    t0 = time.perf_counter()
                    for _ in range(batch):
                        _tbl, metrics = client.execute(task)
                        if isinstance(metrics.get("cost_ledger"), dict):
                            led += 1
                    return time.perf_counter() - t0, led

                arm(True)   # warm compiles + first-span setup costs
                base_s = obs_s = float("inf")
                ledgers_on = ledgers_off = 0
                # interleaved passes so container drift hits both arms
                for _ in range(passes):
                    w, led = arm(False)
                    base_s = min(base_s, w)
                    ledgers_off += led
                    w, led = arm(True)
                    obs_s = min(obs_s, w)
                    ledgers_on += led
            finally:
                srv.shutdown()
                conf.unset(cfg.TRACE_ENABLED)
                conf.unset(cfg.TRACE_PROPAGATE)
                conf.unset(cfg.LEDGER_ENABLED)
            # the on-arm must have engaged on EVERY query of every pass
            # and the off-arm on none — obs_fleet_verdict normalizes to
            # one pass's batch for the engagement contract
            return obs_fleet_verdict(
                base_s, obs_s, smoke,
                ledgers_on=ledgers_on // passes,
                ledgers_off=ledgers_off, queries=batch)
        finally:
            import shutil
            shutil.rmtree(root, ignore_errors=True)
    except Exception as e:   # noqa: BLE001 — verdict, not a crash
        return {"obs_fleet_gate": "fail",
                "obs_fleet_overhead_pct_max": float(
                    smoke.get("obs_fleet_overhead_pct_max", 2.0)),
                "obs_fleet_error": f"{type(e).__name__}: {e}"}


def run_smoke(baseline: dict) -> dict:
    """Tier-1-fast smoke arm: run the q01 operator pipeline in-process
    at a tiny scale and compare against the generous smoke floor — an
    order-of-magnitude tripwire (compile-cache regressions, accidental
    per-row host loops) cheap enough for a test to invoke every run,
    so throughput can't silently decay between bench rounds again.

    Doubles as the CONCURRENCY-TAX gate: every query now enters the
    scheduler (admission + fairness bookkeeping), and this mode asserts
    the solo-query path pays < 2% of wall for it. Measured from the
    slot's own overhead ledger (time INSIDE acquire/turn/release, not
    policy waits) against the best run's wall — a deterministic ratio,
    immune to the container's wall-clock noise that plagues A/B runs.

    And as the JOURNAL-OVERHEAD gate (the crash-safe query journal,
    runtime/journal.py): one extra q01 run with ``auron.journal.dir``
    armed, asserting the journal's HOT-PATH cost (its own ``hot_ns``
    ledger: record enqueues + the commit-boundary drain/fsync waits —
    everything the driving thread ever blocks on) stays under
    ``smoke.journal_overhead_limit_pct`` of that run's wall. Same
    deterministic-ledger discipline as the scheduler tax: a regression
    in the hot-path cost fails the gate instead of hiding in container
    noise.

    And as the WARM-PATH CACHE gate (``run_cache_gate``): with
    ``auron.cache.*`` armed, a repeated identical q01 must be served
    from the result cache bit-identically and at least
    ``smoke.cache_speedup_floor_x`` times faster than fresh, and the
    AOT warmer must replay the recorded plan with zero errors.

    And as the FUSION 2.0 gate (``run_fusion_gate``): on the dup-heavy
    grouped agg the map-side combine must ship fewer rows than it took
    — a fold that silently disengaged fails here.

    And as the SERVING-FLEET gate (``run_fleet_gate``): a two-replica
    fleet with one replica SIGKILLed mid-query must hand the client the
    bit-identical table via failover within
    ``smoke.fleet_failover_ceiling_s`` of detection."""
    import tempfile
    import time

    scale = float(os.environ.get("AURON_PERF_SMOKE_SCALE", "0.5"))
    from auron_tpu.frontend.session import Session
    from auron_tpu.it.queries import q01_dataframe
    from auron_tpu.it.tpcds_data import generate as gen_data
    smoke = baseline.get("smoke", {})
    floor = float(smoke.get("cpu_floor_rows_per_sec", 20000.0))
    tax_limit = float(smoke.get("sched_tax_limit_pct", 2.0))
    journal_limit = float(smoke.get("journal_overhead_limit_pct", 2.0))
    data = tempfile.mkdtemp(prefix="auron_perf_smoke_")
    try:
        tables = gen_data(data, scale=scale)
        from bench import _table_rows
        rows = _table_rows(tables["store_sales"])
        q01_dataframe(Session(), tables).collect()   # warm compiles
        wall, tax_ns = float("inf"), 0
        for _ in range(2):
            s = Session()
            t0 = time.perf_counter()
            q01_dataframe(s, tables).collect()
            w = time.perf_counter() - t0
            if w < wall:
                wall, tax_ns = w, s._scheduler.last_overhead_ns
        value = rows / wall
        tax_pct = tax_ns / (wall * 1e9) * 100.0
        # journal arm: same query, journaling armed, hot-path ledger
        from auron_tpu import config as cfg
        from auron_tpu.runtime import journal as jrn
        conf = cfg.get_config()
        jdir = os.path.join(data, "journal")
        conf.set(cfg.JOURNAL_DIR, jdir)
        try:
            # best-of-2 like the main loop: one cold fsync outlier on
            # this container must not fail a healthy hot path
            journal_pct, jstats = float("inf"), {}
            for _ in range(2):
                s = Session()
                t0 = time.perf_counter()
                q01_dataframe(s, tables).collect()
                jwall = time.perf_counter() - t0
                s.close()
                st = jrn.last_stats()
                pct = st.get("hot_ns", 0) / (jwall * 1e9) * 100.0
                if pct < journal_pct:
                    journal_pct, jstats = pct, st
        finally:
            conf.unset(cfg.JOURNAL_DIR)
        verdict = {
            "perf_gate": "pass" if value >= floor else "fail",
            "mode": "smoke",
            "scale": scale,
            "input_rows": rows,
            "value_rows_per_sec": round(value, 1),
            "floor_rows_per_sec": round(floor, 1),
            "sched_tax_pct": round(tax_pct, 4),
            "sched_tax_limit_pct": tax_limit,
            "journal_overhead_pct": round(journal_pct, 4),
            "journal_overhead_limit_pct": journal_limit,
            "journal_records": jstats.get("records", 0),
            "journal_commits": jstats.get("commits", 0),
        }
        if tax_pct >= tax_limit:
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"scheduler tax {tax_pct:.3f}% >= {tax_limit}% of the "
                f"solo-query wall (concurrency-tax gate)")
        if not jstats.get("records"):
            # the journaled run recorded NOTHING: the plane silently
            # disarmed itself (or degraded) — the gate must not pass
            # on a measurement of an idle journal
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                "journal-overhead gate measured an idle journal "
                "(0 records) — journaling did not engage")
        elif journal_pct >= journal_limit:
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"journal hot-path overhead {journal_pct:.3f}% >= "
                f"{journal_limit}% of the journaled q01 wall "
                f"(crash-safe journal gate)")
        # warm-path cache arm: repeated identical q01 must be served
        # from the result cache (bit-identical, >= the floor's speedup)
        # and the AOT warmer must replay the recorded plan cleanly
        verdict.update(run_cache_gate(tables, smoke))
        if verdict["cache_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"cache gate: {verdict.get('cache_error', 'failed')}")
        # Fusion 2.0 arm: map-side combine must still cut the live
        # shuffle bytes of the dup-heavy grouped-agg A/B by the floor
        # (a silently disengaged fold fails loudly, not as a bytes tie)
        verdict.update(run_fusion_gate())
        if verdict["fusion_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"fusion gate: {verdict.get('fusion_error', 'failed')}")
        # ops-plane arm: the live telemetry endpoint must expose a
        # parseable /metrics carrying the SLO histogram, scraped WHILE
        # q01 runs (unparseable exposition or a vanished
        # auron_query_duration_seconds fails the gate loudly)
        verdict.update(run_ops_gate(tables))
        if verdict["ops_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"ops-plane gate: {verdict.get('ops_error', 'failed')}")
        # serving-fleet arm: a 2-replica fleet must survive a SIGKILL
        # mid-query — bit-identical answer to the client via failover
        # (resume or guarded re-execution), within the latency ceiling
        verdict.update(run_fleet_gate(smoke))
        if verdict["fleet_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"fleet gate: {verdict.get('fleet_error', 'failed')}")
        # fleet-observability arm: the trace-propagation + cost-ledger
        # plumbing on the serving hot path must stay under the
        # obs_fleet_overhead_pct_max share of the A/B wall, with the
        # ledger engaging on-arm and disengaging off-arm
        verdict.update(run_obs_fleet_gate(smoke))
        if verdict["obs_fleet_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"obs-fleet gate: "
                f"{verdict.get('obs_fleet_error', 'failed')}")
        # lint arm: the AST contract checker must hold on HEAD (a
        # missing/stale tools/lint_baseline.json fails loudly — decay
        # of the invariant surface can't hide between rounds either)
        verdict.update(run_lint_gate())
        if verdict["lint_gate"] != "pass" \
                and verdict["perf_gate"] == "pass":
            verdict["perf_gate"] = "fail"
            verdict["reason"] = (
                f"lint gate: {verdict.get('lint_error', 'failed')}")
        return verdict
    finally:
        import shutil
        shutil.rmtree(data, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=_DEFAULT_BASELINE,
                    help="baseline JSON (default tools/perf_baseline.json)")
    ap.add_argument("--bench-json", default=None,
                    help="bench record file ('-' reads stdin) instead of "
                         "running bench.py")
    ap.add_argument("--run", action="store_true",
                    help="run bench.py for a fresh record (the default "
                         "when --bench-json is absent)")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1-fast mode: run the q01 operator "
                         "pipeline in-process at a tiny scale against "
                         "the generous smoke floor (no bench.py child)")
    ap.add_argument("--tolerance-pct", type=float, default=None,
                    help="allowed shortfall vs the baseline floor "
                         "(default: auron.perf_gate.tolerance_pct env "
                         "override, else the baseline file's / the "
                         "platform entry's)")
    args = ap.parse_args(argv)

    baseline = load_baseline(args.baseline)
    if args.smoke:
        verdict = run_smoke(baseline)
        print(f"perf gate [smoke @ scale {verdict['scale']}]: "
              f"{verdict['value_rows_per_sec']:,.0f} rows/s vs floor "
              f"{verdict['floor_rows_per_sec']:,.0f}, sched tax "
              f"{verdict['sched_tax_pct']:.3f}% (limit "
              f"{verdict['sched_tax_limit_pct']:.0f}%), journal "
              f"overhead {verdict['journal_overhead_pct']:.3f}% (limit "
              f"{verdict['journal_overhead_limit_pct']:.0f}%), cache "
              f"{verdict.get('cache_speedup_x', '?')}x (floor "
              f"{verdict.get('cache_speedup_floor_x', '?')}x, aot "
              f"{verdict.get('aot_warmed', '?')} warmed), combine "
              f"{verdict.get('combine_rows_in', '?')} -> "
              f"{verdict.get('combine_rows_out', '?')} rows, "
              f"fleet failover "
              f"{verdict.get('fleet_failover_kind', '?')} in "
              f"{verdict.get('fleet_failover_s', '?')}s (ceiling "
              f"{verdict.get('fleet_failover_ceiling_s', '?'):.0f}s), "
              f"obs overhead "
              f"{verdict.get('obs_fleet_overhead_pct', '?')}% (limit "
              f"{verdict.get('obs_fleet_overhead_pct_max', '?'):.0f}%), "
              f"lint {verdict.get('lint_new', '?')} new → "
              f"{verdict['perf_gate'].upper()}")
        print(json.dumps(verdict))
        return 0 if verdict["perf_gate"] == "pass" else 1
    if args.bench_json == "-":
        record = json.loads(sys.stdin.read().strip().splitlines()[-1])
    elif args.bench_json:
        with open(args.bench_json) as f:
            record = json.loads(f.read().strip().splitlines()[-1])
    else:
        record = fresh_bench_record()

    tolerance = resolve_tolerance(args.tolerance_pct, baseline)
    verdict = evaluate(record, baseline, tolerance,
                       tolerance_pinned=args.tolerance_pct is not None)

    if verdict["perf_gate"] == "unusable":
        print(f"perf gate: UNUSABLE — {verdict['reason']}")
        print(json.dumps(verdict))
        return 2
    print(f"perf gate [{verdict['platform']}]: "
          f"{verdict['value_rows_per_sec']:,.0f} rows/s vs baseline "
          f"{verdict['baseline_rows_per_sec']:,.0f} "
          f"(floor {verdict['floor_rows_per_sec']:,.0f}, "
          f"tolerance {verdict['tolerance_pct']:.0f}%) → "
          f"{verdict['perf_gate'].upper()}")
    if "pipeline" in verdict:
        p = verdict["pipeline"]
        if p["verdict"] in ("skipped", "missing"):
            print(f"  q01 pipeline: {p['verdict'].upper()} — "
                  f"{p['reason']}")
        else:
            print(f"  q01 pipeline: {p['value_rows_per_sec']:,.0f} "
                  f"rows/s vs baseline "
                  f"{p['baseline_rows_per_sec']:,.0f} "
                  f"(floor {p['floor_rows_per_sec']:,.0f}, tolerance "
                  f"{p['tolerance_pct']:.0f}%) → {p['verdict'].upper()}")
    if "mesh" in verdict:
        m = verdict["mesh"]
        if m["verdict"] in ("skipped", "missing"):
            print(f"  mesh (8-dev virtual): {m['verdict'].upper()} — "
                  f"{m['reason']}")
        else:
            print(f"  mesh (8-dev virtual): "
                  f"{m['value_rows_per_sec']:,.0f} rows/s vs baseline "
                  f"{m['baseline_rows_per_sec']:,.0f} "
                  f"(floor {m['floor_rows_per_sec']:,.0f}, tolerance "
                  f"{m['tolerance_pct']:.0f}%, scaling "
                  f"{m.get('scaling_factor')}) → {m['verdict'].upper()}")
    if "profile" in verdict:
        p = verdict["profile"]
        print(f"  host/device split: device={p.get('device_ms')}ms "
              f"host={p.get('host_ms')}ms "
              f"buckets={p.get('host_buckets_ms')}")
    print(json.dumps(verdict))
    return 0 if verdict["perf_gate"] == "pass" else 1


if __name__ == "__main__":
    sys.exit(main())
