"""Chaos harness: seeded fault plans driven over recovery scenarios.

The executable contract of the robustness plane (runtime/faults.py):
for ANY fault plan, a scenario run either produces results bit-identical
to its fault-free baseline (recovery worked) or raises a classified
``AuronError`` (failure surfaced with a verdict) — never silently wrong
rows, never an unclassified crash, and never leaked ``.part``/spill
files after teardown. ``tests/test_zz_chaos_battery.py`` asserts it over
seeds; ``tools/chaos_report.py`` sweeps it and prints the site-by-site
outcome table.

Scenarios are self-contained op pipelines chosen so every injection
site has traffic: ``rss_pipeline`` (RSS write/flush/commit/fetch),
``spill_sort`` (spill write/read through the external-sort path),
``agg_pipeline`` (device compute + program build through a
Session-planned two-phase aggregation). Each ``run()`` constructs a
FRESH operator tree — exchange materialization and spill state are
per-run, exactly like a fresh task attempt.
"""

from __future__ import annotations

import gc
import glob
import os
import sys
import time as _time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np
import pyarrow as pa

from auron_tpu import config as cfg
from auron_tpu import errors
from auron_tpu.runtime import faults


@dataclass
class ChaosOutcome:
    scenario: str
    fault_plan: str
    seed: int
    #: identical | classified | mismatch | unclassified
    status: str
    error_type: Optional[str] = None
    error: Optional[str] = None
    #: {site: {kind: count}} actually injected during the run
    injected: dict = field(default_factory=dict)
    #: leftover .part / spill files after teardown (must be empty)
    leaks: list = field(default_factory=list)
    #: trace id of the run's span timeline (obs/trace; 0 = none)
    trace_id: int = 0
    #: site → {injected, fault_spans, recovery: {span name: count}} —
    #: the fault-injection events linked to the recovery spans they
    #: triggered (tools/chaos_report prints the aggregate table)
    correlation: dict = field(default_factory=dict)
    #: post-mortem bundle directories THIS run created (auron.bundle.*
    #: armed); the bundle audit's findings land in ``leaks`` so a
    #: missing/extra/fault-less bundle fails the run like a leaked file
    bundles: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.status in ("identical", "classified") and not self.leaks


#: span names that ARE recovery actions on the timeline: task-level
#: retries, corrupt-map recomputes, stall verdicts and pressure-ladder
#: sheds (the lifecycle plane's recovery actions)
RECOVERY_SPAN_NAMES = ("task.retry", "shuffle.corruption_recompute",
                       "watchdog.stall",
                       "memmgr.shed", "sched.reject",
                       "exchange.demote", "mesh.quarantine")


#: which injection KINDS can cause each recovery span — the corrupt
#: kind has a DEFERRED effect (injected at write, detected at fetch),
#: so a corruption recompute must skip over interleaved io_error/hang
#: injections when walking back for its cause
_RECOVERY_CAUSE_KINDS = {
    "shuffle.corruption_recompute": ("corrupt",),
    # only a hang goes silent long enough for the stall monitor
    "watchdog.stall": ("hang",),
    # the pressure ladder sheds on injected denies
    "memmgr.shed": ("deny",),
    # admission control sheds at the door on injected denies
    "sched.reject": ("deny",),
    # the mesh fault domain demotes on device loss (io_error/fatal at
    # mesh.all_to_all) and — under demote_on_straggler — on an injected
    # hang's straggling round
    "exchange.demote": ("io_error", "fatal", "hang"),
    "mesh.quarantine": ("io_error", "fatal"),
}


def correlate_spans(spans) -> dict:
    """Link fault-injection events to the recovery spans they triggered:
    each recovery span is attributed to the NEAREST PRECEDING injection
    of a kind that can cause it (the causality proxy — the run is
    single-pipeline, so the recovery that follows an injection was
    triggered by it). Nearest-preceding, not first-injection-onward: a
    multi-site plan must not double-count one task.retry under every
    armed site; kind-aware, because a corrupt fault injected at WRITE
    time recovers only at fetch time, past unrelated injections."""
    inj = sorted((s for s in spans
                  if s.cat == "fault" and s.name == "fault.injected"),
                 key=lambda s: (s.ts_ns, s.span_id))
    rec = [s for s in spans if s.name in RECOVERY_SPAN_NAMES]
    out: dict = {}
    for s in inj:
        site = s.attrs.get("site")
        entry = out.setdefault(site, {"injected": 0, "fault_spans": [],
                                      "recovery": {}})
        entry["injected"] += 1
        if len(entry["fault_spans"]) < 16:
            entry["fault_spans"].append(s.span_id)
    for r in rec:
        kinds = _RECOVERY_CAUSE_KINDS.get(r.name)
        prev = None
        for s in inj:
            if s.ts_ns > r.ts_ns:
                break
            if kinds is None or s.attrs.get("kind") in kinds:
                prev = s
        if prev is None:
            continue
        counts = out[prev.attrs.get("site")]["recovery"]
        counts[r.name] = counts.get(r.name, 0) + 1
    return out


class Scenario:
    """One recovery scenario: a fresh-run factory + leak audit paths."""

    def __init__(self, name: str, run: Callable[[], pa.Table],
                 leak_globs: list[str]):
        self.name = name
        self._run = run
        self.leak_globs = leak_globs
        self._baseline: Optional[pa.Table] = None

    def run(self) -> pa.Table:
        return self._run()

    def baseline(self) -> pa.Table:
        """Fault-free reference output (computed once, faults disarmed)."""
        if self._baseline is None:
            conf = cfg.get_config()
            conf.unset(cfg.FAULTS_PLAN)
            faults.reset()
            self._baseline = self.run()
        return self._baseline

    def leaks(self) -> list[str]:
        gc.collect()   # drop spill refs held by collected generators
        found = []
        for pattern in self.leak_globs:
            found.extend(glob.glob(pattern, recursive=True))
        extra = getattr(self, "extra_audit", None)
        if extra is not None:
            # scenario-specific resource ledger (registered memmgr
            # consumers, tracked spill files) — the zero-leaked-
            # consumers half of the lifecycle contract
            found.extend(extra())
        return found


def _rows(n: int, seed: int = 11) -> pa.RecordBatch:
    rng = np.random.default_rng(seed)
    return pa.record_batch({
        "k": pa.array(rng.integers(0, 64, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
        "c": pa.array(rng.integers(0, 1000, n), pa.int32()),
    })


def _canonical(table: pa.Table) -> pa.Table:
    """Row-order-canonical view for cross-run equality (shuffle reads
    are deterministic per run, but canonicalizing keeps the contract
    about VALUES, which is what integrity protects)."""
    return table.sort_by([(c, "ascending") for c in table.column_names])


def rss_pipeline(workdir: str) -> Scenario:
    """Scan → hash-partitioned RSS shuffle → collect: traffic on every
    rss.* site, map recompute on fetch corruption."""
    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.parallel.exchange import RssShuffleExchangeOp
    from auron_tpu.parallel.partitioning import HashPartitioning
    from auron_tpu.parallel.shuffle_service import FileShuffleService
    from auron_tpu.runtime.executor import collect

    rb = _rows(4096)
    rss_root = os.path.join(workdir, "rss")
    counter = [0]

    def run() -> pa.Table:
        counter[0] += 1
        root = os.path.join(rss_root, f"run_{counter[0]}")
        per = rb.num_rows // 2
        parts = [[rb.slice(i * per, per).slice(o, 512)
                  for o in range(0, per, 512)] for i in range(2)]
        scan = MemoryScanOp(parts, schema_from_arrow(rb.schema),
                            capacity=512)
        op = RssShuffleExchangeOp(
            scan, HashPartitioning([ir.ColumnRef(0)], 4),
            FileShuffleService(root), shuffle_id=1, input_partitions=2)
        return _canonical(collect(op, num_partitions=4))

    return Scenario("rss_pipeline", run,
                    [os.path.join(rss_root, "**", "*.part")])


def spill_sort(workdir: str) -> Scenario:
    """External sort with a 1-byte device budget and a 1-byte host spill
    budget: every run spills every batch to DISK frames — traffic on
    spill.write/spill.read, task-level recompute on spill corruption."""
    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager
    from auron_tpu.ops.sort import SortOp
    from auron_tpu.runtime.executor import collect

    rb = _rows(3000, seed=5)
    spill_dir = os.path.join(workdir, "spill")

    def run() -> pa.Table:
        rbs = [rb.slice(o, 500) for o in range(0, rb.num_rows, 500)]
        scan = MemoryScanOp([rbs], schema_from_arrow(rb.schema),
                            capacity=512)
        orders = [ir.SortOrder(ir.ColumnRef(0), ascending=True),
                  ir.SortOrder(ir.ColumnRef(2), ascending=False)]
        mm = MemManager(total_bytes=1, min_trigger=0,
                        spill_manager=SpillManager(
                            host_budget_bytes=1,
                            spill_dir=spill_dir))
        return collect(SortOp(scan, orders), num_partitions=1,
                       mem_manager=mm)

    return Scenario("spill_sort", run,
                    [os.path.join(spill_dir, "auron-spill-*")])


def agg_pipeline(workdir: str) -> Scenario:
    """Session-planned two-phase aggregation (the q01 shape): traffic on
    device.compute and program.build through the full planner path."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session

    table = pa.Table.from_batches([_rows(4096, seed=23)])

    def run() -> pa.Table:
        s = Session()
        df = (s.from_arrow(table)
              .filter(col("c") > 50)
              .group_by("k")
              .agg(F.sum(col("v")).alias("sv"),
                   F.count(col("c")).alias("n")))
        return _canonical(s.execute(df))

    return Scenario("agg_pipeline", run, [])


def mesh_pipeline(workdir: str) -> Scenario:
    """SPMD chaos scenario: the agg_pipeline shape (Session-planned
    two-phase aggregation) with ``auron.mesh.enabled`` on, so the hash
    exchange rides the on-device all-to-all stage program — the
    ``device.compute`` site fires both per output batch in the drive
    loop AND per all-to-all round inside the sharded-stage
    materialization, and the mesh fault domain's own sites get traffic
    too: ``mesh.all_to_all`` (per round — io_error/fatal simulate a
    device loss the DEMOTION path must recover bit-identically, hang a
    straggling chip) and ``mesh.gang`` (a cancel racing the gang door
    must dequeue without starting a round). A fault mid-exchange must
    classify cleanly (the gang releases, the mesh buffer unregisters,
    the exchange demotes or the task surfaces the verdict); RSS stays
    untouched as the durable fallback tier, which is exactly what this
    scenario proves out."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    from auron_tpu.parallel import mesh as mesh_mod

    table = pa.Table.from_batches([_rows(1024, seed=41 + i)
                                   for i in range(4)])

    def run() -> pa.Table:
        conf = cfg.get_config()
        _missing = object()
        saved = conf._overrides.get(cfg.MESH_ENABLED, _missing)
        conf.set(cfg.MESH_ENABLED, True)
        try:
            # <2 devices is still a valid run — the exchange routes
            # device_buffer and records why; the battery contract
            # (identical-or-classified) holds on either route
            _ = mesh_mod.current_plane()
            s = Session()
            df = (s.from_arrow(table)
                  .repartition(4, "k")
                  .filter(col("c") > 50)
                  .group_by("k")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count(col("c")).alias("n")))
            return _canonical(s.execute(df))
        finally:
            if saved is _missing:
                conf.unset(cfg.MESH_ENABLED)
            else:
                conf.set(cfg.MESH_ENABLED, saved)

    return Scenario("mesh_pipeline", run, [])


def lifecycle_pipeline(workdir: str) -> Scenario:
    """Chaos 2.0 lifecycle scenario: a Session-planned sort+agg under a
    tiny memory budget so spills/memmgr traffic is guaranteed, run with
    a short stall watchdog and the 'shed' pressure policy. Gives the
    lifecycle sites deterministic traffic: ``cancel.race`` fires the
    query's CancelToken mid-drive (→ QueryCancelled), ``task.hang``
    goes silent past the stall timeout (→ TaskStalled, retried once),
    ``memmgr.deny`` forces the degradation ladder to the shed rung
    (→ MemoryExhausted). Every outcome must be identical-or-classified
    with a clean resource ledger (no spill files, no registered
    consumers) — audited per run via ``extra_audit``."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager

    spill_dir = os.path.join(workdir, "spill")
    os.makedirs(spill_dir, exist_ok=True)
    # several record batches: every one is a checkpoint event in the
    # sort/drive loops, so the seeded cancel.race/task.hang Bernoulli
    # sequences see real traffic
    table = pa.Table.from_batches([_rows(512, seed=31 + i)
                                   for i in range(8)])
    last: dict = {}

    # stall timeout sized ABOVE this mesh's worst single-program compile
    # (the monitor credits completed compiles, but one compile longer
    # than the timeout would still flag); hang_s above the timeout so an
    # injected hang reliably trips the stall verdict
    _KNOBS = {cfg.WATCHDOG_STALL_TIMEOUT_S: 1.5,
              cfg.FAULTS_HANG_S: 4.0,
              cfg.MEMMGR_PRESSURE_POLICY: "shed"}

    def run() -> pa.Table:
        conf = cfg.get_config()
        _missing = object()
        saved = {k: conf._overrides.get(k, _missing) for k in _KNOBS}
        for k, v in _KNOBS.items():
            conf.set(k, v)
        mm = MemManager(
            total_bytes=1 << 22, min_trigger=0,
            spill_manager=SpillManager(host_budget_bytes=1,
                                       spill_dir=spill_dir))
        last["mm"] = mm
        s = Session(mem_manager=mm)
        try:
            df = (s.from_arrow(table)
                  .sort("k")
                  .group_by("k")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count(col("c")).alias("n")))
            return _canonical(s.execute(df))
        finally:
            s.close()
            for k, prev in saved.items():
                if prev is _missing:
                    conf.unset(k)
                else:
                    conf.set(k, prev)

    sc = Scenario("lifecycle_pipeline", run,
                  [os.path.join(spill_dir, "auron-spill-*")])

    def extra_audit() -> list[str]:
        mm = last.get("mm")
        if mm is None:
            return []
        gc.collect()
        found = [f"memmgr-consumer:{name}"
                 for name in mm.status()["consumers"]]
        live = mm.spill_manager.live_disk_files() \
            if mm.spill_manager is not None else 0
        if live:
            found.append(f"tracked-spill-files:{live}")
        return found

    sc.extra_audit = extra_audit
    return sc


def overload(workdir: str) -> Scenario:
    """Concurrency chaos: THREE identical Session-planned aggregations
    race through ONE Session whose scheduler is clamped tight
    (max_concurrent=1, queue_depth=1) over a small memory budget under
    the 'shed' pressure policy — the 2x-overload posture. Gives the
    admission/arbitration sites deterministic traffic: ``sched.admit``
    denies shed queries at the door (→ AdmissionRejected, transient),
    ``memmgr.deny`` forces the pressure ladder mid-flight (→
    MemoryExhausted). The contract: every per-query outcome is a table
    bit-identical to the fault-free result OR a classified AuronError —
    never an unclassified crash, never divergent successful results,
    never a leaked consumer/spill file. One query runs on the CALLING
    thread so its admission/shed spans land inside the chaos trace and
    correlate."""
    import threading

    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager

    spill_dir = os.path.join(workdir, "spill")
    os.makedirs(spill_dir, exist_ok=True)
    table = pa.Table.from_batches([_rows(768, seed=47 + i)
                                   for i in range(4)])
    last: dict = {}

    _KNOBS = {cfg.SCHED_MAX_CONCURRENT: 1,
              cfg.SCHED_QUEUE_DEPTH: 1,
              cfg.MEMMGR_PRESSURE_POLICY: "shed"}

    def run() -> pa.Table:
        conf = cfg.get_config()
        _missing = object()
        saved = {k: conf._overrides.get(k, _missing) for k in _KNOBS}
        for k, v in _KNOBS.items():
            conf.set(k, v)
        mm = MemManager(
            total_bytes=1 << 22, min_trigger=0,
            spill_manager=SpillManager(host_budget_bytes=1,
                                       spill_dir=spill_dir))
        last["mm"] = mm
        s = Session(mem_manager=mm)

        def query() -> pa.Table:
            df = (s.from_arrow(table)
                  .sort("k")
                  .group_by("k")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count(col("c")).alias("n")))
            return _canonical(s.execute(df))

        outcomes: list = [None, None, None]

        def worker(i: int) -> None:
            try:
                outcomes[i] = ("ok", query())
            except BaseException as e:   # noqa: BLE001 — audited below
                outcomes[i] = ("err", e)

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in (1, 2)]
        try:
            for t in threads:
                t.start()
            # slot 0 runs on the CALLING thread: its scheduler/memmgr
            # events join the chaos trace scope for correlation
            worker(0)
            for t in threads:
                t.join(timeout=60)
                if t.is_alive():
                    raise RuntimeError("overload worker wedged")
        finally:
            s.close()
            for k, prev in saved.items():
                if prev is _missing:
                    conf.unset(k)
                else:
                    conf.set(k, prev)

        tables = [o[1] for o in outcomes if o and o[0] == "ok"]
        failures = [o[1] for o in outcomes if o and o[0] == "err"]
        for e in failures:
            if not isinstance(e, errors.AuronError):
                raise e     # unclassified: the contract's failure bucket
        for t in tables[1:]:
            if not t.equals(tables[0]):
                raise AssertionError(
                    "concurrent overload queries diverged: identical "
                    "queries produced different tables")
        if not tables:
            raise failures[0]   # everything shed: classified, auditable
        return tables[0]

    sc = Scenario("overload", run,
                  [os.path.join(spill_dir, "auron-spill-*")])

    def extra_audit() -> list[str]:
        mm = last.get("mm")
        if mm is None:
            return []
        gc.collect()
        found = [f"memmgr-consumer:{name}"
                 for name in mm.status()["consumers"]]
        live = mm.spill_manager.live_disk_files() \
            if mm.spill_manager is not None else 0
        if live:
            found.append(f"tracked-spill-files:{live}")
        return found

    sc.extra_audit = extra_audit
    return sc


def journal_pipeline(workdir: str) -> Scenario:
    """Crash-safe-journal chaos scenario: the agg_pipeline shape run
    with ``auron.journal.dir`` armed, so the ``journal.write`` /
    ``journal.commit`` sites see real traffic on the append/fsync path.
    The journal's contract under faults is DEGRADE, NEVER FAIL: an
    injected io_error/fatal on either site disables journaling for that
    query (a ``journal.disable`` event on the timeline) and the query
    itself completes bit-identical — resumability is lost, rows are
    not. The leak audit covers the journal dir: whatever the fault did,
    a completed query leaves no ``*.journal`` file behind."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session

    jdir = os.path.join(workdir, "journal")
    table = pa.Table.from_batches([_rows(2048, seed=61 + i)
                                   for i in range(2)])

    def run() -> pa.Table:
        conf = cfg.get_config()
        _missing = object()
        saved = conf._overrides.get(cfg.JOURNAL_DIR, _missing)
        conf.set(cfg.JOURNAL_DIR, jdir)
        s = None
        try:
            s = Session()
            df = (s.from_arrow(table)
                  .repartition(2, "k")
                  .filter(col("c") > 50)
                  .group_by("k")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count(col("c")).alias("n")))
            return _canonical(s.execute(df))
        finally:
            # close on EVERY path: a classified failure suspends its
            # journal, and in-process a journal never outlives its
            # Session (cross-process survival is the crash case — this
            # scenario's audit treats a leftover as a leak)
            if s is not None:
                s.close()
            if saved is _missing:
                conf.unset(cfg.JOURNAL_DIR)
            else:
                conf.set(cfg.JOURNAL_DIR, saved)

    return Scenario("journal_pipeline", run,
                    [os.path.join(jdir, "*.journal"),
                     os.path.join(jdir, "**", "*.part")])


def fleet_failover(workdir: str) -> Scenario:
    """Serving-fleet chaos scenario (ISSUE 19): TWO real AuronServer
    subprocesses behind an in-process ``FleetRouter``, one SIGKILLed
    mid-query on EVERY run — the router must fail the in-flight query
    over to the survivor (journal RESUME when committed shuffle state
    exists, guarded re-execution otherwise) and hand the client a table
    bit-identical to the fault-free answer. The seeded plans put faults
    on the router's OWN sites: ``fleet.route`` (the admission/routing
    step) and ``fleet.forward`` (the replica leg of a forwarded query),
    which must surface as spill-over retries, a failover, or a
    classified verdict — never an unclassified crash, never wrong rows.
    ``extra_audit`` force-sweeps every run's shared journal dir after
    teardown: no journal / ``.part`` / ``.claim`` / RSS artifact may
    survive a completed run (a resumed query deletes its journal; a
    torn dead-owner journal is reclaimed by the sweep)."""
    import pyarrow.parquet as pq

    journal_root = os.path.join(workdir, "journal")
    data_path = os.path.join(workdir, "fleet.parquet")
    counter = [0]
    task_box: dict = {}

    def _task() -> bytes:
        if "task" not in task_box:
            from auron_tpu.ir import pb
            rng = np.random.default_rng(19)
            n = 600_000   # ~0.7s of drive time: wide kill window
            os.makedirs(workdir, exist_ok=True)
            pq.write_table(pa.table({
                "k": pa.array(rng.integers(0, 64, n), pa.int64()),
                "v": pa.array(rng.normal(size=n), pa.float64())}),
                data_path)
            col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
            plan = pb.PlanNode(agg=pb.AggNode(
                child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(
                    files=[data_path])),
                mode="complete", group_exprs=[col(0)],
                aggs=[pb.AggFunctionP(fn="sum", arg=col(1)),
                      pb.AggFunctionP(fn="count", arg=col(1))]))
            task_box["task"] = pb.TaskDefinition(
                plan=plan, task_id=1).SerializeToString()
        return task_box["task"]

    def run() -> pa.Table:
        import threading

        from auron_tpu.fleet.replica import FleetHarness
        from auron_tpu.utils.envsafe import require_shareable_device

        require_shareable_device("the fleet_failover scenario")
        task = _task()
        counter[0] += 1
        jdir = os.path.join(journal_root, f"run_{counter[0]}")
        os.makedirs(jdir, exist_ok=True)
        with FleetHarness(2, journal_dir=jdir) as h:
            # warm pass: pays the one-off compile so the measured kill
            # below lands mid-DATA, not mid-compile (an injected
            # fleet.* fault here already classifies the run — fine)
            warm, _ = h.client(timeout_s=120).execute(task)
            box: dict = {}

            def drive() -> None:
                try:
                    tbl, _ = h.client(timeout_s=120).execute(task)
                    box["table"] = tbl
                except BaseException as e:   # noqa: BLE001 — audited below
                    box["err"] = e

            t = threading.Thread(target=drive, daemon=True)
            t.start()
            # SIGKILL whichever replica picks the query up, mid-flight
            victim = None
            deadline = _time.monotonic() + 10.0
            while victim is None and t.is_alive() \
                    and _time.monotonic() < deadline:
                h.router._poll_once()
                for i in range(len(h.replicas)):
                    snap = h.router._replicas[i].snapshot
                    if snap is not None and snap.occupancy > 0:
                        victim = i
                        break
                if victim is None:
                    _time.sleep(0.05)
            if victim is not None and h.replicas[victim].alive():
                h.kill_replica(victim)
            t.join(timeout=120)
            if t.is_alive():
                raise RuntimeError("fleet_failover run wedged: the "
                                   "killed query never completed or "
                                   "classified")
            if "err" in box:
                raise box["err"]
            out = box["table"]
            if not out.equals(warm):
                raise AssertionError(
                    "fleet failover diverged: the failed-over query's "
                    "table differs from the same fleet's warm pass")
        return _canonical(out)

    sc = Scenario("fleet_failover", run, [])

    def extra_audit() -> list[str]:
        from auron_tpu.runtime import journal as jrn
        found: list[str] = []
        for d in sorted(glob.glob(os.path.join(journal_root, "run_*"))):
            try:
                jrn.sweep_orphans(d, force=True)
            except OSError:
                pass   # audit still reports the raw globs below
            found += glob.glob(os.path.join(d, "*.journal"))
            found += glob.glob(os.path.join(d, "*.claim"))
            found += glob.glob(os.path.join(d, "**", "*.part"),
                               recursive=True)
            found += [p for p in glob.glob(os.path.join(d, "rss", "*"))
                      if os.path.isdir(p)]
        return found

    sc.extra_audit = extra_audit
    return sc


SCENARIOS: dict[str, Callable[[str], Scenario]] = {
    "rss_pipeline": rss_pipeline,
    "spill_sort": spill_sort,
    "agg_pipeline": agg_pipeline,
    "mesh_pipeline": mesh_pipeline,
    "lifecycle_pipeline": lifecycle_pipeline,
    "overload": overload,
    "journal_pipeline": journal_pipeline,
    "fleet_failover": fleet_failover,
}


def run_chaos(scenario: Scenario, fault_plan: str, seed: int,
              with_trace: bool = True) -> ChaosOutcome:
    """One chaos run: arm the plan at ``seed``, execute a fresh pipeline,
    classify the outcome against the fault-free baseline, audit leaks.
    The global fault config is restored (and the plane reset) whatever
    happens.

    ``with_trace`` (default) records the run under its own trace id
    (obs/trace) and attaches the site→recovery-span correlation, so a
    chaos report links every injected fault to the recovery it
    triggered."""
    from auron_tpu.obs import bundle as _bundle
    from auron_tpu.obs import trace
    baseline = scenario.baseline()
    conf = cfg.get_config()
    # post-mortem correlation (auron.bundle.enabled armed by the
    # caller): snapshot the bundle inventory so this run's new bundles
    # — and ONLY this run's — are audited against its injections
    bundle_root = (_bundle.bundle_dir(conf)
                   if conf.get(cfg.BUNDLE_ENABLED) else None)
    bundles_before = (set(_bundle.list_bundles(bundle_root))
                      if bundle_root else set())
    conf.set(cfg.FAULTS_PLAN, fault_plan)
    conf.set(cfg.FAULTS_SEED, seed)
    _missing = object()
    saved_trace = {}
    if with_trace:
        # save-and-restore, not unset: a caller's own session override
        # (debugging with tracing armed) must survive the chaos run
        for key in (cfg.TRACE_ENABLED, cfg.TRACE_DIR, cfg.TRACE_EVENTS):
            saved_trace[key] = conf._overrides.get(key, _missing)
        conf.set(cfg.TRACE_ENABLED, True)
        # keep spans in memory and every category recording: an ambient
        # auron.trace.dir (CI env var) would make the query scope
        # export-and-DROP the trace before correlate_spans below ever
        # sees it, and an ambient auron.trace.events allowlist would
        # filter out the fault/recovery events the correlation reads
        conf.set(cfg.TRACE_DIR, "")
        conf.set(cfg.TRACE_EVENTS, "")
    faults.reset()
    injected: dict = {}
    trace_id = 0
    correlation: dict = {}
    try:
        scope = trace.query_scope(label=f"chaos:{scenario.name}") \
            if with_trace else None
        try:
            if scope is not None:
                scope.__enter__()
                trace_id = scope.trace_id
            out = scenario.run()
        finally:
            if scope is not None:
                # real exc_info, not Nones: the root span's error
                # attribute is what makes a failed chaos trace
                # self-explaining in trace_report
                scope.__exit__(*sys.exc_info())
            injected = faults.snapshot()
        status = "identical" if out.equals(baseline) else "mismatch"
        err_t = err = None
        bundle_tag = None
    except errors.AuronError as e:
        status, err_t, err = "classified", type(e).__name__, str(e)
        bundle_tag = _bundle.classify(e)
    except Exception as e:   # noqa: BLE001 — the contract's failure bucket
        status, err_t, err = "unclassified", type(e).__name__, str(e)
        bundle_tag = None
    finally:
        if with_trace:
            correlation = correlate_spans(
                trace.tracer().spans(trace_id or None))
            for key, prev in saved_trace.items():
                if prev is _missing:
                    conf.unset(key)
                else:
                    conf.set(key, prev)
            # drop only THIS run's spans: a caller's own in-progress
            # trace (the debugging scenario the save/restore above
            # protects) must survive — a global reset would wipe it
            if trace_id:
                trace.tracer().drop(trace_id)
        conf.unset(cfg.FAULTS_PLAN)
        conf.unset(cfg.FAULTS_SEED)
        faults.reset()
        # a device quarantined by THIS run's injected loss must not
        # silently reroute the next run's exchanges (each chaos run is
        # a fresh pipeline by contract; the quarantine ledger still
        # counted it for the report)
        from auron_tpu.parallel import mesh as _mesh
        _mesh.clear_quarantine()
    new_bundles = ([p for p in _bundle.list_bundles(bundle_root)
                    if p not in bundles_before] if bundle_root else [])
    bundle_leaks = (_audit_bundles(bundle_root, new_bundles, bundle_tag,
                                   err_t, injected, seed, conf)
                    if bundle_root else [])
    return ChaosOutcome(scenario.name, fault_plan, seed, status,
                        error_type=err_t, error=err, injected=injected,
                        leaks=scenario.leaks() + bundle_leaks,
                        trace_id=trace_id,
                        correlation=correlation, bundles=new_bundles)


def _audit_bundles(root: str, new_bundles: list, bundle_tag,
                   err_t, injected: dict, seed: int, conf) -> list[str]:
    """Bundle half of the chaos leak audit (ISSUE 14): a run whose
    terminal error is bundle-eligible must have produced EXACTLY ONE
    bundle for it, that bundle's flight dump must contain the injected
    fault's ``fault.injected`` event (site + seed match — the
    post-mortem provably shows the cause), and the retention cap
    (auron.bundle.max_bundles, oldest-first) must hold so bundles can
    never become the leak they exist to explain. Findings are leak
    strings — they fail the run through ``ChaosOutcome.ok``."""
    from auron_tpu.obs import bundle as _bundle
    from auron_tpu.obs import flight_recorder as _flight
    probs: list[str] = []
    if bundle_tag is not None:
        matching = []
        for p in new_bundles:
            try:
                mf = _bundle.read_manifest(p)
            except Exception as e:   # noqa: BLE001 — audit verdict
                probs.append(f"bundle-unreadable:{p}:{e}")
                continue
            if mf.get("error_type") == err_t:
                matching.append(p)
        if len(matching) != 1:
            probs.append(
                f"bundle-count:{len(matching)} for {err_t} "
                f"(expected exactly 1; new={new_bundles})")
        for p in matching:
            if not injected:
                continue   # classified by knobs, not by an injection
            try:
                events = _flight.read_jsonl(
                    os.path.join(p, "flight.jsonl"))
            except Exception as e:   # noqa: BLE001 — audit verdict
                probs.append(f"bundle-flight-unreadable:{p}:{e}")
                continue
            hit = any(
                ev.get("name") == "fault.injected"
                and ev.get("attrs", {}).get("site") in injected
                and ev.get("attrs", {}).get("seed") == seed
                for ev in events)
            if not hit:
                probs.append(
                    f"bundle-flight-missing-fault:{p} "
                    f"(sites={sorted(injected)}, seed={seed})")
    keep = int(conf.get(cfg.BUNDLE_MAX_BUNDLES))
    total = len(_bundle.list_bundles(root))
    if keep > 0 and total > keep:
        probs.append(f"bundle-retention:{total} bundles > "
                     f"max_bundles={keep}")
    return probs


# ---------------------------------------------------------------------------
# crash scenario: subprocess SIGKILL at every journal stage boundary
# ---------------------------------------------------------------------------
#
# The one failure mode no in-process chaos run can exercise: the Python
# process DIES (SIGKILL — no unwind, no finally, no atexit). A child
# process runs a two-exchange query with the crash-safe journal armed
# (runtime/journal.py) and kills itself at the k-th journal event (map
# commit record / shuffle commit record — the stage boundaries); the
# parent then resumes from the journal and audits the full contract:
#
#   - resumed result BIT-IDENTICAL to a fresh run (group order included)
#   - the child's uncommitted ``.part`` files, orphaned spill files and
#     journal artifacts are reclaimed by the startup sweeps
#   - nothing unclassified anywhere
#
# ``run_crash_sweep`` sweeps EVERY kill point (1..events+1 — the +1 run
# outlives all boundaries and completes in the child, proving the
# no-kill control path); ``tests/test_zz_crash_battery.py`` asserts a
# fast subset tier-1 and the full sweep under ``slow``.

CRASH_SCALE = 0.25          # ~30k fact rows: multi-batch, fast children


@dataclass
class CrashOutcome:
    """One (kill point → resume) cycle's audited outcome."""
    kill_point: int
    #: child exit: -9 = SIGKILLed at the boundary, 0 = ran past every
    #: boundary and completed (the control run)
    child_rc: int
    #: identical | classified | completed | mismatch | unclassified
    status: str
    error_type: Optional[str] = None
    error: Optional[str] = None
    maps_skipped: int = 0
    maps_recomputed: int = 0
    bytes_reused: int = 0
    resume_wall_s: float = 0.0
    #: leftover .part / spill / journal artifacts after the sweeps +
    #: resume (must be empty)
    leaks: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.status in ("identical", "classified", "completed")
                and not self.leaks)


def crash_query(session, files: list):
    """The sweep's TWO-EXCHANGE TPC-DS-shaped query (hash repartition →
    two-phase agg), shared verbatim by the crashing child and the
    parent's fresh baseline so the bit-identity comparison is about the
    JOURNAL, not the plan."""
    from auron_tpu.frontend.dataframe import col, functions as F
    return (session.read_parquet(files, partitions=3)
            .repartition(3, "ss_store_sk")
            .filter(col("ss_quantity") > 5)
            .group_by("ss_store_sk")
            .agg(F.sum(col("ss_sales_price")).alias("total"),
                 F.count(col("ss_net_paid")).alias("paid_cnt")))


def _crash_workdir_init(workdir: str) -> list:
    """Generate the sweep's dataset once under ``workdir`` and persist
    the file manifest the child re-reads. Returns the fact files."""
    import json as _json
    from auron_tpu.it.tpcds_data import generate as gen_data
    manifest = os.path.join(workdir, "manifest.json")
    if not os.path.exists(manifest):
        tables = gen_data(os.path.join(workdir, "data"),
                          scale=CRASH_SCALE)
        with open(manifest, "w") as f:
            _json.dump({"store_sales": tables["store_sales"]}, f)
    import json as _json2
    with open(manifest) as f:
        return _json2.load(f)["store_sales"]


def _crash_child_main(workdir: str, kill_at: int) -> int:
    """Child half of the crash harness: run ``crash_query`` with the
    journal armed and SIGKILL OURSELVES the moment the ``kill_at``-th
    journal boundary event (map record / shuffle commit) returns — no
    unwind, no cleanup, exactly an OOM-kill. ``kill_at <= 0`` disables
    the kill (the event-count probe / completion control): the child
    then writes its result table to ``result.arrow`` and prints one
    JSON line ``{"completed": true, "events": N}``."""
    import json as _json
    import signal

    from auron_tpu import config as _cfg
    from auron_tpu.frontend.session import Session
    from auron_tpu.memmgr import spill as spill_mod
    from auron_tpu.runtime import journal as jrn

    conf = _cfg.get_config()
    conf.set(_cfg.JOURNAL_DIR, os.path.join(workdir, "journal"))
    # a real crashed engine leaves spill files too: drop one carrying
    # THIS process's pid.epoch owner token so the parent can prove the
    # spill startup sweep reclaims a dead writer's artifact
    spill_dir = os.path.join(workdir, "spill")
    os.makedirs(spill_dir, exist_ok=True)
    if kill_at > 0:
        with open(os.path.join(
                spill_dir,
                f"auron-spill-{spill_mod._owner_token()}-0-crash.atb"),
                "wb") as f:
            f.write(b"orphan")

    counter = [0]
    orig_map = jrn.QueryJournal.record_map
    orig_commit = jrn.QueryJournal.record_shuffle_commit

    def _boundary() -> None:
        counter[0] += 1
        if counter[0] == kill_at:
            os.kill(os.getpid(), signal.SIGKILL)

    def record_map(self, *a, **kw):
        orig_map(self, *a, **kw)
        _boundary()

    def record_shuffle_commit(self, *a, **kw):
        orig_commit(self, *a, **kw)
        _boundary()

    jrn.QueryJournal.record_map = record_map
    jrn.QueryJournal.record_shuffle_commit = record_shuffle_commit

    files = _crash_workdir_init(workdir)
    s = Session()
    table = s.execute(crash_query(s, files))
    s.close()
    import pyarrow.feather as feather
    feather.write_feather(table, os.path.join(workdir, "result.arrow"),
                          compression="uncompressed")
    print(_json.dumps({"completed": True, "events": counter[0],
                       "rows": table.num_rows}))
    return 0


def _spawn_crash_child(workdir: str, kill_at: int,
                       timeout_s: float = 240.0):
    """Run one crash child; returns (rc, stdout)."""
    import subprocess

    from auron_tpu.utils.envsafe import require_shareable_device
    require_shareable_device("the crash sweep")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    # the child inherits the parent's environment: its platform, and
    # the one persistent XLA cache every process of a checkout shares
    # (utils/xla_cache.py) — only the first child pays the compile bill
    proc = subprocess.run(
        [sys.executable, "-m", "auron_tpu.it.chaos", "--crash-child",
         workdir, str(kill_at)],
        capture_output=True, text=True, timeout=timeout_s, cwd=repo)
    return proc.returncode, proc.stdout


def crash_probe(workdir: str) -> int:
    """Count the query's journal boundary events (one no-kill child
    run): the sweep's kill points are 1..events."""
    import json as _json
    _crash_workdir_init(workdir)
    rc, out = _spawn_crash_child(workdir, 0)
    if rc != 0:
        raise RuntimeError(f"crash probe child failed rc={rc}: "
                           f"{out[-500:]}")
    return int(_json.loads(out.strip().splitlines()[-1])["events"])


def crash_baseline(workdir: str) -> pa.Table:
    """The parent's fresh, journal-free reference result."""
    from auron_tpu.frontend.session import Session
    files = _crash_workdir_init(workdir)
    s = Session()
    try:
        return s.execute(crash_query(s, files))
    finally:
        s.close()


def run_crash_point(workdir: str, kill_point: int,
                    baseline: Optional[pa.Table] = None) -> CrashOutcome:
    """One full crash cycle: fresh journal/spill dirs for this kill
    point, child SIGKILLed at the boundary, parent startup sweeps
    asserted (spill + RSS tiers), ``Session.resume`` of the journaled
    query, bit-identity vs the fresh baseline, orphan audit."""
    import shutil
    import time

    from auron_tpu.frontend.session import Session
    from auron_tpu.memmgr.spill import SpillManager
    from auron_tpu.runtime import journal as jrn

    if baseline is None:
        baseline = crash_baseline(workdir)
    point_dir = os.path.join(workdir, f"k{kill_point}")
    # each kill point gets fresh journal/spill dirs under the shared
    # data/workdir (the once-per-process sweep memos key on the dir)
    shutil.rmtree(point_dir, ignore_errors=True)
    os.makedirs(point_dir, exist_ok=True)
    for sub in ("journal", "spill"):
        os.makedirs(os.path.join(point_dir, sub), exist_ok=True)
    # the child resolves journal/spill under ITS workdir: symlink the
    # shared data/manifest into the per-point dir
    for shared in ("data", "manifest.json"):
        src = os.path.join(workdir, shared)
        if os.path.exists(src):
            os.symlink(src, os.path.join(point_dir, shared))

    rc, out = _spawn_crash_child(point_dir, kill_point)
    jdir = os.path.join(point_dir, "journal")
    spill_dir = os.path.join(point_dir, "spill")

    # -- startup sweeps (the satellite assertions) ------------------------
    # spill tier: constructing a SpillManager over the dead child's dir
    # IS the startup sweep; the child's crash marker (its own pid.epoch
    # in the filename, its process now provably dead) must be gone
    SpillManager(host_budget_bytes=1, spill_dir=spill_dir)
    leftover_spill = [p for p in glob.glob(
        os.path.join(spill_dir, "auron-spill-*"))]
    if leftover_spill:
        return CrashOutcome(
            kill_point, rc, "unclassified",
            error_type="SpillSweepFailed",
            error=f"spill startup sweep left {leftover_spill}",
            leaks=leftover_spill)

    if rc == 0:
        # the kill point lies past the last boundary: the child ran to
        # completion — its journal must be gone and its result must
        # match the baseline (read back from result.arrow)
        import pyarrow.feather as feather
        table = feather.read_table(
            os.path.join(point_dir, "result.arrow"))
        status = ("completed" if table.equals(baseline) else "mismatch")
        return CrashOutcome(kill_point, rc, status,
                            leaks=_crash_leaks(jdir, spill_dir))

    outcome = CrashOutcome(kill_point, rc, "unclassified")

    # -- resume -----------------------------------------------------------
    stems = [os.path.splitext(os.path.basename(p))[0]
             for p in glob.glob(os.path.join(jdir, "*.journal"))]
    if len(stems) != 1:
        outcome.error_type = "JournalInventory"
        outcome.error = (f"expected exactly one journal after the "
                         f"crash, found {stems}")
        return outcome
    conf = cfg.get_config()
    _missing = object()
    saved = conf._overrides.get(cfg.JOURNAL_DIR, _missing)
    conf.set(cfg.JOURNAL_DIR, jdir)
    try:
        s = Session()
        t0 = time.perf_counter()
        try:
            table = s.resume(stems[0])
            outcome.resume_wall_s = time.perf_counter() - t0
            stats = jrn.last_stats()
            outcome.maps_skipped = stats.get("maps_skipped", 0)
            outcome.maps_recomputed = stats.get("maps_recomputed", 0)
            outcome.bytes_reused = stats.get("bytes_reused", 0)
            outcome.status = ("identical" if table.equals(baseline)
                              else "mismatch")
        except errors.AuronError as e:
            outcome.status = "classified"
            outcome.error_type = type(e).__name__
            outcome.error = str(e)
        except Exception as e:   # noqa: BLE001 — the failure bucket
            outcome.error_type = type(e).__name__
            outcome.error = str(e)
        finally:
            s.close()
    finally:
        if saved is _missing:
            conf.unset(cfg.JOURNAL_DIR)
        else:
            conf.set(cfg.JOURNAL_DIR, saved)
    outcome.leaks = _crash_leaks(jdir, spill_dir)
    return outcome


def _crash_leaks(jdir: str, spill_dir: str) -> list:
    """Orphan audit after one crash cycle: no ``.part`` anywhere under
    the journal root, no journal files, no RSS run dirs, no spill
    files. ``report_*.json`` is a deliberate artifact (the
    tools/journal_report.py input), not a leak."""
    gc.collect()
    found = glob.glob(os.path.join(jdir, "**", "*.part"), recursive=True)
    found += glob.glob(os.path.join(jdir, "*.journal"))
    found += glob.glob(os.path.join(jdir, "*.claim"))
    found += [d for d in glob.glob(os.path.join(jdir, "rss", "*"))
              if os.path.isdir(d)]
    found += glob.glob(os.path.join(spill_dir, "auron-spill-*"))
    return found


def run_crash_sweep(workdir: Optional[str] = None,
                    kill_points: Optional[list] = None) -> list:
    """Sweep every journal boundary of the two-exchange crash query:
    kill points 1..events (each child dies AT that boundary) plus
    events+1 (the child outlives every boundary and completes). Returns
    the list of ``CrashOutcome``; the contract is ``all(o.ok)``."""
    import tempfile

    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="auron_crash_")
    try:
        events = crash_probe(workdir)
        baseline = crash_baseline(workdir)
        points = kill_points or list(range(1, events + 2))
        return [run_crash_point(workdir, k, baseline) for k in points]
    finally:
        if own:
            import shutil
            shutil.rmtree(workdir, ignore_errors=True)


def _main(argv) -> int:
    if len(argv) >= 3 and argv[0] == "--crash-child":
        return _crash_child_main(argv[1], int(argv[2]))
    raise SystemExit(
        "usage: python -m auron_tpu.it.chaos --crash-child "
        "<workdir> <kill_at>")


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
