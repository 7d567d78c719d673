"""Serving-fleet battery: real subprocess replicas behind the router.

The contract under test (auron_tpu/fleet/): a fleet of N AuronServer
PROCESSES behind one FleetRouter serves a concurrent burst with one
replica SIGKILLed mid-flight such that every request completes or
classifies (a structured AdmissionRejected — never an unclassified
error), every successful result is bit-identical to an uninterrupted
run, and the shared journal dir is clean after the dead-owner sweep.

Also here: the mesh-aware resume satellite — a journal written by an
8-device mesh process must resume onto a NARROWER plane (widths 1 and
4) bit-identical, with the planner routing each remaining exchange by
the CURRENT ``exchange_route`` verdict while exchanges that already
hold committed journal state re-plan onto the RSS tier where that
state lives.

Fast subset tier-1; the 3-replica burst and the width sweep's second
width run under ``slow`` (tools/load_report.py --fleet prints the same
acceptance table).
"""

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.fleet import FleetHarness
from auron_tpu.parallel import mesh as mesh_mod
from auron_tpu.runtime import journal as jrn

import tools.load_report as lr

# each replica throttled to one running + one queued query: admission
# capacity — the thing replication buys — is the axis under test
_THROTTLE = {"AURON_CONF_SCHED_MAX_CONCURRENT": "1",
             "AURON_CONF_SCHED_QUEUE_DEPTH": "1"}


@pytest.fixture(scope="module")
def workdir():
    d = tempfile.mkdtemp(prefix="auron_fleet_battery_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


@pytest.fixture(scope="module")
def task_and_data(workdir):
    path = lr._dataset(workdir, 120_000)
    return lr._task_bytes(path)


def _journal_leftovers(jdir):
    """Orphan audit of one shared journal dir AFTER the dead-owner
    sweep: anything still there is a dropped query or a torn artifact."""
    jrn.sweep_orphans(jdir, force=True)
    found = glob.glob(os.path.join(jdir, "*.journal"))
    found += glob.glob(os.path.join(jdir, "*.claim"))
    found += glob.glob(os.path.join(jdir, "**", "*.part"), recursive=True)
    found += [d for d in glob.glob(os.path.join(jdir, "rss", "*"))
              if os.path.isdir(d)]
    return found


class TestFleetEndToEnd:
    def test_round_trip_and_router_stats(self, workdir, task_and_data):
        """Two replicas, one client: the router looks exactly like one
        AuronServer on the wire (plain AuronClient, no fleet awareness)
        and its STATS frame exposes the routing ledger."""
        jdir = os.path.join(workdir, "journal_rt")
        with FleetHarness(2, journal_dir=jdir,
                          env_extra=_THROTTLE) as h:
            client = h.client(timeout_s=120)
            t1, _ = client.execute(task_and_data)
            t2, _ = client.execute(task_and_data)
            assert t1.equals(t2)
            stats = client.stats()
            assert stats["router"]["routed"] == 2
            assert stats["router"]["replica_deaths"] == 0
            assert len(stats["replicas"]) == 2
            hello = client.hello()
            assert hello["role"] == "router"
            assert len(hello["replicas"]) == 2
        assert _journal_leftovers(jdir) == []

    def test_kill_one_mid_burst_completes_or_classifies(
            self, workdir, task_and_data):
        """The acceptance shape at tier-1 scale: a 2-replica fleet,
        4 simultaneous clients, one replica SIGKILLed mid-burst.
        Every request must end ok-or-rejected (zero unclassified
        errors, zero wedged clients), every ok table bit-identical to
        the warm pass, exactly one confirmed death, and the shared
        journal clean after the sweep."""
        jdir = os.path.join(workdir, "journal_kill")
        with FleetHarness(2, journal_dir=jdir,
                          env_extra=_THROTTLE) as h:
            warm, _ = h.client(timeout_s=120).execute(task_and_data)
            outcomes, _wall, tables, wedged, errs = lr._fleet_burst(
                h, task_and_data, clients=4, requests=1,
                kill_index=0, kill_after_s=0.3)
            stats = h.router.stats_dict()
        assert wedged == 0
        assert errs == [], errs
        kinds = sorted(k for k, _ in outcomes)
        assert len(kinds) == 4
        assert all(k in ("ok", "rejected") for k in kinds), kinds
        assert kinds.count("ok") >= 1
        assert all(t.equals(warm) for t in tables)
        r = stats["router"]
        assert r["replica_deaths"] == 1
        assert _journal_leftovers(jdir) == []

    @pytest.mark.slow
    def test_three_replica_burst_scales_admission(self):
        """The full acceptance run (tools/load_report.py --fleet 3):
        zero unclassified errors in both bursts, bit-identical
        successes with one replica SIGKILLed mid-burst, aggregate
        admitted throughput >= 2.5x one replica, clean ledgers."""
        # the report's own fleet-mode defaults: 4xN clients, one
        # simultaneous round, queries long enough (3M rows) that
        # admission capacity — not burst stagger — decides outcomes
        rec = lr.run_fleet(3, clients=12, requests=1, rows=3_000_000)
        assert rec["one"]["error"] == 0, rec["error_samples"]
        assert rec["fleet"]["error"] == 0, rec["error_samples"]
        assert rec["one"]["wedged"] == 0
        assert rec["fleet"]["wedged"] == 0
        assert rec["bit_identical"] is True
        assert rec["admitted_scale_x"] >= 2.5, rec
        assert rec["failover"]["deaths"] == 1
        assert rec["journal_orphans"] == []


# ---------------------------------------------------------------------------
# mesh-aware resume: planner routing unit tier
# ---------------------------------------------------------------------------

class _FakeJournal:
    """The planner's journal surface: id sequencing + the route oracle."""

    def __init__(self, rss_root, committed=()):
        self.rss_root = rss_root
        self._committed = set(committed)
        self._next = 0
        self.recorded = []

    def next_shuffle_id(self):
        sid = self._next
        self._next += 1
        return sid

    def has_shuffle_state(self, sid):
        return sid in self._committed

    def record_exchange(self, *a):
        self.recorded.append(a)


@pytest.fixture
def mesh_plane():
    conf = cfg.get_config()
    conf.set(cfg.MESH_ENABLED, True)
    mesh_mod.reset_plane()
    try:
        plane = mesh_mod.current_plane()
        if plane is None:
            pytest.skip("no multi-device plane on this host")
        yield plane
    finally:
        conf.unset(cfg.MESH_ENABLED)
        mesh_mod.reset_plane()


def _writer_node(num_partitions, input_partitions):
    from auron_tpu.exprs import ir
    from auron_tpu.ir import pb, serde
    return pb.ShuffleWriterNode(
        child=pb.PlanNode(memory_scan=pb.MemoryScanNode(
            table_name="t")),
        partitioning=pb.PartitioningP(
            kind="hash", num_partitions=num_partitions,
            hash_keys=[serde.expr_to_proto(ir.ColumnRef(0))]),
        input_partitions=input_partitions)


def _plan_writer(node, journal, monkeypatch):
    from auron_tpu.ir.planner import PhysicalPlanner, PlannerContext
    monkeypatch.setattr(jrn, "active_journal", lambda: journal)
    t = pa.table({"k": pa.array(list(range(64)), pa.int64())})
    return PhysicalPlanner(
        PlannerContext(catalog={"t": t}))._plan_shuffle_writer(node)


class TestMeshAwareJournalRouting:
    def test_meshable_exchange_skips_the_durable_tier(
            self, mesh_plane, tmp_path, monkeypatch):
        """A journaled query's exchange the mesh can carry stays on the
        all_to_all fast path — journaling must not silently forfeit
        mesh-width exchanges to RSS — while still consuming its
        plan-walk shuffle id so a later resume reproduces the
        sequence."""
        from auron_tpu.parallel.exchange import ShuffleExchangeOp
        journal = _FakeJournal(str(tmp_path / "rss"))
        op = _plan_writer(_writer_node(4, 2), journal, monkeypatch)
        assert isinstance(op, ShuffleExchangeOp)
        assert journal._next == 1          # id consumed regardless
        assert journal.recorded == []      # nothing journaled

    def test_committed_state_pins_the_exchange_to_rss(
            self, mesh_plane, tmp_path, monkeypatch):
        """A RESUME onto a (possibly narrower) mesh: an exchange whose
        committed maps live on the RSS tier re-plans THERE even though
        the current plane could carry it — the durable state is the
        point of the resume."""
        from auron_tpu.parallel.exchange import RssShuffleExchangeOp
        journal = _FakeJournal(str(tmp_path / "rss"), committed={0})
        op = _plan_writer(_writer_node(4, 2), journal, monkeypatch)
        assert isinstance(op, RssShuffleExchangeOp)
        assert journal.recorded and journal.recorded[0][0] == 0

    def test_too_wide_exchange_journals_onto_rss(
            self, mesh_plane, tmp_path, monkeypatch):
        """An exchange wider than the plane routes device_buffer, so a
        journaled query lowers it through the durable tier (the
        resumable case)."""
        from auron_tpu.parallel.exchange import RssShuffleExchangeOp
        wide = mesh_plane.num_devices + 4
        journal = _FakeJournal(str(tmp_path / "rss"))
        op = _plan_writer(_writer_node(wide, 3), journal, monkeypatch)
        assert isinstance(op, RssShuffleExchangeOp)
        assert journal.recorded


# ---------------------------------------------------------------------------
# mesh-aware resume: 8 -> {1, 4} subprocess width sweep
# ---------------------------------------------------------------------------

_MESH_CHILD = r"""
import os, signal, sys
workdir, kill_at = sys.argv[1], int(sys.argv[2])
from auron_tpu.frontend.dataframe import col, functions as F
from auron_tpu.frontend.session import Session
from auron_tpu.runtime import journal as jrn

counter = [0]
orig_map = jrn.QueryJournal.record_map
orig_commit = jrn.QueryJournal.record_shuffle_commit
def _boundary():
    counter[0] += 1
    if counter[0] == kill_at:
        os.kill(os.getpid(), signal.SIGKILL)
def record_map(self, *a, **kw):
    orig_map(self, *a, **kw); _boundary()
def record_shuffle_commit(self, *a, **kw):
    orig_commit(self, *a, **kw); _boundary()
jrn.QueryJournal.record_map = record_map
jrn.QueryJournal.record_shuffle_commit = record_shuffle_commit

s = Session()
df = (s.read_parquet([os.path.join(workdir, "mesh.parquet")],
                     partitions=3)
      .repartition(8, "k")
      .filter(col("c") > 50)
      .repartition(12, "k")
      .group_by("k")
      .agg(F.sum(col("v")).alias("sv"), F.count(col("c")).alias("n")))
table = s.execute(df)
s.close()
import pyarrow.feather as feather
feather.write_feather(table, os.path.join(workdir, "baseline.arrow"),
                      compression="uncompressed")
print("COMPLETED", counter[0])
"""


def _mesh_dataset(workdir):
    import numpy as np
    import pyarrow.parquet as pq
    rng = np.random.default_rng(23)
    n = 50_000
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 48, n), pa.int64()),
        "v": pa.array(rng.normal(size=n), pa.float64()),
        "c": pa.array(rng.integers(0, 100, n), pa.int32())})
    pq.write_table(tbl, os.path.join(workdir, "mesh.parquet"))


def _spawn_mesh_child(workdir, jdir, kill_at):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "AURON_CONF_MESH_ENABLED": "1",
        "AURON_CONF_JOURNAL_DIR": jdir,
    })
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, "-c", _MESH_CHILD, workdir, str(kill_at)],
        capture_output=True, text=True, timeout=300, cwd=repo, env=env)


@pytest.fixture(scope="module")
def mesh_workdir(workdir):
    d = os.path.join(workdir, "mesh_resume")
    os.makedirs(d, exist_ok=True)
    _mesh_dataset(d)
    return d


@pytest.fixture(scope="module")
def mesh_baseline(mesh_workdir):
    """The uninterrupted 8-wide-mesh run's result (a completion-control
    child: same env, kill disabled) — the bit-identity reference for
    every resumed width."""
    jdir = os.path.join(mesh_workdir, "journal_base")
    os.makedirs(jdir, exist_ok=True)
    proc = _spawn_mesh_child(mesh_workdir, jdir, 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import pyarrow.feather as feather
    return feather.read_table(
        os.path.join(mesh_workdir, "baseline.arrow"))


def _resume_at_width(mesh_workdir, mesh_baseline, width):
    """Kill an 8-wide-mesh writer after its first RSS shuffle commit,
    then resume the journal in THIS process at ``width`` (0 = mesh
    off): bit-identical to the uninterrupted run, clean dir after."""
    from auron_tpu.frontend.session import Session
    jdir = os.path.join(mesh_workdir, f"journal_w{width}")
    shutil.rmtree(jdir, ignore_errors=True)
    os.makedirs(jdir)
    # the first journaled exchange is repartition(12) (the 8-wide one
    # rides the mesh, un-journaled): 8 map records + the shuffle
    # commit = event 9 — kill right after the commit returns, so the
    # resume reuses a COMPLETE committed exchange and re-routes
    # everything downstream by the current (narrower) plane's verdict
    proc = _spawn_mesh_child(mesh_workdir, jdir, 9)
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    stems = [os.path.splitext(os.path.basename(p))[0]
             for p in glob.glob(os.path.join(jdir, "*.journal"))]
    assert len(stems) == 1, stems

    conf = cfg.get_config()
    _missing = object()
    saved_jd = conf._overrides.get(cfg.JOURNAL_DIR, _missing)
    conf.set(cfg.JOURNAL_DIR, jdir)
    if width:
        conf.set(cfg.MESH_ENABLED, True)
        conf.set(cfg.MESH_DEVICES, width)
    mesh_mod.reset_plane()
    try:
        s = Session()
        try:
            table = s.resume(stems[0])
        finally:
            s.close()
    finally:
        if saved_jd is _missing:
            conf.unset(cfg.JOURNAL_DIR)
        else:
            conf.set(cfg.JOURNAL_DIR, saved_jd)
        if width:
            conf.unset(cfg.MESH_ENABLED)
            conf.unset(cfg.MESH_DEVICES)
        mesh_mod.reset_plane()
    stats = jrn.last_stats()
    assert table.equals(mesh_baseline), (
        f"resume at width {width} diverged from the uninterrupted "
        f"8-wide run")
    assert stats.get("maps_skipped", 0) >= 1, stats
    assert _journal_leftovers(jdir) == []


def test_mesh_journal_resumes_on_width_1(mesh_workdir, mesh_baseline):
    """8 -> 1: the writer's mesh is gone entirely on the resuming
    process (auron.mesh.enabled off); every remaining exchange routes
    host-side and the committed stage is reused from RSS."""
    _resume_at_width(mesh_workdir, mesh_baseline, 0)


@pytest.mark.slow
def test_mesh_journal_resumes_on_width_4(mesh_workdir, mesh_baseline):
    """8 -> 4: the resuming process has a REAL but narrower plane —
    exchanges the 4-wide mesh can carry ride it, wider ones route by
    the current verdict onto the durable tier, and the result is still
    bit-identical."""
    _resume_at_width(mesh_workdir, mesh_baseline, 4)
