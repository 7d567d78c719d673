"""Mesh fault domain battery (ISSUE 12).

The SPMD plane inherits the robustness model the durable tiers already
have: a device lost mid-all-to-all (or a deterministically failing mesh)
is recovered by ROUTE DEMOTION — the exchange's remaining rounds
re-route down the existing ladder (``all_to_all`` → host
``device_buffer``; RSS stays the durable tier), re-using the lost
round's still-live map inputs, with the result BIT-IDENTICAL to the
fault-free single-device run (group order included). The plane
quarantines the lost device so subsequent exchanges rebuild a smaller
submesh (or route host-side once the square contract breaks), the gang
ticket releases on every unwind path, and a straggling chip is an
observable event (optionally the same demotion) instead of a silent
latency spike.

The differential recovery battery here is the acceptance criterion's
direct proof: an injected fatal ``mesh.all_to_all`` fault at EACH round
index completes via demotion, bit-identical. Seeds are searched against
the fault plane's own decision function so each target round index is
hit deterministically.
"""

import zlib

import numpy as np
import pyarrow as pa
import pytest

import jax

from auron_tpu import config as cfg
from auron_tpu import errors
from auron_tpu.parallel import mesh
from auron_tpu.runtime import faults
from auron_tpu.runtime.watchdog import (MeshRoundGuard, MeshRoundStats,
                                        TaskHeartbeat)

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


# ---------------------------------------------------------------------------
# deterministic seed search against the fault plane's decision function
# ---------------------------------------------------------------------------

def _first_fire(seed: int, kind: str, prob: float, limit: int = 64):
    """Replicates FaultPlane._decide: the event index at which a
    ``mesh.all_to_all:{kind}@{prob}`` rule first injects for ``seed``."""
    for n in range(limit):
        h = zlib.crc32(f"{seed}|mesh.all_to_all|{kind}|{n}".encode())
        if (h & 0xFFFFFFFF) / 2**32 < prob:
            return n
    return None


def _seed_for_round(r: int, kind: str, prob: float) -> int:
    for seed in range(1, 20000):
        if _first_fire(seed, kind, prob) == r:
            return seed
    raise AssertionError(f"no seed fires {kind} first at round {r}")


@pytest.fixture()
def mesh_on():
    conf = cfg.get_config()
    conf.set(cfg.MESH_ENABLED, True)
    try:
        yield mesh.current_plane()
    finally:
        mesh.clear_quarantine()
        conf.unset(cfg.MESH_ENABLED)


@pytest.fixture()
def armed():
    """Arm a fault plan for the test body; guaranteed disarm + plane
    hygiene afterwards."""
    conf = cfg.get_config()

    def arm(plan: str, seed: int, **knobs):
        conf.set(cfg.FAULTS_PLAN, plan)
        conf.set(cfg.FAULTS_SEED, seed)
        for k, v in knobs.items():
            conf.set(getattr(cfg, k), v)
        arm.extra = list(knobs)
        faults.reset()

    arm.extra = []
    yield arm
    conf.unset(cfg.FAULTS_PLAN)
    conf.unset(cfg.FAULTS_SEED)
    for k in arm.extra:
        conf.unset(getattr(cfg, k))
    faults.reset()
    mesh.clear_quarantine()


# ---------------------------------------------------------------------------
# classification at the collective boundary
# ---------------------------------------------------------------------------

class TestClassification:
    def test_device_loss_patterns_become_mesh_unavailable(self):
        for msg in ("Device lost during all-reduce",
                    "INTERNAL: device unavailable",
                    "interconnect timeout between chips",
                    "slice health check failed"):
            out = errors.classify_runtime(RuntimeError(msg))
            assert isinstance(out, errors.MeshUnavailable), msg
            assert errors.is_transient(out)

    def test_deterministic_and_transient_split_unchanged(self):
        out = errors.classify_runtime(RuntimeError("Mosaic lowering bug"))
        assert isinstance(out, errors.KernelLoweringError)
        out = errors.classify_runtime(RuntimeError("RESOURCE_EXHAUSTED"))
        assert isinstance(out, errors.DeviceExecutionError)
        assert not isinstance(out, errors.MeshUnavailable)

    def test_is_mesh_loss_predicate(self):
        from auron_tpu.parallel.mesh_exchange import is_mesh_loss
        assert is_mesh_loss(errors.MeshUnavailable("x"))
        assert is_mesh_loss(
            errors.InjectedFatalError("x", site="mesh.all_to_all"))
        # faults from the map-side child keep their own recovery
        assert not is_mesh_loss(
            errors.InjectedFatalError("x", site="device.compute"))
        assert not is_mesh_loss(errors.DeviceExecutionError("x"))
        assert not is_mesh_loss(RuntimeError("x"))

    def test_classify_collective_passthrough(self):
        from auron_tpu.parallel.mesh_exchange import classify_collective
        e = errors.MeshUnavailable("already classified")
        assert classify_collective(e) is e
        out = classify_collective(RuntimeError("device lost"))
        assert isinstance(out, errors.MeshUnavailable)
        ve = ValueError("not runtime")
        assert classify_collective(ve) is ve


# ---------------------------------------------------------------------------
# straggler stats + gang-aware round guard (pure units)
# ---------------------------------------------------------------------------

class TestRoundStats:
    def test_arms_after_min_rounds(self):
        st = MeshRoundStats(min_rounds=4)
        assert st.p50() is None
        for d in (0.01, 0.012, 0.011, 0.013):
            st.observe(d)
        assert st.p50() is not None
        assert st.is_straggler(0.2, 4.0)
        assert not st.is_straggler(0.02, 4.0)

    def test_disabled_factor_and_window(self):
        st = MeshRoundStats(min_rounds=2, window=4)
        for d in (0.01, 0.01, 0.01, 0.01):
            st.observe(d)
        assert not st.is_straggler(1.0, 0.0)    # factor 0 = disarmed
        # window slides: a run of slow rounds becomes the new baseline
        for d in (1.0, 1.0, 1.0, 1.0):
            st.observe(d)
        assert not st.is_straggler(1.2, 4.0)


class TestRoundGuard:
    def test_forgives_stall_flagged_mid_round(self):
        hb = TaskHeartbeat(timeout_s=1.0)
        with MeshRoundGuard(hb) as g:
            hb.stalled = True           # monitor flags mid-round
            hb.stalled_at_ns = 1
        assert g.forgiven
        assert not hb.stalled           # slow, not dead: forgiven
        assert hb.last_site == "mesh.round"

    def test_preexisting_stall_survives(self):
        hb = TaskHeartbeat(timeout_s=1.0)
        hb.stalled = True               # someone else's verdict
        with MeshRoundGuard(hb) as g:
            pass
        assert not g.forgiven
        assert hb.stalled

    def test_raising_round_is_not_forgiven(self):
        hb = TaskHeartbeat(timeout_s=1.0)
        with pytest.raises(RuntimeError):
            with MeshRoundGuard(hb) as g:
                hb.stalled = True
                raise RuntimeError("device lost")
        assert hb.stalled               # dead round: the flag stands
        assert not g.forgiven

    def test_demotion_handler_forgives_explicitly(self):
        """The demotion path calls forgive_stall() on the FAILED round:
        a stall flagged while the dying round blocked must not abort
        the host re-route at its first checkpoint."""
        hb = TaskHeartbeat(timeout_s=1.0)
        with pytest.raises(RuntimeError):
            with MeshRoundGuard(hb) as g:
                hb.stalled = True
                raise RuntimeError("device lost")
        g.forgive_stall()
        assert not hb.stalled
        assert g.forgiven
        # but a pre-existing flag is never cleared
        hb2 = TaskHeartbeat(timeout_s=1.0)
        hb2.stalled = True
        with pytest.raises(RuntimeError):
            with MeshRoundGuard(hb2) as g2:
                raise RuntimeError("device lost")
        g2.forgive_stall()
        assert hb2.stalled

    def test_none_heartbeat(self):
        with MeshRoundGuard(None) as g:
            pass
        assert g.elapsed_s >= 0.0


# ---------------------------------------------------------------------------
# quarantine-aware routing (pure)
# ---------------------------------------------------------------------------

def test_exchange_route_quarantine_aware():
    from auron_tpu.exprs import ir
    from auron_tpu.parallel.partitioning import HashPartitioning

    class FakePlane:
        num_devices = 8
        usable_width = 6
    hp = HashPartitioning((ir.ColumnRef(0),), 8)
    route, reason = mesh.exchange_route(hp, 8, 2, FakePlane())
    assert route == "device_buffer"
    assert reason.startswith("mesh_quarantined")
    hp4 = HashPartitioning((ir.ColumnRef(0),), 4)
    assert mesh.exchange_route(hp4, 4, 2, FakePlane())[0] == "all_to_all"


def test_quarantine_rereport_is_noop():
    """A stale submesh (built pre-quarantine, e.g. a query parked at the
    gang door) re-reporting the SAME dead chip must be a no-op — not a
    tail-device blame that compounds one real loss into one retired
    healthy chip per concurrent query."""
    plane = mesh.MeshPlane([object() for _ in range(4)])
    assert plane.quarantine(2, "loss") == 2
    assert plane.quarantined() == [2]
    # second report of the same dead device: already retired, no-op
    assert plane.quarantine(2, "loss") == 2
    assert plane.quarantined() == [2]
    assert plane.usable_width == 3
    assert plane.device_losses == 1
    # an UNKNOWN device identity still tail-blames the healthy set
    assert plane.quarantine(None, "loss") == 3
    assert plane.quarantined() == [2, 3]


# ---------------------------------------------------------------------------
# differential recovery battery (the acceptance criterion)
# ---------------------------------------------------------------------------

_ROUNDS = 4
_PROB = 0.4


def _exchange_parts():
    rng = np.random.default_rng(17)
    n = 2000
    rb = pa.record_batch({
        "k": pa.array(rng.integers(0, 37, n), pa.int64()),
        "v": pa.array(list(range(n)), pa.int64()),
    })
    # 4 batches per map, 2 maps -> 4 all-to-all rounds
    return rb, [[rb.slice(o, 250) for o in range(0, 1000, 250)],
                [rb.slice(o, 250) for o in range(1000, 2000, 250)]]


def _build_exchange(rb, parts):
    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    from auron_tpu.parallel.partitioning import HashPartitioning
    scan = MemoryScanOp(parts, schema_from_arrow(rb.schema), capacity=256)
    return ShuffleExchangeOp(scan, HashPartitioning((ir.ColumnRef(0),), 4),
                             input_partitions=2)


@needs_mesh
@pytest.mark.parametrize("round_idx", list(range(_ROUNDS)))
def test_fatal_at_each_round_index_completes_via_demotion(
        round_idx, mesh_on, armed):
    """An injected fatal ``mesh.all_to_all`` fault at EVERY round index
    completes via demotion, bit-identical to the fault-free run —
    rounds the mesh finished are kept (never re-yielded), only the lost
    round's inputs re-route, and the demotion is RECORDED (route
    counter + mesh rounds kept == the failed round's index)."""
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.runtime.executor import collect

    rb, parts = _exchange_parts()
    conf = cfg.get_config()
    conf.unset(cfg.MESH_ENABLED)
    classic = collect(_build_exchange(rb, parts), num_partitions=4)
    conf.set(cfg.MESH_ENABLED, True)

    armed(f"mesh.all_to_all:fatal@{_PROB}",
          _seed_for_round(round_idx, "fatal", _PROB))
    ex = _build_exchange(rb, parts)
    ctx = ExecContext()
    got = []
    for p in range(4):
        for b in ex.execute(p, ctx):
            got.append(b)
    import pyarrow as _pa
    from auron_tpu.columnar.arrow_bridge import schema_to_arrow, to_arrow
    schema = schema_to_arrow(ex.schema())
    table = _pa.Table.from_batches(
        [to_arrow(b, ex.schema()) for b in got if int(b.num_rows)],
        schema=schema)
    assert table.equals(classic), \
        f"demotion at round {round_idx} diverged from the classic path"
    m = ctx.metrics["shuffle_exchange"]
    assert m.counter("exchange_route_demoted").value == 1
    assert m.counter("mesh_demotions").value == 1
    assert m.counter("mesh_rounds").value == round_idx, \
        "completed mesh rounds must equal the failed round's index"
    plane = mesh.current_plane()
    assert plane.quarantined(), "device loss must quarantine"
    assert plane.gang_holder() is None


@needs_mesh
def test_io_error_demotion_and_quarantined_rerouting(mesh_on, armed):
    """After a device loss quarantines one chip, a narrower follow-up
    exchange still rides the all-to-all on the shrunken submesh, while
    one as wide as the FULL mesh routes host-side with the quarantine
    named as the reason — and both stay bit-identical."""
    from auron_tpu.runtime.executor import collect

    rb, parts = _exchange_parts()
    conf = cfg.get_config()
    conf.unset(cfg.MESH_ENABLED)
    classic = collect(_build_exchange(rb, parts), num_partitions=4)
    conf.set(cfg.MESH_ENABLED, True)

    armed(f"mesh.all_to_all:io_error@{_PROB}",
          _seed_for_round(1, "io_error", _PROB))
    got = collect(_build_exchange(rb, parts), num_partitions=4)
    assert got.equals(classic)
    plane = mesh.current_plane()
    assert len(plane.quarantined()) == 1
    assert plane.usable_width == plane.num_devices - 1

    # disarm; the quarantine persists for the rest of the process
    conf.unset(cfg.FAULTS_PLAN)
    conf.unset(cfg.FAULTS_SEED)
    faults.reset()

    from auron_tpu.exprs import ir
    from auron_tpu.parallel.partitioning import HashPartitioning
    hp4 = HashPartitioning((ir.ColumnRef(0),), 4)
    assert mesh.exchange_route(hp4, 4, 2, plane)[0] == "all_to_all"
    full = HashPartitioning((ir.ColumnRef(0),), plane.num_devices)
    route, reason = mesh.exchange_route(full, plane.num_devices, 2, plane)
    assert route == "device_buffer"
    assert reason.startswith("mesh_quarantined")

    # the narrower exchange actually RUNS on the shrunken submesh
    from auron_tpu.ops.base import ExecContext
    ex = _build_exchange(rb, parts)
    ctx = ExecContext()
    out = collect(ex, num_partitions=4)
    assert out.equals(classic)


@needs_mesh
def test_straggler_demotion_bit_identical(mesh_on, armed):
    """A straggling round (injected hang past straggler_factor x the
    rolling p50) under demote_on_straggler demotes the REMAINING rounds
    — the slow round's received rows stay valid on the mesh, nothing is
    quarantined, and the result is bit-identical."""
    from auron_tpu.runtime.executor import collect

    rng = np.random.default_rng(11)
    n = 4000
    rb = pa.record_batch({
        "k": pa.array(rng.integers(0, 37, n), pa.int64()),
        "v": pa.array(list(range(n)), pa.int64()),
    })
    parts = [[rb.slice(o, 250) for o in range(0, 2000, 250)],
             [rb.slice(o, 250) for o in range(2000, 4000, 250)]]

    def build():
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.exprs import ir
        from auron_tpu.io.parquet import MemoryScanOp
        from auron_tpu.parallel.exchange import ShuffleExchangeOp
        from auron_tpu.parallel.partitioning import HashPartitioning
        scan = MemoryScanOp(parts, schema_from_arrow(rb.schema),
                            capacity=256)
        return ShuffleExchangeOp(
            scan, HashPartitioning((ir.ColumnRef(0),), 4),
            input_partitions=2)

    conf = cfg.get_config()
    conf.unset(cfg.MESH_ENABLED)
    classic = collect(build(), num_partitions=4)
    conf.set(cfg.MESH_ENABLED, True)
    plane = mesh.current_plane()
    strag0 = plane.stragglers

    # hang at round 6: the p50 window (min_rounds=4) is armed by then
    armed("mesh.all_to_all:hang@0.15", _seed_for_round(6, "hang", 0.15),
          FAULTS_HANG_S=0.5, MESH_DEMOTE_ON_STRAGGLER=True)
    got = collect(build(), num_partitions=4)
    assert got.equals(classic), "straggler demotion diverged"
    assert plane.stragglers > strag0
    assert plane.demotions.get("straggler", 0) >= 1
    assert plane.quarantined() == [], "a straggler must NOT quarantine"


@needs_mesh
def test_gang_door_cancel_releases_ticket_clean_ledger(mesh_on, armed):
    """ISSUE 12 satellite: a cancel firing while parked at the gang door
    (the ``mesh.gang`` chaos site) releases the ticket, dequeues WITHOUT
    starting a round, surfaces the classified QueryCancelled, and leaves
    a clean consumer/spill ledger (the PR 7 leak-audit contract)."""
    import gc
    import tempfile

    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager

    rng = np.random.default_rng(5)
    table = pa.Table.from_batches([pa.record_batch({
        "k": pa.array(rng.integers(0, 64, 1024), pa.int64()),
        "v": pa.array(rng.normal(size=1024)),
    }) for _ in range(4)])

    armed("mesh.gang:cancel@1.0", 3)
    with tempfile.TemporaryDirectory() as d:
        mm = MemManager(total_bytes=1 << 24, min_trigger=0,
                        spill_manager=SpillManager(
                            host_budget_bytes=1 << 20, spill_dir=d))
        s = Session(mem_manager=mm)
        try:
            df = (s.from_arrow(table).repartition(4, "k")
                  .group_by("k").agg(F.sum(col("v")).alias("sv")))
            with pytest.raises(errors.QueryCancelled):
                s.execute(df)
        finally:
            s.close()
        plane = mesh.current_plane()
        assert plane.gang_holder() is None
        assert plane.stats()["gang_queued"] == 0
        gc.collect()
        assert not mm.status()["consumers"]
        assert mm.spill_manager.live_disk_files() == 0


@needs_mesh
def test_demote_events_recorded_for_mesh_report(mesh_on, armed):
    """The trace half of the demotion record (tools/mesh_report.py's
    input): an ``exchange.demote`` event with reason/rounds/quarantine
    attrs plus the final ``exchange.route`` record with route='demoted'
    and the recompute cost — recovery surfaced, never inferred."""
    from auron_tpu.obs import trace
    from auron_tpu.runtime.executor import collect

    rb, parts = _exchange_parts()
    conf = cfg.get_config()
    armed(f"mesh.all_to_all:fatal@{_PROB}",
          _seed_for_round(1, "fatal", _PROB))
    conf.set(cfg.TRACE_ENABLED, True)
    conf.set(cfg.TRACE_DIR, "")
    try:
        collect(_build_exchange(rb, parts), num_partitions=4)
        spans = trace.tracer().spans()
    finally:
        conf.unset(cfg.TRACE_ENABLED)
        conf.unset(cfg.TRACE_DIR)
        trace.reset()
    dem = [s for s in spans if s.name == "exchange.demote"]
    assert len(dem) == 1
    assert dem[0].attrs["reason"] == "device_loss"
    assert dem[0].attrs["rounds_completed"] == 1
    assert dem[0].attrs["quarantined"]
    quar = [s for s in spans if s.name == "mesh.quarantine"]
    assert len(quar) == 1
    routes = [s for s in spans if s.name == "exchange.route"
              and s.attrs.get("route") == "demoted"]
    assert len(routes) == 1
    a = routes[0].attrs
    assert a["reason"] == "device_loss"
    assert a["recompute_rows"] > 0
    assert a["recompute_bytes"] > 0
    assert a["latency_ms"] >= 0
    # the route mix is what tools/mesh_report.summarize aggregates
    import tools.mesh_report as mr
    summary = mr.summarize([
        {"name": s.name, "attrs": dict(s.attrs)} for s in spans])
    assert summary["demotions"] == {"device_loss": 1}
    assert summary["quarantines"] == 1
    assert "demoted" in summary["by_route"]


@pytest.fixture(scope="module")
def tpcds_tables():
    import tempfile

    from auron_tpu.it.tpcds import generate
    with tempfile.TemporaryDirectory(prefix="mesh_faults_tpcds_") as d:
        yield generate(d, scale=0.01)


@needs_mesh
@pytest.mark.parametrize("round_idx", [0, 1, 2])
def test_tpcds_fatal_each_round_completes_via_demotion(
        round_idx, tpcds_tables, mesh_on, armed):
    """The acceptance criterion end to end: a TPC-DS sharded query
    (store_sales scanned in 4 partitions, hash-repartitioned on
    ss_store_sk with scan batch rows clamped so the exchange runs
    several all-to-all rounds, then aggregated) with an injected fatal
    ``mesh.all_to_all`` fault at each round index completes via
    demotion, bit-identical to the fault-free single-device run (group
    order included)."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session

    def run_q():
        s = Session()
        df = (s.read_parquet(tpcds_tables["store_sales"], partitions=4)
              .repartition(4, "ss_store_sk")
              .filter(col("ss_quantity") > 5)
              .group_by("ss_store_sk")
              .agg(F.sum(col("ss_sales_price")).alias("total"),
                   F.count(col("ss_net_paid")).alias("paid_cnt")))
        return s.execute(df)

    conf = cfg.get_config()
    conf.set(cfg.SCAN_BATCH_ROWS, 2048)   # several rounds per exchange
    try:
        conf.unset(cfg.MESH_ENABLED)
        single = run_q()
        conf.set(cfg.MESH_ENABLED, True)
        armed(f"mesh.all_to_all:fatal@{_PROB}",
              _seed_for_round(round_idx, "fatal", _PROB))
        sharded = run_q()
    finally:
        conf.unset(cfg.SCAN_BATCH_ROWS)
    assert sharded.equals(single), \
        f"TPC-DS demotion at round {round_idx} differs from " \
        f"single-device (values or order)"
    plane = mesh.current_plane()
    assert plane.demotions.get("device_loss", 0) >= 1


@needs_mesh
def test_session_query_demotes_bit_identical(mesh_on, armed):
    """Session-planned sharded query (fused chain folded into the mesh
    program): a device loss mid-exchange demotes with the SAME rows —
    the host continuation seeds each map's member carries from the last
    completed round's snapshot."""
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session

    rng = np.random.default_rng(23)
    table = pa.Table.from_batches([pa.record_batch({
        "k": pa.array(rng.integers(0, 64, 800), pa.int64()),
        "v": pa.array(rng.normal(size=800)),
        "c": pa.array(rng.integers(0, 1000, 800), pa.int32()),
    }) for _ in range(4)])

    def run():
        s = Session()
        df = (s.from_arrow(table)
              .repartition(4, "k")
              .filter(col("c") > 50)
              .group_by("k")
              .agg(F.sum(col("v")).alias("sv"),
                   F.count(col("c")).alias("n")))
        return s.execute(df)

    conf = cfg.get_config()
    conf.unset(cfg.MESH_ENABLED)
    base = run()
    conf.set(cfg.MESH_ENABLED, True)
    armed(f"mesh.all_to_all:fatal@{_PROB}",
          _seed_for_round(1, "fatal", _PROB))
    got = run()
    assert got.equals(base), \
        "sharded query demotion diverged from single-device (values " \
        "or group order)"


@needs_mesh
@pytest.mark.parametrize("round_idx", [0, 2])
def test_fatal_mid_combined_exchange_demotes_bit_identical(
        round_idx, mesh_on, armed):
    """Fusion 2.0 chaos case: a fatal all_to_all fault mid-COMBINED
    exchange (the map-side combine stage folded into the staged mesh
    program) demotes to the host route with the combine threading
    intact — bit-identical rows AND order vs the fault-free
    single-device run, the demotion recorded, and the demoted run still
    booking honest combine counters (rows_in > rows_out > 0: the host
    continuation combines too, it does not silently passthrough)."""
    from auron_tpu.frontend import Session, col, functions as F
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.parallel.exchange import ShuffleExchangeOp

    rng = np.random.default_rng(29)
    n = 8000
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 40, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })

    def plan():
        # capacity 512 -> 4 batches per map -> 4 all_to_all rounds, so
        # both parametrized fault indices land MID-exchange
        s = Session(batch_capacity=512)
        s.register("cx", tbl)
        df = (s.table("cx").repartition(4)
              .group_by("k").agg(F.sum(col("v")).alias("sv"),
                                 F.count(col("v")).alias("n")))
        return df, s.plan_physical(df)

    def walk(o):
        yield o
        for c in o.children:
            yield from walk(c)

    def run(op, parts):
        ctx = ExecContext()
        rows = []
        for p in range(parts):
            for b in op.execute(p, ctx):
                m = int(b.num_rows)
                rows.extend(zip(*(np.asarray(c.data[:m]).tolist()
                                  for c in b.columns)))
        return rows, ctx

    conf = cfg.get_config()
    # the repartition's read hands a map its 2,000 rows in batches of
    # the configured capacity (PR 49: one batch where they fit one)
    conf.set(cfg.BATCH_CAPACITY, 512)
    try:
        conf.unset(cfg.MESH_ENABLED)
        df, op = plan()
        classic, _ = run(op, df.num_partitions)
        conf.set(cfg.MESH_ENABLED, True)

        armed(f"mesh.all_to_all:fatal@{_PROB}",
              _seed_for_round(round_idx, "fatal", _PROB))
        df, op = plan()
        ex = [o for o in walk(op) if isinstance(o, ShuffleExchangeOp)]
        # the exchange really is combined — this must not silently decay
        # into a plain-exchange demotion test
        assert ex and ex[0].combine_mode == "combine", \
            f"exchange not combined: {ex and ex[0].combine_why}"
        got, ctx = run(op, df.num_partitions)
    finally:
        conf.unset(cfg.BATCH_CAPACITY)
    assert got == classic, \
        f"demotion at round {round_idx} mid-combined-exchange " \
        f"diverged from the single-device run (values or order)"
    m = ctx.metrics["shuffle_exchange"]
    assert m.counter("exchange_route_demoted").value == 1
    rows_in = m.counter("combine_rows_in").value
    rows_out = m.counter("combine_rows_out").value
    assert rows_in > rows_out > 0, (rows_in, rows_out)
