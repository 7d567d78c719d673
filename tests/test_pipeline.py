"""Pipelined async execution (the prefetching scan + the look-ahead
drive of runtime/executor.py + async-aware attribution).

The contracts this file holds:

- ``lookahead`` preserves order exactly and propagates close/errors,
  alone and at its caller (``ExecutionRuntime.arrow_batches``);
- the scan prefetcher streams batches in source order, registers its
  decoded bytes with the memory manager, unregisters on close (the
  tier-1 leak-audit fixtures watch the same ledger), re-raises worker
  errors with their type intact, and shrinks its lookahead to 1 under
  pressure-ladder rung 1;
- a cancel mid-prefetch unwinds classified and leaks neither consumers
  nor spill files;
- attribution still sums to wall (device measured at the sync points,
  per-call dispatch kept);
- a real parquet query answers as pyarrow does over the same file
  (that its task reports the prefetch worker's spans and seconds is
  held in tests/test_layer_spans.py).
"""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg
from auron_tpu.memmgr.manager import MemManager
from auron_tpu.ops.base import ExecContext
from auron_tpu.runtime import executor


# ---------------------------------------------------------------------------
# lookahead window
# ---------------------------------------------------------------------------

class TestLookahead:
    def test_preserves_order_and_exhausts(self):
        for depth in (0, 1, 2, 5, 100):
            assert list(executor.lookahead(iter(range(7)), depth)) \
                == list(range(7))
        assert list(executor.lookahead(iter([]), 1)) == []

    def test_pulls_ahead_of_yield(self):
        pulled = []

        def src():
            for i in range(4):
                pulled.append(i)
                yield i

        it = executor.lookahead(src(), depth=1)
        assert next(it) == 0
        # item 1 was pulled BEFORE item 0 was yielded (the overlap)
        assert pulled == [0, 1]

    def test_close_propagates(self):
        closed = []

        def src():
            try:
                for i in range(100):
                    yield i
            finally:
                closed.append(True)

        it = executor.lookahead(src(), depth=1)
        assert next(it) == 0
        it.close()
        assert closed == [True]

    def test_error_surfaces(self):
        def src():
            yield 1
            raise ValueError("decode failed")

        it = executor.lookahead(src(), depth=1)
        with pytest.raises(ValueError, match="decode failed"):
            list(it)


class TestArrowBatchesDrive:
    """The look-ahead at its one caller, ``ExecutionRuntime
    .arrow_batches``, over a recording stand-in for ``batches()``."""

    def _runtime(self, nbatches=5, fail_at=None):
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.io.parquet import MemoryScanOp
        rbs = [pa.record_batch({"x": pa.array([10 * i, 10 * i + 1],
                                              pa.int64())})
               for i in range(nbatches)]
        scan = MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                            capacity=4)
        rt = executor.ExecutionRuntime(scan, executor.TaskDefinition())
        events = []
        real = rt.batches

        def recording():
            try:
                for i, batch in enumerate(real()):
                    if i == fail_at:
                        raise ValueError("kernel failed")
                    events.append(i)
                    yield batch
            finally:
                events.append("closed")

        rt.batches = recording
        return rt, events

    def test_yields_batches_order_one_batch_ahead(self):
        rt, events = self._runtime()
        it = rt.arrow_batches()
        first = next(it)
        assert first.column("x").to_pylist() == [0, 1]
        assert events == [0, 1]      # batch 1 was pulled before 0 came
        rest = [rb.column("x").to_pylist() for rb in it]
        assert rest == [[10 * i, 10 * i + 1] for i in range(1, 5)]
        assert events == [0, 1, 2, 3, 4, "closed"]

    def test_early_stop_closes_the_operator_chain(self):
        rt, events = self._runtime()
        it = rt.arrow_batches()
        next(it)
        it.close()
        assert events == [0, 1, "closed"]

    def test_a_producer_error_surfaces_with_its_type_one_batch_early(self):
        """An operator's error reaches the consumer unwrapped, on the
        pull that raised it: batch 2's failure comes when batch 1 is
        asked for, after batch 0 was delivered."""
        rt, events = self._runtime(fail_at=2)
        it = rt.arrow_batches()
        assert next(it).column("x").to_pylist() == [0, 1]
        with pytest.raises(ValueError, match="kernel failed"):
            next(it)
        assert events == [0, 1, "closed"]


# ---------------------------------------------------------------------------
# scan prefetcher
# ---------------------------------------------------------------------------

def _write_parquet(tmp, rows=50_000, row_group=4096):
    rng = np.random.default_rng(0)
    path = os.path.join(tmp, "t.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 100, rows), pa.int64()),
        "v": pa.array(rng.normal(size=rows), pa.float64()),
    }), path, row_group_size=row_group)
    return path


class TestScanPrefetcher:
    def _prefetcher(self, source, ctx=None, depth=2):
        from auron_tpu.io.parquet import ScanPrefetcher
        return ScanPrefetcher(source, ctx or ExecContext(), depth)

    def test_order_and_drain(self):
        from auron_tpu.ops.base import MetricsSet
        items = [(i, 10) for i in range(20)]
        pf = self._prefetcher(iter(items))
        try:
            out = list(pf.batches(MetricsSet().counter("io_time")))
        finally:
            pf.close()
        assert out == list(range(20))

    def test_memmgr_accounting_and_unregister(self):
        from auron_tpu.memmgr import manager as mgr
        from auron_tpu.ops.base import MetricsSet
        mem = MemManager(total_bytes=1 << 30)
        before = mgr.live_consumer_count()
        gate = threading.Event()

        def src():
            for i in range(6):
                yield i, 1000
            gate.wait(5)

        ctx = ExecContext(mem_manager=mem)
        pf = self._prefetcher(src(), ctx)
        try:
            it = pf.batches(MetricsSet().counter("io_time"))
            next(it)
            # worker holds up to depth buffered items; accounting is
            # queued bytes (0..depth*1000), consistent with the ledger
            deadline = time.monotonic() + 2
            while pf.mem_used() == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert 0 <= pf.mem_used() <= 2 * 1000
            assert mgr.live_consumer_count() == before + 1
        finally:
            gate.set()
            pf.close()
        assert pf.mem_used() == 0
        assert mgr.live_consumer_count() == before

    def test_worker_error_reraised_with_type(self):
        from auron_tpu.ops.base import MetricsSet

        def src():
            yield 0, 1
            raise RuntimeError("corrupt row group")

        pf = self._prefetcher(src())
        try:
            with pytest.raises(RuntimeError, match="corrupt row group"):
                list(pf.batches(MetricsSet().counter("io_time")))
        finally:
            pf.close()

    def test_depth_shrinks_under_pressure_rung1(self):
        """Pressure-ladder rung 1 (the shrink rung: advised_batch_rows
        < base) must degrade the prefetch lookahead to 1."""
        mem = MemManager(total_bytes=1 << 30)
        ctx = ExecContext(mem_manager=mem)
        pf = self._prefetcher(iter([]), ctx, depth=4)
        try:
            assert pf.target_depth() == 4
            mem._shrink_level = 1          # rung 1 taken
            assert pf.target_depth() == 1
            mem._shrink_level = 0
            assert pf.target_depth() == 4
            pf.shrink()                    # the ladder's direct ask
            assert pf.target_depth() == 1
        finally:
            pf.close()

    def test_cancel_mid_prefetch_no_leaks(self):
        """Cancel while the worker is mid-stream: the consumer unwinds
        with the classified error, the worker stops, and the memmgr
        ledger returns to its pre-scan state (the tier-1 leak-audit
        fixtures check the same globals after this test)."""
        from auron_tpu.memmgr import manager as mgr
        from auron_tpu.ops.base import MetricsSet
        from auron_tpu.runtime.lifecycle import CancelToken

        mem = MemManager(total_bytes=1 << 30)
        before = mgr.live_consumer_count()
        token = CancelToken(query_id="q_prefetch")
        ctx = ExecContext(mem_manager=mem, cancel_event=token)

        def src():
            i = 0
            while True:          # endless decode — only cancel stops it
                yield i, 100
                i += 1
                time.sleep(0.001)

        pf = self._prefetcher(src(), ctx)
        try:
            it = pf.batches(MetricsSet().counter("io_time"))
            next(it)
            threading.Thread(target=lambda: (time.sleep(0.05),
                                             token.cancel()),
                             daemon=True).start()
            from auron_tpu import errors
            with pytest.raises(errors.QueryCancelled):
                for _ in it:
                    pass
        finally:
            pf.close()
        assert pf.mem_used() == 0
        assert mgr.live_consumer_count() == before
        # the worker thread exits promptly after close
        pf._thread.join(timeout=2)
        assert not pf._thread.is_alive()


# ---------------------------------------------------------------------------
# end-to-end: parquet scan
# ---------------------------------------------------------------------------

class TestPipelinedScan:
    @pytest.fixture(scope="class")
    def data(self, tmp_path_factory):
        tmp = str(tmp_path_factory.mktemp("pipe_scan"))
        return _write_parquet(tmp)

    def _q(self, path):
        from auron_tpu.frontend.dataframe import col
        from auron_tpu.frontend.session import Session
        s = Session()
        return (s.read_parquet([path])
                .filter(col("k") < 50)
                .group_by("k")
                .agg(__import__(
                    "auron_tpu.frontend.dataframe",
                    fromlist=["functions"]).functions.sum(col("v"))
                    .alias("sv"))
                .collect())

    def test_answer_matches_pyarrow(self, data):
        """scan → filter → group-by answers as pyarrow does over the
        same file (a reference independent of the engine; the engine's
        order is not part of the answer, so both sort on ``k``)."""
        import pyarrow.compute as pc
        got = self._q(data).sort_by("k")
        t = pq.read_table(data)
        want = (t.filter(pc.less(t["k"], 50)).group_by("k")
                .aggregate([("v", "sum")]).sort_by("k"))
        assert got.column("k").to_pylist() == want.column("k").to_pylist()
        np.testing.assert_allclose(got.column("sv").to_numpy(),
                                   want.column("v_sum").to_numpy(),
                                   rtol=1e-9)

    def test_scan_cancel_through_session_is_clean(self, data):
        """df.collect(timeout_s=tiny) during a parquet scan: classified
        deadline, and the scan prefetcher's consumer is gone after (the
        autouse leak fixtures re-check at module end)."""
        from auron_tpu import errors
        from auron_tpu.frontend.dataframe import col
        from auron_tpu.frontend.session import Session
        from auron_tpu.memmgr import manager as mgr
        before = mgr.live_consumer_count()
        s = Session(mem_manager=MemManager(total_bytes=1 << 30))
        df = s.read_parquet([data]).filter(col("k") >= 0)
        with pytest.raises(errors.QueryCancelled):
            df.collect(timeout_s=0.000001)
        import gc
        gc.collect()
        assert mgr.live_consumer_count() <= before

    def test_pipelined_attribution_sums_and_fences_device(self, data):
        """Async-aware timing: with profiling on and pipelining on, the
        metric tree still carries elapsed_device (fenced at the to_arrow
        boundary / control readbacks), and per-op attribution never
        exceeds wall by more than the documented tolerance."""
        from auron_tpu.frontend.dataframe import col
        from auron_tpu.frontend.session import Session
        from auron_tpu.obs import metric_tree as mt
        s = Session()
        df = s.read_parquet([data]).filter(col("k") < 10)
        tree, _table = mt.explain_analyze(
            s.plan_physical(df), num_partitions=df.num_partitions,
            mem_manager=s.mem_manager, config=s.config)
        records = [{"op": n.name, "metrics": n.metrics}
                   for n in tree.walk() if n.metrics]
        assert records
        total_device = sum(r["metrics"].get("elapsed_device", 0)
                           for r in records)
        assert total_device > 0, records
        # per-record: buckets inside elapsed_compute stay bounded by it
        for r in records:
            m = r["metrics"]
            wall = m.get("elapsed_compute", 0)
            if not wall:
                continue
            inside = m.get("elapsed_host_dispatch", 0) \
                + m.get("elapsed_host_other", 0)
            assert inside <= wall * 1.10 + 500_000, r


# ---------------------------------------------------------------------------
# donation sweep plumbing
# ---------------------------------------------------------------------------

class TestDonationSweep:
    def test_stage_program_keys_split_on_donate(self):
        """The fused-stage program cache must key on the donate flag —
        a donating and a non-donating caller can never share a
        compiled program."""
        from auron_tpu.ops import fused
        site = fused._STAGE_PROGRAMS
        stats0 = site.stats()["builds"]
        from auron_tpu.columnar.schema import DataType, Field, Schema
        import jax.numpy as jnp
        from auron_tpu.ops.fused import KernelFragment

        def apply(batch, pid, carry):
            return (batch,), carry

        frag = KernelFragment(key=("test_donate_plumb",), apply=apply)
        schema = Schema((Field("x", DataType.INT64),))
        k1, b1 = fused.stage_program(("a",), schema, 16, [frag], False)
        k2, b2 = fused.stage_program(("a",), schema, 16, [frag], True)
        k3, b3 = fused.stage_program(("a",), schema, 16, [frag], False)
        assert b1 and b2 and not b3
        assert site.stats()["builds"] == stats0 + 2

    def test_agg_donation_gate(self):
        """Owned child + no collect kinds → donate; collect kinds or
        borrowed batches → never."""
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.exprs import ir
        from auron_tpu.io.parquet import DeviceBatchScanOp, MemoryScanOp
        from auron_tpu.ops.agg import AggOp
        rb = pa.record_batch({"k": pa.array([1, 2], pa.int64()),
                              "v": pa.array([0.5, 1.5], pa.float64())})
        schema = schema_from_arrow(rb.schema)
        owned = MemoryScanOp([[rb]], schema, capacity=16)
        ctx = ExecContext()
        agg = AggOp(owned, [ir.ColumnRef(0)],
                    [ir.AggFunction("sum", ir.ColumnRef(1))],
                    mode="complete")
        assert agg._donate_contributions(ctx)
        borrowed = DeviceBatchScanOp([[None]], schema)
        agg_b = AggOp(borrowed, [ir.ColumnRef(0)],
                      [ir.AggFunction("sum", ir.ColumnRef(1))],
                      mode="complete")
        assert not agg_b._donate_contributions(ctx)
        agg_c = AggOp(owned, [ir.ColumnRef(0)],
                      [ir.AggFunction("collect_list", ir.ColumnRef(1))],
                      mode="complete")
        assert not agg_c._donate_contributions(ctx)

    def test_programs_jit_never_donates_one_buffer_twice(
            self, monkeypatch):
        """The first thing the shuffle split hit on a real chip: a
        scan-fed batch whose columns share ONE all-valid mask, donated
        whole — "Attempt to donate the same buffer twice". The
        donation-aware jit runs the non-donating twin for such a call
        (accelerator branch emulated: the CPU never really donates)."""
        import jax
        import jax.numpy as jnp

        from auron_tpu.runtime import programs
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        real_jit = jax.jit
        calls = []

        def fake_jit(f, donate_argnums=(), **kw):
            jitted = real_jit(f, **kw)

            def run(*a, **k):
                calls.append(bool(donate_argnums))
                return jitted(*a, **k)
            return run

        monkeypatch.setattr(jax, "jit", fake_jit)
        fn = programs.jit(lambda cols, n: sum(cols) + n,
                          donate_argnums=(0,))
        a, b = jnp.ones(4), jnp.ones(4)
        assert float(fn((a, b), 1.0)[0]) == 3.0     # distinct: donates
        assert float(fn((a, a), 1.0)[0]) == 3.0     # aliased: plain twin
        assert float(fn((a, b), a)[0]) == 3.0       # donated AND kept
        assert calls == [True, False, False]

    def test_q01_runs_with_the_accelerator_branches_taken(
            self, monkeypatch, tmp_path):
        """Donation checked by a RUN, not only by lint: with
        default_backend() reporting an accelerator every donate gate
        opens, and the CPU client enforces the same rules a chip does
        (double donation, use after donation). q01's scan-fed shuffle
        split is the plan that failed first light on the v5e."""
        import jax

        from auron_tpu import config as cfg
        from auron_tpu.frontend.session import Session
        from auron_tpu.it import tpcds_data
        from auron_tpu.it.queries import q01_dataframe
        from auron_tpu.runtime import programs
        tables = tpcds_data.generate(str(tmp_path), scale=0.2)
        want = q01_dataframe(Session(), tables, partitions=4).collect()
        conf = cfg.get_config()
        conf.set(cfg.KERNELS_BACKEND, "dense")   # no native Mosaic here
        programs.clear_all()
        jax.clear_caches()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        try:
            got = q01_dataframe(Session(), tables, partitions=4).collect()
        finally:
            monkeypatch.undo()
            conf.unset(cfg.KERNELS_BACKEND)
            programs.clear_all()      # drop the donating programs
            jax.clear_caches()
        assert got.equals(want)

    def test_aliased_contributions_never_donate(self):
        """sum(x) + avg(x) share the x column object — the reduce must
        detect the aliasing and fall back to the non-donating program
        (duplicate donated buffers are illegal on real backends), while
        producing identical results."""
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.exprs import ir
        from auron_tpu.io.parquet import MemoryScanOp
        from auron_tpu.ops.agg import AggOp
        from auron_tpu.runtime.executor import (ExecutionRuntime,
                                                TaskDefinition)
        rng = np.random.default_rng(1)
        rb = pa.record_batch({
            "k": pa.array(rng.integers(0, 5, 256), pa.int64()),
            "v": pa.array(rng.normal(size=256), pa.float64())})
        scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema),
                            capacity=256)
        op = AggOp(scan, [ir.ColumnRef(0)],
                   [ir.AggFunction("sum", ir.ColumnRef(1)),
                    ir.AggFunction("avg", ir.ColumnRef(1))],
                   mode="complete")
        rt = ExecutionRuntime(op, TaskDefinition(task_id=1))
        tbl = rt.collect()
        assert tbl.num_rows == 5
