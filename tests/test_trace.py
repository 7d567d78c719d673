"""Tracing plane + mirrored metric tree + process registry
(auron_tpu/obs/): span tree shape, exporter validity, positional
EXPLAIN ANALYZE correctness, histogram percentiles, chaos correlation,
and the overhead-harness smoke.

Budget note: every engine run here is small-row-count and reuses
compile sites the rest of the suite already exercises (scan/filter/
project/agg) — no new kernel shapes beyond the pinned budget."""

import json

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.columnar.schema import DataType
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import metric_tree as mt
from auron_tpu.obs import registry as obs_registry
from auron_tpu.obs import trace
from auron_tpu.ops.project import FilterOp


@pytest.fixture
def traced():
    """Arm tracing on the PROCESS-GLOBAL config (the tracer resolves
    its settings there, epoch-cached) and guarantee teardown."""
    conf = cfg.get_config()
    conf.set(cfg.TRACE_ENABLED, True)
    trace.reset()
    try:
        yield conf
    finally:
        conf.unset(cfg.TRACE_ENABLED)
        conf.unset(cfg.TRACE_EVENTS)
        conf.unset(cfg.TRACE_MAX_SPANS)
        trace.reset()


def _scan(rows=512, seed=3, capacity=256):
    rng = np.random.default_rng(seed)
    rb = pa.record_batch({
        "k": pa.array(rng.integers(0, 8, rows), pa.int64()),
        "v": pa.array(rng.normal(size=rows)),
        "c": pa.array(rng.integers(0, 100, rows), pa.int32()),
    })
    chunks = [rb.slice(o, capacity) for o in range(0, rows, capacity)]
    return MemoryScanOp([chunks], schema_from_arrow(rb.schema),
                        capacity=capacity)


# ---------------------------------------------------------------------------
# span plane
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_is_noop(self):
        assert not trace.enabled()
        before = len(trace.tracer().spans())
        with trace.span("task", "task.attempt", x=1):
            trace.event("task", "task.retry")
        assert len(trace.tracer().spans()) == before

    def test_span_tree_shape(self, traced):
        with trace.query_scope(label="t") as scope:
            with trace.span("task", "task.attempt", partition=0):
                trace.event("fault", "fault.injected", site="rss.write",
                            kind="io_error")
                with trace.span("shuffle", "rss.flush"):
                    pass
        spans = trace.tracer().spans(scope.trace_id)
        by_name = {s.name: s for s in spans}
        assert set(by_name) == {"query.execute", "task.attempt",
                                "fault.injected", "rss.flush"}
        q = by_name["query.execute"]
        t = by_name["task.attempt"]
        assert q.parent_id == 0
        assert t.parent_id == q.span_id
        assert by_name["fault.injected"].parent_id == t.span_id
        assert by_name["rss.flush"].parent_id == t.span_id
        # events are zero-duration; enclosing spans have duration
        assert by_name["fault.injected"].dur_ns == 0
        assert t.dur_ns >= by_name["rss.flush"].dur_ns
        # every span carries the scope's trace id
        assert {s.trace_id for s in spans} == {scope.trace_id}
        assert by_name["fault.injected"].attrs["site"] == "rss.write"

    def test_category_filter(self, traced):
        traced.set(cfg.TRACE_EVENTS, "task,fault")
        with trace.span("shuffle", "rss.flush"):
            pass
        trace.event("fault", "fault.injected", site="s", kind="k")
        names = {s.name for s in trace.tracer().spans()}
        assert "fault.injected" in names
        assert "rss.flush" not in names

    def test_max_spans_cap(self, traced):
        traced.set(cfg.TRACE_MAX_SPANS, 5)
        for _ in range(20):
            trace.event("task", "task.retry")
        assert len(trace.tracer().spans()) <= 5
        assert trace.tracer().dropped >= 15

    def test_chrome_trace_export_is_valid(self, traced, tmp_path):
        with trace.query_scope():
            with trace.span("task", "task.attempt", partition=1):
                trace.event("program", "program.hit", site="x")
        path = str(tmp_path / "trace.json")
        n = trace.export_chrome(path)
        assert n >= 3
        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list)
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X"
            assert isinstance(ev["ts"], float)
            assert isinstance(ev["dur"], float)
            assert "name" in ev and "pid" in ev and "tid" in ev
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert {"query.execute", "task.attempt", "program.hit"} <= names

    def test_jsonl_round_trip(self, traced, tmp_path):
        with trace.span("spill", "spill.run_write", consumer="c",
                        batches=3):
            pass
        path = str(tmp_path / "trace.jsonl")
        n = trace.export_jsonl(path)
        loaded = trace.read_jsonl(path)
        assert len(loaded) == n
        orig = trace.tracer().spans()
        for a, b in zip(orig, loaded):
            assert (a.trace_id, a.span_id, a.parent_id, a.cat, a.name,
                    a.tid, a.attrs) == \
                   (b.trace_id, b.span_id, b.parent_id, b.cat, b.name,
                    b.tid, b.attrs)
            assert abs(a.dur_ns - b.dur_ns) < 1000   # µs serialization

    def test_filtered_query_category_still_scopes(self, traced, tmp_path):
        """auron.trace.events without 'query' (the CONFIG.md example)
        must not leak query_depth or skip the trace-dir export."""
        traced.set(cfg.TRACE_EVENTS, "task,shuffle,fault")
        traced.set(cfg.TRACE_DIR, str(tmp_path))
        try:
            with trace.query_scope(label="a") as s1:
                trace.event("task", "task.retry")
            assert s1.trace_id > 0
            assert any(p.name.endswith(".jsonl")
                       for p in tmp_path.iterdir())
            # depth unwound: the next scope is outermost again and
            # rotates to a fresh trace id
            with trace.query_scope(label="b") as s2:
                pass
            assert s2.trace_id == s1.trace_id + 1
        finally:
            traced.unset(cfg.TRACE_DIR)

    def test_out_of_order_span_exit_unwinds_stack(self, traced):
        """Spans wrapping generators can exit out of LIFO order (a
        merge interleaving two streams); the dead id must not stay on
        the thread stack and misparent later spans."""
        a = trace.span("shuffle", "shuffle.fetch", side="left")
        a.__enter__()
        b = trace.span("shuffle", "shuffle.fetch", side="right")
        b.__enter__()
        a.__exit__(None, None, None)      # left stream exhausts first
        b.__exit__(None, None, None)
        trace.event("task", "task.retry")
        ev = next(s for s in trace.tracer().spans()
                  if s.name == "task.retry")
        assert ev.parent_id == 0          # stack fully unwound

    def test_query_scope_exports_to_trace_dir(self, traced, tmp_path):
        traced.set(cfg.TRACE_DIR, str(tmp_path))
        try:
            with trace.query_scope(label="q"):
                trace.event("task", "task.retry")
            files = sorted(p.name for p in tmp_path.iterdir())
            assert any(f.endswith(".json") for f in files)
            assert any(f.endswith(".jsonl") for f in files)
            # exported spans are dropped from the buffer (memory bound)
            assert trace.tracer().spans() == []
            # and the thread's trace id is cleared: between-query spans
            # must not tag onto the exported (dropped) trace
            assert trace.tracer().current_trace == 0
        finally:
            traced.unset(cfg.TRACE_DIR)


# ---------------------------------------------------------------------------
# engine emission: task spans, program builds, shuffle fetches
# ---------------------------------------------------------------------------

class TestEngineSpans:
    def test_query_produces_task_compile_and_shuffle_spans(self, traced):
        from auron_tpu.frontend.dataframe import col, functions as F
        from auron_tpu.frontend.session import Session

        rng = np.random.default_rng(7)
        t = pa.table({"k": rng.integers(0, 8, 1024),
                      "v": rng.normal(size=1024)})
        s = Session()
        df = (s.from_arrow(t).repartition(2, "k").group_by("k")
              .agg(F.sum(col("v")).alias("sv")))
        out = s.execute(df)
        assert out.num_rows == 8
        spans = trace.tracer().spans()
        names = {sp.name for sp in spans}
        assert "query.execute" in names
        assert "task.attempt" in names
        assert "shuffle.fetch" in names        # >=1 shuffle fetch
        cats = {sp.cat for sp in spans}
        assert "program" in cats               # >=1 build or hit
        # task spans nest under the query root
        root = next(sp for sp in spans if sp.name == "query.execute")
        tasks = [sp for sp in spans if sp.name == "task.attempt"]
        assert tasks and all(sp.trace_id == root.trace_id
                             for sp in tasks)

    def test_retry_event_carries_backoff(self, traced):
        from auron_tpu.runtime.executor import run_task_with_retries

        class Flaky(FilterOp):
            name = "flaky"
            fusable = False
            attempts = 0

            def execute(self, partition, ctx):
                type(self).attempts += 1
                if type(self).attempts == 1:
                    raise IOError("transient blip")
                return super().execute(partition, ctx)

        op = Flaky(_scan(), [ir.BinaryExpr(
            ">", ir.ColumnRef(2), ir.Literal(50, DataType.INT32))])
        conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
        run_task_with_retries(op, 0, 1, config=conf)
        retries = [s for s in trace.tracer().spans()
                   if s.name == "task.retry"]
        assert len(retries) == 1
        assert retries[0].attrs["error"] == "OSError"
        assert "backoff_s" in retries[0].attrs


# ---------------------------------------------------------------------------
# mirrored metric tree / EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

class TestMetricTree:
    def test_positional_mirroring_two_same_named_ops(self):
        """Two FilterOps in one plan must attribute DIFFERENT
        output_rows to their own nodes (per-instance sets), while the
        legacy name-keyed aggregate still sees the sum."""
        scan = _scan(rows=512)
        gt20 = FilterOp(scan, [ir.BinaryExpr(
            ">", ir.ColumnRef(2), ir.Literal(20, DataType.INT32))])
        gt80 = FilterOp(gt20, [ir.BinaryExpr(
            ">", ir.ColumnRef(2), ir.Literal(80, DataType.INT32))])
        conf = cfg.AuronConfig().set(cfg.FUSION_ENABLED, False)
        tree, table = mt.explain_analyze(gt80, num_partitions=1,
                                         config=conf)
        outer, inner, leaf = tree, tree.children[0], \
            tree.children[0].children[0]
        assert leaf.name == "memory_scan"
        assert leaf.metrics["output_rows"] == 512
        assert inner.metrics["output_rows"] > outer.metrics["output_rows"]
        assert outer.metrics["output_rows"] == table.num_rows
        # positional congruence with the plan tree
        assert inner.name == outer.name == "filter"

    def test_explain_analyze_fused_plan_all_nodes_nonzero(self):
        """The acceptance shape: a fused Session plan where EVERY node
        shows nonzero elapsed_compute and output_rows."""
        from auron_tpu.frontend.dataframe import col, functions as F
        from auron_tpu.frontend.session import Session

        rng = np.random.default_rng(11)
        t = pa.table({"k": rng.integers(0, 8, 2048),
                      "v": rng.normal(size=2048),
                      "c": rng.integers(0, 100, 2048)})
        s = Session()
        df = (s.from_arrow(t)
              .filter(col("c") > 10)
              .select(col("k"), (col("v") * 2.0).alias("v2"))
              .group_by("k").agg(F.sum(col("v2")).alias("sv")))
        op = s.plan_physical(df)
        tree, table = mt.explain_analyze(op, num_partitions=1,
                                         config=s.config)
        assert table.num_rows == 8
        nodes = list(tree.walk())
        assert len(nodes) >= 3
        for n in nodes:
            assert n.metrics.get("output_rows", 0) > 0, n.op_repr
            assert n.metrics.get("elapsed_compute", 0) > 0, n.op_repr
        # the DSL face renders the same tree
        text = df.explain(analyze=True)
        assert "output_rows=" in text and "elapsed_compute=" in text
        # one line per node, then the footer: the per-query
        # program-cache line (the shared central cache means a query's
        # hit rate is its OWN ledger's, surfaced here) and the
        # process's result-cache line — counted as the renderer writes
        # them, one ``[name] ...`` line each
        lines = text.splitlines()
        footer = [l for l in lines if l.startswith("[")]
        assert len(lines) - len(footer) == len(nodes)
        assert lines[-len(footer):] == footer
        assert [l.split("]")[0] for l in footer] == ["[program cache",
                                                      "[result cache"]
        assert "[program cache] builds=" in text and "hit_rate=" in text

    def test_render_formats_and_totals(self):
        node = mt.MetricNode("sort", "SortOp", {"elapsed_compute": 2_500_000,
                                                "output_rows": 10},
                             [mt.MetricNode("scan", "ScanOp",
                                            {"output_rows": 20,
                                             "elapsed_compute": 1_000_000})])
        text = mt.render(node)
        assert "SortOp" in text and "2.5ms" in text
        assert text.index("SortOp") < text.index("ScanOp")
        tot = mt.totals(node)
        assert tot == {"nodes": 2, "elapsed_compute_ms": 3.5,
                       "output_rows": 30}


# ---------------------------------------------------------------------------
# process registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_histogram_percentiles(self):
        r = obs_registry.MetricsRegistry()
        h = r.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0, 10.0))
        for v in [0.005] * 50 + [0.05] * 40 + [5.0] * 10:
            h.observe(v)
        assert h.count == 100
        # ranks: 50 values <=0.01, 90 <=0.1, the last 10 in (1, 10]
        assert h.percentile(0.50) <= 0.01
        assert 0.01 < h.percentile(0.85) <= 0.1
        assert 1.0 < h.percentile(0.95) <= 10.0
        assert 1.0 < h.percentile(0.99) <= 10.0
        snap = r.snapshot()["lat_seconds"]
        assert snap["count"] == 100
        assert snap["p50"] <= 0.01 < snap["p99"]

    def test_prometheus_exposition(self):
        r = obs_registry.MetricsRegistry()
        r.counter("auron_test_total", site="a").inc(3)
        r.gauge("auron_test_gauge").set(7)
        r.histogram("auron_test_seconds", buckets=(1.0,)).observe(0.5)
        text = r.render_prometheus()
        assert '# TYPE auron_test_total counter' in text
        assert 'auron_test_total{site="a"} 3' in text
        assert "auron_test_gauge 7" in text
        assert 'auron_test_seconds_bucket{le="1"} 1' in text
        assert 'auron_test_seconds_bucket{le="+Inf"} 1' in text
        assert "auron_test_seconds_count 1" in text
        # the runtime collectors + trace_salt info ride every exposition
        assert "auron_info{trace_salt=" in text
        assert "auron_program_builds_total" in text

    def test_type_conflict_rejected(self):
        r = obs_registry.MetricsRegistry()
        r.counter("auron_x_total")
        with pytest.raises(TypeError):
            r.gauge("auron_x_total")

    def test_tasks_feed_registry(self):
        from auron_tpu.runtime.executor import collect
        r = obs_registry.get_registry()
        before = r.counter("auron_tasks_total").value
        collect(_scan(rows=64), num_partitions=1)
        assert r.counter("auron_tasks_total").value == before + 1

    def test_retries_feed_registry(self):
        """The retry counter must ride the FINALIZE snapshot (the raw
        ctx snapshot never contains recovery.transient_retries)."""
        from auron_tpu.runtime.executor import run_task_with_retries

        class FlakyOnce(FilterOp):
            name = "flaky_once"
            fusable = False
            attempts = 0

            def execute(self, partition, ctx):
                type(self).attempts += 1
                if type(self).attempts == 1:
                    raise IOError("transient blip")
                return super().execute(partition, ctx)

        r = obs_registry.get_registry()
        before = r.counter("auron_task_retries_total").value
        op = FlakyOnce(_scan(), [ir.BinaryExpr(
            ">", ir.ColumnRef(2), ir.Literal(50, DataType.INT32))])
        conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
        run_task_with_retries(op, 0, 1, config=conf)
        assert r.counter("auron_task_retries_total").value == before + 1

    def test_registry_disabled_skips_feeding(self):
        from auron_tpu.runtime.executor import collect
        conf = cfg.get_config()
        conf.set(cfg.METRICS_REGISTRY, False)
        try:
            r = obs_registry.get_registry()
            before = r.counter("auron_tasks_total").value
            collect(_scan(rows=64), num_partitions=1)
            assert r.counter("auron_tasks_total").value == before
        finally:
            conf.unset(cfg.METRICS_REGISTRY)


# ---------------------------------------------------------------------------
# chaos correlation + overhead smoke
# ---------------------------------------------------------------------------

class TestChaosCorrelation:
    def test_fault_site_links_to_recovery_spans(self, tmp_path):
        """A chaos run's outcome carries the site→recovery correlation:
        injected spill.read IO errors trigger task retries, and the
        report links them."""
        from auron_tpu.it import chaos

        scenario = chaos.spill_sort(str(tmp_path))
        out = chaos.run_chaos(scenario, "spill.read:io_error@1.0", seed=1)
        assert out.trace_id > 0
        assert out.status in ("identical", "classified")
        assert "spill.read" in out.correlation
        c = out.correlation["spill.read"]
        assert c["injected"] >= 1
        assert c["fault_spans"]
        assert c["recovery"].get("task.retry", 0) >= 1
        # tracing is restored off afterwards
        assert not trace.enabled()


class TestOverheadHarness:
    def test_trace_overhead_ab_smoke(self, monkeypatch):
        """The bench A/B harness computes a finite overhead figure on a
        tiny subset (the <2% acceptance gate itself is measured by
        bench.py at real scale, not asserted here — a 64-row CI box
        cannot measure 2%)."""
        monkeypatch.setenv("AURON_BENCH_TRACE_SCALE", "0.002")
        monkeypatch.setenv("AURON_BENCH_TRACE_REPS", "1")
        monkeypatch.setenv("AURON_BENCH_TRACE_QUERIES", "q3")
        import bench   # env knobs are read at call time, no reload
        try:
            res = bench.bench_trace_overhead()
        finally:
            cfg.get_config().unset(cfg.TRACE_ENABLED)
            trace.reset()
        assert res["trace_ab_queries"] == ["q3"]
        assert res["trace_ab_off_s"] > 0
        assert res["trace_ab_on_s"] > 0
        assert res["trace_ab_noprofile_s"] > 0
        assert np.isfinite(res["trace_overhead_pct"])
        assert res["trace_overhead_gate_pct"] == 2.0
        # the third arm: profiler-attribution overhead (PR 6 <2% gate,
        # measured at real scale by bench.py — finiteness only here)
        assert np.isfinite(res["profile_overhead_pct"])
        assert res["profile_overhead_gate_pct"] == 2.0
        # the fourth arm: always-on flight recorder (ISSUE 14 — armed
        # recorder, trace export off, the shipping posture)
        assert res["trace_ab_norecorder_s"] > 0
        assert np.isfinite(res["flight_overhead_pct"])
        assert res["flight_overhead_gate_pct"] == 2.0
        assert res["trace_ab_spans"] > 0
        assert not trace.enabled()
        from auron_tpu.obs import profile as obs_profile
        assert obs_profile.enabled()   # default restored
        from auron_tpu.obs import flight_recorder as _flight
        assert _flight.armed()         # default restored
