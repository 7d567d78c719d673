"""Profiler hooks — ``auron.profile.enabled`` (PR 6, obs/profile.py):
host/device time attribution — per-operator ``elapsed_device`` +
``elapsed_host_*`` buckets, the program-call wrapper, and the near-zero
disabled path.
"""

import numpy as np
import pyarrow as pa

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import profile as obs_profile
from auron_tpu.runtime.executor import ExecutionRuntime, TaskDefinition

C = ir.ColumnRef


# ---------------------------------------------------------------------------
# host/device attribution (obs/profile.py — PR 6)
# ---------------------------------------------------------------------------

def _run_project_plan(n=8192, config=None):
    """scan → project(k+1, v*2): one compute operator whose only timed
    section is the project kernel — the cleanest attribution target."""
    from auron_tpu.ops.project import ProjectOp
    rng = np.random.default_rng(0)
    rb = pa.record_batch({"k": pa.array(rng.integers(0, 100, n),
                                        pa.int64()),
                          "v": pa.array(rng.normal(size=n))})
    schema = schema_from_arrow(rb.schema)
    scan = MemoryScanOp([[rb]], schema, capacity=n)
    from auron_tpu.columnar.schema import DataType
    op = ProjectOp(scan, [
        ir.BinaryExpr("+", C(0), ir.Literal(1, DataType.INT64)),
        ir.BinaryExpr("*", C(1), ir.Literal(2.0, DataType.FLOAT64))],
        ["k1", "v2"])
    rt = ExecutionRuntime(op, TaskDefinition(task_id=7), config=config)
    tbl = rt.collect()
    assert tbl.num_rows == n
    return op, rt


class TestAttribution:
    def test_attribution_sums_to_wall(self):
        """Per-operator invariant: elapsed_device + every elapsed_host_*
        bucket equals elapsed_compute (the timer's measured wall) within
        clock-granularity tolerance — the 'other' residue bucket makes
        the identity hold by construction."""
        op, rt = _run_project_plan()
        sets = rt.ctx.op_metric_sets(op)
        assert sets, "project recorded no per-instance metrics"
        snap = sets[0].snapshot()
        wall = snap["elapsed_compute"]
        assert wall > 0
        # the project is the plan's root, and the executor books its own
        # drive loop and the result's to_arrow on the root node as the
        # iter / convert buckets OUTSIDE any timer (a cold to_arrow is
        # tens of ms when this test is the process's first): they are
        # not part of the timer's identity
        attributed = snap.get("elapsed_device", 0) + sum(
            v for k, v in snap.items() if k.startswith("elapsed_host_")
            and k not in ("elapsed_host_convert", "elapsed_host_iter"))
        assert attributed > 0
        # within 5% of wall (the flush itself costs a few clock reads)
        assert abs(attributed - wall) <= max(wall * 0.05, 200_000), snap

    def test_program_calls_record_device_time(self, monkeypatch):
        """The registry's ProfiledProgram wrapper records the dispatch
        of every call and never waits: the operator's device time is
        exactly what the executor's fence at to_arrow measured (what
        test_pipeline's attribution test shows for a scan)."""
        fences = []
        real_fence = obs_profile.device_fence

        def fence(value, sink=None):
            ns = real_fence(value, sink)
            fences.append(ns)
            return ns

        monkeypatch.setattr(obs_profile, "device_fence", fence)
        op, rt = _run_project_plan()
        snap = rt.ctx.op_metric_sets(op)[0].snapshot()
        assert snap.get("elapsed_host_dispatch", 0) > 0, snap
        assert len(fences) == 1                    # one batch, one fence
        assert snap.get("elapsed_device", 0) == fences[0] > 0, snap

    def test_disabled_path_records_nothing(self):
        conf = cfg.AuronConfig({cfg.PROFILE_ENABLED: False})
        # the knob is read from the PROCESS config by the registry
        # wrapper; pin it globally for the duration
        g = cfg.get_config()
        g.set(cfg.PROFILE_ENABLED, False)
        try:
            op, rt = _run_project_plan(config=conf)
            snap = rt.ctx.op_metric_sets(op)[0].snapshot()
            assert "elapsed_device" not in snap, snap
            assert not any(k.startswith("elapsed_host_") for k in snap), \
                snap
            assert obs_profile.push_frame() is None
        finally:
            g.unset(cfg.PROFILE_ENABLED)

    def test_wrapper_passthrough_and_identity(self):
        """The registry memo keeps the RAW program; the wrapper is
        transparent to attribute access and disappears when profiling
        is off."""
        from auron_tpu.runtime import programs
        cache = programs.ProgramCache("test.profile.site", maxsize=4)

        def build():
            def kern(x):
                return x + 1
            kern.marker = "raw"
            return kern

        g = cfg.get_config()
        g.set(cfg.PROFILE_ENABLED, True)
        try:
            v1, built = cache.get_or_build(("a",), build)
            assert built
            assert isinstance(v1, obs_profile.ProfiledProgram)
            assert v1.marker == "raw"      # __getattr__ passthrough
            assert v1(41) == 42
            g.set(cfg.PROFILE_ENABLED, False)
            v2, built = cache.get_or_build(("a",), build)
            assert not built               # memo hit on the raw value
            assert not isinstance(v2, obs_profile.ProfiledProgram)
            assert v2.marker == "raw"
        finally:
            g.unset(cfg.PROFILE_ENABLED)

    def test_bucket_hint_classifies_host_sections(self):
        """A kernel-free timer with a bucket hint classifies its whole
        wall into that bucket (scan decode → convert, shuffle serde →
        serde)."""
        import time

        from auron_tpu.ops.base import MetricsSet, timer
        ms = MetricsSet()
        with timer(ms.counter("io_time"), bucket="convert"):
            time.sleep(0.002)
        snap = ms.snapshot()
        assert snap.get("elapsed_host_convert", 0) > 1_000_000, snap
        assert "elapsed_host_other" not in snap or \
            snap["elapsed_host_other"] < snap["elapsed_host_convert"]

    def test_summarize_tree_rollup(self):
        from auron_tpu.obs import metric_tree as mt
        root = mt.MetricNode("a", "A", metrics={
            "elapsed_compute": 10_000_000, "elapsed_device": 6_000_000,
            "elapsed_host_dispatch": 3_000_000,
            "elapsed_host_other": 1_000_000})
        root.children.append(mt.MetricNode("b", "B", metrics={
            "elapsed_compute": 5_000_000,
            "elapsed_host_convert": 5_000_000}))
        s = obs_profile.summarize_tree(root)
        assert s["device_ms"] == 6.0
        assert s["host_ms"] == 9.0
        assert s["host_buckets_ms"] == {"dispatch": 3.0, "convert": 5.0,
                                        "other": 1.0}
        assert s["elapsed_compute_ms"] == 15.0
