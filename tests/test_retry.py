"""Partition-granularity task retries (SURVEY §5.3 — the retry driver
the reference delegates to Spark's scheduler; here the driver collect
path owns it). The engine is functional so a retry is an exact
recompute; cancellation is never retried."""

import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu import errors
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.base import PhysicalOp, TaskCancelled
from auron_tpu.runtime import executor
from auron_tpu.runtime.executor import (ExecutionRuntime, TaskDefinition,
                                        collect, run_task_with_retries)


class FlakyOp(PhysicalOp):
    """Pass-through operator whose host-side stream raises for the first
    N attempts (a transient external dependency: remote-FS blip, RSS
    hiccup). Attempt counting is per instance, mimicking external state
    that heals between attempts."""

    name = "flaky"

    def __init__(self, child, failures: int, exc=IOError):
        self.child = child
        self.failures = failures
        self.exc = exc
        self.attempts = 0

    @property
    def children(self):
        return [self.child]

    def schema(self):
        return self.child.schema()

    def execute(self, partition, ctx):
        self.attempts += 1
        if self.attempts <= self.failures:
            raise self.exc("transient backend failure (injected)")
        yield from self.child.execute(partition, ctx)


def _scan():
    rb = pa.record_batch({"x": pa.array([1, 2, 3, 4], pa.int64())})
    return MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=8)


def test_transient_failure_retried():
    op = FlakyOp(_scan(), failures=1)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
    out = collect(op, num_partitions=1, config=conf)
    assert out.column("x").to_pylist() == [1, 2, 3, 4]
    assert op.attempts == 2            # one failure + one clean rerun


def test_retries_exhausted_raises_last_error():
    op = FlakyOp(_scan(), failures=10)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
    with pytest.raises(IOError, match="transient"):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 3            # initial attempt + 2 retries


def test_zero_retries_fail_fast():
    op = FlakyOp(_scan(), failures=1)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 0)
    with pytest.raises(IOError):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_deterministic_valueerror_not_retried():
    """A ValueError is a deterministic engine/plan defect (shape
    mismatch, violated kernel bound): recomputing cannot succeed, so it
    surfaces on the first attempt (ADVICE round 5)."""
    op = FlakyOp(_scan(), failures=10, exc=ValueError)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 3)
    with pytest.raises(ValueError):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_deterministic_runtimeerror_patterns_not_retried():
    """RuntimeErrors carrying shape/lowering signatures are XLA's
    deterministic-defect class and must not retry."""
    def exc(msg):
        return RuntimeError("Mosaic lowering failed: unsupported op")
    op = FlakyOp(_scan(), failures=10, exc=exc)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 3)
    with pytest.raises(RuntimeError, match="lowering"):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_transient_runtimeerror_still_retried():
    """Plain RuntimeErrors (external services, resource blips) keep
    retrying — only the deterministic message patterns are excluded."""
    def exc(msg):
        return RuntimeError("connection reset by peer")
    op = FlakyOp(_scan(), failures=1, exc=exc)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
    out = run_task_with_retries(op, 0, 1, config=conf)
    assert out.column("x").to_pylist() == [1, 2, 3, 4]
    assert op.attempts == 2


def test_cancellation_not_retried():
    op = FlakyOp(_scan(), failures=10, exc=lambda msg: TaskCancelled())
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 3)
    with pytest.raises(TaskCancelled):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_no_message_pattern_matching_left_on_retry_path():
    """The retry driver routes purely on the error taxonomy: the
    _NO_RETRY_RUNTIME_PATTERNS table and its matcher are gone from the
    executor (classification of XLA's ambiguous RuntimeErrors happens
    once, at the device-compute boundary, via errors.classify_runtime)."""
    assert not hasattr(executor, "_NO_RETRY_RUNTIME_PATTERNS")
    assert not hasattr(executor, "_is_deterministic_failure")


@pytest.mark.parametrize("exc_cls", [
    errors.DeviceExecutionError,   # transient device/backend blip
    errors.RssUnavailableError,    # RSS service IO failure
    errors.SpillIOError,           # spill-file IO failure
    errors.SpillCorruption,        # per-attempt artifact: recompute rewrites
    errors.StorageIOError,
])
def test_transient_taxonomy_classes_retried(exc_cls):
    assert errors.is_transient(exc_cls("injected"))
    op = FlakyOp(_scan(), failures=1, exc=exc_cls)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 2)
    out = run_task_with_retries(op, 0, 1, config=conf)
    assert out.column("x").to_pylist() == [1, 2, 3, 4]
    assert op.attempts == 2


@pytest.mark.parametrize("exc_cls", [
    errors.KernelLoweringError,    # deterministic lowering/shape defect
    errors.InjectedFatalError,     # chaos plans' deterministic kind
    errors.ShuffleCorruption,      # needs map recompute, not reducer rerun
    errors.PlanError,
])
def test_deterministic_taxonomy_classes_fail_fast(exc_cls):
    assert not errors.is_transient(exc_cls("injected"))
    op = FlakyOp(_scan(), failures=10, exc=exc_cls)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 3)
    with pytest.raises(exc_cls):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_classify_runtime_splits_xla_ambiguity():
    """The device-compute boundary's classifier: lowering/shape
    signatures become the deterministic class, anything else the
    transient class — and both land in the legacy RuntimeError family
    so existing except sites keep working."""
    det = errors.classify_runtime(RuntimeError("Mosaic lowering failed"))
    assert isinstance(det, errors.KernelLoweringError)
    assert isinstance(det, RuntimeError) and not det.transient
    trans = errors.classify_runtime(RuntimeError("connection reset"))
    assert isinstance(trans, errors.DeviceExecutionError)
    assert isinstance(trans, RuntimeError) and trans.transient


def test_classify_runtime_shields_notimplemented():
    """Error-taxonomy trap: NotImplementedError IS-A RuntimeError, so
    classify_runtime must route it (and TypeError-adjacent lowering
    errors) by NO_RETRY_TYPES membership BEFORE the generic
    RuntimeError message split — returned unchanged (non-transient),
    never re-wrapped as the transient device class."""
    e = NotImplementedError("unsupported plan shape")
    out = errors.classify_runtime(e)
    assert out is e                          # original type survives
    assert not errors.is_transient(out)
    assert not isinstance(out, errors.DeviceExecutionError)
    te = TypeError("jit traced a non-hashable static argument")
    assert errors.classify_runtime(te) is te
    assert not errors.is_transient(te)


def test_notimplemented_fails_fast_through_retry_driver():
    """End to end: a NotImplementedError surfacing through the device
    boundary reaches the caller on the FIRST attempt, as itself."""
    op = FlakyOp(_scan(), failures=10, exc=NotImplementedError)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 3)
    with pytest.raises(NotImplementedError):
        run_task_with_retries(op, 0, 1, config=conf)
    assert op.attempts == 1


def test_exponential_backoff_full_jitter_bounds():
    from auron_tpu.runtime.executor import _retry_backoff_s
    assert _retry_backoff_s(5, base=0.0, cap=30.0) == 0.0
    for attempt in range(6):
        bound = min(4.0, 0.25 * 2 ** attempt)
        draws = [_retry_backoff_s(attempt, base=0.25, cap=4.0)
                 for _ in range(200)]
        assert all(0.0 <= d <= bound for d in draws)
        # full jitter: draws spread over the window, not a fixed point
        assert max(draws) - min(draws) > bound * 0.1


def test_finalize_snapshot_carries_recovery_counters():
    rb = pa.record_batch({"x": pa.array([1, 2], pa.int64())})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=8)
    rt = ExecutionRuntime(
        scan, TaskDefinition(partition_id=0, num_partitions=1, task_id=2),
        attempt=2, retry_stats={"transient_retries": 2})
    rt.collect()
    rec = rt.finalize()["recovery"]
    assert rec["attempts"] == 3
    assert rec["transient_retries"] == 2
    assert rec["corruption_recomputes"] == 0
    # the backend watchdog and its fallback counter are gone: a process
    # runs on the platform jax gives it
    assert "watchdog_fallbacks" not in rec
    assert rec["faults_injected"] == 0


def test_multi_partition_retries_only_failed_partition():
    class PartitionFlaky(FlakyOp):
        def execute(self, partition, ctx):
            if partition == 1:
                self.attempts += 1
                if self.attempts <= self.failures:
                    raise IOError("transient (partition 1 only)")
            yield from self.child.execute(partition, ctx)

    rb = pa.record_batch({"x": pa.array([1, 2], pa.int64())})
    scan = MemoryScanOp([[rb], [rb]], schema_from_arrow(rb.schema),
                        capacity=8)
    op = PartitionFlaky(scan, failures=1)
    conf = cfg.AuronConfig().set(cfg.TASK_MAX_RETRIES, 1)
    out = collect(op, num_partitions=2, config=conf)
    assert sorted(out.column("x").to_pylist()) == [1, 1, 2, 2]
    assert op.attempts == 2
