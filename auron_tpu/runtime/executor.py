"""Per-task execution runtime.

The analogue of the reference's NativeExecutionRuntime (reference:
native-engine/auron/src/rt.rs:64-300): owns one partition's execution of a
physical plan — drives the operator stream, surfaces batches to the caller
(host Arrow or downstream stage), translates failures, and mirrors metrics
back on finalize. The tokio runtime + 1-slot channel of the reference maps
to the double-buffered generator chain here: jax dispatch is already async
(XLA executions overlap with host orchestration until a result is read).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

import pyarrow as pa

from auron_tpu.columnar.arrow_bridge import to_arrow
from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp

logger = logging.getLogger("auron_tpu")

_SENTINEL = object()


def lookahead(it: Iterator, depth: int = 1) -> Iterator:
    """Double-buffered drive: pull item N+1 from ``it`` BEFORE yielding
    item N, so the producer's async work (kernel dispatch, prefetch
    refill) for the next batch is already queued while the consumer
    blocks on the current one (host materialization, sink writes).

    Order is preserved exactly — this is a window, not a reorder. A
    producer exception surfaces on the pull that raised it, which is up
    to ``depth`` items earlier than plain iteration would have surfaced
    it; all-or-nothing consumers (collect) can't tell the difference.
    ``close()`` propagates to the inner iterator so cancellation
    unwinds generators exactly as plain iteration does."""
    if depth <= 0:
        yield from it
        return
    it = iter(it)
    window: list = []
    try:
        for _ in range(depth):
            item = next(it, _SENTINEL)
            if item is _SENTINEL:
                break
            window.append(item)
        while window:
            nxt = next(it, _SENTINEL)
            yield window.pop(0)
            if nxt is not _SENTINEL:
                window.append(nxt)
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


@dataclass
class TaskDefinition:
    """Mirror of the proto TaskDefinition (reference: auron.proto:804-813)."""
    stage_id: int = 0
    partition_id: int = 0
    task_id: int = 0
    num_partitions: int = 1


class ExecutionRuntime:
    """Runs one (plan, partition) pair to completion.

    ``attempt``/``retry_stats`` carry the retry driver's recovery
    counters into the finalize snapshot: the runtime that finally
    succeeds reports how many attempts the task took."""

    def __init__(self, plan: PhysicalOp, task: TaskDefinition,
                 mem_manager=None, config=None, attempt: int = 0,
                 retry_stats: Optional[dict] = None, cancel_token=None):
        self.plan = plan
        self.task = task
        self.attempt = attempt
        self.retry_stats = retry_stats if retry_stats is not None else {}
        self.cancel_token = cancel_token
        self.ctx = ExecContext(
            stage_id=task.stage_id,
            partition_id=task.partition_id,
            task_id=task.task_id,
            num_partitions=task.num_partitions,
            mem_manager=mem_manager,
            config=config,
        )
        if cancel_token is not None:
            # the query's CancelToken IS the task's cancellation
            # registry: socket-level CANCEL, session.cancel(query_id)
            # and deadline expiry all land through one mechanism
            self.ctx.cancel_event = cancel_token
        self._started = time.time()
        # per-task XLA compile attribution (round-5 directive 7): NEW
        # program builds during this task surface in the finalize metrics
        try:
            from auron_tpu.utils import compile_stats
            self._compile_start = compile_stats.snapshot()
        except Exception:
            self._compile_start = None
        # per-task program-cache attribution (central registry,
        # runtime/programs.py): builds vs hits across every compile
        # site. Under the concurrent scheduler a PROCESS-total delta
        # would blame this task for neighbor queries' compiles, so when
        # the task runs under a query token the delta is taken from the
        # per-QUERY ledger instead (cross-query safety audit). The
        # ledger is only WRITTEN while the lifecycle thread-local is
        # bound to this query (Session/serving do that); a bare token
        # handed straight to collect() would read permanent zeros from
        # it, so such callers keep the legacy process delta.
        try:
            from auron_tpu.runtime import lifecycle, programs
            qid = (getattr(cancel_token, "query_id", "")
                   if cancel_token is not None else "")
            self._programs_query = \
                qid if qid and lifecycle.current_query_id() == qid else ""
            self._programs_start = (
                programs.query_totals(self._programs_query)
                if self._programs_query else programs.totals())
        except Exception:
            self._programs_query = ""
            self._programs_start = None
        # per-task fault attribution (runtime/faults)
        from auron_tpu.runtime import faults as _faults
        self._faults_start = _faults.totals()

    def cancel(self) -> None:
        """Tear the running task down: operators polling the context's
        cancellation registry unwind within one batch (reference:
        cancel_all_tasks, rt.rs:296)."""
        self.ctx.cancel()

    def batches(self) -> Iterator[DeviceBatch]:
        """Device-batch stream (stays on device; used for stage
        chaining). A device profile of it is any profiler session
        around the process (``jax.profiler.start_server``, the
        benchmark's slice): the layer spans of obs/trace.py annotate
        it, and one task cannot own the process's one session."""
        from auron_tpu import errors
        from auron_tpu.obs import trace
        from auron_tpu.ops.base import TaskCancelled
        from auron_tpu.runtime import faults, watchdog
        # drive-loop glue (cancel polls, fault checks, generator
        # bookkeeping between batches) attributed to the ROOT plan node
        # as the "iter" host bucket — the per-batch host tax the fused
        # pipelines pay even when every kernel is warm
        iter_c = (self.ctx.metrics_for(self.plan)
                  .counter("elapsed_host_iter")
                  if _profile.enabled() else None)
        # stall-watchdog heartbeat: per ATTEMPT (a retry self-clears the
        # stall flag by registering a fresh heartbeat); None disarmed
        hb = watchdog.register_heartbeat(
            task_id=self.task.task_id, stage_id=self.task.stage_id,
            partition_id=self.task.partition_id, attempt=self.attempt,
            config=self.ctx.config)
        self.ctx.heartbeat = hb
        try:
            with trace.span("task", "task.attempt",
                            stage=self.task.stage_id,
                            partition=self.task.partition_id,
                            task=self.task.task_id,
                            attempt=self.attempt):
                for batch in self.plan.execute(self.task.partition_id,
                                               self.ctx):
                    t0 = (time.perf_counter_ns() if iter_c is not None
                          else 0)
                    # checkpoint covers the lifecycle plane: heartbeat,
                    # cancel.race / task.hang injection, cancel raise
                    self.ctx.checkpoint("task.batch")
                    faults.maybe_fail("device.compute",
                                      errors.DeviceExecutionError)
                    if iter_c is not None:
                        iter_c.add(time.perf_counter_ns() - t0)
                    yield batch
        except (TaskCancelled, errors.QueryCancelled):
            # reference behavior: task-kill is teardown, not failure
            # (is_task_running checks, rt.rs:208-238); the classified
            # QueryCancelled/DeadlineExceeded unwind the same way but
            # keep their verdict for the caller
            logger.info(
                "task cancelled: stage=%d partition=%d task=%d",
                self.task.stage_id, self.task.partition_id,
                self.task.task_id)
            raise
        except NotImplementedError:
            # the one NO_RETRY_TYPES member that IS a RuntimeError
            # subclass: shield it from classify_runtime below (callers
            # catch it to reject unsupported plans); the other
            # deterministic builtins reach the generic handler unchanged
            logger.exception(
                "task failed: stage=%d partition=%d task=%d",
                self.task.stage_id, self.task.partition_id, self.task.task_id)
            raise
        except RuntimeError as e:
            # the device-compute boundary: XLA wraps BOTH transient
            # resource failures and deterministic lowering defects in
            # bare RuntimeError — classify here, at the boundary that
            # owns the ambiguity, so the retry driver routes purely on
            # the AuronError taxonomy (classified errors pass through)
            logger.exception(
                "task failed: stage=%d partition=%d task=%d",
                self.task.stage_id, self.task.partition_id, self.task.task_id)
            if isinstance(e, errors.AuronError):
                raise
            raise errors.classify_runtime(e) from e
        except Exception:
            # real failures surface with task identity attached
            logger.exception(
                "task failed: stage=%d partition=%d task=%d",
                self.task.stage_id, self.task.partition_id, self.task.task_id)
            raise
        finally:
            watchdog.unregister_heartbeat(hb)

    def arrow_batches(self) -> Iterator[pa.RecordBatch]:
        """Host materialization (the FFI export boundary of the reference).

        The drive is double-buffered (``lookahead``): batch N+1 is
        pulled from the operator chain — dispatching its kernels
        asynchronously and refilling the scan prefetcher — BEFORE batch
        N materializes to Arrow, so the device computes N+1 while the
        host converts N. to_arrow is the semantic sync point; the wait
        for N's in-flight arrays is fenced explicitly there and
        attributed to the root node's ``elapsed_device``.

        The device→host export runs jitted gather/concat programs, so
        XLA's ambiguous RuntimeErrors surface here exactly as they do in
        the compute loop — classify them at this boundary too, or a
        deterministic lowering defect in the export path would retry as
        if transient."""
        from auron_tpu import errors
        from auron_tpu.obs import trace
        schema = self.plan.schema()
        profiling = _profile.enabled()
        # the root plan node's metrics take what happens outside any
        # operator's timer: the fence's device wait, and the device→host
        # materialization (pure arrow↔jax conversion) as the "convert"
        # host bucket
        fence_sink = self.ctx.metrics_for(self.plan) if profiling else None
        convert_c = (fence_sink.counter("elapsed_host_convert")
                     if profiling else None)
        for batch in lookahead(self.batches(), depth=1):
            rb = None
            # the span closes before the yield: the consumer's time is
            # not this layer's
            with trace.layer_span("convert", "to_arrow"):
                if fence_sink is not None:
                    # materialization boundary: wait out batch N's
                    # in-flight kernels HERE (N+1 is already dispatched)
                    # and book the wait as device time — BEFORE the
                    # num_rows readback below silently absorbs it
                    _profile.device_fence(batch, fence_sink)
                if _profile.row_count(batch) > 0:
                    t0 = (time.perf_counter_ns() if convert_c is not None
                          else 0)
                    try:
                        rb = to_arrow(batch, schema)
                    except NotImplementedError:
                        raise
                    except RuntimeError as e:
                        if isinstance(e, errors.AuronError):
                            raise
                        logger.exception(
                            "host materialization failed: stage=%d "
                            "partition=%d task=%d", self.task.stage_id,
                            self.task.partition_id, self.task.task_id)
                        raise errors.classify_runtime(e) from e
                    if convert_c is not None:
                        convert_c.add(time.perf_counter_ns() - t0)
            if rb is not None:
                yield rb

    def collect(self) -> pa.Table:
        from auron_tpu.columnar.arrow_bridge import schema_to_arrow
        batches = list(self.arrow_batches())
        if not batches:
            return pa.table(
                {f.name: [] for f in schema_to_arrow(self.plan.schema())},
                schema=schema_to_arrow(self.plan.schema()))
        return pa.Table.from_batches(batches)

    def finalize(self) -> dict:
        """Metric mirror-back (reference: update_metric_node,
        rt.rs:302-308)."""
        snap = self.ctx.metrics_snapshot()
        if self._compile_start is not None:
            from auron_tpu.utils import compile_stats
            d = compile_stats.delta(self._compile_start)
            snap["xla_compiles"] = d.count
            snap["xla_compile_seconds"] = round(d.seconds, 4)
        if self._programs_start is not None:
            from auron_tpu.runtime import programs
            now = (programs.query_totals(self._programs_query)
                   if self._programs_query else programs.totals())
            snap["program_builds"] = now.builds - self._programs_start.builds
            snap["program_hits"] = now.hits - self._programs_start.hits
        # recovery counters (robustness plane): attempts/retries from the
        # retry driver, corruption recomputes from the RSS exchange's
        # ctx counters (already under the "recovery" metrics key),
        # the fault delta from its monotonic total
        from auron_tpu.runtime import faults as _faults
        rec = snap.setdefault("recovery", {})
        rec.setdefault("corruption_recomputes", 0)
        rec["attempts"] = self.attempt + 1
        rec["transient_retries"] = self.retry_stats.get(
            "transient_retries", self.attempt)
        rec["faults_injected"] = _faults.totals() - self._faults_start
        # SPMD plane occupancy (process-level: the gang ledger spans
        # queries by design — one slot = the mesh)
        try:
            from auron_tpu.parallel import mesh as _mesh
            plane = _mesh.current_plane()
            if plane is not None:
                snap["mesh"] = plane.stats()
        except Exception:   # pragma: no cover - observability only  # graft: disable=GL004 -- observability export is best-effort by contract
            pass
        return snap


def _retry_backoff_s(attempt: int, base: float, cap: float) -> float:
    """Exponential backoff with FULL jitter (attempt k draws uniform
    from [0, min(cap, base * 2^k)]): concurrently failed partitions
    spread their retries instead of hammering the healing external
    system in lockstep."""
    import random
    if base <= 0:
        return 0.0
    return random.uniform(0.0, min(cap, base * (2.0 ** attempt)))


def _observe_task(rt: "ExecutionRuntime", table: pa.Table,
                  metric_tree=None) -> None:
    """Post-success observability for one task: mirror the per-op metric
    sets onto the positional metric tree (obs/metric_tree — the
    update_metric_node walk) and feed the process registry. Both halves
    are cheap and gated; failures here must never fail a finished
    task."""
    try:
        from auron_tpu.obs import metric_tree as mt
        from auron_tpu.obs import registry as obs_registry
        if metric_tree is not None:
            mt.mirror(metric_tree, rt.plan, rt.ctx)
        if obs_registry.enabled():
            # finalize(), not the raw ctx snapshot: only finalize
            # injects the recovery counters (transient_retries from the
            # retry driver) the registry exists to expose
            obs_registry.observe_task(
                time.time() - rt._started, rt.finalize(),
                output_rows=table.num_rows)
    except Exception:   # pragma: no cover - observability is best-effort
        logger.exception("task observability update failed")


def run_task_with_retries(plan: PhysicalOp, partition: int,
                          num_partitions: int, mem_manager=None,
                          config=None, metric_tree=None,
                          cancel_token=None) -> pa.Table:
    """Run one (plan, partition) task, retrying transient failures at
    partition granularity — the retry driver the reference delegates to
    Spark's task scheduler (SURVEY §5.3; rt.rs's is_task_running checks
    distinguish kill from failure the same way). The engine is
    functional, so an attempt is an exact recompute: sinks are
    retry-idempotent and RSS attempts invalidate, making re-execution
    safe end to end. Each attempt gets a fresh ExecutionRuntime and a
    distinct task_id (attempt number in the low bits, like Spark TIDs).

    Routing is purely the error taxonomy (auron_tpu/errors.py):
    classified errors carry their own ``transient`` verdict — the
    device-compute boundary classifies XLA's ambiguous RuntimeErrors
    before they get here, so NO message-pattern matching happens on the
    retry path. Cancellation is surfaced immediately, never retried
    (and its cancel-to-unwind latency feeds the registry histogram);
    a stall verdict (errors.TaskStalled) retries exactly ONCE.

    ``cancel_token`` (runtime/lifecycle.CancelToken) is the query's
    cancellation registry: checked before every attempt, installed as
    every runtime's cancel_event, and it bounds the backoff sleeps —
    clamped to the remaining deadline budget and woken by a cancel."""
    import time as _time

    from auron_tpu import config as cfg
    from auron_tpu import errors
    from auron_tpu.ops.base import TaskCancelled
    from auron_tpu.runtime import lifecycle

    conf = config if config is not None else cfg.get_config()
    # graft: disable=GL001 -- configuration values, host data
    retries = max(0, int(conf.get(cfg.TASK_MAX_RETRIES)))
    # graft: disable=GL001 -- configuration values, host data
    backoff = float(conf.get(cfg.TASK_RETRY_BACKOFF_S))
    # graft: disable=GL001 -- configuration values, host data
    backoff_cap = float(conf.get(cfg.TASK_RETRY_BACKOFF_MAX_S))
    retry_stats = {"transient_retries": 0, "stall_retries": 0}
    last_err = None
    for attempt in range(retries + 1):
        if cancel_token is not None:
            # a cancel that lands between attempts must not start one
            cancel_token.raise_for_status()
        rt = ExecutionRuntime(
            plan,
            TaskDefinition(partition_id=partition,
                           num_partitions=num_partitions,
                           task_id=partition * 1000 + attempt),
            mem_manager=mem_manager, config=config,
            attempt=attempt, retry_stats=retry_stats,
            cancel_token=cancel_token)
        try:
            table = rt.collect()
            _observe_task(rt, table, metric_tree)
            return table
        except TaskCancelled:
            raise
        except errors.QueryCancelled:
            # classified cancellation (cancel or deadline): surface
            # immediately and record how long the unwind took from the
            # moment the token flipped — the acceptance gate's number
            if cancel_token is not None:
                lifecycle.observe_unwind(
                    cancel_token, kind=cancel_token.reason or "cancel")
            raise
        except errors.TaskStalled as e:
            # the watchdog's verdict is transient ONCE: a wedged
            # external dependency may have healed, but an infinite
            # stall-retry loop would hide a deterministic wedge forever
            lifecycle.observe_unwind(_stall_latency_s(rt), kind="stall")
            if retry_stats["stall_retries"] >= 1 or attempt >= retries:
                raise
            retry_stats["stall_retries"] += 1
            retry_stats["transient_retries"] += 1
            last_err = e
            logger.warning(
                "task attempt %d/%d stalled for partition %d (%s); "
                "retrying once", attempt + 1, retries + 1, partition, e)
            from auron_tpu.obs import trace
            trace.event("task", "task.retry", partition=partition,
                        attempt=attempt, backoff_s=0.0,
                        error=type(e).__name__)
        except Exception as e:         # noqa: BLE001 — retry boundary
            # non-transient classes — plan/schema/engine defects,
            # classified corruption needing a DIFFERENT recovery
            # granularity (ShuffleCorruption → map recompute, not a
            # blind reducer rerun) — surface immediately instead of
            # paying retries+1 full computes; transient classes retry
            if not errors.is_transient(e):
                raise
            last_err = e
            if attempt >= retries:
                break
            retry_stats["transient_retries"] += 1
            if isinstance(e, errors.MeshUnavailable):
                # a device loss that ESCAPED the exchange's in-place
                # demotion (e.g. prior rounds' mesh-resident shards were
                # unreadable too): the retry re-routes against the
                # already-quarantined plane, so name that in the log —
                # this recompute will run host-side, not re-enter the
                # dead chip
                try:
                    from auron_tpu.parallel import mesh as _mesh
                    plane = _mesh.current_plane()
                    quarantined = (plane.quarantined()
                                   if plane is not None else [])
                except Exception:   # pragma: no cover - log best-effort
                    quarantined = []
                logger.warning(
                    "task attempt %d/%d lost a mesh device for "
                    "partition %d (%s); retrying against the "
                    "quarantined plane (quarantined=%s)",
                    attempt + 1, retries + 1, partition, e, quarantined)
            else:
                logger.warning(
                    "task attempt %d/%d failed for partition %d (%s); "
                    "retrying", attempt + 1, retries + 1, partition, e)
            delay = _retry_backoff_s(attempt, backoff, backoff_cap)
            if cancel_token is not None:
                rem = cancel_token.remaining()
                if rem is not None:
                    # never sleep past the deadline budget: a backoff
                    # that outlives the deadline just converts a retry
                    # into a guaranteed DeadlineExceeded later
                    delay = min(delay, rem)
            from auron_tpu.obs import trace
            trace.event("task", "task.retry", partition=partition,
                        attempt=attempt, backoff_s=round(delay, 4),
                        error=type(e).__name__)
            if delay > 0:
                if cancel_token is not None:
                    # interruptible: wakes (and raises) on cancellation
                    # instead of sleeping out the full jittered interval
                    cancel_token.sleep(delay)
                else:
                    _time.sleep(delay)
    raise last_err


def _stall_latency_s(rt: "ExecutionRuntime"):
    """Stall-flag-to-unwind latency of one attempt (None when the
    heartbeat carries no stall timestamp)."""
    hb = getattr(rt.ctx, "heartbeat", None)
    if hb is None or not getattr(hb, "stalled_at_ns", 0):
        return None
    import time as _time
    return (_time.monotonic_ns() - hb.stalled_at_ns) * 1e-9


def collect(plan: PhysicalOp, num_partitions: int = 1,
            mem_manager=None, config=None, metric_tree=None,
            cancel_token=None) -> pa.Table:
    """Run every partition of a plan and concatenate (driver-side
    collect), with per-partition transient-failure retries.
    ``metric_tree`` (obs/metric_tree.build_tree(plan)) accumulates every
    task's per-op metrics positionally — the EXPLAIN ANALYZE source.
    ``cancel_token`` threads the query's cancellation registry through
    every partition's retry driver."""
    from auron_tpu import errors as _errors
    from auron_tpu.runtime import lifecycle as _lifecycle
    from auron_tpu.runtime import scheduler as _scheduler
    # driver progress for the ops plane's /queries table: total stamped
    # up front, done bumped per finished partition (CancelToken carries
    # the counters; a bare Event / None costs nothing). Only the
    # OUTERMOST collect on a token tracks — a nested execute (host-fn
    # child, scalar subquery) rides the ENCLOSING token and must not
    # clobber the parent's progress
    track = (cancel_token is not None
             and getattr(cancel_token, "tasks_total", None) == 0)
    if track:
        cancel_token.tasks_total = num_partitions
        cancel_token.tasks_done = 0
    tables = []
    for p in range(num_partitions):
        # task-level fairness: a token admitted by the concurrent
        # scheduler carries its slot — take the weighted-round-robin
        # turn before each task so running queries interleave instead
        # of one query monopolizing the driver (one getattr for bare
        # tokens / direct collect calls)
        try:
            _scheduler.turn(cancel_token)
        except _errors.QueryCancelled:
            # a cancel landing during the fairness wait still counts
            # on the cancel-latency histogram (run_task_with_retries
            # observes mid-task cancels; this is the between-task site)
            _lifecycle.observe_unwind(
                cancel_token,
                kind=getattr(cancel_token, "reason", None) or "cancel")
            raise
        tables.append(run_task_with_retries(
            plan, p, num_partitions, mem_manager=mem_manager,
            config=config, metric_tree=metric_tree,
            cancel_token=cancel_token))
        if track:
            cancel_token.tasks_done += 1
    return pa.concat_tables(tables)
