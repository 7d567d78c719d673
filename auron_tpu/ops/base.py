"""Operator framework.

The analogue of the reference's ExecutionPlan/ExecutionContext pair
(reference: datafusion-ext-plans/src/common/execution_context.rs:70-767),
re-shaped for a host-driven TPU engine: operators are a tree of
``PhysicalOp``s; ``execute(partition, ctx)`` returns a pull-based iterator of
DeviceBatches. The host loop stays in Python (it only orchestrates); every
per-batch computation inside an operator is a jit-compiled kernel cached per
(operator config, shape bucket), so steady-state execution is a chain of XLA
executions with no per-row host work — the tokio stream chain of the
reference collapses into Python generators driving device kernels.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

from auron_tpu.columnar.batch import DeviceBatch, shrink as shrink_batch
from auron_tpu.columnar.schema import Schema
from auron_tpu.obs import profile as _profile
from auron_tpu.obs import trace as _trace


class Metric:
    __slots__ = ("value", "_mirror", "_owner")

    def __init__(self, mirror: "Optional[Metric]" = None,
                 owner: "Optional[MetricsSet]" = None):
        self.value = 0
        self._mirror = mirror
        #: the MetricsSet this counter was created by — lets a timer
        #: wrapping one counter flush its host/device attribution
        #: (obs/profile) into sibling counters of the same operator
        #: without threading the set through every helper signature
        self._owner = owner

    def add(self, v):
        self.value += v
        m = self._mirror
        if m is not None:
            m.value += v


class MetricsSet:
    """Per-operator metrics, mirrored into the host tree on finalize —
    canonical names follow the reference (NativeHelper.scala:170-238):
    output_rows, output_batches, elapsed_compute, mem_spill_count, ...

    A set may carry a ``mirror``: every counter then chains its adds
    into the same-named counter of the mirror set. That is how per-op
    POSITIONAL sets (ExecContext.metrics_for(op) — the metric-tree /
    EXPLAIN ANALYZE source, obs/metric_tree.py) stay consistent with
    the legacy name-keyed aggregate (``ctx.metrics[op.name]``) without
    double bookkeeping at call sites."""

    def __init__(self, mirror: "Optional[MetricsSet]" = None,
                 name: str = "op"):
        self._metrics: dict[str, Metric] = {}
        self._mirror = mirror
        #: the operator's display name: the key of its layer spans
        #: (``auron:op/<name>``) and of the ledger's ``ops_s``
        self.name = name

    def counter(self, name: str) -> Metric:
        m = self._metrics.get(name)
        if m is None:
            chained = (self._mirror.counter(name)
                       if self._mirror is not None else None)
            m = self._metrics[name] = Metric(chained, owner=self)
        return m

    def snapshot(self) -> dict[str, int]:
        return {k: m.value for k, m in self._metrics.items()}


class timer:
    """Context manager adding wall nanoseconds to a metric
    (reference: common/timer_helper.rs). It never waits for the device:
    kernels stay in flight past the scope, and the device wait is timed
    where execution synchronizes (obs/profile ``device_fence`` /
    ``timed_get``).

    When the profiler is on (``auron.profile.enabled``, obs/profile.py)
    and the metric belongs to a MetricsSet, the scope additionally opens
    an attribution frame: wrapped program calls record their
    dispatch/device split into it, ``track`` marks the dispatch→device
    boundary for kernels that bypass the program registry, and
    ``bucket`` classifies kernel-free host sections (scan decode waits
    → "convert", shuffle serde → "serde"). The flush lands
    ``elapsed_device`` / ``elapsed_host_*`` counters next to this
    metric in the same set — EXPLAIN ANALYZE's host/device columns.

    Whatever the profiler says, a timer on an operator's metric is also
    the operator's layer span (``auron:op/<name>``, obs/trace.py): its
    self time — less its children's spans and its own readbacks — is
    the operator's exclusive host time in the task's ledger."""

    __slots__ = ("metric", "t0", "_frame", "_bucket", "_t_track", "_span")

    def __init__(self, metric: Metric, bucket: "Optional[str]" = None):
        self.metric = metric
        self._bucket = bucket
        self._frame = None
        self._t_track = 0

    def track(self, value):
        """Mark the dispatch→device boundary of the scope's profile
        frame at a kernel result; returns it."""
        if self._frame is not None:
            self._t_track = time.perf_counter_ns()
        return value

    def __enter__(self):
        owner = self.metric._owner
        if owner is not None:
            self._span = _trace.layer_span("op", owner.name)
            self._span.__enter__()
            self._frame = _profile.push_frame()
        else:
            self._span = None
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter_ns() - self.t0
        self.metric.add(wall)
        if self._span is not None:
            self._span.__exit__(*exc)
            self._span = None
        if self._frame is not None:
            _profile.pop_frame(
                self._frame, self.metric._owner, wall,
                (self._t_track - self.t0) if self._t_track else None,
                self._bucket)
            self._frame = None
            self._t_track = 0
        return False


@dataclass
class ExecContext:
    """Per-task execution context (reference: TaskContext propagated through
    rt.rs:113-139): identity, metrics registry, memory manager hook."""

    stage_id: int = 0
    partition_id: int = 0
    task_id: int = 0
    num_partitions: int = 1
    metrics: dict[str, MetricsSet] = field(default_factory=dict)
    mem_manager: Optional[object] = None
    #: shared cancellation flag (reference: cancel_all_tasks registry,
    #: execution_context.rs:452 + is_task_running checks, rt.rs:208-238).
    #: A threading.Event created EAGERLY so derived contexts (ctx.child)
    #: always share the same registry object — a lazily-created event
    #: would not reach children built before the first cancel; the host
    #: (serving handler, task-kill) flips it from another thread and
    #: operators poll between batches.
    cancel_event: object = field(default_factory=lambda: _new_event())
    #: the task's stall-watchdog heartbeat (runtime/watchdog.TaskHeartbeat)
    #: when auron.watchdog.stall_timeout_s arms the monitor; operators
    #: beat it through ``checkpoint`` so the monitor can tell a slow
    #: batch from a wedged one
    heartbeat: Optional[object] = None
    # typed config (auron_tpu.config); None = process-wide defaults
    config: Optional[object] = None
    #: per-op-INSTANCE metric sets keyed (id(op), suffix) — the
    #: positional source the metric tree mirrors from
    #: (obs/metric_tree.mirror); shared with child contexts like
    #: ``metrics`` so map-side work attributes to the same plan nodes
    op_metrics: dict = field(default_factory=dict)

    def child(self, **overrides) -> "ExecContext":
        """Derived context for a sub-execution (the map side of an
        exchange, a subquery, a broadcast build): inherits the memory
        manager, config AND the cancellation registry — a cancel on the
        parent must reach every nested execution — while identity fields
        (stage/partition/task) and metrics may be overridden."""
        base = dict(
            stage_id=self.stage_id, partition_id=self.partition_id,
            task_id=self.task_id, num_partitions=self.num_partitions,
            metrics=self.metrics, mem_manager=self.mem_manager,
            cancel_event=self.cancel_event, heartbeat=self.heartbeat,
            config=self.config, op_metrics=self.op_metrics)
        base.update(overrides)
        return ExecContext(**base)

    def cancel(self) -> None:
        """Flip the task's cancellation flag (thread-safe)."""
        self.cancel_event.set()

    @property
    def cancelled(self) -> bool:
        ev = self.cancel_event
        return ev is not None and ev.is_set()

    def check_cancelled(self) -> None:
        """Raise the task's teardown error if the host tore it down —
        called by operators between child batches so a cancel lands
        within one batch of compute. Three teardown verdicts, most
        specific first: a stall flag from the watchdog monitor raises
        the classified ``errors.TaskStalled`` (retry driver: transient
        once); a CancelToken registry raises its own classified error
        (QueryCancelled / DeadlineExceeded by reason); a bare Event
        registry keeps the legacy TaskCancelled."""
        hb = self.heartbeat
        if hb is not None and getattr(hb, "stalled", False):
            from auron_tpu import errors
            raise errors.TaskStalled(
                f"task {self.task_id} (stage {self.stage_id}, partition "
                f"{self.partition_id}) flagged stalled by the watchdog "
                f"(last heartbeat at {hb.last_site or '?'})")
        ev = self.cancel_event
        if ev is not None and ev.is_set():
            raise_for = getattr(ev, "raise_for_status", None)
            if raise_for is not None:
                raise_for()
            raise TaskCancelled(
                f"task {self.task_id} (stage {self.stage_id}, partition "
                f"{self.partition_id}) was cancelled")

    def checkpoint(self, site: str = "") -> None:
        """The cooperative-lifecycle poll for long-running loops (batch
        drives, shuffle fetch/materialize, spill consumers): beat the
        stall watchdog with ``site`` (the last-heartbeat attribution a
        StallReport prints), give the lifecycle chaos sites traffic
        (``cancel.race`` races a cancel against this very poll,
        ``task.hang`` wedges mid-stream — both no-ops at one cached
        epoch-compare each when unarmed), AND surface any pending
        cancellation."""
        hb = self.heartbeat
        if hb is not None:
            hb.beat(site)
        from auron_tpu.runtime import faults
        faults.lifecycle_poll(self)
        if hb is not None and not hb.stalled:
            # an injected hang may have slept here: re-beat so the
            # SLEEP is not misread as the task's own silence (a stall
            # flag set meanwhile survives — beats never clear it)
            hb.beat(site)
        self.check_cancelled()

    @property
    def should_stop(self) -> bool:
        """True when this task must unwind (cancelled OR stall-flagged)
        without raising — the poll the fault plane's interruptible hang
        loop uses (runtime/faults.maybe_fail)."""
        hb = self.heartbeat
        if hb is not None and getattr(hb, "stalled", False):
            return True
        return self.cancelled

    @property
    def conf(self):
        if self.config is None:
            from auron_tpu.config import get_config
            self.config = get_config()
        return self.config

    @property
    def mesh_plane(self):
        """The process's SPMD mesh plane (parallel/mesh.current_plane),
        resolved once per context — None when
        ``auron.mesh.enabled`` is off or fewer than 2 devices exist.
        PROCESS-GLOBAL by the knob's contract (the device set is
        process state)."""
        cached = getattr(self, "_mesh_plane", None)
        if cached is None:
            from auron_tpu.parallel import mesh
            cached = (mesh.current_plane(),)
            self._mesh_plane = cached
        return cached[0]

    def metrics_for(self, op, suffix: str = "") -> MetricsSet:
        """The metric set for ``op``.

        Passing a *string* returns the legacy name-keyed set (shared by
        every same-named op — plan-wide categories like "kernels" and
        "recovery" live here). Passing the *PhysicalOp instance* returns
        a per-instance set whose counters chain into the name-keyed one,
        giving the metric tree positional attribution
        (obs/metric_tree.py) while every existing name-keyed consumer
        keeps seeing the aggregate."""
        if isinstance(op, str):
            name = op + suffix
            if name not in self.metrics:
                self.metrics[name] = MetricsSet(name=name)
            return self.metrics[name]
        key = (id(op), suffix)
        entry = self.op_metrics.get(key)
        if entry is None:
            # the cache value PINS the op: id() keys are only unique
            # while the object lives, and a gc'd subquery plan's id can
            # be recycled by a later op in the same task
            entry = (op, MetricsSet(
                mirror=self.metrics_for(op.name + suffix),
                name=op.name + suffix))
            self.op_metrics[key] = entry
        return entry[1]

    def op_metric_sets(self, op) -> list[MetricsSet]:
        """Every per-instance metric set ``op`` recorded under this
        context (all suffixes — an exchange records both its write side
        and its "_read" side)."""
        oid = id(op)
        return [entry[1] for (i, _s), entry in self.op_metrics.items()
                if i == oid]

    def metrics_snapshot(self) -> dict[str, dict[str, int]]:
        return {k: v.snapshot() for k, v in self.metrics.items()}


def _new_event():
    import threading
    return threading.Event()


class TaskCancelled(Exception):
    """The host cancelled this task mid-stream (reference: task-kill
    detection via is_task_running, rt.rs:208-238); operators unwind and
    the runtime tears down without reporting a failure."""


class PhysicalOp:
    """Base physical operator."""

    #: operator display name (metric key prefix)
    name: str = "op"

    #: whole-stage fusion protocol (ops/fused.py): True on operators whose
    #: per-batch work is a pure row-local device computation expressible as
    #: a KernelFragment — the planner's stage-fusion pass
    #: (ir/planner.fuse_stages) chains them into one jit-compiled program.
    #: Stage breakers (agg cores, joins, sorts, exchanges, scans) stay
    #: False and terminate fusion chains.
    fusable: bool = False

    #: kernel fan-out of this op's fragment (ExpandOp emits one batch per
    #: projection); the fusion pass bounds the product along a chain.
    fusion_fanout: int = 1

    #: does this op drop rows and hand its batch on at its INPUT's
    #: capacity (the filters)? Its ``count_output`` — and that of the
    #: fused stage that holds it — then asks for the batch to leave at
    #: the capacity its live rows need. Operators that size their own
    #: output (join, aggregation, sort) stay False.
    drops_rows: bool = False

    #: does this op's fragment do real device compute? Pass-through
    #: fragments (limit's num_rows rewrite, rename's identity) are False:
    #: a stage made ONLY of those would compile a program for work the
    #: unfused operators do host-side for free, so the fusion pass only
    #: creates stages containing at least one computing member.
    fragment_computes: bool = False

    #: SPMD layout declaration (parallel/mesh.buffer_spec): what KIND of
    #: buffer this op's output is, for the replicate-vs-shard decision —
    #: "broadcast"/"hash_build" replicate across the mesh, "scan_batch"/
    #: "shuffle_entry"/"agg_partial" shard on the batch dim. None = no
    #: declared kind (shards by default). The planner's annotate_mesh
    #: pass resolves this into ``mesh_spec`` on each node.
    mesh_buffer_kind: Optional[str] = None

    #: resolved sharding spec ("replicate" | "shard" | "gang"), stamped
    #: by ir/planner.annotate_mesh when the mesh plane is active; "gang"
    #: marks an exchange whose materialization occupies the whole mesh
    mesh_spec: Optional[str] = None

    #: may a consumer destroy (donate to XLA) the batches execute() yields?
    #: True for ops that construct fresh device arrays per output batch;
    #: "inherit" for pass-through ops (limit/union/rename/coalesce) whose
    #: outputs alias their children's; False for sources that replay
    #: shared, long-lived batches (device scans, broadcast buffers).
    #: Resolve through ``yields_owned_batches``, never read directly.
    owns_output = True

    def build_kernel_fragment(self) -> Optional["object"]:
        """Return this op's KernelFragment (ops/fused.py) — the traceable
        per-batch function the stage-fusion pass composes into one XLA
        program — or None when the op cannot fuse. Implemented iff
        ``fusable`` is True."""
        return None

    @property
    def children(self) -> list["PhysicalOp"]:
        return []

    def schema(self) -> Schema:
        raise NotImplementedError

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        raise NotImplementedError

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + repr(self) + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def __repr__(self):
        return type(self).__name__


def yields_owned_batches(op: PhysicalOp) -> bool:
    """True when every batch ``op.execute`` yields is freshly constructed
    and dead to the producer once consumed — the precondition for a
    consumer kernel to donate it to XLA (buffer donation halves peak HBM
    on single-consumer steps; donating a shared batch would corrupt later
    readers). Pass-through ops inherit from their children."""
    owned = getattr(op, "owns_output", True)
    if owned == "inherit":
        return all(yields_owned_batches(c) for c in op.children)
    return bool(owned)


def count_output(stream, metrics: MetricsSet, timed: bool = False,
                 also: tuple = (), shrink: bool = False):
    """Wrap a batch stream with output_rows/output_batches counting.

    ``shrink=True`` — asked by the operators that drop rows and keep
    their input's capacity (``PhysicalOp.drops_rows``) — hands a batch
    on at the capacity that fits its live rows where
    ``columnar/batch.shrink_target`` says so, from the row count read
    here anyway: one ``columnar.batch.shrink`` launch, no new read;
    counted as ``batch_shrinks`` / ``batch_shrink_lanes``.

    ``also`` ((count key, factor), ...) are counts of the task's ledger
    (``obs/trace.COUNT_KEYS``) that grow by ``factor`` x the rows of
    every batch that leaves — the rows an expand put out, the rows a
    decimal division ran for — from the ONE read of the row count this
    wrapper makes anyway.

    ``timed=True`` additionally accrues the time spent INSIDE the
    producer's ``next()`` into ``elapsed_compute`` — the inclusive
    host-side elapsed for operators that run no device kernels of their
    own (scans, limits, exchange reads) so EXPLAIN ANALYZE shows a
    nonzero elapsed on every plan node. Operators that time their
    kernels explicitly must NOT pass it (they would double-count).

    Timed or not, each ``next()`` runs inside the operator's layer span
    (``auron:op/<name>``): the generator's own glue between its timers
    and its children's spans is the operator's exclusive host time. The
    span closes before the ``yield``. The row count of a batch is read
    inside it through ``profile.row_count``: where the count is still on
    the device the read waits for the chip, and that wait is the
    operator's device wait, not its host time."""
    rows = metrics.counter("output_rows")
    batches = metrics.counter("output_batches")
    elapsed = metrics.counter("elapsed_compute") if timed else None
    name = metrics.name
    it = iter(stream)
    while True:
        with _trace.layer_span("op", name):
            t0 = time.perf_counter_ns()
            b = next(it, None)
            if elapsed is not None:
                elapsed.add(time.perf_counter_ns() - t0)
            if b is not None:
                n = _profile.row_count(b)
                rows.add(n)
                batches.add(1)
                for key, factor in also:
                    _trace.count(key, factor * n)
                if shrink:
                    small = shrink_batch(b, n)
                    if small is not b:
                        _trace.count("batch_shrinks")
                        _trace.count("batch_shrink_lanes",
                                     b.capacity - small.capacity)
                        b = small
        if b is None:
            return
        yield b
