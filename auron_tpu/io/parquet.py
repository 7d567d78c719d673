"""Parquet scan.

The reference reads parquet through a JVM FileSystem wrapper into DataFusion's
parquet opener with row-group/page pruning (reference: datafusion-ext-plans/
src/parquet_exec.rs:151-237, scan/internal_file_reader.rs). Here the host side
is pyarrow (row-group statistics pruning + dictionary-aware reads, of the
columns the scan names) feeding padded DeviceBatches to the TPU. Which columns
a scan names is decided above it: a Spark host ships its scans pruned, and for
every other plan the planner's required-columns pass (``ir/pruning.py``) fills
``columns`` from the expressions of the project / filter / sort / limit / agg
chain over the scan; a scan under a node kind that pass has no rule for reads
its files whole. ``counts.scan_columns_read`` / ``scan_columns_pruned`` say
what each scan did. The scan is the host→device
on-ramp, deliberately kept off the device's critical path by the prefetching
worker (``ScanPrefetcher``): while the device crunches batch N, a bounded
background thread decodes and transfers batch N+1 (and beyond, up to
``auron.scan.prefetch_batches``), with the decoded bytes registered with the
memory manager so lookahead degrades to 1 under pressure.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Iterator, Optional

import pyarrow as pa
import pyarrow.dataset as pa_ds
import pyarrow.parquet as pq

from auron_tpu.columnar.arrow_bridge import schema_from_arrow, to_device
from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.columnar.schema import Schema
from auron_tpu.exprs import ir
from auron_tpu.obs import trace
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.utils.shapes import DEFAULT_BATCH_CAPACITY


def _expr_to_arrow_filter(e: ir.Expr, names: list[str]):
    """Best-effort translation of predicates to pyarrow dataset filters for
    row-group pruning; anything untranslatable is skipped (the device filter
    re-applies everything, so this is pruning-only — same contract as the
    reference's rowgroup pruning, conf.rs:43-46)."""
    import pyarrow.compute as pc
    try:
        if isinstance(e, ir.BinaryExpr) and e.op in ("==", "!=", "<", "<=", ">", ">="):
            l, r = e.left, e.right
            if isinstance(l, ir.ColumnRef) and isinstance(r, ir.Literal):
                f = pc.field(names[l.index])
                v = r.value
                return {"==": f == v, "!=": f != v, "<": f < v,
                        "<=": f <= v, ">": f > v, ">=": f >= v}[e.op]
        if isinstance(e, ir.BinaryExpr) and e.op == "and":
            a = _expr_to_arrow_filter(e.left, names)
            b = _expr_to_arrow_filter(e.right, names)
            if a is not None and b is not None:
                return a & b
            return a if a is not None else b
        if isinstance(e, ir.InList) and isinstance(e.child, ir.ColumnRef) and not e.negated:
            return pc.field(names[e.child.index]).isin(list(e.values))
        if isinstance(e, ir.IsNotNull) and isinstance(e.child, ir.ColumnRef):
            return ~pc.field(names[e.child.index]).is_null()
    except Exception:
        return None
    return None


def file_schema(files: list[str], fmt: str = "parquet") -> pa.Schema:
    """The Arrow schema a scan of ``files`` emits when it names no
    columns (the first file's, as the scan itself takes it)."""
    from auron_tpu.io.fs import resolve_many
    fs, paths = resolve_many(list(files))
    return pa_ds.dataset(paths, format=fmt, filesystem=fs).schema


class ScanPrefetcher:
    """Bounded background decode worker for the file scans.

    One daemon thread drives the decode→transfer iterator and parks the
    resulting DeviceBatches in a bounded buffer; the query thread drains
    it in order, so row-group N+1 decodes while the device computes
    batch N. Three contracts beyond the overlap:

    - **memory**: the buffered decoded bytes are registered with the
      memory manager (a duck-typed MemConsumer named ``scan_prefetch``),
      and the effective lookahead degrades to 1 whenever the pressure
      ladder's shrink rung is active (``advised_batch_rows`` < base) or
      the ladder asked this consumer to ``shrink()`` — prefetch depth is
      the first thing a struggling query gives back;
    - **cancellation**: the consumer polls ``ExecContext.checkpoint``
      while waiting, so a cancel/deadline unwinds within one poll
      interval; ``close()`` stops the worker, drains the buffer, zeroes
      the memmgr accounting and unregisters — a cancel mid-prefetch
      leaks neither consumers nor buffered batches;
    - **errors**: a worker-side exception (decode failure, classified
      memmgr shed) is re-raised on the query thread with its type
      intact.

    Batches arrive in exactly source order — prefetching changes WHEN
    decode happens, never what streams out.
    """

    consumer_name = "scan_prefetch"

    #: consumer-side wait quantum (seconds): bounds cancel latency while
    #: parked on an empty buffer
    _POLL_S = 0.02

    def __init__(self, source, ctx: ExecContext, depth: int):
        self._source = source
        self._ctx = ctx
        self._depth = max(1, int(depth))
        self._cond = threading.Condition()
        self._buf: deque = deque()
        self._bytes = 0
        self._done = False
        self._stop = False
        self._err: Optional[BaseException] = None
        self._degraded = False
        self._mem = ctx.mem_manager
        #: serializes the worker's accounting update against close()'s
        #: unregister, so a slow in-flight update_mem_used (it may walk
        #: the spill loop) can never re-insert an unregistered consumer
        self._mem_lock = threading.Lock()
        if self._mem is not None:
            self._mem.register_consumer(self)
        #: the served task that starts this scan: the worker's layer
        #: spans (auron:scan/{decode,encode,h2d}) and transfer counts
        #: are booked to it, beside the task thread's own time
        self._task = trace.current_task()
        self._thread = threading.Thread(
            target=self._run, name="auron-scan-prefetch", daemon=True)
        self._thread.start()

    # -- memmgr duck-type ---------------------------------------------------

    def mem_used(self) -> int:
        with self._cond:
            return self._bytes

    def spill(self) -> int:
        """Prefetched batches cannot be released without losing data —
        the prefetcher degrades by shrinking lookahead, not by
        spilling."""
        return 0

    def shrink(self) -> int:
        """Pressure-ladder rung 1: give back the lookahead for the rest
        of this scan (the worker stops refilling past depth 1)."""
        self._degraded = True
        return 0

    def target_depth(self) -> int:
        """Effective lookahead right now: 1 while the memory manager's
        shrink rung is active (or the ladder shrank this consumer),
        else the configured depth."""
        if self._degraded:
            return 1
        mem = self._mem
        if mem is not None:
            fn = getattr(mem, "advised_batch_rows", None)
            if fn is not None and fn(1 << 20) < (1 << 20):
                return 1
        return self._depth

    # -- worker -------------------------------------------------------------

    def _run(self) -> None:
        with trace.worker_scope(self._task):
            self._run_bound()

    def _run_bound(self) -> None:
        try:
            for item in self._source:
                with self._cond:
                    while (len(self._buf) >= self.target_depth()
                           and not self._stop):
                        self._cond.wait(self._POLL_S)
                    if self._stop:
                        return
                    self._buf.append(item)
                    self._bytes += item[1]
                    self._cond.notify_all()
                with self._mem_lock:
                    if self._mem is not None and not self._stop:
                        # outside the condition: accounting may spill /
                        # walk the pressure ladder synchronously
                        # (shrink() re-enters on this thread, a flag
                        # set only)
                        self._mem.update_mem_used(self, self.mem_used())
                if self._stop or self._ctx.should_stop:
                    return
        except BaseException as e:   # noqa: BLE001 — forwarded verbatim
            with self._cond:
                self._err = e
                self._cond.notify_all()
        finally:
            with self._cond:
                self._done = True
                self._cond.notify_all()

    # -- consumer -----------------------------------------------------------

    def batches(self, io_time) -> Iterator[DeviceBatch]:
        """Drain in order. The dequeue wait is decode time the worker
        could not hide — attributed to the ``convert`` host bucket and
        to ``auron:scan/wait``."""
        while True:
            with timer(io_time, bucket="convert"), \
                    trace.layer_span("scan", "wait"):
                with self._cond:
                    while (not self._buf and not self._done
                           and self._err is None):
                        self._cond.wait(self._POLL_S)
                        # surface cancel/deadline/stall while parked
                        self._ctx.checkpoint("scan.prefetch")
                    if self._err is not None:
                        raise self._err
                    if self._buf:
                        batch, nbytes = self._buf.popleft()
                        self._bytes -= nbytes
                        self._cond.notify_all()
                    else:   # done and drained
                        return
            with self._mem_lock:
                if self._mem is not None and not self._stop:
                    self._mem.update_mem_used(self, self.mem_used())
            self._ctx.checkpoint("scan.decode")
            yield batch

    def close(self) -> None:
        """Stop the worker, drop buffered batches, zero the accounting
        and unregister from the memory manager (idempotent)."""
        with self._cond:
            self._stop = True
            self._buf.clear()
            self._bytes = 0
            self._cond.notify_all()
        self._thread.join(timeout=5.0)
        with self._mem_lock:
            if self._mem is not None:
                self._mem.unregister_consumer(self)
                self._mem = None


class ParquetScanOp(PhysicalOp):
    name = "parquet_scan"
    #: pyarrow.dataset format — OrcScanOp subclasses with "orc"
    _format = "parquet"
    #: SPMD layout: scan output shards on the batch dim (one map
    #: partition per mesh device — parallel/mesh.buffer_spec)
    mesh_buffer_kind = "scan_batch"

    def __init__(self, files: list[str], schema: Optional[Schema] = None,
                 columns: Optional[list[str]] = None,
                 predicates: Optional[list[ir.Expr]] = None,
                 batch_rows: int = DEFAULT_BATCH_CAPACITY,
                 string_widths: Optional[dict[str, int]] = None):
        self.files = list(files)
        self.columns = columns
        self.predicates = predicates or []
        self.batch_rows = batch_rows
        # remote-FS seam (the reference reads through its JVM Hadoop
        # FileSystem wrapper; here io/fs.py resolves URIs to pyarrow
        # filesystems — hdfs://, s3://, gs://, registered providers)
        from auron_tpu.io.fs import resolve_many
        self._fs, self.files = resolve_many(self.files)
        if schema is None:
            # a caller that hands over the schema has read the files (a
            # host's plan; the planner's required-columns pass): only a
            # bare scan opens them to plan
            arrow_schema = pa_ds.dataset(self.files, format=self._format,
                                         filesystem=self._fs).schema
            if columns:
                arrow_schema = pa.schema(
                    [arrow_schema.field(c) for c in columns])
            schema = schema_from_arrow(arrow_schema)
        self._schema = schema
        # Pre-size string widths from the data unless caller pinned them, so
        # every batch of a file lands in the same compiled kernel bucket.
        self.string_widths = dict(string_widths or {})

    @property
    def children(self):
        return []

    def schema(self) -> Schema:
        return self._schema

    def _partition_files(self, partition: int, num_partitions: int) -> list[str]:
        return [f for i, f in enumerate(self.files)
                if i % num_partitions == partition]

    def _capacity_for(self, partition: int, files: list[str]) -> int:
        """Conversion capacity for one partition's file set: pinned to
        batch_rows (ONE program shape per scan) but clamped to the
        partition's actual row-count bucket, so a small file never pads
        its batches to the full configured batch size. Metadata-only
        (parquet footers / ORC stripe stats), cached per partition so
        retries don't re-parse footers; falls back to batch_rows when
        the count is unavailable."""
        cache = getattr(self, "_cap_cache", None)
        if cache is None:
            cache = self._cap_cache = {}
        cap = cache.get(partition)
        if cap is not None:
            return cap
        from auron_tpu.utils.shapes import bucket_rows
        cap = self.batch_rows
        try:
            ds = pa_ds.dataset(files, format=self._format,
                               filesystem=self._fs)
            total = ds.count_rows()
            if total:
                cap = min(self.batch_rows, bucket_rows(int(total)))
        except Exception:
            pass
        cache[partition] = cap
        return cap

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        io_time = metrics.counter("io_time")
        files = self._partition_files(partition, max(ctx.num_partitions, 1))

        arrow_filter = None
        for p in self.predicates:
            f = _expr_to_arrow_filter(p, self._schema.names)
            if f is not None:
                arrow_filter = f if arrow_filter is None else (arrow_filter & f)

        def advised_rows(base: int) -> int:
            fn = getattr(ctx.mem_manager, "advised_batch_rows", None) \
                if ctx.mem_manager is not None else None
            return fn(base) if fn is not None else base

        capacity = (self._capacity_for(partition, files)
                    if files else self.batch_rows)

        def host_batches():
            if not files:
                return
            # file -> Arrow is the reader's own work: opening the
            # files, then each row group; every span closes before the
            # yield hands a batch on
            with trace.layer_span("scan", "decode"):
                ds = pa_ds.dataset(files, format=self._format,
                                   filesystem=self._fs)
                trace.count("scan_columns_read", len(self._schema))
                trace.count("scan_columns_pruned",
                            len(ds.schema.names) - len(self._schema))
                scanner = ds.scanner(columns=self.columns,
                                     filter=arrow_filter,
                                     batch_size=self.batch_rows)
                it = iter(scanner.to_batches())
            while True:
                with trace.layer_span("scan", "decode"):
                    rb = next(it, None)
                if rb is None:
                    return
                if rb.num_rows == 0:
                    continue
                # split oversized batches (scanner batch_size is a
                # hint); under memory pressure the manager's shrink rung
                # advises smaller slices (memmgr degradation ladder) so
                # the scan stops ramming full-capacity batches into a
                # budget that just denied
                rows = advised_rows(self.batch_rows)
                for off in range(0, rb.num_rows, rows):
                    yield rb.slice(off, min(rows, rb.num_rows - off))

        def convert(rb):
            # capacity stays pinned per scan unless the pressure ladder
            # shrank the slices — smaller capacity is the point then
            from auron_tpu.utils.shapes import bucket_rows
            cap = capacity
            if rb.num_rows < cap and advised_rows(cap) < cap:
                cap = bucket_rows(rb.num_rows)
            with trace.layer_span("scan", "encode"):
                widths = self._widths_for(rb)
            return to_device(rb, capacity=cap, string_widths=widths)[0]

        from auron_tpu import config as cfg
        depth = max(1, int(ctx.conf.get(cfg.SCAN_PREFETCH_BATCHES)))

        def decoded():
            from auron_tpu.columnar.batch import batch_nbytes
            for rb in host_batches():
                batch = convert(rb)
                # account the DEVICE footprint of what sits in the
                # buffer (padded to capacity), not the smaller Arrow
                # slice it came from — under-reporting would hide the
                # prefetch buffer from the pressure ladder
                yield batch, batch_nbytes(batch)

        def stream():
            pf = ScanPrefetcher(decoded(), ctx, depth)
            try:
                for batch in pf.batches(io_time):
                    yield batch
            finally:
                pf.close()

        return count_output(stream(), metrics, timed=True)

    def _widths_for(self, rb: pa.RecordBatch) -> dict[str, int]:
        """Stable width buckets per string column, learned once per scan from
        parquet statistics / first batch and then pinned."""
        import pyarrow.compute as pc
        from auron_tpu.utils.shapes import bucket_string_width
        widths = self.string_widths
        for i, f in enumerate(rb.schema):
            if pa.types.is_string(f.type) or pa.types.is_large_string(f.type):
                if f.name not in widths:
                    col = rb.column(i)
                    max_len = pc.max(pc.binary_length(col)).as_py() or 1
                    widths[f.name] = bucket_string_width(max(max_len, 1))
                else:
                    col = rb.column(i)
                    max_len = pc.max(pc.binary_length(col)).as_py() or 0
                    if max_len > widths[f.name]:
                        widths[f.name] = bucket_string_width(max_len)
        return widths

    def __repr__(self):
        cols = f", columns={self.columns}" if self.columns else ""
        return f"{type(self).__name__}[{len(self.files)} files{cols}]"


class MemoryScanOp(PhysicalOp):
    """In-memory source (tests and broadcast-side plumbing)."""

    name = "memory_scan"
    mesh_buffer_kind = "scan_batch"   # SPMD layout: shard on batch dim

    def __init__(self, partitions: list[list[pa.RecordBatch]], schema: Schema,
                 capacity: int = DEFAULT_BATCH_CAPACITY,
                 string_widths: Optional[dict[str, int]] = None):
        self.partitions = partitions
        self._schema = schema
        self.capacity = capacity
        self.string_widths = string_widths

    @property
    def children(self):
        return []

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)

        def stream():
            for rb in self.partitions[partition]:
                if rb.num_rows:
                    yield to_device(rb, capacity=self.capacity,
                                    string_widths=self.string_widths)[0]

        return count_output(stream(), metrics, timed=True)


class DeviceBatchScanOp(PhysicalOp):
    """Source over already-device-resident batches (shuffle-read side)."""

    name = "device_scan"
    #: replays stored batches (broadcast builds, resource maps) that
    #: later readers share — consumers must never donate them
    owns_output = False
    #: SPMD layout: replayed shared batches behave like broadcast
    #: relations — every shard reads them whole
    mesh_buffer_kind = "broadcast"

    def __init__(self, partitions, schema: Schema):
        self.partitions = partitions  # list[list[DeviceBatch]] or callable
        self._schema = schema

    @property
    def children(self):
        return []

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        parts = self.partitions(partition) if callable(self.partitions) \
            else self.partitions[partition]
        metrics = ctx.metrics_for(self)
        return count_output(iter(parts), metrics, timed=True)
