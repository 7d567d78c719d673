"""Session: catalog, config, and plan execution for the DataFrame DSL.

The driver-side runtime the Spark session plays for the reference
(AuronSparkSessionExtension.scala): owns the table catalog and the planner
context, serializes each DataFrame's plan to TaskDefinition bytes, and runs
the engine's physical plan per partition — including materializing
host-fallback boundaries before native planning (the ConvertToNative
transition, SURVEY.md §3.1)."""

from __future__ import annotations

import contextlib
import itertools
from typing import Callable, Optional

import pyarrow as pa
import pyarrow.parquet as pq

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.columnar.schema import Schema
from auron_tpu.frontend.dataframe import DataFrame
from auron_tpu.ir import pb, plan_from_bytes
from auron_tpu.ir.planner import PhysicalPlanner, PlannerContext
from auron_tpu.runtime.executor import collect as _collect

#: process-wide query-id sequence: ids key process-global ledgers (the
#: program cache's per-query attribution, the memmgr query ledger), so
#: two Sessions must never mint the same id
_QUERY_SEQ = itertools.count(1)


class Session:
    def __init__(self, batch_capacity: Optional[int] = None, mem_manager=None,
                 config=None):
        from auron_tpu.config import get_config
        self.config = config or get_config()
        from auron_tpu.utils import xla_cache
        xla_cache.bind(self.config)
        # SPMD mesh plane (parallel/mesh.py): resolved EAGERLY at Session
        # init so the device layout exists before the first plan. The
        # plane is process-global by the knob's contract — consumers
        # (annotate_mesh, ExecContext.mesh_plane, exchange routing) all
        # resolve mesh.current_plane() themselves, so nothing is stored
        # per Session.
        from auron_tpu.parallel import mesh as _mesh
        _mesh.current_plane()
        self.ctx = PlannerContext(batch_capacity=batch_capacity,
                                  config=self.config)
        self.mem_manager = mem_manager
        if mem_manager is not None \
                and getattr(mem_manager, "config", None) is None:
            # bind the session config as the manager's knob source so
            # the auto per-query quota divisor and the scheduler's
            # admission clamp read the SAME auron.sched.max_concurrent
            # (first binding wins for a shared manager)
            mem_manager.config = self.config
            if hasattr(mem_manager, "_quota_cache"):
                mem_manager._quota_cache = (-1, 0, 1)
        self._ids = itertools.count()
        #: host-fallback registrations: rid -> (child DataFrame, fn)
        self._host_fns: dict[str, tuple[DataFrame, Callable]] = {}
        #: live query lifecycles: query_id -> CancelToken (the
        #: session.cancel(query_id) registry); guarded by _queries_lock
        #: because serving/admin threads cancel while the driver runs
        import threading
        self._queries_lock = threading.Lock()
        self._active_queries: dict[str, object] = {}
        self._closed = False
        #: thread-local current token: nested executes (host-fn
        #: children, scalar subqueries) join the ENCLOSING query's
        #: lifecycle — one cancel/deadline covers the whole tree
        self._tls = threading.local()
        #: the concurrent-query control plane (runtime/scheduler.py):
        #: every top-level execute is admitted through it — bounded run
        #: queue, weighted-round-robin task fairness, overload shedding
        #: with the classified errors.AdmissionRejected. Nested executes
        #: inherit the enclosing query's slot and NEVER queue (queueing
        #: a child behind its slot-holding parent would deadlock both).
        from auron_tpu.runtime.scheduler import QueryScheduler
        self._scheduler = QueryScheduler(name="session",
                                         mem_manager=mem_manager,
                                         config=self.config)
        #: crash-safe query journals this Session opened (runtime/
        #: journal.py): completed queries delete their own; close()
        #: deletes the rest — in-process, a journal never outlives its
        #: Session (cross-process survival is exactly the crash case)
        self._journals: list = []
        from auron_tpu.runtime import journal as _jrn
        if _jrn.enabled(self.config):
            # startup orphan sweep: reclaim dead predecessors' torn
            # journals and unreferenced RSS run dirs (resumable
            # journals of dead processes are KEPT — they are the
            # resume inventory)
            _jrn.sweep_orphans(_jrn.journal_dir(self.config))
        #: warm-path serving plane (auron_tpu/cache): register the
        #: process-wide result cache as a sheddable consumer on this
        #: Session's manager (refcounted — detached in close(), so the
        #: consumer ledger stays balanced), then START the AOT warmer
        #: (auron.cache.aot_top_n; a no-op at the default 0, NEVER
        #: raises — a corrupt inventory must not fail construction).
        #: The warm runs on a background daemon thread overlapping the
        #: first user query's planning; close() joins it (aot.wait)
        from auron_tpu.cache import aot as _aot
        from auron_tpu.cache import result_cache as _rcache
        self._result_cache = _rcache.get_cache()
        self._cache_attached = self._result_cache.attach(mem_manager)
        _aot.warm(self)
        #: ops plane (obs/ops_server.py): acquire the process's live
        #: telemetry endpoint when auron.ops.enabled — refcounted, so
        #: several Sessions share one server and the LAST close stops
        #: it. ops_address is the bound (host, port) — the ephemeral-
        #: port (auron.ops.port=0) discovery surface. Acquired LAST:
        #: nothing after this can raise, so a failed __init__ (whose
        #: close() never runs) can never strand the refcount above
        #: zero and keep the port bound for the process lifetime.
        from auron_tpu.obs import ops_server as _ops
        self._ops = _ops.ensure_started(self.config)
        self.ops_address = (self._ops.address
                            if self._ops is not None else None)

    # -- sources ------------------------------------------------------------

    def register(self, name: str, table: pa.Table) -> None:
        self.ctx.catalog[name] = table

    def table(self, name: str) -> DataFrame:
        table = self.ctx.catalog[name]
        node = pb.PlanNode(memory_scan=pb.MemoryScanNode(table_name=name))
        return DataFrame(self, node, schema_from_arrow(table.schema))

    def from_arrow(self, table: pa.Table,
                   name: Optional[str] = None) -> DataFrame:
        name = name or f"__mem_{next(self._ids)}"
        self.register(name, table)
        return self.table(name)

    def read_parquet(self, files, columns=None,
                     partitions: Optional[int] = None) -> DataFrame:
        """``partitions`` sets the scan parallelism (files are split
        round-robin across partitions, like Spark input splits); default 1."""
        files = [files] if isinstance(files, str) else list(files)
        node = pb.PlanNode(parquet_scan=pb.ParquetScanNode(
            files=files, columns=columns or []))
        schema = schema_from_arrow(pq.read_schema(files[0]))
        if columns:
            # requested order, not file order: the scan op emits columns in
            # the order they were asked for
            schema = Schema(tuple(schema[schema.index_of(c)] for c in columns))
        return DataFrame(self, node, schema,
                         num_partitions=partitions or 1)

    def read_orc(self, files, columns=None) -> DataFrame:
        from pyarrow import orc
        files = [files] if isinstance(files, str) else list(files)
        node = pb.PlanNode(orc_scan=pb.OrcScanNode(
            files=files, columns=columns or []))
        schema = schema_from_arrow(orc.ORCFile(files[0]).schema)
        if columns:
            schema = Schema(tuple(schema[schema.index_of(c)] for c in columns))
        return DataFrame(self, node, schema)

    # -- host fallback ------------------------------------------------------

    def _register_host_fn(self, fn: Callable, child_df: DataFrame) -> str:
        rid = f"__hostfn_{next(self._ids)}"
        self._host_fns[rid] = (child_df, fn)
        return rid

    def _materialize_host_fns(self, plan: pb.PlanNode) -> None:
        """Execute host-fallback children referenced by this plan and put
        their transformed output into the catalog."""
        rids = []

        def walk(node: pb.PlanNode):
            kind = node.WhichOneof("node")
            if kind is None:
                return
            if kind == "memory_scan" and \
                    node.memory_scan.table_name.startswith("__hostfn_"):
                rids.append(node.memory_scan.table_name)
            inner = getattr(node, kind)
            for _f, sub in inner.ListFields():
                if isinstance(sub, pb.PlanNode):
                    walk(sub)
                elif hasattr(sub, "__iter__") and not isinstance(sub, (str, bytes)):
                    for item in sub:
                        if isinstance(item, pb.PlanNode):
                            walk(item)

        walk(plan)
        for rid in rids:
            if rid in self.ctx.catalog:
                continue
            child_df, fn = self._host_fns[rid]
            child_table = self.execute(child_df)
            out_batches = []
            for rb in child_table.to_batches():
                out = fn(rb)
                if out.num_rows:
                    out_batches.append(out)
            self.ctx.catalog[rid] = (
                pa.Table.from_batches(out_batches) if out_batches
                else child_table.schema.empty_table())

    # -- execution ----------------------------------------------------------

    def plan_physical(self, df: DataFrame):
        self._materialize_host_fns(df.plan)
        return plan_from_bytes(df.task_bytes(), self.ctx)

    # -- query lifecycle ----------------------------------------------------

    def _begin_query(self, timeout_s: Optional[float]):
        """Create + register one query's CancelToken. The deadline is
        the explicit ``timeout_s`` when given, else the session default
        ``auron.query.deadline_s`` (0 = none)."""
        from auron_tpu import config as cfg
        from auron_tpu.runtime.lifecycle import CancelToken
        if timeout_s is None:
            default = float(self.config.get(cfg.QUERY_DEADLINE_S))
            timeout_s = default if default > 0 else None
        qid = f"q{next(_QUERY_SEQ)}"
        token = CancelToken(query_id=qid, deadline_s=timeout_s)
        with self._queries_lock:
            self._active_queries[qid] = token
        return token

    def _end_query(self, token) -> None:
        with self._queries_lock:
            self._active_queries.pop(token.query_id, None)
        # drop the query's program-cache attribution ledger (bounded
        # memory; explain_analyze reads it BEFORE ending the query)
        from auron_tpu.runtime import programs
        programs.pop_query(token.query_id)

    @contextlib.contextmanager
    def _admitted_query(self, timeout_s: Optional[float]):
        """One top-level query's full admission choreography as a
        context manager: begin (token + registry entry) → scheduler
        acquire (admission control; the token's slot rides it) →
        lifecycle/thread-local binding; unwound in exact reverse on
        exit. execute() and explain_analyze() share this so the
        teardown ordering can never desynchronize between them.

        Doubles as the query's end-to-end OBSERVATION point (the ops
        plane): every exit — success, shed, cancel, failure — lands on
        the ``auron_query_duration_seconds{outcome}`` registry
        histogram, and a CLASSIFIED failure writes its post-mortem
        bundle here (obs/bundle.maybe_write — the unwind that still
        sees the scheduler, memmgr and the token's plan tree)."""
        import time as _time

        from auron_tpu import errors
        from auron_tpu.obs import bundle as _bundle
        from auron_tpu.obs import registry as _registry
        from auron_tpu.runtime import lifecycle
        t0 = _time.monotonic()
        token = self._begin_query(timeout_s)

        def observe(exc) -> None:
            try:
                _registry.observe_query(
                    _time.monotonic() - t0,
                    _registry.classify_outcome(exc),
                    served_from=getattr(token, "served_from", None))
            except Exception:   # pragma: no cover - telemetry only
                pass

        # admission BEFORE any planning/execution work: a shed query
        # costs nothing (AdmissionRejected / the token's own classified
        # error when cancelled while queued)
        try:
            slot = self._scheduler.acquire(token)
        except errors.QueryCancelled as e:
            # queue-phase cancels feed the same cancel-latency
            # histogram as mid-execution ones (every cancel class
            # counts toward the acceptance-gate metric)
            lifecycle.observe_unwind(token, kind=token.reason or "cancel")
            observe(e)
            self._end_query(token)
            raise
        except BaseException as e:
            observe(e)
            self._end_query(token)
            raise
        token.slot = slot
        self._tls.token = token
        prev_bind = lifecycle.bind_token(token)
        try:
            yield token
        except BaseException as e:
            # classified-failure post-mortem (shed/deadline/stall/mesh/
            # journal — obs/bundle.classify decides; plain cancels and
            # unclassified crashes write nothing). maybe_write never
            # raises: the query's own verdict always wins the unwind.
            _bundle.maybe_write(e, token=token, config=self.config,
                                scheduler=self._scheduler,
                                mem_manager=self.mem_manager)
            observe(e)
            raise
        else:
            observe(None)
        finally:
            self._tls.token = None
            lifecycle.bind_token(prev_bind)
            slot.release()
            self._end_query(token)

    def cancel(self, query_id: str) -> bool:
        """Cancel a running query by id (thread-safe; the API face of
        the serving CANCEL frame). Returns True when a live query was
        cancelled; False — the idempotent after-DONE no-op — when the
        id is unknown or already finished."""
        with self._queries_lock:
            token = self._active_queries.get(query_id)
        if token is None:
            return False
        token.cancel()
        return True

    def active_queries(self) -> dict:
        """{query_id: CancelToken} of the queries currently executing."""
        with self._queries_lock:
            return dict(self._active_queries)

    def close(self) -> None:
        """End the session: drain the scheduler DETERMINISTICALLY —
        queued queries are cancelled first (reason "session-closed";
        their waiting acquires dequeue without ever starting, so no
        executor or consumer/spill ledger entry is ever created for
        them), then the running tokens — and finally sweep the spill
        tier's orphaned files (the commit-time ``.part`` sweep's
        equivalent for per-attempt spill artifacts — a crashed or
        cancelled attempt must not leak storage past the session)."""
        if self._closed:
            return
        self._closed = True
        # the AOT warmer overlaps this session's first queries on a
        # background thread; join it FIRST (bounded) so the spill and
        # journal sweeps below never race a still-warming plan
        from auron_tpu.cache import aot as _aot
        _aot.wait(timeout=60.0)
        # queued-first through the scheduler's drain order...
        self._scheduler.drain("session-closed")
        # ...then any token the scheduler has not seen yet (admission
        # raced close): cancel idempotently, first reason wins
        with self._queries_lock:
            tokens = list(self._active_queries.values())
        for t in tokens:
            t.cancel("session-closed")
        # cancellation is COOPERATIVE: wait (bounded) for the driver
        # threads to unwind and unregister before sweeping, or the
        # sweep would unlink spill files a still-running task is about
        # to read — turning the classified QueryCancelled into an
        # unclassified FileNotFoundError
        if tokens:
            import time as _time
            deadline = _time.monotonic() + 5.0
            while _time.monotonic() < deadline:
                with self._queries_lock:
                    if not self._active_queries:
                        break
                _time.sleep(0.02)
        spill_mgr = getattr(self.mem_manager, "spill_manager", None)
        if spill_mgr is not None and hasattr(spill_mgr, "sweep_orphans"):
            spill_mgr.sweep_orphans()
        # a journal's in-process lifetime is bounded by its Session:
        # completed queries already deleted theirs; failed/cancelled
        # ones are reclaimed here (a journal that outlives its process
        # is exactly — and only — the crash-recovery inventory)
        for jr in self._journals:
            try:
                jr.complete()
            except Exception:   # pragma: no cover - cleanup best-effort
                pass
        self._journals = []
        # balance the warm-path cache's consumer registration (the
        # cache itself is process-wide and keeps its entries; only this
        # Session's memmgr attachment ends)
        if self._cache_attached:
            self._result_cache.detach(self.mem_manager)
            self._cache_attached = False
        # ops endpoint: drop this Session's acquisition — the LAST
        # release stops the server (clean shutdown, no dangling port)
        if self._ops is not None:
            from auron_tpu.obs import ops_server as _ops
            _ops.release()
            self._ops = None

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def execute(self, df: DataFrame,
                timeout_s: Optional[float] = None) -> pa.Table:
        from auron_tpu.obs import trace
        # nested execute (a host-fn child or scalar subquery driven from
        # inside an enclosing query): join the enclosing lifecycle — the
        # outer token's cancel/deadline covers the whole tree, and the
        # enclosing query's scheduler SLOT travels with the token (a
        # nested query must never queue behind its own parent)
        enclosing = getattr(self._tls, "token", None)
        if enclosing is not None:
            with trace.query_scope(label=f"p{df.num_partitions}"):
                op = self.plan_physical(df)
                return _collect(op, num_partitions=df.num_partitions,
                                mem_manager=self.mem_manager,
                                config=self.config,
                                cancel_token=enclosing)
        # one trace per TOP-LEVEL query: nested executes (host-fn
        # children, scalar subqueries) join the enclosing trace, and the
        # outermost scope exports into auron.trace.dir when set
        with self._admitted_query(timeout_s) as token:
            with trace.query_scope(label=f"p{df.num_partitions}"):
                # warm-path lookup BEFORE journal/plan work: an exact
                # re-submission (same plan fp + source fps + trace
                # salt — cache/identity.py) is answered from the
                # process cache; the key embeds the live source
                # fingerprints, so a mutated source simply misses
                pb_bytes = df.task_bytes()
                cache_key = self._result_cache.result_key(
                    pb_bytes, self.ctx.catalog)
                if cache_key is not None:
                    cached = self._result_cache.get_result(cache_key)
                    if cached is not None:
                        token.served_from = "cache"
                        return cached
                jr = self._journal_begin(df, token, plan_bytes=pb_bytes)
                try:
                    op = self.plan_physical(df)
                    # with bundles armed, mirror task metrics onto a
                    # positional tree as the query runs: a failure
                    # bundle then carries the explain-with-metrics of
                    # every task that DID finish (obs/bundle.py)
                    mtree = None
                    from auron_tpu.obs import bundle as _bundle
                    if _bundle.armed(self.config):
                        from auron_tpu.obs import metric_tree as mt
                        mtree = mt.build_tree(op)
                        token.plan_tree = mtree
                    table = _collect(op, num_partitions=df.num_partitions,
                                     mem_manager=self.mem_manager,
                                     config=self.config,
                                     metric_tree=mtree,
                                     cancel_token=token)
                except BaseException:
                    if jr is not None:
                        # the query failed IN-PROCESS: flush and keep
                        # the journal — an identical re-submission
                        # under auron.journal.reuse (or a resume) can
                        # pick the committed stages up; close() deletes
                        # whatever is never reused
                        jr.suspend()
                    raise
                if jr is not None:
                    jr.complete(write_report=True)
                    self._journal_discard(jr)
                if cache_key is not None:
                    self._result_cache.put_result(cache_key, table)
                from auron_tpu.cache import aot as _aot
                _aot.record_plan(pb_bytes, self.ctx.catalog,
                                 df.num_partitions, self.config)
                return table

    def _journal_discard(self, jr) -> None:
        """Drop a COMPLETED journal from the Session ledger (its disk
        state is already gone) — only suspended journals stay tracked,
        for close() to reclaim.  Without this a long-lived Session
        retains one QueryJournal (plan bytes included) per executed
        query forever."""
        try:
            self._journals.remove(jr)
        except ValueError:
            pass

    def _journal_begin(self, df: DataFrame, token, plan_bytes=None):
        """Open (adopt or mint) the crash-safe journal for one
        top-level query; None when journaling is disarmed or this plan
        opted out (runtime/journal.begin). ``plan_bytes`` lets the
        caller reuse an already-serialized plan (execute() serializes
        once for the cache key and the journal)."""
        from auron_tpu.runtime import journal as jrn
        if not jrn.enabled(self.config):
            return None
        if plan_bytes is None:
            plan_bytes = df.task_bytes()
        jr = jrn.begin(token, plan_bytes, df.num_partitions,
                       self.ctx.catalog, self.config)
        if jr is not None:
            self._journals.append(jr)
        return jr

    def resume(self, query_id: str,
               timeout_s: Optional[float] = None) -> pa.Table:
        """Resume a crashed process's journaled query: load + validate
        its journal (classified ResumeUnavailable / JournalCorrupt /
        JournalInvalidated on every not-resumable shape — never a wrong
        answer), re-plan from the journaled plan bytes, and execute
        with the journal bound so every fully-committed exchange is
        satisfied (map side skipped, reducers fetch the journaled RSS
        files) and partially-committed hash/round-robin/single
        exchanges skip exactly their committed maps. The resumed
        result is bit-identical to a fresh run, group order included;
        the journal (and its RSS run directory) is deleted at
        completion, leaving a resume report for
        tools/journal_report.py."""
        from auron_tpu.obs import trace
        from auron_tpu.runtime import journal as jrn
        jr = jrn.load_for_resume(jrn.journal_dir(self.config), query_id,
                                 self.ctx.catalog, self.config)
        try:
            with self._admitted_query(timeout_s) as token:
                with trace.query_scope(label=f"resume:{query_id}"):
                    jrn.attach_resumed(token, jr)
                    self._journals.append(jr)
                    op = plan_from_bytes(jr.plan_bytes, self.ctx)
                    if jr.scope == "task":
                        # serving-journaled Spark task: the host engine
                        # owns the partition fan-out — replay exactly
                        # the journaled task's own partition, not the
                        # whole range (which would over-produce)
                        from auron_tpu.runtime.executor import \
                            run_task_with_retries
                        task = pb.TaskDefinition.FromString(
                            jr.plan_bytes)
                        table = run_task_with_retries(
                            op, task.partition_id, jr.num_partitions,
                            mem_manager=self.mem_manager,
                            config=self.config, cancel_token=token)
                    else:
                        table = _collect(op,
                                         num_partitions=jr.num_partitions,
                                         mem_manager=self.mem_manager,
                                         config=self.config,
                                         cancel_token=token)
        except BaseException:
            # covers admission shedding / cancel-while-queued too: the
            # load claimed the journal's open stem, so EVERY unwind
            # must release it or the query becomes unresumable with
            # reason='open' until process restart (suspend is
            # idempotent — a no-op when the run already completed)
            jr.suspend()
            raise
        jr.complete(write_report=True)
        self._journal_discard(jr)
        return table

    def explain_analyze(self, df: DataFrame) -> str:
        """EXPLAIN ANALYZE: run the plan with a positional metric tree
        mirrored at every task finalize (obs/metric_tree — the
        update_metric_node walk of the reference, rt.rs:302-308) and
        render the annotated plan, followed by the query's program-cache
        footer (per-QUERY builds/hits — under the concurrent scheduler
        the central cache is shared across queries, so the hit rate a
        query actually enjoyed is its ledger's, not the process's)."""
        from auron_tpu.obs import metric_tree as mt
        from auron_tpu.obs import trace
        from auron_tpu.runtime import programs

        def analyzed(token) -> str:
            with trace.query_scope(label="explain_analyze"):
                op = self.plan_physical(df)
                tree, _table = mt.explain_analyze(
                    op, num_partitions=df.num_partitions,
                    mem_manager=self.mem_manager, config=self.config,
                    cancel_token=token)
            snap = programs.query_totals(token.query_id)
            total = snap.builds + snap.hits
            footer = (f"[program cache] builds={snap.builds} "
                      f"hits={snap.hits} hit_rate="
                      f"{(snap.hits / total * 100.0) if total else 0.0:.1f}%"
                      f" (query {token.query_id})\n")
            # warm-path result cache: PROCESS totals (the cache is
            # shared across sessions/queries by design — explain runs
            # fresh for the metric tree, so its own lookup is not in
            # these numbers)
            rc = self._result_cache.stats()
            footer += (f"[result cache] enabled={rc['enabled']} "
                       f"hits={rc['hits']} misses={rc['misses']} "
                       f"evictions={rc['evictions']} "
                       f"entries={rc['entries']} bytes={rc['bytes']}\n")
            return mt.render(tree) + footer

        # nested (a host fn analyzing mid-query): inherit the enclosing
        # token and slot exactly like execute() — acquiring here would
        # queue this analysis behind its own slot-holding parent
        enclosing = getattr(self._tls, "token", None)
        if enclosing is not None:
            return analyzed(enclosing)
        with self._admitted_query(None) as token:
            return analyzed(token)
