"""Physical planner: proto PlanNode → PhysicalOp tree.

The engine-side half of the contract — the analogue of the reference's
``PhysicalPlanner::create_plan`` (reference:
native-engine/auron-planner/src/planner.rs:121-856), recursively
materializing executable operators from the IR. Scans resolve named tables
through a catalog; exchange/broadcast nodes resolve cross-stage data through
a resource map (the analogue of JniBridge.putResource/getResource,
reference: auron-core/src/main/java/org/apache/auron/jni/JniBridge.java).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import pyarrow as pa

from auron_tpu.columnar.schema import Schema
from auron_tpu.ir import auron_pb2 as pb
from auron_tpu.ir import serde
from auron_tpu.ops.base import PhysicalOp


@dataclass
class PlannerContext:
    """Host-side environment plans resolve against.

    catalog: table name → pyarrow.Table (or list of per-partition
      RecordBatch lists) for MemoryScanNode.
    resources: id → engine object (bucketed shuffle output, broadcast
      batches, bloom filters...) for IpcReader/BroadcastJoin nodes.
    """

    catalog: dict[str, Any] = field(default_factory=dict)
    resources: dict[str, Any] = field(default_factory=dict)
    # None = resolve from the typed config (auron.batch.capacity)
    batch_capacity: Optional[int] = None
    config: Optional[Any] = None
    #: (table name, column index) -> (table ref, (min, max)) — memoizes
    #: the O(n) key-column stats scan the dense-kernel derivation needs,
    #: so repeated planning over a registered table pays it once. The
    #: entry holds a STRONG reference to the scanned table and hits only
    #: on identity (`is`): a re-registered table can never alias a
    #: recycled id and serve stale stats for different data
    _col_stats: dict = field(default_factory=dict)

    def __post_init__(self):
        from auron_tpu import config as cfg
        if self.config is None:
            self.config = cfg.get_config()
        if self.batch_capacity is None:
            self.batch_capacity = self.config.get(cfg.BATCH_CAPACITY)

    def put_resource(self, rid: str, value: Any) -> None:
        self.resources[rid] = value

    def get_resource(self, rid: str) -> Any:
        if rid not in self.resources:
            raise KeyError(f"unknown resource id {rid!r}")
        return self.resources[rid]


class PhysicalPlanner:
    def __init__(self, ctx: Optional[PlannerContext] = None):
        self.ctx = ctx or PlannerContext()
        #: subplans of the tree being planned that several parents read
        #: (ir/reuse.py): serialised AggNode -> what its handles share
        self._shared: dict = {}

    # -- entry points -------------------------------------------------------

    def plan_task(self, task: pb.TaskDefinition) -> PhysicalOp:
        # the file scans read the columns the plan reads (ir/pruning.py);
        # a scalar subquery's own plan is pruned when the binder plans it
        from auron_tpu.ir.pruning import prune_scan_columns
        plan = prune_scan_columns(task.plan)
        if _collect_subqueries(plan):
            # resolve every uncorrelated scalar subquery in the tree ONCE
            # at task start, then re-plan with literals substituted
            # (reference: spark_scalar_subquery_wrapper.rs role); the
            # binder applies the stage-fusion pass after substitution
            from auron_tpu.ops.subquery import ScalarSubqueryBinderOp
            return ScalarSubqueryBinderOp(plan, self.ctx)
        return self.plan_tree(plan)

    def plan_tree(self, plan: pb.PlanNode) -> PhysicalOp:
        """Operators for a pruned plan without scalar subqueries: a
        subplan that several parents read is planned once (ir/reuse.py),
        then the post-planning passes run over the whole tree, which
        reach that subplan through the one handle that owns it."""
        from auron_tpu.ir.reuse import find_shared_subplans
        from auron_tpu.ops.reuse import SharedSubplan
        self._shared = {key: SharedSubplan()
                        for key in find_shared_subplans(plan)}
        return self.finalize_plan(self.create_plan(plan))

    def finalize_plan(self, op: PhysicalOp) -> PhysicalOp:
        """Post-planning passes over the materialized operator tree:
        whole-stage fusion (fuse_stages — greedy chains plus the
        map-side combine fold), then the SPMD mesh annotation
        (annotate_mesh — a no-op while auron.mesh.enabled is off)."""
        from auron_tpu.parallel import mesh as mesh_mod
        return annotate_mesh(fuse_stages(op, self.ctx.config),
                             mesh_mod.current_plane())

    def create_plan(self, node: pb.PlanNode) -> PhysicalOp:
        kind = node.WhichOneof("node")
        if kind is None:
            raise ValueError("empty PlanNode")
        handler = getattr(self, f"_plan_{kind}", None)
        if handler is None:
            raise NotImplementedError(f"plan node {kind!r} not supported yet")
        return handler(getattr(node, kind))

    # -- sources ------------------------------------------------------------

    def _file_scan_batch_rows(self) -> int:
        """File-scan batch sizing: ``auron.scan.batch_rows`` when set;
        0 (the default) resolves per platform — 2^17 on the CPU mesh,
        where larger batches amortize the per-batch host glue that
        dominates throughput (PERF.md 'Pipelined execution'), else the
        legacy ``auron.io.parquet.batch_rows``. The scan clamps its
        conversion capacity to the partition's actual row-count bucket,
        so the larger default never inflates small files' padding."""
        from auron_tpu import config as cfg
        explicit = int(self.ctx.config.get(cfg.SCAN_BATCH_ROWS))
        if explicit > 0:
            return explicit
        import jax
        if jax.default_backend() == "cpu":
            return 1 << 17
        return self.ctx.config.get(cfg.PARQUET_BATCH_ROWS)

    def _plan_parquet_scan(self, n: pb.ParquetScanNode) -> PhysicalOp:
        from auron_tpu.io.parquet import ParquetScanOp
        return ParquetScanOp(
            files=list(n.files),
            schema=serde.parse_schema(n.schema) if n.schema.fields else None,
            columns=list(n.columns) or None,
            predicates=[serde.parse_expr(p) for p in n.predicates],
            batch_rows=n.batch_rows or self._file_scan_batch_rows(),
        )

    def _plan_orc_scan(self, n: pb.OrcScanNode) -> PhysicalOp:
        from auron_tpu.io.orc import OrcScanOp
        return OrcScanOp(
            files=list(n.files),
            schema=serde.parse_schema(n.schema) if n.schema.fields else None,
            columns=list(n.columns) or None,
            batch_rows=n.batch_rows or self._file_scan_batch_rows(),
        )

    def _plan_memory_scan(self, n: pb.MemoryScanNode) -> PhysicalOp:
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.io.parquet import MemoryScanOp
        if n.table_name not in self.ctx.catalog:
            raise KeyError(
                f"table {n.table_name!r} not in planner catalog "
                f"(known: {sorted(self.ctx.catalog)})")
        table = self.ctx.catalog[n.table_name]
        if isinstance(table, pa.Table):
            partitions = [table.to_batches(
                max_chunksize=n.batch_rows or self.ctx.batch_capacity)]
            schema = schema_from_arrow(table.schema)
        else:  # pre-partitioned: list[list[RecordBatch]]
            partitions = table
            schema = schema_from_arrow(partitions[0][0].schema)
        return MemoryScanOp(partitions, schema,
                            capacity=n.batch_rows or self.ctx.batch_capacity)

    def _plan_ipc_reader(self, n: pb.IpcReaderNode) -> PhysicalOp:
        from auron_tpu.io.parquet import DeviceBatchScanOp
        partitions = self.ctx.get_resource(n.resource_id)
        return DeviceBatchScanOp(partitions, serde.parse_schema(n.schema))

    def _plan_empty_partitions(self, n: pb.EmptyPartitionsNode) -> PhysicalOp:
        from auron_tpu.ops.limit import EmptyPartitionsOp
        return EmptyPartitionsOp(serde.parse_schema(n.schema),
                                 n.num_partitions)

    def _plan_kafka_scan(self, n: pb.KafkaScanNode) -> PhysicalOp:
        from auron_tpu.streaming.kafka import KafkaScanOp
        return KafkaScanOp(topic=n.topic, bootstrap=n.bootstrap,
                           schema=serde.parse_schema(n.schema),
                           fmt=n.format or "json",
                           max_batches=n.max_batches or None,
                           group_id=n.group_id or None)

    def _plan_streaming_window_agg(
            self, n: pb.StreamingWindowAggNode) -> PhysicalOp:
        from auron_tpu.streaming.window import StreamingWindowAggOp
        return StreamingWindowAggOp(
            self.create_plan(n.child), n.time_col, n.window_us,
            [serde.parse_expr(e) for e in n.group_exprs],
            [serde.parse_agg(a) for a in n.aggs],
            ooo_bound_us=n.ooo_bound_us,
            group_names=list(n.group_names) or None,
            agg_names=list(n.agg_names) or None)

    # -- row transforms -----------------------------------------------------

    def _plan_filter(self, n: pb.FilterNode) -> PhysicalOp:
        from auron_tpu.ops.project import FilterOp
        return FilterOp(self.create_plan(n.child),
                        [serde.parse_expr(p) for p in n.predicates])

    def _plan_project(self, n: pb.ProjectNode) -> PhysicalOp:
        from auron_tpu.ops.project import ProjectOp
        return ProjectOp(self.create_plan(n.child),
                         [serde.parse_expr(e) for e in n.exprs],
                         list(n.names))

    def _plan_agg(self, n: pb.AggNode) -> PhysicalOp:
        if self._shared:
            from auron_tpu.ir.reuse import subplan_key
            shared = self._shared.get(subplan_key(n))
            if shared is not None:
                # the first parent to be planned brings the producer
                from auron_tpu.ops.reuse import SubplanReadOp
                if shared.owner is None:
                    return SubplanReadOp(shared, self._plan_agg_node(n))
                return SubplanReadOp(shared)
        return self._plan_agg_node(n)

    def _plan_agg_node(self, n: pb.AggNode) -> PhysicalOp:
        from auron_tpu import config as cfg
        from auron_tpu.ops.agg import AggOp
        child = self.create_plan(n.child)
        group_exprs = [serde.parse_expr(e) for e in n.group_exprs]
        aggs = [serde.parse_agg(a) for a in n.aggs]
        return AggOp(
            child, group_exprs, aggs,
            mode=n.mode or "complete",
            group_names=list(n.group_names) or None,
            agg_names=list(n.agg_names) or None,
            initial_capacity=self.ctx.config.get(cfg.AGG_INITIAL_CAPACITY),
            key_domain=self._agg_key_domain(n, child, group_exprs, aggs),
        )

    def _agg_key_domain(self, n: pb.AggNode, child: PhysicalOp,
                        group_exprs, aggs) -> Optional[int]:
        """Table-stats key-domain bound for the dense grouped-agg kernels
        (auron_tpu/kernels): proven, not guessed. A bound is derived only
        when the single group key is a direct ColumnRef reaching a
        catalog memory table through schema-preserving nodes, the key
        column is integer, null-free and non-negative, and every
        aggregate is exact under the dense formulation (count/min/max,
        and sum/avg over integers — float sums re-associate on the MXU
        grids, so auto-selection skips them; an explicit AggOp
        key_domain hint still enables the float path). The bound is
        re-verified at runtime by the operator (ops/agg.py)."""
        from auron_tpu import config as cfg
        from auron_tpu.columnar.schema import DataType
        from auron_tpu.exprs import ir
        from auron_tpu.exprs.eval import infer_dtype
        conf = self.ctx.config
        try:
            if not conf.get(cfg.KERNELS_ENABLED):
                return None
            if (n.mode or "complete") not in ("partial", "complete"):
                return None
            if len(group_exprs) != 1 or not isinstance(group_exprs[0],
                                                       ir.ColumnRef):
                return None
            from auron_tpu.kernels.dispatch import DENSE_VALUE_DTYPES
            schema = child.schema()
            ints = (DataType.INT8, DataType.INT16, DataType.INT32,
                    DataType.INT64)
            for a in aggs:
                if a.distinct and a.fn not in ("min", "max"):
                    return None
                # mirror the runtime dispatch's value-dtype filter so
                # the stats scan below is never paid for a plan that
                # falls back at execute time anyway
                if a.arg is not None and \
                        infer_dtype(a.arg, schema)[0] not in \
                        DENSE_VALUE_DTYPES:
                    return None
                if a.fn in ("count", "count_star", "min", "max"):
                    continue
                if a.fn in ("sum", "avg") and a.arg is not None \
                        and infer_dtype(a.arg, schema)[0] in ints:
                    continue
                return None
            # walk to a memory scan through schema-preserving nodes
            node = n.child
            while True:
                kind = node.WhichOneof("node")
                if kind == "filter":
                    node = node.filter.child
                elif kind == "coalesce_batches":
                    node = node.coalesce_batches.child
                elif kind == "memory_scan":
                    break
                else:
                    return None
            table = self.ctx.catalog.get(node.memory_scan.table_name)
            if not isinstance(table, pa.Table):
                return None
            idx = group_exprs[0].index
            if not 0 <= idx < table.num_columns:
                return None
            col = table.column(idx)
            if not pa.types.is_integer(col.type) or col.null_count \
                    or table.num_rows == 0:
                return None
            ckey = (node.memory_scan.table_name, idx)
            cached = self.ctx._col_stats.get(ckey)
            if cached is not None and cached[0] is table:
                stats = cached[1]
            else:
                import pyarrow.compute as pc
                mm = pc.min_max(col)
                stats = (mm["min"].as_py(), mm["max"].as_py())
                self.ctx._col_stats[ckey] = (table, stats)
            lo, hi = stats
            if lo is None or lo < 0:
                return None
            if hi + 1 > conf.get(cfg.KERNELS_MAX_KEY_DOMAIN):
                return None
            return int(hi) + 1
        except Exception:
            # stats derivation is advisory; a failure here must never
            # fail planning — the sort path is always correct
            return None

    def _plan_sort(self, n: pb.SortNode) -> PhysicalOp:
        from auron_tpu.ops.sort import SortOp
        # proto3 leaves unset fetch at 0; a 0-row top-k is meaningless, so
        # any fetch <= 0 means "no limit"
        return SortOp(self.create_plan(n.child),
                      [serde.parse_sort_order(o) for o in n.sort_orders],
                      fetch=None if n.fetch <= 0 else n.fetch)

    def _plan_limit(self, n: pb.LimitNode) -> PhysicalOp:
        from auron_tpu.ops.limit import LimitOp
        return LimitOp(self.create_plan(n.child), n.limit)

    def _plan_union(self, n: pb.UnionNode) -> PhysicalOp:
        from auron_tpu.ops.limit import UnionOp
        return UnionOp([self.create_plan(c) for c in n.children])

    def _plan_coalesce_batches(self, n: pb.CoalesceBatchesNode) -> PhysicalOp:
        from auron_tpu.ops.limit import CoalesceBatchesOp
        return CoalesceBatchesOp(self.create_plan(n.child), n.target_rows)

    def _plan_rename_columns(self, n: pb.RenameColumnsNode) -> PhysicalOp:
        from auron_tpu.ops.limit import RenameColumnsOp
        return RenameColumnsOp(self.create_plan(n.child), list(n.names))

    def _plan_debug(self, n: pb.DebugNode) -> PhysicalOp:
        from auron_tpu.ops.debug import DebugOp
        return DebugOp(self.create_plan(n.child), n.label)

    def _plan_window(self, n: pb.WindowNode) -> PhysicalOp:
        from auron_tpu.ops.window import WindowFunctionSpec, WindowOp
        fns = []
        for f in n.functions:
            default = None
            if f.HasField("default_value"):
                default = serde._parse_literal(f.default_value).value
            fns.append(WindowFunctionSpec(
                kind=f.kind, fn=f.fn,
                arg=serde.parse_expr(f.arg) if f.HasField("arg") else None,
                offset=f.offset if f.HasField("offset") else 1,
                default=default,
                frame=((f.frame_lo, f.frame_hi)
                       if (f.HasField("frame_lo")
                           or f.HasField("frame_hi")) else None)))
        return WindowOp(
            self.create_plan(n.child),
            partition_by=[serde.parse_expr(e) for e in n.partition_by],
            order_by=[serde.parse_sort_order(o) for o in n.order_by],
            functions=fns,
            output_names=list(n.output_names) or None,
            group_limit=None if n.group_limit < 0 else (n.group_limit or None),
        )

    def _plan_expand(self, n: pb.ExpandNode) -> PhysicalOp:
        from auron_tpu.ops.expand import ExpandOp
        return ExpandOp(
            self.create_plan(n.child),
            [[serde.parse_expr(e) for e in proj.exprs]
             for proj in n.projections],
            list(n.names) or None,
        )

    def _plan_generate(self, n: pb.GenerateNode) -> PhysicalOp:
        from auron_tpu.ops.generate import GenerateOp
        return GenerateOp(
            self.create_plan(n.child),
            kind=n.kind,
            generator=serde.parse_expr(n.generator)
            if n.HasField("generator") else None,
            json_fields=list(n.json_fields),
            udtf_name=n.udtf_registry_name or None,
            required_child_output=list(n.required_child_output),
            outer=n.outer,
            output_names=list(n.output_names) or None,
        )

    # -- joins --------------------------------------------------------------

    def _plan_hash_join(self, n: pb.HashJoinNode) -> PhysicalOp:
        from auron_tpu.ops.joins import HashJoinOp
        return HashJoinOp(
            self.create_plan(n.probe), self.create_plan(n.build),
            [serde.parse_expr(e) for e in n.probe_keys],
            [serde.parse_expr(e) for e in n.build_keys],
            join_type=n.join_type or "inner",
        )

    def _plan_cross_join(self, n: pb.CrossJoinNode) -> PhysicalOp:
        from auron_tpu.ops.joins import CrossJoinOp
        if (n.join_type or "inner") != "inner" or n.HasField("condition"):
            raise NotImplementedError(
                f"join without keys: type {n.join_type or 'inner'!r}"
                + (" with a condition" if n.HasField("condition") else "")
                + "; only the inner join without a condition is planned")
        return CrossJoinOp(self.create_plan(n.probe),
                           self.create_plan(n.build))

    def _plan_sort_merge_join(self, n: pb.SortMergeJoinNode) -> PhysicalOp:
        from auron_tpu.ops.joins import SortMergeJoinOp
        return SortMergeJoinOp(
            self.create_plan(n.probe), self.create_plan(n.build),
            [serde.parse_expr(e) for e in n.probe_keys],
            [serde.parse_expr(e) for e in n.build_keys],
            join_type=n.join_type or "inner",
        )

    def _plan_broadcast_join(self, n: pb.BroadcastJoinNode) -> PhysicalOp:
        from auron_tpu.io.parquet import DeviceBatchScanOp
        from auron_tpu.ops.joins import HashJoinOp
        build_partitions = self.ctx.get_resource(n.build_resource_id)
        build = DeviceBatchScanOp(build_partitions,
                                  serde.parse_schema(n.build_schema))
        return HashJoinOp(
            self.create_plan(n.probe), build,
            [serde.parse_expr(e) for e in n.probe_keys],
            [serde.parse_expr(e) for e in n.build_keys],
            join_type=n.join_type or "inner",
        )

    # -- exchange -----------------------------------------------------------

    def _parse_partitioning(self, p: pb.PartitioningP):
        from auron_tpu.parallel.partitioning import (HashPartitioning,
                                                     RoundRobinPartitioning,
                                                     SinglePartitioning)
        if p.kind == "hash":
            return HashPartitioning(
                tuple(serde.parse_expr(e) for e in p.hash_keys),
                p.num_partitions)
        if p.kind == "round_robin":
            return RoundRobinPartitioning(p.num_partitions)
        if p.kind == "single":
            return SinglePartitioning()
        if p.kind == "range":
            # bounds are sampled at execution time by the exchange operator
            from auron_tpu.parallel.partitioning import RangePartitioning
            return RangePartitioning(
                tuple(serde.parse_sort_order(o) for o in p.range_orders),
                p.num_partitions, bounds=())
        raise NotImplementedError(f"partitioning {p.kind!r}")

    def _plan_shuffle_writer(self, n: pb.ShuffleWriterNode) -> PhysicalOp:
        rss_root, shuffle_id, orphan_sweep = n.rss_root, n.shuffle_id, True
        journal = None
        if rss_root:
            # explicit RSS root with a journal active (a journaled —
            # or RESUMING — serving task): restrict the service's
            # startup sweep to .part files. The full sweep rmtree's a
            # dead predecessor's UNCOMMITTED shuffle dirs, which is
            # exactly where the individually-committed map outputs a
            # task-scope journal recorded live until resume reuses
            # them (eager GC of such dirs falls to non-journaled
            # constructions of the same root).
            from auron_tpu.runtime import journal as jrn
            if jrn.active_journal() is not None:
                orphan_sweep = "parts"
        if not rss_root:
            # crash-safe journal routing (runtime/journal.py): while a
            # journal is active for the driving thread's query, its
            # shuffles lower through the DURABLE RSS tier under the
            # journal's run directory, with shuffle ids assigned in
            # plan-walk order — deterministic, so a fresh process
            # re-planning the identical bytes reproduces them and
            # resume can match committed stages to plan nodes. The
            # journal's own sweep governs whole-dir lifecycle there
            # (a dead predecessor's partial maps are what resume
            # reuses), so the service sweeps .part files only.
            from auron_tpu.runtime import journal as jrn
            journal = jrn.active_journal()
            if journal is not None:
                shuffle_id = journal.next_shuffle_id()
                # mesh-aware journal routing: consume the plan-walk
                # shuffle id UNCONDITIONALLY (resume re-plans the same
                # bytes and must reproduce every id, whichever tier
                # each exchange lands on), then route by the CURRENT
                # exchange_route verdict. An exchange the mesh can
                # carry stays on the all_to_all fast path — journaling
                # a query must not silently forfeit 8-wide exchanges to
                # the durable tier — at the price of that one stage's
                # resumability. The exception is an exchange the
                # journal already holds durable state for (a RESUME
                # onto a possibly NARROWER mesh): its committed maps
                # live on the RSS tier, so it re-plans there
                # regardless of what the current plane could carry.
                from auron_tpu.parallel import mesh as mesh_mod
                route, _ = mesh_mod.exchange_route(
                    self._parse_partitioning(n.partitioning),
                    n.partitioning.num_partitions,
                    n.input_partitions or 1, mesh_mod.current_plane())
                if route == "all_to_all" \
                        and not journal.has_shuffle_state(shuffle_id):
                    journal = None   # non-durable mesh fast path
                else:
                    rss_root = journal.rss_root
                    orphan_sweep = "parts"
        if rss_root:
            # RSS tier: push partition frames to the host shuffle service
            # so other hosts can read them (exchange.RssShuffleExchangeOp)
            from auron_tpu.parallel.exchange import RssShuffleExchangeOp
            from auron_tpu.parallel.shuffle_service import FileShuffleService
            op = RssShuffleExchangeOp(
                self.create_plan(n.child),
                self._parse_partitioning(n.partitioning),
                FileShuffleService(rss_root, orphan_sweep=orphan_sweep),
                shuffle_id,
                input_partitions=n.input_partitions or 1)
            if journal is not None:
                journal.record_exchange(
                    shuffle_id, n.input_partitions or 1,
                    n.partitioning.num_partitions,
                    n.partitioning.kind or "single")
        else:
            from auron_tpu.parallel.exchange import ShuffleExchangeOp
            op = ShuffleExchangeOp(self.create_plan(n.child),
                                   self._parse_partitioning(n.partitioning),
                                   input_partitions=n.input_partitions or 1)
        if n.output_resource_id:
            self.ctx.put_resource(n.output_resource_id, op)
        return op

    def _plan_rss_shuffle_read(self, n: pb.RssShuffleReadNode) -> PhysicalOp:
        from auron_tpu.parallel.exchange import RssShuffleReadOp
        from auron_tpu.parallel.shuffle_service import FileShuffleService
        from auron_tpu.runtime import journal as jrn
        # same sweep restriction as _plan_shuffle_writer, and for the
        # same reason: read nodes plan BEFORE writer nodes, so a full
        # sweep here would rmtree the dead predecessor's uncommitted
        # dirs (and memoize the root) before the writer's 'parts'
        # guard ever ran — destroying the committed maps a task-scope
        # journal recorded for resume
        sweep = "parts" if jrn.active_journal() is not None else True
        return RssShuffleReadOp(
            FileShuffleService(n.rss_root, orphan_sweep=sweep),
            n.shuffle_id, serde.parse_schema(n.schema),
            n.num_partitions or 1)

    def _plan_broadcast_exchange(self, n: pb.BroadcastExchangeNode) -> PhysicalOp:
        from auron_tpu.parallel.exchange import BroadcastExchangeOp
        # warm-path subplan identity (auron.cache.subplan): fingerprint
        # the broadcast SUBTREE as its own plan — same identity
        # components as a full result (cache/identity.py), with the
        # input fan-out folded in — so successive/concurrent queries
        # whose outer plans differ still share the built relation
        subplan_key = None
        try:
            from auron_tpu.cache import result_cache as _rcache
            cache = _rcache.get_cache()
            if cache.subplan_enabled():
                subplan_key = cache.subplan_cache_key(
                    pb.TaskDefinition(plan=n.child).SerializeToString(),
                    self.ctx.catalog,
                    input_partitions=n.input_partitions or 1)
        except Exception:   # planning must survive a cache-plane bug
            subplan_key = None
        op = BroadcastExchangeOp(self.create_plan(n.child),
                                 input_partitions=n.input_partitions or 1,
                                 subplan_key=subplan_key)
        if n.output_resource_id:
            self.ctx.put_resource(n.output_resource_id, op)
        return op

    # -- sinks --------------------------------------------------------------

    def _plan_parquet_sink(self, n: pb.ParquetSinkNode) -> PhysicalOp:
        from auron_tpu.io.sinks import ParquetSinkOp
        return ParquetSinkOp(self.create_plan(n.child), n.path,
                             partition_by=list(n.partition_by),
                             compression=n.compression or "snappy")

    def _plan_orc_sink(self, n: pb.OrcSinkNode) -> PhysicalOp:
        from auron_tpu.io.sinks import OrcSinkOp
        return OrcSinkOp(self.create_plan(n.child), n.path,
                         compression=n.compression or "zstd")


def plan_from_bytes(data: bytes,
                    ctx: Optional[PlannerContext] = None) -> PhysicalOp:
    """Decode a serialized TaskDefinition and materialize its plan — the
    `callNative` entry analogue (reference: auron/src/exec.rs:42-118)."""
    task = pb.TaskDefinition.FromString(data)
    return PhysicalPlanner(ctx).plan_task(task)


# ---------------------------------------------------------------------------
# whole-stage fusion pass
# ---------------------------------------------------------------------------

#: bound on the fan-out product (expand projections multiply the batch
#: count inside one program) a fused stage may unroll
_MAX_STAGE_FANOUT = 16


def fuse_stages(op: PhysicalOp, config=None) -> PhysicalOp:
    """Whole-stage fusion (ops/fused.py): greedily group maximal chains
    of fusable row-local operators into FusedStageOp nodes, and push the
    key/value projection of partial/complete aggregations below the agg
    so its expression evaluation joins the fused chain. Stage breakers —
    agg cores, joins, sorts, exchanges, window, generate, scans — never
    implement the fragment protocol, so a chain cannot cross them by
    construction. Gated on ``auron.fusion.enabled``; chain length is
    bounded by ``auron.fusion.max_stage_ops``.

    After the greedy chaining, _fold_combine walks the tree and stamps
    the map-side combine fold on every eligible
    exchange-over-partial-agg shape. The tree is a function of the plan
    and the configuration alone: nothing an earlier task ran decides
    it."""
    from auron_tpu import config as cfg
    conf = config if config is not None else cfg.get_config()
    # the pre-agg projection normalization runs regardless of the fusion
    # switch: it moves key/value expression evaluation from the agg's
    # eager per-batch loop into a jitted project kernel, and eager vs
    # jitted float arithmetic differ in the last ulp (XLA contracts
    # elementwise chains) — applying it on BOTH settings keeps
    # fusion.enabled on/off bit-identical, the differential battery's
    # contract
    op = _normalize(op)
    if not conf.get(cfg.FUSION_ENABLED):
        return op
    max_ops = max(2, conf.get(cfg.FUSION_MAX_STAGE_OPS))
    op = _fuse(op, max_ops)
    _fold_combine(op)
    return op


def _normalize(op: PhysicalOp) -> PhysicalOp:
    op = _elide_agg_child_projection(op)
    op = _push_agg_projection(op)
    _replace_children(op, _normalize)
    return op


def _wrap_single(child: PhysicalOp) -> PhysicalOp:
    """Wrap a lone computing fusable op so a fold-capable parent (the
    exchange's split, the hash join's probe) can absorb its fragment
    into ONE program. Pass-through ops (limit/rename) stay bare — their
    host-side bookkeeping is free, a program for it would not be."""
    from auron_tpu.ops.fused import FusedStageOp
    if getattr(child, "fusable", False) and child.fragment_computes \
            and not isinstance(child, FusedStageOp):
        return FusedStageOp([child])
    return child


def _fuse(op: PhysicalOp, max_ops: int) -> PhysicalOp:
    from auron_tpu.ops.fused import FusedStageOp
    from auron_tpu.ops.joins import HashJoinOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    if isinstance(op, (ShuffleExchangeOp, HashJoinOp)):
        _replace_children(op, lambda c: _fuse(c, max_ops))
        # wrap a lone computing child: the exchange folds the stage's
        # fragments into its split program, the join into its probe
        # program (chain + pids/keys + sort/search = ONE XLA launch)
        if isinstance(op, ShuffleExchangeOp):
            op.child = _wrap_single(op.child)
        else:
            op.probe = _wrap_single(op.probe)
        return op
    if getattr(op, "fusable", False):
        # collect the maximal chain op → … → deepest fusable descendant
        chain = [op]
        fanout = op.fusion_fanout
        while True:
            child = chain[-1].children[0]
            if not getattr(child, "fusable", False):
                break
            if len(chain) >= max_ops:
                break
            if fanout * child.fusion_fanout > _MAX_STAGE_FANOUT:
                break
            chain.append(child)
            fanout *= child.fusion_fanout
        # recurse below the stage input, keeping the member links intact
        tail = chain[-1]
        _replace_children(tail, lambda c: _fuse(c, max_ops))
        if len(chain) >= 2 and any(m.fragment_computes for m in chain):
            # a chain of pure pass-throughs (limit→rename) would compile
            # a program for work the host loop does for free — skip
            return FusedStageOp(list(reversed(chain)))
        return op
    _replace_children(op, lambda c: _fuse(c, max_ops))
    return op


def _fold_combine(op: PhysicalOp) -> None:
    """Runs after _fuse: stamp the map-side combine fold on every hash
    exchange whose child is an eligible partial AggOp, or the reason it
    is not eligible.

    The fold keeps the agg node in the tree (schema, metrics and explain
    stay intact — the folded-chain convention); at materialize time the
    exchange executes the agg's child with the combine stage folded into
    its split program."""
    from auron_tpu.exprs import ir as xir
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    from auron_tpu.parallel.partitioning import (HashPartitioning,
                                                 SinglePartitioning)

    def keys_only(exchange, n_keys: int) -> bool:
        # every partitioning expr must be a plain ref into the group-key
        # prefix of the partial layout: a group's rows (combined or not)
        # then land on ONE reducer — the fold's correctness condition
        if isinstance(exchange.partitioning, SinglePartitioning):
            return True
        if not isinstance(exchange.partitioning, HashPartitioning):
            return False
        return all(isinstance(e, xir.ColumnRef) and e.index < n_keys
                   for e in exchange.partitioning.exprs)

    def walk(o: PhysicalOp) -> None:
        if isinstance(o, ShuffleExchangeOp) and isinstance(o.child, AggOp):
            child = o.child
            reason = child.combine_fold_reason()
            if reason is None \
                    and not keys_only(o, len(child.group_exprs)):
                reason = "partitioning_not_on_keys"
            if reason is None:
                o.combine_mode = "combine"
                # a lone computing op under the agg folds as a chain
                child.child = _wrap_single(child.child)
            else:
                o.combine_why = reason
        for c in o.children:
            walk(c)

    walk(op)


def _replace_children(op: PhysicalOp, fn) -> None:
    """Apply ``fn`` to every direct child and swap the rewritten ops back
    into the parent's attributes (operators hold children as plain
    attributes — ``child``, ``probe``/``build``, ``inputs`` lists)."""
    for name, val in list(vars(op).items()):
        if isinstance(val, PhysicalOp):
            setattr(op, name, fn(val))
        elif isinstance(val, list) and val \
                and all(isinstance(v, PhysicalOp) for v in val):
            setattr(op, name, [fn(v) for v in val])


def _elide_agg_child_projection(op: PhysicalOp) -> PhysicalOp:
    """Drop a pure column-pick ProjectOp feeding an aggregation: when the
    agg's group/arg expressions are plain ColumnRefs into a projection
    whose referenced outputs are themselves plain ColumnRefs, the
    projection does no device compute the agg needs — the agg's
    per-batch contribution step picks columns by index anyway, so the
    refs are remapped to the projection's input and one whole program
    per (exprs, schema, capacity) disappears from the plan. Values are
    untouched (identical column arrays), so results are bit-identical
    under both fusion settings."""
    from auron_tpu.exprs import ir as eir
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.ops.project import ProjectOp
    if not isinstance(op, AggOp) or not op.from_rows:
        return op
    child = op.children[0]
    if not isinstance(child, ProjectOp):
        return op
    for a in op.aggs:
        if a.fn == "bloom_filter" or a.fn.startswith("udaf:"):
            return op
    used = list(op.group_exprs) + [a.arg for a in op.aggs
                                   if a.arg is not None]
    if not used or not all(isinstance(e, eir.ColumnRef) for e in used):
        return op
    refs = {e.index for e in used}
    if not all(0 <= i < len(child.exprs)
               and isinstance(child.exprs[i], eir.ColumnRef)
               for i in refs):
        return op
    remap = {i: child.exprs[i].index for i in refs}
    from dataclasses import replace as _dc_replace
    new_groups = [eir.ColumnRef(remap[e.index]) for e in op.group_exprs]
    new_aggs = [a if a.arg is None
                else _dc_replace(a, arg=eir.ColumnRef(remap[a.arg.index]))
                for a in op.aggs]
    rewritten = AggOp(child.children[0], new_groups, new_aggs, mode=op.mode,
                      group_names=op.group_names, agg_names=op.agg_names,
                      initial_capacity=op.initial_capacity,
                      key_domain=op.key_domain)
    if rewritten.schema() != op.schema():
        return op
    # the child's child may itself be a pure projection: elide again
    return _elide_agg_child_projection(rewritten)


def _push_agg_projection(op: PhysicalOp) -> PhysicalOp:
    """Pre-agg key/value projection: rewrite AggOp(group_exprs, aggs)
    over arbitrary expressions into AggOp(ColumnRefs) over a ProjectOp
    evaluating those expressions — the projection then fuses with the
    chain below the agg, so key/value evaluation runs inside the fused
    stage program instead of eagerly per batch in the agg's host loop.
    Only for partial/complete device-side aggregations; the rewrite is
    expression-for-expression, so results are bit-identical."""
    from auron_tpu.exprs import ir as eir
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.ops.project import ProjectOp
    if not isinstance(op, AggOp) or not op.from_rows:
        return op
    for a in op.aggs:
        # host-side accumulator states (bloom/udaf) evaluate their own
        # inputs against the child schema — leave those plans untouched
        if a.fn == "bloom_filter" or a.fn.startswith("udaf:"):
            return op
    if not getattr(op.children[0], "fusable", False):
        # nothing below to fuse the projection into (agg over a join /
        # exchange / scan): a standalone projection would ADD a program
        # without saving one — leave key/value evaluation to the agg's
        # per-batch loop, identically under both fusion settings
        return op
    used = list(op.group_exprs) + [a.arg for a in op.aggs
                                   if a.arg is not None]
    if not used or all(isinstance(e, eir.ColumnRef) for e in used):
        return op   # nothing to push down

    proj_exprs: list = []
    index_of: dict = {}

    def col(e):
        if e not in index_of:
            index_of[e] = len(proj_exprs)
            proj_exprs.append(e)
        return eir.ColumnRef(index_of[e])

    new_groups = [col(e) for e in op.group_exprs]
    from dataclasses import replace as _dc_replace
    new_aggs = [a if a.arg is None else _dc_replace(a, arg=col(a.arg))
                for a in op.aggs]
    proj = ProjectOp(op.children[0], proj_exprs,
                     [f"_pre{i}" for i in range(len(proj_exprs))])
    rewritten = AggOp(proj, new_groups, new_aggs, mode=op.mode,
                      group_names=op.group_names, agg_names=op.agg_names,
                      initial_capacity=op.initial_capacity,
                      key_domain=op.key_domain)
    if rewritten.schema() != op.schema():
        # defensive: a projection that would change the agg's output
        # contract (shouldn't happen — infer_field is deterministic)
        # must never reach execution
        return op
    return rewritten


# ---------------------------------------------------------------------------
# SPMD mesh annotation pass
# ---------------------------------------------------------------------------

def annotate_mesh(op: PhysicalOp, plane) -> PhysicalOp:
    """Stamp each node's resolved SPMD spec (``op.mesh_spec``) when the
    mesh plane is active:

    - eligible hash exchanges become ``"gang"`` — each round of their
      materialization occupies the whole mesh
      (parallel/exchange._materialize_mesh);
    - nodes declaring a buffer kind (``mesh_buffer_kind``) resolve
      through the replicate-vs-shard table (parallel/mesh.buffer_spec):
      broadcast relations and hash-join build sides ``"replicate"``
      (every shard reads them whole), scan batches / shuffle entries /
      partial-agg rows ``"shard"`` on the batch dim;
    - everything else shards (the default — throughput scales with
      devices; replication is the exception).

    The annotation is the static half of the routing contract — the
    runtime decision (exchange_route at materialize time) re-derives it
    from the same pure function, so the plan a user inspects and the
    route the engine takes can never disagree."""
    if plane is None:
        return op
    _annotate_mesh(op, plane)
    return op


def _annotate_mesh(op: PhysicalOp, plane) -> None:
    from auron_tpu.ops.joins import HashJoinOp
    from auron_tpu.parallel import mesh as mesh_mod
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    if isinstance(op, ShuffleExchangeOp):
        route, _reason = mesh_mod.exchange_route(
            op.partitioning, op.num_partitions, op.input_partitions,
            plane)
        op.mesh_spec = "gang" if route == "all_to_all" else "shard"
    else:
        op.mesh_spec = mesh_mod.buffer_spec(op.mesh_buffer_kind)
    for c in op.children:
        _annotate_mesh(c, plane)
    if isinstance(op, HashJoinOp) and op.build.mesh_spec != "gang":
        # the build side replicates: every probe shard reads the full
        # build relation (the join declares the kind — mesh_build_kind
        # — so the decision stays in the replicate-vs-shard table). A
        # gang-annotated build exchange keeps its stamp: the exchange
        # itself is mesh-routed; it is the COLLECTED hash table that
        # replicates.
        op.build.mesh_spec = mesh_mod.buffer_spec(op.mesh_build_kind)


def _collect_subqueries(msg) -> list:
    """All ScalarSubqueryE messages reachable from ``msg`` (any proto
    node), outermost occurrences only — a subquery's own plan is scanned
    again when IT is planned."""
    found = []
    for fd, val in msg.ListFields():
        if fd.type != fd.TYPE_MESSAGE:
            continue
        vals = val if fd.is_repeated else [val]
        for v in vals:
            if isinstance(v, pb.ExprNode) \
                    and v.WhichOneof("expr") == "scalar_subquery":
                found.append(v.scalar_subquery)
            elif isinstance(v, pb.ScalarSubqueryE):
                continue   # do not descend into the subquery's own plan
            else:
                found.extend(_collect_subqueries(v))
    return found


def subquery_key(q) -> bytes:
    """Dedup key for a ScalarSubqueryE: the plan + result type WITHOUT the
    sid — two structurally equal subqueries (built separately, so stamped
    with different sids) must share one resolution."""
    k = pb.ScalarSubqueryE()
    k.CopyFrom(q)
    k.sid = 0
    return k.SerializeToString()


def substitute_subqueries(node: pb.PlanNode,
                          values: dict[bytes, "pb.ExprNode"]) -> pb.PlanNode:
    """Copy of ``node`` with every scalar_subquery ExprNode replaced by
    the resolved literal ExprNode from ``values`` (keyed by
    ``subquery_key`` — identical subqueries share one resolution)."""
    out = pb.PlanNode()
    out.CopyFrom(node)

    def walk(msg):
        for fd, val in msg.ListFields():
            if fd.type != fd.TYPE_MESSAGE:
                continue
            vals = val if fd.is_repeated else [val]
            for v in vals:
                if isinstance(v, pb.ExprNode) \
                        and v.WhichOneof("expr") == "scalar_subquery":
                    v.CopyFrom(values[subquery_key(v.scalar_subquery)])
                else:
                    walk(v)

    walk(out)
    return out
