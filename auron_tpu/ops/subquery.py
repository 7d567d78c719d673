"""Uncorrelated scalar subquery resolution (reference:
datafusion-ext-exprs/src/spark_scalar_subquery_wrapper.rs — there the
host engine evaluates the subquery and the wrapper fetches the value
through JNI; here the engine executes the embedded child plan itself).

``ScalarSubqueryBinderOp`` wraps any plan subtree containing
scalar_subquery expressions: at first execute it runs each subquery plan
to a single value (0 rows → NULL, >1 rows → error, matching Spark's
"more than one row returned by a subquery used as an expression"), then
re-plans the subtree with the values substituted as literals so every
downstream kernel sees plain constants. Resolution happens once per
TASK, not per partition — the resolved inner op is cached."""

from __future__ import annotations

import threading
from typing import Iterator

from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.columnar.schema import Schema
from auron_tpu.exprs import ir
from auron_tpu.ir import pb
from auron_tpu.ops.base import ExecContext, PhysicalOp


class ScalarSubqueryBinderOp(PhysicalOp):
    name = "scalar_subquery_binder"

    def __init__(self, node: pb.PlanNode, planner_ctx):
        self._node = node
        self._planner_ctx = planner_ctx
        self._lock = threading.Lock()
        self._inner: PhysicalOp | None = None
        self._schema_op: PhysicalOp | None = None

    # -- schema before resolution: substitute typed NULLs ------------------

    def _placeholder_plan(self) -> PhysicalOp:
        from auron_tpu.ir.planner import (PhysicalPlanner,
                                          _collect_subqueries,
                                          substitute_subqueries)
        from auron_tpu.ir.serde import expr_to_proto
        from auron_tpu.ir.planner import subquery_key
        subs = _collect_subqueries(self._node)
        values = {}
        for q in subs:
            from auron_tpu.ir.serde import _P_TO_DT
            lit = ir.Literal(None, _P_TO_DT[q.dtype], q.precision, q.scale)
            values[subquery_key(q)] = expr_to_proto(lit)
        node = substitute_subqueries(self._node, values)
        return PhysicalPlanner(self._planner_ctx).create_plan(node)

    def schema(self) -> Schema:
        if self._inner is not None:
            return self._inner.schema()
        if self._schema_op is None:
            self._schema_op = self._placeholder_plan()
        return self._schema_op.schema()

    @property
    def children(self):
        inner = self._inner or self._schema_op
        return [inner] if inner is not None else []

    # -- resolution --------------------------------------------------------

    def _resolve_one(self, q: "pb.ScalarSubqueryE", ctx: ExecContext):
        """Run one subquery plan to completion, single partition."""
        from auron_tpu.ir.planner import PhysicalPlanner
        # plan_task, not create_plan: the subquery's own plan may contain
        # further scalar subqueries (nested binder resolves them)
        op = PhysicalPlanner(self._planner_ctx).plan_task(
            pb.TaskDefinition(plan=q.plan))
        # ctx.child keeps the cancellation registry: cancelling the task
        # also stops an in-flight subquery resolution
        sub_ctx = ctx.child(partition_id=0, num_partitions=1, metrics={})
        rows = 0
        value = None
        from auron_tpu.columnar.arrow_bridge import to_arrow
        from auron_tpu.obs import profile as _profile
        for batch in op.execute(0, sub_ctx):
            sub_ctx.checkpoint("subquery.collect")
            n = int(_profile.timed_get(batch.num_rows))
            if n == 0:
                continue
            rb = to_arrow(batch, op.schema())
            rows += rb.num_rows
            if rows > 1:
                from auron_tpu import errors
                raise errors.ScalarSubqueryError(
                    "more than one row returned by a subquery used as "
                    "an expression")
            value = rb.column(0)[0].as_py()
        return self._normalize(value, q)

    @staticmethod
    def _normalize(value, q: "pb.ScalarSubqueryE"):
        """Arrow python scalar → the engine's Literal value convention
        (decimals are UNSCALED ints; dates are epoch days; timestamps
        epoch micros)."""
        if value is None:
            return None
        import datetime
        import decimal

        from auron_tpu.columnar.schema import DataType
        from auron_tpu.ir.serde import _P_TO_DT
        dt = _P_TO_DT[q.dtype]
        if dt == DataType.DECIMAL and isinstance(value, decimal.Decimal):
            # graft: disable=GL001 -- a decimal.Decimal, host data
            return int(value.scaleb(q.scale).to_integral_value())
        if dt == DataType.DATE32 and isinstance(value, datetime.date):
            return (value - datetime.date(1970, 1, 1)).days
        if dt == DataType.TIMESTAMP_US \
                and isinstance(value, datetime.datetime):
            if value.tzinfo is None:
                value = value.replace(tzinfo=datetime.timezone.utc)
            # integer arithmetic: float .timestamp() has ~0.24 us ulp at
            # the current epoch and can be off by one microsecond
            epoch = datetime.datetime(1970, 1, 1,
                                      tzinfo=datetime.timezone.utc)
            return (value - epoch) // datetime.timedelta(microseconds=1)
        return value

    def _resolved_inner(self, ctx: ExecContext) -> PhysicalOp:
        with self._lock:
            if self._inner is not None:
                return self._inner
            from auron_tpu.ir.planner import (PhysicalPlanner,
                                              _collect_subqueries,
                                              substitute_subqueries,
                                              subquery_key)
            from auron_tpu.ir.serde import _P_TO_DT, expr_to_proto
            values = {}
            for q in _collect_subqueries(self._node):
                key = subquery_key(q)
                if key in values:
                    continue
                v = self._resolve_one(q, ctx)
                lit = ir.Literal(v, _P_TO_DT[q.dtype], q.precision,
                                 q.scale)
                values[key] = expr_to_proto(lit)
            node = substitute_subqueries(self._node, values)
            # plan_tree: the substituted plan gets the same subplan reuse
            # and stage-fusion passes a subquery-free task would
            self._inner = PhysicalPlanner(self._planner_ctx).plan_tree(node)
            return self._inner

    def execute(self, partition: int,
                ctx: ExecContext) -> Iterator[DeviceBatch]:
        yield from self._resolved_inner(ctx).execute(partition, ctx)

    def __repr__(self):
        return "ScalarSubqueryBinderOp"
