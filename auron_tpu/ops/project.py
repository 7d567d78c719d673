"""Projection / filter operators (+ fused filter-project).

reference: datafusion-ext-plans/src/project_exec.rs, filter_exec.rs; the
fusion mirrors CachedExprsEvaluator's project+filter fusion (reference:
datafusion-ext-plans/src/common/cached_exprs_evaluator.rs:50+) — here the
fused path is a single jit kernel, so XLA CSEs shared subexpressions and
fuses everything into one HLO computation.
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import DeviceBatch, compact
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import (EvalContext, decimal_divides, evaluate,
                                  infer_dtype, infer_field)
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.runtime.programs import program_cache


def division_counts(exprs: tuple, in_schema: Schema) -> tuple:
    """``count_output``'s ``also`` of an operator that evaluates
    ``exprs``: the rows that leave it count once a decimal / decimal it
    holds (a projection's input rows; under a filter in the same program
    the survivors, a lower bound)."""
    n = decimal_divides(exprs, in_schema)
    return (("decimal_div_rows", n),) if n else ()


def project_schema(exprs: tuple, names: tuple[str, ...], in_schema: Schema) -> Schema:
    # infer_field keeps nested metadata (list elem / map key+value /
    # struct children) that the (dtype, p, s) 3-tuple cannot carry
    return Schema(tuple(infer_field(e, in_schema, name=n)
                        for e, n in zip(exprs, names)))


@program_cache("ops.project.project", maxsize=512)
def _project_kernel(exprs: tuple, in_schema: Schema, capacity: int):
    """One compiled kernel per (expression tuple, schema, capacity)."""

    @jax.jit
    def auron_ops_project_project(batch: DeviceBatch, partition_id,
                                  row_num_offset):
        ctx = EvalContext(partition_id=partition_id,
                          row_num_offset=row_num_offset, memo={})
        cols = tuple(evaluate(e, batch, in_schema, ctx).col for e in exprs)
        return DeviceBatch(cols, batch.num_rows)

    return auron_ops_project_project


@program_cache("ops.project.filter", maxsize=512)
def _filter_kernel(predicates: tuple, in_schema: Schema, capacity: int):
    @jax.jit
    def auron_ops_project_filter(batch: DeviceBatch, partition_id,
                                 row_num_offset):
        ctx = EvalContext(partition_id=partition_id,
                          row_num_offset=row_num_offset, memo={})
        keep = batch.row_mask()
        for p in predicates:
            v = evaluate(p, batch, in_schema, ctx)
            keep = keep & v.data.astype(bool) & v.validity
        return compact(batch, keep)

    return auron_ops_project_filter


@program_cache("ops.project.filter_project", maxsize=512)
def _filter_project_kernel(predicates: tuple, exprs: tuple, in_schema: Schema,
                           capacity: int):
    @jax.jit
    def auron_ops_project_filter_project(batch: DeviceBatch, partition_id,
                                         row_num_offset):
        ctx = EvalContext(partition_id=partition_id,
                          row_num_offset=row_num_offset, memo={})
        keep = batch.row_mask()
        for p in predicates:
            v = evaluate(p, batch, in_schema, ctx)
            keep = keep & v.data.astype(bool) & v.validity
        filtered = compact(batch, keep)
        cols = tuple(evaluate(e, filtered, in_schema, ctx).col for e in exprs)
        return DeviceBatch(cols, filtered.num_rows)

    return auron_ops_project_filter_project


class ProjectOp(PhysicalOp):
    name = "project"
    fusable = True
    fragment_computes = True

    def __init__(self, child: PhysicalOp, exprs: list[ir.Expr], names: list[str]):
        self.child = child
        self.exprs = tuple(exprs)
        self.names = tuple(names)
        self._schema = project_schema(self.exprs, self.names, child.schema())

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    def build_kernel_fragment(self):
        from auron_tpu.ops.fused import KernelFragment
        exprs, in_schema = self.exprs, self.child.schema()

        def apply(batch, partition_id, carry):
            ctx = EvalContext(partition_id=partition_id,
                              row_num_offset=carry, memo={})
            cols = tuple(evaluate(e, batch, in_schema, ctx).col
                         for e in exprs)
            out = DeviceBatch(cols, batch.num_rows)
            return (out,), carry + jnp.asarray(batch.num_rows, jnp.int64)

        return KernelFragment(
            key=("project", exprs, in_schema), apply=apply,
            row_counts=division_counts(exprs, in_schema))

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            row_off = 0
            for batch in self.child.execute(partition, ctx):
                kern = _project_kernel(self.exprs, in_schema, batch.capacity)
                with timer(elapsed) as t:
                    out = t.track(kern(batch, jnp.int32(partition),
                                       jnp.int64(row_off)))
                row_off += _profile.row_count(batch)
                yield out

        return count_output(stream(), metrics,
                            also=division_counts(self.exprs, in_schema))

    def __repr__(self):
        return f"ProjectOp[{', '.join(self.names)}]"


class FilterOp(PhysicalOp):
    name = "filter"
    fusable = True
    fragment_computes = True
    drops_rows = True

    def __init__(self, child: PhysicalOp, predicates: list[ir.Expr]):
        self.child = child
        self.predicates = tuple(predicates)

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def build_kernel_fragment(self):
        from auron_tpu.ops.fused import KernelFragment
        predicates, in_schema = self.predicates, self.child.schema()

        def apply(batch, partition_id, carry):
            ctx = EvalContext(partition_id=partition_id,
                              row_num_offset=carry, memo={})
            keep = batch.row_mask()
            for p in predicates:
                v = evaluate(p, batch, in_schema, ctx)
                keep = keep & v.data.astype(bool) & v.validity
            out = compact(batch, keep)
            return (out,), carry + jnp.asarray(batch.num_rows, jnp.int64)

        return KernelFragment(
            key=("filter", predicates, in_schema), apply=apply,
            row_counts=division_counts(predicates, in_schema))

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            row_off = 0
            for batch in self.child.execute(partition, ctx):
                kern = _filter_kernel(self.predicates, in_schema, batch.capacity)
                with timer(elapsed) as t:
                    out = t.track(kern(batch, jnp.int32(partition),
                                       jnp.int64(row_off)))
                row_off += _profile.row_count(batch)
                yield out

        return count_output(
            stream(), metrics,
            also=division_counts(self.predicates, in_schema), shrink=True)

    def __repr__(self):
        return f"FilterOp[{len(self.predicates)} predicates]"


class FilterProjectOp(PhysicalOp):
    """Fused filter+project — one kernel launch, full XLA fusion."""

    name = "filter_project"
    fusable = True
    fragment_computes = True
    drops_rows = True

    def __init__(self, child: PhysicalOp, predicates: list[ir.Expr],
                 exprs: list[ir.Expr], names: list[str]):
        self.child = child
        self.predicates = tuple(predicates)
        self.exprs = tuple(exprs)
        self.names = tuple(names)
        self._schema = project_schema(self.exprs, self.names, child.schema())

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    def build_kernel_fragment(self):
        from auron_tpu.ops.fused import KernelFragment
        predicates, exprs = self.predicates, self.exprs
        in_schema = self.child.schema()

        def apply(batch, partition_id, carry):
            # ONE shared EvalContext, like _filter_project_kernel: the
            # memo keys on (batch, expr) so predicate/projection CSE
            # still only shares within the same intermediate batch
            ctx = EvalContext(partition_id=partition_id,
                              row_num_offset=carry, memo={})
            keep = batch.row_mask()
            for p in predicates:
                v = evaluate(p, batch, in_schema, ctx)
                keep = keep & v.data.astype(bool) & v.validity
            filtered = compact(batch, keep)
            cols = tuple(evaluate(e, filtered, in_schema, ctx).col
                         for e in exprs)
            out = DeviceBatch(cols, filtered.num_rows)
            return (out,), carry + jnp.asarray(batch.num_rows, jnp.int64)

        return KernelFragment(
            key=("filter_project", predicates, exprs, in_schema),
            apply=apply,
            row_counts=division_counts(predicates + exprs, in_schema))

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            row_off = 0
            for batch in self.child.execute(partition, ctx):
                kern = _filter_project_kernel(self.predicates, self.exprs,
                                              in_schema, batch.capacity)
                with timer(elapsed) as t:
                    out = t.track(kern(batch, jnp.int32(partition),
                                       jnp.int64(row_off)))
                row_off += _profile.row_count(batch)
                yield out

        return count_output(
            stream(), metrics,
            also=division_counts(self.predicates + self.exprs, in_schema),
            shrink=True)

    def __repr__(self):
        return f"FilterProjectOp[{len(self.predicates)} predicates -> {', '.join(self.names)}]"
