"""Expand operator: N projections per input row (GROUPING SETS / ROLLUP /
CUBE lowering — reference: datafusion-ext-plans/src/expand_exec.rs).

TPU design: each projection is the existing project kernel; the outputs are
emitted as one batch per projection rather than row-interleaved — downstream
is always an aggregate, which is order-insensitive, and per-projection
batches keep every kernel dense."""

from __future__ import annotations

from typing import Iterator, Optional

from auron_tpu.columnar.schema import Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import infer_dtype
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.ops.project import _project_kernel


class ExpandOp(PhysicalOp):
    name = "expand"
    fusable = True
    fragment_computes = True

    def __init__(self, child: PhysicalOp, projections: list[list[ir.Expr]],
                 names: Optional[list[str]] = None):
        assert projections and all(
            len(p) == len(projections[0]) for p in projections), \
            "expand projections must agree on arity"
        self.child = child
        self.projections = tuple(tuple(p) for p in projections)
        self.fusion_fanout = len(self.projections)
        in_schema = child.schema()
        n_out = len(self.projections[0])
        self.names = list(names or [f"c{i}" for i in range(n_out)])
        fields = []
        for i in range(n_out):
            # result type: first projection wins (all must be compatible —
            # the host converter guarantees it, like the reference's schema)
            dt, p, s = infer_dtype(self.projections[0][i], in_schema)
            fields.append(Field(self.names[i], dt, True, p, s))
        self._schema = Schema(tuple(fields))

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    def build_kernel_fragment(self):
        import jax.numpy as jnp

        from auron_tpu.columnar.batch import DeviceBatch
        from auron_tpu.exprs.eval import EvalContext, evaluate
        from auron_tpu.ops.fused import KernelFragment
        projections, in_schema = self.projections, self.child.schema()

        def apply(batch, partition_id, carry):
            outs = []
            for proj in projections:
                # every projection of one input batch sees the same row
                # offset, exactly like the unfused per-projection kernels
                ctx = EvalContext(partition_id=partition_id,
                                  row_num_offset=carry, memo={})
                cols = tuple(evaluate(e, batch, in_schema, ctx).col
                             for e in proj)
                outs.append(DeviceBatch(cols, batch.num_rows))
            return tuple(outs), \
                carry + jnp.asarray(batch.num_rows, jnp.int64)

        return KernelFragment(key=("expand", projections, in_schema),
                              apply=apply, fanout=len(projections),
                              row_counts=(("expand_rows_out", 1),))

    def execute(self, partition: int, ctx: ExecContext) -> Iterator:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            import jax.numpy as jnp
            row_off = 0
            for batch in self.child.execute(partition, ctx):
                for proj in self.projections:
                    kern = _project_kernel(proj, in_schema, batch.capacity)
                    with timer(elapsed) as t:
                        out = t.track(kern(batch, jnp.int32(partition),
                                           jnp.int64(row_off)))
                    yield out
                row_off += _profile.row_count(batch)

        return count_output(stream(), metrics,
                            also=(("expand_rows_out", 1),))

    def __repr__(self):
        return f"ExpandOp[{len(self.projections)} projections]"
