"""Limit / union / coalesce-batches / empty / rename operators.

reference: datafusion-ext-plans/src/limit_exec.rs, union_exec.rs,
coalesce_batches_exec.rs, empty_partitions_exec.rs, rename_columns_exec.rs.
"""

from __future__ import annotations

from typing import Iterator

import jax.numpy as jnp

from auron_tpu.columnar.batch import DeviceBatch, concat_batches, resize
from auron_tpu.columnar.schema import Schema
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output
from auron_tpu.utils.shapes import bucket_rows


class LimitOp(PhysicalOp):
    name = "limit"
    fusable = True
    owns_output = "inherit"   # yields the child's batches (truncated)

    def __init__(self, child: PhysicalOp, limit: int):
        self.child = child
        self.limit = limit

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def build_kernel_fragment(self):
        """Limit-within-batch as a carry: the remaining-row budget lives
        in the member's int64 carry slot, truncation is a num_rows
        rewrite (no data movement), and the host polls the slot to stop
        pulling the child — see FusedStageOp.execute."""
        from auron_tpu.ops.fused import KernelFragment

        def apply(batch, partition_id, carry):
            n = jnp.asarray(batch.num_rows, jnp.int64)
            take = jnp.minimum(n, jnp.maximum(carry, 0))
            out = DeviceBatch(batch.columns, take.astype(jnp.int32))
            return (out,), carry - take

        return KernelFragment(key=("limit", self.limit), apply=apply,
                              init_carry=self.limit, is_limit=True)

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)

        def stream():
            remaining = self.limit
            for batch in self.child.execute(partition, ctx):
                if remaining <= 0:
                    break
                n = _profile.row_count(batch)
                if n <= remaining:
                    remaining -= n
                    yield batch
                else:
                    yield DeviceBatch(batch.columns,
                                      jnp.asarray(remaining, jnp.int32))
                    remaining = 0
                    break

        return count_output(stream(), metrics, timed=True)

    def __repr__(self):
        return f"LimitOp[{self.limit}]"


class UnionOp(PhysicalOp):
    """UNION ALL: chains children streams (reference maps each input to a
    distinct partition set; single-stream chain is equivalent per-partition)."""

    name = "union"
    owns_output = "inherit"

    def __init__(self, inputs: list[PhysicalOp]):
        self.inputs = inputs

    @property
    def children(self):
        return list(self.inputs)

    def schema(self) -> Schema:
        return self.inputs[0].schema()

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)

        def stream():
            for child in self.inputs:
                yield from child.execute(partition, ctx)

        return count_output(stream(), metrics, timed=True)


class CoalesceBatchesOp(PhysicalOp):
    """Merge small batches up to a target row count so downstream kernels run
    at full occupancy (reference: coalesce_batches_exec.rs; the reference's
    ExecutionContext also coalesces on output, execution_context.rs:146-233)."""

    name = "coalesce_batches"
    owns_output = "inherit"   # big batches pass through unchanged

    def __init__(self, child: PhysicalOp, target_rows: int):
        self.child = child
        self.target_rows = target_rows

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        target_cap = bucket_rows(self.target_rows)

        def stream():
            acc = None
            acc_rows = 0
            for batch in self.child.execute(partition, ctx):
                n = _profile.row_count(batch)
                if n == 0:
                    continue
                if n >= self.target_rows and acc is None:
                    yield batch
                    continue
                if acc is None:
                    acc = resize(batch, target_cap)
                    acc_rows = n
                else:
                    grown = concat_batches(acc, batch)
                    acc = resize(grown, max(target_cap, grown.capacity)) \
                        if grown.capacity > target_cap else grown
                    acc_rows += n
                if acc_rows >= self.target_rows:
                    yield acc
                    acc = None
                    acc_rows = 0
            if acc is not None and acc_rows > 0:
                yield acc

        return count_output(stream(), metrics, timed=True)


class EmptyPartitionsOp(PhysicalOp):
    """Produces N empty partitions (reference: empty_partitions_exec.rs)."""

    name = "empty_partitions"

    def __init__(self, schema: Schema, num_partitions: int):
        self._schema = schema
        self.num_partitions = num_partitions

    @property
    def children(self):
        return []

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        return iter(())


class RenameColumnsOp(PhysicalOp):
    """Schema-only rename (reference: rename_columns_exec.rs)."""

    name = "rename_columns"
    fusable = True
    owns_output = "inherit"

    def build_kernel_fragment(self):
        """Identity fragment: fusion chains cross renames for free."""
        from auron_tpu.ops.fused import KernelFragment
        return KernelFragment(key=("rename",),
                              apply=lambda batch, pid, carry:
                              ((batch,), carry))

    def __init__(self, child: PhysicalOp, names: list[str]):
        self.child = child
        self.names = list(names)
        base = child.schema()
        self._schema = Schema(tuple(f.with_name(n) for f, n in zip(base, self.names)))

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        return self.child.execute(partition, ctx)
