"""Generate operator: explode / posexplode / json_tuple / host UDTF.

Reference: datafusion-ext-plans/src/generate/ (explode.rs, json_tuple.rs,
spark_udtf_wrapper.rs). TPU design: explode over the padded ListColumn
layout is a single device kernel — flatten [cap, max_elems] → [cap*max_elems],
mask slots past each list's length, and compact; pass-through columns ride
along via a row-index gather. json_tuple and UDTFs are host generators (the
reference round-trips those to the JVM the same way, spark_udtf_wrapper.rs),
operating on Arrow batches at the host boundary.
"""

from __future__ import annotations

import json
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import pyarrow as pa

from auron_tpu.columnar.arrow_bridge import to_arrow, to_device
from auron_tpu.columnar.batch import (DeviceBatch, ListColumn,
                                      PrimitiveColumn, compact)
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs import udf as udf_registry
from auron_tpu.exprs.eval import EvalContext, evaluate, infer_dtype
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output, timer
from auron_tpu.utils.shapes import bucket_rows
from auron_tpu.runtime.programs import program_cache


@program_cache("ops.generate.explode", maxsize=128)
def _explode_kernel(generator: ir.Expr, pass_through: tuple, with_pos: bool,
                    outer: bool, in_schema: Schema, capacity: int):
    """One launch: rows × list elements → flattened live rows."""

    @jax.jit
    def auron_ops_generate_explode(batch: DeviceBatch):
        ectx = EvalContext()
        from auron_tpu.columnar.batch import StringColumn, StringListColumn
        v = evaluate(generator, batch, in_schema, ectx)
        col = v.col
        assert isinstance(col, (ListColumn, StringListColumn)), \
            "explode needs a list column"
        cap, m = col.capacity, col.max_elems
        flat_n = cap * m
        live = batch.row_mask()

        elem_idx = jnp.tile(jnp.arange(m, dtype=jnp.int32), cap)
        row_idx = jnp.repeat(jnp.arange(cap, dtype=jnp.int32), m)
        in_list = elem_idx < col.lens[row_idx]
        keep = in_list & live[row_idx]
        if isinstance(col, StringListColumn):
            values = None   # string payloads flatten to (chars, lens)
            flat_chars = col.chars.reshape(flat_n, col.width)
            flat_slens = col.slens.reshape(flat_n)
        else:
            values = col.values.reshape(flat_n)
        elem_valid = col.elem_valid.reshape(flat_n)

        outer_slot = jnp.zeros(flat_n, bool)
        if outer:
            # rows with empty/null lists still emit one row (null element,
            # null pos — Spark posexplode_outer)
            empty = (col.lens == 0) | ~col.validity
            outer_slot = (elem_idx == 0) & empty[row_idx] & live[row_idx]
            keep = keep | outer_slot
            elem_valid = elem_valid & ~outer_slot

        from auron_tpu.columnar.batch import gather_column
        cols = [gather_column(batch.columns[i], row_idx, keep)
                for i in pass_through]
        if with_pos:
            cols.append(PrimitiveColumn(
                elem_idx.astype(jnp.int64), keep & ~outer_slot))
        if values is None:
            cols.append(StringColumn(flat_chars, flat_slens,
                                     elem_valid & keep))
        else:
            cols.append(PrimitiveColumn(values, elem_valid & keep))

        flat = DeviceBatch(tuple(cols), jnp.asarray(flat_n, jnp.int32))
        return compact(flat, keep)

    return auron_ops_generate_explode


class GenerateOp(PhysicalOp):
    name = "generate"

    def __init__(self, child: PhysicalOp, kind: str,
                 generator: Optional[ir.Expr] = None,
                 json_fields: Optional[list[str]] = None,
                 udtf_name: Optional[str] = None,
                 required_child_output: Optional[list[int]] = None,
                 outer: bool = False,
                 output_names: Optional[list[str]] = None):
        assert kind in ("explode", "posexplode", "json_tuple", "udtf")
        self.child = child
        self.kind = kind
        self.generator = generator
        self.json_fields = list(json_fields or [])
        self.udtf_name = udtf_name
        in_schema = child.schema()
        self.required_child_output = list(
            required_child_output
            if required_child_output is not None
            else range(len(in_schema)))
        self.outer = outer

        pass_fields = [in_schema[i] for i in self.required_child_output]
        gen_fields: list[Field] = []
        if kind in ("explode", "posexplode"):
            if kind == "posexplode":
                gen_fields.append(Field("pos", DataType.INT64, False))
            dt, _, _ = infer_dtype(generator, in_schema)
            assert dt == DataType.LIST, "explode generator must be a list"
            from auron_tpu.exprs.fn_arrays import elem_dtype_of
            elem = elem_dtype_of(generator, in_schema)
            gen_fields.append(Field("col", elem or DataType.INT64, True))
        elif kind == "json_tuple":
            gen_fields = [Field(n, DataType.STRING, True)
                          for n in self.json_fields]
        else:  # udtf
            self._udtf = udf_registry.lookup_udtf(udtf_name)
            gen_fields = [Field(n, dt, True)
                          for n, dt in self._udtf.output_fields]
        names = output_names
        if names:
            gen_fields = [f.with_name(n) for f, n in zip(gen_fields, names)]
        self._schema = Schema(tuple(pass_fields) + tuple(gen_fields))

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self._schema

    # -- host paths ---------------------------------------------------------

    def _json_tuple_host(self, rb: pa.RecordBatch,
                         in_schema: Schema) -> pa.RecordBatch:
        # row count is preserved (bad JSON yields nulls), so pass-through
        # columns are reused as-is
        texts = rb.column(self.generator.index).to_pylist()
        outs: list[list] = [[] for _ in self.json_fields]
        for t in texts:
            vals = [None] * len(self.json_fields)
            if t is not None:
                try:
                    obj = json.loads(t)
                    for j, f in enumerate(self.json_fields):
                        v = obj.get(f) if isinstance(obj, dict) else None
                        if v is not None and not isinstance(v, str):
                            v = json.dumps(v)
                        vals[j] = v
                except (ValueError, TypeError):
                    pass
            for j, v in enumerate(vals):
                outs[j].append(v)
        arrays = [rb.column(i) for i in self.required_child_output]
        arrays += [pa.array(o, pa.string()) for o in outs]
        from auron_tpu.columnar.arrow_bridge import schema_to_arrow
        return pa.RecordBatch.from_arrays(
            arrays, schema=schema_to_arrow(self._schema))

    def _udtf_host(self, rb: pa.RecordBatch) -> pa.RecordBatch:
        rows = rb.to_pylist()
        out_rows = []
        for row in rows:
            vals = tuple(row.values())
            produced = list(self._udtf(vals))
            if not produced and self.outer:
                produced = [(None,) * (len(self._schema)
                                       - len(self.required_child_output))]
            for gen in produced:
                passed = tuple(vals[i] for i in self.required_child_output)
                out_rows.append(passed + tuple(gen))
        from auron_tpu.columnar.arrow_bridge import schema_to_arrow
        sch = schema_to_arrow(self._schema)
        cols = list(zip(*out_rows)) if out_rows else [[] for _ in sch]
        return pa.RecordBatch.from_arrays(
            [pa.array(list(c), type=f.type) for c, f in zip(cols, sch)],
            schema=sch)

    # -- execute ------------------------------------------------------------

    def execute(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        metrics = ctx.metrics_for(self)
        elapsed = metrics.counter("elapsed_compute")
        in_schema = self.child.schema()

        def stream():
            for batch in self.child.execute(partition, ctx):
                if self.kind in ("explode", "posexplode"):
                    kern = _explode_kernel(
                        self.generator, tuple(self.required_child_output),
                        self.kind == "posexplode", self.outer,
                        in_schema, batch.capacity)
                    with timer(elapsed) as t:
                        out = t.track(kern(batch))
                    yield out
                else:
                    rb = to_arrow(batch, in_schema)
                    out = (self._json_tuple_host(rb, in_schema)
                           if self.kind == "json_tuple"
                           else self._udtf_host(rb))
                    if out.num_rows:
                        dev, _ = to_device(
                            out, capacity=bucket_rows(out.num_rows))
                        yield dev

        return count_output(stream(), metrics)

    def __repr__(self):
        return f"GenerateOp[{self.kind}, outer={self.outer}]"
