"""Expression evaluation: lowering expr trees onto DeviceBatches.

The analogue of the reference's PhysicalExpr evaluation (reference:
datafusion-ext-exprs/, datafusion-ext-functions/), except nothing is
interpreted at runtime: ``evaluate`` runs inside a traced jax function, so
the whole expression tree flattens into one fused XLA computation per
operator — XLA's fusion pass is the CachedExprsEvaluator (reference:
datafusion-ext-plans/src/common/cached_exprs_evaluator.rs) of this design.

Null semantics follow Spark/SQL: arithmetic/comparison propagate null;
AND/OR are three-valued; casts are non-ANSI (overflow wraps / saturates like
the JVM, invalid parses give null).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import DeviceBatch, PrimitiveColumn, StringColumn
from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.ops import hashing
from auron_tpu.ops import strings as S
from auron_tpu.utils.shapes import bucket_string_width

# ---------------------------------------------------------------------------
# typed values
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TypedValue:
    col: object            # PrimitiveColumn | StringColumn
    dtype: DataType
    precision: int = 0
    scale: int = 0

    @property
    def data(self):
        return self.col.data

    @property
    def validity(self):
        return self.col.validity


@dataclass(frozen=True)
class EvalContext:
    """Ambient scalars available to expressions.

    ``memo``: when a kernel passes a fresh dict, evaluate() caches each
    subexpression's TypedValue per (batch, expr) — the common-subexpression
    evaluator (reference: datafusion-ext-plans/src/common/
    cached_exprs_evaluator.rs). Safe only for a dict created INSIDE the
    traced kernel (tracer lifetimes match the trace); the default None
    disables caching, so the shared default context can never leak
    tracers across traces."""
    partition_id: object = 0          # device or python int32
    row_num_offset: object = 0        # rows produced before this batch
    num_partitions: int = 1
    memo: object = None               # dict | None; see docstring


_JNP = {
    DataType.BOOL: jnp.bool_,
    DataType.INT8: jnp.int8,
    DataType.INT16: jnp.int16,
    DataType.INT32: jnp.int32,
    DataType.INT64: jnp.int64,
    DataType.FLOAT32: jnp.float32,
    DataType.FLOAT64: jnp.float64,
    DataType.DATE32: jnp.int32,
    DataType.TIMESTAMP_US: jnp.int64,
    DataType.DECIMAL: jnp.int64,
    DataType.NULL: jnp.bool_,
}

def null_column_for_field(field, cap: int):
    """All-null device column shaped for ``field`` (outer-join padding)."""
    from auron_tpu.columnar.batch import PrimitiveColumn, StringColumn
    if field.dtype == DataType.STRING:
        return StringColumn(jnp.zeros((cap, 8), jnp.uint8),
                            jnp.zeros(cap, jnp.int32), jnp.zeros(cap, bool))
    if field.dtype == DataType.DECIMAL and field.precision > 18:
        from auron_tpu.columnar.decimal128 import Decimal128Column
        return Decimal128Column(jnp.zeros(cap, jnp.int64),
                                jnp.zeros(cap, jnp.int64),
                                jnp.zeros(cap, bool))
    if field.dtype == DataType.MAP and field.key == DataType.STRING:
        from auron_tpu.columnar.batch import StringMapColumn
        return StringMapColumn(jnp.zeros((cap, 1, 8), jnp.uint8),
                               jnp.zeros((cap, 1), jnp.int32),
                               jnp.zeros((cap, 1, 8), jnp.uint8),
                               jnp.zeros((cap, 1), jnp.int32),
                               jnp.zeros((cap, 1), bool),
                               jnp.zeros(cap, jnp.int32),
                               jnp.zeros(cap, bool))
    if field.dtype == DataType.LIST and field.elem == DataType.STRING:
        from auron_tpu.columnar.batch import StringListColumn
        return StringListColumn(jnp.zeros((cap, 1, 8), jnp.uint8),
                                jnp.zeros((cap, 1), jnp.int32),
                                jnp.zeros((cap, 1), bool),
                                jnp.zeros(cap, jnp.int32),
                                jnp.zeros(cap, bool))
    if field.dtype == DataType.LIST:
        from auron_tpu.columnar.batch import ListColumn
        return ListColumn(jnp.zeros((cap, 1), _JNP[field.elem]),
                          jnp.zeros((cap, 1), bool),
                          jnp.zeros(cap, jnp.int32), jnp.zeros(cap, bool))
    if field.dtype == DataType.MAP:
        from auron_tpu.columnar.batch import MapColumn
        return MapColumn(jnp.zeros((cap, 1), _JNP[field.key]),
                         jnp.zeros((cap, 1), _JNP[field.elem]),
                         jnp.zeros((cap, 1), bool),
                         jnp.zeros(cap, jnp.int32), jnp.zeros(cap, bool))
    if field.dtype == DataType.STRUCT:
        from auron_tpu.columnar.batch import StructColumn
        return StructColumn(
            tuple(null_column_for_field(cf, cap) for cf in field.children),
            jnp.zeros(cap, bool))
    return PrimitiveColumn(jnp.zeros(cap, _JNP[field.dtype]),
                           jnp.zeros(cap, bool))


_RANK = [DataType.INT8, DataType.INT16, DataType.INT32, DataType.INT64,
         DataType.FLOAT32, DataType.FLOAT64]


def decimal_result_type(op: str, lp: int, ls: int, rp: int,
                        rs: int) -> tuple[int, int, int]:
    """Spark decimal binary result type with allowPrecisionLoss scale
    adjustment (Spark's DecimalPrecision.adjustPrecisionScale): returns
    (precision, scale, full_scale) where full_scale is the scale the raw
    limb computation produces before any precision-loss rescale. ONE
    definition shared by infer_dtype and evaluation so declared schemas
    and evaluated columns can't drift."""
    if op == "*":
        p, s = lp + rp + 1, ls + rs
        full_s = ls + rs
    elif op == "/":
        # Spark's Divide: the quotient is computed AT the adjusted scale
        # (one HALF_UP rounding of the exact quotient), so full_s is it
        s = max(6, ls + rp + 1)
        p = lp - ls + rs + s
        if p > 38:
            s = max(38 - (p - s), min(s, 6))
            p = 38
        return p, s, s
    else:   # + - and comparisons share add/sub typing
        s = max(ls, rs)
        p = max(lp - ls, rp - rs) + s + 1
        full_s = s
    if p <= 38:
        return p, s, full_s
    digits_int = p - s
    min_scale = min(s, 6)
    adj_s = max(38 - digits_int, min_scale)
    return 38, adj_s, full_s


def decimal_divides(exprs, schema: Schema) -> int:
    """How many decimal / decimal nodes these expression trees hold: an
    operator's factor of ``counts.decimal_div_rows``."""
    n = 0
    stack = list(exprs)
    while stack:
        e = stack.pop()
        stack.extend(e.children())
        if isinstance(e, ir.BinaryExpr) and e.op == "/" \
                and infer_dtype(e.left, schema)[0] == DataType.DECIMAL \
                and infer_dtype(e.right, schema)[0] == DataType.DECIMAL:
            n += 1
    return n


def common_type(a: DataType, b: DataType) -> DataType:
    if a == b:
        return a
    if a == DataType.NULL:
        return b
    if b == DataType.NULL:
        return a
    if a == DataType.DECIMAL or b == DataType.DECIMAL:
        # decimal vs float → float64; decimal vs int → decimal handled upstream
        if b.is_floating or a.is_floating:
            return DataType.FLOAT64
        return DataType.DECIMAL
    if a in _RANK and b in _RANK:
        return _RANK[max(_RANK.index(a), _RANK.index(b))]
    if {a, b} <= {DataType.DATE32, DataType.STRING}:
        return DataType.DATE32
    if {a, b} <= {DataType.TIMESTAMP_US, DataType.STRING}:
        return DataType.TIMESTAMP_US
    raise TypeError(f"no common type for {a} and {b}")


def _const_column(value, dtype: DataType, capacity: int, width_hint: int = 8):
    """Materialize a literal as a broadcast column."""
    if dtype == DataType.STRING:
        b = value.encode() if isinstance(value, str) else (value or b"")
        w = bucket_string_width(max(len(b), 1))
        row, _ = S.literal_to_device(b, w)
        chars = jnp.broadcast_to(jnp.asarray(row)[None, :], (capacity, w))
        lens = jnp.full(capacity, len(b), jnp.int32)
        validity = jnp.full(capacity, value is not None, bool)
        return StringColumn(chars, lens, validity)
    jdt = _JNP[dtype]
    if value is None:
        return PrimitiveColumn(jnp.zeros(capacity, jdt),
                               jnp.zeros(capacity, bool))
    return PrimitiveColumn(jnp.full(capacity, value, jdt),
                           jnp.ones(capacity, bool))


# ---------------------------------------------------------------------------
# main entry
# ---------------------------------------------------------------------------

def evaluate(expr: ir.Expr, batch: DeviceBatch, schema: Schema,
             ctx: EvalContext = EvalContext()) -> TypedValue:
    """Evaluate ``expr`` against ``batch``; with ctx.memo set, each
    distinct subexpression evaluates once per batch (CSE — expr trees are
    frozen/hashable, so structural duplicates share one result; host
    callbacks like string parsing benefit most since XLA cannot CSE
    those)."""
    memo = ctx.memo
    if memo is None or isinstance(expr, (ir.ColumnRef, ir.Literal)):
        return _evaluate(expr, batch, schema, ctx)
    key = (id(batch), expr)
    hit = memo.get(key)
    if hit is None:
        hit = _evaluate(expr, batch, schema, ctx)
        memo[key] = hit
    return hit


def _evaluate(expr: ir.Expr, batch: DeviceBatch, schema: Schema,
              ctx: EvalContext) -> TypedValue:
    cap = batch.capacity
    if isinstance(expr, ir.ColumnRef):
        f = schema[expr.index]
        return TypedValue(batch.columns[expr.index], f.dtype, f.precision, f.scale)

    if isinstance(expr, ir.ScalarSubquery):
        raise RuntimeError(
            "unresolved scalar subquery reached evaluation — plans with "
            "subqueries must go through plan_from_bytes / the DataFrame "
            "API (ScalarSubqueryBinderOp substitutes the value)")

    if isinstance(expr, ir.Literal):
        if expr.dtype == DataType.DECIMAL and expr.precision > 18:
            from auron_tpu.columnar.decimal128 import (Decimal128Column,
                                                       limbs_from_ints)
            vals = [None if expr.value is None else int(expr.value)] * cap
            hi, lo, valid = limbs_from_ints(vals, cap)
            return TypedValue(
                Decimal128Column(jnp.asarray(hi), jnp.asarray(lo),
                                 jnp.asarray(valid)),
                DataType.DECIMAL, expr.precision, expr.scale)
        return TypedValue(_const_column(expr.value, expr.dtype, cap),
                          expr.dtype, expr.precision, expr.scale)

    if isinstance(expr, ir.BinaryExpr):
        return _eval_binary(expr, batch, schema, ctx)

    if isinstance(expr, ir.Not):
        v = evaluate(expr.child, batch, schema, ctx)
        return TypedValue(PrimitiveColumn(~v.data.astype(bool), v.validity),
                          DataType.BOOL)

    if isinstance(expr, ir.IsNull):
        v = evaluate(expr.child, batch, schema, ctx)
        return TypedValue(PrimitiveColumn(~v.validity & batch.row_mask(),
                                          jnp.ones(cap, bool)), DataType.BOOL)

    if isinstance(expr, ir.IsNotNull):
        v = evaluate(expr.child, batch, schema, ctx)
        return TypedValue(PrimitiveColumn(v.validity & batch.row_mask(),
                                          jnp.ones(cap, bool)), DataType.BOOL)

    if isinstance(expr, ir.Negative):
        v = evaluate(expr.child, batch, schema, ctx)
        from auron_tpu.columnar import decimal128 as D
        if isinstance(v.col, D.Decimal128Column):
            return TypedValue(
                D.Decimal128Column(*D.neg128(v.col.hi, v.col.lo),
                                   v.validity),
                v.dtype, v.precision, v.scale)
        return TypedValue(PrimitiveColumn(-v.data, v.validity),
                          v.dtype, v.precision, v.scale)

    if isinstance(expr, ir.Cast):
        v = evaluate(expr.child, batch, schema, ctx)
        return cast_value(v, expr.dtype, expr.precision, expr.scale,
                          safe=expr.safe)

    if isinstance(expr, ir.CaseWhen):
        return _eval_case(expr, batch, schema, ctx)

    if isinstance(expr, ir.InList):
        return _eval_in_list(expr, batch, schema, ctx)

    if isinstance(expr, (ir.Like, ir.StringStartsWith, ir.StringEndsWith,
                         ir.StringContains)):
        return _eval_like(expr, batch, schema, ctx)

    if isinstance(expr, ir.ScalarFunction):
        from auron_tpu.exprs.functions import dispatch_function
        return dispatch_function(expr, batch, schema, ctx)

    if isinstance(expr, ir.RowNum):
        rn = jnp.arange(cap, dtype=jnp.int64) + jnp.asarray(ctx.row_num_offset, jnp.int64)
        return TypedValue(PrimitiveColumn(rn, jnp.ones(cap, bool)), DataType.INT64)

    if isinstance(expr, ir.SparkPartitionId):
        pid = jnp.full(cap, 0, jnp.int32) + jnp.asarray(ctx.partition_id, jnp.int32)
        return TypedValue(PrimitiveColumn(pid, jnp.ones(cap, bool)), DataType.INT32)

    if isinstance(expr, ir.MonotonicallyIncreasingId):
        # Spark: partition_id << 33 | row index
        base = jnp.asarray(ctx.partition_id, jnp.int64) << 33
        mid = base + jnp.arange(cap, dtype=jnp.int64) + jnp.asarray(
            ctx.row_num_offset, jnp.int64)
        return TypedValue(PrimitiveColumn(mid, jnp.ones(cap, bool)), DataType.INT64)

    if isinstance(expr, ir.BloomFilterMightContain):
        from auron_tpu.exprs.bloom import might_contain_device
        v = evaluate(expr.value, batch, schema, ctx)
        vals = v.data.astype(jnp.int64)
        hit = might_contain_device(expr.serialized, vals)
        return TypedValue(PrimitiveColumn(hit, v.validity), DataType.BOOL)

    if isinstance(expr, ir.GetIndexedField):
        from auron_tpu.columnar.batch import ListColumn
        if isinstance(expr.child, ir.ScalarFunction) \
                and expr.child.name == "split":
            # split(...)[i] fused — string lists are never materialized
            from auron_tpu.exprs.fn_strings import split_index
            return split_index(expr.child.args, expr.ordinal, batch,
                               schema, ctx)
        v = evaluate(expr.child, batch, schema, ctx)
        from auron_tpu.columnar.batch import StringListColumn
        assert isinstance(v.col, (ListColumn, StringListColumn)), \
            "GetIndexedField needs a list"
        i = expr.ordinal
        in_range = (i >= 0) & (i < v.col.lens)
        idx = min(max(i, 0), v.col.max_elems - 1)
        if isinstance(v.col, StringListColumn):
            valid = v.col.validity & in_range & v.col.elem_valid[:, idx]
            return TypedValue(
                StringColumn(v.col.chars[:, idx],
                             jnp.where(valid, v.col.slens[:, idx], 0),
                             valid), DataType.STRING)
        elem_dt, _, _ = infer_dtype(expr, schema)
        return TypedValue(
            PrimitiveColumn(v.col.values[:, idx],
                            v.col.validity & in_range
                            & v.col.elem_valid[:, idx]),
            elem_dt)

    if isinstance(expr, ir.GetStructField):
        from auron_tpu.columnar.batch import StructColumn
        v = evaluate(expr.child, batch, schema, ctx)
        assert isinstance(v.col, StructColumn), "GetStructField needs struct"
        child = v.col.children[expr.ordinal]
        cf = infer_field(expr.child, schema).children[expr.ordinal]
        return TypedValue(
            child.with_validity(child.validity & v.validity),
            cf.dtype, cf.precision, cf.scale)

    if isinstance(expr, ir.HostUDF):
        return _eval_host_udf(expr, batch, schema, ctx)

    raise NotImplementedError(f"expression {type(expr).__name__}")


def infer_dtype(expr: ir.Expr, schema: Schema) -> tuple[DataType, int, int]:
    """Static result type of an expression (dtype, precision, scale)."""
    if isinstance(expr, ir.ColumnRef):
        f = schema[expr.index]
        return f.dtype, f.precision, f.scale
    if isinstance(expr, ir.Literal):
        return expr.dtype, expr.precision, expr.scale
    if isinstance(expr, ir.BinaryExpr):
        if expr.op in ("==", "!=", "<", "<=", ">", ">=", "and", "or"):
            return DataType.BOOL, 0, 0
        lt, lp, ls = infer_dtype(expr.left, schema)
        rt, rp, rs = infer_dtype(expr.right, schema)
        if lt == DataType.DECIMAL and rt == DataType.DECIMAL:
            # Spark decimal result types (precision 19..38 runs on the
            # two-limb kernels, columnar/decimal128.py)
            p, s, _fs = decimal_result_type(expr.op, lp, ls, rp, rs)
            return DataType.DECIMAL, p, s
        out = common_type(lt, rt)
        if expr.op == "/" and out in _RANK and not out.is_floating:
            # integer '/' keeps integer semantics here; Spark's true divide
            # is expressed by the host converter as cast-to-double first.
            return out, 0, 0
        return out, 0, 0
    if isinstance(expr, (ir.Not, ir.IsNull, ir.IsNotNull, ir.Like,
                         ir.StringStartsWith, ir.StringEndsWith,
                         ir.StringContains, ir.InList,
                         ir.BloomFilterMightContain)):
        return DataType.BOOL, 0, 0
    if isinstance(expr, ir.Negative):
        return infer_dtype(expr.child, schema)
    if isinstance(expr, ir.Cast):
        return expr.dtype, expr.precision, expr.scale
    if isinstance(expr, ir.ScalarSubquery):
        return expr.dtype, expr.precision, expr.scale
    if isinstance(expr, ir.CaseWhen):
        if expr.when_then:
            return infer_dtype(expr.when_then[0][1], schema)
        return infer_dtype(expr.otherwise, schema)
    if isinstance(expr, ir.ScalarFunction):
        from auron_tpu.exprs.functions import function_result_type
        return function_result_type(expr, schema)
    if isinstance(expr, ir.RowNum) or isinstance(expr, ir.MonotonicallyIncreasingId):
        return DataType.INT64, 0, 0
    if isinstance(expr, ir.SparkPartitionId):
        return DataType.INT32, 0, 0
    if isinstance(expr, ir.HostUDF):
        return expr.dtype, 0, 0
    if isinstance(expr, ir.GetIndexedField):
        if isinstance(expr.child, ir.ScalarFunction) \
                and expr.child.name == "split":
            return DataType.STRING, 0, 0
        child_dt = infer_dtype(expr.child, schema)
        if child_dt[0] == DataType.LIST:
            # element type rides in the field's elem slot / array expr
            if isinstance(expr.child, ir.ColumnRef):
                return schema[expr.child.index].elem, 0, 0
            from auron_tpu.exprs.fn_arrays import elem_dtype_of
            return elem_dtype_of(expr.child, schema), 0, 0
        raise NotImplementedError("GetIndexedField on non-column list")
    if isinstance(expr, ir.GetStructField):
        cf = infer_field(expr.child, schema).children[expr.ordinal]
        return cf.dtype, cf.precision, cf.scale
    raise NotImplementedError(f"infer_dtype for {type(expr).__name__}")


def infer_field(expr: ir.Expr, schema: Schema, name: str = "c") -> Field:
    """Nested-aware result field of an expression — like infer_dtype but
    keeping list/map element types and struct children (the 3-tuple
    (dtype, p, s) cannot describe nested results)."""
    if isinstance(expr, ir.ColumnRef):
        return schema[expr.index].with_name(name)
    if isinstance(expr, ir.ScalarFunction):
        from auron_tpu.exprs.functions import function_result_field
        f = function_result_field(expr, schema)
        if f is not None:
            return f.with_name(name)
    if isinstance(expr, ir.GetStructField):
        return infer_field(expr.child, schema).children[expr.ordinal] \
            .with_name(name)
    if isinstance(expr, ir.CaseWhen) and expr.otherwise is not None:
        f = infer_field(expr.otherwise, schema)
        if f.dtype in (DataType.MAP, DataType.STRUCT, DataType.LIST):
            return f.with_name(name)
    dt, p, s = infer_dtype(expr, schema)
    if dt in (DataType.MAP, DataType.STRUCT):
        # no nested-aware arm matched above: a Field without key/children
        # metadata would crash schema_to_arrow/serde far downstream — fail
        # at plan time instead (e.g. CaseWhen over maps with no otherwise)
        raise NotImplementedError(
            f"cannot infer nested ({dt.value}) result metadata for "
            f"{type(expr).__name__}; add an explicit typed branch "
            "(e.g. an 'otherwise' arm) or project the nested column "
            "directly")
    elem = None
    if dt == DataType.LIST:
        if isinstance(expr, ir.ScalarFunction):
            from auron_tpu.exprs.fn_arrays import elem_dtype_of
            elem = elem_dtype_of(expr, schema)
    return Field(name, dt, True, p, s, elem=elem)


# ---------------------------------------------------------------------------
# binary ops
# ---------------------------------------------------------------------------

def _numeric_promote(v: TypedValue, target: DataType, cap: int) -> TypedValue:
    if v.dtype == target:
        return v
    return cast_value(v, target)


def _eval_binary(expr: ir.BinaryExpr, batch, schema, ctx) -> TypedValue:
    op = expr.op
    l = evaluate(expr.left, batch, schema, ctx)
    r = evaluate(expr.right, batch, schema, ctx)
    cap = batch.capacity

    if op in ("and", "or"):
        ld, rd = l.data.astype(bool), r.data.astype(bool)
        lv, rv = l.validity, r.validity
        if op == "and":
            data = (ld & lv) & (rd & rv)
            # null unless any FALSE or both valid
            validity = (lv & rv) | (lv & ~ld) | (rv & ~rd)
        else:
            data = (ld & lv) | (rd & rv)
            validity = (lv & rv) | (lv & ld) | (rv & rd)
        return TypedValue(PrimitiveColumn(data, validity), DataType.BOOL)

    # string comparisons
    if isinstance(l.col, StringColumn) or isinstance(r.col, StringColumn):
        if not (isinstance(l.col, StringColumn) and isinstance(r.col, StringColumn)):
            raise TypeError(f"cannot {op} string with non-string")
        lt, eq = S.compare(l.col.chars, l.col.lens, r.col.chars, r.col.lens)
        validity = l.validity & r.validity
        table = {"==": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
                 ">": ~(lt | eq), ">=": ~lt}
        if op not in table:
            raise TypeError(f"unsupported string op {op}")
        return TypedValue(PrimitiveColumn(table[op], validity), DataType.BOOL)

    # decimal alignment
    if l.dtype == DataType.DECIMAL or r.dtype == DataType.DECIMAL:
        return _eval_decimal_binary(op, l, r, cap)

    target = common_type(l.dtype, r.dtype)
    l = _numeric_promote(l, target, cap)
    r = _numeric_promote(r, target, cap)
    ld, rd = l.data, r.data
    validity = l.validity & r.validity

    if op in ("==", "!=", "<", "<=", ">", ">="):
        fn = {"==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
              "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal}[op]
        return TypedValue(PrimitiveColumn(fn(ld, rd), validity), DataType.BOOL)

    if op == "+":
        data = ld + rd
    elif op == "-":
        data = ld - rd
    elif op == "*":
        data = ld * rd
    elif op == "/":
        if target.is_floating:
            # Spark double semantics: x/0 → null (non-ANSI divide)
            safe = jnp.where(rd == 0, 1.0, rd)
            data = ld / safe
            validity = validity & (rd != 0)
        else:
            # Java-style truncating division; x/0 → null
            safe = jnp.where(rd == 0, 1, rd)
            q = jnp.sign(ld) * jnp.sign(safe) * (jnp.abs(ld) // jnp.abs(safe))
            data = q.astype(ld.dtype)
            validity = validity & (rd != 0)
    elif op == "%":
        if target.is_floating:
            safe = jnp.where(rd == 0, 1, rd)
            data = jnp.where(rd == 0, jnp.nan, ld - jnp.trunc(ld / safe) * safe)
        else:
            safe = jnp.where(rd == 0, 1, rd)
            data = (ld - (jnp.sign(ld) * jnp.sign(safe)
                          * (jnp.abs(ld) // jnp.abs(safe))).astype(ld.dtype) * safe)
            validity = validity & (rd != 0)
    else:
        raise NotImplementedError(f"binary op {op}")
    return TypedValue(PrimitiveColumn(data, validity), target)


def _eval_decimal_binary(op, l: TypedValue, r: TypedValue, cap: int) -> TypedValue:
    """Decimal arithmetic on unscaled int64 (reference decimal semantics live
    in spark-extension NativeConverters decimal arith + check_overflow;
    precision capped at 18 here)."""
    # promote ints to decimal scale 0
    if l.dtype != DataType.DECIMAL:
        l = TypedValue(PrimitiveColumn(l.data.astype(jnp.int64), l.validity),
                       DataType.DECIMAL, 18, 0) if not l.dtype.is_floating else l
    if r.dtype != DataType.DECIMAL:
        r = TypedValue(PrimitiveColumn(r.data.astype(jnp.int64), r.validity),
                       DataType.DECIMAL, 18, 0) if not r.dtype.is_floating else r
    if op == "/" and l.dtype == r.dtype == DataType.DECIMAL:
        return _eval_decimal_divide(l, r)
    if l.dtype.is_floating or r.dtype.is_floating or op == "/":
        lf = _decimal_to_f64(l)
        rf = _decimal_to_f64(r)
        return _eval_binary_simple(op, lf, rf)
    from auron_tpu.columnar.decimal128 import Decimal128Column
    s = max(l.scale, r.scale)
    # route to the two-limb path when either side is wide or the Spark
    # result type exceeds 18 digits (the int64 payload would wrap)
    rp, rs, full_s = decimal_result_type(op, l.precision, l.scale,
                                         r.precision, r.scale)
    wide = (isinstance(l.col, Decimal128Column)
            or isinstance(r.col, Decimal128Column) or rp > 18
            or full_s > rs)
    if wide:
        return _eval_decimal128_binary(op, l, r, rp, rs, full_s)
    ld = l.data * (10 ** (s - l.scale))
    rd = r.data * (10 ** (s - r.scale))
    validity = l.validity & r.validity
    if op in ("==", "!=", "<", "<=", ">", ">="):
        fn = {"==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
              "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal}[op]
        return TypedValue(PrimitiveColumn(fn(ld, rd), validity), DataType.BOOL)
    if op == "+":
        return TypedValue(PrimitiveColumn(ld + rd, validity), DataType.DECIMAL,
                          rp, s)
    if op == "-":
        return TypedValue(PrimitiveColumn(ld - rd, validity), DataType.DECIMAL,
                          rp, s)
    if op == "*":
        return TypedValue(PrimitiveColumn(l.data * r.data, validity),
                          DataType.DECIMAL, rp, l.scale + r.scale)
    raise NotImplementedError(f"decimal op {op}")


def _limbs_of(v: TypedValue):
    """(hi, lo) limbs of a decimal TypedValue of either representation."""
    from auron_tpu.columnar import decimal128 as D
    if isinstance(v.col, D.Decimal128Column):
        return v.col.hi, v.col.lo
    return D.from_int64(v.data.astype(jnp.int64))


def _mk_decimal(hi, lo, validity, precision: int, scale: int) -> TypedValue:
    """Wrap limb results in the narrowest faithful column class."""
    from auron_tpu.columnar import decimal128 as D
    if precision <= 18:
        v64, _fits = D.to_int64(hi, lo)   # |x| < 10^18 always fits
        return TypedValue(PrimitiveColumn(v64, validity), DataType.DECIMAL,
                          precision, scale)
    return TypedValue(D.Decimal128Column(hi, lo, validity),
                      DataType.DECIMAL, precision, scale)


def _eval_decimal128_binary(op, l: TypedValue, r: TypedValue, rp: int,
                            rs: int, full_s: int) -> TypedValue:
    """Two-limb decimal arithmetic/comparison for precision 19..38
    (reference computes these in Rust i128; columnar/decimal128.py is the
    limb kernel library). Declared-precision overflow nulls the row
    (Spark non-ANSI check_overflow semantics); when adjustPrecisionScale
    reduced the scale (full_s > rs), the raw result rescales HALF_UP.

    Rescaling by 10^ds can push a 38-digit value past 2^127 and wrap, so
    every rescale is guarded by a fits_precision(38 - ds) pre-check: for
    arithmetic an unsafe rescale implies result overflow (null); for
    comparisons those rows fall back to float64 ordering."""
    from auron_tpu.columnar import decimal128 as D
    s = max(l.scale, r.scale)
    lh, ll_ = _limbs_of(l)
    rh, rl = _limbs_of(r)
    validity = l.validity & r.validity

    def rescale_safe(h, lo, ds):
        if ds == 0:
            return h, lo, jnp.ones_like(validity)
        ok = D.fits_precision(h, lo, 38 - ds)
        h2, l2 = D.mul_pow10(h, lo, ds)
        return h2, l2, ok

    if op in ("==", "!=", "<", "<=", ">", ">="):
        ah, al, oka = rescale_safe(lh, ll_, s - l.scale)
        bh, bl, okb = rescale_safe(rh, rl, s - r.scale)
        lt, eq = D.cmp128(ah, al, bh, bl)
        # At most ONE side can be rescale-unsafe (only the smaller-scale
        # side has ds > 0), and an unsafe side's magnitude at the common
        # scale is >= 10^38 while a safe side's is < 10^38 — so the
        # unsafe side strictly dominates and its SIGN decides the order.
        a_unsafe = ~oka
        b_unsafe = ~okb
        a_neg = D.is_negative(lh, ll_)
        b_neg = D.is_negative(rh, rl)
        lt = jnp.where(a_unsafe, a_neg,
                       jnp.where(b_unsafe, ~b_neg, lt))
        eq = jnp.where(a_unsafe | b_unsafe, False, eq)
        out = {"==": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
               ">": ~(lt | eq), ">=": ~lt}[op]
        return TypedValue(PrimitiveColumn(out, validity), DataType.BOOL)
    if op in ("+", "-"):
        ah, al, oka = rescale_safe(lh, ll_, s - l.scale)
        bh, bl, okb = rescale_safe(rh, rl, s - r.scale)
        if op == "+":
            oh, ol = D.add128(ah, al, bh, bl)
            bsign = D.is_negative(bh, bl)
        else:
            oh, ol = D.sub128(ah, al, bh, bl)
            bsign = ~D.is_negative(bh, bl) & ~((bh == 0) & (bl == 0))
        # 128-bit wrap detection: same-sign operands whose result flips
        # sign overflowed 2^127 (would otherwise slip past the
        # post-rescale precision check as a plausible wrong value)
        asign = D.is_negative(ah, al)
        osign = D.is_negative(oh, ol)
        no_wrap = ~((asign == bsign) & (osign != asign))
        ok = oka & okb & no_wrap
    elif op == "*":
        oh, ol = D.mul128(lh, ll_, rh, rl)
        # a RAW product beyond 2^127 wraps silently in the low-128
        # multiply; guard with a float magnitude check at the
        # representability bound (2^127 ~ 1.70e38, margin for float
        # error). Known limitation vs Spark's unbounded BigDecimal
        # intermediates: a product whose raw (pre-precision-loss-rescale)
        # value exceeds 2^127 nulls even if the rescaled result would fit.
        mag = jnp.abs(D.to_float64(lh, ll_) * D.to_float64(rh, rl))
        ok = mag < 1.6e38
    else:
        raise NotImplementedError(f"decimal128 op {op}")
    if full_s > rs:
        # precision-loss rescale (Spark adjustPrecisionScale, HALF_UP)
        oh, ol = D.div_pow10_half_up(oh, ol, full_s - rs)
    ok = ok & D.fits_precision(oh, ol, rp)
    return _mk_decimal(oh, ol, validity & ok, rp, rs)


def _eval_decimal_divide(l: TypedValue, r: TypedValue) -> TypedValue:
    """decimal / decimal as Spark's Divide types and rounds it: the
    result type of ``decimal_result_type("/")``, the exact quotient
    rounded HALF_UP once at that scale, null on a zero divisor and where
    the quotient passes the result precision. (Spark itself rounds twice,
    at scale 38 or 39 by version and then at the result scale; the two
    agree unless the unscaled divisor passes 10^(38 - scale - the
    dividend's scale): 10^16 for decimal(17,2) / decimal(17,2).) An
    operand past its declared precision is null, so the static iteration
    count of the division covers every row it divides."""
    from auron_tpu.columnar import decimal128 as D
    lp = l.precision or (38 if isinstance(l.col, D.Decimal128Column) else 18)
    rp_ = r.precision or (38 if isinstance(r.col, D.Decimal128Column) else 18)
    p, s, _fs = decimal_result_type("/", lp, l.scale, rp_, r.scale)
    k = s - l.scale + r.scale
    lh, ll_ = _limbs_of(l)
    rh, rl = _limbs_of(r)
    ok = (l.validity & r.validity & ~((rh == 0) & (rl == 0))
          & D.fits_precision(lh, ll_, lp) & D.fits_precision(rh, rl, rp_))
    neg = D.is_negative(lh, ll_) ^ D.is_negative(rh, rl)
    ah, al = D.abs128(lh, ll_)
    bh, bl = D.abs128(rh, rl)
    # a null row's limbs are whatever the producer left there: divide
    # by one, the loop is total
    bh = jnp.where(ok, bh, 0)
    bl = jnp.where(ok, bl, 1)
    qh, ql, fits = D.div_scaled_half_up(ah, al, k, bh, bl, lp + k)
    ok = ok & fits & D.fits_precision(qh, ql, p)
    nh, nl = D.neg128(qh, ql)
    return _mk_decimal(jnp.where(neg, nh, qh), jnp.where(neg, nl, ql),
                       ok, p, s)


def _decimal_to_f64(v: TypedValue) -> TypedValue:
    from auron_tpu.columnar import decimal128 as D
    if isinstance(v.col, D.Decimal128Column):
        f = D.to_float64(v.col.hi, v.col.lo) / (10.0 ** v.scale)
        return TypedValue(PrimitiveColumn(f, v.validity), DataType.FLOAT64)
    if v.dtype == DataType.DECIMAL:
        return TypedValue(
            PrimitiveColumn(v.data.astype(jnp.float64) / (10.0 ** v.scale),
                            v.validity), DataType.FLOAT64)
    if v.dtype != DataType.FLOAT64:
        return TypedValue(PrimitiveColumn(v.data.astype(jnp.float64), v.validity),
                          DataType.FLOAT64)
    return v


def _eval_binary_simple(op, l: TypedValue, r: TypedValue) -> TypedValue:
    validity = l.validity & r.validity
    fn = {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
          "/": jnp.divide,
          "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
          "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal}[op]
    data = fn(l.data, r.data)
    out_t = DataType.BOOL if op in ("==", "!=", "<", "<=", ">", ">=") else DataType.FLOAT64
    return TypedValue(PrimitiveColumn(data, validity), out_t)


# ---------------------------------------------------------------------------
# case / in-list / like
# ---------------------------------------------------------------------------

def _eval_case(expr: ir.CaseWhen, batch, schema, ctx) -> TypedValue:
    branches = [(evaluate(w, batch, schema, ctx), evaluate(t, batch, schema, ctx))
                for w, t in expr.when_then]
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if expr.otherwise is not None:
        otherwise = evaluate(expr.otherwise, batch, schema, ctx)
    else:
        t0 = branches[0][1]
        # no ELSE: all null, in the first branch's own column class
        null_col = jax.tree_util.tree_map(jnp.zeros_like, t0.col)
        otherwise = TypedValue(null_col, t0.dtype, t0.precision, t0.scale)

    def limbs(v: TypedValue) -> Decimal128Column:
        if isinstance(v.col, Decimal128Column):
            return v.col
        return Decimal128Column(*_limbs_of(v), v.validity)

    result = otherwise
    for cond, val in reversed(branches):
        take = cond.data.astype(bool) & cond.validity
        if isinstance(val.col, StringColumn):
            rw = max(val.col.width, result.col.width)
            vc = _widen_string(val.col, rw)
            rc = _widen_string(result.col, rw)
            col = StringColumn(
                jnp.where(take[:, None], vc.chars, rc.chars),
                jnp.where(take, vc.lens, rc.lens),
                jnp.where(take, vc.validity, rc.validity))
        elif isinstance(val.col, Decimal128Column) \
                or isinstance(result.col, Decimal128Column):
            # a two-limb branch beside a one-word one (or a NULL
            # literal's): both as limbs, the wider type the result's
            vc, rc = limbs(val), limbs(result)
            col = Decimal128Column(jnp.where(take, vc.hi, rc.hi),
                                   jnp.where(take, vc.lo, rc.lo),
                                   jnp.where(take, vc.validity, rc.validity))
            if not isinstance(val.col, Decimal128Column):
                val = result
        else:
            col = PrimitiveColumn(
                jnp.where(take, val.data, result.data),
                jnp.where(take, val.validity, result.validity))
        result = TypedValue(col, val.dtype, val.precision, val.scale)
    return result


def _widen_string(col: StringColumn, width: int) -> StringColumn:
    if col.width == width:
        return col
    return StringColumn(jnp.pad(col.chars, ((0, 0), (0, width - col.width))),
                        col.lens, col.validity)


def _eval_in_list(expr: ir.InList, batch, schema, ctx) -> TypedValue:
    v = evaluate(expr.child, batch, schema, ctx)
    cap = batch.capacity
    if isinstance(v.col, StringColumn):
        hit = jnp.zeros(cap, bool)
        for s in expr.values:
            b = s.encode() if isinstance(s, str) else s
            lit_row, lit_len = S.literal_to_device(b, v.col.width)
            if lit_len > v.col.width:
                continue
            eq = jnp.all(v.col.chars == jnp.asarray(lit_row)[None, :], axis=1) \
                & (v.col.lens == lit_len)
            hit = hit | eq
    else:
        hit = jnp.zeros(cap, bool)
        for s in expr.values:
            hit = hit | (v.data == jnp.asarray(s, v.data.dtype))
    if expr.negated:
        hit = ~hit
    return TypedValue(PrimitiveColumn(hit, v.validity), DataType.BOOL)


def _eval_like(expr, batch, schema, ctx) -> TypedValue:
    v = evaluate(expr.child, batch, schema, ctx)
    if not isinstance(v.col, StringColumn):
        raise TypeError("LIKE on non-string")
    chars, lens = v.col.chars, v.col.lens

    if isinstance(expr, ir.StringStartsWith):
        hit = S.starts_with(chars, lens, expr.prefix.encode())
    elif isinstance(expr, ir.StringEndsWith):
        hit = S.ends_with(chars, lens, expr.suffix.encode())
    elif isinstance(expr, ir.StringContains):
        hit = S.contains(chars, lens, expr.infix.encode())
    else:
        pat = expr.pattern
        body = pat.strip("%")
        if "%" not in pat and "_" not in pat:
            row, ln = S.literal_to_device(pat.encode(), v.col.width)
            hit = (jnp.all(chars == jnp.asarray(row)[None, :], axis=1)
                   & (lens == ln)) if ln <= v.col.width else jnp.zeros(batch.capacity, bool)
        elif "_" not in body and "%" not in body:
            starts = not pat.startswith("%")
            ends = not pat.endswith("%")
            if starts and ends:
                # 'a%b' pattern
                parts = pat.split("%")
                hit = S.starts_with(chars, lens, parts[0].encode())
                for p in parts[1:-1]:
                    if p:
                        hit = hit & S.contains(chars, lens, p.encode())
                hit = hit & S.ends_with(chars, lens, parts[-1].encode())
                minlen = sum(len(p) for p in parts)
                hit = hit & (lens >= minlen)
            elif starts:
                hit = S.starts_with(chars, lens, body.encode())
            elif ends:
                hit = S.ends_with(chars, lens, body.encode())
            else:
                hit = S.contains(chars, lens, body.encode())
        else:
            # general pattern: host regex fallback
            import re
            rx = re.compile("^" + re.escape(pat).replace("%", ".*").replace("_", ".") + "$",
                            re.S)
            def host_like(chars_np, lens_np):
                out = np.zeros(chars_np.shape[0], bool)
                for i in range(chars_np.shape[0]):
                    s = bytes(chars_np[i, :lens_np[i]]).decode("utf-8", "replace")
                    out[i] = rx.match(s) is not None
                return out
            hit = jax.pure_callback(
                host_like, jax.ShapeDtypeStruct((batch.capacity,), jnp.bool_),
                chars, lens, vmap_method="sequential")
    if getattr(expr, "negated", False):
        hit = ~hit
    return TypedValue(PrimitiveColumn(hit, v.validity), DataType.BOOL)


# ---------------------------------------------------------------------------
# cast
# ---------------------------------------------------------------------------

_INT_BITS = {DataType.INT8: 8, DataType.INT16: 16, DataType.INT32: 32,
             DataType.INT64: 64}


def cast_value(v: TypedValue, dtype: DataType, precision: int = 0,
               scale: int = 0, safe: bool = True) -> TypedValue:
    """Spark cast semantics (checklist: reference
    datafusion-ext-commons/src/arrow/cast.rs). safe=True is the default
    null-on-failure mode (Spark non-ANSI / TryCast); safe=False raises on
    unparseable strings (ANSI), checked at the host boundary."""
    if v.dtype == dtype and (dtype != DataType.DECIMAL
                             or (v.scale == scale
                                 and v.precision <= precision)):
        return v
    validity = v.validity
    cap = validity.shape[0]

    if isinstance(v.col, StringColumn):
        return _cast_from_string(v, dtype, precision, scale, safe)

    from auron_tpu.columnar import decimal128 as _D128
    if isinstance(v.col, _D128.Decimal128Column) or (
            v.dtype == DataType.DECIMAL and dtype == DataType.DECIMAL
            and precision > 18):
        return _cast_decimal128(v, dtype, precision, scale)

    if dtype == DataType.STRING:
        return _cast_to_string(v)

    d = v.data

    if v.dtype == DataType.DECIMAL:
        if dtype == DataType.DECIMAL:
            # rescale with integer math: round half-up like Spark
            ds = scale - v.scale
            limit = 10 ** min(precision, 18)
            if ds >= 0:
                # overflow-check BEFORE multiplying (int64 wrap would
                # otherwise slip past the bound)
                pre_limit = limit // (10 ** ds) if ds <= 18 else 0
                ok = jnp.abs(d) < max(pre_limit, 1)
                unscaled = jnp.where(ok, d, 0) * (10 ** min(ds, 18))
            else:
                div = 10 ** (-ds)
                # round half away from zero (Spark HALF_UP)
                q_abs = (jnp.abs(d) + div // 2) // div
                unscaled = jnp.where(d >= 0, q_abs, -q_abs)
                ok = jnp.abs(unscaled) < limit
            return TypedValue(
                PrimitiveColumn(jnp.where(ok, unscaled, 0).astype(jnp.int64),
                                validity & ok),
                DataType.DECIMAL, precision, scale)
        if dtype.is_integer:
            # truncate toward zero on the decimal value (Spark)
            div = 10 ** v.scale
            q = jnp.where(d >= 0, d // div, -((-d) // div))
            target = _JNP[dtype]
            return TypedValue(PrimitiveColumn(q.astype(target), validity),
                              dtype)
        f = d.astype(jnp.float64) / (10.0 ** v.scale)
        return cast_value(TypedValue(PrimitiveColumn(f, validity),
                                     DataType.FLOAT64), dtype, precision, scale)

    if dtype == DataType.DECIMAL:
        if precision > 18 and v.dtype.is_floating:
            # double → wide decimal: build limbs from the float magnitude
            # (doubles carry 53 bits — digits beyond ~17 are already
            # approximation in Spark too, which rounds BigDecimal(double))
            from auron_tpu.columnar import decimal128 as D
            mag = jnp.abs(jnp.round(d.astype(jnp.float64) * (10.0 ** scale)))
            ok = mag < float(10 ** precision)
            magc = jnp.where(ok, mag, 0.0)
            hi_f = jnp.floor(magc / (2.0 ** 64))
            lo_f = magc - hi_f * (2.0 ** 64)
            hi = hi_f.astype(jnp.int64)
            lo = jnp.where(lo_f >= 2.0 ** 63,
                           (lo_f - 2.0 ** 64).astype(jnp.int64),
                           lo_f.astype(jnp.int64))
            neg = d < 0
            nh, nl = D.neg128(hi, lo)
            hi = jnp.where(neg, nh, hi)
            lo = jnp.where(neg, nl, lo)
            return TypedValue(D.Decimal128Column(hi, lo, validity & ok),
                              DataType.DECIMAL, precision, scale)
        if precision > 18 and not v.dtype.is_floating:
            # int → wide decimal: exact limb promotion + scale-up
            from auron_tpu.columnar import decimal128 as D
            hi, lo = D.from_int64(d.astype(jnp.int64))
            hi, lo = D.mul_pow10(hi, lo, scale)
            ok = D.fits_precision(hi, lo, precision)
            return TypedValue(D.Decimal128Column(hi, lo, validity & ok),
                              DataType.DECIMAL, precision, scale)
        if v.dtype.is_floating:
            unscaled = jnp.round(d.astype(jnp.float64) * (10.0 ** scale))
            ok = jnp.abs(unscaled) < float(10 ** min(precision, 18))
            out = jnp.where(ok, unscaled, 0).astype(jnp.int64)
            return TypedValue(PrimitiveColumn(out, validity & ok),
                              DataType.DECIMAL, precision, scale)
        unscaled = d.astype(jnp.int64) * (10 ** scale)
        ok = jnp.abs(unscaled) < (10 ** min(precision, 18))
        return TypedValue(PrimitiveColumn(jnp.where(ok, unscaled, 0), validity & ok),
                          DataType.DECIMAL, precision, scale)

    if dtype == DataType.BOOL:
        return TypedValue(PrimitiveColumn(d != 0, validity), DataType.BOOL)

    if v.dtype == DataType.BOOL:
        d = d.astype(jnp.int32)

    if dtype in _INT_BITS:
        target = _JNP[dtype]
        if v.dtype.is_floating:
            # Spark non-ANSI Cast: truncate toward zero; NaN, ±inf and
            # values outside the target range become NULL (not the JVM
            # d2i saturate — cast(2.5e9 as int) is NULL, not MaxValue).
            # The range check mirrors Spark's, where Long.MaxValue
            # promotes to double 2^63: the input exactly 2^63 is ADMITTED
            # and d2l-saturates to MaxValue, while anything above nulls.
            bits = _INT_BITS[dtype]
            t = jnp.trunc(d.astype(jnp.float64))
            lo_f = -(2.0 ** (bits - 1))
            hi_adm = float(2 ** (bits - 1) - 1)   # int64: rounds to 2^63
            ok = (t >= lo_f) & (t <= hi_adm)      # False for NaN/±inf too
            at_top = t >= 2.0 ** (bits - 1)       # the admitted boundary
            out = jnp.where(ok & ~at_top, t, 0.0).astype(target)
            out = jnp.where(at_top, jnp.asarray(2 ** (bits - 1) - 1, target),
                            out)
            return TypedValue(PrimitiveColumn(out, validity & ok), dtype)
        # int→int narrowing wraps (Java semantics)
        return TypedValue(PrimitiveColumn(d.astype(target), validity), dtype)

    if dtype in (DataType.FLOAT32, DataType.FLOAT64):
        return TypedValue(PrimitiveColumn(d.astype(_JNP[dtype]), validity), dtype)

    if dtype == DataType.DATE32:
        if v.dtype == DataType.TIMESTAMP_US:
            days = jnp.floor_divide(d, 86_400_000_000)
            return TypedValue(PrimitiveColumn(days.astype(jnp.int32), validity),
                              DataType.DATE32)
        return TypedValue(PrimitiveColumn(d.astype(jnp.int32), validity),
                          DataType.DATE32)

    if dtype == DataType.TIMESTAMP_US:
        if v.dtype == DataType.DATE32:
            us = d.astype(jnp.int64) * 86_400_000_000
            return TypedValue(PrimitiveColumn(us, validity), DataType.TIMESTAMP_US)
        return TypedValue(PrimitiveColumn(d.astype(jnp.int64), validity),
                          DataType.TIMESTAMP_US)

    raise NotImplementedError(f"cast {v.dtype} -> {dtype}")


def _cast_decimal128(v: TypedValue, dtype: DataType, precision: int,
                     scale: int) -> TypedValue:
    """Casts touching the two-limb representation: rescale between wide
    and narrow decimals (HALF_UP, overflow→null), to float, to ints, and
    to string via the host (reference: arrow/cast.rs decimal arms)."""
    from auron_tpu.columnar import decimal128 as D
    validity = v.validity
    hi, lo = _limbs_of(v)
    if dtype == DataType.DECIMAL:
        ds = scale - v.scale
        if ds >= 0:
            hi2, lo2 = D.mul_pow10(hi, lo, ds)
        else:
            hi2, lo2 = D.div_pow10_half_up(hi, lo, -ds)
        ok = D.fits_precision(hi2, lo2, precision)
        return _mk_decimal(hi2, lo2, validity & ok, precision, scale)
    if dtype.is_floating or dtype == DataType.FLOAT64:
        f = D.to_float64(hi, lo) / (10.0 ** v.scale)
        return cast_value(TypedValue(PrimitiveColumn(f, validity),
                                     DataType.FLOAT64), dtype)
    if dtype.is_integer:
        # truncate toward zero, then int64 range check (Spark)
        qh, ql = D.div_pow10_trunc(hi, lo, v.scale)
        v64, fits = D.to_int64(qh, ql)
        target = _JNP[dtype]
        return TypedValue(PrimitiveColumn(v64.astype(target),
                                          validity & fits), dtype)
    if dtype == DataType.STRING:
        import jax
        import numpy as np
        cap = validity.shape[0]
        width = 48  # 38 digits + sign + point + margin

        def host(hi_np, lo_np, valid_np):
            import decimal
            ints = D.ints_from_limbs(hi_np, lo_np, valid_np)
            chars = np.zeros((cap, width), np.uint8)
            lens = np.zeros(cap, np.int32)
            with decimal.localcontext() as dctx:
                dctx.prec = 60
                for i, x in enumerate(ints):
                    if x is None:
                        continue
                    d = decimal.Decimal(x).scaleb(-v.scale)
                    # plain notation, never scientific (Spark CAST output)
                    b = format(d, "f").encode()[:width]
                    chars[i, :len(b)] = np.frombuffer(b, np.uint8)
                    lens[i] = len(b)
            return chars, lens

        chars, lens = jax.pure_callback(
            host,
            (jax.ShapeDtypeStruct((cap, width), jnp.uint8),
             jax.ShapeDtypeStruct((cap,), jnp.int32)),
            hi, lo, validity, vmap_method="sequential")
        return TypedValue(StringColumn(chars, lens, validity),
                          DataType.STRING)
    raise NotImplementedError(f"decimal128 cast to {dtype}")


def _cast_to_string(v: TypedValue) -> TypedValue:
    """Numeric→string via host callback (cold path, like the reference's JVM
    UDF fallback)."""
    cap = v.data.shape[0]
    if v.dtype == DataType.BOOL:
        fmt = lambda x: str(bool(x)).lower()
        width = 8
    elif v.dtype.is_integer:
        fmt = lambda x: str(int(x))
        width = 24
    elif v.dtype == DataType.DECIMAL:
        scale = v.scale
        def fmt(x):
            from decimal import Decimal
            return str(Decimal(int(x)).scaleb(-scale))
        width = 24
    elif v.dtype == DataType.DATE32:
        import datetime
        fmt = lambda x: (datetime.date(1970, 1, 1)
                         + datetime.timedelta(days=int(x))).isoformat()
        width = 16
    elif v.dtype == DataType.TIMESTAMP_US:
        import datetime
        def fmt(x):
            ts = (datetime.datetime(1970, 1, 1)
                  + datetime.timedelta(microseconds=int(x)))
            s = ts.strftime("%Y-%m-%d %H:%M:%S")
            if ts.microsecond:
                s += f".{ts.microsecond:06d}".rstrip("0")
            return s
        width = 32
    else:
        is_f32 = v.dtype == DataType.FLOAT32
        def fmt(x):
            f = float(x)
            if f != f:
                return "NaN"
            if f == float("inf"):
                return "Infinity"
            if f == float("-inf"):
                return "-Infinity"
            a = abs(f)
            if a != 0 and (a >= 1e7 or a < 1e-3):
                # Java Float/Double.toString switches to scientific
                # notation outside [1e-3, 1e7): '1.0E30'
                s = np.format_float_scientific(
                    np.float32(x) if is_f32 else f, unique=True,
                    trim="0", exp_digits=1)
                mant, exp = s.split("e")
                if "." not in mant:
                    mant += ".0"
                return f"{mant}E{int(exp)}"
            if f == int(f):
                return f"{f:.1f}"
            if is_f32:
                # shortest round-trip at f32 precision: '0.1', not the
                # widened double representation '0.10000000149...'
                return np.format_float_positional(
                    np.float32(x), unique=True, trim="0")
            return repr(f)
        width = 32

    def host_fmt(data_np):
        chars = np.zeros((cap, width), np.uint8)
        lens = np.zeros(cap, np.int32)
        for i, x in enumerate(data_np):
            b = fmt(x).encode()[:width]
            chars[i, : len(b)] = np.frombuffer(b, np.uint8)
            lens[i] = len(b)
        return chars, lens

    chars, lens = jax.pure_callback(
        host_fmt,
        (jax.ShapeDtypeStruct((cap, width), jnp.uint8),
         jax.ShapeDtypeStruct((cap,), jnp.int32)),
        v.data, vmap_method="sequential")
    return TypedValue(StringColumn(chars, lens, v.validity), DataType.STRING)


def _cast_from_string(v: TypedValue, dtype: DataType, precision: int,
                      scale: int, safe: bool = True) -> TypedValue:
    """string→numeric parse on host; invalid → null when safe (TryCast /
    non-ANSI), raise when not (ANSI) (reference:
    datafusion-ext-exprs/src/cast.rs)."""
    col: StringColumn = v.col
    cap = col.capacity

    if dtype == DataType.BOOL:
        parse = lambda s: {"true": True, "t": True, "1": True, "yes": True, "y": True,
                           "false": False, "f": False, "0": False, "no": False,
                           "n": False}.get(s.strip().lower())
        np_t = np.bool_
    elif dtype.is_integer or dtype == DataType.DATE32:
        if dtype == DataType.DATE32:
            import datetime
            import re
            # Spark accepts non-zero-padded fields: yyyy-[m]m-[d]d
            # (DateTimeUtils.stringToDate); fromisoformat would reject
            # "2020-1-2"
            date_re = re.compile(r"^(\d{1,4})-(\d{1,2})-(\d{1,2})$")
            def parse(s):
                m = date_re.match(s.strip())
                if not m:
                    return None
                try:
                    d = datetime.date(int(m.group(1)), int(m.group(2)),
                                      int(m.group(3)))
                except ValueError:
                    return None
                return (d - datetime.date(1970, 1, 1)).days
            np_t = np.int32
        else:
            bits = _INT_BITS[dtype]
            lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
            def parse(s):
                # Spark UTF8String.toInt/toLong: trimmed, optional sign,
                # digits with an optional '.' + digit fraction that
                # TRUNCATES toward zero ('4.5'→4, '.5'→0); scientific
                # notation ('1e2') stays NULL. Exact int parsing keeps
                # Long.MaxValue-class strings lossless.
                s = s.strip()
                if not s:
                    return None
                sign = -1 if s[0] == "-" else 1
                body = s[1:] if s[0] in "+-" else s
                if not (body.isascii() and body.isdigit()):
                    intpart, dot, frac = body.partition(".")
                    if not dot or not (frac == "" or (frac.isascii()
                                                     and frac.isdigit())):
                        return None
                    if intpart and not (intpart.isascii()
                                        and intpart.isdigit()):
                        return None
                    if not intpart and not frac:
                        return None      # bare '.' / '+.'
                    body = intpart or "0"
                r = sign * int(body)
                return r if lo <= r <= hi else None
            np_t = _JNP[dtype]
    elif dtype == DataType.DECIMAL:
        from decimal import ROUND_HALF_UP, Decimal, InvalidOperation
        def parse(s):
            try:
                # Spark Decimal.changePrecision rescales HALF_UP:
                # cast('1.005' as decimal(10,2)) → 1.01, not banker's 1.00.
                # OverflowError: 'Infinity' parses as a Decimal but cannot
                # convert to int — NULL, not a crash
                r = int(Decimal(s.strip()).scaleb(scale)
                        .to_integral_value(rounding=ROUND_HALF_UP))
            except (InvalidOperation, ValueError, OverflowError):
                return None
            # beyond the declared precision → null (Spark
            # Decimal.changePrecision failure)
            if precision and abs(r) >= 10 ** precision:
                return None
            return r
        np_t = np.int64
    elif dtype == DataType.TIMESTAMP_US:
        import datetime
        def parse(s):
            try:
                ts = datetime.datetime.fromisoformat(s.strip())
            except ValueError:
                return None
            if ts.tzinfo is None:
                ts = ts.replace(tzinfo=datetime.timezone.utc)
            else:
                ts = ts.astimezone(datetime.timezone.utc)
            return int(ts.timestamp() * 1e6)
        np_t = np.int64
    else:
        def parse(s):
            try:
                return float(s.strip())
            except ValueError:
                return None
        np_t = _JNP[dtype]

    def host_parse(chars_np, lens_np, valid_np):
        data = np.zeros(cap, np_t)
        ok = np.zeros(cap, bool)
        for i in range(cap):
            s = bytes(chars_np[i, : lens_np[i]]).decode("utf-8", "replace")
            try:
                r = parse(s)
            except (ValueError, OverflowError):
                r = None
            if r is not None:
                try:
                    data[i] = r
                    ok[i] = True
                except (OverflowError, ValueError):
                    # parsed but does not fit the target width → null
                    data[i] = 0
                    ok[i] = False
            if not ok[i] and not safe and valid_np[i]:
                raise ValueError(
                    f"[CAST_INVALID_INPUT] cannot cast {s!r} to "
                    f"{dtype.value} (ANSI mode)")
        return data, ok

    data, ok = jax.pure_callback(
        host_parse,
        (jax.ShapeDtypeStruct((cap,), np_t),
         jax.ShapeDtypeStruct((cap,), jnp.bool_)),
        col.chars, col.lens, v.validity, vmap_method="sequential")
    return TypedValue(PrimitiveColumn(data, v.validity & ok), dtype,
                      precision, scale)


# ---------------------------------------------------------------------------
# host UDF escape hatch
# ---------------------------------------------------------------------------

def _eval_host_udf(expr: ir.HostUDF, batch, schema, ctx) -> TypedValue:
    import pyarrow as pa
    args = [evaluate(a, batch, schema, ctx) for a in expr.args]
    cap = batch.capacity

    # argument wire: primitives as (data, validity); strings via the
    # (chars, lens, validity) protocol (the Arrow-FFI round trip of the
    # reference's SparkUDFWrapperContext, spark_udf_wrapper.rs:43-230)
    leaves: list = []
    layout: list[str] = []
    for a in args:
        if isinstance(a.col, StringColumn):
            layout.append("s")
            leaves += [a.col.chars, a.col.lens, a.validity]
        else:
            layout.append("p")
            leaves += [a.data, a.validity]

    string_result = expr.dtype == DataType.STRING
    out_np = None if string_result else _JNP[expr.dtype]
    # result width bound: adaptive to the string inputs (a concat-style
    # UDF fits), floored at 256; truncation happens on UTF-8 codepoint
    # boundaries so an overflow can never corrupt the column
    out_w = 0
    if string_result:
        in_w = sum(a.col.width for a in args
                   if isinstance(a.col, StringColumn))
        out_w = bucket_string_width(max(2 * in_w + 64, 256))

    def host(*cols):
        arrays = []
        pos = 0
        for kind in layout:
            if kind == "s":
                chars, lens, ok = cols[pos:pos + 3]
                pos += 3
                vals = [bytes(chars[i, :lens[i]]).decode("utf-8", "replace")
                        if ok[i] else None for i in range(cap)]
                arrays.append(pa.array(vals, pa.string()))
            else:
                d, ok = cols[pos:pos + 2]
                pos += 2
                arrays.append(pa.array(
                    np.where(ok, d, None).tolist() if not ok.all() else d))
        result = expr.fn(arrays)
        ok = ~np.asarray(result.is_null()) if result.null_count \
            else np.ones(cap, bool)
        if string_result:
            chars = np.zeros((cap, out_w), np.uint8)
            lens = np.zeros(cap, np.int32)
            for i, v in enumerate(result.to_pylist()):
                if v is None:
                    continue
                b = v.encode()
                if len(b) > out_w:
                    b = b[:out_w]
                    # back off to a codepoint boundary (0b10xxxxxx bytes
                    # are continuations)
                    while b and (b[-1] & 0xC0) == 0x80:
                        b = b[:-1]
                chars[i, :len(b)] = np.frombuffer(b, np.uint8)
                lens[i] = len(b)
            return chars, lens, ok
        res_np = np.asarray(result.fill_null(0).to_numpy(
            zero_copy_only=False), dtype=out_np)
        return res_np.astype(out_np), ok

    if string_result:
        chars, lens, ok = jax.pure_callback(
            host,
            (jax.ShapeDtypeStruct((cap, out_w), jnp.uint8),
             jax.ShapeDtypeStruct((cap,), jnp.int32),
             jax.ShapeDtypeStruct((cap,), jnp.bool_)),
            *leaves, vmap_method="sequential")
        return TypedValue(StringColumn(chars, lens, ok), DataType.STRING)
    data, ok = jax.pure_callback(
        host,
        (jax.ShapeDtypeStruct((cap,), out_np),
         jax.ShapeDtypeStruct((cap,), jnp.bool_)),
        *leaves, vmap_method="sequential")
    return TypedValue(PrimitiveColumn(data, ok), expr.dtype)
