"""Physical expression IR.

The in-memory analogue of the reference's ``PhysicalExprNode`` protobuf
(reference: native-engine/auron-planner/proto/auron.proto:60-127). The
protobuf layer (auron_tpu.ir) deserializes into these nodes; the evaluator
(auron_tpu.exprs.eval) lowers them onto device batches as jax ops.

Expressions are frozen dataclass trees so they can be hashed/compared and
used as jit static arguments — one compiled kernel per (expression tree,
shape bucket) pair.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from auron_tpu.columnar.schema import DataType


class Expr:
    """Base class; subclasses are frozen dataclasses."""

    def children(self) -> tuple["Expr", ...]:
        return ()


@dataclass(frozen=True)
class ColumnRef(Expr):
    """Bound reference to input column by ordinal (the reference binds by
    index too, auron.proto BoundReference)."""
    index: int
    # optional name for debugging only
    name: str = ""


@dataclass(frozen=True)
class Literal(Expr):
    value: Any           # python scalar; None for typed null
    dtype: DataType
    precision: int = 0   # decimal only
    scale: int = 0


@dataclass(frozen=True)
class BinaryExpr(Expr):
    """op in {+,-,*,/,%, ==,!=,<,<=,>,>=, and,or}."""
    op: str
    left: Expr
    right: Expr

    def children(self):
        return (self.left, self.right)


@dataclass(frozen=True)
class Not(Expr):
    child: Expr

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class IsNull(Expr):
    child: Expr

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class IsNotNull(Expr):
    child: Expr

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class Negative(Expr):
    child: Expr

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class Cast(Expr):
    child: Expr
    dtype: DataType
    precision: int = 0
    scale: int = 0
    # try_cast: null on failure instead of error (reference: TryCast,
    # datafusion-ext-exprs/src/cast.rs)
    safe: bool = True

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class CaseWhen(Expr):
    """CASE WHEN c1 THEN v1 ... ELSE e END; when_then pairs, else optional."""
    when_then: tuple[tuple[Expr, Expr], ...]
    otherwise: Optional[Expr] = None

    def children(self):
        out = []
        for w, t in self.when_then:
            out += [w, t]
        if self.otherwise is not None:
            out.append(self.otherwise)
        return tuple(out)


@dataclass(frozen=True)
class InList(Expr):
    child: Expr
    values: tuple[Any, ...]   # python scalars (non-null)
    negated: bool = False

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class Like(Expr):
    """LIKE with a constant pattern; lowered to starts/ends/contains/regex."""
    child: Expr
    pattern: str
    negated: bool = False

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class StringStartsWith(Expr):
    child: Expr
    prefix: str

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class StringEndsWith(Expr):
    child: Expr
    suffix: str

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class StringContains(Expr):
    child: Expr
    infix: str

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class ScalarFunction(Expr):
    """Named scalar function from the registry (reference:
    datafusion-ext-functions/src/lib.rs)."""
    name: str
    args: tuple[Expr, ...]
    # some functions need a target type/scale (e.g. make_decimal)
    dtype: Optional[DataType] = None
    precision: int = 0
    scale: int = 0

    def children(self):
        return self.args


@dataclass(frozen=True)
class BloomFilterMightContain(Expr):
    """Membership probe against a serialized Spark bloom filter (reference:
    datafusion-ext-exprs/src/bloom_filter_might_contain.rs). The filter
    bytes travel in the expression, as in Spark's runtime filter pushdown."""
    value: Expr
    serialized: bytes

    def children(self):
        return (self.value,)


@dataclass(frozen=True)
class GetIndexedField(Expr):
    """list[ordinal] element access, 0-based (reference:
    datafusion-ext-exprs/src/get_indexed_field.rs)."""
    child: Expr
    ordinal: int

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class GetStructField(Expr):
    """struct.field access by child ordinal (reference:
    datafusion-ext-exprs/src/get_indexed_field.rs struct arm +
    Spark GetStructField)."""
    child: Expr
    ordinal: int

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class ScalarSubquery(Expr):
    """Uncorrelated scalar subquery: ``plan_bytes`` is a serialized
    PlanNode executed ONCE at task start; its single value is substituted
    as a Literal before any kernel builds (reference:
    datafusion-ext-exprs/src/spark_scalar_subquery_wrapper.rs — there the
    value comes back from the JVM, here the engine runs the child plan).
    Held as bytes so the expr tree stays hashable for kernel caching; it
    has NO expr children (the plan is opaque at this level)."""
    plan_bytes: bytes
    dtype: DataType
    precision: int = 0
    scale: int = 0
    sid: int = 0


@dataclass(frozen=True)
class RowNum(Expr):
    """Monotonic row number within the partition stream (reference:
    datafusion-ext-exprs/src/row_num.rs)."""


@dataclass(frozen=True)
class SparkPartitionId(Expr):
    pass


@dataclass(frozen=True)
class MonotonicallyIncreasingId(Expr):
    pass


@dataclass(frozen=True)
class HostUDF(Expr):
    """Escape hatch: evaluate an arbitrary host (python/pyarrow) function on
    the host via jax.pure_callback — the analogue of the reference's
    SparkUDFWrapperExpr JVM round-trip (reference:
    datafusion-ext-exprs/src/spark_udf_wrapper.rs:43-230)."""
    fn: Any                 # callable: list[pa.Array] -> pa.Array
    args: tuple[Expr, ...]
    dtype: DataType
    name: str = "udf"

    def children(self):
        return self.args

    def __hash__(self):
        return hash((id(self.fn), self.args, self.dtype, self.name))


# ---------------------------------------------------------------------------
# sort / aggregate helper nodes (used by operators, not standalone exprs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SortOrder:
    expr: Expr
    ascending: bool = True
    nulls_first: bool = True


@dataclass(frozen=True)
class AggFunction:
    """One aggregate: fn in {sum,count,avg,min,max,first,first_ignores_null,
    count_star, bloom_filter, collect_list, collect_set}."""
    fn: str
    arg: Optional[Expr] = None     # None for count(*)
    distinct: bool = False
    # bloom_filter sizing; 0 = engine defaults
    expected_items: int = 0
    fpp: float = 0.0
    #: this function's own mode inside its aggregation (partial |
    #: partial_merge | final | complete); None = the node's
    mode: Optional[str] = None
