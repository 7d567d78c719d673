"""DataFrame DSL → proto plan builder.

Unresolved column names resolve against the child's schema at build time —
the same late binding the reference's converters do against the Spark
plan's output attributes (NativeConverters.scala:95+)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Union

import pyarrow as pa

from auron_tpu.columnar.schema import DataType, Field, Schema
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import infer_dtype
from auron_tpu.ir import pb, serde

# ---------------------------------------------------------------------------
# column expressions (unresolved)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Col:
    """Unresolved expression + optional alias."""

    node: Any             # _Ref | ir-builder tree of Cols
    name: Optional[str] = None

    # -- operators ----------------------------------------------------------
    def _bin(self, op, other) -> "Col":
        return Col(("bin", op, self, _wrap(other)))

    def __add__(self, o): return self._bin("+", o)
    def __radd__(self, o): return _wrap(o)._bin("+", self)
    def __sub__(self, o): return self._bin("-", o)
    def __rsub__(self, o): return _wrap(o)._bin("-", self)
    def __mul__(self, o): return self._bin("*", o)
    def __rmul__(self, o): return _wrap(o)._bin("*", self)
    def __truediv__(self, o): return self._bin("/", o)
    def __mod__(self, o): return self._bin("%", o)
    def __eq__(self, o): return self._bin("==", o)      # type: ignore
    def __ne__(self, o): return self._bin("!=", o)      # type: ignore
    def __lt__(self, o): return self._bin("<", o)
    def __le__(self, o): return self._bin("<=", o)
    def __gt__(self, o): return self._bin(">", o)
    def __ge__(self, o): return self._bin(">=", o)
    def __and__(self, o): return self._bin("and", o)
    def __or__(self, o): return self._bin("or", o)
    def __invert__(self): return Col(("not", self))

    def __hash__(self):
        return id(self)

    # -- builders -----------------------------------------------------------
    def alias(self, name: str) -> "Col":
        return Col(self.node, name)

    def cast(self, dtype: DataType, precision: int = 0,
             scale: int = 0) -> "Col":
        return Col(("cast", self, dtype, precision, scale), self.name)

    def is_null(self) -> "Col":
        return Col(("is_null", self))

    def is_not_null(self) -> "Col":
        return Col(("is_not_null", self))

    def isin(self, *values) -> "Col":
        vals = values[0] if len(values) == 1 and isinstance(
            values[0], (list, tuple)) else values
        return Col(("in", self, tuple(vals)))

    def like(self, pattern: str) -> "Col":
        return Col(("like", self, pattern))

    def startswith(self, prefix: str) -> "Col":
        return Col(("startswith", self, prefix))

    def endswith(self, suffix: str) -> "Col":
        return Col(("endswith", self, suffix))

    def contains(self, infix: str) -> "Col":
        return Col(("contains", self, infix))

    def getitem(self, ordinal: int) -> "Col":
        return Col(("index", self, ordinal))

    def asc(self, nulls_first: bool = True) -> "SortCol":
        return SortCol(self, True, nulls_first)

    def desc(self, nulls_first: bool = False) -> "SortCol":
        return SortCol(self, False, nulls_first)

    def out_name(self, default: str = "col") -> str:
        if self.name:
            return self.name
        if isinstance(self.node, str):
            return self.node
        return default


@dataclass(frozen=True)
class SortCol:
    col: Col
    ascending: bool = True
    nulls_first: bool = True


@dataclass(frozen=True)
class AggCol:
    fn: str
    arg: Optional[Col]
    name: Optional[str] = None
    distinct: bool = False

    def alias(self, name: str) -> "AggCol":
        return AggCol(self.fn, self.arg, name, self.distinct)

    def out_name(self, i: int) -> str:
        if self.name:
            return self.name
        argname = self.arg.out_name() if self.arg is not None else ""
        return f"{self.fn}({argname})" if argname else self.fn


@dataclass
class WinFn:
    """One window function spec for DataFrame.window() (the DSL face of
    WindowFunctionP / ops.window.WindowFunctionSpec)."""
    kind: str                   # rank_like | offset | agg
    fn: str
    arg: Optional["Col"] = None
    offset: int = 1
    default: Any = None
    name: Optional[str] = None
    #: ROWS BETWEEN (lo, hi) for agg functions; None = default frame
    frame: Optional[tuple] = None

    def alias(self, name: str) -> "WinFn":
        from dataclasses import replace as _replace
        return _replace(self, name=name)

    def out_name(self, i: int) -> str:
        return self.name or f"{self.fn}_{i}"


def col(name: str) -> Col:
    return Col(name)


def lit(value, dtype: Optional[DataType] = None) -> Col:
    return Col(("lit", value, dtype))


def _wrap(v) -> Col:
    return v if isinstance(v, Col) else lit(v)


def _py_dtype(v) -> DataType:
    if isinstance(v, bool):
        return DataType.BOOL
    if isinstance(v, int):
        return DataType.INT64
    if isinstance(v, float):
        return DataType.FLOAT64
    if isinstance(v, str):
        return DataType.STRING
    raise TypeError(f"cannot infer literal type for {type(v).__name__}")


def resolve(c: Col, schema: Schema) -> ir.Expr:
    """Resolve a Col tree to a bound ir.Expr against ``schema``."""
    n = c.node
    if isinstance(n, str):
        return ir.ColumnRef(schema.index_of(n), n)
    if isinstance(n, ir.Expr):
        return n
    tag = n[0]
    if tag == "lit":
        _, value, dtype = n
        if value is None:
            return ir.Literal(None, dtype or DataType.NULL)
        return ir.Literal(value, dtype or _py_dtype(value))
    if tag == "bin":
        _, op, l, r = n
        return ir.BinaryExpr(op, resolve(l, schema), resolve(r, schema))
    if tag == "not":
        return ir.Not(resolve(n[1], schema))
    if tag == "is_null":
        return ir.IsNull(resolve(n[1], schema))
    if tag == "is_not_null":
        return ir.IsNotNull(resolve(n[1], schema))
    if tag == "cast":
        _, child, dtype, p, s = n
        return ir.Cast(resolve(child, schema), dtype, p, s)
    if tag == "in":
        return ir.InList(resolve(n[1], schema), tuple(n[2]))
    if tag == "like":
        return ir.Like(resolve(n[1], schema), n[2])
    if tag == "startswith":
        return ir.StringStartsWith(resolve(n[1], schema), n[2])
    if tag == "endswith":
        return ir.StringEndsWith(resolve(n[1], schema), n[2])
    if tag == "contains":
        return ir.StringContains(resolve(n[1], schema), n[2])
    if tag == "index":
        return ir.GetIndexedField(resolve(n[1], schema), n[2])
    if tag == "fn":
        _, fname, args = n
        return ir.ScalarFunction(
            fname, tuple(resolve(a, schema) for a in args))
    if tag == "udf":
        _, registry_name, args, dtype = n
        from auron_tpu.exprs import udf as udf_registry
        fn, dt, p, s = udf_registry.lookup_udf(registry_name)
        return ir.HostUDF(fn, tuple(resolve(a, schema) for a in args),
                          dt, registry_name)
    if tag == "subquery":
        _, plan_bytes, dtype, p, s, sid = n
        return ir.ScalarSubquery(plan_bytes, dtype, p, s, sid)
    raise NotImplementedError(f"cannot resolve column node {tag!r}")


_SUBQUERY_IDS = iter(range(1, 1 << 30))


def scalar_subquery(df) -> Col:
    """An uncorrelated scalar subquery over a single-column DataFrame:
    the plan executes once per task and its one value becomes a literal
    (Spark's ScalarSubquery; 0 rows → NULL, >1 rows → runtime error).
    Correlated subqueries must still be rewritten as joins — exactly as
    Spark's own optimizer does before the physical plan exists."""
    if len(df.schema) != 1:
        raise ValueError(
            f"scalar subquery must produce exactly one column, got "
            f"{[f.name for f in df.schema]}")
    f = df.schema[0]
    return Col(("subquery", df.plan.SerializeToString(), f.dtype,
                f.precision, f.scale, next(_SUBQUERY_IDS)))


class _Functions:
    """`functions.upper(col) / functions.sum(col) / ...` — scalar function
    and aggregate builders (reference: datafusion-ext-functions registry +
    agg set)."""

    _AGGS = {"sum", "count", "avg", "min", "max", "first",
             "first_ignores_null", "collect_list", "collect_set"}

    def __getattr__(self, name: str) -> Callable[..., Any]:
        fname = name.rstrip("_")
        if fname in self._AGGS:
            def agg_builder(c: Optional[Col] = None, distinct=False):
                return AggCol(fname, _wrap(c) if c is not None else None,
                              distinct=distinct)
            return agg_builder

        def builder(*args):
            return Col(("fn", fname, tuple(_wrap(a) for a in args)))
        return builder

    def count_star(self) -> AggCol:
        return AggCol("count_star", None)

    # -- window function builders (DataFrame.window) ------------------------

    def row_number(self) -> WinFn:
        return WinFn("rank_like", "row_number")

    def rank(self) -> WinFn:
        return WinFn("rank_like", "rank")

    def dense_rank(self) -> WinFn:
        return WinFn("rank_like", "dense_rank")

    def percent_rank(self) -> WinFn:
        return WinFn("rank_like", "percent_rank")

    def cume_dist(self) -> WinFn:
        return WinFn("rank_like", "cume_dist")

    def ntile(self, n: int) -> WinFn:
        return WinFn("rank_like", "ntile", offset=n)

    def lead(self, c, offset: int = 1, default=None) -> WinFn:
        return WinFn("offset", "lead", _wrap(c), offset, default)

    def lag(self, c, offset: int = 1, default=None) -> WinFn:
        return WinFn("offset", "lag", _wrap(c), offset, default)

    def nth_value(self, c, n: int) -> WinFn:
        return WinFn("offset", "nth_value", _wrap(c), n)

    def first_value(self, c) -> WinFn:
        return WinFn("offset", "first_value", _wrap(c))

    def last_value(self, c) -> WinFn:
        return WinFn("offset", "last_value", _wrap(c))

    def win_agg(self, fn: str, c=None, frame=None) -> WinFn:
        """Running aggregate over the window frame (Spark default frame:
        UNBOUNDED PRECEDING..CURRENT ROW with ORDER BY, else whole
        partition): win_agg("sum", col) / win_agg("count_star").
        ``frame=(lo, hi)`` selects ROWS BETWEEN lo..hi (relative row
        offsets, negative = preceding), e.g. frame=(-1, 1) is the q47/
        q57-class centered moving window."""
        return WinFn("agg", fn, _wrap(c) if c is not None else None,
                     frame=tuple(frame) if frame is not None else None)

    def udf(self, registry_name: str, *args) -> Col:
        return Col(("udf", registry_name, tuple(_wrap(a) for a in args),
                    None))


functions = _Functions()


# ---------------------------------------------------------------------------
# DataFrame
# ---------------------------------------------------------------------------

class GroupedData:
    def __init__(self, df: "DataFrame", keys: Sequence[Col]):
        self.df = df
        self.keys = [_wrap(k) if not isinstance(k, Col) else k
                     for k in keys]

    #: the name of the DISTINCT argument as a group column of the first
    #: three aggregates of a single-DISTINCT plan
    _DISTINCT_ARG = "__distinct_arg__"

    @staticmethod
    def _dedupes(a: ir.AggFunction) -> bool:
        """DISTINCT changes what count / sum / avg see; min / max / first
        see the same rows with it or without."""
        return a.distinct and a.arg is not None \
            and a.fn in ("count", "sum", "avg")

    @staticmethod
    def _agg_node(child, group_exprs, agg_fns, mode, group_names,
                  agg_names) -> pb.PlanNode:
        return pb.PlanNode(agg=pb.AggNode(
            child=child,
            group_exprs=[serde.expr_to_proto(e) for e in group_exprs],
            aggs=[serde.agg_to_proto(a) for a in agg_fns],
            mode=mode, group_names=group_names, agg_names=agg_names))

    @staticmethod
    def _exchange(child, n_keys: int, n_part: int) -> pb.PlanNode:
        """Hash exchange on the first ``n_keys`` columns; with no key,
        every row to one partition."""
        if n_keys > 0:
            part = pb.PartitioningP(
                kind="hash", num_partitions=n_part,
                hash_keys=[serde.expr_to_proto(ir.ColumnRef(i))
                           for i in range(n_keys)])
        else:
            part = pb.PartitioningP(kind="single", num_partitions=1)
        return pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
            child=child, partitioning=part, input_partitions=n_part))

    def _plan_one_distinct(self, group_exprs, group_names, agg_fns,
                           agg_names, arg: ir.Expr) -> pb.PlanNode:
        """count / sum / avg DISTINCT over ONE argument x, beside any
        plain functions, as Spark plans it
        (AggUtils.planAggregateWithOneDistinct) — four aggregates:

          1. keys ++ [x], the plain functions ``partial``;
          2. the same keys, those functions ``partial_merge`` (after a
             hash exchange on keys ++ [x] where there are partitions):
             the groups that leave are the distinct (keys, x) pairs;
          3. the keys alone, the plain functions ``partial_merge`` and
             the DISTINCT ones ``partial`` over the column x, in one node;
          4. every function ``final`` (after a hash exchange on the keys,
             or a gather to one partition where there are none).

        At one partition the four operators stay, as in Spark, and no
        exchange stands between them. The DISTINCT functions run as plain
        ones over deduplicated rows, so their result types are the plain
        functions': Spark's."""
        n_keys, n_part = len(group_exprs), self.df.num_partitions
        plain = [(a, nm) for a, nm in zip(agg_fns, agg_names)
                 if not self._dedupes(a)]
        plain_fns = [a for a, _ in plain]
        plain_names = [nm for _, nm in plain]
        pair_names = group_names + [self._DISTINCT_ARG]
        pair_refs = [ir.ColumnRef(i) for i in range(n_keys + 1)]
        merged = [ir.AggFunction(a.fn) for a in plain_fns]

        node = self._agg_node(self.df.plan, list(group_exprs) + [arg],
                              plain_fns, "partial", pair_names, plain_names)
        if n_part > 1:
            node = self._exchange(node, n_keys + 1, n_part)
        node = self._agg_node(node, pair_refs, merged, "partial_merge",
                              pair_names, plain_names)
        # the third aggregate keeps the caller's order of functions: the
        # merging ones find their states at the end of their child's
        # output in that order whatever stands between them
        third = [ir.AggFunction(a.fn, ir.ColumnRef(n_keys), mode="partial")
                 if self._dedupes(a) else ir.AggFunction(a.fn)
                 for a in agg_fns]
        node = self._agg_node(node, pair_refs[:n_keys], third,
                              "partial_merge", group_names, agg_names)
        if n_part > 1:
            node = self._exchange(node, n_keys, n_part)
        return self._agg_node(node, pair_refs[:n_keys],
                              [ir.AggFunction(a.fn) for a in agg_fns],
                              "final", group_names, agg_names)

    def agg(self, *aggs: AggCol) -> "DataFrame":
        schema = self.df.schema
        group_exprs = [resolve(k, schema) for k in self.keys]
        group_names = [k.out_name(f"k{i}") for i, k in enumerate(self.keys)]
        agg_fns = [ir.AggFunction(
            a.fn, resolve(a.arg, schema) if a.arg is not None else None,
            a.distinct) for a in aggs]
        agg_names = [a.out_name(i) for i, a in enumerate(aggs)]
        n_keys = len(group_exprs)
        n_part = self.df.num_partitions

        out_partitions = n_part
        out_prov = self.df.partitioning
        if n_part > 1:
            if n_keys > 0:
                out_prov = ("hash", tuple(group_names), n_part)
            else:
                out_partitions = 1
                out_prov = ("single",)
        distinct_args = {a.arg for a in agg_fns if self._dedupes(a)}
        if len(distinct_args) > 1:
            raise NotImplementedError(
                "DISTINCT aggregates in one agg() call must share one "
                "argument expression (Spark plans differing arguments "
                "through Expand); split them into separate agg()s.")
        if distinct_args:
            node = self._plan_one_distinct(group_exprs, group_names, agg_fns,
                                           agg_names, distinct_args.pop())
        elif n_part > 1:
            # Spark-shaped two-phase plan: partial agg on every map
            # partition → exchange → final agg (the reference converts
            # HashAggregateExec pairs the same way,
            # AuronConverters.scala convertHashAggregateExec). Keyed aggs
            # hash-exchange on the group keys; a GLOBAL agg (no keys)
            # coalesces every partial row into one partition — without
            # that, each partition would emit its own "global" row.
            partial = self._agg_node(self.df.plan, group_exprs, agg_fns,
                                     "partial", group_names, agg_names)
            node = self._agg_node(
                self._exchange(partial, n_keys, n_part),
                [ir.ColumnRef(i) for i in range(n_keys)],
                [ir.AggFunction(a.fn, None, a.distinct) for a in agg_fns],
                "final", group_names, agg_names)
        else:
            node = self._agg_node(self.df.plan, group_exprs, agg_fns,
                                  "complete", group_names, agg_names)

        # schema via a throwaway op build is overkill; compute directly
        key_fields = []
        for e, nm in zip(group_exprs, group_names):
            dt, p, s = infer_dtype(e, schema)
            key_fields.append(Field(nm, dt, True, p, s))
        out_fields = list(key_fields)
        from auron_tpu.ops.agg import make_acc_spec
        for a, nm in zip(agg_fns, agg_names):
            # a DISTINCT function's type is the plain function's
            spec = make_acc_spec(ir.AggFunction(a.fn, a.arg), schema,
                                 "complete")
            out_fields.append(Field(nm, spec.result[0], True,
                                    spec.result[1], spec.result[2],
                                    elem=spec.elem))
        return DataFrame(self.df.session, node, Schema(tuple(out_fields)),
                         out_partitions, out_prov)


class DataFrame:
    def __init__(self, session, plan: pb.PlanNode, schema: Schema,
                 num_partitions: int = 1, partitioning=None):
        self.session = session
        self.plan = plan
        self.schema = schema
        self.num_partitions = num_partitions
        #: output-partitioning provenance, the EnsureRequirements signal:
        #: ("hash", (key names...), n) after repartition-by-key, ("single",)
        #: after a coalescing exchange, else None (unknown layout). Joins
        #: use it to recognize co-partitioned inputs; anything else gets a
        #: broadcast build side.
        self.partitioning = partitioning

    # -- transforms ---------------------------------------------------------

    def filter(self, cond: Col) -> "DataFrame":
        e = resolve(cond, self.schema)
        node = pb.PlanNode(filter=pb.FilterNode(
            child=self.plan, predicates=[serde.expr_to_proto(e)]))
        # row-preserving: the partition layout survives a filter
        return DataFrame(self.session, node, self.schema,
                         self.num_partitions, self.partitioning)

    where = filter

    def _project_provenance(self, exprs, names):
        """Hash-partitioning provenance survives a projection when every
        key column passes through untransformed — possibly renamed, in
        which case the provenance carries the NEW name (the data layout
        is unchanged; only the label moved)."""
        prov = self.partitioning
        if not prov or prov[0] != "hash":
            return None
        renames = {}
        for e, nm in zip(exprs, names):
            if isinstance(e, ir.ColumnRef):
                renames.setdefault(self.schema[e.index].name, nm)
        try:
            new_keys = tuple(renames[k] for k in prov[1])
        except KeyError:
            return None
        return ("hash", new_keys, prov[2])

    def select(self, *cols: Union[str, Col]) -> "DataFrame":
        cs = [col(c) if isinstance(c, str) else c for c in cols]
        exprs = [resolve(c, self.schema) for c in cs]
        names = [c.out_name(f"c{i}") for i, c in enumerate(cs)]
        node = pb.PlanNode(project=pb.ProjectNode(
            child=self.plan, exprs=[serde.expr_to_proto(e) for e in exprs],
            names=names))
        fields = []
        for e, nm in zip(exprs, names):
            dt, p, s = infer_dtype(e, self.schema)
            fields.append(Field(nm, dt, True, p, s))
        return DataFrame(self.session, node, Schema(tuple(fields)),
                         self.num_partitions,
                         self._project_provenance(exprs, names))

    def with_column(self, name: str, c: Col) -> "DataFrame":
        existing = [col(f.name) for f in self.schema]
        return self.select(*existing, c.alias(name))

    def expand(self, projections: Sequence[Sequence[Col]],
               names: Sequence[str]) -> "DataFrame":
        """Emit every projection once per input row (Spark ExpandExec —
        the engine of rollup/cube/grouping sets; reference:
        datafusion-ext-plans/src/expand_exec.rs). The FIRST projection
        determines the output types, so put the most-typed one first."""
        schema = self.schema
        projs = [[resolve(_wrap(c), schema) for c in p]
                 for p in projections]
        node = pb.PlanNode(expand=pb.ExpandNode(
            child=self.plan,
            projections=[pb.ExpandNode.Projection(
                exprs=[serde.expr_to_proto(e) for e in p])
                for p in projs],
            names=list(names)))
        fields = []
        for e, nm in zip(projs[0], names):
            dt, p, s = infer_dtype(e, schema)
            fields.append(Field(nm, dt, True, p, s))
        return DataFrame(self.session, node, Schema(tuple(fields)),
                         self.num_partitions, None)

    def grouping_sets(self, keys: Sequence[Union[str, Col]],
                      sets: Sequence[Sequence[int]]) -> "GroupedData":
        """GROUP BY GROUPING SETS: expand one copy of the input per set,
        null-filling grouped-out keys, and tag ``spark_grouping_id``
        (bit i set = key i rolled up, leftmost key = highest bit — Spark's
        encoding). The grouping id participates in the group keys so a
        natural NULL key stays distinct from a rolled-up one."""
        kcols = [col(k) if isinstance(k, str) else k for k in keys]
        schema = self.schema
        key_names = [k.out_name(f"k{i}") for i, k in enumerate(kcols)]
        n = len(kcols)
        pass_names = list(schema.names)
        out_names = pass_names + [f"{kn}#g" for kn in key_names] \
            + ["spark_grouping_id"]
        null_keys = []
        for k in kcols:
            dt, p, s = infer_dtype(resolve(k, schema), schema)
            null_keys.append(Col(ir.Literal(None, dt, p, s)))
        projections = []
        for st in sets:
            inc = set(st)
            gid = sum(1 << (n - 1 - i) for i in range(n) if i not in inc)
            projections.append(
                [col(c) for c in pass_names]
                + [kcols[i] if i in inc else null_keys[i]
                   for i in range(n)]
                + [lit(gid, DataType.INT32)])
        # the full set must come first: it types the expanded columns
        projections.sort(key=lambda p: sum(
            1 for c in p if isinstance(c.node, ir.Literal)
            and c.node.value is None))
        expanded = self.expand(projections, out_names)
        gkeys = [col(f"{kn}#g").alias(kn) for kn in key_names] \
            + [col("spark_grouping_id")]
        return GroupedData(expanded, gkeys)

    def rollup(self, *keys: Union[str, Col]) -> "GroupedData":
        """GROUP BY ROLLUP(k1..kn): the n+1 prefix grouping sets."""
        n = len(keys)
        return self.grouping_sets(
            keys, [list(range(i)) for i in range(n, -1, -1)])

    def cube(self, *keys: Union[str, Col]) -> "GroupedData":
        """GROUP BY CUBE(k1..kn): all 2^n grouping sets."""
        import itertools
        n = len(keys)
        sets = []
        for r in range(n, -1, -1):
            sets.extend(list(c) for c in
                        itertools.combinations(range(n), r))
        return self.grouping_sets(keys, sets)

    def group_by(self, *keys: Union[str, Col]) -> GroupedData:
        ks = [col(k) if isinstance(k, str) else k for k in keys]
        return GroupedData(self, ks)

    def _to_sort_orders(self, orders) -> list[ir.SortOrder]:
        """str/Col/SortCol → resolved ir.SortOrder (shared by sort and
        window)."""
        sos = []
        for o in orders:
            if isinstance(o, str):
                o = col(o).asc()
            elif isinstance(o, Col):
                o = o.asc()
            sos.append(ir.SortOrder(resolve(o.col, self.schema),
                                    o.ascending, o.nulls_first))
        return sos

    def window(self, funcs: list, partition_by=(), order_by=(),
               group_limit: Optional[int] = None) -> "DataFrame":
        """Append window-function columns (WindowNode → ops/window.py).
        Multi-partition frames hash-exchange on the partition keys first
        (Spark's required child distribution for window execs); an empty
        partition_by coalesces to a single partition."""
        if group_limit is not None and group_limit < 1:
            raise ValueError(f"group_limit must be >= 1, got {group_limit}")
        pbs = [col(k) if isinstance(k, str) else k for k in partition_by]
        sos = self._to_sort_orders(order_by)
        pb_exprs = [resolve(c, self.schema) for c in pbs]
        child = self.plan
        out_partitions = self.num_partitions
        prov = None
        if self.num_partitions > 1:
            if pb_exprs:
                part = pb.PartitioningP(
                    kind="hash", num_partitions=self.num_partitions,
                    hash_keys=[serde.expr_to_proto(e) for e in pb_exprs])
                prov = ("hash", tuple(c.out_name() for c in pbs),
                        self.num_partitions)
            else:
                part = pb.PartitioningP(kind="single", num_partitions=1)
                out_partitions = 1
                prov = ("single",)
            child = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
                child=child, partitioning=part,
                input_partitions=self.num_partitions))
        # ONE spec build; protos and schema both derive from it (keeps the
        # spec's own validation ahead of wire construction)
        from auron_tpu.ops.window import WindowFunctionSpec, _result_field
        names = [f.out_name(i) for i, f in enumerate(funcs)]
        specs = []
        for f in funcs:
            default = None
            if f.default is not None:
                lit_ir = resolve(_wrap(f.default), self.schema)
                if not isinstance(lit_ir, ir.Literal):
                    raise TypeError(
                        f"{f.fn} default must be a literal, got "
                        f"{type(lit_ir).__name__}")
                default = lit_ir
            specs.append((WindowFunctionSpec(
                kind=f.kind, fn=f.fn,
                arg=resolve(f.arg, self.schema) if f.arg is not None
                else None, offset=f.offset,
                default=None if default is None else default.value,
                frame=getattr(f, "frame", None)),
                default))
        fprotos = []
        for (spec, default) in specs:
            wp = pb.WindowFunctionP(kind=spec.kind, fn=spec.fn)
            if spec.arg is not None:
                wp.arg.CopyFrom(serde.expr_to_proto(spec.arg))
            wp.offset = spec.offset
            if default is not None:
                wp.default_value.CopyFrom(
                    serde.expr_to_proto(default).literal)
            if spec.frame is not None:
                wp.frame_lo, wp.frame_hi = spec.frame
            fprotos.append(wp)
        node = pb.PlanNode(window=pb.WindowNode(
            child=child,
            partition_by=[serde.expr_to_proto(e) for e in pb_exprs],
            order_by=[serde.sort_order_to_proto(s) for s in sos],
            functions=fprotos, output_names=names,
            group_limit=-1 if group_limit is None else group_limit))
        extra = [_result_field(spec, nm, self.schema)
                 for (spec, _d), nm in zip(specs, names)]
        out_schema = Schema(tuple(self.schema.fields) + tuple(extra))
        return DataFrame(self.session, node, out_schema, out_partitions,
                         prov)

    def sort(self, *orders: Union[str, Col, SortCol],
             limit: Optional[int] = None) -> "DataFrame":
        sos = self._to_sort_orders(orders)
        so_protos = [serde.sort_order_to_proto(s) for s in sos]
        child = self.plan
        out_partitions = self.num_partitions
        prov = None
        if self.num_partitions > 1:
            # a per-partition sort is not a global sort: top-k runs a
            # MAP-SIDE SortNode(fetch=k) per partition so only
            # n_part * k rows cross the coalescing exchange, then the
            # final top-k; a full sort range-exchanges so per-partition
            # runs concatenate globally ordered (the Spark global-sort /
            # TakeOrdered shape, reference: shuffle/mod.rs:204-279)
            if limit is not None:
                child = pb.PlanNode(sort=pb.SortNode(
                    child=child, sort_orders=so_protos, fetch=limit))
                part = pb.PartitioningP(kind="single", num_partitions=1)
                out_partitions = 1
                prov = ("single",)
            else:
                part = pb.PartitioningP(kind="range",
                                        num_partitions=self.num_partitions,
                                        range_orders=so_protos)
            child = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
                child=child, partitioning=part,
                input_partitions=self.num_partitions))
        node = pb.PlanNode(sort=pb.SortNode(
            child=child, sort_orders=so_protos,
            fetch=-1 if limit is None else limit))
        return DataFrame(self.session, node, self.schema,
                         out_partitions, prov)

    order_by = sort

    def limit(self, n: int) -> "DataFrame":
        child = self.plan
        out_partitions = self.num_partitions
        prov = self.partitioning
        if self.num_partitions > 1:
            # LIMIT is global: a map-side LocalLimit caps each partition
            # at n rows so at most n_part * n rows cross the coalescing
            # exchange, then the global limit truncates (the Spark
            # LocalLimit/GlobalLimit pair)
            child = pb.PlanNode(limit=pb.LimitNode(child=child, limit=n))
            child = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
                child=child,
                partitioning=pb.PartitioningP(kind="single",
                                              num_partitions=1),
                input_partitions=self.num_partitions))
            out_partitions = 1
            prov = ("single",)
        node = pb.PlanNode(limit=pb.LimitNode(child=child, limit=n))
        return DataFrame(self.session, node, self.schema,
                         out_partitions, prov)

    def union(self, other: "DataFrame") -> "DataFrame":
        if other.num_partitions != self.num_partitions:
            raise ValueError(
                "union requires equal partition counts "
                f"({self.num_partitions} vs {other.num_partitions}); "
                "repartition one side first")
        node = pb.PlanNode(union=pb.UnionNode(
            children=[self.plan, other.plan]))
        return DataFrame(self.session, node, self.schema,
                         self.num_partitions)

    def _co_partitioned_with(self, other: "DataFrame", keys: list) -> bool:
        """True when both sides are laid out so probe partition p only
        needs build partition p: both single-partition, or both
        hash-partitioned on exactly the join keys with equal counts."""
        if self.num_partitions == 1 and other.num_partitions == 1:
            return True
        a, b = self.partitioning, other.partitioning
        return (a is not None and b is not None
                and a[0] == "hash" and b[0] == "hash"
                and a[1] == b[1] == tuple(keys)
                and a[2] == b[2] == self.num_partitions
                == other.num_partitions)

    def _broadcast_plan(self) -> pb.PlanNode:
        """This frame collected once and replayed to every partition of
        a join's probe side."""
        return pb.PlanNode(broadcast_exchange=pb.BroadcastExchangeNode(
            child=self.plan, input_partitions=self.num_partitions))

    def join(self, other: "DataFrame", on: Union[str, Sequence[str]],
             how: str = "inner") -> "DataFrame":
        keys = [on] if isinstance(on, str) else list(on)
        pk = [serde.expr_to_proto(resolve(col(k), self.schema))
              for k in keys]
        bk = [serde.expr_to_proto(resolve(col(k), other.schema))
              for k in keys]
        build_plan = other.plan
        if not self._co_partitioned_with(other, keys):
            # sides are not provably co-partitioned: collect the build
            # side once and replay it to every probe partition (broadcast
            # join, reference: NativeBroadcastExchangeBase / SURVEY §3.4)
            # — without this, probe partition p silently only sees build
            # partition p
            build_plan = other._broadcast_plan()
        node = pb.PlanNode(hash_join=pb.HashJoinNode(
            probe=self.plan, build=build_plan, probe_keys=pk,
            build_keys=bk, join_type=how))
        if how in ("semi", "anti"):
            return DataFrame(self.session, node, self.schema,
                             self.num_partitions, self.partitioning)
        if how == "existence":
            out = Schema(tuple(self.schema.fields)
                         + (Field("exists", DataType.BOOL, False),))
            return DataFrame(self.session, node, out, self.num_partitions,
                             self.partitioning)
        # USING-style join: the build side's key columns are dropped
        # (Spark/SQL `JOIN ... USING` semantics)
        raw = Schema(tuple(self.schema.fields)
                     + tuple(other.schema.fields))
        p = len(self.schema)
        keep = list(range(p)) + [
            p + i for i, f in enumerate(other.schema)
            if f.name not in keys]
        joined = DataFrame(self.session, node, raw, self.num_partitions,
                           self.partitioning)
        return joined.select(*[Col(ir.ColumnRef(i, raw[i].name),
                                   raw[i].name) for i in keep])

    def cross_join(self, other: "DataFrame", how: str = "inner",
                   condition: Optional[Col] = None) -> "DataFrame":
        """Every row beside every row of ``other`` (SQL's comma join with
        no condition; Spark's BroadcastNestedLoopJoin BuildRight Inner).
        ``other`` is the build side: collected whole, and replayed to
        every partition of this side where either has more than one. An
        outer type or a condition — what Spark's nested-loop join also
        runs — is refused here, at plan time."""
        if how != "inner" or condition is not None:
            raise NotImplementedError(
                f"cross_join(how={how!r}, condition="
                f"{'given' if condition is not None else None}): only the "
                "inner join without a condition; join on keys and filter")
        both_single = self.num_partitions == 1 and other.num_partitions == 1
        node = pb.PlanNode(cross_join=pb.CrossJoinNode(
            probe=self.plan,
            build=other.plan if both_single else other._broadcast_plan()))
        return DataFrame(self.session, node,
                         Schema(tuple(self.schema.fields)
                                + tuple(other.schema.fields)),
                         self.num_partitions, self.partitioning)

    def explode(self, c: Union[str, Col], outer: bool = False,
                keep: Optional[Sequence[str]] = None) -> "DataFrame":
        cc = col(c) if isinstance(c, str) else c
        gen = resolve(cc, self.schema)
        keep_idx = ([self.schema.index_of(k) for k in keep]
                    if keep is not None else list(range(len(self.schema))))
        node = pb.PlanNode(generate=pb.GenerateNode(
            child=self.plan, kind="explode",
            generator=serde.expr_to_proto(gen),
            required_child_output=keep_idx, outer=outer))
        elem = (self.schema[gen.index].elem
                if isinstance(gen, ir.ColumnRef) else DataType.INT64)
        fields = tuple(self.schema[i] for i in keep_idx) + (
            Field("col", elem, True),)
        return DataFrame(self.session, node, Schema(fields),
                         self.num_partitions)

    def repartition(self, n: int,
                    *keys: Union[str, Col]) -> "DataFrame":
        if keys:
            ks = [col(k) if isinstance(k, str) else k for k in keys]
            part = pb.PartitioningP(
                kind="hash", num_partitions=n,
                hash_keys=[serde.expr_to_proto(resolve(k, self.schema))
                           for k in ks])
            prov = ("hash", tuple(k.out_name() for k in ks), n)
        else:
            part = pb.PartitioningP(kind="round_robin", num_partitions=n)
            prov = ("single",) if n == 1 else None
        node = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
            child=self.plan, partitioning=part,
            input_partitions=self.num_partitions))
        return DataFrame(self.session, node, self.schema, n, prov)

    def map_batches(self, fn: Callable[[pa.RecordBatch], pa.RecordBatch],
                    schema: Optional[Schema] = None) -> "DataFrame":
        """Host-fallback boundary: run an arbitrary Arrow-batch function on
        the host (the ConvertToNative / C2R transition of the reference)."""
        rid = self.session._register_host_fn(fn, self)
        node = pb.PlanNode(memory_scan=pb.MemoryScanNode(table_name=rid))
        return DataFrame(self.session, node, schema or self.schema,
                         self.num_partitions)

    # -- actions ------------------------------------------------------------

    def task_bytes(self, partition_id: int = 0) -> bytes:
        return pb.TaskDefinition(
            partition_id=partition_id, num_partitions=self.num_partitions,
            plan=self.plan).SerializeToString()

    def collect(self, timeout_s: Optional[float] = None) -> pa.Table:
        """Execute and materialize. ``timeout_s`` arms a per-query
        deadline: past it, every cooperative poll site unwinds with the
        classified ``errors.DeadlineExceeded`` and the query's resources
        (spill files, shuffle buffers, memmgr consumers) are released —
        the same token mechanism ``session.cancel(query_id)`` and the
        serving CANCEL frame flip."""
        return self.session.execute(self, timeout_s=timeout_s)

    def to_pandas(self):
        return self.collect().to_pandas()

    def explain(self, analyze: bool = False) -> str:
        """The plan tree; ``analyze=True`` EXECUTES the plan and
        annotates every node with its mirrored metrics
        (elapsed_compute, output_rows, spill/shuffle counters — the
        EXPLAIN ANALYZE of obs/metric_tree.py)."""
        if analyze:
            return self.session.explain_analyze(self)
        op = self.session.plan_physical(self)
        return op.tree_string()
