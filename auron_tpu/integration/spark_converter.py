"""Spark physical plan → auron proto converter.

The engine-integration slice (L1): consumes a RECORDED Spark physical
plan in Spark's own ``toJSON`` tree encoding (see spark_plan.py) and
lowers it node-by-node to this engine's protobuf IR, the way the
reference's Scala extension converts live plans (reference:
AuronConverters.scala:209-310 per-class dispatch + tryConvert tagging;
NativeConverters.scala:95-1540 expression translation;
AuronConvertStrategy.scala:41-76 convertible/never-convert tags).

Strategy contract:
- every plan node gets a tag: ``convertible`` or a never-convert reason
  (``ConversionReport.tags``);
- an unconvertible node WITH declared output becomes an explicit fallback
  boundary — a MemoryScanNode on a well-known table name the embedding
  host must populate with that subtree's rows (the ConvertToNativeExec
  boundary of the reference, SURVEY §3.1); its subtree stays unconverted;
- an unconvertible node without declared output poisons its ancestors up
  to the nearest fallback-capable node.

Simplifications vs live Spark JSON (documented, fixture-facing): case
objects (join type, agg mode, build side) may appear either as Spark's
``{"object": "...Inner$"}`` or as plain strings; scan file lists come
from ``metadata.Location``'s ``InMemoryFileIndex[...]`` rendering.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from auron_tpu.integration.spark_plan import SparkNode, parse_plan
from auron_tpu.ir import pb

# ---------------------------------------------------------------------------
# dtype mapping
# ---------------------------------------------------------------------------

_DT = {
    "boolean": pb.DT_BOOL,
    "byte": pb.DT_INT8, "tinyint": pb.DT_INT8,
    "short": pb.DT_INT16, "smallint": pb.DT_INT16,
    "integer": pb.DT_INT32, "int": pb.DT_INT32,
    "long": pb.DT_INT64, "bigint": pb.DT_INT64,
    "float": pb.DT_FLOAT32, "real": pb.DT_FLOAT32,
    "double": pb.DT_FLOAT64,
    "string": pb.DT_STRING, "varchar": pb.DT_STRING,
    "date": pb.DT_DATE32,
    "timestamp": pb.DT_TIMESTAMP_US, "timestamp_ntz": pb.DT_TIMESTAMP_US,
}

_DECIMAL_RE = re.compile(r"decimal\((\d+),\s*(\d+)\)")


def _dtype_to_proto(s: str) -> tuple[int, int, int]:
    """spark dataType string → (DataTypeP, precision, scale)."""
    if s in _DT:
        return _DT[s], 0, 0
    m = _DECIMAL_RE.fullmatch(s)
    if m:
        return pb.DT_DECIMAL, int(m.group(1)), int(m.group(2))
    raise NotImplementedError(f"unsupported Spark dataType {s!r}")


def _object_name(v) -> str:
    """'Inner' from {"object": "...joins.Inner$"} or plain "Inner"."""
    if isinstance(v, dict):
        v = v.get("object", "")
    v = str(v)
    return v.rstrip("$").rsplit(".", 1)[-1]


# ---------------------------------------------------------------------------
# attributes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Attr:
    name: str
    expr_id: int
    dtype: str     # spark dataType string


def _expr_id(raw: dict) -> int:
    e = raw.get("exprId") or raw.get("resultId") or {}
    return int(e.get("id", -1))


def _attr_of(node: SparkNode) -> Attr:
    return Attr(node.fields.get("name", "?"), _expr_id(node.fields),
                node.fields.get("dataType", "long"))


def _parse_output(node: SparkNode) -> list[Attr]:
    return [_attr_of(t) for t in node.field_trees("output")]


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------

_BINARY = {
    "Add": "+", "Subtract": "-", "Multiply": "*", "Divide": "/",
    "Remainder": "%", "EqualTo": "==", "GreaterThan": ">",
    "LessThan": "<", "GreaterThanOrEqual": ">=", "LessThanOrEqual": "<=",
    "And": "and", "Or": "or",
}

_STRING_PRED = {"StartsWith": "starts_with", "EndsWith": "ends_with",
                "Contains": "contains"}

_SCALAR_FN = {"Upper": "upper", "Lower": "lower", "Length": "length",
              "Substring": "substring", "Concat": "concat",
              "Coalesce": "coalesce", "Abs": "abs",
              # round-3 surface expansion (exprs/fn_*.py)
              "ConcatWs": "concat_ws", "InitCap": "initcap",
              "StringRepeat": "repeat", "Reverse": "reverse",
              "StringLPad": "lpad", "StringRPad": "rpad",
              "StringInstr": "instr", "StringLocate": "locate",
              "SubstringIndex": "substring_index",
              "StringTranslate": "translate", "Ascii": "ascii", "Chr": "chr",
              "Year": "year", "Month": "month", "DayOfMonth": "day",
              "Quarter": "quarter", "DayOfWeek": "dayofweek",
              "DayOfYear": "dayofyear", "WeekOfYear": "weekofyear",
              "Hour": "hour", "Minute": "minute", "Second": "second",
              "DateAdd": "date_add", "DateSub": "date_sub",
              "DateDiff": "datediff", "DateFormatClass": "date_format",
              "FromUnixTime": "from_unixtime",
              "UnixTimestamp": "unix_timestamp",
              "ToUnixTimestamp": "to_unix_timestamp",
              "TruncDate": "trunc", "TruncTimestamp": "date_trunc",
              "AddMonths": "add_months", "LastDay": "last_day",
              "MonthsBetween": "months_between", "NextDay": "next_day",
              "MakeDate": "make_date",
              "Md5": "md5", "Sha1": "sha1", "Sha2": "sha2", "Crc32": "crc32",
              "Base64": "base64", "UnBase64": "unbase64",
              "Hex": "hex", "Unhex": "unhex",
              "GetJsonObject": "get_json_object",
              "RegExpExtract": "regexp_extract",
              "RegExpReplace": "regexp_replace", "RLike": "rlike",
              "CreateArray": "array", "ArrayContains": "array_contains",
              "ArrayPosition": "array_position", "ElementAt": "element_at",
              "Size": "size", "SortArray": "sort_array",
              "ArrayMax": "array_max", "ArrayMin": "array_min",
              "CreateMap": "map", "MapFromArrays": "map_from_arrays",
              "MapKeys": "map_keys", "MapValues": "map_values",
              "MapContainsKey": "map_contains_key",
              "MapConcat": "map_concat", "GetMapValue": "get_map_value",
              "CreateNamedStruct": "named_struct",
              "Round": "round", "BRound": "bround", "Pow": "pow",
              "Sqrt": "sqrt", "Exp": "exp", "Log": "log",
              "Floor": "floor", "Ceil": "ceil", "Greatest": "greatest",
              "Least": "least", "IsNaN": "isnan", "NaNvl": "nanvl",
              "NullIf": "nullif", "If": "if",
              "StringTrim": "trim", "StringTrimLeft": "ltrim",
              "StringTrimRight": "rtrim", "Murmur3Hash": "hash",
              "XxHash64": "xxhash64"}

_AGG_FN = {"Sum": "sum", "Min": "min", "Max": "max", "Average": "avg",
           "Count": "count", "First": "first",
           "CollectList": "collect_list", "CollectSet": "collect_set"}

#: per-function argument positions whose kernels require a static literal
#: (checked at conversion time so non-literal uses fall back cleanly)
_LITERAL_ARGS = {
    "repeat": (1,), "lpad": (1, 2), "rpad": (1, 2), "instr": (1,),
    "locate": (0,), "substring_index": (1, 2), "translate": (1, 2),
    "space": (0,), "sha2": (1,), "regexp_extract": (1, 2),
    "regexp_replace": (1, 2), "rlike": (1,), "get_json_object": (1,),
    "date_format": (1,), "from_unixtime": (1,), "unix_timestamp": (1,),
    "to_unix_timestamp": (1,), "trunc": (1,), "date_trunc": (0,),
    "next_day": (1,), "sort_array": (1,), "array_repeat": (1,),
}


class ExprConverter:
    def __init__(self, attrs: list[Attr], shims=None, plan_converter=None):
        from auron_tpu.integration.shims import SparkShims
        self.index_of = {a.expr_id: i for i, a in enumerate(attrs)}
        self.attrs = attrs
        self.shims = shims or SparkShims()
        # callback converting an embedded Spark plan (toJSON tree) into a
        # pb.PlanNode — used by ScalarSubquery expressions
        self.plan_converter = plan_converter

    def convert(self, e: SparkNode) -> pb.ExprNode:
        cls = e.simple_name
        # version shims: identity wrappers unwrap; overflow wrappers
        # (CheckOverflow around decimal arith) reduce to a decimal cast
        # whose non-ANSI path IS the null-on-overflow contract
        if self.shims.is_identity_expr(cls):
            return self.convert(e.children[0])
        if self.shims.is_overflow_wrapper(cls):
            if e.fields.get("nullOnOverflow") is False:
                # ANSI mode: Spark RAISES on overflow; the engine's cast
                # nulls — fall back rather than silently diverge
                raise NotImplementedError(
                    "CheckOverflow with nullOnOverflow=false (ANSI)")
            dt, p, s = _dtype_to_proto(str(e.fields.get("dataType", "")))
            return pb.ExprNode(cast=pb.CastE(
                child=self.convert(e.children[0]), dtype=dt, precision=p,
                scale=s))
        if cls == "AttributeReference":
            eid = _expr_id(e.fields)
            if eid not in self.index_of:
                raise NotImplementedError(
                    f"attribute {e.fields.get('name')}#{eid} not found in "
                    f"child output")
            return pb.ExprNode(column=pb.ColumnRefE(
                index=self.index_of[eid],
                name=e.fields.get("name", "")))
        if cls == "Literal":
            return self._literal(e)
        if cls == "Alias":
            return self.convert(e.children[0])
        if cls in _BINARY:
            return pb.ExprNode(binary=pb.BinaryE(
                op=_BINARY[cls], left=self.convert(e.children[0]),
                right=self.convert(e.children[1])))
        if cls == "Not":
            return pb.ExprNode(unary=pb.UnaryE(
                op="not", child=self.convert(e.children[0])))
        if cls == "IsNull":
            return pb.ExprNode(unary=pb.UnaryE(
                op="is_null", child=self.convert(e.children[0])))
        if cls == "IsNotNull":
            return pb.ExprNode(unary=pb.UnaryE(
                op="is_not_null", child=self.convert(e.children[0])))
        if cls in ("Cast", "AnsiCast", "TryCast"):
            dt, p, s = _dtype_to_proto(e.fields["dataType"])
            return pb.ExprNode(cast=pb.CastE(
                child=self.convert(e.children[0]), dtype=dt, precision=p,
                scale=s, try_cast=(cls == "TryCast"),
                ansi=(cls == "AnsiCast")))
        if cls == "In":
            child, *vals = e.children
            lits = []
            for v in vals:
                if v.simple_name != "Literal":
                    raise NotImplementedError("non-literal IN list")
                lits.append(self._literal(v).literal)
            return pb.ExprNode(in_list=pb.InListE(
                child=self.convert(child), values=lits))
        if cls in _STRING_PRED:
            return pb.ExprNode(string_pred=pb.StringPredE(
                kind=_STRING_PRED[cls], child=self.convert(e.children[0]),
                pattern=str(e.children[1].fields.get("value", ""))))
        if cls == "Like":
            return pb.ExprNode(like=pb.LikeE(
                child=self.convert(e.children[0]),
                pattern=str(e.children[1].fields.get("value", ""))))
        if cls in _SCALAR_FN:
            fn = _SCALAR_FN[cls]
            # functions whose kernels need a static (literal) argument must
            # reject non-literal args HERE, at conversion time, so the
            # subtree falls back to the host engine instead of failing the
            # task at kernel-build time
            for idx in _LITERAL_ARGS.get(fn, ()):
                if idx < len(e.children) \
                        and e.children[idx].simple_name != "Literal":
                    raise NotImplementedError(
                        f"{fn}: argument {idx} must be a literal")
            return pb.ExprNode(scalar_function=pb.ScalarFunctionE(
                name=fn,
                args=[self.convert(c) for c in e.children]))
        if cls == "GetStructField":
            # Spark carries the child ordinal as a field, not an argument
            return pb.ExprNode(get_struct_field=pb.GetStructFieldE(
                child=self.convert(e.children[0]),
                ordinal=int(e.fields.get("ordinal", 0))))
        if cls == "ScalarSubquery":
            # uncorrelated scalar subquery: Spark embeds the subquery's
            # physical plan; it executes once and acts as a constant
            # (reference: spark_scalar_subquery_wrapper.rs)
            sub = e.fields.get("plan")
            if sub is None or self.plan_converter is None:
                raise NotImplementedError(
                    "ScalarSubquery without an embedded plan")
            node = self.plan_converter(sub)
            dt, p, s = _dtype_to_proto(str(e.fields.get("dataType", "")))
            sid = 0
            eid = e.fields.get("exprId")
            if isinstance(eid, dict):
                sid = int(eid.get("id", 0))
            return pb.ExprNode(scalar_subquery=pb.ScalarSubqueryE(
                plan=node, dtype=dt, precision=p, scale=s, sid=sid))
        raise NotImplementedError(f"unsupported Spark expression {cls}")

    def _literal(self, e: SparkNode) -> pb.ExprNode:
        dt_s = e.fields.get("dataType", "null")
        raw = e.fields.get("value")
        if raw is None or dt_s == "null":
            dt, p, s = (pb.DT_NULL, 0, 0) if dt_s == "null" \
                else _dtype_to_proto(dt_s)
            return pb.ExprNode(literal=pb.LiteralE(dtype=dt, is_null=True,
                                                   precision=p, scale=s))
        dt, p, s = _dtype_to_proto(dt_s)
        lit = pb.LiteralE(dtype=dt, precision=p, scale=s)
        try:
            if dt in (pb.DT_FLOAT32, pb.DT_FLOAT64):
                lit.f64 = float(raw)
            elif dt == pb.DT_STRING:
                lit.str = str(raw)
            elif dt == pb.DT_BOOL:
                lit.i64 = 1 if str(raw).lower() == "true" else 0
            elif dt == pb.DT_DECIMAL:
                # decimals travel as the scaled unscaled integer
                from decimal import Decimal
                lit.i64 = int(Decimal(str(raw)).scaleb(s))
            else:
                lit.i64 = int(raw)
        except (ValueError, ArithmeticError) as e:
            # surface as never-convert, not a crash of the whole plan
            raise NotImplementedError(
                f"unparseable {dt_s} literal {raw!r}: {e}") from e
        return pb.ExprNode(literal=lit)

    def sort_order(self, e: SparkNode) -> pb.SortOrderP:
        assert e.simple_name == "SortOrder", e.cls
        direction = _object_name(e.fields.get("direction", "Ascending"))
        null_ord = _object_name(e.fields.get("nullOrdering", ""))
        asc = direction == "Ascending"
        nulls_first = (null_ord == "NullsFirst") if null_ord \
            else asc  # spark default: nulls first iff ascending
        return pb.SortOrderP(expr=self.convert(e.children[0]),
                             ascending=asc, nulls_first=nulls_first)


# ---------------------------------------------------------------------------
# plan conversion
# ---------------------------------------------------------------------------

@dataclass
class ConversionReport:
    """convertible / never-convert tagging + fallback boundaries — the
    record the reference keeps in plan tags (convertibleTag,
    neverConvertReasonTag, AuronConvertStrategy.scala:41-47)."""
    tags: list = field(default_factory=list)        # (cls, ok, reason)
    boundaries: list = field(default_factory=list)  # (table, cls, [Attr])

    def tag(self, node: SparkNode, ok: bool, reason: str = ""):
        self.tags.append((node.simple_name, ok, reason))

    @property
    def never_converted(self):
        return [(c, r) for c, ok, r in self.tags if not ok]

    def summary(self) -> str:
        lines = []
        for cls, ok, reason in self.tags:
            lines.append(f"  [{'native' if ok else 'FALLBACK'}] {cls}"
                         + (f" — {reason}" if reason else ""))
        return "\n".join(lines)


@dataclass
class _Converted:
    node: pb.PlanNode
    attrs: list          # output Attrs
    partitions: int = 1  # partition count flowing to parents


_TRANSPARENT = ("WholeStageCodegenExec", "InputAdapter",
                "AQEShuffleReadExec", "CustomShuffleReaderExec",
                "AdaptiveSparkPlanExec", "QueryStageExec",
                "ShuffleQueryStageExec", "BroadcastQueryStageExec")


class SparkPlanConverter:
    """One-shot converter for a recorded plan. ``path_rewrite`` maps the
    recorded file paths into the local filesystem (fixtures record the
    original cluster paths). ``spark_version`` selects the version shims
    (integration/shims.py — the @sparkver seam analogue)."""

    def __init__(self, path_rewrite=None, spark_version: str = "3.5.0"):
        from auron_tpu.integration.shims import SparkShims
        self.path_rewrite = path_rewrite or (lambda p: p)
        self.report = ConversionReport()
        self.shims = SparkShims(spark_version)
        self._fallback_ids = 0

    # -- public entry -------------------------------------------------------

    def convert(self, plan) -> tuple[pb.PlanNode, ConversionReport]:
        root = plan if isinstance(plan, SparkNode) else parse_plan(plan)
        conv = self._convert(root)
        return conv.node, self.report

    def task_bytes(self, plan, partition_id: int = 0) -> bytes:
        node, _ = self.convert(plan)
        return pb.TaskDefinition(plan=node,
                                 partition_id=partition_id).SerializeToString()

    def _convert_subplan(self, plan) -> pb.PlanNode:
        """Convert a plan embedded inside an expression (ScalarSubquery).
        Runs a FRESH converter sharing rewrite/shims: the subquery's
        tags/fallbacks must not pollute this plan's report, and an
        unconvertible subquery falls back as a whole via the raised
        NotImplementedError."""
        sub = SparkPlanConverter(path_rewrite=self.path_rewrite,
                                 spark_version=self.shims.version_str)
        node, report = sub.convert(plan)
        if report.never_converted:
            raise NotImplementedError(
                "unconvertible subquery plan: "
                + "; ".join(r for _c, r in report.never_converted))
        return node

    # -- dispatch with tagging ---------------------------------------------

    def _convert(self, node: SparkNode) -> _Converted:
        cls = node.simple_name
        if cls in _TRANSPARENT or self.shims.is_transparent_plan(cls):
            return self._convert(node.children[0])
        handler = getattr(self, f"_c_{cls}", None)
        try:
            if handler is None:
                raise NotImplementedError(f"no converter for {cls}")
            out = handler(node)
            self.report.tag(node, True)
            return out
        except NotImplementedError as e:
            return self._fallback(node, str(e))

    def _fallback(self, node: SparkNode, reason: str) -> _Converted:
        """ConvertToNative boundary: the host engine executes this subtree
        and feeds rows in via a well-known catalog table."""
        self.report.tag(node, False, reason)
        attrs = _parse_output(node)
        if not attrs:
            raise NotImplementedError(
                f"{node.simple_name} unconvertible ({reason}) and declares "
                "no output to fall back on")
        self._fallback_ids += 1
        table = f"__spark_fallback_{self._fallback_ids}"
        self.report.boundaries.append((table, node.simple_name, attrs))
        return _Converted(
            pb.PlanNode(memory_scan=pb.MemoryScanNode(table_name=table)),
            attrs)

    # -- leaves -------------------------------------------------------------

    _LOCATION_RE = re.compile(r"\[(.*)\]")

    def _scan_files(self, node: SparkNode) -> list[str]:
        meta = node.fields.get("metadata") or {}
        loc = meta.get("Location", "")
        m = self._LOCATION_RE.search(loc)
        if not m:
            raise NotImplementedError(
                f"scan without parseable Location: {loc!r}")
        files = [f.strip() for f in m.group(1).split(",") if f.strip()]
        return [self.path_rewrite(f.replace("file:", "")) for f in files]

    def _c_FileSourceScanExec(self, node: SparkNode) -> _Converted:
        attrs = _parse_output(node)
        meta = node.fields.get("metadata") or {}
        fmt = str(meta.get("Format", "Parquet")).lower()
        files = self._scan_files(node)
        fields = []
        for a in attrs:
            dt, p, s = _dtype_to_proto(a.dtype)
            fields.append(pb.FieldP(name=a.name, dtype=dt, nullable=True,
                                    precision=p, scale=s))
        schema = pb.SchemaP(fields=fields)
        if fmt == "parquet":
            n = pb.PlanNode(parquet_scan=pb.ParquetScanNode(
                files=files, schema=schema,
                columns=[a.name for a in attrs]))
        elif fmt == "orc":
            n = pb.PlanNode(orc_scan=pb.OrcScanNode(
                files=files, schema=schema,
                columns=[a.name for a in attrs]))
        else:
            raise NotImplementedError(f"scan format {fmt}")
        return _Converted(n, attrs, partitions=max(len(files), 1))

    def _c_BatchScanExec(self, node: SparkNode) -> _Converted:
        """DSv2 scans (Iceberg / Paimon / Hudi ride this node): delegate to
        the lakehouse convert-providers (integration/providers.py — the
        reference's ConvertProvider plugin seam, thirdparty/auron-iceberg
        etc.); unmatched scans fall back."""
        from auron_tpu.integration.providers import try_convert_scan
        attrs = _parse_output(node)
        got = try_convert_scan(node, attrs, _dtype_to_proto,
                               self.path_rewrite)
        if got is None:
            raise NotImplementedError(
                "BatchScanExec with no matching scan provider")
        n, partitions, provider = got
        self.report.tag(node, True, f"provider:{provider}")
        return _Converted(n, attrs, partitions=partitions)

    # -- unary row transforms ----------------------------------------------

    def _c_FilterExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        cond = node.field_tree("condition")
        n = pb.PlanNode(filter=pb.FilterNode(
            child=child.node, predicates=[ec.convert(cond)]))
        return _Converted(n, child.attrs, child.partitions)

    def _project(self, child: _Converted,
                 project_list: list) -> _Converted:
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        exprs, names, attrs = [], [], []
        for t in project_list:
            exprs.append(ec.convert(t))
            name = t.fields.get("name", "col")
            eid = _expr_id(t.fields)
            dtype = t.fields.get("dataType", "")
            if t.simple_name == "Alias" and not dtype:
                dtype = t.children[0].fields.get("dataType", "long")
            names.append(name)
            attrs.append(Attr(name, eid, dtype or "long"))
        n = pb.PlanNode(project=pb.ProjectNode(
            child=child.node, exprs=exprs, names=names))
        return _Converted(n, attrs, child.partitions)

    def _c_ProjectExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        return self._project(child, node.field_trees("projectList"))

    def _c_SortExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        orders = [ec.sort_order(t) for t in node.field_trees("sortOrder")]
        n = pb.PlanNode(sort=pb.SortNode(child=child.node,
                                         sort_orders=orders, fetch=-1))
        return _Converted(n, child.attrs, child.partitions)

    def _c_TakeOrderedAndProjectExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        orders = [ec.sort_order(t) for t in node.field_trees("sortOrder")]
        limit = int(node.fields.get("limit", -1))
        # global top-k: map-side SortNode(fetch=k) per partition so only
        # n_part * k rows cross the coalescing exchange
        plan = child.node
        if child.partitions > 1:
            plan = pb.PlanNode(sort=pb.SortNode(
                child=plan, sort_orders=orders, fetch=limit))
            plan = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
                child=plan,
                partitioning=pb.PartitioningP(kind="single",
                                              num_partitions=1),
                input_partitions=child.partitions))
        sort = pb.PlanNode(sort=pb.SortNode(child=plan, sort_orders=orders,
                                            fetch=limit))
        out = _Converted(sort, child.attrs, 1)
        plist = node.field_trees("projectList")
        if plist:
            return self._project(out, plist)
        return out

    def _c_LocalLimitExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        n = pb.PlanNode(limit=pb.LimitNode(
            child=child.node, limit=int(node.fields.get("limit", 0))))
        return _Converted(n, child.attrs, child.partitions)

    def _c_GlobalLimitExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        plan = child.node
        parts = child.partitions
        limit = int(node.fields.get("limit", 0))
        if parts > 1:
            # map-side LocalLimit caps each partition before the
            # coalescing exchange (the LocalLimit/GlobalLimit pair)
            plan = pb.PlanNode(limit=pb.LimitNode(child=plan, limit=limit))
            plan = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
                child=plan,
                partitioning=pb.PartitioningP(kind="single",
                                              num_partitions=1),
                input_partitions=parts))
            parts = 1
        n = pb.PlanNode(limit=pb.LimitNode(child=plan, limit=limit))
        return _Converted(n, child.attrs, parts)

    def _c_UnionExec(self, node: SparkNode) -> _Converted:
        kids = [self._convert(c) for c in node.children]
        n = pb.PlanNode(union=pb.UnionNode(children=[k.node for k in kids]))
        return _Converted(n, kids[0].attrs,
                          max(k.partitions for k in kids))

    # -- exchanges ----------------------------------------------------------

    def _partitioning(self, tree: SparkNode,
                      ec: ExprConverter) -> tuple[pb.PartitioningP, int]:
        # a case object renders with Scala's trailing "$"
        cls = tree.simple_name.rstrip("$")
        n_out = int(tree.fields.get("numPartitions", 1))
        if cls == "HashPartitioning":
            return pb.PartitioningP(
                kind="hash", num_partitions=n_out,
                hash_keys=[ec.convert(c) for c in tree.children]), n_out
        if cls == "SinglePartition":
            return pb.PartitioningP(kind="single", num_partitions=1), 1
        if cls == "RoundRobinPartitioning":
            return pb.PartitioningP(kind="round_robin",
                                    num_partitions=n_out), n_out
        if cls == "RangePartitioning":
            return pb.PartitioningP(
                kind="range", num_partitions=n_out,
                range_orders=[ec.sort_order(c)
                              for c in tree.children]), n_out
        raise NotImplementedError(f"partitioning {cls}")

    def _c_ShuffleExchangeExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        ptree = node.field_tree("outputPartitioning")
        part, n_out = self._partitioning(ptree, ec)
        n = pb.PlanNode(shuffle_writer=pb.ShuffleWriterNode(
            child=child.node, partitioning=part,
            input_partitions=child.partitions))
        return _Converted(n, child.attrs, n_out)

    def _c_BroadcastExchangeExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        n = pb.PlanNode(broadcast_exchange=pb.BroadcastExchangeNode(
            child=child.node, input_partitions=child.partitions))
        return _Converted(n, child.attrs, 1)

    # -- joins --------------------------------------------------------------

    _JOIN_TYPE = {"Inner": "inner", "LeftOuter": "left",
                  "RightOuter": "right", "FullOuter": "full",
                  "LeftSemi": "semi", "LeftAnti": "anti",
                  "ExistenceJoin": "existence", "Cross": "inner"}

    def _join_common(self, node: SparkNode):
        jt = _object_name(node.fields.get("joinType", "Inner"))
        # ExistenceJoin(exists#n) renders with a parameter
        jt = "ExistenceJoin" if jt.startswith("ExistenceJoin") else jt
        if jt not in self._JOIN_TYPE:
            raise NotImplementedError(f"join type {jt}")
        if node.fields.get("condition"):
            raise NotImplementedError("non-equi join condition")
        return self._JOIN_TYPE[jt]

    def _c_BroadcastHashJoinExec(self, node: SparkNode) -> _Converted:
        jt = self._join_common(node)
        side = _object_name(node.fields.get("buildSide", "BuildRight"))
        if side != "BuildRight":
            raise NotImplementedError("BuildLeft broadcast join")
        left = self._convert(node.children[0])
        right = self._convert(node.children[1])
        lec, rec = (ExprConverter(left.attrs, self.shims, self._convert_subplan),
                    ExprConverter(right.attrs, self.shims, self._convert_subplan))
        lk = [lec.convert(t) for t in node.field_trees("leftKeys")]
        rk = [rec.convert(t) for t in node.field_trees("rightKeys")]
        n = pb.PlanNode(hash_join=pb.HashJoinNode(
            probe=left.node, build=right.node, probe_keys=lk,
            build_keys=rk, join_type=jt))
        attrs = self._join_attrs(node, jt, left, right)
        return _Converted(n, attrs, left.partitions)

    _c_ShuffledHashJoinExec = _c_BroadcastHashJoinExec

    def _c_BroadcastNestedLoopJoinExec(self, node: SparkNode) -> _Converted:
        """The join without keys: Inner (or Cross), no condition, the
        right side built. Anything else is a fallback boundary."""
        jt = _object_name(node.fields.get("joinType", "Inner"))
        if jt not in ("Inner", "Cross"):
            raise NotImplementedError(f"nested-loop join type {jt}")
        if node.fields.get("condition"):
            raise NotImplementedError("nested-loop join condition")
        side = _object_name(node.fields.get("buildSide", "BuildRight"))
        if side != "BuildRight":
            raise NotImplementedError("BuildLeft nested-loop join")
        left = self._convert(node.children[0])
        right = self._convert(node.children[1])
        build = right.node
        if right.partitions > 1:
            # CartesianProductExec pairs every partition with every
            # partition; here each left partition meets the whole right
            build = pb.PlanNode(broadcast_exchange=pb.BroadcastExchangeNode(
                child=right.node, input_partitions=right.partitions))
        n = pb.PlanNode(cross_join=pb.CrossJoinNode(
            probe=left.node, build=build))
        return _Converted(n, list(left.attrs) + list(right.attrs),
                          left.partitions)

    _c_CartesianProductExec = _c_BroadcastNestedLoopJoinExec

    def _c_SortMergeJoinExec(self, node: SparkNode) -> _Converted:
        jt = self._join_common(node)
        left = self._convert(node.children[0])
        right = self._convert(node.children[1])
        lec, rec = (ExprConverter(left.attrs, self.shims, self._convert_subplan),
                    ExprConverter(right.attrs, self.shims, self._convert_subplan))
        lk = [lec.convert(t) for t in node.field_trees("leftKeys")]
        rk = [rec.convert(t) for t in node.field_trees("rightKeys")]
        n = pb.PlanNode(sort_merge_join=pb.SortMergeJoinNode(
            probe=left.node, build=right.node, probe_keys=lk,
            build_keys=rk, join_type=jt))
        attrs = self._join_attrs(node, jt, left, right)
        return _Converted(n, attrs, left.partitions)

    @staticmethod
    def _join_attrs(node, jt, left, right) -> list[Attr]:
        if jt in ("semi", "anti"):
            return list(left.attrs)
        if jt == "existence":
            declared = _parse_output(node)
            exists = declared[-1] if declared else Attr("exists", -1,
                                                        "boolean")
            return list(left.attrs) + [exists]
        return list(left.attrs) + list(right.attrs)

    # -- aggregation --------------------------------------------------------

    _AGG_MODE = {"Partial": "partial", "PartialMerge": "partial_merge",
                 "Final": "final", "Complete": "complete"}

    def _agg_parts(self, node: SparkNode):
        """(grouping trees, aggregate trees, the node's mode, each
        function's own). A node emits either states (Partial,
        PartialMerge) or results (Final, Complete); inside either a
        function may differ from its neighbours — the third aggregate of
        Spark's single-DISTINCT plan merges the plain functions and
        starts the DISTINCT ones over the deduplicated column."""
        groups = node.field_trees("groupingExpressions")
        agg_exprs = node.field_trees("aggregateExpressions")
        modes = []
        for a in agg_exprs:
            m = _object_name(a.fields.get("mode", "Complete"))
            if m not in self._AGG_MODE:
                raise NotImplementedError(f"aggregate mode {m}")
            modes.append(self._AGG_MODE[m])
        have = set(modes) or {"complete"}
        emits_state = have <= {"partial", "partial_merge"}
        if not emits_state and have & {"partial", "partial_merge"}:
            raise NotImplementedError(f"mixed agg modes {sorted(have)}")
        if emits_state:
            mode = "partial_merge" if "partial_merge" in have else "partial"
        else:
            mode = "final" if "final" in have else "complete"
        return groups, agg_exprs, mode, modes

    def _agg_fn(self, agg_expr: SparkNode) -> tuple[str, SparkNode, bool]:
        fn_tree = agg_expr.children[0]
        cls = fn_tree.simple_name
        if cls not in _AGG_FN:
            raise NotImplementedError(f"aggregate function {cls}")
        fn = _AGG_FN[cls]
        distinct = bool(agg_expr.fields.get("isDistinct", False))
        arg = fn_tree.children[0] if fn_tree.children else None
        if fn == "count" and arg is None:
            fn = "count_star"
        return fn, arg, distinct

    def _c_HashAggregateExec(self, node: SparkNode) -> _Converted:
        child = self._convert(node.children[0])
        groups, agg_exprs, mode, fn_modes = self._agg_parts(node)
        ec = ExprConverter(child.attrs, self.shims, self._convert_subplan)
        group_names = [g.fields.get("name", f"k{i}")
                       for i, g in enumerate(groups)]

        aggs, agg_attrs = [], []
        for a, fn_mode in zip(agg_exprs, fn_modes):
            fn, arg, distinct = self._agg_fn(a)
            rid = _expr_id(a.fields)
            fn_tree = a.children[0]
            agg_attrs.append(Attr(fn, rid,
                                  fn_tree.fields.get("dataType", "double")))
            # Spark keeps isDistinct on the functions of a single-DISTINCT
            # plan's last two aggregates for show: their input is
            # deduplicated by the plan, and they run as plain functions
            out = pb.AggFunctionP(
                fn=fn, distinct=distinct and fn_mode == "complete",
                mode="" if fn_mode == mode else fn_mode)
            if fn_mode in ("partial", "complete") and arg is not None:
                out.arg.CopyFrom(ec.convert(arg))
            aggs.append(out)

        if "partial_merge" in fn_modes or "final" in fn_modes:
            # the refs of a node that reads states are bound to attrs that
            # stand one a function for a layout of one or two columns a
            # state: only the leading (group) columns are where they say
            for i, g in enumerate(groups):
                idx = ec.convert(g).column.index
                if idx != i:
                    raise NotImplementedError(
                        "merging agg grouping not in partial column order")
            group_protos = [pb.ExprNode(column=pb.ColumnRefE(index=i))
                            for i in range(len(groups))]
        else:
            group_protos = [ec.convert(g) for g in groups]

        agg_names = [a.name for a in agg_attrs]
        n = pb.PlanNode(agg=pb.AggNode(
            child=child.node, group_exprs=group_protos, aggs=aggs,
            mode=mode, group_names=group_names,
            agg_names=agg_names))
        group_attrs = [Attr(nm, _expr_id(g.fields),
                            g.fields.get("dataType", "long"))
                       for nm, g in zip(group_names, groups)]
        out = _Converted(n, group_attrs + agg_attrs, child.partitions)

        if mode in ("final", "complete"):
            result = node.field_trees("resultExpressions")
            if result and not self._is_identity(result, out.attrs):
                return self._project(out, result)
        return out

    _c_SortAggregateExec = _c_HashAggregateExec
    _c_ObjectHashAggregateExec = _c_HashAggregateExec

    @staticmethod
    def _is_identity(result_trees: list, attrs: list) -> bool:
        if len(result_trees) != len(attrs):
            return False
        for t, a in zip(result_trees, attrs):
            tr = t.children[0] if t.simple_name == "Alias" else t
            if tr.simple_name != "AttributeReference":
                return False
            if _expr_id(tr.fields) != a.expr_id:
                return False
            # an Alias that renames is not identity — the projection must
            # run so downstream sees the aliased name
            if t.simple_name == "Alias" and t.fields.get("name") != a.name:
                return False
        return True
