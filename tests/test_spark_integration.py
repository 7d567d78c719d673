"""Host-engine integration: recorded Spark physical plans execute
natively end-to-end.

The L1 slice (reference: AuronConverters.scala:209-310,
AuronConvertStrategy.scala:41-76): fixtures under tests/fixtures/ are
TPC-DS-class plans in Spark's plan.toJSON encoding; the converter lowers
them to the engine's proto, the planner executes them, and results are
diffed against a pandas oracle. The fallback fixture verifies
never-convert tagging and the ConvertToNative boundary.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar import arrow_bridge
from auron_tpu.integration import SparkPlanConverter, parse_plan
from auron_tpu.ir import pb
from auron_tpu.ir.planner import PlannerContext, plan_from_bytes
from auron_tpu.it.tpcds_data import generate, load_pandas
from auron_tpu.ops.base import ExecContext

_FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "fixtures")


def _fixture(name):
    with open(os.path.join(_FIXTURES, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("spark_it")
    tables = generate(str(root), scale=0.2)
    by_basename = {os.path.basename(f): f
                   for files in tables.values() for f in files}
    # the fixtures record the cluster's /data/tpcds/... paths; remap by
    # basename onto the locally generated dataset
    rewrite = lambda p: by_basename[os.path.basename(p)]
    return tables, load_pandas(tables), rewrite


def _execute(node: pb.PlanNode, ctx: PlannerContext, schema_names,
             partitions: int = 1) -> pa.Table:
    op = plan_from_bytes(
        pb.TaskDefinition(plan=node).SerializeToString(), ctx)
    tables = []
    for p in range(partitions):
        for b in op.execute(p, ExecContext(partition_id=p,
                                           num_partitions=partitions)):
            if int(b.num_rows):
                tables.append(pa.Table.from_batches(
                    [arrow_bridge.to_arrow(b, op.schema())]))
    out = (pa.concat_tables(tables) if tables
           else pa.table({n: [] for n in schema_names}))
    assert out.column_names == schema_names
    return out


def test_q03_executes_natively(dataset):
    _tables, pd_tables, rewrite = dataset
    conv = SparkPlanConverter(path_rewrite=rewrite)
    node, report = conv.convert(_fixture("spark_plan_q03.json"))
    assert not report.never_converted, report.summary()

    got = _execute(node, PlannerContext(), ["i_category", "total_sales"])

    ss, it = pd_tables["store_sales"], pd_tables["item"]
    j = ss.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    j = j[j.i_category.isin(["Books", "Music", "Shoes"])]
    exp = (j.groupby("i_category").agg(total_sales=("ss_sales_price",
                                                    "sum"))
           .reset_index()
           .sort_values(["total_sales", "i_category"],
                        ascending=[False, True]).head(10))
    got_rows = list(zip(got.column("i_category").to_pylist(),
                        got.column("total_sales").to_pylist()))
    exp_rows = list(zip(exp.i_category, exp.total_sales))
    assert len(got_rows) == len(exp_rows)
    for (gc, gv), (ec, ev) in zip(got_rows, exp_rows):
        assert gc == ec
        assert abs(gv - ev) < 1e-6 * max(1.0, abs(ev))


def test_q04_smj_executes_natively(dataset):
    _tables, pd_tables, rewrite = dataset
    conv = SparkPlanConverter(path_rewrite=rewrite)
    node, report = conv.convert(_fixture("spark_plan_q04_smj.json"))
    assert not report.never_converted, report.summary()

    got = _execute(node, PlannerContext(), ["s_state", "profit", "n"],
                   partitions=4)

    j = pd_tables["store_sales"].merge(
        pd_tables["store"], left_on="ss_store_sk", right_on="s_store_sk")
    exp = j.groupby("s_state").agg(
        profit=("ss_net_profit", "sum"),
        n=("ss_net_profit", "count")).reset_index()
    got_m = {r["s_state"]: (r["profit"], r["n"])
             for r in got.to_pylist()}
    exp_m = {r.s_state: (r.profit, r.n) for r in exp.itertuples()}
    assert set(got_m) == set(exp_m)
    for k in exp_m:
        assert abs(got_m[k][0] - exp_m[k][0]) < 1e-6 * max(
            1.0, abs(exp_m[k][0]))
        assert got_m[k][1] == exp_m[k][1]


def test_fallback_boundary(dataset):
    """An unconvertible node (python UDF exec) becomes a tagged fallback
    boundary; registering the host-computed subtree result executes the
    rest natively."""
    _tables, pd_tables, rewrite = dataset
    conv = SparkPlanConverter(path_rewrite=rewrite)
    node, report = conv.convert(_fixture("spark_plan_fallback.json"))

    nevers = report.never_converted
    assert len(nevers) == 1
    assert nevers[0][0] == "BatchEvalPythonExec"
    assert "no converter" in nevers[0][1]
    assert len(report.boundaries) == 1
    table, cls, attrs = report.boundaries[0]
    assert cls == "BatchEvalPythonExec"
    assert [a.name for a in attrs] == ["ss_store_sk", "ss_quantity",
                                       "py_bucket"]

    # the host engine executes the unconvertible subtree (here: pandas
    # stands in for Spark) and feeds rows through the boundary
    ss = pd_tables["store_sales"]
    sub = ss[ss.ss_store_sk.notna()][["ss_store_sk", "ss_quantity"]].copy()
    sub["py_bucket"] = sub.ss_quantity % 3
    ctx = PlannerContext()
    ctx.catalog[table] = pa.Table.from_pandas(sub.reset_index(drop=True),
                                              preserve_index=False)

    got = _execute(node, ctx, ["py_bucket", "qty"], partitions=2)
    exp = sub.groupby("py_bucket").agg(qty=("ss_quantity",
                                            "sum")).reset_index()
    got_m = {r["py_bucket"]: r["qty"] for r in got.to_pylist()}
    exp_m = {r.py_bucket: r.qty for r in exp.itertuples()}
    assert got_m == exp_m


def test_report_tags_every_node(dataset):
    _tables, _pd, rewrite = dataset
    conv = SparkPlanConverter(path_rewrite=rewrite)
    _node, report = conv.convert(_fixture("spark_plan_q03.json"))
    # transparent wrappers (WholeStageCodegen/InputAdapter) are unwrapped,
    # every real exec is tagged convertible
    tagged = [c for c, ok, _ in report.tags]
    assert tagged.count("FileSourceScanExec") == 2
    assert tagged.count("HashAggregateExec") == 2
    assert all(ok for _c, ok, _r in report.tags)


def test_parse_plan_roundtrip_structure():
    plan = _fixture("spark_plan_q03.json")
    root = parse_plan(plan)
    assert root.simple_name == "TakeOrderedAndProjectExec"
    # flattening invariant: node count == raw array length
    def count(n):
        return 1 + sum(count(c) for c in n.children)
    # expression fields are separate flattened arrays, not plan children
    assert count(root) < len(plan) or count(root) == len(plan)


class TestVersionShims:
    """integration/shims.py — the @sparkver / Shims seam analogue."""

    def test_semantic_version(self):
        from auron_tpu.integration.shims import SemanticVersion as V
        assert V.parse("3.5.1") > V.parse("3.5")
        assert V.parse("3.2") >= V.parse("3.2.0")
        assert V.parse("4.0.0-preview") > V.parse("3.5.4")
        assert str(V.parse("3.3")) == "3.3.0"

    def test_promote_precision_and_check_overflow_unwrap(self):
        """Real Spark <=3.3 plans wrap decimal arithmetic in
        PromotePrecision/CheckOverflow; both must convert (identity /
        decimal cast) instead of falling back."""
        from auron_tpu.integration.spark_converter import (ExprConverter,
                                                           Attr)
        from auron_tpu.integration.shims import SparkShims
        from auron_tpu.integration.spark_plan import SparkNode

        attr_node = SparkNode(
            cls="org.apache.spark.sql.catalyst.expressions"
                ".AttributeReference",
            fields={"name": "d", "dataType": "decimal(12,2)",
                    "exprId": {"id": 7}}, children=[])
        wrapped = SparkNode(
            cls="org.apache.spark.sql.catalyst.expressions.CheckOverflow",
            fields={"dataType": "decimal(14,2)", "nullOnOverflow": True},
            children=[SparkNode(
                cls="org.apache.spark.sql.catalyst.expressions"
                    ".PromotePrecision",
                fields={}, children=[attr_node])])
        ec = ExprConverter([Attr("d", 7, "decimal(12,2)")],
                           SparkShims("3.3.0"))
        out = ec.convert(wrapped)
        assert out.WhichOneof("expr") == "cast"
        assert out.cast.precision == 14 and out.cast.scale == 2
        assert out.cast.child.WhichOneof("expr") == "column"

    def test_map_struct_expressions_convert(self):
        """GetStructField (ordinal in fields, not args), CreateNamedStruct
        and GetMapValue must convert to the engine's struct/map surface
        (reference: named_struct.rs, get_map_value.rs)."""
        from auron_tpu.integration.spark_converter import (Attr,
                                                           ExprConverter)
        from auron_tpu.integration.spark_plan import SparkNode
        CAT = "org.apache.spark.sql.catalyst.expressions."
        attr_node = SparkNode(
            cls=CAT + "AttributeReference",
            fields={"name": "st", "dataType": "struct<a:bigint,b:string>",
                    "exprId": {"id": 3}}, children=[])
        gsf = SparkNode(cls=CAT + "GetStructField",
                        fields={"ordinal": 1, "name": "b"},
                        children=[attr_node])
        ec = ExprConverter([Attr("st", 3, "struct<a:bigint,b:string>"),
                            Attr("m", 4, "map<bigint,bigint>"),
                            Attr("k", 5, "bigint")])
        out = ec.convert(gsf)
        assert out.WhichOneof("expr") == "get_struct_field"
        assert out.get_struct_field.ordinal == 1

        m_attr = SparkNode(cls=CAT + "AttributeReference",
                           fields={"name": "m",
                                   "dataType": "map<bigint,bigint>",
                                   "exprId": {"id": 4}}, children=[])
        k_attr = SparkNode(cls=CAT + "AttributeReference",
                           fields={"name": "k", "dataType": "bigint",
                                   "exprId": {"id": 5}}, children=[])
        gmv = SparkNode(cls=CAT + "GetMapValue", fields={},
                        children=[m_attr, k_attr])
        out = ec.convert(gmv)
        assert out.WhichOneof("expr") == "scalar_function"
        assert out.scalar_function.name == "get_map_value"

        cns = SparkNode(
            cls=CAT + "CreateNamedStruct", fields={},
            children=[SparkNode(cls=CAT + "Literal",
                                fields={"value": "a", "dataType": "string"},
                                children=[]),
                      k_attr])
        out = ec.convert(cns)
        assert out.scalar_function.name == "named_struct"

    def test_aqe_reader_both_spellings_transparent(self):
        from auron_tpu.integration.shims import SparkShims
        for v in ("3.0.3", "3.5.1"):
            sh = SparkShims(v)
            assert sh.is_transparent_plan("CustomShuffleReaderExec")
            assert sh.is_transparent_plan("AQEShuffleReadExec")


def _q28_band(files, ids, lo, hi, tag):
    """One derived table of TPC-DS q28 as Spark's physical plan: Filter
    into the four HashAggregates of ``planAggregateWithOneDistinct``
    (Partial; PartialMerge; PartialMerge + Partial over the deduplicated
    column in ONE node; Final), a hash exchange on the price and a gather
    between them. ``ids`` numbers this band's expression ids."""
    from spark_fixture_builder import (agg_expr, alias, attr, binop,
                                       file_scan, filter_, hash_agg,
                                       hash_partitioning, lit,
                                       shuffle_exchange, single_partition)
    qty = attr("ss_quantity", ids, "long")
    price = attr("ss_list_price", ids + 1, "decimal(7,2)")
    band = filter_(
        binop("And",
              binop("GreaterThanOrEqual", qty, lit(lo, "long")),
              binop("LessThanOrEqual", qty, lit(hi, "long"))),
        file_scan([qty, price], files))
    avg_t, cnt_t = "decimal(11,6)", "long"

    def plain(mode):
        return [agg_expr("Average", price, mode, ids + 2, avg_t),
                agg_expr("Count", price, mode, ids + 3, cnt_t)]

    def counted(mode):   # Spark keeps isDistinct on these, for show
        return agg_expr("Count", price, mode, ids + 4, cnt_t, distinct=True)

    first = hash_agg([price], plain("Partial"), [], band)
    second = hash_agg([price], plain("PartialMerge"), [],
                      shuffle_exchange(hash_partitioning([price], 4), first))
    third = hash_agg([], plain("PartialMerge") + [counted("Partial")], [],
                     second)
    results = [alias(attr("avg", ids + 2, avg_t), f"{tag}_LP", ids + 5),
               alias(attr("count", ids + 3, cnt_t), f"{tag}_CNT", ids + 6),
               alias(attr("count", ids + 4, cnt_t), f"{tag}_CNTD", ids + 7)]
    return hash_agg([], plain("Final") + [counted("Final")], results,
                    shuffle_exchange(single_partition(), third))


def test_a_single_distinct_plan_converts_and_answers(tmp_path):
    """Two bands of q28 as Spark plans them, joined by a
    BroadcastNestedLoopJoin without a condition: ``PartialMerge``, the
    node whose functions differ in mode, and the join without keys all
    convert (no fallback boundary), and the answer is exact."""
    import decimal

    import pyarrow.parquet as pq
    from spark_fixture_builder import SPARK_EXEC, T, broadcast_exchange
    rng = np.random.default_rng(44)
    files, rows = [], []
    for i in range(4):
        qty = rng.integers(1, 13, 60)
        cents = rng.integers(100, 160, 60)
        files.append(str(tmp_path / f"store_sales_{i}.parquet"))
        pq.write_table(pa.table({
            "ss_quantity": pa.array(qty, pa.int64()),
            "ss_list_price": pa.array(
                [decimal.Decimal(int(c)).scaleb(-2) for c in cents],
                pa.decimal128(7, 2))}), files[-1])
        rows += list(zip(qty.tolist(), cents.tolist()))
    join = T(f"{SPARK_EXEC}.joins.BroadcastNestedLoopJoinExec",
             [_q28_band(files, 100, 0, 5, "B1"),
              broadcast_exchange(_q28_band(files, 200, 6, 10, "B2"))],
             buildSide={"object": "org.apache.spark.sql.catalyst."
                        "optimizer.BuildRight$"},
             joinType={"object": "org.apache.spark.sql.catalyst.plans."
                       "Inner$"},
             condition=None)
    conv = SparkPlanConverter()
    node, report = conv.convert(join.flatten())
    assert not report.never_converted, report.summary()
    tagged = [c for c, ok, _ in report.tags if ok]
    assert tagged.count("HashAggregateExec") == 8
    assert tagged.count("BroadcastNestedLoopJoinExec") == 1
    assert node.WhichOneof("node") == "cross_join"
    # the third aggregate: one node, the count of the deduplicated column
    # in a mode of its own and plain by now
    third = node.cross_join.probe.project.child.agg.child \
        .shuffle_writer.child.agg
    assert third.mode == "partial_merge"
    assert [(f.fn, f.mode, f.distinct) for f in third.aggs] == [
        ("avg", "", False), ("count", "", False),
        ("count", "partial", False)]
    assert third.child.agg.mode == "partial_merge"

    names = [f"B{b}_{c}" for b in (1, 2) for c in ("LP", "CNT", "CNTD")]
    got = _execute(node, PlannerContext(), names).to_pylist()
    want = {}
    for tag, lo, hi in (("B1", 0, 5), ("B2", 6, 10)):
        cents = [c for q, c in rows if lo <= q <= hi]
        want[f"{tag}_LP"] = (decimal.Decimal(sum(cents)).scaleb(-2)
                             / len(cents)).quantize(
            decimal.Decimal("0.000001"), rounding=decimal.ROUND_HALF_UP)
        want[f"{tag}_CNT"] = len(cents)
        want[f"{tag}_CNTD"] = len(set(cents))
    assert got == [want]

    # an outer type or a condition stays a fallback boundary
    for field, value in (("joinType", {"object": "org.apache.spark.sql."
                                       "catalyst.plans.LeftOuter$"}),
                         ("condition", [{"class": "x", "num-children": 0}])):
        bad = T(join.cls, join.children, **{**join.fields, field: value,
                                            "output": []})
        conv = SparkPlanConverter()
        with pytest.raises(NotImplementedError, match="nested-loop join"):
            conv.convert(bad.flatten())
