"""The file scans read the columns the plan reads (PR 33; tier-1, on the
CPU: counts and answers, no timings).

``ir/pruning.py`` runs in ``PhysicalPlanner.plan_task`` before any
operator is built: it names, in each ``parquet_scan`` / ``orc_scan``, the
columns the project / filter / sort / limit / agg chain over it reads, and
re-indexes the chain's column references. Three things are pinned here:

(a) a task of the benchmark's star join moves 7 + 7 + 10 buffers to the
    device where it moved 41 + 20 + 42, and answers as its oracle does;
(b) plans at the edges of the rule (no chain, a reordered and repeated
    column, a filter-only column, ``count(*)``, a caller's own ``columns``,
    a nullable string between dropped neighbours, a subquery's own scan,
    ORC) answer as pyarrow does over the whole file;
(c) a node kind the pass has no rule for, and everything under it, comes
    out byte for byte as it went in.
"""

import os
import sys

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from auron_tpu.frontend import Session, col, functions as F, lit, \
    scalar_subquery
from auron_tpu.ir import pb, pruning
from auron_tpu.ir.planner import plan_from_bytes
from auron_tpu.obs import trace

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PLANS = ("q3", "q42", "q52", "q55")


def _scans(node: pb.PlanNode) -> list:
    """Every file scan under ``node`` (subquery plans left out), in the
    walk's order."""
    kind = node.WhichOneof("node")
    inner = getattr(node, kind)
    if kind in ("parquet_scan", "orc_scan"):
        return [inner]
    out = []
    for fd, val in inner.ListFields():
        if fd.message_type is pb.PlanNode.DESCRIPTOR:
            for child in (val if fd.is_repeated else (val,)):
                out.extend(_scans(child))
    return out


def _pruned(df) -> pb.PlanNode:
    task = pb.TaskDefinition.FromString(df.task_bytes(0))
    return pruning.prune_scan_columns(task.plan)


def _rows(table: pa.Table) -> list:
    return sorted(map(repr, table.to_pylist()))


# -- (a) the benchmark's plans ----------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process and the benchmark's four plans over
    one ``store_sales`` split of one scan batch (scale 0.02)."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, datagen

    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    (path, first, rows), *_ = datagen.write_splits(
        root, "store_sales", arrow["store_sales"], 16_384)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    task_rows = dict(arrow,
                     store_sales=arrow["store_sales"].slice(first, rows))
    session = Session()
    modules = {p: cell.load_module("plans", p) for p in PLANS}
    tasks = {p: m.build(session, dims, [path], 1).task_bytes(0)
             for p, m in modules.items()}
    server = AuronServer()
    server.serve_background()
    host, port = server.address

    def run(plan):
        table, metrics = AuronClient(host, port,
                                     timeout_s=600).execute(tasks[plan])
        return table, metrics["cost_ledger"]

    run.oracle = lambda plan: modules[plan].oracle(task_rows)
    run.tasks = tasks
    yield run
    server.shutdown()
    server.server_close()
    session.close()


@pytest.mark.parametrize("plan", PLANS)
def test_a_star_join_task_moves_the_columns_it_reads(plan, served):
    from harness import compare
    table, led = served(plan)
    counts = led["counts"]
    # a primitive or decimal column is data + validity, a string column
    # chars + lens + validity, and a batch carries its row count:
    # store_sales 3 x 2 + 1, date_dim 3 x 2 + 1, item 3 x 2 + 3 + 1
    # (41 + 20 + 42 before the pass)
    assert counts["h2d_transfers"] == 7 + 7 + 10
    assert counts["scan_columns_read"] == 3 + 3 + 4
    assert counts["scan_columns_pruned"] == 17 + 6 + 12
    res = compare.compare_tables(table, served.oracle(plan))
    assert compare.answer_ok(res), res


@pytest.mark.parametrize("plan", PLANS)
def test_a_star_join_scan_names_its_columns_in_file_order(plan, served):
    task = pb.TaskDefinition.FromString(served.tasks[plan])
    assert all(not s.columns for s in _scans(task.plan))
    fact, date_dim, item = _scans(pruning.prune_scan_columns(task.plan))
    assert list(fact.columns) == ["ss_sold_date_sk", "ss_item_sk",
                                  "ss_ext_sales_price"]
    assert list(date_dim.columns) == ["d_date_sk", "d_year", "d_moy"]
    assert len(item.columns) == 4 and item.columns[0] == "i_item_sk"
    for scan in (fact, date_dim, item):
        whole = pq.read_schema(scan.files[0]).names
        assert list(scan.columns) == [n for n in whole
                                      if n in set(scan.columns)]
        assert [f.name for f in scan.schema.fields] == list(scan.columns)
    # the rewrite is on a copy: the task that came in is what it was
    assert task.SerializeToString() == served.tasks[plan]
    # EXPLAIN names what each scan reads
    text = plan_from_bytes(served.tasks[plan]).tree_string()
    assert "columns=['ss_sold_date_sk', 'ss_item_sk', " \
           "'ss_ext_sales_price']" in text


# -- (b) the edges of the rule, against pyarrow over the whole file ---------

@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("pruning")
    n = 500
    wide = pa.table({
        "s0": pa.array([f"row{i}" for i in range(n)], pa.string()),
        "a": pa.array(range(n), pa.int64()),
        "b": pa.array([i % 7 for i in range(n)], pa.int64()),
        "name": pa.array([None if i % 3 == 0 else f"n{i % 11}"
                          for i in range(n)], pa.string()),
        "c": pa.array([i * 0.5 for i in range(n)], pa.float64()),
        "d": pa.array([None if i % 5 == 0 else i for i in range(n)],
                      pa.int32()),
    })
    dim = pa.table({
        "b": pa.array(range(7), pa.int64()),
        "label": pa.array([f"l{i}" for i in range(7)], pa.string()),
        "w": pa.array([i * 10 for i in range(7)], pa.int64()),
    })
    out = {"wide": str(root / "wide.parquet"),
           "dim": str(root / "dim.parquet"),
           "orc": str(root / "wide.orc")}
    pq.write_table(wide, out["wide"])
    pq.write_table(dim, out["dim"])
    from pyarrow import orc
    orc.write_table(wide, out["orc"])
    return out, wide, dim


def _join_without_a_project(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).join(s.read_parquet(f["dim"]), on="b")
    want = wide.join(dim, keys="b", join_type="inner")
    return df, want, [[], []]


def _select_reorders_and_repeats(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).select(
        "c", "a", col("a").alias("a2"), (col("a") + col("b")).alias("ab"))
    want = pa.table({"c": wide["c"], "a": wide["a"], "a2": wide["a"],
                     "ab": pc.add(wide["a"], wide["b"])})
    return df, want, [["a", "b", "c"]]


def _filter_names_a_dropped_column(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).filter(col("b") == 3) \
        .filter(col("d") > 100).select("a")
    keep = pc.and_(pc.equal(wide["b"], 3),
                   pc.fill_null(pc.greater(wide["d"], 100), False))
    return df, wide.filter(keep).select(["a"]), [["a", "b", "d"]]


def _count_star(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).group_by().agg(
        F.count_star().alias("n"))
    # the first column is a string: the first FIXED-WIDTH one is kept
    return df, pa.table({"n": pa.array([wide.num_rows], pa.int64())}), \
        [["a"]]


def _callers_columns_then_select(s, f, wide, dim):
    df = s.read_parquet(f["wide"], columns=["c", "name", "a", "b"]) \
        .filter(col("a") < 50).select("b", "c")
    want = wide.filter(pc.less(wide["a"], 50)).select(["b", "c"])
    # narrowed in the caller's order, not the file's
    return df, want, [["c", "a", "b"]]


def _nullable_string_between_dropped_neighbours(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).select("name")
    return df, wide.select(["name"]), [["name"]]


def _scalar_subquery_over_its_own_scan(s, f, wide, dim):
    top = scalar_subquery(s.read_parquet(f["dim"]).group_by().agg(
        F.max(col("w")).alias("m")))
    df = s.read_parquet(f["wide"]).filter(col("a") * lit(10) < top) \
        .select("a", "name")
    want = wide.filter(pc.less(pc.multiply(wide["a"], 10), 60)) \
        .select(["a", "name"])
    # the subquery's plan is another scope: pruned when the binder
    # plans it, its references never taken for the outer filter's
    return df, want, [["a", "name"]]


def _orc_scan(s, f, wide, dim):
    df = s.read_orc(f["orc"]).filter(col("b") == 1).select("name", "c")
    want = wide.filter(pc.equal(wide["b"], 1)).select(["name", "c"])
    return df, want, [["b", "name", "c"]]


def _agg_over_a_filter_over_the_scan(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).filter(col("a") >= 10) \
        .group_by("b").agg(F.sum(col("a")).alias("sa"))
    want = wide.filter(pc.greater_equal(wide["a"], 10)) \
        .group_by("b", use_threads=False).aggregate([("a", "sum")]) \
        .rename_columns(["b", "sa"])
    return df, want, [["a", "b"]]


def _sort_and_limit_under_a_select(s, f, wide, dim):
    df = s.read_parquet(f["wide"]).sort(col("a").desc()).limit(5) \
        .select("s0")
    want = wide.take(pc.sort_indices(
        wide, sort_keys=[("a", "descending")]).slice(0, 5)).select(["s0"])
    return df, want, [["s0", "a"]]


CASES = (_join_without_a_project, _select_reorders_and_repeats,
         _filter_names_a_dropped_column, _count_star,
         _callers_columns_then_select,
         _nullable_string_between_dropped_neighbours,
         _scalar_subquery_over_its_own_scan, _orc_scan,
         _agg_over_a_filter_over_the_scan, _sort_and_limit_under_a_select)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[1:])
def test_a_pruned_plan_answers_as_pyarrow_over_the_whole_file(case, files):
    f, wide, dim = files
    s = Session()
    try:
        df, want, columns = case(s, f, wide, dim)
        assert [list(scan.columns) for scan in _scans(_pruned(df))] \
            == columns
        with trace.task_scope(case.__name__) as acc:
            got = df.collect()
        counts = acc.sealed(0.0)["counts"]
    finally:
        s.close()
    assert got.column_names == want.column_names
    assert _rows(got) == _rows(want.cast(got.schema))
    # what ran is what the pass named (a subquery's scan runs apart)
    if case is not _scalar_subquery_over_its_own_scan:
        whole = [len(wide.schema) if "wide" in scan.files[0]
                 else len(dim.schema) for scan in _scans(_pruned(df))]
        read = [len(c) or n for c, n in zip(columns, whole)]
        assert counts["scan_columns_read"] == sum(read)
        assert counts["scan_columns_pruned"] == sum(whole) - sum(read)


def test_a_subquerys_scan_is_pruned_when_the_binder_plans_it(files):
    f, wide, dim = files
    s = Session()
    try:
        df, _want, _ = _scalar_subquery_over_its_own_scan(s, f, wide, dim)
        (q,) = [e.binary.right.scalar_subquery
                for e in _pruned(df).project.child.filter.predicates]
    finally:
        s.close()
    assert [list(x.columns) for x in _scans(q.plan)] == [[]]
    assert [list(x.columns)
            for x in _scans(pruning.prune_scan_columns(q.plan))] == [["w"]]


def test_a_hosts_scan_is_narrowed_without_opening_its_files():
    """A host ships ``columns`` and ``schema`` together: the pass narrows
    both by the same rule and reads no file for it."""
    F64 = pb.FieldP(name="x", dtype=pb.DT_FLOAT64, nullable=True)
    scan = pb.PlanNode(parquet_scan=pb.ParquetScanNode(
        files=["/no/such/file.parquet"], columns=["k", "x", "s"],
        schema=pb.SchemaP(fields=[
            pb.FieldP(name="k", dtype=pb.DT_INT64), F64,
            pb.FieldP(name="s", dtype=pb.DT_STRING, nullable=True)])))
    ref = pb.ExprNode(column=pb.ColumnRefE(index=2, name="s"))
    plan = pb.PlanNode(project=pb.ProjectNode(
        child=scan, exprs=[ref], names=["s"]))
    out = pruning.prune_scan_columns(plan)
    (narrow,) = _scans(out)
    assert list(narrow.columns) == ["s"]
    assert [f.name for f in narrow.schema.fields] == ["s"]
    assert out.project.exprs[0].column.index == 0
    # every column read: left as it came
    all_three = pb.PlanNode(project=pb.ProjectNode(
        child=scan, names=["a", "b", "c"], exprs=[
            pb.ExprNode(column=pb.ColumnRefE(index=i)) for i in (2, 0, 1)]))
    assert pruning.prune_scan_columns(all_three) == all_three
    # count(*) keeps the first fixed-width column of the host's schema
    count = pb.PlanNode(agg=pb.AggNode(
        child=scan, mode="complete", agg_names=["n"],
        aggs=[pb.AggFunctionP(fn="count_star")]))
    (one,) = _scans(pruning.prune_scan_columns(count))
    assert list(one.columns) == ["k"]


def test_the_schema_the_pass_hands_over_is_the_one_the_scan_infers(
        tmp_path):
    """The pass opens the file and hands the operator its schema, so
    that building the operator opens nothing: it must be the schema a
    bare scan of those columns infers, type for type."""
    import datetime
    import decimal

    from auron_tpu.io.parquet import ParquetScanOp
    table = pa.table({
        "i8": pa.array([1, None], pa.int8()),
        "i64": pa.array([1, 2], pa.int64()),
        "f32": pa.array([1.0, None], pa.float32()),
        "flag": pa.array([True, False], pa.bool_()),
        "money": pa.array([decimal.Decimal("1.25"), None],
                          pa.decimal128(7, 2)),
        "wide": pa.array([decimal.Decimal("1.5"), None],
                         pa.decimal128(30, 4)),
        "day": pa.array([datetime.date(2000, 1, 2), None], pa.date32()),
        "ts": pa.array([1, 2], pa.timestamp("us")),
        "text": pa.array(["a", None], pa.string()),
        "tags": pa.array([["x"], None], pa.list_(pa.string())),
        "nums": pa.array([[1, 2], []], pa.list_(pa.int64())),
        "pair": pa.array([{"p": 1, "q": "z"}, None],
                         pa.struct([("p", pa.int64()), ("q", pa.string())])),
    })
    path = str(tmp_path / "types.parquet")
    pq.write_table(table, path)
    scan = pb.PlanNode(parquet_scan=pb.ParquetScanNode(files=[path]))
    for i, name in enumerate(table.column_names):
        plan = pb.PlanNode(project=pb.ProjectNode(
            child=scan, names=[name],
            exprs=[pb.ExprNode(column=pb.ColumnRefE(index=i))]))
        task = pb.TaskDefinition(plan=plan).SerializeToString()
        (op,) = plan_from_bytes(task).children
        assert op.columns == [name]
        assert op.schema() == ParquetScanOp([path], columns=[name]).schema()


# -- (c) a node kind without a rule -----------------------------------------

RULED = ("parquet_scan", "orc_scan", "filter", "sort", "limit",
         "coalesce_batches", "debug", "project", "agg")
KINDS = [f.name for f in pb.PlanNode.DESCRIPTOR.oneofs_by_name["node"].fields]


def _chain_over_missing_file() -> pb.PlanNode:
    """A filter over a scan the pass WOULD narrow if anything above asked
    for less than all of it (and whose file it would have to open)."""
    scan = pb.PlanNode(parquet_scan=pb.ParquetScanNode(
        files=["/no/such/file.parquet"]))
    pred = pb.ExprNode(binary=pb.BinaryE(
        op="==", left=pb.ExprNode(column=pb.ColumnRefE(index=1)),
        right=pb.ExprNode(literal=pb.LiteralE(dtype=pb.DT_INT64, i64=1))))
    return pb.PlanNode(filter=pb.FilterNode(child=scan, predicates=[pred]))


def test_the_rules_are_the_ones_this_file_knows():
    assert sorted(RULED) == sorted(
        set(pruning._PASS_THROUGH) | set(pruning._FIXES_OUTPUT)
        | set(pruning._SCAN_FORMATS))


@pytest.mark.parametrize("kind", [k for k in KINDS if k not in RULED]
                         + ["agg_final"])
def test_a_node_kind_without_a_rule_is_left_byte_for_byte(kind):
    """A ``project`` that reads one column, over the unruled node, over a
    chain the pass would narrow: the node asks its children for every
    column, so nothing under it moves (the scan's file does not exist:
    had the pass tried to narrow it, opening the file would raise)."""
    node = pb.PlanNode()
    inner = getattr(node, "agg" if kind == "agg_final" else kind)
    inner.SetInParent()
    if kind == "agg_final":
        # a final agg reads its child's partial layout by position
        inner.mode = "final"
        inner.group_exprs.add().column.index = 0
    for fd in inner.DESCRIPTOR.fields:
        if fd.message_type is pb.PlanNode.DESCRIPTOR:
            child = getattr(inner, fd.name)
            (child.add() if fd.is_repeated else child).CopyFrom(
                _chain_over_missing_file())
    plan = pb.PlanNode(project=pb.ProjectNode(
        child=node, names=["x"],
        exprs=[pb.ExprNode(column=pb.ColumnRefE(index=0))]))
    before = plan.SerializeToString()
    assert pruning.prune_scan_columns(plan).SerializeToString() == before
    assert plan.SerializeToString() == before


def test_a_chain_under_an_unruled_node_is_still_narrowed(files):
    """The walk goes on below a node it leaves alone: a ``project`` under
    a join starts a chain of its own."""
    f, wide, dim = files
    s = Session()
    try:
        left = s.read_parquet(f["wide"]).select("a", "b")
        right = s.read_parquet(f["dim"]).select("b", "w")
        df = left.join(right, on="b")
        assert [list(x.columns) for x in _scans(_pruned(df))] \
            == [["a", "b"], ["b", "w"]]
        got = df.collect()
    finally:
        s.close()
    want = wide.select(["a", "b"]).join(dim.select(["b", "w"]), keys="b")
    assert _rows(got) == _rows(want.select(got.column_names)
                               .cast(got.schema))
