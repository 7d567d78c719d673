"""A single-DISTINCT aggregate as Spark plans it (PR 44; tier-1, on the
CPU: answers, types, plan shapes and counts, no timings).

``GroupedData.agg`` plans any ``agg()`` whose DISTINCT functions share one
argument as Spark's four aggregates (``AggUtils.
planAggregateWithOneDistinct``): partial by keys ++ [x]; ``partial_merge``
of the same; the keys alone with the plain functions ``partial_merge`` and
the DISTINCT ones ``partial`` over the deduplicated column, in ONE node;
``final``. Held here:

- TPC-DS q28 at its published text (``benchmark/plans/q28.py``: six such
  aggregates over six scans, cross-joined) as a served task against its
  own oracle, all 18 cells exact, at 1 scan partition and at 4 — the
  latter on the 4-device mesh route and off it, bit for bit;
- the plan's shape: four aggregates a band, their modes, exchanges only
  where there are partitions, five joins without keys;
- a sweep of single-DISTINCT aggregates against Python's ``decimal`` and
  sets: alone and mixed with plain functions, 0 / 1 / 2 group keys, int /
  decimal(7,2) / decimal(25,2) arguments, nulls, an all-null group, an
  empty input, 1 and 3 partitions;
- ``counts.agg_distinct_groups`` = the sum of the distinct counts,
  ``counts.cross_join_rows`` = the rows the joins put out, both 0 in a
  plan that holds neither;
- the join without keys: 1 x 1, n x 1, n x m past one program's slots, an
  empty side, a refused outer type and condition;
- every plan the benchmark had before this PR serialises to the bytes it
  had at the parent commit.
"""

import decimal
import hashlib
import os
import sys
from decimal import Decimal

import jax
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg
from auron_tpu.frontend import Session
from auron_tpu.frontend.dataframe import col, functions as F
from auron_tpu.ir import pb

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SPLIT_ROWS, BATCH_ROWS = 36_000, 12_288
AVG = pa.decimal128(11, 6)


def _bench():
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, compare, datagen
    return cell, compare, datagen


def _leaf_sum(tree, key) -> float:
    if isinstance(tree, dict):
        return sum(v if k == key and isinstance(v, (int, float))
                   else _leaf_sum(v, key) for k, v in tree.items())
    if isinstance(tree, list):
        return sum(_leaf_sum(v, key) for v in tree)
    return 0


def _nodes(node: pb.PlanNode):
    """Every plan node under ``node``, parents first."""
    kind = node.WhichOneof("node")
    inner = getattr(node, kind)
    yield kind, inner
    for fd, val in inner.ListFields():
        if fd.message_type is pb.PlanNode.DESCRIPTOR:
            for child in (val if fd.is_repeated else (val,)):
                yield from _nodes(child)


# -- q28 as a served task ----------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process; q28 over one split of three scan
    batches in one partition, and over four splits in four (scale 0.05:
    144,000 fact rows in four splits)."""
    cell, compare, datagen = _bench()
    from auron_tpu.parallel import mesh
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.05,
                             tables=("store_sales",))
    fact = arrow["store_sales"]
    paths = []
    for i in range(4):
        paths.append(os.path.join(root, f"store_sales_{i:04d}.parquet"))
        pq.write_table(fact.slice(i * SPLIT_ROWS, SPLIT_ROWS), paths[-1],
                       row_group_size=BATCH_ROWS)
    session = Session()
    q28 = cell.load_module("plans", "q28")
    frames = {1: q28.build(session, {}, paths[:1], 1),
              4: q28.build(session, {}, paths, 4)}
    rows = {1: fact.slice(0, SPLIT_ROWS), 4: fact}
    server = AuronServer()
    server.serve_background()
    host, port = server.address
    conf = cfg.get_config()
    answers = {}

    def run(partitions, on_mesh=False):
        if (partitions, on_mesh) not in answers:
            if on_mesh:
                conf.set(cfg.MESH_ENABLED, True)
                conf.set(cfg.MESH_DEVICES, 4)
            try:
                table, done = AuronClient(host, port, timeout_s=600) \
                    .execute(frames[partitions].task_bytes(0))
            finally:
                conf.unset(cfg.MESH_ENABLED)
                conf.unset(cfg.MESH_DEVICES)
            answers[(partitions, on_mesh)] = (table, done)
        return answers[(partitions, on_mesh)]

    run.frames = frames
    run.oracle = lambda partitions, **kw: q28.oracle(
        {"store_sales": rows[partitions]}, **kw)
    run.compare = compare
    yield run
    server.shutdown()
    server.server_close()
    session.close()
    mesh.reset_plane()


def test_q28_answers_its_oracle_in_all_18_cells(served):
    table, done = served(1)
    want = served.oracle(1)
    res = served.compare.compare_tables(table, want)
    assert served.compare.answer_ok(res), res
    assert res["cells"] == 18 and table.num_rows == 1
    assert table.column_names == [f"B{i}_{c}" for i in range(1, 7)
                                  for c in ("LP", "CNT", "CNTD")]
    for f in table.schema:
        assert f.type == (AVG if f.name.endswith("_LP") else pa.int64()), f
    row = table.to_pylist()[0]
    # a band keeps 5 % of the quantities and a quarter of those: hundreds
    # of rows, nearly as many prices
    for i in range(1, 7):
        assert 200 < row[f"B{i}_CNTD"] <= row[f"B{i}_CNT"] < 800
    assert done["cost_ledger"]["outcome"] == "ok"


def test_q28_counts_its_distinct_groups_and_its_joins(served):
    table, done = served(1)
    led = done["cost_ledger"]
    counts, row = led["counts"], table.to_pylist()[0]
    distinct = sum(row[f"B{i}_CNTD"] for i in range(1, 7))
    assert counts["agg_distinct_groups"] == distinct
    assert counts["cross_join_rows"] == 5
    # the two keyed aggregates of a band each emit its distinct prices
    assert counts["agg_groups"] == 2 * distinct
    # three scan batches a band into the first, its one output batch into
    # the second: on the hash table; the two without keys on the sort path
    assert counts["agg_hash_batches"] == 6 * (3 + 1)
    assert counts["agg_sort_batches"] == 6 * 2
    assert counts["agg_sort_fallbacks"] == counts["agg_demoted_to_sort"] == 0
    assert counts["scan_columns_read"] == 6 * 4
    assert counts["program_calls_by_site"]["ops.joins.cross"] == 5
    ops = led["ops_s"]
    assert ops["agg"]["batches"] > 0 and ops["cross_join"]["batches"] >= 5
    assert "hash_join" not in ops


def test_the_controls_differ_from_the_exact_reference(served):
    """The precision below decimal(7,2): an average carried in float32
    misses Spark's digits in most of the six cells; float32 still tells
    two prices under $1,310.72 a cent apart, so the COUNT moves only at
    float16."""
    import numpy as np
    exact = served.oracle(1)
    cmp = served.compare.compare_tables
    assert cmp(served.oracle(1, float32_average=True),
               exact)["exact_mismatches"] >= 3
    assert cmp(served.oracle(1, distinct_dtype=np.float32),
               exact)["exact_mismatches"] == 0
    assert cmp(served.oracle(1, distinct_dtype=np.float16),
               exact)["exact_mismatches"] == 6


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_q28_over_four_partitions_on_the_mesh_and_off_it(served):
    table, done = served(4, on_mesh=True)
    single, done_single = served(4)
    res = served.compare.compare_tables(table, served.oracle(4))
    assert served.compare.answer_ok(res) and res["cells"] == 18, res
    assert table.equals(single)
    # each band's hash exchange on the decimal price crosses the mesh;
    # its gather to one partition does not
    assert _leaf_sum(done, "exchange_route_all_to_all") == 6
    assert _leaf_sum(done, "exchange_route_demoted") == 0
    assert _leaf_sum(done_single, "exchange_route_all_to_all") == 0
    row = table.to_pylist()[0]
    distinct = sum(row[f"B{i}_CNTD"] for i in range(1, 7))
    for frame in (done, done_single):
        counts = frame["cost_ledger"]["counts"]
        # the four partitions of a band's second aggregate hold disjoint
        # prices: their groups sum to the distinct count
        assert counts["agg_distinct_groups"] == distinct
        assert counts["cross_join_rows"] == 5
    # a round a scan batch of a partition: three an exchange
    assert done["cost_ledger"]["counts"]["mesh_rounds"] == 6 * 3


@pytest.mark.parametrize("partitions", (1, 4))
def test_the_plan_is_sparks_four_aggregates_a_band(served, partitions):
    task = pb.TaskDefinition()
    task.ParseFromString(served.frames[partitions].task_bytes(0))
    nodes = list(_nodes(task.plan))
    kinds = [k for k, _ in nodes]
    assert kinds[0] == "limit" and kinds.count("cross_join") == 5
    assert kinds.count("parquet_scan") == 6 and kinds.count("agg") == 24
    assert "hash_join" not in kinds and "broadcast_exchange" not in kinds
    aggs = [n for k, n in nodes if k == "agg"]
    # parents first: final, the mixed node, partial_merge, partial
    for band in range(6):
        final, third, second, first = aggs[4 * band: 4 * band + 4]
        assert [a.mode for a in (final, third, second, first)] == \
            ["final", "partial_merge", "partial_merge", "partial"]
        assert [f.fn for f in final.aggs] == ["avg", "count", "count"]
        assert [f.mode for f in final.aggs] == ["", "", ""]
        assert [(f.fn, f.mode) for f in third.aggs] == \
            [("avg", ""), ("count", ""), ("count", "partial")]
        assert third.aggs[2].arg.column.index == 0
        assert not any(f.distinct for n in (final, third, second, first)
                       for f in n.aggs)
        assert [f.fn for f in second.aggs] == [f.fn for f in first.aggs] \
            == ["avg", "count"]
        assert len(first.group_exprs) == len(second.group_exprs) == 1
        assert len(third.group_exprs) == len(final.group_exprs) == 0
        # exchanges exactly where there are partitions: hash on the price
        # under the second aggregate, a gather under the last
        under = {id(n): n.child.WhichOneof("node")
                 for n in (final, third, second, first)}
        if partitions == 1:
            assert [under[id(n)] for n in (final, third, second)] == \
                ["agg", "agg", "agg"]
        else:
            assert [under[id(n)] for n in (final, third, second)] == \
                ["shuffle_writer", "agg", "shuffle_writer"]
            assert final.child.shuffle_writer.partitioning.kind == "single"
            part = second.child.shuffle_writer.partitioning
            assert part.kind == "hash" and part.num_partitions == 4
            assert [k.column.index for k in part.hash_keys] == [0]
    assert kinds.count("shuffle_writer") == (0 if partitions == 1 else 12)


# -- the sweep ---------------------------------------------------------------

def _arg_values(kind):
    """40 values, a few of them null and many repeated; rows 30-39 (the
    third value of the first key) are all null."""
    raw = [(7 * i * i + 3 * i) % 23 - 9 for i in range(40)]
    if kind == "int":
        vals, typ = raw, pa.int64()
    elif kind == "money":
        vals, typ = [Decimal(v * 125).scaleb(-2) for v in raw], \
            pa.decimal128(7, 2)
    else:
        vals = [Decimal(v * (10 ** 21 + 7)).scaleb(-2) for v in raw]
        typ = pa.decimal128(25, 2)
    vals = [None if i % 7 == 3 or i >= 30 else v
            for i, v in enumerate(vals)]
    return vals, typ


def _frame(kind, rows=40):
    vals, typ = _arg_values(kind)
    return pa.table({
        "g": pa.array([i // 10 for i in range(40)][:rows], pa.int64()),
        "h": pa.array([i % 2 for i in range(40)][:rows], pa.int64()),
        "x": pa.array(vals[:rows], typ),
        "y": pa.array(list(range(40))[:rows], pa.int64())})


def _avg(vals, typ):
    if not vals:
        return None
    if pa.types.is_decimal(typ):
        return (sum(vals) / len(vals)).quantize(
            Decimal(1).scaleb(-(typ.scale + 4)),
            rounding=decimal.ROUND_HALF_UP)
    return sum(vals) / len(vals)


def _expect(table, keys, names):
    """The reference: Python sets, sums and ``decimal`` a group."""
    typ = table.schema.field("x").type
    groups = {}
    for r in table.to_pylist():
        groups.setdefault(tuple(r[k] for k in keys), []).append(r)
    if not keys and not groups:
        groups[()] = []
    out = {}
    for key, rows in groups.items():
        xs = [r["x"] for r in rows if r["x"] is not None]
        ys = [r["y"] for r in rows]
        every = {
            "cd": len(set(xs)),
            "sd": sum(set(xs)) if xs else None,
            "ad": _avg(sorted(set(xs)), typ),
            "s": sum(xs) if xs else None, "a": _avg(xs, typ),
            "c": len(xs), "mn": min(ys, default=None),
            "mx": max(xs, default=None), "n": len(rows)}
        out[key] = {n: every[n] for n in names}
    return out


_ALL = {"cd": lambda: F.count(col("x"), distinct=True),
        "sd": lambda: F.sum(col("x"), distinct=True),
        "ad": lambda: F.avg(col("x"), distinct=True),
        "s": lambda: F.sum(col("x")), "a": lambda: F.avg(col("x")),
        "c": lambda: F.count(col("x")), "mn": lambda: F.min(col("y")),
        "mx": lambda: F.max(col("x")), "n": lambda: F.count_star()}
_MIX = ("a", "cd", "s", "sd", "c", "mn", "ad", "mx", "n")


def _run(table, keys, names, partitions=1):
    session = Session(batch_capacity=16)
    try:
        df = session.from_arrow(table)
        if partitions > 1:
            df = df.repartition(partitions)
        out = session.execute(df.group_by(*keys).agg(
            *[_ALL[n]().alias(n) for n in names]))
    finally:
        session.close()
    return out


def _check(out, table, keys, names):
    want = _expect(table, keys, names)
    got = {tuple(r[k] for k in keys): {n: r[n] for n in names}
           for r in out.to_pylist()}
    assert set(got) == set(want)
    for key, cells in want.items():
        for name, value in cells.items():
            have = got[key][name]
            if isinstance(value, float):
                assert have == pytest.approx(value, rel=1e-12), (key, name)
            else:
                assert have == value, (key, name, have, value)
    typ = table.schema.field("x").type
    if pa.types.is_decimal(typ):
        fields = {f.name: f.type for f in out.schema}
        for name in set(names) & {"ad", "a"}:
            assert fields[name] == pa.decimal128(typ.precision + 4,
                                                 typ.scale + 4)
        for name in set(names) & {"sd", "s"}:
            assert fields[name] == pa.decimal128(typ.precision + 10,
                                                 typ.scale)
    for name in set(names) & {"cd", "c", "n"}:
        assert out.schema.field(name).type == pa.int64()


@pytest.mark.parametrize("partitions", (1, 3))
@pytest.mark.parametrize("keys", ((), ("g",), ("g", "h")),
                         ids=("no_key", "one_key", "two_keys"))
@pytest.mark.parametrize("kind", ("int", "money", "wide"))
def test_distinct_mixed_with_plain_functions(kind, keys, partitions):
    """count / sum / avg DISTINCT of one argument beside sum, avg, count,
    min, max and count(*), in the caller's order."""
    table = _frame(kind)
    _check(_run(table, keys, _MIX, partitions), table, keys, _MIX)


@pytest.mark.parametrize("name", ("cd", "sd", "ad"))
@pytest.mark.parametrize("kind", ("int", "money", "wide"))
def test_a_distinct_function_alone(kind, name):
    table = _frame(kind)
    _check(_run(table, ("g",), (name,)), table, ("g",), (name,))


@pytest.mark.parametrize("keys", ((), ("g",)), ids=("no_key", "one_key"))
@pytest.mark.parametrize("kind", ("int", "money", "wide"))
def test_an_empty_input(kind, keys):
    """No row: without keys NULL / 0 / 0 in one row, with keys no row."""
    table = _frame(kind, rows=0)
    out = _run(table, keys, _MIX)
    assert out.num_rows == (0 if keys else 1)
    _check(out, table, keys, _MIX)


def test_differing_distinct_arguments_are_refused():
    session = Session(batch_capacity=16)
    try:
        df = session.from_arrow(_frame("int"))
        with pytest.raises(NotImplementedError, match="one argument"):
            df.group_by("g").agg(F.count(col("x"), distinct=True),
                                 F.sum(col("y"), distinct=True))
        # min / max see the same rows with DISTINCT or without
        out = session.execute(df.group_by("g").agg(
            F.count(col("x"), distinct=True).alias("cd"),
            F.max(col("y"), distinct=True).alias("mx")))
        assert {r["g"]: r["mx"] for r in out.to_pylist()} == \
            {0: 9, 1: 19, 2: 29, 3: 39}
    finally:
        session.close()


def test_a_plan_without_distinct_counts_no_distinct_group(tmp_path):
    """``agg_distinct_groups`` and ``cross_join_rows`` of a task's ledger:
    what the aggregation says, and 0 in a plan that holds neither."""
    from auron_tpu.obs import trace
    for key in ("agg_distinct_groups", "cross_join_rows"):
        assert key in trace.COUNT_KEYS
        assert trace.TaskAccumulator().sealed(0.0)["counts"][key] == 0
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    session = Session(batch_capacity=16)
    server = AuronServer()
    server.serve_background()
    try:
        client = AuronClient(*server.address, timeout_s=600)
        table = _frame("money")
        path = str(tmp_path / "sweep.parquet")
        pq.write_table(table, path)
        df = session.read_parquet(path)
        plain = df.group_by("g").agg(F.sum(col("x")).alias("s"))
        _t, done = client.execute(plain.task_bytes(0))
        counts = done["cost_ledger"]["counts"]
        assert counts["agg_distinct_groups"] == 0
        assert counts["cross_join_rows"] == 0 and counts["agg_groups"] == 4
        mixed = df.group_by("g").agg(
            F.count(col("x"), distinct=True).alias("cd"),
            F.avg(col("x")).alias("a"))
        got, done = client.execute(mixed.task_bytes(0))
        counts = done["cost_ledger"]["counts"]
        # a group of nulls alone is a (key, NULL) pair too: counted as a
        # group that left, not as a distinct value
        want = _expect(table, ("g",), ("cd",))
        pairs = sum(v["cd"] for v in want.values())
        assert sum(r["cd"] for r in got.to_pylist()) == pairs
        null_pairs = len({r["g"] for r in table.to_pylist()
                          if r["x"] is None})
        assert counts["agg_distinct_groups"] == pairs + null_pairs
    finally:
        server.shutdown()
        server.server_close()
        session.close()


# -- the join without keys ---------------------------------------------------

def _ints(name, n):
    return pa.table({name: pa.array(list(range(n)), pa.int64())})


@pytest.mark.parametrize("left, right", ((1, 1), (37, 1), (1, 5), (37, 5),
                                         (0, 3), (3, 0)))
def test_cross_join_pairs_every_row_with_every_row(left, right):
    session = Session(batch_capacity=16)
    try:
        a = session.from_arrow(_ints("a", left))
        b = session.from_arrow(_ints("b", right))
        out = session.execute(a.cross_join(b))
    finally:
        session.close()
    assert out.column_names == ["a", "b"]
    assert sorted((r["a"], r["b"]) for r in out.to_pylist()) == \
        [(i, j) for i in range(left) for j in range(right)]


def test_cross_join_takes_a_long_build_side_in_slices(monkeypatch):
    """A build side past one program's slots is taken in slices, the
    last of them short."""
    from auron_tpu.ops import joins
    monkeypatch.setattr(joins, "_CROSS_MAX_SLOTS", 64)
    session = Session(batch_capacity=16)
    try:
        a = session.from_arrow(_ints("a", 21))
        b = session.from_arrow(_ints("b", 11)).repartition(3)
        out = session.execute(a.cross_join(b))
    finally:
        session.close()
    assert sorted((r["a"], r["b"]) for r in out.to_pylist()) == \
        [(i, j) for i in range(21) for j in range(11)]


def test_cross_join_refuses_an_outer_type_and_a_condition():
    from auron_tpu.ir.planner import PlannerContext, plan_from_bytes
    from auron_tpu.ir.serde import expr_to_proto
    from auron_tpu.exprs import ir
    session = Session(batch_capacity=16)
    try:
        a = session.from_arrow(_ints("a", 3))
        b = session.from_arrow(_ints("b", 2))
        with pytest.raises(NotImplementedError, match="inner"):
            a.cross_join(b, how="left")
        with pytest.raises(NotImplementedError, match="condition"):
            a.cross_join(b, condition=col("a") < col("b"))
        # a host's plan that carries either is refused by the planner
        good = a.cross_join(b).plan
        for change in ("type", "condition"):
            node = pb.PlanNode()
            node.CopyFrom(good)
            if change == "type":
                node.cross_join.join_type = "full"
            else:
                node.cross_join.condition.CopyFrom(expr_to_proto(
                    ir.BinaryExpr("<", ir.ColumnRef(0), ir.ColumnRef(1))))
            with pytest.raises(NotImplementedError, match="join without keys"):
                plan_from_bytes(
                    pb.TaskDefinition(plan=node).SerializeToString(),
                    PlannerContext(catalog=dict(session.ctx.catalog)))
    finally:
        session.close()


# -- the plans the benchmark already had -------------------------------------

#: sha256 (16 hex digits) of each plan's TaskDefinition at the parent of
#: PR 44 (f6b13d5): seed 2147483659, scale 0.01, splits of 7,200 rows,
#: every scan's files cut to their base names. [1 partition, 4 partitions]
PARENT_PLAN_DIGESTS = {
    "q3": ("12bde51b089c10f9", "96933a6a9413cbe6"),
    "q36": ("f773025d67d2ce13", "d4cf859618bab737"),
    "q42": ("9c933fb854eebe5a", "086eff28d807d2ab"),
    "q52": ("9e2693259296bff7", "57e41176fe3e8bf0"),
    "q53": ("25c4c3ba71ddf7d0", "7aaeb1e919dfa11d"),
    "q55": ("f48036a831def2a2", "2b90be39878930b8"),
    "q59": ("8380f50c3bc8343d", "31b7318bb3d68778"),
    "q65": ("fe5cd1488d720c35", "86d2c00e5a76d587"),
    "q65m": ("3abe2c5a86c201da", "944abcee91aadfe7"),
    "q65sa": ("8bccbb827ffe932e", "1d2e910321434a5c"),
    "q65sam": ("9a2a460f115de38b", "1c8f5e273c5bf6de"),
    "q98": ("40b352fc6b2e79b1", "d92c538d3a5aa9ad"),
}


@pytest.fixture(scope="module")
def old_plans(tmp_path_factory):
    cell, _compare, datagen = _bench()
    root = str(tmp_path_factory.mktemp("plans"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.01)
    splits = datagen.write_splits(root, "store_sales", arrow["store_sales"],
                                  7200)
    dims = {n: datagen.write_whole(root, n, arrow[n])
            for n in arrow if n != "store_sales"}
    session = Session()

    def digest(plan, partitions):
        module = cell.load_module("plans", plan)
        df = module.build(session, dims, [s[0] for s in splits[:partitions]],
                          partitions)
        task = pb.TaskDefinition()
        task.ParseFromString(df.task_bytes(0))
        for kind, node in _nodes(task.plan):
            if kind == "parquet_scan":
                node.files[:] = [os.path.basename(f) for f in node.files]
        return hashlib.sha256(
            task.SerializeToString(deterministic=True)).hexdigest()[:16]

    yield digest
    session.close()


@pytest.mark.parametrize("plan", sorted(PARENT_PLAN_DIGESTS))
def test_an_existing_benchmark_plan_serialises_as_at_the_parent(plan,
                                                                old_plans):
    assert (old_plans(plan, 1), old_plans(plan, 4)) \
        == PARENT_PLAN_DIGESTS[plan]
