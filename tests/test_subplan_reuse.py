"""Common-subplan reuse (PR 37; tier-1, on the CPU: plans, counts and
answers, no timings).

``ir/reuse.py`` finds the subtrees of a decoded plan that several parents
read, by their own bytes; ``ir/planner.py`` plans each once and hangs a
``SubplanReadOp`` (``ops/reuse.py``) where every parent had it. Held
here: what the pass shares and what it leaves alone, on the benchmark's
eight plans and on plans made for the purpose; that the producer runs
once a partition whoever asks first and however little; that nobody's
batches are destroyed under another reader; and that the held result is
let go.
"""

import os
import sys
from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar.arrow_bridge import (schema_from_arrow, to_arrow,
                                             to_device)
from auron_tpu.frontend import Session, col, functions as F, lit, \
    scalar_subquery
from auron_tpu.ir import pb
from auron_tpu.ir.planner import PhysicalPlanner
from auron_tpu.ir.pruning import prune_scan_columns
from auron_tpu.ir.reuse import find_shared_subplans
from auron_tpu.obs import trace
from auron_tpu.ops.base import (ExecContext, PhysicalOp, TaskCancelled,
                                yields_owned_batches)
from auron_tpu.ops.limit import LimitOp
from auron_tpu.ops.reuse import SharedSubplan, SubplanReadOp

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
#: the benchmark's plans that read one subtree from two parents ...
SHARING = ("q65", "q65m")
#: ... and those with no duplicate: the check plans and the star joins
PLAIN = ("q65sa", "q65sam", "q3", "q42", "q52", "q55")


def _walk(op):
    yield op
    for c in op.children:
        yield from _walk(c)


def _handles(op):
    return [o for o in _walk(op) if isinstance(o, SubplanReadOp)]


def _rows(op, partition=0, ctx=None):
    """The rows ``op`` yields for one partition, as a pandas frame."""
    ctx = ctx or ExecContext()
    batches = [to_arrow(b, op.schema()) for b in op.execute(partition, ctx)]
    return pa.Table.from_batches(batches).to_pandas() if batches else None


# -- the benchmark's plans ----------------------------------------------------

@pytest.fixture(scope="module")
def bench_plans(tmp_path_factory):
    """The eight plans' decoded, pruned ``PlanNode``s over one split."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    import pyarrow.parquet as pq
    from harness import cell, datagen
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    path = os.path.join(root, "store_sales_0000.parquet")
    pq.write_table(arrow["store_sales"].slice(0, 8_192), path)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    session = Session()
    plans = {}
    for name in SHARING + PLAIN:
        task = pb.TaskDefinition.FromString(
            cell.load_module("plans", name).build(
                session, dims, [path], 1).task_bytes(0))
        plans[name] = prune_scan_columns(task.plan)
    yield plans
    session.close()


@pytest.mark.parametrize("name", SHARING)
def test_the_wide_aggregation_is_planned_once(name, bench_plans):
    shared = find_shared_subplans(bench_plans[name])
    assert len(shared) == 1
    agg = pb.AggNode.FromString(next(iter(shared)))
    assert list(agg.group_names) == ["ss_store_sk", "ss_item_sk"]
    op = PhysicalPlanner().plan_tree(bench_plans[name])
    handles = _handles(op)
    assert len(handles) == 2
    assert handles[0].shared is handles[1].shared
    assert handles[0].shared.consumers == 2
    assert [len(h.children) for h in handles] == [1, 0]
    # one fact scan and one (store, item) aggregation in the whole tree
    names = Counter(o.name for o in _walk(op))
    assert names["parquet_scan"] == 4 and names["agg"] == 2
    assert op.tree_string().count("ss_sales_price']") == 1


@pytest.mark.parametrize("name", PLAIN)
def test_a_plan_without_a_duplicate_is_planned_as_before(
        name, bench_plans, monkeypatch):
    assert find_shared_subplans(bench_plans[name]) == frozenset()
    op = PhysicalPlanner().plan_tree(bench_plans[name])
    assert not _handles(op)
    from auron_tpu.ir import reuse
    monkeypatch.setattr(reuse, "find_shared_subplans",
                        lambda plan: frozenset())
    before = PhysicalPlanner().plan_tree(bench_plans[name])
    assert op.tree_string() == before.tree_string()


# -- what the pass shares, on plans made for the purpose ----------------------

def _table(n=600, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table({
        "g": pa.array(rng.integers(0, 6, n), pa.int64()),
        "k": pa.array(rng.integers(0, 40, n), pa.int64()),
        "v": pa.array(rng.normal(10.0, 4.0, n).round(3), pa.float64())})


@pytest.fixture()
def session():
    s = Session()
    s.register("t", _table())
    yield s
    s.close()


def _sa(session, cut=0.0):
    return (session.table("t").filter(col("v") > lit(cut))
            .group_by("g", "k").agg(F.sum(col("v")).alias("s")))


def _under_average(session, sa):
    """q65's shape: the pairs under their group's average."""
    sb = sa.group_by("g").agg(F.avg(col("s")).alias("a"))
    return sa.join(sb, "g").filter(col("s") <= col("a"))


def _under_average_oracle(table, cut=0.0):
    t = table.to_pandas()
    sa = t[t.v > cut].groupby(["g", "k"], as_index=False).v.sum() \
        .rename(columns={"v": "s"})
    sb = sa.groupby("g", as_index=False).s.mean().rename(columns={"s": "a"})
    j = sa.merge(sb, on="g")
    return j[j.s <= j.a].sort_values(["g", "k"]).reset_index(drop=True)


def _count_scans(monkeypatch):
    """Executions of a ``memory_scan``, by partition."""
    from auron_tpu.io.parquet import MemoryScanOp
    runs = Counter()
    real = MemoryScanOp.execute

    def execute(self, partition, ctx):
        runs[partition] += 1
        return real(self, partition, ctx)

    monkeypatch.setattr(MemoryScanOp, "execute", execute)
    return runs


def test_two_parents_read_one_run_of_the_producer(session, monkeypatch):
    runs = _count_scans(monkeypatch)
    op = session.plan_physical(_under_average(session, _sa(session)))
    handles = _handles(op)
    assert len(handles) == 2
    with trace.task_scope() as acc:
        got = _rows(op).sort_values(["g", "k"]).reset_index(drop=True)
    want = _under_average_oracle(_table())
    assert len(got) == len(want) > 10
    assert (got.g.values == want.g.values).all()
    assert (got.k.values == want.k.values).all()
    assert np.allclose(got.s.values, want.s.values, rtol=1e-12)
    assert runs == {0: 1}, "the table is scanned once"
    assert acc.counts["subplan_reuse_hits"] == 1
    assert handles[0].shared.held_partitions() == [], "let go"


def test_subtrees_that_differ_in_one_literal_are_not_shared(session,
                                                           monkeypatch):
    runs = _count_scans(monkeypatch)
    sa, other = _sa(session, 0.0), _sa(session, 0.5)
    sb = other.group_by("g").agg(F.avg(col("s")).alias("a"))
    df = sa.join(sb, "g").filter(col("s") <= col("a"))
    assert find_shared_subplans(df.plan) == frozenset()
    op = session.plan_physical(df)
    assert not _handles(op)
    with trace.task_scope() as acc:
        assert len(_rows(op)) > 0
    assert runs == {0: 2} and acc.counts["subplan_reuse_hits"] == 0


def _scan(**kw):
    return pb.PlanNode(memory_scan=pb.MemoryScanNode(table_name="t", **kw))


def _agg_over(child, mode="complete"):
    from auron_tpu.exprs import ir
    from auron_tpu.ir import serde
    return pb.PlanNode(agg=pb.AggNode(
        child=child, mode=mode,
        group_exprs=[serde.expr_to_proto(ir.ColumnRef(0))],
        aggs=[serde.agg_to_proto(ir.AggFunction("sum", ir.ColumnRef(2)))],
        group_names=["g"], agg_names=["s"]))


def _filter_over(child, expr):
    return pb.PlanNode(filter=pb.FilterNode(child=child, predicates=[expr]))


def _twice(node):
    return pb.PlanNode(union=pb.UnionNode(children=[node, node]))


def _left_alone():
    from auron_tpu.exprs import ir
    from auron_tpu.ir import serde
    positive = serde.expr_to_proto(ir.BinaryExpr(
        ">", ir.ColumnRef(2), ir.Literal(0.0, ir.DataType.FLOAT64)))
    row_num = pb.ExprNode(nullary=pb.NullaryE(kind="row_num"))
    udf = pb.ExprNode(host_udf=pb.HostUDFE(registry_name="f"))
    schema = pb.SchemaP()
    return {
        "root_is_a_filter": _filter_over(_scan(), positive),
        "root_is_a_partial_agg": _agg_over(_scan(), mode="partial"),
        "kafka_scan_leaf": _agg_over(pb.PlanNode(kafka_scan=pb.KafkaScanNode(
            topic="t", bootstrap="b", schema=schema))),
        "ipc_reader_leaf": _agg_over(pb.PlanNode(ipc_reader=pb.IpcReaderNode(
            resource_id="r", schema=schema))),
        "broadcast_read": _agg_over(pb.PlanNode(
            broadcast_join=pb.BroadcastJoinNode(
                probe=_scan(), build_resource_id="b"))),
        "row_number": _agg_over(_filter_over(_scan(), row_num)),
        "host_udf": _agg_over(_filter_over(_scan(), udf)),
        "host_udaf": pb.PlanNode(agg=pb.AggNode(
            child=_scan(), mode="complete",
            aggs=[pb.AggFunctionP(fn="udaf:mine")], agg_names=["x"])),
        "debug_node": _agg_over(pb.PlanNode(debug=pb.DebugNode(
            child=_scan(), label="x"))),
    }


@pytest.mark.parametrize("case", sorted(_left_alone()))
def test_a_duplicate_the_pass_does_not_understand_is_left_alone(case):
    assert find_shared_subplans(_twice(_left_alone()[case])) == frozenset()


def test_a_duplicate_it_understands_is_shared_from_its_largest_root():
    from auron_tpu.exprs import ir
    from auron_tpu.ir import serde
    positive = serde.expr_to_proto(ir.BinaryExpr(
        ">", ir.ColumnRef(2), ir.Literal(0.0, ir.DataType.FLOAT64)))
    inner = _agg_over(_filter_over(_scan(), positive))
    outer = _agg_over(inner, mode="final")
    # the outer aggregation is shared; the inner one, met once with it,
    # is not; a third copy of the inner one outside is
    assert find_shared_subplans(_twice(outer)) == {
        outer.agg.SerializeToString(deterministic=True)}
    three = pb.PlanNode(union=pb.UnionNode(children=[outer, outer, inner]))
    assert find_shared_subplans(three) == {
        outer.agg.SerializeToString(deterministic=True),
        inner.agg.SerializeToString(deterministic=True)}
    # inside a duplicate that is left alone, the pass goes on
    kafka = pb.PlanNode(kafka_scan=pb.KafkaScanNode(topic="t"))
    mixed = _agg_over(pb.PlanNode(union=pb.UnionNode(
        children=[kafka, inner])), mode="final")
    assert find_shared_subplans(_twice(mixed)) == {
        inner.agg.SerializeToString(deterministic=True)}


def test_three_parents_of_one_subplan(session, monkeypatch):
    runs = _count_scans(monkeypatch)
    sa = _sa(session)
    op = session.plan_physical(sa.union(sa).union(sa))
    handles = _handles(op)
    assert len(handles) == 3
    assert {id(h.shared) for h in handles} == {id(handles[0].shared)}
    assert handles[0].shared.consumers == 3
    with trace.task_scope() as acc:
        got = _rows(op)
    once = _table().to_pandas()
    once = once[once.v > 0].groupby(["g", "k"]).v.sum()
    assert len(got) == 3 * len(once)
    assert np.isclose(got.s.sum(), 3 * once.sum(), rtol=1e-12)
    assert runs == {0: 1} and acc.counts["subplan_reuse_hits"] == 2
    assert handles[0].shared.held_partitions() == []


def test_a_plan_with_a_scalar_subquery(session, monkeypatch):
    """The binder plans the substituted tree through the same pass; the
    subquery's own plan, resolved first, is a tree of its own."""
    runs = _count_scans(monkeypatch)
    sa = _sa(session)
    floor = scalar_subquery(
        session.table("t").group_by().agg(F.min(col("v")).alias("m")))
    df = _under_average(session, sa).filter(col("s") > floor)
    op = session.plan_physical(df)
    assert type(op).__name__ == "ScalarSubqueryBinderOp"
    with trace.task_scope() as acc:
        got = _rows(op).sort_values(["g", "k"]).reset_index(drop=True)
    want = _under_average_oracle(_table())
    want = want[want.s > _table().to_pandas().v.min()]
    assert len(got) == len(want) > 10
    assert np.allclose(got.s.values, want.s.values, rtol=1e-12)
    assert len(_handles(op)) == 2
    # the subquery's scan, and ONE scan for both parents of sa
    assert runs == {0: 2} and acc.counts["subplan_reuse_hits"] == 1


# -- the operator, over a producer that counts its runs -----------------------

class _Producer(PhysicalOp):
    """Three fresh batches a partition, a run counted."""

    name = "stub"

    def __init__(self, partitions=1, cancel_after=None):
        self.runs = Counter()
        self.partitions = partitions
        self.cancel_after = cancel_after
        self._arrow = pa.schema([("x", pa.int64())])

    def schema(self):
        return schema_from_arrow(self._arrow)

    def execute(self, partition, ctx):
        self.runs[partition] += 1
        for i in range(3):
            if self.cancel_after == i:
                ctx.cancel()
            lo = 100 * partition + 10 * i
            yield to_device(pa.record_batch(
                [pa.array(range(lo, lo + 4), pa.int64())],
                schema=self._arrow), capacity=16)[0]


def _shared_handles(n, **kw):
    producer = _Producer(**kw)
    shared = SharedSubplan()
    handles = [SubplanReadOp(shared, producer)]
    handles += [SubplanReadOp(shared) for _ in range(n - 1)]
    return producer, shared, handles


def _values(op, partition=0, ctx=None):
    frame = _rows(op, partition, ctx)
    return [] if frame is None else frame.x.tolist()


def test_a_limit_over_one_handle_leaves_the_other_the_whole_result():
    producer, shared, (a, b) = _shared_handles(2)
    assert _values(LimitOp(a, 2)) == [0, 1]
    assert producer.runs == {0: 1}
    assert shared.held_partitions() == [0], "b has yet to read"
    assert _values(b) == [0, 1, 2, 3, 10, 11, 12, 13, 20, 21, 22, 23]
    assert producer.runs == {0: 1}
    assert shared.held_partitions() == []


def test_a_reader_that_stops_after_one_batch_changes_nothing():
    producer, shared, (a, b) = _shared_handles(2)
    stream = iter(a.execute(0, ExecContext()))
    assert next(stream) is not None
    stream.close()          # a consumer that stops after one batch
    assert len(_values(b)) == 12 and producer.runs == {0: 1}
    assert shared.held_partitions() == []


def test_two_partitions_keep_two_results():
    producer, shared, (a, b) = _shared_handles(2, partitions=2)
    first = {p: _values(a, p) for p in (0, 1)}
    assert first[0][0] == 0 and first[1][0] == 100
    assert shared.held_partitions() == [0, 1]
    assert _values(b, 1) == first[1]
    assert shared.held_partitions() == [0]
    assert _values(b, 0) == first[0]
    assert shared.held_partitions() == []
    assert producer.runs == {0: 1, 1: 1}


def test_a_second_pass_over_the_same_tree_runs_the_producer_again():
    """A retry executes the tree again: nothing stale is served."""
    producer, shared, (a, b) = _shared_handles(2)
    for _ in range(2):
        assert _values(a) == _values(b)
    assert producer.runs == {0: 2} and shared.held_partitions() == []


def test_readers_on_many_threads_share_one_run_a_partition():
    """More threads than cores, each reading one (handle, partition): a
    lost update would show as a second run, a short read or a result
    still held."""
    import threading
    parts, n_handles = 4, 6
    producer, shared, handles = _shared_handles(n_handles, partitions=parts)
    got, errors = {}, []
    start = threading.Barrier(parts * n_handles)

    def reader(i, p):
        try:
            start.wait(timeout=30)
            got[i, p] = _values(handles[i], p)
        except Exception as e:       # the assertion below reports it
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i, p))
               for i in range(n_handles) for p in range(parts)]
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(before)
    assert not errors and not any(t.is_alive() for t in threads)
    assert producer.runs == {p: 1 for p in range(parts)}
    for (i, p), values in got.items():
        assert values == got[0, p] and len(values) == 12
        assert values[0] == 100 * p
    assert len(got) == parts * n_handles
    assert shared.held_partitions() == [] and not shared._held


class _Donating(PhysicalOp):
    """What a donating consumer does to its input on the chip, where
    ``yields_owned_batches`` allows it: the input's buffers are gone once
    the batch is consumed. (On the CPU XLA ignores a donation.)"""

    name = "donating"

    def __init__(self, child):
        self.child = child

    def schema(self):
        return self.child.schema()

    def execute(self, partition, ctx):
        import jax
        donate = yields_owned_batches(self.child)
        for batch in self.child.execute(partition, ctx):
            out = jax.tree_util.tree_map(lambda x: x + 0, batch)
            if donate:
                for leaf in jax.tree_util.tree_leaves(batch):
                    leaf.delete()
            yield out


def test_no_consumer_destroys_a_batch_another_parent_reads():
    producer, shared, (a, b) = _shared_handles(2)
    assert not yields_owned_batches(a) and not yields_owned_batches(b)
    assert not yields_owned_batches(LimitOp(a, 5)), "pass-throughs inherit"
    assert yields_owned_batches(producer)
    assert _values(_Donating(a)) == _values(b)
    assert producer.runs == {0: 1}
    # the control: over an operator that owns its output the same
    # consumer leaves nothing behind
    direct = _Producer()
    kept = []

    class _Tee(PhysicalOp):
        name = "tee"

        def schema(self):
            return direct.schema()

        def execute(self, partition, ctx):
            for batch in direct.execute(partition, ctx):
                kept.append(batch)
                yield batch

    assert len(_values(_Donating(_Tee()))) == 12
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(kept[0].columns[0].data)


def test_a_cancelled_task_lets_the_result_go():
    # cancelled while the producer runs: nothing is kept
    producer, shared, (a, b) = _shared_handles(2, cancel_after=1)
    with pytest.raises(TaskCancelled):
        _values(a)
    assert shared.held_partitions() == [] and not shared._held
    # cancelled between the two reads: the held result goes with the
    # reader that unwinds, though another handle never came
    producer, shared, (a, b) = _shared_handles(2)
    ctx = ExecContext()
    stream = iter(a.execute(0, ctx))
    next(stream)
    assert shared.held_partitions() == [0]
    ctx.cancel()
    stream.close()
    assert shared.held_partitions() == [] and not shared._held


def test_the_counter_is_a_ledger_count_and_no_fault_to_a_client():
    assert "subplan_reuse_hits" in trace.COUNT_KEYS
    assert trace.TaskAccumulator().sealed(0.0)["counts"][
        "subplan_reuse_hits"] == 0
    # benchmark/run.py fails a task whose frame has a nonzero leaf named
    # after a fault
    assert not any(n in "subplan_reuse_hits"
                   for n in ("retr", "recompute", "demot", "interpret"))
