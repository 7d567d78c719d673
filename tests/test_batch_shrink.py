"""A filtered batch leaves its stage at its live bucket (PR 45; tier-1, on
the CPU: answers and counts, no timings).

``columnar/batch.compact`` keeps a filtered batch at its input's
capacity, so whatever consumed it — a keyed aggregation first — hashed,
probed and scattered every slot for the few rows that passed. Since
PR 45 the operators that drop rows (``PhysicalOp.drops_rows``: the
filters, and the fused stage that holds one) ask ``ops/base.
count_output`` to hand the batch on at ``columnar/batch.shrink_target``
of the row count it reads anyway: ONE ``columnar.batch.shrink`` launch,
no new read. Held here:

- the rule: two rungs a capacity (``// 64``, ``// 8``), each a function
  of the capacity alone, at least 16,384 lanes saved;
- the program over every column kind ``resize`` covers;
- a stream of mixed capacities (one batch cut to the low rung, the next
  not, the third to the high rung) through the hash-table aggregation,
  the sort-path aggregation, a hash join's probe and its build, the
  sort's concatenation and a host-route exchange split: the answers of
  the run without the shrink, row for row;
- q28 as a served task: ``counts.batch_shrinks`` = its filtered scan
  batches, the 18 cells exact, ``agg_distinct_groups`` unchanged, no
  read added;
- q3 and q65m where no filtered batch qualifies: 0 shrinks and the
  parent's programs, call for call;
- the launch budget of a filtered 65,536-row batch into a keyed
  aggregate: one program call more and no eager launch more.
"""

import decimal
import os

import jax
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar import batch as cb
from auron_tpu.columnar.schema import DataType
from auron_tpu.columnar.arrow_bridge import (schema_from_arrow, to_arrow,
                                             to_device)
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import trace
from auron_tpu.ops import base as ops_base
from auron_tpu.ops.agg import AggOp
from auron_tpu.ops.fused import FusedStageOp
from auron_tpu.ops.joins import HashJoinOp
from auron_tpu.ops.project import FilterOp
from auron_tpu.ops.sort import SortOp
from auron_tpu.parallel.exchange import ShuffleExchangeOp
from auron_tpu.parallel.partitioning import HashPartitioning
from auron_tpu.runtime.executor import collect
from tests.test_distinct_regroup import _bench
from tests.test_join_launches import _traced

C = ir.ColumnRef
ONE = ir.Literal(1, DataType.INT64)
SHRINK = "columnar.batch.shrink"


def _no_shrink(monkeypatch):
    """The shrink request taken out: every batch leaves as it came."""
    monkeypatch.setattr(ops_base, "shrink_batch", lambda batch, n: batch)


# -- the rule -----------------------------------------------------------------

CAPACITIES = (16_384, 32_768, 65_536, 131_072)


def _row_counts(cap):
    return (0, 1, cap // 64, cap // 64 + 1, cap // 8, cap // 8 + 1, cap)


@pytest.mark.parametrize("cap,n", [(cap, n) for cap in CAPACITIES
                                   for n in _row_counts(cap)])
def test_the_rule_is_two_rungs_of_the_capacity(cap, n):
    got = cb.shrink_target(cap, n)
    if cap <= 16_384 or n > cap // 8:
        assert got is None
    else:
        assert got == (cap // 64 if n <= cap // 64 else cap // 8)
        assert n <= got and cap - got >= cb.SHRINK_MIN_LANES


@pytest.mark.parametrize("cap", CAPACITIES)
def test_a_capacity_leaves_in_at_most_three_shapes(cap):
    """Every row count a batch can hold, not a sample: a shape keyed on
    the count's own power of two would compile whenever the data crosses
    one (S4)."""
    out = {cb.shrink_target(cap, n) or cap for n in range(cap + 1)}
    assert len(out) <= 3 and max(out) == cap
    assert out == ({cap} if cap <= 16_384
                   else {cap, cap // 8, cap // 64})


# -- the program, over every column kind ---------------------------------------

ROWS, CAP = 300, 32_768


def _kind_column(kind: str, rng) -> pa.Array:
    nulls = rng.random(ROWS) < 0.2
    ints = rng.integers(-1000, 1000, ROWS)
    words = [f"w{v}" * (1 + v % 3) for v in ints]
    if kind == "primitive":
        return pa.array(ints, pa.int64(), mask=nulls)
    if kind == "string":
        return pa.array(words, pa.string(), mask=nulls)
    if kind == "decimal128":
        return pa.array([None if m else decimal.Decimal(int(v)) * 10 ** 20
                         for v, m in zip(ints, nulls)],
                        pa.decimal128(30, 2))
    if kind == "list":
        return pa.array([None if m else list(range(v % 4))
                         for v, m in zip(ints, nulls)], pa.list_(pa.int64()))
    if kind == "string_list":
        return pa.array([None if m else [w] * (v % 3)
                         for v, w, m in zip(ints, words, nulls)],
                        pa.list_(pa.string()))
    if kind == "map":
        return pa.array([None if m else [(int(v), int(v) * 2)]
                         for v, m in zip(ints, nulls)],
                        pa.map_(pa.int64(), pa.int64()))
    if kind == "string_map":
        return pa.array([None if m else [(w, w + "!")]
                         for w, m in zip(words, nulls)],
                        pa.map_(pa.string(), pa.string()))
    assert kind == "struct"
    return pa.array([None if m else {"a": int(v), "b": w}
                     for v, w, m in zip(ints, words, nulls)],
                    pa.struct([("a", pa.int64()), ("b", pa.string())]))


KINDS = ("primitive", "string", "decimal128", "list", "string_list", "map",
         "string_map", "struct")


@pytest.mark.parametrize("kind", KINDS)
def test_the_program_is_the_prefix_of_every_leaf(kind):
    rb = pa.record_batch({"c": _kind_column(kind, np.random.default_rng(7)),
                          "row": pa.array(np.arange(ROWS), pa.int64())})
    batch, schema = to_device(rb, capacity=CAP)
    with trace.task_scope("shrink-" + kind) as acc:
        small = cb.shrink(batch, ROWS)
    assert small.capacity == CAP // 64 == 512
    assert dict(acc.calls_by_site) == {SHRINK: 1}
    want = jax.tree_util.tree_leaves(batch.columns)
    got = jax.tree_util.tree_leaves(small.columns)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == (512,) + w.shape[1:]
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w)[:512])
    assert isinstance(small.num_rows, jax.Array)
    assert int(small.num_rows) == ROWS
    assert to_arrow(small, schema).equals(rb)
    # a batch the rule keeps is handed back as it is, with no launch
    assert cb.shrink(batch, CAP // 8 + 1) is batch


# -- a stream of mixed capacities through what consumes it ---------------------

#: rows a batch (of capacity 32,768) and the rows of it the filter
#: passes: cut to 512, kept whole, cut to 4,096
STREAM = ((30_000, 300), (30_000, 20_000), (20_000, 3_000))


def _stream_batches():
    rng = np.random.default_rng(11)
    out, row0 = [], 0
    for rows, passing in STREAM:
        keep = np.zeros(rows, np.int64)
        keep[rng.choice(rows, passing, replace=False)] = 1
        k = rng.integers(0, 700, rows)
        out.append(pa.record_batch({
            "k": pa.array(k, pa.int64(), mask=rng.random(rows) < 0.05),
            "v": pa.array(rng.integers(-1000, 1000, rows), pa.int64(),
                          mask=rng.random(rows) < 0.1),
            "s": pa.array([f"s{x % 37}" for x in k], pa.string()),
            "d": pa.array([decimal.Decimal(int(x)) / 100
                           for x in rng.integers(0, 99999, rows)],
                          pa.decimal128(7, 2)),
            "keep": pa.array(keep, pa.int64()),
            "row": pa.array(np.arange(rows) + row0, pa.int64()),
        }))
        row0 += rows
    return out


def _filtered(fused: bool):
    rbs = _stream_batches()
    scan = MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                        capacity=CAP)
    flt = FilterOp(scan, [ir.BinaryExpr("==", C(4), ONE)])
    return FusedStageOp([flt]) if fused else flt


def _small_side():
    rb = pa.record_batch({
        "id": pa.array(np.arange(0, 700, 3), pa.int64()),
        "name": pa.array([f"n{i}" for i in range(0, 700, 3)], pa.string())})
    return MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=256)


AGGS = [ir.AggFunction("sum", C(1)), ir.AggFunction("count", C(1)),
        ir.AggFunction("sum", C(3)), ir.AggFunction("min", C(2))]


def _consumer(name: str, fused: bool):
    """(plan, partitions, config): the filtered stream into ``name``."""
    src = _filtered(fused)
    if name in ("hash_agg", "sort_agg"):
        backend = "hash" if name == "hash_agg" else "sort"
        return (AggOp(src, [C(0)], AGGS, mode="complete",
                      group_names=["k"],
                      agg_names=["sv", "cv", "sd", "ms"]),
                1, cfg.AuronConfig({cfg.HASHTABLE_BACKEND: backend}))
    if name == "join_probe":
        return HashJoinOp(src, _small_side(), [C(0)], [C(0)],
                          join_type="inner"), 1, None
    if name == "join_build":
        return HashJoinOp(_small_side(), src, [C(0)], [C(0)],
                          join_type="inner"), 1, None
    if name == "sort_concat":
        return SortOp(src, [ir.SortOrder(C(2), True, True),
                            ir.SortOrder(C(0), False, False)]), 1, None
    assert name == "exchange_split"
    return ShuffleExchangeOp(src, HashPartitioning((C(0),), 4),
                             input_partitions=1), 4, None


def _run(name, fused):
    plan, partitions, conf = _consumer(name, fused)
    with trace.task_scope(name) as acc:
        table = collect(plan, num_partitions=partitions, config=conf)
    return table, acc


#: the join's probe side and an exchange's child FOLD a fused chain into
#: their own programs (``_probe_fold``, ``_fold_spec``) and never run
#: ``FusedStageOp.execute``: there the unfused filter is the one that asks
CONSUMERS = [("hash_agg", True), ("hash_agg", False), ("sort_agg", True),
             ("sort_agg", False), ("join_probe", False),
             ("join_build", True), ("join_build", False),
             ("sort_concat", True), ("sort_concat", False),
             ("exchange_split", False)]


@pytest.mark.parametrize("name,fused", CONSUMERS)
def test_a_stream_of_mixed_capacities_answers_as_the_unshrunk_one(
        name, fused, monkeypatch):
    got, acc = _run(name, fused)
    assert acc.counts["batch_shrinks"] == 2
    assert acc.counts["batch_shrink_lanes"] == (CAP - 512) + (CAP - 4096)
    assert acc.calls_by_site[SHRINK] == 2
    _no_shrink(monkeypatch)
    want, plain = _run(name, fused)
    assert plain.counts["batch_shrinks"] == 0
    assert SHRINK not in plain.calls_by_site
    # no read added: the shrink rides the count that was read anyway
    for key in ("row_syncs", "readbacks"):
        assert acc.counts[key] == plain.counts[key], key
    assert got.num_rows == want.num_rows > 0
    if name in ("hash_agg", "sort_agg"):
        assert got.num_rows == 701          # 700 keys and the null key
    if name != "sort_concat":               # a sort's order is its answer
        order = [(c, "ascending") for c in got.column_names]
        got, want = got.sort_by(order), want.sort_by(order)
    assert got.equals(want)


# -- served tasks: q28 (every filtered scan batch qualifies), q3, q65m (none) --

Q28_SPLIT, Q28_BATCH = 72_000, 36_000


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process. q28 over one split of two scan batches
    of 36,000 rows at the chip's 65,536-row scan capacity; q3 and q65m
    over one small split beside their dimension tables."""
    cell, compare, datagen = _bench()
    from auron_tpu.frontend import Session
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.03)
    fact = arrow["store_sales"]
    wide = os.path.join(root, "wide.parquet")
    pq.write_table(fact.slice(0, Q28_SPLIT), wide, row_group_size=Q28_BATCH)
    small = [p for p, _lo, _n in datagen.write_splits(
        root, "store_sales", fact, 16_384)][:1]
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    conf = cfg.get_config()
    conf.set(cfg.SCAN_BATCH_ROWS, 1 << 16)
    session = Session()
    plans = {name: cell.load_module("plans", name)
             for name in ("q28", "q3", "q65m")}
    tasks = {"q28": plans["q28"].build(session, {}, [wide], 1),
             "q3": plans["q3"].build(session, dims, small, 1),
             "q65m": plans["q65m"].build(session, dims, small, 1)}
    rows = {"q28": fact.slice(0, Q28_SPLIT), "q3": fact.slice(0, 16_384),
            "q65m": fact.slice(0, 16_384)}
    server = AuronServer()
    server.serve_background()
    host, port = server.address

    def run(name):
        table, done = AuronClient(host, port, timeout_s=600) \
            .execute(tasks[name].task_bytes(0))
        return table, done["cost_ledger"]["counts"]

    run.oracle = lambda name: plans[name].oracle(
        dict(arrow, store_sales=rows[name]))
    run.rows = rows
    run.q28 = plans["q28"]
    run.compare = compare
    yield run
    server.shutdown()
    server.server_close()
    session.close()
    conf.unset(cfg.SCAN_BATCH_ROWS)


def _q28_passing(served):
    """Rows that pass each band's filter in each scan batch, from the
    published text's predicates over the batch's own rows."""
    q28, fact = served.q28, served.rows["q28"]
    out = []
    for (q_lo, q_hi), *froms in q28.BANDS:
        for lo in range(0, Q28_SPLIT, Q28_BATCH):
            ss = fact.slice(lo, Q28_BATCH)
            either = None
            for (name, width), start in zip(q28.ARMS, froms):
                arm = q28._between(ss[name], start, start + width)
                either = arm if either is None else pc.or_(either, arm)
            mask = pc.and_(q28._between(ss["ss_quantity"], q_lo, q_hi),
                           either)
            out.append(pc.sum(mask.cast(pa.int64())).as_py())
    return out


def test_q28_hands_every_filtered_scan_batch_on_at_its_live_bucket(
        served, monkeypatch):
    passing = _q28_passing(served)
    assert len(passing) == 12 and 0 < min(passing) <= max(passing) <= 1024
    table, counts = served("q28")
    res = served.compare.compare_tables(table, served.oracle("q28"))
    assert served.compare.answer_ok(res), res
    assert res["cells"] == 18 and table.num_rows == 1
    qualifying = [n for n in passing
                  if cb.shrink_target(1 << 16, n) is not None]
    assert counts["batch_shrinks"] == len(qualifying) == 12
    assert counts["batch_shrink_lanes"] == 12 * ((1 << 16) - 1024)
    assert counts["program_calls_by_site"][SHRINK] == 12
    # the first aggregate's twelve steps run at the shrunken capacity:
    # nothing grew, the distinct (band, price) pairs are the answer's
    distinct = sum(table.column(f"B{i}_CNTD")[0].as_py()
                   for i in range(1, 7))
    assert counts["agg_distinct_groups"] == distinct > 0
    _no_shrink(monkeypatch)
    plain_table, plain = served("q28")
    assert plain["batch_shrinks"] == 0
    assert plain_table.equals(table)
    assert plain["agg_distinct_groups"] == counts["agg_distinct_groups"]
    for key in ("row_syncs", "readbacks", "agg_hash_batches",
                "agg_sort_batches", "agg_groups", "cross_join_rows"):
        assert plain[key] == counts[key], key
    sites = dict(counts["program_calls_by_site"])
    assert sites.pop(SHRINK) == 12
    assert sites == plain["program_calls_by_site"]
    assert counts["program_calls"] == plain["program_calls"] + 12


@pytest.mark.parametrize("plan", ["q3", "q65m"])
def test_a_plan_without_a_qualifying_batch_runs_the_parents_programs(
        plan, served, monkeypatch):
    served(plan)        # the cost history's first observation re-plans a
    #                     probe fold: compare the second run and the third
    table, counts = served(plan)
    res = served.compare.compare_tables(table, served.oracle(plan))
    assert served.compare.answer_ok(res), res
    assert counts["batch_shrinks"] == 0 == counts["batch_shrink_lanes"]
    _no_shrink(monkeypatch)
    plain_table, plain = served(plan)
    assert plain_table.equals(table)
    assert counts["program_calls_by_site"] == plain["program_calls_by_site"]
    for key in ("program_calls", "row_syncs", "readbacks"):
        assert counts[key] == plain[key], key


# -- the launch budget -------------------------------------------------------

SPANS = ("auron:op/filter", "auron:op/fused_stage", "auron:op/agg")


def _filtered_aggregate(fused: bool):
    """One full 65,536-row batch, 790 of its rows past the filter, into
    a keyed aggregate with q28's three accumulators."""
    rng = np.random.default_rng(3)
    rows = 1 << 16
    keep = np.zeros(rows, np.int64)
    keep[rng.choice(rows, 790, replace=False)] = 1
    rb = pa.record_batch({
        "price": pa.array(rng.integers(100, 25_000, rows), pa.int64()),
        "keep": pa.array(keep, pa.int64())})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=rows)
    flt = FilterOp(scan, [ir.BinaryExpr("==", C(1), ONE)])
    src = FusedStageOp([flt]) if fused else flt
    return AggOp(src, [C(0)], [ir.AggFunction("avg", C(0)),
                               ir.AggFunction("count", C(0))],
                 mode="partial", group_names=["price"],
                 agg_names=["avg", "cnt"])


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_a_filtered_batch_into_a_keyed_aggregate_costs_one_call_more(
        fused, tmp_path, monkeypatch):
    conf = cfg.AuronConfig({cfg.HASHTABLE_BACKEND: "hash"})

    def run():
        with trace.task_scope("budget") as acc:
            table = collect(_filtered_aggregate(fused), config=conf)
        return table, acc

    run()                                        # warm: no compiles
    (table, acc), (eager, engine) = _traced(run, tmp_path / "on", SPANS)
    assert "auron_columnar_batch_shrink" in engine
    assert acc.counts["batch_shrinks"] == 1
    assert acc.counts["batch_shrink_lanes"] == (1 << 16) - 1024
    _no_shrink(monkeypatch)
    run()
    (plain_table, plain), (plain_eager, _) = _traced(run, tmp_path / "off",
                                                     SPANS)
    # the shrink launches nothing eager: what these spans launched
    # outside a program before (the aggregation's state set-up, the
    # stage's carries), they launch now, event for event
    assert sorted(eager) == sorted(plain_eager)
    assert acc.counts["program_calls"] == plain.counts["program_calls"] + 1
    sites = dict(acc.calls_by_site)
    assert sites.pop(SHRINK) == 1
    assert sites == dict(plain.calls_by_site)
    for key in ("row_syncs", "readbacks"):
        assert acc.counts[key] == plain.counts[key], key
    order = [("price", "ascending")]
    assert table.sort_by(order).equals(plain_table.sort_by(order))
    assert table.num_rows > 700
