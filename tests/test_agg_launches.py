"""The aggregation's launch budget (PR 46; tier-1, on the CPU: counts only).

Everything ``AggOp`` does to a device array runs inside a program handed
out by ``runtime/programs.py``: a batch's group keys and contributions
are evaluated inside the program that consumes them
(``hashtable.agg_step`` on the hash table, ``ops.agg.batch_reduce`` on
the sort path and without keys), a table is set up by ONE
``hashtable.agg_init`` and an operator's output — the table's export,
the cut to its occupancy bucket, every function's finalisation — is ONE
``ops.agg.emit``. Before PR 46 a q28 task launched 480 eager
single-primitive programs round the 42 engine programs of its 24
aggregation operators (table set-up 168, emit 162, contributions 132,
``ord_base`` 18); this file is the guard that keeps such glue from
growing back.

A launch is a ``PjitFunction(<name>)`` event of the profiler's host plane
whose innermost operator span is ``auron:op/agg``; it is *eager* when
``<name>`` is no ``auron_<site>`` program. (jax writes two events a call,
so every count here is in events.) The budget of one operator over B
batches: B steps or reduces, at most one set-up, one emit — growths and
the sort path's merges apart, which ``agg_state_grows`` and the merge
site count as they did.
"""

import decimal
from collections import Counter

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import trace
from auron_tpu.ops.agg import AggOp
from auron_tpu.runtime.executor import collect
from tests.test_join_launches import _traced

C = ir.ColumnRef
F = ir.AggFunction
SPANS = ("auron:op/agg",)
STEP, GROW, INIT, EXPORT = ("hashtable.agg_step", "hashtable.agg_grow",
                            "hashtable.agg_init", "hashtable.agg_export")
PASS = "ops.agg.passthrough"
REDUCE, MERGE, EMIT = ("ops.agg.batch_reduce", "ops.agg.state_merge",
                       "ops.agg.emit")
ROWS, CAP = 600, 1024
MONEY = pa.decimal128(7, 2)

#: the counts the parent commit's DONE frame carried for each case below
#: (no read added, none removed, the same path taken): row_syncs,
#: readbacks, agg_hash_batches, agg_sort_batches, agg_state_grows
PARENT = {
    "int_key": (6, 5, 3, 0, 0),
    "string_key": (6, 5, 3, 0, 0),
    "price_partial": (7, 4, 2, 0, 0),
    "price_partial_merge": (9, 5, 3, 0, 0),
    "price_final": (9, 5, 3, 0, 0),
    "keyless_partial": (11, 6, 3, 1, 0),
    "keyless_final": (13, 7, 3, 2, 0),
    "sort_path": (5, 5, 0, 2, 0),
    "grows": (6, 9, 3, 0, 3),
    "partial_skip": (14, 7, 1, 0, 0),
}


def _batches(n, make):
    return [[pa.record_batch(make(np.random.default_rng(7 + i), i))
             for i in range(n)]]


def _scan(parts):
    return MemoryScanOp(parts, schema_from_arrow(parts[0][0].schema),
                        capacity=CAP)


def _prices(rng, _i):
    cents = rng.integers(100, 400, ROWS)
    mask = rng.random(ROWS) < 0.1
    return {"price": pa.array(
        [None if m else decimal.Decimal(int(c)) / 100
         for c, m in zip(cents, mask)], MONEY)}


def _price_chain(depth: int):
    """The first ``depth`` aggregates of one band of q28 — Spark's plan of
    ``avg(x), count(x), count(DISTINCT x)`` without keys: ``partial`` by
    x, ``partial_merge`` by x, the keyless node that merges two functions
    and starts the third over the deduplicated column, ``final``."""
    parts = _batches(2, _prices)
    table = pa.Table.from_batches(parts[0])
    fns = [F("avg", C(0)), F("count", C(0))]
    names = ["avg", "cnt"]
    op = AggOp(_scan(parts), [C(0)], fns, mode="partial",
               group_names=["x"], agg_names=names)
    if depth >= 2:
        op = AggOp(op, [C(0)], [F("avg"), F("count")], mode="partial_merge",
                   group_names=["x"], agg_names=names)
    if depth >= 3:
        op = AggOp(op, [], [F("avg"), F("count"),
                            F("count", C(0), mode="partial")],
                   mode="partial_merge", agg_names=names + ["cntd"])
    if depth >= 4:
        op = AggOp(op, [], [F("avg"), F("count"), F("count")], mode="final",
                   agg_names=names + ["cntd"])
    return op, table


def _price_final():
    parts = _batches(2, _prices)
    fns = [F("avg", C(0)), F("count", C(0))]
    op = AggOp(_scan(parts), [C(0)], fns, mode="partial",
               group_names=["x"], agg_names=["avg", "cnt"])
    op = AggOp(op, [C(0)], [F("avg"), F("count")], mode="final",
               group_names=["x"], agg_names=["avg", "cnt"])
    return op, pa.Table.from_batches(parts[0])


def _int_key():
    def make(rng, _i):
        return {"k": pa.array(rng.integers(0, 40, ROWS), pa.int64(),
                              mask=rng.random(ROWS) < 0.05),
                "v": pa.array(rng.integers(-50, 50, ROWS), pa.int64(),
                              mask=rng.random(ROWS) < 0.1),
                "w": pa.array(rng.integers(0, 1000, ROWS), pa.int64())}
    parts = _batches(3, make)
    fns = [F("sum", C(1)), F("count", C(1)), F("min", C(1)),
           F("max", C(1)), F("first", C(2))]
    op = AggOp(_scan(parts), [C(0)], fns, mode="complete", group_names=["k"],
               agg_names=["s", "c", "lo", "hi", "f"])
    return op, pa.Table.from_batches(parts[0])


def _string_key():
    def make(rng, i):
        # the second batch's keys are wider than the table's store, the
        # third's narrower: the step widens the one and pads the other
        tail = ("", "-a-longer-tail", "")[i]
        return {"k": pa.array([f"k{v:02d}{tail}"
                               for v in rng.integers(0, 30, ROWS)],
                              pa.string(), mask=rng.random(ROWS) < 0.05),
                "v": pa.array(rng.integers(0, 90, ROWS), pa.int64())}
    parts = _batches(3, make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("count", C(1))],
               mode="complete", group_names=["k"], agg_names=["s", "c"])
    return op, pa.Table.from_batches(parts[0])


def _sort_path():
    def make(rng, _i):
        return {"k": pa.array(rng.integers(0, 40, ROWS), pa.int64()),
                "v": pa.array(np.round(rng.normal(0, 9, ROWS), 2),
                              pa.float64(), mask=rng.random(ROWS) < 0.1)}
    parts = _batches(2, make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("avg", C(1))],
               mode="complete", group_names=["k"], agg_names=["s", "a"])
    return op, pa.Table.from_batches(parts[0])


def _grows():
    def make(rng, i):
        return {"k": pa.array(rng.integers(0, 900, ROWS) + 300 * i,
                              pa.int64()),
                "v": pa.array(rng.integers(0, 9, ROWS), pa.int64())}
    parts = _batches(3, make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("count", C(1))],
               mode="complete", group_names=["k"], agg_names=["s", "c"],
               initial_capacity=256)
    return op, pa.Table.from_batches(parts[0])


def _partial_skip():
    """Every row its own group: the partial aggregate decides after its
    first batch (``auron.agg.partial_skip.min_rows`` lowered to one
    batch) to stop merging, emits its table and passes the two other
    batches through in state layout."""
    def make(rng, i):
        return {"k": pa.array(np.arange(ROWS) + ROWS * i, pa.int64()),
                "v": pa.array(rng.integers(0, 9, ROWS), pa.int64())}
    parts = _batches(3, make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("count", C(1))],
               mode="partial", group_names=["k"], agg_names=["s", "c"])
    return op, pa.Table.from_batches(parts[0])


#: case -> (plan, aggregation operators, batches into the first)
CASES = {
    "int_key": (_int_key, 1, 3),
    "string_key": (_string_key, 1, 3),
    "price_partial": (lambda: _price_chain(1), 1, 2),
    "price_partial_merge": (lambda: _price_chain(2), 2, 2),
    "price_final": (_price_final, 2, 2),
    "keyless_partial": (lambda: _price_chain(3), 3, 2),
    "keyless_final": (lambda: _price_chain(4), 4, 2),
    "sort_path": (_sort_path, 1, 2),
    "grows": (_grows, 1, 3),
    "partial_skip": (_partial_skip, 1, 3),
}


def _oracle(case: str, table: pa.Table) -> pa.Table:
    """The plain reference: Acero's group-by over the batches' rows."""
    if case == "int_key":
        return table.group_by("k").aggregate(
            [("v", "sum"), ("v", "count"), ("v", "min"), ("v", "max")]) \
            .rename_columns(["k", "s", "c", "lo", "hi"])
    if case in ("string_key", "grows"):
        return table.group_by("k").aggregate(
            [("v", "sum"), ("v", "count")]).rename_columns(["k", "s", "c"])
    if case == "sort_path":
        return table.group_by("k").aggregate(
            [("v", "sum"), ("v", "mean")]).rename_columns(["k", "s", "a"])
    raise AssertionError(case)


def _check_answer(case: str, got: pa.Table, table: pa.Table):
    if case == "partial_skip":
        # one state row a row, merged or passed through
        assert sorted(zip(*(got[c].to_pylist() for c in got.column_names))) \
            == sorted((k, v, True, 1) for k, v in zip(
                table["k"].to_pylist(), table["v"].to_pylist()))
        return
    if case in ("int_key", "string_key", "grows", "sort_path"):
        want = _oracle(case, table)
        if case == "int_key":
            # 'first' has no Acero twin: any value of the group is not
            # enough, it is the group's first row's
            first = {}
            for k, w in zip(table["k"].to_pylist(), table["w"].to_pylist()):
                first.setdefault(k, w)
            assert dict(zip(got["k"].to_pylist(),
                            got["f"].to_pylist())) == first
            got = got.drop_columns(["f"])
        order = [("k", "ascending")]
        got, want = got.sort_by(order), want.sort_by(order)
        assert got.num_rows == want.num_rows > 0
        for name in want.column_names:
            a, b = got[name].to_pylist(), want[name].to_pylist()
            if case == "sort_path" and name != "k":
                assert np.allclose(np.array(a, float), np.array(b, float),
                                   rtol=1e-12, equal_nan=True), name
            else:
                assert a == b, name
        return
    prices = [p for p in table["price"].to_pylist() if p is not None]
    by = Counter(prices)
    if case in ("price_partial", "price_partial_merge"):
        # state columns: the sum at the input's scale, and the count
        assert got.num_rows == len(by) + 1          # and the null group
        rows = {x: (s, c) for x, s, c in zip(
            got["x"].to_pylist(), got["avg#sum"].to_pylist(),
            got["avg#count"].to_pylist())}
        for x, n in by.items():
            assert rows[x] == (x * n, n), x
        assert rows[None][1] == 0
    elif case == "price_final":
        rows = dict(zip(got["x"].to_pylist(), got["avg"].to_pylist()))
        assert len(rows) == len(by) + 1 and rows[None] is None
        for x in by:
            assert rows[x] == x.quantize(decimal.Decimal("0.000001")), x
    else:
        assert got.num_rows == 1
        if case == "keyless_final":
            total = sum(prices)
            with decimal.localcontext() as ctx:
                ctx.rounding = decimal.ROUND_HALF_UP
                avg = (total / len(prices)).quantize(
                    decimal.Decimal("0.000001"))
            assert got["avg"][0].as_py() == avg
            assert got["cnt"][0].as_py() == len(prices)
            assert got["cntd"][0].as_py() == len(by)
        else:
            assert got["avg#sum"][0].as_py() == sum(prices)
            assert got["avg#count"][0].as_py() == len(prices)
            assert got["cntd#count"][0].as_py() == len(by)


@pytest.mark.parametrize("case", list(CASES))
def test_an_aggregation_launches_programs_only(case, tmp_path):
    plan, operators, batches = CASES[case]
    conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_MIN_ROWS: ROWS}
                           if case == "partial_skip" else {})
    collect(plan()[0], config=conf)         # warm: compiles stay outside

    def run():
        op, table = plan()
        with trace.task_scope("agg-" + case) as acc:
            got = collect(op, config=conf)
        return got, table, acc

    (got, table, acc), (eager, engine) = _traced(run, tmp_path, SPANS)
    assert eager == [], Counter(eager)
    sites = dict(acc.calls_by_site)
    counts = acc.counts
    # jax writes two events a call
    assert len(engine) == 2 * sum(
        n for s, n in sites.items()
        if s.startswith(("hashtable.agg_", "ops.agg.")))
    # growths apart: a re-bucketing, the step a grown table runs again,
    # the sort path's merges
    # (and the export of a table that goes on as a sorted state: the
    # partial skip's decision)
    apart = sites.get(GROW, 0) + sites.get(MERGE, 0) + sites.get(EXPORT, 0) \
        + sites.get(STEP, 0) - counts["agg_hash_batches"]
    # B + 2 an operator: its steps or reduces, one set-up, one emit; only
    # the first operator of a chain sees more than one batch
    budget = (batches + 2) + (operators - 1) * 3
    assert len(engine) // 2 - apart <= budget, sites
    assert sites[EMIT] == operators
    assert sites.get(INIT, 0) <= operators
    assert counts["agg_hash_batches"] + sites.get(REDUCE, 0) \
        + sites.get(PASS, 0) == batches + (operators - 1)
    got_counts = tuple(counts[k] for k in (
        "row_syncs", "readbacks", "agg_hash_batches", "agg_sort_batches",
        "agg_state_grows"))
    assert got_counts == PARENT[case]
    _check_answer(case, got, table)


# -- served tasks of the benchmark's plans -------------------------------------

from tests.test_batch_shrink import served  # noqa: E402,F401  (the fixture)

#: plan -> the parent commit's DONE-frame counts of one task over this
#: fixture's split (row_syncs, readbacks, agg_hash_batches,
#: agg_sort_batches, agg_state_grows, agg_sort_fallbacks, agg_groups),
#: its aggregation operators, and the calls of their programs by site
SERVED = {
    # six bands x Spark's four aggregates: 18 keyed steps over 12 fresh
    # tables, 12 reduces without keys, 24 emits (480 eager launches and
    # 12 exports of their own before)
    "q28": ((96, 31, 18, 12, 0, 0, 9782), 24,
            {INIT: 12, STEP: 18, REDUCE: 12, EMIT: 24}),
    "q3": ((14, 6, 1, 0, 0, 0, 6), 1, {INIT: 1, STEP: 1, EMIT: 1}),
    "q65m": ((26, 11, 1, 1, 0, 0, 1154), 2,
             {INIT: 1, STEP: 1, REDUCE: 1, EMIT: 2}),
}


@pytest.fixture
def no_program_guard():
    """The guard on live programs clears every cache at a task's end once
    this worker's earlier modules have piled enough up: the traced task
    would then trace its programs again, and tracing dispatches eagerly."""
    conf = cfg.get_config()
    guard = conf.get(cfg.MAX_LIVE_PROGRAMS)
    conf.set(cfg.MAX_LIVE_PROGRAMS, 0)
    yield
    conf.set(cfg.MAX_LIVE_PROGRAMS, guard)


@pytest.mark.parametrize("plan", list(SERVED))
def test_a_served_task_aggregates_in_programs_only(plan, served, tmp_path,
                                                   no_program_guard):
    parent, operators, want = SERVED[plan]
    served(plan)
    served(plan)        # the cost history's first observation re-plans a
    #                     probe fold: trace the third run
    (table, counts), (eager, engine) = _traced(lambda: served(plan),
                                               tmp_path, SPANS)
    assert eager == [], Counter(eager)
    sites = {s: n for s, n in counts["program_calls_by_site"].items()
             if s.startswith(("hashtable.agg_", "ops.agg."))}
    assert sites == want
    assert len(engine) == 2 * sum(want.values())
    # B + 2 an operator, summed: every batch is a step or a reduce
    assert sum(want.values()) <= want.get(STEP, 0) + want.get(REDUCE, 0) \
        + 2 * operators
    got = tuple(counts[k] for k in (
        "row_syncs", "readbacks", "agg_hash_batches", "agg_sort_batches",
        "agg_state_grows", "agg_sort_fallbacks", "agg_groups"))
    assert got == parent
    res = served.compare.compare_tables(table, served.oracle(plan))
    assert served.compare.answer_ok(res), res
