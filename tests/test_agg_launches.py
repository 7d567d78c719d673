"""The aggregation's launch budget (PR 46; tier-1, on the CPU: counts only).

Everything ``AggOp`` does to a device array runs inside a program handed
out by ``runtime/programs.py``: a batch's group keys and contributions
are evaluated inside the program that consumes them
(``hashtable.agg_step`` on the hash table, ``ops.agg.batch_reduce`` on
the sort path and without keys), a table is set up INSIDE its first step
(the ``fresh`` form of ``hashtable.agg_step``) and an operator's output — the table's export, the cut to its
occupancy bucket, every function's finalisation — is ONE ``ops.agg.emit``,
which leaves with the host's count of its groups and reads nothing.
Before PR 46 a q28 task launched 480 eager
single-primitive programs round the 42 engine programs of its 24
aggregation operators (table set-up 168, emit 162, contributions 132,
``ord_base`` 18); this file is the guard that keeps such glue from
growing back.

A launch is a ``PjitFunction(<name>)`` event of the profiler's host plane
whose innermost operator span is ``auron:op/agg``; it is *eager* when
``<name>`` is no ``auron_<site>`` program. (jax writes two events a call,
so every count here is in events.) The budget of one operator over B
batches: B steps or reduces and one emit — growths and the sort path's
merges apart, which ``agg_state_grows`` and the merge site count as they
did.

What the aggregation reads from the chip (PR 51): a keyed step's one
``(n_new, overflow)``, the sort path's ``(num_groups, needed)`` a reduce
and a merge, and a keyless reduce's only where its batch's row count is
still on the device. Every count handed on by the host is held here to the
count the program returned.
"""

import decimal
from collections import Counter

import jax
import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow, to_device
from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import trace
from auron_tpu.ops.agg import AggOp
from auron_tpu.ops.base import PhysicalOp
from auron_tpu.runtime.executor import collect
from tests.test_join_launches import _traced

C = ir.ColumnRef
F = ir.AggFunction
SPANS = ("auron:op/agg",)
STEP, GROW, EXPORT = ("hashtable.agg_step", "hashtable.agg_grow",
                      "hashtable.agg_export")
PASS = "ops.agg.passthrough"
REDUCE, MERGE, EMIT = ("ops.agg.batch_reduce", "ops.agg.state_merge",
                       "ops.agg.emit")
ROWS, CAP = 600, 1024
MONEY = pa.decimal128(7, 2)

#: the counts a task's DONE frame carries for each case below: row_syncs,
#: readbacks, agg_hash_batches, agg_sort_batches, agg_state_grows. An
#: emit reads no row count (nor does the collector, of the same batch), a
#: keyless reduce over an aggregate's output reads nothing, a fresh step
#: that overflows re-buckets nothing. row_syncs is the scan's one a batch
#: (and the partial skip's passed-through batches, whose counts ARE on
#: the device); readbacks a step's, a sorted reduce's or merge's, a
#: growth's
PARENT = {
    "int_key": (3, 5, 3, 0, 0),
    "string_key": (3, 5, 3, 0, 0),
    "price_partial": (4, 4, 2, 0, 0),
    "price_partial_merge": (4, 5, 3, 0, 0),
    "price_final": (4, 5, 3, 0, 0),
    "keyless_partial": (4, 5, 3, 1, 0),
    "keyless_final": (4, 5, 3, 2, 0),
    "sort_path": (2, 5, 0, 2, 0),
    "grows": (3, 8, 3, 0, 3),
    "partial_skip": (10, 7, 1, 0, 0),
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
    # B + 1 an operator: its steps or reduces and one emit (the set-up
    # rides the first step); only the first operator of a chain sees more
    # than one batch
    budget = (batches + 1) + (operators - 1) * 2
    assert len(engine) // 2 - apart <= budget, sites
    assert sites[EMIT] == operators
    assert "hashtable.agg_init" not in sites
    assert counts["agg_hash_batches"] + sites.get(REDUCE, 0) \
        + sites.get(PASS, 0) == batches + (operators - 1)
    got_counts = tuple(counts[k] for k in (
        "row_syncs", "readbacks", "agg_hash_batches", "agg_sort_batches",
        "agg_state_grows"))
    assert got_counts == PARENT[case]
    _check_answer(case, got, table)


# -- what the host hands on is what the program returned ------------------------

@pytest.fixture
def emits(monkeypatch):
    """Every ``ops.agg.emit`` launch of the test as ``[the row count the
    PROGRAM returned, the one the operator handed on]``, and every batch
    ``_emit`` returned."""
    from auron_tpu.ops import agg
    launches, out = [], []
    kernel, table_batch, emit = (agg._emit_kernel, AggOp._table_batch,
                                 AggOp._emit)

    def recording(*key):
        program = kernel(*key)

        def call(*operands):
            batch = program(*operands)
            launches.append([batch.num_rows])
            return batch
        return call

    def handed(self, *args, **kw):
        batch, cap = table_batch(self, *args, **kw)
        launches[-1].append(batch.num_rows)
        return batch, cap

    def emitted(self, *args, **kw):
        batch = emit(self, *args, **kw)
        out.append(batch)
        return batch

    monkeypatch.setattr(agg, "_emit_kernel", recording)
    monkeypatch.setattr(AggOp, "_table_batch", handed)
    monkeypatch.setattr(AggOp, "_emit", emitted)
    return launches, out


def _bloom():
    rb = pa.record_batch({"v": pa.array(range(0, 2000, 2), pa.int64())})
    op = AggOp(_scan([[rb]]), [], [F("bloom_filter", C(0),
                                     expected_items=2000)],
               mode="complete", agg_names=["bf"], initial_capacity=16)
    return op, pa.Table.from_batches([rb])


def _dense():
    def make(rng, _i):
        return {"k": pa.array(rng.integers(0, 41, ROWS), pa.int64()),
                "v": pa.array(rng.integers(-50, 50, ROWS), pa.int64())}
    parts = _batches(3, make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("count", C(1))],
               mode="complete", group_names=["k"], agg_names=["s", "c"],
               key_domain=64)
    return op, pa.Table.from_batches(parts[0])


def _spilling(tmp_path):
    """``_int_key`` under a manager whose other consumer rams the budget
    after every batch: the aggregation's state is spilled as the victim,
    again and again, and the runs re-enter the merge at the end."""
    from auron_tpu.memmgr import MemConsumer, MemManager, SpillManager
    mm = MemManager(total_bytes=1 << 16, min_trigger=0,
                    spill_manager=SpillManager(spill_dir=str(tmp_path)))

    class _Rammer(MemConsumer):
        consumer_name = "rammer"

        def mem_used(self):
            return 1 << 20

        def spill(self):
            return 0

    rammer = _Rammer()
    mm.register_consumer(rammer)
    op, table = _int_key()
    scan = op.child
    pull = scan.execute

    def ramming(partition, ctx):
        for batch in pull(partition, ctx):
            yield batch
            mm.update_mem_used(rammer, 1 << 20)

    scan.execute = ramming
    return op, table, mm


def _unpressed(plan):
    return lambda _tmp_path: (*plan(), None)


#: path -> (plan over a scratch directory: the operator, its input, its
#: memory manager; the case of ``_check_answer`` its answer is held to)
EMIT_PATHS = {
    "hash": (_unpressed(_int_key), "int_key"),
    "sorted_keyed": (_unpressed(_sort_path), "sort_path"),
    "keyless": (_unpressed(lambda: _price_chain(4)), "keyless_final"),
    "spill_restored": (_spilling, "int_key"),
    "host_spliced": (_unpressed(_bloom), None),
    "dense_domain": (_unpressed(_dense), "grows"),
}


@pytest.mark.parametrize("path", list(EMIT_PATHS))
def test_an_emit_leaves_with_the_hosts_count_and_it_is_the_devices(
        path, emits, tmp_path):
    """The count an emit hands on is a host integer — nobody downstream
    waits for the program to learn it — and it is, to the unit, the row
    count the program itself returned."""
    plan, case = EMIT_PATHS[path]
    op, table, mm = plan(tmp_path)
    with trace.task_scope("emit-" + path) as acc:
        got = collect(op, mem_manager=mm)
    launches, out = emits
    assert launches and out
    for device, host in launches:
        assert isinstance(device, jax.Array)
        assert type(host) is np.int32
        assert int(device) == int(host)
    for batch in out:
        assert type(batch.num_rows) is np.int32
    assert int(out[-1].num_rows) == got.num_rows
    sites = dict(acc.calls_by_site)
    if mm is not None:
        # the spilled runs are emits in state layout, and came back
        assert mm.num_spills > 1 and sites[EMIT] > mm.num_spills
    if path == "host_spliced":
        assert got.num_rows == 1 and got["bf"][0].as_py()
    else:
        _check_answer(case, got, table)


def test_the_dense_domain_stream_still_reads_its_control_scalars_once():
    """The dense path's ONE batched readback (group count, key bounds,
    null flag, rows) is what checks the planner's ``key_domain`` promise:
    it stays, and the group count it brings is the one its emit leaves
    with — the row counts left are the scan's."""
    op, table = _dense()
    collect(_dense()[0])
    with trace.task_scope("dense") as acc:
        got = collect(op)
    # that readback and the collector's two; a row count a scan batch
    assert acc.counts["readbacks"] == 3
    assert acc.counts["row_syncs"] == 3
    assert dict(acc.calls_by_site)[EMIT] == 1
    _check_answer("grows", got, table)


# -- a keyless reduce reads where the host does not know the count ------------

class _Handed(PhysicalOp):
    """The batches it was given, as they are."""

    name = "stub"

    def __init__(self, batches, schema):
        self.batches, self._schema = batches, schema

    def schema(self):
        return self._schema

    def execute(self, partition, ctx):
        yield from self.batches


def _keyless_over(row_counts, counted: bool):
    """sum / count / min without keys over batches of ``row_counts`` rows,
    their row counts on the host (``counted``) or on the device."""
    rng = np.random.default_rng(3)
    batches, rows, schema = [], [], None
    for n in row_counts:
        rb = pa.record_batch({"v": pa.array(rng.integers(-9, 9, n),
                                            pa.int64())})
        batch, schema = to_device(rb, capacity=16)
        assert isinstance(batch.num_rows, jax.Array)
        if counted:
            batch = DeviceBatch(batch.columns, np.int32(n))
        batches.append(batch)
        rows += rb["v"].to_pylist()
    op = AggOp(_Handed(batches, schema), [],
               [F("sum", C(0)), F("count", C(0)), F("min", C(0))],
               mode="complete", agg_names=["s", "c", "lo"])
    with trace.task_scope("keyless") as acc:
        got = collect(op)
    sites = dict(acc.calls_by_site)
    assert sites[REDUCE] == len(row_counts) and sites[EMIT] == 1
    return got, rows, acc.counts["readbacks"], sites.get(MERGE, 0)


@pytest.mark.parametrize("row_counts", [(7,), (7, 5), (0,), (0, 4)],
                         ids=["one", "two", "empty", "empty_then_rows"])
def test_a_keyless_reduce_reads_only_a_count_the_host_lacks(row_counts):
    """Over a batch whose row count is on the device the reduce reads its
    group count, as the parent did; over a counted batch it reads nothing
    — the merge of two tables keeps its read — and the answers are the
    same, an EMPTY counted batch's too (no live row: no group, as the
    program says)."""
    _keyless_over(row_counts, False)            # warm
    read, rows, reads_device, merges = _keyless_over(row_counts, False)
    told, _rows, reads_counted, _m = _keyless_over(row_counts, True)
    assert merges == len(row_counts) - 1
    assert reads_device - reads_counted == len(row_counts)
    assert told.equals(read)
    if rows:
        assert told.to_pylist() == [
            {"s": sum(rows), "c": len(rows), "lo": min(rows)}]
    else:
        # what the parent answered over a batch without a row
        assert told.num_rows == 0


# -- the set-up rides the first step, and the protocol stays transactional ----

def test_a_first_batch_that_overflows_commits_nothing(monkeypatch):
    """600 rows of ~450 keys into a fresh table of 16 slots: the fresh
    step overflows its probe rounds, commits nothing — no table, no
    count, no row ordinal — and runs again, fresh, at the doubled
    capacity; nothing is re-bucketed until a step has committed.
    ``agg_state_grows`` counts what the parent counted (``grows`` beside
    it: the same data from 256 slots)."""
    from auron_tpu.hashtable import agg as ht
    grow = ht.HashAggState._grow
    fresh = []

    def watched(self):
        if not self.built:
            assert self.th is None and self.accs is None
            assert self.count == 0 and self.rows_seen == 0
            fresh.append(self.cap)
        grow(self)

    monkeypatch.setattr(ht.HashAggState, "_grow", watched)
    op, table = _grows()
    op.initial_capacity = 16
    with trace.task_scope("overflow") as acc:
        got = collect(op)
    sites, counts = dict(acc.calls_by_site), acc.counts
    assert fresh == [16, 32, 64, 128, 256]
    # 7 doublings, the first five of no table: 2 re-bucketing programs,
    # 8 steps for 3 batches
    assert counts["agg_state_grows"] == 7
    assert sites[GROW] == 7 - len(fresh)
    assert sites[STEP] == 3 + len(fresh)
    assert counts["agg_hash_batches"] == 3
    assert counts["agg_sort_fallbacks"] == 0
    _check_answer("grows", got, table)


def _late_keys(highs):
    """Batch i draws its keys from ``range(highs[i])``, into 256 slots."""
    def make(rng, i):
        return {"k": pa.array(rng.integers(0, highs[i], ROWS), pa.int64()),
                "v": pa.array(rng.integers(0, 9, ROWS), pa.int64())}
    parts = _batches(len(highs), make)
    op = AggOp(_scan(parts), [C(0)], [F("sum", C(1)), F("count", C(1))],
               mode="complete", group_names=["k"], agg_names=["s", "c"],
               initial_capacity=256)
    return op, pa.Table.from_batches(parts[0])


@pytest.mark.parametrize("highs, steps, grows", [
    ((20, 900, 900), 4, 3), ((20, 20, 900), 4, 2)],
    ids=["second_batch", "third_batch"])
def test_a_step_into_a_built_table_that_overflows_retries_into_the_grown(
        highs, steps, grows, monkeypatch):
    """20 keys commit at 256 slots; a later batch brings ~450 new ones,
    more than the step can place: it commits nothing, the table is
    re-bucketed at 512 and the SAME batch steps into the re-bucketed
    table — one retry, not one a doubling up to the ceiling."""
    from auron_tpu.hashtable import agg as ht
    grow = ht.HashAggState._grow
    caps = []

    def watched(self):
        grow(self)
        assert self.built and self.th.shape[0] == self.cap
        caps.append(self.cap)

    monkeypatch.setattr(ht.HashAggState, "_grow", watched)
    op, table = _late_keys(highs)
    with trace.task_scope("late") as acc:
        got = collect(op)
    sites, counts = dict(acc.calls_by_site), acc.counts
    assert caps == [512, 1024, 2048][:grows]
    assert counts["agg_state_grows"] == sites[GROW] == grows
    assert sites[STEP] == steps == len(highs) + 1     # one retry
    assert counts["agg_hash_batches"] == len(highs)
    assert counts["agg_sort_fallbacks"] == 0
    assert counts["agg_groups"] == len(table["k"].unique())
    _check_answer("grows", got, table)


# -- served tasks of the benchmark's plans -------------------------------------

from tests.test_batch_shrink import served  # noqa: E402,F401  (the fixture)

#: plan -> the DONE-frame counts of one task over this fixture's split
#: (row_syncs, readbacks, agg_hash_batches, agg_sort_batches,
#: agg_state_grows, agg_sort_fallbacks, agg_groups), its aggregation
#: operators, and the calls of their programs by site
SERVED = {
    # six bands x Spark's four aggregates: 18 keyed steps (12 of them
    # fresh: the table's set-up inside), 12 reduces without keys over an
    # aggregate's counted output (no read), 24 emits (no read)
    "q28": ((43, 19, 18, 12, 0, 0, 9782), 24,
            {STEP: 18, REDUCE: 12, EMIT: 24}),
    "q3": ((12, 6, 1, 0, 0, 0, 6), 1, {STEP: 1, EMIT: 1}),
    "q65m": ((19, 11, 1, 1, 0, 0, 1154), 2,
             {STEP: 1, REDUCE: 1, EMIT: 2}),
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
    # B + 1 an operator, summed: every batch is a step or a reduce
    assert sum(want.values()) <= want.get(STEP, 0) + want.get(REDUCE, 0) \
        + operators
    got = tuple(counts[k] for k in (
        "row_syncs", "readbacks", "agg_hash_batches", "agg_sort_batches",
        "agg_state_grows", "agg_sort_fallbacks", "agg_groups"))
    assert got == parent
    res = served.compare.compare_tables(table, served.oracle(plan))
    assert served.compare.answer_ok(res), res
