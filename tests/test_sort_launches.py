"""The global sort's launch budget (PR 41; tier-1, on the CPU: counts only).

A 4-partition stage runs ``ORDER BY … LIMIT`` as a range exchange 4 → 4,
a sort a partition and a gather 4 → 1. Everything on that path that
touches a device array between one sync and the next runs inside a
program handed out by ``runtime/programs.py``: one
``parallel.partitioning.range_sample`` a sampled batch, one
``parallel.exchange.fused_split`` a map batch (the partition ids — range
bounds an operand — and the sort by them), one
``parallel.exchange.read_cut`` a reducer partition, which hands it its
maps' rows as ONE batch (PR 49: no ``ops.sort.concat``, which a
partition handed more than one batch would launch first), one
``ops.sort.sort`` a partition. Before PR 41 the range exchange launched
122–215 eager single-primitive programs a batch (every bound × every key
word) and the sort's concatenation ≈ 100 a partition; this file is the
guard that keeps such glue from growing back.

A launch is a ``PjitFunction(<name>)`` event of the profiler's host plane
whose innermost operator span is ``auron:op/shuffle_exchange`` or
``auron:op/sort``; it is *eager* when ``<name>`` is no ``auron_<site>``
program. (jax writes two events a call, so every count here is in
events.)
"""

import decimal

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import trace
from auron_tpu.ops.limit import LimitOp
from auron_tpu.ops.sort import SortOp
from auron_tpu.parallel.exchange import ShuffleExchangeOp
from auron_tpu.parallel.partitioning import (RangePartitioning,
                                             SinglePartitioning)
from auron_tpu.runtime.executor import collect
from tests.test_join_launches import _traced

C = ir.ColumnRef
MAPS = 4
ROWS = 48                    # a map batch, of capacity 64
#: eager events the whole path may still launch (two events a call):
#: none is left today
BUDGET = 0
SPANS = ("auron:op/shuffle_exchange", "auron:op/sort")
SPLIT = "parallel.exchange.fused_split"
SAMPLE = "parallel.partitioning.range_sample"
CONCAT = "ops.sort.concat"
SORT = "ops.sort.sort"
READ_CUT = "parallel.exchange.read_cut"


def _key(kind: str, rng) -> pa.Array:
    """One map batch's key column: repeats (ties), and nulls."""
    mask = rng.random(ROWS) < 0.15
    if kind == "int":
        vals = rng.integers(-40, 40, ROWS)
        return pa.array(vals, pa.int64(), mask=mask)
    if kind == "double":
        vals = np.round(rng.normal(0, 50, ROWS), 1)
        return pa.array(vals, pa.float64(), mask=mask)
    if kind == "decimal":
        vals = [None if m else decimal.Decimal(int(v)) / 100
                for v, m in zip(rng.integers(-5000, 5000, ROWS), mask)]
        return pa.array(vals, pa.decimal128(7, 2))
    assert kind == "string"
    # two width buckets across the batches: a map's strings are all
    # short (width 8) or reach 13–20 bytes (width 16 / 32)
    long = rng.random() < 0.5
    vals = [None if m else
            ("item-%02d" % v + ("-description" * long))[:int(8 + 12 * long)]
            for v, m in zip(rng.integers(0, 30, ROWS), mask)]
    return pa.array(vals, pa.string())


def _plan(kind: str, ascending: bool, nulls_first: bool, seed: int = 5):
    rng = np.random.default_rng(seed)
    maps = [[pa.record_batch({
        "k": _key(kind, rng),
        "tie": pa.array(rng.integers(0, 3, ROWS), pa.int64()),
        "row": pa.array(np.arange(ROWS) + ROWS * m, pa.int64()),
    })] for m in range(MAPS)]
    schema = schema_from_arrow(maps[0][0].schema)
    scan = MemoryScanOp(maps, schema, capacity=64)
    orders = (ir.SortOrder(C(0), ascending, nulls_first),
              ir.SortOrder(C(1), True, True))
    ranged = ShuffleExchangeOp(scan, RangePartitioning(orders, MAPS, ()),
                               input_partitions=MAPS)
    gathered = ShuffleExchangeOp(SortOp(ranged, list(orders)),
                                 SinglePartitioning(),
                                 input_partitions=MAPS)
    table = pa.Table.from_batches([rb for m in maps for rb in m])
    return LimitOp(gathered, 150), table, orders


def _oracle(table: pa.Table, orders, limit: int) -> pa.Table:
    """The plain reference: Acero's stable sort of the maps' rows in map
    order. Spark's null placement is a key's own; Arrow's is the sort's,
    so the nulls are ranked by a column of their own."""
    first = orders[0]
    is_null = table["k"].is_null().cast(pa.int8())
    keyed = table.append_column("k_null", is_null)
    idx = pa.compute.sort_indices(keyed, sort_keys=[
        ("k_null", "descending" if first.nulls_first else "ascending"),
        ("k", "ascending" if first.ascending else "descending"),
        ("tie", "ascending")])
    return table.take(idx).slice(0, limit)


CASES = [(kind, asc, nf)
         for kind in ("int", "decimal", "double", "string")
         for asc, nf in ((True, True), (True, False), (False, True),
                         (False, False))]


@pytest.mark.parametrize("kind,ascending,nulls_first", CASES)
def test_a_global_sort_launches_programs_only(kind, ascending, nulls_first,
                                              tmp_path):
    collect(_plan(kind, ascending, nulls_first)[0])   # warm: no compiles

    def run():
        plan, table, orders = _plan(kind, ascending, nulls_first)
        with trace.task_scope(f"sort-{kind}") as acc:
            got = collect(plan)
        return got, table, orders, dict(acc.calls_by_site)

    (got, table, orders, sites), (eager, engine) = _traced(run, tmp_path,
                                                           SPANS)
    assert len(eager) <= BUDGET, sorted(set(eager))
    # a map batch is sampled once and split twice (the range exchange
    # and the gather); a partition's rows are read with one launch —
    # ONE batch, whatever maps sent it rows (PR 49) — and sorted once,
    # with nothing left to concatenate; the gather's read is one more
    assert sites[SAMPLE] == MAPS
    assert sites[SPLIT] == MAPS + MAPS
    assert sites[SORT] == MAPS
    assert sites[READ_CUT] == MAPS + 1
    assert CONCAT not in sites
    assert "parallel.exchange.sort_by_pid" not in sites
    assert set(engine) >= {"auron_" + s.replace(".", "_")
                           for s in (SAMPLE, SPLIT, SORT)}
    # the same rows in the same order as the plain reference, ties in
    # the maps' own order
    want = _oracle(table, orders, 150)
    assert got.column("row").to_pylist() == want.column("row").to_pylist()
