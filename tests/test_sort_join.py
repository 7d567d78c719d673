import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.columnar.schema import DataType
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.joins import HashJoinOp, SortMergeJoinOp
from auron_tpu.ops.sort import SortOp
from auron_tpu.runtime.executor import collect

C = ir.ColumnRef


def mem_scan(rb, capacity=64):
    return MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=capacity)


def test_sort_multi_key_with_nulls():
    rb = pa.record_batch({
        "a": pa.array([3, 1, None, 1, 2, None], pa.int64()),
        "b": pa.array([1.0, 5.0, 2.0, None, 3.0, 1.0], pa.float64()),
    })
    op = SortOp(mem_scan(rb, capacity=8), [
        ir.SortOrder(C(0), ascending=True, nulls_first=True),
        ir.SortOrder(C(1), ascending=False, nulls_first=False),
    ])
    out = collect(op)
    assert out.column("a").to_pylist() == [None, None, 1, 1, 2, 3]
    assert out.column("b").to_pylist() == [2.0, 1.0, 5.0, None, 3.0, 1.0]


def test_sort_strings_desc():
    rb = pa.record_batch({"s": pa.array(["b", "abc", None, "ab", "c"], pa.string())})
    out = collect(SortOp(mem_scan(rb, capacity=8),
                         [ir.SortOrder(C(0), ascending=False, nulls_first=False)]))
    assert out.column("s").to_pylist() == ["c", "b", "abc", "ab", None]


def test_sort_random_differential():
    rng = np.random.default_rng(11)
    n = 3000
    rb = pa.record_batch({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.normal(size=n), pa.float64()),
    })
    # multi-batch input
    rbs = [rb.slice(o, 500) for o in range(0, n, 500)]
    scan = MemoryScanOp([rbs], schema_from_arrow(rb.schema), capacity=512)
    out = collect(SortOp(scan, [ir.SortOrder(C(0)), ir.SortOrder(C(1))]))
    df = rb.to_pandas().sort_values(["k", "v"]).reset_index(drop=True)
    got = out.to_pandas()
    np.testing.assert_array_equal(got["k"], df["k"])
    np.testing.assert_allclose(got["v"], df["v"])


def test_sort_fetch():
    rb = pa.record_batch({"x": pa.array([5, 3, 8, 1, 9], pa.int64())})
    out = collect(SortOp(mem_scan(rb, capacity=8), [ir.SortOrder(C(0))], fetch=3))
    assert out.column("x").to_pylist() == [1, 3, 5]


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def _join_case(join_type, expected_rows):
    left = pa.record_batch({
        "lk": pa.array([1, 2, 3, None, 2], pa.int64()),
        "lv": pa.array(["a", "b", "c", "d", "e"], pa.string()),
    })
    right = pa.record_batch({
        "rk": pa.array([2, 2, 4, None], pa.int64()),
        "rv": pa.array([20, 21, 40, 99], pa.int64()),
    })
    op = HashJoinOp(mem_scan(left, capacity=8), mem_scan(right, capacity=8),
                    [C(0)], [C(0)], join_type=join_type)
    out = collect(op)
    rows = set()
    for r in out.to_pylist():
        rows.add(tuple(r.values()))
    assert rows == expected_rows, f"{join_type}: {rows}"


def test_inner_join():
    _join_case("inner", {
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
    })


def test_left_join():
    _join_case("left", {
        (1, "a", None, None), (2, "b", 2, 20), (2, "b", 2, 21),
        (3, "c", None, None), (None, "d", None, None),
        (2, "e", 2, 20), (2, "e", 2, 21),
    })


def test_right_join():
    _join_case("right", {
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
        (None, None, 4, 40), (None, None, None, 99),
    })


def test_full_join():
    _join_case("full", {
        (1, "a", None, None), (2, "b", 2, 20), (2, "b", 2, 21),
        (3, "c", None, None), (None, "d", None, None),
        (2, "e", 2, 20), (2, "e", 2, 21),
        (None, None, 4, 40), (None, None, None, 99),
    })


def test_semi_join():
    _join_case("semi", {(2, "b"), (2, "e")})


def test_anti_join():
    _join_case("anti", {(1, "a"), (3, "c"), (None, "d")})


def test_existence_join():
    _join_case("existence", {
        (1, "a", False), (2, "b", True), (3, "c", False),
        (None, "d", False), (2, "e", True),
    })


def test_join_string_keys():
    left = pa.record_batch({"k": pa.array(["x", "y", "z"], pa.string()),
                            "v": pa.array([1, 2, 3], pa.int64())})
    right = pa.record_batch({"rk": pa.array(["y", "z", "w"], pa.string()),
                             "u": pa.array([20, 30, 40], pa.int64())})
    op = HashJoinOp(mem_scan(left, capacity=4), mem_scan(right, capacity=4),
                    [C(0)], [C(0)], join_type="inner")
    out = collect(op)
    rows = {tuple(r.values()) for r in out.to_pylist()}
    assert rows == {("y", 2, "y", 20), ("z", 3, "z", 30)}


def test_join_random_differential():
    rng = np.random.default_rng(13)
    nl, nr = 2000, 1500
    left = pa.table({
        "k": pa.array(rng.integers(0, 200, nl), pa.int64()),
        "lv": pa.array(rng.integers(0, 10**6, nl), pa.int64()),
    })
    right = pa.table({
        "k": pa.array(rng.integers(0, 200, nr), pa.int64()),
        "rv": pa.array(rng.integers(0, 10**6, nr), pa.int64()),
    })
    lb = left.to_batches()[0]
    rb = right.to_batches()[0]
    op = HashJoinOp(mem_scan(lb, capacity=2048), mem_scan(rb, capacity=2048),
                    [C(0)], [C(0)], join_type="inner")
    got = collect(op).to_pandas().rename(columns={"k": "lk"})
    got.columns = ["lk", "lv", "rk", "rv"]

    expected = left.to_pandas().merge(right.to_pandas(), on="k", how="inner")
    assert len(got) == len(expected)
    gs = got.sort_values(["lk", "lv", "rv"]).reset_index(drop=True)
    es = expected.sort_values(["k", "lv", "rv"]).reset_index(drop=True)
    np.testing.assert_array_equal(gs["lk"], es["k"])
    np.testing.assert_array_equal(gs["lv"], es["lv"])
    np.testing.assert_array_equal(gs["rv"], es["rv"])


def test_smj_same_results():
    left = pa.record_batch({"k": pa.array([1, 2, 2, 3], pa.int64()),
                            "lv": pa.array([1, 2, 3, 4], pa.int64())})
    right = pa.record_batch({"rk": pa.array([2, 3, 3], pa.int64()),
                             "rv": pa.array([10, 20, 30], pa.int64())})
    op = SortMergeJoinOp(mem_scan(left, capacity=4), mem_scan(right, capacity=4),
                         [C(0)], [C(0)], join_type="inner")
    rows = {tuple(r.values()) for r in collect(op).to_pylist()}
    assert rows == {(2, 2, 2, 10), (2, 3, 2, 10), (3, 4, 3, 20), (3, 4, 3, 30)}


# ---------------------------------------------------------------------------
# sort-merge join (real streaming merge)
# ---------------------------------------------------------------------------

def _smj_case(join_type, expected_rows):
    # same data as _join_case but pre-sorted on the keys (nulls first), the
    # SMJ contract
    left = pa.record_batch({
        "lk": pa.array([None, 1, 2, 2, 3], pa.int64()),
        "lv": pa.array(["d", "a", "b", "e", "c"], pa.string()),
    })
    right = pa.record_batch({
        "rk": pa.array([None, 2, 2, 4], pa.int64()),
        "rv": pa.array([99, 20, 21, 40], pa.int64()),
    })
    op = SortMergeJoinOp(mem_scan(left, capacity=8), mem_scan(right, capacity=8),
                         [C(0)], [C(0)], join_type=join_type)
    out = collect(op)
    rows = {tuple(r.values()) for r in out.to_pylist()}
    assert rows == expected_rows, f"{join_type}: {rows}"


def test_smj_inner():
    _smj_case("inner", {
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
    })


def test_smj_left():
    _smj_case("left", {
        (None, "d", None, None), (1, "a", None, None),
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
        (3, "c", None, None),
    })


def test_smj_right():
    _smj_case("right", {
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
        (None, None, 4, 40), (None, None, None, 99),
    })


def test_smj_full():
    _smj_case("full", {
        (None, "d", None, None), (1, "a", None, None),
        (2, "b", 2, 20), (2, "b", 2, 21), (2, "e", 2, 20), (2, "e", 2, 21),
        (3, "c", None, None),
        (None, None, 4, 40), (None, None, None, 99),
    })


def test_smj_semi_anti_existence():
    _smj_case("semi", {(2, "b"), (2, "e")})
    _smj_case("anti", {(None, "d"), (1, "a"), (3, "c")})
    _smj_case("existence", {
        (None, "d", False), (1, "a", False), (2, "b", True),
        (2, "e", True), (3, "c", False),
    })


def test_smj_order_preserved_multibatch():
    """The round-3 contract: SMJ output preserves the children's sort order,
    streaming across many small batches on both sides."""
    rng = np.random.default_rng(7)
    nl, nr = 700, 900
    lk = np.sort(rng.integers(0, 120, nl))
    rk = np.sort(rng.integers(0, 120, nr))
    left = pa.record_batch({"k": pa.array(lk, pa.int64()),
                            "lv": pa.array(np.arange(nl), pa.int64())})
    right = pa.record_batch({"rk": pa.array(rk, pa.int64()),
                             "rv": pa.array(np.arange(nr), pa.int64())})
    lbs = [left.slice(o, 64) for o in range(0, nl, 64)]
    rbs = [right.slice(o, 96) for o in range(0, nr, 96)]
    op = SortMergeJoinOp(
        MemoryScanOp([lbs], schema_from_arrow(left.schema), capacity=64),
        MemoryScanOp([rbs], schema_from_arrow(right.schema), capacity=96),
        [C(0)], [C(0)], join_type="inner")
    got = collect(op).to_pandas()
    got.columns = ["lk", "lv", "rk", "rv"]

    # exact order: ascending (left row position, right row position)
    ldf = pd.DataFrame({"k": lk, "lv": np.arange(nl)})
    rdf = pd.DataFrame({"k": rk, "rv": np.arange(nr)})
    exp = ldf.merge(rdf, on="k", how="inner").sort_values(
        ["lv", "rv"]).reset_index(drop=True)
    assert len(got) == len(exp)
    np.testing.assert_array_equal(got["lv"], exp["lv"])
    np.testing.assert_array_equal(got["rv"], exp["rv"])
    np.testing.assert_array_equal(got["lk"], exp["k"])
    # left-outer variant: left order must hold globally over the output
    opl = SortMergeJoinOp(
        MemoryScanOp([lbs], schema_from_arrow(left.schema), capacity=64),
        MemoryScanOp([rbs], schema_from_arrow(right.schema), capacity=96),
        [C(0)], [C(0)], join_type="left")
    gl = collect(opl).to_pandas()
    gl.columns = ["lk", "lv", "rk", "rv"]
    expl = ldf.merge(rdf, on="k", how="left").sort_values(
        ["lv", "rv"], na_position="last").reset_index(drop=True)
    assert len(gl) == len(expl)
    np.testing.assert_array_equal(gl["lv"], expl["lv"])


def test_smj_string_keys_mixed_widths():
    left = pa.record_batch({
        "k": pa.array(["aa", "bb", "bb", "a-very-long-key-string"], pa.string()),
        "lv": pa.array([1, 2, 3, 4], pa.int64()),
    })
    right = pa.record_batch({
        "rk": pa.array(["bb", "a-very-long-key-string", "zz"], pa.string()),
        "rv": pa.array([10, 20, 30], pa.int64()),
    })
    # children sorted on key
    ls = SortOp(mem_scan(left, capacity=8), [ir.SortOrder(C(0))])
    rs = SortOp(mem_scan(right, capacity=8), [ir.SortOrder(C(0))])
    op = SortMergeJoinOp(ls, rs, [C(0)], [C(0)], join_type="inner")
    rows = {tuple(r.values()) for r in collect(op).to_pylist()}
    assert rows == {("bb", 2, "bb", 10), ("bb", 3, "bb", 10),
                    ("a-very-long-key-string", 4, "a-very-long-key-string", 20)}


def test_smj_multi_key_differential():
    rng = np.random.default_rng(23)
    nl, nr = 800, 600
    left = pa.table({
        "a": pa.array(rng.integers(0, 12, nl), pa.int64()),
        "b": pa.array(rng.integers(0, 6, nl), pa.int64()),
        "lv": pa.array(np.arange(nl), pa.int64()),
    }).to_batches()[0]
    right = pa.table({
        "a": pa.array(rng.integers(0, 12, nr), pa.int64()),
        "b": pa.array(rng.integers(0, 6, nr), pa.int64()),
        "rv": pa.array(np.arange(nr), pa.int64()),
    }).to_batches()[0]
    keys = [ir.SortOrder(C(0)), ir.SortOrder(C(1))]
    op = SortMergeJoinOp(
        SortOp(mem_scan(left, capacity=1024), keys),
        SortOp(mem_scan(right, capacity=1024), keys),
        [C(0), C(1)], [C(0), C(1)], join_type="inner")
    got = collect(op).to_pandas()
    got.columns = ["la", "lb", "lv", "ra", "rb", "rv"]
    exp = left.to_pandas().merge(right.to_pandas(), on=["a", "b"],
                                 how="inner")
    assert len(got) == len(exp)
    gs = got.sort_values(["la", "lb", "lv", "rv"]).reset_index(drop=True)
    es = exp.sort_values(["a", "b", "lv", "rv"]).reset_index(drop=True)
    np.testing.assert_array_equal(gs["lv"], es["lv"])
    np.testing.assert_array_equal(gs["rv"], es["rv"])


def test_hash_join_build_spill_falls_back_to_smj():
    """Oversized build side must spill and degrade to the external merge
    join instead of OOMing (round-3 join memory safety)."""
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager

    rng = np.random.default_rng(31)
    nl, nr = 1200, 4000
    left = pa.record_batch({
        "k": pa.array(rng.integers(0, 500, nl), pa.int64()),
        "lv": pa.array(np.arange(nl), pa.int64()),
    })
    right = pa.record_batch({
        "k": pa.array(rng.integers(0, 500, nr), pa.int64()),
        "rv": pa.array(np.arange(nr), pa.int64()),
    })
    lbs = [left.slice(o, 256) for o in range(0, nl, 256)]
    rbs = [right.slice(o, 256) for o in range(0, nr, 256)]
    mm = MemManager(total_bytes=64 << 10, min_trigger=0,
                    spill_manager=SpillManager(host_budget_bytes=1 << 24))
    op = HashJoinOp(
        MemoryScanOp([lbs], schema_from_arrow(left.schema), capacity=256),
        MemoryScanOp([rbs], schema_from_arrow(right.schema), capacity=256),
        [C(0)], [C(0)], join_type="inner")
    got = collect(op, mem_manager=mm).to_pandas()
    got.columns = ["lk", "lv", "rk", "rv"]
    exp = left.to_pandas().merge(right.to_pandas(), on="k", how="inner")
    assert mm.num_spills > 0, "build side must have spilled"
    assert len(got) == len(exp)
    gs = got.sort_values(["lk", "lv", "rv"]).reset_index(drop=True)
    es = exp.sort_values(["k", "lv", "rv"]).reset_index(drop=True)
    np.testing.assert_array_equal(gs["lv"], es["lv"])
    np.testing.assert_array_equal(gs["rv"], es["rv"])


# ---------------------------------------------------------------------------
# PR 29: the join's match phase runs as one program a probe batch. Its
# rows equal the rows of the eager path it replaced, in order: the
# expected rows were recorded from the parent commit (680d28f) by
# running these same cases there (tests/fixtures/join_rows_eager.json)
# ---------------------------------------------------------------------------

import decimal  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

_JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti", "existence")
_KEY_KINDS = {
    "int": (pa.int64(), lambda i: i),
    "string": (pa.string(), lambda i: "s" * (i % 4 + 1) + str(i)),
    # past 18 digits: two int64 limbs, equal only if both are
    "decimal128": (pa.decimal128(38, 2),
                   lambda i: decimal.Decimal(f"{10 ** 28 + i}.25")),
}


def _identity_join(join_type, kind):
    """Four probe batches over one build batch: null keys on both sides,
    duplicate build keys (2) and probe keys (2, 4, 7), a probe batch the
    filter empties (``f`` all null), and one without a candidate."""
    from auron_tpu.ops.project import FilterOp
    typ, key = _KEY_KINDS[kind]
    keys = lambda ids: pa.array(    # noqa: E731
        [None if i is None else key(i) for i in ids], typ)
    probe = [pa.record_batch({
        "lk": keys(ids),
        "lv": pa.array([10 * b + j for j in range(len(ids))], pa.int64()),
        "f": pa.array([None if b == 1 else 1] * len(ids), pa.int64()),
    }) for b, ids in enumerate(([1, 2, 3, None, 2], [2, 4, 6],
                                [7, 8, 9, 7, None], [4, 4, 2, 5, 6]))]
    build = pa.record_batch({
        "rk": keys([2, 2, 4, None, 6]),
        "rv": pa.array([20, 21, 40, 99, 60], pa.int64()),
    })
    scan = MemoryScanOp([probe], schema_from_arrow(probe[0].schema),
                        capacity=8)
    return HashJoinOp(FilterOp(scan, [ir.IsNotNull(C(2))]),
                      mem_scan(build, capacity=8), [C(0)], [C(0)],
                      join_type=join_type)


def _plain_rows(table):
    return [[v if v is None or isinstance(v, (int, bool, str)) else str(v)
             for v in row.values()] for row in table.to_pylist()]


@pytest.fixture(scope="module")
def eager_rows():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "join_rows_eager.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", sorted(_KEY_KINDS))
@pytest.mark.parametrize("join_type", _JOIN_TYPES)
def test_join_rows_equal_the_eager_paths(join_type, kind, eager_rows):
    got = _plain_rows(collect(_identity_join(join_type, kind)))
    assert got == eager_rows[f"{join_type}.{kind}"]


# -- the sort's concatenation as a program (PR 41) --------------------------

def _concat_batches(n_batches: int, seed: int = 9):
    """Device batches of differing capacity, live rows (one of them
    none) and string width bucket, and the same rows as one table."""
    from auron_tpu.columnar.arrow_bridge import to_device
    from auron_tpu.columnar.batch import DeviceBatch
    rng = np.random.default_rng(seed)
    batches, tables = [], []
    for i in range(n_batches):
        n = int(rng.integers(3, 30))
        mask = rng.random(n) < 0.2
        tail = "-with-a-long-tail" * (i % 2)
        rb = pa.record_batch({
            "k": pa.array(rng.integers(0, 6, n), pa.int64()),
            "s": pa.array([None if m else "s%02d" % v + tail for v, m in
                           zip(rng.integers(0, 9, n), mask)], pa.string()),
            "row": pa.array(np.arange(n) + 100 * i, pa.int64()),
        })
        b, schema = to_device(rb, capacity=32 if i % 3 else 64)
        if i == 1:
            b, rb = DeviceBatch(b.columns, 0), rb.slice(0, 0)
        batches.append(b)
        tables.append(rb)
    return batches, schema, pa.Table.from_batches(tables)


@pytest.mark.parametrize("n_batches", [2, 3, 4, 5])
def test_concat_sort_equals_the_sort_of_the_host_concatenation(n_batches):
    """``ops.sort.concat`` then ``ops.sort.sort`` over N collected
    batches: the rows of their host concatenation in the stable order of
    the keys (ties in input order), the row count their sum and still on
    the device, every row past it padding of no validity."""
    import jax
    from auron_tpu.columnar.arrow_bridge import to_arrow
    from auron_tpu.ops import sort as S
    from auron_tpu.utils.shapes import bucket_rows
    batches, schema, table = _concat_batches(n_batches)
    orders = (ir.SortOrder(C(0), ascending=False, nulls_first=True),
              ir.SortOrder(C(1), ascending=True, nulls_first=False))

    merged = S._concat_all(batches)
    assert isinstance(merged.num_rows, jax.Array)
    assert int(merged.num_rows) == table.num_rows
    assert merged.capacity == bucket_rows(sum(b.capacity for b in batches))
    assert to_arrow(merged, schema).to_pylist() == table.to_pylist()
    for leaf in (c.validity for c in merged.columns):
        assert not np.asarray(leaf)[table.num_rows:].any()

    out = S._sort_kernel(orders, schema, merged.capacity, False)(merged)
    assert int(out.num_rows) == table.num_rows
    for leaf in (c.validity for c in out.columns):
        assert not np.asarray(leaf)[table.num_rows:].any()
    null_last = table["s"].is_null().cast(pa.int8())
    idx = pa.compute.sort_indices(
        table.append_column("s_null", null_last),
        sort_keys=[("k", "descending"), ("s_null", "ascending"),
                   ("s", "ascending")])
    assert to_arrow(out, schema).column("row").to_pylist() \
        == table.take(idx).column("row").to_pylist()


def test_concat_program_is_keyed_by_shapes_not_row_counts():
    """Other row counts in the same capacities and widths build no new
    program, and the arity is rounded up with zero-row views where that
    leaves the capacity bucket as it is."""
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.ops import sort as S
    batches, _schema, _table = _concat_batches(3)
    S._concat_all(batches)
    before = S._concat_kernel.cache.stats()
    other = [DeviceBatch(b.columns, n) for b, n in zip(batches, (1, 2, 0))]
    merged = S._concat_all(other)
    after = S._concat_kernel.cache.stats()
    assert int(merged.num_rows) == 3
    assert after["builds"] == before["builds"]
    assert after["hits"] == before["hits"] + 1
    assert merged.capacity == 128      # 64 + 32 + 32: no room for a view
    same = [batches[1]] * 3             # 3 x 32 -> 4 x 32, one program
    S._concat_all(same)
    S._concat_all(same + same[:1])
    last = S._concat_kernel.cache.stats()
    assert (last["builds"], last["hits"]) \
        == (after["builds"] + 1, after["hits"] + 1)
