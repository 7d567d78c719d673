import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.columnar.schema import DataType
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.agg import AggOp
from auron_tpu.ops.base import ExecContext
from auron_tpu.ops.sort import SortOp
from auron_tpu.parallel.exchange import BroadcastExchangeOp, ShuffleExchangeOp
from auron_tpu.parallel.partitioning import (HashPartitioning,
                                             RangePartitioning,
                                             RoundRobinPartitioning,
                                             SinglePartitioning)
from auron_tpu.runtime.executor import collect
from tests.reference_impls import murmur3_long

C = ir.ColumnRef


def test_hash_partition_ids_match_spark():
    """pmod(murmur3(key, 42), n) — parity with the reference shuffle
    (shuffle/mod.rs:163-188)."""
    from auron_tpu.columnar.arrow_bridge import to_device
    rb = pa.record_batch({"k": pa.array([1, 2, 3, 100, -5], pa.int64())})
    batch, schema = to_device(rb, capacity=8)
    p = HashPartitioning((C(0),), 4)
    pids = np.asarray(p.partition_ids(batch, schema))[:5]
    expected = [((murmur3_long(k, 42) % 4) + 4) % 4 for k in [1, 2, 3, 100, -5]]
    assert pids.tolist() == expected


def test_shuffle_exchange_hash_repartition():
    n = 1000
    rb = pa.record_batch({
        "k": pa.array([i % 37 for i in range(n)], pa.int64()),
        "v": pa.array(list(range(n)), pa.int64()),
    })
    rbs = [rb.slice(o, 250) for o in range(0, n, 250)]
    # two map partitions, each with 2 batches
    scan = MemoryScanOp([rbs[:2], rbs[2:]], schema_from_arrow(rb.schema),
                        capacity=256)
    ex = ShuffleExchangeOp(scan, HashPartitioning((C(0),), 4),
                           input_partitions=2)
    # union of all output partitions == input; same key → same partition
    out = collect(ex, num_partitions=4)
    assert out.num_rows == n
    assert sorted(out.column("v").to_pylist()) == list(range(n))
    # verify co-location: each key appears in exactly one partition
    seen = {}
    for p in range(4):
        t = collect_partition(ex, p)
        for k in set(t.column("k").to_pylist()):
            assert seen.setdefault(k, p) == p


def collect_partition(op, p):
    from auron_tpu.runtime.executor import ExecutionRuntime, TaskDefinition
    return ExecutionRuntime(op, TaskDefinition(partition_id=p,
                                               num_partitions=op.num_partitions)).collect()


def test_round_robin_balance():
    n = 100
    rb = pa.record_batch({"v": pa.array(list(range(n)), pa.int64())})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=128)
    ex = ShuffleExchangeOp(scan, RoundRobinPartitioning(4), input_partitions=1)
    sizes = [collect_partition(ex, p).num_rows for p in range(4)]
    assert sizes == [25, 25, 25, 25]


def test_range_partition_global_sort():
    """Range exchange + per-partition sort == global sort (the reference's
    global-sort pattern, SURVEY.md §2.3)."""
    rng = np.random.default_rng(3)
    vals = rng.integers(-1000, 1000, 500)
    rb = pa.record_batch({"x": pa.array(vals, pa.int64())})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=512)
    orders = (ir.SortOrder(C(0)),)
    ex = ShuffleExchangeOp(scan, RangePartitioning(orders, 4, ()),
                           input_partitions=1)
    srt = SortOp(ex, list(orders))
    pieces = [collect_partition_sorted(srt, ex, p) for p in range(4)]
    flat = [x for piece in pieces for x in piece]
    assert flat == sorted(vals.tolist())


def collect_partition_sorted(srt, ex, p):
    from auron_tpu.runtime.executor import ExecutionRuntime, TaskDefinition
    t = ExecutionRuntime(srt, TaskDefinition(partition_id=p,
                                             num_partitions=4)).collect()
    return t.column("x").to_pylist()


def test_two_phase_agg_over_exchange():
    """partial agg → hash exchange on keys → final agg; the canonical
    distributed agg plan (SURVEY.md §3.3)."""
    n = 2000
    rb = pa.record_batch({
        "k": pa.array([i % 53 for i in range(n)], pa.int64()),
        "v": pa.array([float(i) for i in range(n)], pa.float64()),
    })
    rbs = [rb.slice(o, 500) for o in range(0, n, 500)]
    scan = MemoryScanOp([rbs[:2], rbs[2:]], schema_from_arrow(rb.schema),
                        capacity=512)
    partial = AggOp(scan, [C(0)], [ir.AggFunction("sum", C(1)),
                                   ir.AggFunction("count", C(1))],
                    mode="partial", group_names=["k"], agg_names=["s", "c"],
                    initial_capacity=64)
    ex = ShuffleExchangeOp(partial, HashPartitioning((C(0),), 4),
                           input_partitions=2)
    final = AggOp(ex, [C(0)], [ir.AggFunction("sum", None),
                               ir.AggFunction("count", None)],
                  mode="final", group_names=["k"], agg_names=["s", "c"],
                  initial_capacity=64)
    out = collect(final, num_partitions=4)
    assert out.num_rows == 53
    got = {r["k"]: (r["s"], r["c"]) for r in out.to_pylist()}
    import pandas as pd
    df = rb.to_pandas().groupby("k")["v"].agg(["sum", "count"])
    for k, row in df.iterrows():
        assert got[k][0] == pytest.approx(row["sum"])
        assert got[k][1] == row["count"]


def test_broadcast_exchange():
    rb = pa.record_batch({"x": pa.array([1, 2, 3], pa.int64())})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=4)
    bc = BroadcastExchangeOp(scan, input_partitions=1)
    # every consumer partition sees the full data
    for p in range(3):
        assert collect_partition_generic(bc, p, 3).column("x").to_pylist() == [1, 2, 3]


def collect_partition_generic(op, p, n):
    from auron_tpu.runtime.executor import ExecutionRuntime, TaskDefinition
    return ExecutionRuntime(op, TaskDefinition(partition_id=p,
                                               num_partitions=n)).collect()


# ---------------------------------------------------------------------------
# mesh all-to-all
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_mesh_all_to_all_exchange():
    from auron_tpu.parallel.mesh_exchange import (exchange_device_batches,
                                                  make_mesh)
    mesh = make_mesh(8)
    n_dev, cap = 8, 128
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 10**6, n_dev * cap).astype(np.int64)
    pids = (vals % n_dev).astype(np.int32)
    num_rows = np.full(n_dev, cap, np.int32)  # all rows live

    out_cols, out_nr, quota = exchange_device_batches(
        mesh, (jnp.asarray(vals),), jnp.asarray(pids), jnp.asarray(num_rows))
    out_vals = np.asarray(out_cols[0])
    out_nr = np.asarray(out_nr)

    # every row lands on the device matching its pid
    local_cap = out_vals.shape[0] // n_dev
    got_all = []
    for d in range(n_dev):
        local = out_vals[d * local_cap: d * local_cap + out_nr[d]]
        assert np.all(local % n_dev == d)
        got_all.extend(local.tolist())
    assert sorted(got_all) == sorted(vals.tolist())


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_mesh_exchange_overflow_retry():
    from auron_tpu.parallel.mesh_exchange import (exchange_device_batches,
                                                  make_mesh)
    from auron_tpu.parallel import mesh_exchange
    mesh = make_mesh(8)
    n_dev, cap = 8, 64
    # fully skewed: every row targets partition 0 → guaranteed overflow at
    # the initial quota, exercising the single-retry escalation path
    vals = np.arange(n_dev * cap, dtype=np.int64)
    pids = np.zeros(n_dev * cap, np.int32)
    num_rows = np.full(n_dev, cap, np.int32)
    mesh_exchange._exchange_fn.cache_clear()
    out_cols, out_nr, quota = exchange_device_batches(
        mesh, (jnp.asarray(vals),), jnp.asarray(pids), jnp.asarray(num_rows))
    # max-count feedback jumps straight to the needed pow2 quota: at most
    # two compiled programs even under extreme skew
    assert mesh_exchange._exchange_fn.cache_info().misses <= 2
    assert quota & (quota - 1) == 0  # pow2 → reusable bucket set
    out_nr = np.asarray(out_nr)
    assert out_nr[0] == n_dev * cap
    assert out_nr[1:].sum() == 0
    local_cap = np.asarray(out_cols[0]).shape[0] // n_dev
    got = np.asarray(out_cols[0])[:out_nr[0]]
    assert sorted(got.tolist()) == vals.tolist()


def test_shuffle_64_partitions_spills_under_pressure(tmp_path):
    """The VERDICT gate: a 64-partition shuffle of a larger-than-budget
    dataset completes with spill counters > 0 — exchange entries are
    memmgr-registered and round-trip host storage with their offset
    index (reference spill contract: sort_repartitioner.rs:44-254)."""
    from auron_tpu.memmgr import MemManager, SpillManager
    from auron_tpu.parallel.partitioning import HashPartitioning

    n_out = 64
    rows = 20_000
    rng = np.random.default_rng(12)
    k = rng.integers(0, 100_000, rows)
    v = rng.normal(size=rows)
    rbs = [pa.record_batch({"k": pa.array(k[i:i + 2048], pa.int64()),
                            "v": pa.array(v[i:i + 2048], pa.float64())})
           for i in range(0, rows, 2048)]
    scan = MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                        capacity=2048)
    ex = ShuffleExchangeOp(
        scan, HashPartitioning((ir.ColumnRef(0),), n_out))
    mm = MemManager(total_bytes=1, min_trigger=0,
                    spill_manager=SpillManager(host_budget_bytes=1 << 22,
                                               spill_dir=str(tmp_path)))
    ctx = ExecContext(mem_manager=mm)
    got = {}
    total = 0
    for p in range(n_out):
        for b in ex.execute(p, ctx):
            n = int(b.num_rows)
            total += n
            col_k = np.asarray(b.columns[0].data[:n])
            col_v = np.asarray(b.columns[1].data[:n])
            for kk, vv in zip(col_k.tolist(), col_v.tolist()):
                got.setdefault(kk, []).append(vv)
    assert total == rows
    spills = ctx.metrics["shuffle_exchange"].counter(
        "mem_spill_count").value
    assert spills > 0, "larger-than-budget exchange must spill"
    # content integrity across the spill round-trip
    exp = {}
    for kk, vv in zip(k.tolist(), v.tolist()):
        exp.setdefault(kk, []).append(vv)
    assert set(got) == set(exp)
    for kk in exp:
        assert sorted(got[kk]) == pytest.approx(sorted(exp[kk]))


def test_broadcast_larger_than_budget_spills(tmp_path):
    """VERDICT r3 directive 6: a broadcast whose collected build side
    exceeds the memory budget must spill via the memmgr (reference
    registers broadcast maps: join_hash_map.rs:365-387) and every consumer
    partition still replays the full content from host tiers."""
    from auron_tpu.memmgr import MemManager, SpillManager
    from auron_tpu.parallel.exchange import BroadcastExchangeOp

    rows = 8_000
    rng = np.random.default_rng(7)
    k = rng.integers(0, 1_000, rows)
    v = rng.normal(size=rows)
    rbs = [pa.record_batch({"k": pa.array(k[i:i + 1024], pa.int64()),
                            "v": pa.array(v[i:i + 1024], pa.float64())})
           for i in range(0, rows, 1024)]
    scan = MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                        capacity=1024)
    bc = BroadcastExchangeOp(scan, input_partitions=1)
    mm = MemManager(total_bytes=1, min_trigger=0,
                    spill_manager=SpillManager(host_budget_bytes=1 << 22,
                                               spill_dir=str(tmp_path)))
    ctx = ExecContext(mem_manager=mm)
    for p in range(3):  # three consumers replay the same broadcast
        got_k, got_v = [], []
        for b in bc.execute(p, ctx):
            n = int(b.num_rows)
            got_k.extend(np.asarray(b.columns[0].data[:n]).tolist())
            got_v.extend(np.asarray(b.columns[1].data[:n]).tolist())
        assert sorted(got_k) == sorted(k.tolist())
        assert sorted(got_v) == pytest.approx(sorted(v.tolist()))
    spills = ctx.metrics["broadcast_exchange"].counter(
        "mem_spill_count").value
    assert spills > 0, "larger-than-budget broadcast must spill"


def test_range_bounds_sampled_in_single_pass():
    """Range partitioning must not execute the child twice (round-1
    weakness): count scan executions."""
    from auron_tpu.parallel.partitioning import RangePartitioning

    rb = pa.record_batch({"x": pa.array(list(range(512)), pa.int64())})
    inner = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=512)
    calls = {"n": 0}

    class CountingScan:
        name = "scan"
        @property
        def children(self):
            return []
        def schema(self):
            return inner.schema()
        def execute(self, p, ctx):
            calls["n"] += 1
            return inner.execute(p, ctx)

    so = ir.SortOrder(ir.ColumnRef(0), True, True)
    ex = ShuffleExchangeOp(CountingScan(),
                           RangePartitioning((so,), 4, ()))
    ctx = ExecContext()
    out = []
    for p in range(4):
        for b in ex.execute(p, ctx):
            n = int(b.num_rows)
            out.extend(np.asarray(b.columns[0].data[:n]).tolist())
    assert sorted(out) == list(range(512))
    assert calls["n"] == 1, "child must execute exactly once"


# -- the range split as a program (PR 41) ----------------------------------

def _range_batches(seed=41, n=40, batches=3, long_at=()):
    """Device batches of (nullable string, int, double) rows; the
    batches at ``long_at`` hold strings of another width bucket."""
    from auron_tpu.columnar.arrow_bridge import to_device
    rng = np.random.default_rng(seed)
    out = []
    for i in range(batches):
        mask = rng.random(n) < 0.2
        tail = "-a-longer-description" if i in long_at else ""
        rb = pa.record_batch({
            "s": pa.array([None if m else "it-%03d" % v + tail for v, m in
                           zip(rng.integers(0, 25, n), mask)], pa.string()),
            "x": pa.array(rng.integers(-100, 100, n), pa.int64()),
            "d": pa.array(np.round(rng.normal(0, 9, n), 2), pa.float64()),
        })
        b, schema = to_device(rb, capacity=64)
        out.append(b)
    return out, schema


_RANGE_ORDERS = (ir.SortOrder(C(0), False, False),
                 ir.SortOrder(C(1), True, True),
                 ir.SortOrder(C(2), False, True))


def test_range_sample_picks_the_parents_bounds():
    """The sample program's words, sorted and cut on the host as before:
    bit for bit the bounds the eager sample chose on this seed (PR 40's
    tree, same batches)."""
    from auron_tpu.parallel.partitioning import (range_bounds,
                                                 sample_range_words)
    batches, schema = _range_batches()
    samples = [sample_range_words(b, _RANGE_ORDERS, schema) for b in batches]
    bounds, layout = range_bounds(samples, _RANGE_ORDERS, 4)
    assert layout == (2, 2, 2)
    assert bounds == (
        (0, 10847995917421248511, 1, 9223372036854775852, 1,
         13845022269457720934),
        (0, 10847995917437960191, 1, 9223372036854775817, 1,
         13841149173778182308),
        (0, 10847995917438418943, 1, 9223372036854775741, 1,
         13844082143035507343))


def _reference_pids(words: np.ndarray, bounds) -> list:
    """Plain lexicographic searchsorted (side right) over word tuples."""
    return [sum(tuple(int(w) for w in row) >= tuple(b) for b in bounds)
            for row in words]


@pytest.mark.parametrize("case", ["plain", "deduplicated_bound",
                                  "empty_batch", "null_keys",
                                  "wider_batch", "narrower_batch"])
def test_range_split_program_matches_lexicographic_searchsorted(case):
    """The split program's partition ids — rows counted a partition, and
    the rows each partition holds in their input order — equal a numpy
    searchsorted over the same order words and bounds, row for row."""
    from auron_tpu.columnar.arrow_bridge import to_arrow
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.ops.base import MetricsSet, timer
    from auron_tpu.parallel.exchange import _Split
    from auron_tpu.parallel.partitioning import (_align_words, range_bounds,
                                                 sample_range_words)
    long_at = {"wider_batch": (2,), "narrower_batch": (0, 1)}.get(case, ())
    batches, schema = _range_batches(long_at=long_at)
    sample, probe = batches[:2], batches[2]
    samples = [sample_range_words(b, _RANGE_ORDERS, schema) for b in sample]
    if case == "deduplicated_bound":
        # a degenerate sample: one row, so that every bound is the same
        samples = [(samples[0][0][:1], samples[0][1])]
    bounds, layout = range_bounds(samples, _RANGE_ORDERS, 4)
    assert len(bounds) == (1 if case == "deduplicated_bound" else 3)
    if case == "empty_batch":
        probe = DeviceBatch(probe.columns, 0)
    part = RangePartitioning(_RANGE_ORDERS, 4, bounds, layout)

    words, probe_layout = sample_range_words(probe, _RANGE_ORDERS, schema)
    assert (probe_layout != layout) == (case in ("wider_batch",
                                                 "narrower_batch"))
    target = tuple(max(a, b) for a, b in zip(probe_layout, layout))
    b_mat = _align_words(np.array(bounds, np.uint64), layout, target,
                         _RANGE_ORDERS)
    want = _reference_pids(
        _align_words(words, probe_layout, target, _RANGE_ORDERS),
        [tuple(int(x) for x in b) for b in b_mat])
    if case == "null_keys":
        assert probe.columns[0].validity[:len(want)].sum() < len(want)

    # the traced ids themselves, bounds as an operand
    mat, n_live = part.bounds_operand()
    assert mat.shape[0] == 3 and n_live == len(bounds)
    got = np.asarray(jax.jit(
        lambda b, m, n: part.partition_ids(b, schema, (m, n)))(
            probe, mat, n_live))[:len(want)]
    assert got.tolist() == want

    # and through the program every route splits with
    split = _Split(part, 4, schema, schema, False)
    with timer(MetricsSet(name="shuffle_exchange").counter("t")) as t:
        sorted_batch, counts, _carries, _ = split(probe, 0, split.carries(),
                                                  t)
    assert counts.tolist() == [want.count(p) for p in range(4)]
    rows = to_arrow(probe, schema).column("x").to_pylist()[:len(want)]
    stable = [r for p in range(4) for r, w in zip(rows, want) if w == p]
    assert to_arrow(sorted_batch, schema).column("x").to_pylist() == stable
