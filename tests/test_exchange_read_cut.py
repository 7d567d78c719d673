"""The reduce side's read of an exchange buffer as ONE program (PR 39)
that hands a reducer ONE batch a partition (PR 49).

``parallel.exchange.read_cut`` gathers the slices a reducer reads out of
column trees into one batch, with the slice starts and live counts as
operands. Here, on the CPU's virtual devices, against the eager
``gather_batch`` of every slice:

- the mesh buffer's layout (shard ``p`` = ``[src * quota + r]``, a tree
  a round): one call a partition gives ONE batch that holds what the
  eager per-(source, round) gathers gave one after another, leaf for
  leaf on the live rows, padding invalid, source-major and
  rounds-minor, empty slices skipped, on the home device, its row count
  a host integer; over one, two and three rounds; packed into as few
  batches as hold it where it exceeds the batch capacity, none above
  it; no batch and no launch for a partition nothing reached;
- the host route's ``(lo, n_p)`` of one sorted entry, and its entries
  packed the same way, spilled ones restored among them;
- the demoted buffer still interleaves source-major, both tiers'
  slices in one packing;
- the program's key follows shapes, never the counts.
"""

import decimal

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from auron_tpu.columnar.arrow_bridge import to_device
from auron_tpu.columnar.batch import DeviceBatch, gather_batch
from auron_tpu.obs import trace
from auron_tpu.ops.base import MetricsSet
from auron_tpu.parallel import exchange
from auron_tpu.runtime import programs
from auron_tpu.utils.shapes import bucket_rows

N_DEV = 4
QUOTA = 32
SHARD_ROWS = N_DEV * QUOTA

pytestmark = pytest.mark.skipif(len(jax.devices()) < N_DEV,
                                reason="needs 4 virtual devices")


def _arrow_columns(n: int, seed: int) -> dict:
    """One column of every kind that crosses an exchange here, with a
    row id beside it."""
    rng = np.random.default_rng(seed)
    ints = rng.integers(-2**40, 2**40, n)
    wide = [decimal.Decimal(int(v)) * 10**15 + decimal.Decimal(int(v)) / 100
            for v in ints]
    return {
        "primitive": pa.array(ints, pa.int64()),
        "string": pa.array([f"brand #{v % 977}" if v % 11 else None
                            for v in ints], pa.string()),
        "decimal128": pa.array(wide, pa.decimal128(38, 2)),
        "double_payload": pa.array(rng.normal(0, 1e5, n), pa.float64()),
        "nullable_key": pa.array(
            [int(v % 401) if v % 7 else None for v in ints], pa.int32()),
    }


KINDS = tuple(_arrow_columns(1, 0))

_MIXED = [[5, 0, QUOTA, 17], [1, 2, 3, 4], [0, 0, 0, 0], [0, 0, 0, 1]]
_FULL = [[QUOTA] * N_DEV] * N_DEV
_ONE_EMPTY = [[9, 9, 0, 9]] * N_DEV

#: (live rows a (partition, source) of every round, the batch capacity
#: or None for the configured one): an empty source, a full quota, a
#: partition nothing reached, a lone row
CASES = {
    "mixed": ([_MIXED, _MIXED[::-1]], None),
    "full": ([_FULL, _FULL], None),
    "one_empty_source": ([_ONE_EMPTY, _ONE_EMPTY], None),
    "one_round": ([_MIXED], None),
    "three_rounds": ([_MIXED, _ONE_EMPTY, _MIXED[::-1]], None),
    # 2 x 4 x 32 rows a partition, three slices fit a batch of 100
    "over_capacity": ([_FULL, _FULL], 100),
    # partition 2 is empty in every round
    "a_partition_nothing_reached": ([_MIXED, _MIXED], None),
    # nothing to merge: slices / batches = 1
    "one_source": ([[[0, 9, 0, 0]] * N_DEV], None),
}


def _batch(kind: str, n: int, seed: int):
    cols = _arrow_columns(n, seed)
    rb = pa.record_batch({"id": pa.array(np.arange(n) + seed * 100_000,
                                         pa.int64()),
                          kind: cols[kind]})
    batch, _schema = to_device(rb, capacity=n)
    return batch


def _mesh():
    return Mesh(np.array(jax.devices()[:N_DEV]), ("data",))


def _mesh_buffer(mesh, rounds):
    """A mesh buffer holding ``rounds`` = [(global batch, counts)]."""
    buf = exchange._MeshExchangeBuffer(object(), mesh, "data", N_DEV, None,
                                       MetricsSet())
    for batch, counts in rounds:
        cols = jax.device_put(batch.columns,
                              NamedSharding(mesh, P("data")))
        buf.add_round(cols, np.asarray(counts, np.int64), QUOTA)
    return buf


def _eager(columns, lo: int, n: int) -> DeviceBatch:
    """What the read gave before it was a program."""
    base = DeviceBatch(columns, jnp.asarray(n, jnp.int32))
    idx = jnp.minimum(lo + jnp.arange(bucket_rows(n), dtype=jnp.int32),
                      base.capacity - 1)
    return gather_batch(base, idx, jnp.asarray(n, jnp.int32))


def _live_rows(batch: DeviceBatch) -> list:
    n = int(batch.num_rows)
    return [np.asarray(leaf)[:n]
            for leaf in jax.tree_util.tree_leaves(batch.columns)]


def _assert_same_live_rows(got: DeviceBatch, *want: DeviceBatch):
    """``got`` holds the live rows of ``want``, one batch after another,
    and nothing valid past them."""
    n = sum(int(w.num_rows) for w in want)
    assert int(got.num_rows) == n
    assert got.num_rows.dtype == want[0].num_rows.dtype
    got_leaves, got_tree = jax.tree_util.tree_flatten(got.columns)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want[0].columns)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape[1:] == w.shape[1:]
    for g, *ws in zip(_live_rows(got), *map(_live_rows, want)):
        np.testing.assert_array_equal(g, np.concatenate(ws))
    for col in got.columns:
        assert not np.asarray(col.validity)[n:].any()


def _pack(sizes: list, capacity: int) -> list:
    """The greedy packing: indices of ``sizes``, in order, in runs whose
    sum fits ``capacity``."""
    runs, rows = [], 0
    for k, n in enumerate(sizes):
        if not runs or rows + n > capacity:
            runs.append([])
            rows = 0
        runs[-1].append(k)
        rows += n
    return runs


def _live_rounds(rounds, p: int) -> list:
    """The rounds that brought partition ``p`` anything."""
    return [c for _b, c in rounds if c[p].max() > 0]


def _shard(batch: DeviceBatch, p: int):
    return jax.tree_util.tree_map(
        lambda a: a[p * SHARD_ROWS:(p + 1) * SHARD_ROWS], batch.columns)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", KINDS)
def test_mesh_partition_is_cut_as_the_eager_gathers_cut_it(kind, case):
    mesh = _mesh()
    home = mesh.devices.flat[0]
    tables, capacity = CASES[case]
    rounds = [(_batch(kind, N_DEV * SHARD_ROWS, 1 + r), np.asarray(counts))
              for r, counts in enumerate(tables)]
    buf = _mesh_buffer(mesh, rounds)
    if capacity is not None:
        buf.capacity = capacity
    site = programs.site("parallel.exchange.read_cut")
    calls = lambda: site.stats()["builds"] + site.stats()["hits"]
    for p in range(N_DEV):
        before = calls()
        with trace.task_scope(f"read-{p}") as acc:
            got = list(buf.partition_batches(p))
            counted = acc.sealed(1.0)["counts"]
        # what the eager read gave: a batch a non-empty (source, round)
        want = [_eager(_shard(b, p), s * QUOTA, int(c[p, s]))
                for s in range(N_DEV) for b, c in rounds if c[p, s] > 0]
        sizes = [int(w.num_rows) for w in want]
        # ... packed in that order into as few batches as hold them
        runs = _pack(sizes, buf.capacity)
        # one launch a batch, none for a partition nothing reached
        assert calls() - before == len(got) == len(runs)
        assert len(runs) == (1 if want and capacity is None else
                             3 if want else 0)
        for g, run in zip(got, runs):
            _assert_same_live_rows(g, *(want[k] for k in run))
            # a host integer: nobody reads it from the device
            assert isinstance(g.num_rows, np.int32)
            assert int(g.num_rows) <= buf.capacity
            if capacity is None:
                # the capacity follows the read's shape, never the sum
                # of its counts: every (source, round) x the bucket of
                # the fullest slice
                assert g.capacity == bucket_rows(
                    N_DEV * len(_live_rounds(rounds, p))
                    * bucket_rows(max(sizes)))
            else:
                assert g.capacity == bucket_rows(int(g.num_rows))
            assert all(leaf.devices() == {home}
                       for leaf in jax.tree_util.tree_leaves(g.columns))
        assert counted["mesh_read_batches"] == len(runs)
        assert counted["mesh_read_slices"] == len(want)
        assert counted["mesh_read_rows"] == sum(sizes)
        if case == "one_source":
            assert counted["mesh_read_slices"] == \
                counted["mesh_read_batches"] == 1
    buf.close()


@pytest.mark.parametrize("kind", KINDS)
def test_host_route_entry_is_cut_at_lo_and_n(kind):
    cap = 64
    batch = _batch(kind, cap, 3)
    # the last partition's bucket reaches past the entry's capacity
    offsets = np.array([0, 0, 7, 40, 64], np.int64)
    buf = exchange._ExchangeBuffer(object(), None, MetricsSet())
    buf.add(batch, offsets)
    assert list(buf.partition_batches(0)) == []
    for p in range(1, 4):
        (got,) = buf.partition_batches(p)
        lo, n = int(offsets[p]), int(offsets[p + 1] - offsets[p])
        _assert_same_live_rows(got, _eager(batch.columns, lo, n))
        assert got.capacity == bucket_rows(n)
    buf.close()


class _Spilled:
    """A spilled entry's handle: one serialized frame a partition."""

    def __init__(self, batch: DeviceBatch, offsets):
        from auron_tpu.columnar.serde import (batch_to_host,
                                              serialize_host_batch,
                                              slice_host_batch)
        host = batch_to_host(batch, int(offsets[-1]))
        self.frames = [serialize_host_batch(slice_host_batch(
            host, int(lo), int(hi))) for lo, hi in zip(offsets, offsets[1:])]

    def frame_at(self, p: int) -> bytes:
        return self.frames[p]

    def release(self):
        pass


@pytest.mark.parametrize("spilled", [(), (1,), (0, 2)],
                         ids=["on_device", "middle_spilled", "ends_spilled"])
@pytest.mark.parametrize("capacity", [65_536, 40],
                         ids=["one_batch", "packed"])
@pytest.mark.parametrize("kind", KINDS)
def test_host_route_hands_on_as_few_batches_as_hold_the_rows(
        kind, capacity, spilled):
    """The host buffer's entries reach a reducer packed like a mesh
    partition's slices (the four one-row batches of a gather as one
    batch): the same batches whichever entries spilled, so a double sum
    adds up in one order under memory pressure and without it."""
    cap = 64
    entries = [_batch(kind, cap, 20 + i) for i in range(3)]
    offsets = [np.array([0, 0, 7, 40, 64], np.int64),
               np.array([0, 5, 5, 6, 64], np.int64),   # nothing for p = 1
               np.array([0, 0, 1, 64, 64], np.int64)]  # nothing for p = 3
    buf = exchange._ExchangeBuffer(object(), None, MetricsSet())
    buf.capacity = capacity
    for e, o in zip(entries, offsets):
        buf.add(e, o)
    for i in spilled:
        buf.entries[i] = ["spill", _Spilled(entries[i], offsets[i]),
                          offsets[i], cap]
    site = programs.site("parallel.exchange.read_cut")
    calls = lambda: site.stats()["builds"] + site.stats()["hits"]
    for p in range(4):
        before = calls()
        got = list(buf.partition_batches(p))
        sizes = [int(o[p + 1] - o[p]) for o in offsets]
        want = [[_eager(entries[i].columns, int(offsets[i][p]), sizes[i])
                 for i in run if sizes[i]]
                for run in _pack(sizes, capacity)]
        want = [w for w in want if w]
        assert len(got) == len(want) and len(got) == \
            (1 if capacity > 64 else {0: 1, 1: 1, 2: 2, 3: 2}[p])
        # a launch a batch (a restored frame that is a batch already is
        # handed on as it is)
        assert calls() - before <= len(got)
        for g, w in zip(got, want):
            _assert_same_live_rows(g, *w)
            assert isinstance(g.num_rows, np.int32)
            assert int(g.num_rows) <= max(capacity, max(sizes))
    buf.entries = []
    buf.close()


def test_demoted_buffer_still_interleaves_source_major():
    mesh = _mesh()
    counts = np.asarray(_MIXED)
    mesh_buf = _mesh_buffer(
        mesh, [(_batch("primitive", N_DEV * SHARD_ROWS, 1), counts)])
    host_buf = exchange._ExchangeBuffer(object(), None, MetricsSet())
    # host entries of maps 2, 0, 2 (append order), 16 rows a partition
    host_sources = [2, 0, 2]
    offsets = np.arange(N_DEV + 1, dtype=np.int64) * 16
    entries = [_batch("primitive", 64, 10 + i) for i in range(3)]
    for e in entries:
        host_buf.add(e, offsets)
    demoted = exchange._DemotedExchangeBuffer(mesh_buf, host_buf,
                                              host_sources, N_DEV)

    def ids(b):
        return np.asarray(b.columns[0].data)[:int(b.num_rows)].tolist()

    global_ids = np.arange(N_DEV * SHARD_ROWS) + 100_000
    for p in range(N_DEV):
        want = []
        for s in range(N_DEV):
            if counts[p, s] > 0:
                lo = p * SHARD_ROWS + s * QUOTA
                want.append(global_ids[lo:lo + counts[p, s]].tolist())
            for i, src in enumerate(host_sources):
                if src == s:
                    lo = (10 + i) * 100_000 + 16 * p
                    want.append(list(range(lo, lo + 16)))
        # ... in ONE batch: the slices of both tiers, packed as the pure
        # routes pack them
        assert [ids(b) for b in demoted.partition_batches(p)] == \
            [sum(want, [])]
    demoted.close()


def test_the_key_follows_shapes_and_never_the_counts():
    mesh = _mesh()
    site = programs.site("parallel.exchange.read_cut")
    batch = _batch("double_payload", N_DEV * SHARD_ROWS, 5)
    # three count matrices whose fullest slice shares one bucket
    tables = ([[17, 20, 31, 32]] * N_DEV, [[32, 0, 0, 0]] * N_DEV,
              [[1, 1, 1, 18]] * N_DEV)
    builds = []
    for counts in tables:
        buf = _mesh_buffer(mesh, [(batch, counts)])
        before = site.stats()["builds"]
        assert sum(len(list(buf.partition_batches(p)))
                   for p in range(N_DEV)) == N_DEV
        builds.append(site.stats()["builds"] - before)
        buf.close()
    assert builds[0] <= 1 and builds[1:] == [0, 0]
