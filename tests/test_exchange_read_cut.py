"""The reduce side's read of an exchange buffer as ONE program (PR 39).

``parallel.exchange.read_cut`` cuts the slices a reducer reads out of a
column tree with the slice starts and live counts as operands. Here, on
the CPU's virtual devices, against the eager ``gather_batch`` it
replaced:

- the mesh buffer's layout (shard ``p`` = ``[src * quota + r]``): one
  call a partition and round gives the batches the eager per-(source,
  round) gathers gave, leaf for leaf on the live rows, padding invalid,
  source-major and rounds-minor, empty slices skipped, on the home
  device;
- the host route's ``(lo, n_p)`` of one sorted entry;
- the demoted buffer still interleaves source-major;
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

#: live rows a (partition, source): an empty source, a full quota, a
#: partition nothing reached, a lone row
COUNTS = {
    "mixed": [[5, 0, QUOTA, 17], [1, 2, 3, 4], [0, 0, 0, 0], [0, 0, 0, 1]],
    "full": [[QUOTA] * N_DEV] * N_DEV,
    "one_empty_source": [[9, 9, 0, 9]] * N_DEV,
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


def _assert_same_live_rows(got: DeviceBatch, want: DeviceBatch):
    n = int(want.num_rows)
    assert int(got.num_rows) == n
    assert got.num_rows.dtype == want.num_rows.dtype
    got_leaves, got_tree = jax.tree_util.tree_flatten(got.columns)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want.columns)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape[1:] == w.shape[1:]
        np.testing.assert_array_equal(np.asarray(g)[:n], np.asarray(w)[:n])
    for col in got.columns:
        assert not np.asarray(col.validity)[n:].any()


def _shard(batch: DeviceBatch, p: int):
    return jax.tree_util.tree_map(
        lambda a: a[p * SHARD_ROWS:(p + 1) * SHARD_ROWS], batch.columns)


@pytest.mark.parametrize("case", COUNTS)
@pytest.mark.parametrize("kind", KINDS)
def test_mesh_partition_is_cut_as_the_eager_gathers_cut_it(kind, case):
    mesh = _mesh()
    home = mesh.devices.flat[0]
    first = np.asarray(COUNTS[case])
    second = first[::-1].copy()          # another round, other counts
    rounds = [(_batch(kind, N_DEV * SHARD_ROWS, 1), first),
              (_batch(kind, N_DEV * SHARD_ROWS, 2), second)]
    buf = _mesh_buffer(mesh, rounds)
    site = programs.site("parallel.exchange.read_cut")
    calls = lambda: site.stats()["builds"] + site.stats()["hits"]
    for p in range(N_DEV):
        before = calls()
        got = list(buf.partition_batches(p))
        # one call a round that brought the partition anything
        assert calls() - before == sum(
            1 for _b, c in rounds if c[p].max() > 0)
        want = [(c[p], _eager(_shard(b, p), s * QUOTA, int(c[p, s])))
                for s in range(N_DEV) for b, c in rounds if c[p, s] > 0]
        assert len(got) == len(want)
        for g, (live, w) in zip(got, want):
            _assert_same_live_rows(g, w)
            # one capacity a partition and round: its fullest slice's
            assert g.capacity == bucket_rows(int(live.max()))
            assert all(leaf.devices() == {home}
                       for leaf in jax.tree_util.tree_leaves(g))
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


def test_demoted_buffer_still_interleaves_source_major():
    mesh = _mesh()
    counts = np.asarray(COUNTS["mixed"])
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
        assert [ids(b) for b in demoted.partition_batches(p)] == want
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
                   for p in range(N_DEV)) == int(np.count_nonzero(counts))
        builds.append(site.stats()["builds"] - before)
        buf.close()
    assert builds[0] <= 1 and builds[1:] == [0, 0]
