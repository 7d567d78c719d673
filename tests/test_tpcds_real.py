"""The real-schema TPC-DS gate runs as six shards, one file each
(tests/test_tpcds_real_s<i>.py, built by tests/tpcds_real_shard.py);
this file holds that together they are the whole of it."""

from auron_tpu.it.tpcds_queries import QUERIES
from tests.tpcds_real_shard import SHARDS, shard_names


def test_shards_partition_the_queries():
    seen = [name for i in range(SHARDS) for name in shard_names(i)]
    assert sorted(seen) == sorted(q.name for q in QUERIES)
    # the 99 query shapes, and q65m: q65 with money kept decimal
    assert len(set(seen)) == len(QUERIES) == 100     # none twice
