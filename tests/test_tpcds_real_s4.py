"""Shard 4 of 6 of the real-schema TPC-DS gate (tests/tpcds_real_shard.py)."""

from tests.tpcds_real_shard import define

define(4, globals())
