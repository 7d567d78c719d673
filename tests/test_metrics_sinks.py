"""Streaming sinks: file sinks must stream bounded chunks instead of
buffering the whole partition (parquet_sink_exec.rs streams row
groups)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.io.sinks import OrcSinkOp, ParquetSinkOp
from auron_tpu.runtime.executor import collect


def _scan(rb, capacity=4096, nbatches=1):
    rbs = [rb] * nbatches
    return MemoryScanOp([rbs], schema_from_arrow(rb.schema),
                        capacity=capacity)


class TestStreamingSinks:
    def test_parquet_sink_streams_row_groups(self, tmp_path):
        rng = np.random.default_rng(5)
        rb = pa.record_batch({
            "a": pa.array(rng.integers(0, 100, 1000), pa.int64()),
        })
        conf = cfg.AuronConfig({cfg.SINK_BUFFER_ROWS: 1000})
        sink = ParquetSinkOp(_scan(rb, capacity=1024, nbatches=8),
                             str(tmp_path / "out"))
        res = collect(sink, config=conf)
        assert res.column("num_rows").to_pylist() == [8000]
        f = pq.ParquetFile(str(tmp_path / "out" / "part-00000.parquet"))
        # 8 batches of 1000 rows with a 1000-row buffer → multiple flushes,
        # one row group each: the whole partition was never buffered
        assert f.metadata.num_row_groups >= 4
        assert f.metadata.num_rows == 8000
        table = f.read()
        assert table.column("a").to_pylist() == rb.column("a").to_pylist() * 8

    def test_parquet_sink_dynamic_partitions_stream(self, tmp_path):
        rb = pa.record_batch({
            "k": pa.array([0, 1] * 500, pa.int64()),
            "v": pa.array(np.arange(1000), pa.int64()),
        })
        conf = cfg.AuronConfig({cfg.SINK_BUFFER_ROWS: 1000})
        sink = ParquetSinkOp(_scan(rb, capacity=1024, nbatches=4),
                             str(tmp_path / "ds"), partition_by=["k"])
        res = collect(sink, config=conf)
        assert res.column("num_rows").to_pylist() == [4000]
        got = pq.read_table(str(tmp_path / "ds"))
        assert got.num_rows == 4000
        # hive layout with one dir per key
        assert (tmp_path / "ds" / "k=0").is_dir()
        assert (tmp_path / "ds" / "k=1").is_dir()

    def test_sink_failure_leaves_no_output(self, tmp_path):
        """Mid-stream child failure must not leave a truncated-but-valid
        output file behind (all-or-nothing per attempt)."""
        from auron_tpu.ops.base import PhysicalOp

        class _FailingOp(PhysicalOp):
            name = "failing"

            def __init__(self, inner, after):
                self.inner, self.after = inner, after

            def schema(self):
                return self.inner.schema()

            def execute(self, partition, ctx):
                def stream():
                    for i, b in enumerate(self.inner.execute(partition, ctx)):
                        if i >= self.after:
                            raise RuntimeError("child blew up")
                        yield b
                return stream()

        rb = pa.record_batch({"a": pa.array(np.arange(1000), pa.int64())})
        conf = cfg.AuronConfig({cfg.SINK_BUFFER_ROWS: 500})
        sink = ParquetSinkOp(
            _FailingOp(_scan(rb, capacity=1024, nbatches=6), after=3),
            str(tmp_path / "boom"))
        with pytest.raises(RuntimeError):
            collect(sink, config=conf)
        # the partial part file (2+ flushed chunks) must be gone
        assert not (tmp_path / "boom" / "part-00000.parquet").exists()

    def test_orc_sink_streams(self, tmp_path):
        rb = pa.record_batch({"a": pa.array(np.arange(500), pa.int64())})
        conf = cfg.AuronConfig({cfg.SINK_BUFFER_ROWS: 400})
        sink = OrcSinkOp(_scan(rb, capacity=512, nbatches=5),
                         str(tmp_path / "orc"))
        res = collect(sink, config=conf)
        assert res.column("num_rows").to_pylist() == [2500]
        from pyarrow import orc
        got = orc.read_table(str(tmp_path / "orc" / "part-00000.orc"))
        assert got.num_rows == 2500
        assert got.column("a").to_pylist() == list(np.arange(500)) * 5
