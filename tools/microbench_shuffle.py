"""Shuffle durable-tier microbench: frame checksum overhead A/B.

Measures the engine's shuffle path with ``auron.durability.checksum``
on vs off and prints the relative overhead (median of PAIRED
interleaved reps, alternating order, so system drift cancels). The
ISSUE 4 acceptance gate is < 3% regression on the default ``e2e`` mode
— a full RssShuffleExchangeOp materialize+read cycle, exactly the path
queries pay (partition-id kernel, device→host, serde, durable-tier
framing+CRC, host→device). Spill frames share the same CRC code path,
so this is the integrity tax for both durable tiers.

``--mode serde`` strips the device/kernel half and measures
serialize→write→commit→fetch→deserialize; ``--mode raw`` strips serde
too and measures framing+CRC alone over opaque frames — the most
adversarial slice (nothing amortizes the checksum), for sizing the CRC
itself, not the gate.

    python tools/microbench_shuffle.py                  # e2e, the gate
    python tools/microbench_shuffle.py --mode serde --rows 32768
    python tools/microbench_shuffle.py --mode raw --gate 100

Prints one human table and ends with ONE JSON line (same driver
contract as bench.py / compile_report.py).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_host_batches(n_batches: int, rows: int):
    """Serde-level host batches (3 columns: int64 key, f64 value, int32
    code — the chaos/TPC-DS row shape) — built directly so the bench
    needs no device round trip."""
    import numpy as np

    from auron_tpu.columnar.serde import HostBatch, HostPrimitive

    rng = np.random.default_rng(11)
    out = []
    for _ in range(n_batches):
        valid = np.ones(rows, bool)
        out.append(HostBatch([
            HostPrimitive(rng.integers(0, 1 << 40, rows,
                                       dtype=np.int64), valid),
            HostPrimitive(rng.normal(size=rows), valid),
            HostPrimitive(rng.integers(0, 1000, rows,
                                       dtype=np.int32).astype(np.int32),
                          valid),
        ], rows))
    return out


def _run_serde(root: str, hosts, num_partitions: int) -> tuple[float, int]:
    """One serialize→write→commit→fetch→deserialize cycle; returns
    (wall seconds, payload bytes on the durable tier)."""
    from auron_tpu.columnar.serde import (deserialize_host_batch,
                                          serialize_host_batch)
    from auron_tpu.parallel.shuffle_service import FileShuffleService

    service = FileShuffleService(root)
    t0 = time.perf_counter()
    nbytes = 0
    with service.partition_writer(1, 0, num_partitions) as w:
        for i, host in enumerate(hosts):
            frame = serialize_host_batch(host, codec_level=1)
            nbytes += len(frame)
            w.write(i % num_partitions, frame)
        w.commit()
    service.commit_shuffle(1, 1)
    rows = 0
    for p in range(num_partitions):
        for fr in service.map_partition_frames(1, 0, p):
            host, _ = deserialize_host_batch(fr)
            rows += host.num_rows
    dt = time.perf_counter() - t0
    assert rows == sum(h.num_rows for h in hosts)
    service.delete_shuffle(1)
    return dt, nbytes


def _make_record_batches(n_batches: int, rows: int):
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(11)
    return [pa.record_batch({
        "k": pa.array(rng.integers(0, 1 << 20, rows), pa.int64()),
        "v": pa.array(rng.normal(size=rows)),
        "c": pa.array(rng.integers(0, 1000, rows), pa.int32()),
    }) for _ in range(n_batches)]


def _run_e2e(root: str, rbs, num_partitions: int) -> tuple[float, int]:
    """One full RssShuffleExchangeOp materialize+read cycle — the
    engine's shuffle path exactly as queries drive it (partition-id
    kernel, device→host, serde, durable tier, host→device)."""
    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.parallel.exchange import RssShuffleExchangeOp
    from auron_tpu.parallel.partitioning import HashPartitioning
    from auron_tpu.parallel.shuffle_service import FileShuffleService
    from auron_tpu.runtime.executor import collect

    service = FileShuffleService(root)
    scan = MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                        capacity=rbs[0].num_rows)
    op = RssShuffleExchangeOp(
        scan, HashPartitioning([ir.ColumnRef(0)], num_partitions),
        service, shuffle_id=1, input_partitions=1)
    t0 = time.perf_counter()
    out = collect(op, num_partitions=num_partitions)
    dt = time.perf_counter() - t0
    assert out.num_rows == sum(rb.num_rows for rb in rbs)
    nbytes = sum(os.path.getsize(p) for p in service.map_outputs(1))
    service.delete_shuffle(1)
    return dt, nbytes


def _run_raw(root: str, frames, num_partitions: int) -> tuple[float, int]:
    """Framing-only cycle over opaque frames (no serde)."""
    from auron_tpu.parallel.shuffle_service import FileShuffleService

    service = FileShuffleService(root)
    t0 = time.perf_counter()
    with service.partition_writer(1, 0, num_partitions) as w:
        for i, fr in enumerate(frames):
            w.write(i % num_partitions, fr)
        w.commit()
    service.commit_shuffle(1, 1)
    fetched = 0
    for p in range(num_partitions):
        for fr in service.map_partition_frames(1, 0, p):
            fetched += len(fr)
    dt = time.perf_counter() - t0
    assert fetched == sum(len(f) for f in frames)
    service.delete_shuffle(1)
    return dt, fetched


def bench(args) -> dict:
    import numpy as np

    from auron_tpu import config as cfg
    from auron_tpu.utils import checksum as cks

    if args.mode == "raw":
        rng = np.random.default_rng(11)
        payload = [rng.integers(0, 64, args.frame_kb << 10,
                                dtype=np.uint8).tobytes()
                   for _ in range(args.batches)]
        runner = _run_raw
    elif args.mode == "serde":
        payload = _make_host_batches(args.batches, args.rows)
        runner = _run_serde
    else:
        payload = _make_record_batches(args.batches, args.rows)
        runner = _run_e2e

    conf = cfg.get_config()
    root = tempfile.mkdtemp(prefix="shuffle_bench_")
    on_times, off_times, nbytes = [], [], 0
    try:
        # warm-up rep (page cache, import paths) then PAIRED interleaved
        # reps: each rep runs on then off back to back, and the reported
        # overhead is the MEDIAN of per-rep ratios — system drift between
        # reps cancels within a pair instead of polluting the A/B
        conf.set(cfg.DURABILITY_CHECKSUM, False)
        runner(os.path.join(root, "warmup"), payload, args.partitions)
        for r in range(args.reps):
            # alternate which half goes first so ordering effects
            # (page-cache state, allocator warmth) cancel across reps
            for on in ((True, False) if r % 2 == 0 else (False, True)):
                import gc
                gc.collect()   # keep collector pauses out of the pair
                conf.set(cfg.DURABILITY_CHECKSUM, on)
                dt, nbytes = runner(
                    os.path.join(root, f"{'on' if on else 'off'}_{r}"),
                    payload, args.partitions)
                (on_times if on else off_times).append(dt)
    finally:
        conf.unset(cfg.DURABILITY_CHECKSUM)
        shutil.rmtree(root, ignore_errors=True)
    mb = nbytes / 2**20
    ratios = sorted(a / b for a, b in zip(on_times, off_times))
    overhead = ratios[len(ratios) // 2] - 1.0
    import jax
    return {
        "mode": args.mode,
        "platform": jax.devices()[0].platform,
        "algo": {cks.ALGO_CRC32C: "crc32c", cks.ALGO_CRC32: "zlib-crc32"}[
            cks.preferred_algo()],
        "frames": args.batches, "mb": round(mb, 1), "reps": args.reps,
        "shuffle_mb_per_sec_checksum_on": mb / min(on_times),
        "shuffle_mb_per_sec_checksum_off": mb / min(off_times),
        "checksum_overhead_pct": overhead * 100.0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=65536,
                    help="rows per batch (serde mode; the engine's "
                         "default spill/shuffle frame)")
    ap.add_argument("--batches", type=int, default=32,
                    help="batches (frames in --raw mode)")
    ap.add_argument("--frame-kb", type=int, default=256,
                    help="bytes per frame (KiB, --raw mode)")
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mode", choices=["e2e", "serde", "raw"],
                    default="e2e",
                    help="e2e: the engine's full exchange path (the "
                         "gate); serde: serialize+frame+fetch only; "
                         "raw: framing+CRC over opaque frames (the "
                         "most adversarial slice)")
    ap.add_argument("--gate", type=float, default=None,
                    help="fail (exit 1) when overhead exceeds this pct")
    args = ap.parse_args(argv)

    r = bench(args)
    print(f"mode                 {r['mode']}")
    print(f"platform             {r['platform']}")
    print(f"algorithm            {r['algo']}")
    print(f"payload              {r['frames']} frames, {r['mb']:.0f} MiB "
          f"on the durable tier, {args.partitions} partitions")
    print(f"checksum on          {r['shuffle_mb_per_sec_checksum_on']:.0f} "
          f"MiB/s (write+commit+fetch)")
    print(f"checksum off         {r['shuffle_mb_per_sec_checksum_off']:.0f} "
          f"MiB/s")
    print(f"overhead             {r['checksum_overhead_pct']:+.2f}%")
    print(json.dumps(r))
    if args.gate is not None and r["checksum_overhead_pct"] > args.gate:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
