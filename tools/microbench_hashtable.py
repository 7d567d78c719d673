"""Microbenchmark for the device hash table (auron_tpu/hashtable):
build / probe / agg_update in isolation, plus the fused single-shot
grouped aggregation against the sort-based formulation.

    python tools/microbench_hashtable.py                 # defaults
    python tools/microbench_hashtable.py --rows 20 --keys 16
    # rows/keys are log2; --dups runs the duplicate-heavy shape

Prints one human table and ends with ONE JSON line (same driver contract
as bench.py / compile_report.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, iters: int = 5):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=20, help="log2 input rows")
    ap.add_argument("--keys", type=int, default=16,
                    help="log2 distinct keys")
    ap.add_argument("--load", type=float, default=0.125,
                    help="table load factor (capacity sizing)")
    args = ap.parse_args(argv)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from auron_tpu.columnar.batch import PrimitiveColumn
    from auron_tpu.hashtable import grouped_agg_once
    from auron_tpu.hashtable import core
    from auron_tpu.hashtable.agg import _hashes
    from auron_tpu.utils.shapes import next_pow2

    n = 1 << args.rows
    n_keys = 1 << args.keys
    cap = next_pow2(int(n_keys / args.load))
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, n_keys, n).astype(np.int64))
    v = jnp.asarray(rng.normal(size=n))
    valid = jnp.asarray(rng.random(n) > 0.05)
    live = jnp.ones(n, bool)
    keys = (PrimitiveColumn(k, jnp.ones(n, bool)),)
    meta = core.key_meta(keys)
    results = {}

    # -- build: hash + claim rounds + install --------------------------------
    @jax.jit
    def build(k):
        cols = (PrimitiveColumn(k, jnp.ones(n, bool)),)
        h = _hashes(cols, n)
        w = core.key_words(cols, meta)
        th = jnp.full(cap, core.EMPTY, jnp.uint64)
        tw = jnp.zeros((cap, core.total_words(meta)), jnp.uint64)
        claims, slot, resolved = core.insert_loop(th, tw, h, w, live,
                                                  128, 1, tail_frac=8)
        th, tw = core.table_install(th, tw, h, w, claims)
        return th, tw, slot, resolved

    th, tw, slot, resolved = build(k)
    dt = _time(build, k)
    results["build_rows_per_sec"] = n / dt
    print(f"build       {dt * 1e3:8.1f} ms   {n / dt:14,.0f} rows/s "
          f"(cap 2^{cap.bit_length() - 1})")

    # -- probe: lookup-only --------------------------------------------------
    @jax.jit
    def probe(k, th, tw):
        cols = (PrimitiveColumn(k, jnp.ones(n, bool)),)
        h = _hashes(cols, n)
        w = core.key_words(cols, meta)
        return core.probe_loop(th, tw, h, w, live, 128)

    _slot2, found = probe(k, th, tw)
    assert bool(jnp.all(found)), "probe missed inserted keys"
    dt = _time(probe, k, th, tw)
    results["probe_rows_per_sec"] = n / dt
    print(f"probe       {dt * 1e3:8.1f} ms   {n / dt:14,.0f} rows/s")

    # -- agg_update: slot-indexed accumulator scatters -----------------------
    acc_meta = (("sum", "float64"), ("sum", "int32"))

    @jax.jit
    def update(slot, resolved, v, valid):
        accs, auxs = core.init_accs(acc_meta, cap)
        accs, _ = core.agg_update(
            accs, auxs, acc_meta, slot, resolved,
            (jnp.where(valid, v, 0.0), valid.astype(jnp.int32)),
            jnp.int64(0))
        return accs

    dt = _time(update, slot, resolved, v, valid)
    results["agg_update_rows_per_sec"] = n / dt
    print(f"agg_update  {dt * 1e3:8.1f} ms   {n / dt:14,.0f} rows/s")

    # -- fused single-shot vs the sort formulation ---------------------------
    @jax.jit
    def fused(k, v, valid):
        cols, accs, ng, gvalid = grouped_agg_once(
            (PrimitiveColumn(k, jnp.ones(n, bool)),),
            (jnp.where(valid, v, 0.0), valid.astype(jnp.int32)),
            ("sum", "sum"), live, cap)
        return accs[0], accs[1], ng

    @jax.jit
    def sort_formulation(k, v, valid):
        h = _hashes((PrimitiveColumn(k, jnp.ones(n, bool)),), n)
        perm = jnp.argsort(h, stable=True)
        h_s, k_s = h[perm], k[perm]
        v_s = jnp.where(valid, v, 0.0)[perm]
        c_s = valid.astype(jnp.int32)[perm]
        first = jnp.concatenate([jnp.ones(1, bool), h_s[1:] != h_s[:-1]])
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1
        sums = jax.ops.segment_sum(v_s, seg, num_segments=n)
        cnts = jax.ops.segment_sum(c_s, seg, num_segments=n)
        return sums, cnts, jnp.sum(first.astype(jnp.int32))

    dt_h = _time(fused, k, v, valid)
    dt_s = _time(sort_formulation, k, v, valid)
    results["hash_agg_rows_per_sec"] = n / dt_h
    results["sort_agg_rows_per_sec"] = n / dt_s
    results["hash_vs_sort"] = dt_s / dt_h
    print(f"hash agg    {dt_h * 1e3:8.1f} ms   {n / dt_h:14,.0f} rows/s")
    print(f"sort agg    {dt_s * 1e3:8.1f} ms   {n / dt_s:14,.0f} rows/s")
    print(f"hash vs sort: {dt_s / dt_h:.2f}x")

    dev = jax.devices()[0]
    print(f"platform: {dev.platform} ({dev.device_kind})")
    print(json.dumps({"metric": "microbench_hashtable",
                      "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "rows": n, "distinct_keys": n_keys,
                      "capacity": cap,
                      **{m: round(val, 1) for m, val in results.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
