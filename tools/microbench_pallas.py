"""Pallas grouped-aggregate kernel microbench.

The kernel itself now lives in the engine — auron_tpu/kernels/
grouped_agg.py ``pallas_sum_count`` (promoted from this script's round-5
prototype), selected per-plan by kernels/dispatch.py. This script keeps
the standalone measurement harness: block-size sweep, chained-dependency
timing, and an f64 numpy accuracy cross-check.

The XLA formulations of the dense 2^16-domain group-aggregate are bound
by materializing [n, 512..1024] one-hot operands in HBM (~4 GB per 1M
rows). The VMEM kernel builds the one-hot tiles in VMEM per row block
and accumulates the [hi, lo] grids in VMEM across the whole grid — HBM
traffic collapses to the 12 B/row inputs.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from auron_tpu.kernels.grouped_agg import (MAX_KEY_DOMAIN,  # noqa: E402
                                           pallas_sum_count)

_DOMAIN = MAX_KEY_DOMAIN


def main():
    print("devices:", jax.devices())
    interpret = jax.default_backend() != "tpu"
    if interpret:
        print("non-TPU backend: running the kernel INTERPRETED "
              "(correctness sweep only, timings are meaningless)")
    rng = np.random.default_rng(0)
    n = 1 << (14 if interpret else 20)
    iters = 2 if interpret else 20
    k0 = jnp.asarray(rng.integers(0, _DOMAIN, size=n).astype(np.int32))
    c0 = jnp.asarray((rng.random(n) > 0.05).astype(np.float32))
    # v arrives pre-masked (nulls zeroed), as in the engine kernel
    v0 = jnp.asarray(rng.normal(size=n).astype(np.float32)) * c0

    for blk in (1024, 2048, 4096, 8192):
        def f(k, v, c, _blk=blk):
            return pallas_sum_count(k, v, c, _DOMAIN, blk=_blk,
                                    interpret=interpret)
        s, cn = f(k0, v0, c0)
        s.block_until_ready()
        # chained timing: output scalar feeds next input, defeating any
        # async/dedup effects; final host readback is the sync point
        def step(v):
            s, cn = f(k0, v, c0)
            return v + s[0] * 1e-30
        st = jax.jit(step)
        v = st(v0)
        _ = float(jnp.sum(v))
        t0 = time.perf_counter()
        for _ in range(iters):
            v = st(v)
        _ = float(jnp.sum(v))
        dt = (time.perf_counter() - t0) / iters
        # accuracy
        s, cn = f(k0, v0, c0)
        kk = np.asarray(k0)
        vv = np.asarray(v0, np.float64) * np.asarray(c0, np.float64)
        rs = np.zeros(_DOMAIN)
        np.add.at(rs, kk, vv)
        rc = np.zeros(_DOMAIN)
        np.add.at(rc, kk, np.asarray(c0, np.float64))
        serr = (np.max(np.abs(np.asarray(s, np.float64) - rs))
                / np.max(np.abs(rs)))
        cerr = np.max(np.abs(np.asarray(cn, np.float64) - rc))
        print(f"pallas blk={blk:5d} {dt*1e3:8.3f} ms "
              f"{n/dt/1e6:9.1f} M rows/s rel={serr:.2e} cnt={cerr:.1f}")


if __name__ == "__main__":
    main()
