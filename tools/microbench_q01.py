"""Microbench: dense group-aggregate kernel variants on the live chip.

The flagship kernel's einsum currently runs at Precision.HIGHEST — on TPU
that is ~6 bf16 passes per [n,512]x[n,256] contraction. Variants here
restructure the work so exact parts (one-hot counts) pay 1 pass and the
value operand pays 2-3 additive bf16-split passes, and measure accuracy
against the f64 host reference.

Run: python tools/microbench_q01.py  (uses the ambient accelerator)
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

_GRID = 256
_DOMAIN = _GRID * _GRID


def make_inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, _DOMAIN, size=n).astype(np.int32)
    v = rng.normal(size=n).astype(np.float32)
    c = (rng.random(n) > 0.05).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(c)


def ref_sums_counts(k, v, c):
    k = np.asarray(k)
    v = np.asarray(v, np.float64)
    c = np.asarray(c, np.float64)
    sums = np.zeros(_DOMAIN)
    cnts = np.zeros(_DOMAIN)
    np.add.at(sums, k, v * c)
    np.add.at(cnts, k, c)
    return sums, cnts


def v_current(kb, vb, cb):
    """Today's kernel: stacked lhs, HIGHEST f32 einsum."""
    def block(inp):
        kk, vals, cnts = inp
        hi = jax.nn.one_hot(kk >> 8, _GRID, dtype=jnp.float32)
        lo = jax.nn.one_hot(kk & 255, _GRID, dtype=jnp.float32)
        lhs = jnp.concatenate([hi * (vals * cnts)[:, None],
                               hi * cnts[:, None]], axis=1)
        out = jnp.einsum("nh,nl->hl", lhs, lo,
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        return out[:_GRID], out[_GRID:]
    s, c = lax.map(block, (kb, vb, cb))
    return jnp.sum(s, axis=0), jnp.sum(c, axis=0)


def _mask_hi(x):
    """Top-16-bit truncation of f32 via opaque bit ops: exactly
    bf16-representable, and XLA's bf16-propagation pass cannot fold the
    residual x - _mask_hi(x) to zero (it does fold f32->bf16->f32 convert
    pairs, silently collapsing a convert-based split to 1 term)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def make_masked_variant(terms):
    """Split the value operand into `terms` additive bf16-exact f32 arrays;
    one stacked DEFAULT-precision matmul (1 bf16 pass per term + 1 for
    counts) replaces HIGHEST's 6 passes over the double-height lhs."""
    def v_split(kb, vb, cb):
        def block(inp):
            kk, vals, cnts = inp
            hi_ids = kk >> 8
            lo = jax.nn.one_hot(kk & 255, _GRID, dtype=jnp.float32)
            hv = jax.nn.one_hot(hi_ids, _GRID, dtype=jnp.float32) \
                * (vals * cnts)[:, None]
            parts, rem = [], hv
            for _ in range(terms - 1):
                p = _mask_hi(rem)
                parts.append(p)
                rem = rem - p
            parts.append(rem)
            hi_c = jax.nn.one_hot(hi_ids, _GRID, dtype=jnp.float32) \
                * cnts[:, None]
            lhs = jnp.concatenate(parts + [hi_c], axis=1)
            out = jnp.einsum("nh,nl->hl", lhs, lo,
                             precision=lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32)
            sums = out[:_GRID]
            for t in range(1, terms):
                sums = sums + out[t * _GRID:(t + 1) * _GRID]
            return sums, out[terms * _GRID:]
        s, c = lax.map(block, (kb, vb, cb))
        return jnp.sum(s, axis=0), jnp.sum(c, axis=0)
    return v_split


def make_f32_lhs_bf16_rhs(prec):
    """f32 lhs, bf16-exact rhs, per-operand precision tuple."""
    def v(kb, vb, cb):
        def block(inp):
            kk, vals, cnts = inp
            hi = jax.nn.one_hot(kk >> 8, _GRID, dtype=jnp.float32)
            lo = jax.nn.one_hot(kk & 255, _GRID, dtype=jnp.float32)
            lhs = jnp.concatenate([hi * (vals * cnts)[:, None],
                                   hi * cnts[:, None]], axis=1)
            out = jnp.einsum("nh,nl->hl", lhs, lo, precision=prec,
                             preferred_element_type=jnp.float32)
            return out[:_GRID], out[_GRID:]
        s, c = lax.map(block, (kb, vb, cb))
        return jnp.sum(s, axis=0), jnp.sum(c, axis=0)
    return v


def bench(name, fn, k, v, c, n, block, iters=10):
    nb = n // block
    kb = k.reshape(nb, block)
    cb = c.reshape(nb, block)
    # distinct value inputs per iteration, so no two timed launches are
    # the same (executable, inputs) pair
    vbs = [(v + jnp.float32(i)).reshape(nb, block) for i in range(iters)]
    jax.block_until_ready(vbs)
    jf = jax.jit(fn)
    out = jf(kb, v.reshape(nb, block), cb)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    outs = [jf(kb, vb_i, cb) for vb_i in vbs]
    jax.block_until_ready(outs)
    dt = (time.perf_counter() - t0) / iters
    out = jf(kb, v.reshape(nb, block), cb)
    sums, cnts = out
    sums = np.asarray(sums, np.float64).reshape(-1)
    cnts = np.asarray(cnts, np.float64).reshape(-1)
    rs, rc = ref_sums_counts(k, v, c)
    s_err = float(np.max(np.abs(np.asarray(sums, np.float64) - rs))
                  / max(1.0, np.max(np.abs(rs))))
    c_err = float(np.max(np.abs(np.asarray(cnts, np.float64) - rc)))
    print(f"{name:28s} block={block:6d} {n / dt / 1e6:9.1f} M rows/s "
          f"rel_sum_err={s_err:.2e} abs_cnt_err={c_err:.1f}")
    return n / dt


if __name__ == "__main__":
    print("devices:", jax.devices())
    n = 1 << 20
    k, v, c = make_inputs(n)
    for block in (1 << 14, 1 << 16):
        bench("current_highest", v_current, k, v, c, n, block)
    for block in (1 << 14, 1 << 15, 1 << 16, 1 << 17):
        bench("mask2", make_masked_variant(2), k, v, c, n, block)
        bench("mask3", make_masked_variant(3), k, v, c, n, block)
