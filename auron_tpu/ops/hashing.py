"""Spark-compatible murmur3_x86_32 and xxhash64 as vectorized JAX kernels.

Bit-exact with Spark (and with the reference's Rust implementations,
reference: native-engine/datafusion-ext-commons/src/hash/mur.rs,
hash/xxhash.rs, spark_hash.rs): every value contributes the murmur/xxhash of
its little-endian byte representation; multi-column hashes chain the running
hash through the seed; NULL leaves the running hash unchanged. murmur3 with
seed 42 drives hash-shuffle partitioning (reference:
datafusion-ext-plans/src/shuffle/mod.rs:163-188), so exact parity here means
a Spark driver and this engine agree on row placement.

All kernels are row-vectorized: scalar bit-twiddling from the reference
becomes lane-parallel int32/uint64 VPU ops; the per-string block loop is a
``lax.fori_loop`` over the (static, bucketed) width with per-row predication.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from auron_tpu.columnar.batch import Column, DeviceBatch, PrimitiveColumn, StringColumn

SPARK_SHUFFLE_SEED = 42

# numpy scalars, not jnp: a module-level jnp constant forces jax backend
# init at import time, which hangs any process whose ambient accelerator
# client is wedged (round-2 driver gate, MULTICHIP_r02.json rc=124) before
# the dryrun can re-exec itself with a safe platform.
_M3_C1 = np.uint32(0xCC9E2D51)
_M3_C2 = np.uint32(0x1B873593)
_M3_MIX = np.uint32(0xE6546B64)


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * _M3_C1
    k1 = _rotl32(k1, 15)
    return k1 * _M3_C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * jnp.uint32(5) + _M3_MIX


def _fmix(h1, length):
    h1 = h1 ^ length
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def murmur3_int32(values: jax.Array, seed: jax.Array) -> jax.Array:
    """murmur3 of a 4-byte LE value (int8/16/32 are widened to i32 first,
    matching Spark). values: int32[n]; seed: int32[n] or scalar → int32[n]."""
    h1 = _mix_h1(jnp.uint32(seed) if jnp.ndim(seed) == 0 else seed.astype(jnp.uint32),
                 _mix_k1(values.astype(jnp.int32).view(jnp.uint32)
                         if values.dtype != jnp.int32 else values.view(jnp.uint32)))
    return _fmix(h1, jnp.uint32(4)).view(jnp.int32)


def murmur3_u32_pair(low: jax.Array, high: jax.Array, seed) -> jax.Array:
    """murmur3 of an 8-byte LE value given as (low, high) uint32 words."""
    h1 = jnp.uint32(seed) if jnp.ndim(seed) == 0 else seed.astype(jnp.uint32)
    h1 = _mix_h1(h1, _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, jnp.uint32(8)).view(jnp.int32)


def murmur3_int64(values: jax.Array, seed: jax.Array) -> jax.Array:
    """murmur3 of an 8-byte LE value: low word then high word."""
    v = values.astype(jnp.int64)
    low = (v & 0xFFFFFFFF).astype(jnp.uint32)
    high = ((v >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
    return murmur3_u32_pair(low, high, seed)


def canonicalize_float(d: jax.Array) -> jax.Array:
    """Spark NormalizeNaNAndZero / Java doubleToLongBits canonicalization:
    -0.0 → 0.0 and every NaN payload → the canonical quiet NaN. Applied to
    float KEY values before hashing, order-word encoding, or equality so
    equal-under-Spark keys agree bit-for-bit; non-float arrays pass
    through."""
    if not jnp.issubdtype(d.dtype, jnp.floating):
        return d
    v = jnp.where(d == 0.0, jnp.zeros((), d.dtype), d)
    return jnp.where(d != d, jnp.full((), jnp.nan, d.dtype), v)


def nan_aware_eq(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise key equality with Spark semantics: NaN == NaN (floats
    only; plain == elsewhere). -0.0 == 0.0 already holds under IEEE ==."""
    same = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        same = same | ((a != a) & (b != b))
    return same


def adjacent_eq(col) -> jax.Array:
    """bool[cap-1]: row i structurally equals row i-1 under Spark key
    semantics — null == null, NaN == NaN, struct fieldwise. Shared by
    group-boundary and window-partition detection."""
    from auron_tpu.columnar.batch import (ListColumn, MapColumn,
                                          StringColumn, StringListColumn,
                                          StringMapColumn, StructColumn)
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(col, (MapColumn, ListColumn, StringListColumn,
                        StringMapColumn)):
        raise NotImplementedError(
            f"grouping / partitioning on {type(col).__name__} keys is not "
            "supported — Spark itself disallows map-typed keys; key on "
            "the individual elements instead")
    both_valid = col.validity[1:] & col.validity[:-1]
    both_null = ~col.validity[1:] & ~col.validity[:-1]
    if isinstance(col, StructColumn):
        same = jnp.ones_like(both_valid)
        for ch in col.children:
            same = same & adjacent_eq(
                ch.with_validity(ch.validity & col.validity))
    elif isinstance(col, StringColumn):
        same = jnp.all(col.chars[1:] == col.chars[:-1], axis=1) \
            & (col.lens[1:] == col.lens[:-1])
    elif isinstance(col, Decimal128Column):
        same = (col.hi[1:] == col.hi[:-1]) & (col.lo[1:] == col.lo[:-1])
    else:
        same = nan_aware_eq(col.data[1:], col.data[:-1])
    return (both_valid & same) | both_null


def pairwise_eq(pc, probe_idx, bc, build_idx) -> jax.Array:
    """Structural value equality of pc[probe_idx] vs bc[build_idx] under
    Spark key semantics (NaN == NaN; struct fieldwise with null-field ==
    null-field). Does NOT include the top-level validity conjunction —
    equi-join null keys never match, so the caller applies its own
    null rule."""
    from auron_tpu.columnar.batch import (ListColumn, MapColumn,
                                          StringColumn, StringListColumn,
                                          StringMapColumn, StructColumn)
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(pc, (MapColumn, ListColumn, StringListColumn,
                       StringMapColumn)):
        raise NotImplementedError(
            f"join keys of {type(pc).__name__} type are not supported")
    if isinstance(pc, StructColumn):
        same = jnp.ones(probe_idx.shape[0], bool)
        for cp, cb in zip(pc.children, bc.children):
            pv = cp.validity[probe_idx] & pc.validity[probe_idx]
            bv = cb.validity[build_idx] & bc.validity[build_idx]
            child_same = pairwise_eq(cp, probe_idx, cb, build_idx)
            same = same & ((pv & bv & child_same) | (~pv & ~bv))
        return same
    if isinstance(pc, StringColumn):
        return jnp.all(pc.chars[probe_idx] == bc.chars[build_idx], axis=1) \
            & (pc.lens[probe_idx] == bc.lens[build_idx])
    if isinstance(pc, Decimal128Column):
        return (pc.hi[probe_idx] == bc.hi[build_idx]) \
            & (pc.lo[probe_idx] == bc.lo[build_idx])
    return nan_aware_eq(pc.data[probe_idx], bc.data[build_idx])


def _f64_bits(d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Canonicalized bits of f64 as (low, high) uint32 words. Not on
    the TPU: it carries a double as a pair of float32 and its compiler
    refuses every bitcast of one (UNIMPLEMENTED in the X64 rewrite, PR
    34's chip run), so a plan that hashes a double KEY does not compile
    there yet; a sort key goes through ops/sort.f64_order_word."""
    v = canonicalize_float(d)
    pair = lax.bitcast_convert_type(v, jnp.uint32)  # [..., 2]
    # trailing dim order: index 0 = least-significant word on LE targets
    return pair[..., 0], pair[..., 1]


def _vary_like(x: jax.Array, ref: jax.Array) -> jax.Array:
    """``x`` varying over the mesh axes ``ref`` varies over. Inside a
    ``shard_map`` (the mesh stage program hashes its partition keys and
    its combine stage's group keys there) a loop's carry must enter with
    the axes it leaves with, and a constant seed mixed with a shard's
    strings enters with none. Outside one this is ``x``."""
    missing = tuple(jax.typeof(ref).vma - jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def murmur3_string(chars: jax.Array, lens: jax.Array, seed) -> jax.Array:
    """murmur3 over variable-length bytes held in a fixed-width matrix.

    chars: uint8[n, width] zero-padded; lens: int32[n]. Full 4-byte LE blocks
    mix in order; trailing bytes mix one-at-a-time sign-extended — exactly the
    reference's split_at(len - len%4) scheme (mur.rs:19-29).
    """
    n, width = chars.shape
    nwords = (width + 3) // 4
    padded = chars if width % 4 == 0 else jnp.pad(chars, ((0, 0), (0, 4 - width % 4)))
    u32 = padded.astype(jnp.uint32).reshape(n, nwords, 4)
    words = (u32[:, :, 0] | (u32[:, :, 1] << 8) | (u32[:, :, 2] << 16)
             | (u32[:, :, 3] << 24))  # LE words [n, nwords]
    nfull = (lens // 4).astype(jnp.int32)  # number of full words per row

    seed_arr = _vary_like(
        jnp.broadcast_to(jnp.uint32(seed) if jnp.ndim(seed) == 0
                         else seed.astype(jnp.uint32), (n,)), lens)

    def word_step(i, h1):
        active = i < nfull
        mixed = _mix_h1(h1, _mix_k1(words[:, i]))
        return jnp.where(active, mixed, h1)

    h1 = lax.fori_loop(0, nwords, word_step, seed_arr)

    # Trailing bytes: positions nfull*4 .. lens-1, each sign-extended.
    def tail_step(j, h1):
        pos = nfull * 4 + j
        active = pos < lens
        byte = jnp.take_along_axis(
            chars, jnp.clip(pos, 0, width - 1)[:, None], axis=1)[:, 0]
        half_word = byte.astype(jnp.int8).astype(jnp.int32).view(jnp.uint32)
        mixed = _mix_h1(h1, _mix_k1(half_word))
        return jnp.where(active, mixed, h1)

    h1 = lax.fori_loop(0, 3, tail_step, h1)
    return _fmix(h1, lens.view(jnp.uint32) if lens.dtype == jnp.int32
                 else lens.astype(jnp.uint32)).view(jnp.int32)


# ---------------------------------------------------------------------------
# xxhash64 (Spark XxHash64, seed-chained like murmur)
# ---------------------------------------------------------------------------

# numpy scalars for the same import-time-laziness reason as the murmur
# constants above
_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)
_P4 = np.uint64(0x85EBCA77C2B2AE63)
_P5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    return (x << r) | (x >> (64 - r))


def _xx_avalanche(h):
    h = h ^ (h >> 33)
    h = h * _P2
    h = h ^ (h >> 29)
    h = h * _P3
    return h ^ (h >> 32)


def _xx_round(acc, inp):
    acc = acc + inp * _P2
    acc = _rotl64(acc, 31)
    return acc * _P1


def xxhash64_int64(values: jax.Array, seed) -> jax.Array:
    """xxhash64 of one 8-byte LE value (<32 bytes path of xxhash.rs:60-88)."""
    v = values.astype(jnp.int64).view(jnp.uint64)
    h = (jnp.uint64(seed) if jnp.ndim(seed) == 0 else seed.astype(jnp.uint64)) + _P5
    h = h + jnp.uint64(8)
    h = h ^ _xx_round(jnp.uint64(0), v)
    h = _rotl64(h, 27) * _P1 + _P4
    return _xx_avalanche(h).view(jnp.int64)


def xxhash64_int32(values: jax.Array, seed) -> jax.Array:
    """xxhash64 of one 4-byte LE value."""
    v = values.astype(jnp.int32).view(jnp.uint32).astype(jnp.uint64)
    h = (jnp.uint64(seed) if jnp.ndim(seed) == 0 else seed.astype(jnp.uint64)) + _P5
    h = h + jnp.uint64(4)
    h = h ^ (v * _P1)
    h = _rotl64(h, 23) * _P2 + _P3
    return _xx_avalanche(h).view(jnp.int64)


def xxhash64_string(chars: jax.Array, lens: jax.Array, seed) -> jax.Array:
    """xxhash64 over variable-length bytes in a fixed-width matrix.

    Handles all three phases of xxhash.rs:31-88 (32-byte stripes, 8-byte
    blocks, 4-byte block, tail bytes) with per-row predication.
    """
    n, width = chars.shape
    n64 = (width + 7) // 8
    padded = chars if width % 8 == 0 else jnp.pad(chars, ((0, 0), (0, 8 - width % 8)))
    b = padded.astype(jnp.uint64).reshape(n, n64, 8)
    shifts = (jnp.arange(8, dtype=jnp.uint64) * 8)[None, None, :]
    words64 = jnp.sum(b << shifts, axis=2)  # LE u64 words [n, n64]

    u32_padded = chars if width % 4 == 0 else jnp.pad(chars, ((0, 0), (0, 4 - width % 4)))
    w32 = u32_padded.astype(jnp.uint32).reshape(n, (width + 3) // 4, 4)
    words32 = (w32[:, :, 0] | (w32[:, :, 1] << 8) | (w32[:, :, 2] << 16)
               | (w32[:, :, 3] << 24)).astype(jnp.uint64)

    lens_u = lens.astype(jnp.uint64)
    seed_arr = _vary_like(
        jnp.broadcast_to(jnp.uint64(seed) if jnp.ndim(seed) == 0
                         else seed.astype(jnp.uint64), (n,)), lens)

    nstripes = (lens // 32).astype(jnp.int32)  # 32-byte stripes
    has_stripes = lens >= 32

    acc1 = seed_arr + _P1 + _P2
    acc2 = seed_arr + _P2
    acc3 = seed_arr
    acc4 = seed_arr - _P1
    max_stripes = width // 32 + (1 if width % 32 else 0)

    def stripe_step(s, accs):
        a1, a2, a3, a4 = accs
        active = s < nstripes
        base = s * 4

        def w(k):
            idx = jnp.clip(base + k, 0, n64 - 1)
            return words64[jnp.arange(n), idx]

        na1 = _xx_round(a1, w(0))
        na2 = _xx_round(a2, w(1))
        na3 = _xx_round(a3, w(2))
        na4 = _xx_round(a4, w(3))
        return (jnp.where(active, na1, a1), jnp.where(active, na2, a2),
                jnp.where(active, na3, a3), jnp.where(active, na4, a4))

    if max_stripes > 0:
        acc1, acc2, acc3, acc4 = lax.fori_loop(
            0, max_stripes, stripe_step, (acc1, acc2, acc3, acc4))

    merged = (_rotl64(acc1, 1) + _rotl64(acc2, 7) + _rotl64(acc3, 12)
              + _rotl64(acc4, 18))
    for acc in (acc1, acc2, acc3, acc4):
        merged = (merged ^ _xx_round(jnp.uint64(0), acc)) * _P1 + _P4
    h = jnp.where(has_stripes, merged, seed_arr + _P5)
    h = h + lens_u

    # 8-byte blocks after the stripes.
    consumed8 = nstripes * 4  # in u64 words
    n8 = ((lens % 32) // 8).astype(jnp.int32)

    def blk8_step(j, h):
        active = j < n8
        idx = jnp.clip(consumed8 + j, 0, n64 - 1)
        w = words64[jnp.arange(n), idx]
        nh = (_rotl64(h ^ _xx_round(jnp.uint64(0), w), 27)) * _P1 + _P4
        return jnp.where(active, nh, h)

    h = lax.fori_loop(0, 4, blk8_step, h)

    # One 4-byte block.
    consumed4 = (lens // 8 * 2).astype(jnp.int32)  # in u32 words
    has4 = (lens % 8) >= 4
    idx4 = jnp.clip(consumed4, 0, words32.shape[1] - 1)
    w4 = words32[jnp.arange(n), idx4]
    h4 = (_rotl64(h ^ (w4 * _P1), 23)) * _P2 + _P3
    h = jnp.where(has4, h4, h)

    # Tail bytes.
    tail_start = (lens // 4 * 4).astype(jnp.int32)

    def tail_step(j, h):
        pos = tail_start + j
        active = pos < lens
        byte = jnp.take_along_axis(
            chars, jnp.clip(pos, 0, width - 1)[:, None], axis=1)[:, 0].astype(jnp.uint64)
        nh = (_rotl64(h ^ (byte * _P5), 11)) * _P1
        return jnp.where(active, nh, h)

    h = lax.fori_loop(0, 3, tail_step, h)
    return _xx_avalanche(h).view(jnp.int64)


# ---------------------------------------------------------------------------
# Column / batch level hashing (seed chaining + null skipping)
# ---------------------------------------------------------------------------

def _reject_nested(col) -> None:
    from auron_tpu.columnar.batch import (ListColumn, MapColumn,
                                          StringListColumn, StringMapColumn)
    if isinstance(col, (MapColumn, ListColumn, StringListColumn,
                        StringMapColumn)):
        raise NotImplementedError(
            f"hash partitioning / hash join / hash agg on "
            f"{type(col).__name__} keys is not supported — Spark itself "
            "disallows map-typed keys; for array keys, hash the "
            "individual elements instead")


def _hash_column_murmur(col: Column, hashes: jax.Array) -> jax.Array:
    """One column's contribution to the running murmur3 hash (int32[n])."""
    _reject_nested(col)
    from auron_tpu.columnar.batch import StructColumn
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(col, StructColumn):
        # Spark create_hashes recurses into struct fields, chaining the
        # running hash through each (spark_hash.rs); a NULL struct row
        # leaves the running hash untouched, like any null column
        new = hashes
        for ch in col.children:
            new = _hash_column_murmur(
                ch.with_validity(ch.validity & col.validity), new)
        return jnp.where(col.validity, new, hashes)
    if isinstance(col, Decimal128Column):
        # limb-pair hashing: chain the low then high limb as two int64
        # words. DELIBERATE DEVIATION from Spark, which hashes wide
        # decimals as minimal big-endian two's-complement byte arrays
        # (variable length — hostile to static shapes); engine-internal
        # consistency is what hash partitioning / hash agg need, and both
        # sides of any exchange run this same kernel.
        new = murmur3_int64(col.lo, hashes.view(jnp.uint32))
        new = murmur3_int64(col.hi, new.view(jnp.uint32))
        return jnp.where(col.validity, new, hashes)
    if isinstance(col, StringColumn):
        new = murmur3_string(col.chars, col.lens, hashes.view(jnp.uint32))
    else:
        d = col.data
        if d.dtype == jnp.bool_:
            new = murmur3_int32(d.astype(jnp.int32), hashes.view(jnp.uint32))
        elif d.dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.int16), jnp.dtype(jnp.int32)):
            new = murmur3_int32(d.astype(jnp.int32), hashes.view(jnp.uint32))
        elif d.dtype == jnp.dtype(jnp.int64):
            new = murmur3_int64(d, hashes.view(jnp.uint32))
        elif d.dtype == jnp.dtype(jnp.float32):
            # Java floatToIntBits: -0.0 → 0.0, NaN payloads canonicalized.
            new = murmur3_int32(canonicalize_float(d).view(jnp.int32),
                                hashes.view(jnp.uint32))
        elif d.dtype == jnp.dtype(jnp.float64):
            lo, hi = _f64_bits(d)
            new = murmur3_u32_pair(lo, hi, hashes.view(jnp.uint32))
        else:
            raise NotImplementedError(f"murmur3 for {d.dtype}")
    return jnp.where(col.validity, new, hashes)


def _hash_column_xxhash(col: Column, hashes: jax.Array) -> jax.Array:
    _reject_nested(col)
    from auron_tpu.columnar.batch import StructColumn
    from auron_tpu.columnar.decimal128 import Decimal128Column
    if isinstance(col, StructColumn):
        new = hashes
        for ch in col.children:
            new = _hash_column_xxhash(
                ch.with_validity(ch.validity & col.validity), new)
        return jnp.where(col.validity, new, hashes)
    if isinstance(col, Decimal128Column):
        # limb-pair hashing; see _hash_column_murmur for the Spark deviation
        new = xxhash64_int64(col.lo, hashes.view(jnp.uint64))
        new = xxhash64_int64(col.hi, new.view(jnp.uint64))
        return jnp.where(col.validity, new, hashes)
    if isinstance(col, StringColumn):
        new = xxhash64_string(col.chars, col.lens, hashes.view(jnp.uint64))
    else:
        d = col.data
        if d.dtype == jnp.bool_:
            new = xxhash64_int32(d.astype(jnp.int32), hashes.view(jnp.uint64))
        elif d.dtype in (jnp.dtype(jnp.int8), jnp.dtype(jnp.int16), jnp.dtype(jnp.int32)):
            new = xxhash64_int32(d.astype(jnp.int32), hashes.view(jnp.uint64))
        elif d.dtype == jnp.dtype(jnp.int64):
            new = xxhash64_int64(d, hashes.view(jnp.uint64))
        elif d.dtype == jnp.dtype(jnp.float32):
            new = xxhash64_int32(canonicalize_float(d).view(jnp.int32),
                                 hashes.view(jnp.uint64))
        elif d.dtype == jnp.dtype(jnp.float64):
            lo, hi = _f64_bits(d)
            u64 = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << 32)
            new = xxhash64_int64(u64.view(jnp.int64), hashes.view(jnp.uint64))
        else:
            raise NotImplementedError(f"xxhash64 for {d.dtype}")
    return jnp.where(col.validity, new, hashes)


def murmur3_columns(cols: list[Column], capacity: int,
                    seed: int = SPARK_SHUFFLE_SEED) -> jax.Array:
    """Spark create_hashes: running int32 hash chained across columns."""
    hashes = jnp.full((capacity,), seed, jnp.int32)
    for col in cols:
        hashes = _hash_column_murmur(col, hashes)
    return hashes


def xxhash64_columns(cols: list[Column], capacity: int, seed: int = 42) -> jax.Array:
    hashes = jnp.full((capacity,), seed, jnp.int64)
    for col in cols:
        hashes = _hash_column_xxhash(col, hashes)
    return hashes


def murmur3_batch(batch: DeviceBatch, key_indices: list[int],
                  seed: int = SPARK_SHUFFLE_SEED) -> jax.Array:
    return murmur3_columns([batch.columns[i] for i in key_indices],
                           batch.capacity, seed)
