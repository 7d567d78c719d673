"""128-bit decimal columns as two 64-bit limbs — precision 19..38.

The reference stores Spark decimals as Arrow Decimal128 and does the
arithmetic in Rust i128 (reference: datafusion-ext-commons/src/arrow/
cast.rs decimal paths, datafusion-ext-functions/src/spark_check_overflow
.rs, spark_make_decimal.rs). TPUs have no 128-bit (or even native 64-bit)
integers, so here a decimal(p>18) column is a pair of int64 arrays —
``hi`` (signed high limb) and ``lo`` (low limb, the bit pattern of an
unsigned 64-bit value) — and every operation is branch-free limb
arithmetic that XLA lowers to 32-bit pairs on TPU:

  - add/sub: unsigned-compare carry propagation;
  - mul: 32-bit half-limb schoolbook multiply keeping the low 128 bits;
  - scale by 10^k: constant multiply / chunked long division in base 2^32
    with divisor chunks <= 10^9 so partial remainders fit int63;
  - compare: signed hi then unsigned lo.

Values are two's-complement 128-bit integers; precision 38 bounds
|value| < 10^38 < 2^127, so no operation here can overflow the
representation itself — overflow beyond the DECLARED precision is
detected against 10^p bounds and nulled (Spark non-ANSI semantics).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

import jax
import jax.numpy as jnp

I64 = jnp.int64
#: 1 << 63 as an int64 bit pattern. Plain python int — a module-level
#: jnp array would force jax backend init at import time, which breaks
#: child processes that must control platform selection before first use
#: (the round-2 dryrun lesson; see ops/hashing.py).
_SIGN = -0x8000000000000000
MAX_PRECISION = 38


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Decimal128Column:
    """Two-limb decimal column: value = hi * 2^64 + u64(lo)."""

    hi: jax.Array        # int64[capacity], signed high limb
    lo: jax.Array        # int64[capacity], bit pattern of unsigned low limb
    validity: jax.Array  # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]

    def with_validity(self, validity: jax.Array) -> "Decimal128Column":
        return replace(self, validity=validity)


# ---------------------------------------------------------------------------
# unsigned-64 helpers on int64 bit patterns
# ---------------------------------------------------------------------------

def _ult(a, b):
    """Unsigned a < b over int64 bit patterns (flip the sign bit)."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def _u32_parts(x):
    lo = x & jnp.int64(0xFFFFFFFF)
    hi = (x >> 32) & jnp.int64(0xFFFFFFFF)
    return hi, lo


def _lsr32(x):
    """Logical (unsigned) right shift by 32 of an int64 bit pattern —
    32x32 partial products can exceed int63, so arithmetic shifts would
    sign-extend garbage into the carries."""
    return (x >> 32) & jnp.int64(0xFFFFFFFF)


def _mul_u64(a, b):
    """Full 64x64 -> 128 unsigned multiply of int64 bit patterns; returns
    (hi64, lo64) bit patterns."""
    ah, al = _u32_parts(a)
    bh, bl = _u32_parts(b)
    ll = al * bl          # may exceed int63: treat as u64 bit pattern
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    mid = _lsr32(ll) + (lh & jnp.int64(0xFFFFFFFF)) \
        + (hl & jnp.int64(0xFFFFFFFF))
    lo = (ll & jnp.int64(0xFFFFFFFF)) | (mid << 32)
    hi = hh + _lsr32(lh) + _lsr32(hl) + _lsr32(mid)
    return hi, lo


# ---------------------------------------------------------------------------
# core 128-bit ops (elementwise over (hi, lo) pairs)
# ---------------------------------------------------------------------------

def add128(ah, al, bh, bl):
    lo = al + bl
    carry = _ult(lo, al).astype(I64)
    return ah + bh + carry, lo


def neg128(h, l):
    nl = (~l) + 1
    borrow = (nl == 0).astype(I64)
    return (~h) + borrow, nl


def sub128(ah, al, bh, bl):
    nh, nl = neg128(bh, bl)
    return add128(ah, al, nh, nl)


def mul128(ah, al, bh, bl):
    """Low 128 bits of a*b (two's complement — low bits are sign-correct)."""
    hi, lo = _mul_u64(al, bl)
    hi = hi + al * bh + ah * bl
    return hi, lo


def cmp128(ah, al, bh, bl):
    """(lt, eq) for signed 128-bit comparison."""
    eq = (ah == bh) & (al == bl)
    lt = (ah < bh) | ((ah == bh) & _ult(al, bl))
    return lt, eq


def is_negative(h, _l):
    return h < 0


def abs128(h, l):
    neg = is_negative(h, l)
    nh, nl = neg128(h, l)
    return jnp.where(neg, nh, h), jnp.where(neg, nl, l)


def from_int64(x):
    """Sign-extend an int64 (e.g. a scaled decimal(<=18)) into limbs."""
    return jnp.where(x < 0, jnp.int64(-1), jnp.int64(0)), x


def to_int64(h, l):
    """(value as int64, fits flag): exact when the 128-bit value is within
    int64 range (hi is pure sign extension of lo)."""
    fits = h == jnp.where(l < 0, jnp.int64(-1), jnp.int64(0))
    return l, fits


# ---------------------------------------------------------------------------
# powers of ten
# ---------------------------------------------------------------------------

def _pow10_limbs(k: int) -> tuple[int, int]:
    v = 10 ** k
    lo = v & ((1 << 64) - 1)
    hi = v >> 64
    if lo >= 1 << 63:
        lo -= 1 << 64
    return hi, lo


def mul_pow10(h, l, k: int):
    """value * 10^k (k in [0, 38])."""
    if k == 0:
        return h, l
    ph, pl = _pow10_limbs(k)
    rh, rl = mul128(h, l, jnp.int64(ph), jnp.int64(pl))
    return rh, rl


def _divmod_small(h, l, d: int):
    """Unsigned (h,l) // d and remainder for 1 <= d <= 10^9, via base-2^32
    long division (every partial value < d * 2^32 < 2^62 fits int64)."""
    assert 1 <= d <= 10 ** 9
    limbs = [(h >> 32) & jnp.int64(0xFFFFFFFF), h & jnp.int64(0xFFFFFFFF),
             (l >> 32) & jnp.int64(0xFFFFFFFF), l & jnp.int64(0xFFFFFFFF)]
    q = []
    r = jnp.zeros_like(h)
    for limb in limbs:
        cur = (r << 32) | limb
        q.append(cur // d)
        r = cur % d
    qh = (q[0] << 32) | q[1]
    ql = (q[2] << 32) | q[3]
    return qh, ql, r


def _divmod_u64_runtime(ah, al, d):
    """Unsigned (ah,al) // d and remainder for a RUNTIME int64 divisor
    1 <= d < 2^31 (base-2^32 long division keeps every partial value
    r*2^32 + limb < d*2^32 < 2^63). The pow10 dividers above only take
    compile-time divisor constants."""
    limbs = [(ah >> 32) & jnp.int64(0xFFFFFFFF), ah & jnp.int64(0xFFFFFFFF),
             (al >> 32) & jnp.int64(0xFFFFFFFF), al & jnp.int64(0xFFFFFFFF)]
    q = []
    r = jnp.zeros_like(ah)
    for limb in limbs:
        cur = (r << 32) | limb
        q.append(cur // d)
        r = cur % d
    return (q[0] << 32) | q[1], (q[2] << 32) | q[3], r


def avg_pow10_div_half_up(h, l, count, k: int):
    """(value * 10^k) / count with HALF_UP, for avg finalizers: the sum
    accumulates UNSHIFTED (so only genuinely-overflowing totals wrap
    2^127) and the result-scale shift composes with the division here as
    q*10^k + round((r*10^k)/count), which never widens past the result.
    Returns (hi, lo, fits) — fits=False when |q| >= 10^(38-k), i.e. the
    scaled average cannot fit decimal(38) and Spark nulls it."""
    assert 0 <= k <= 9   # frac term: 2*r*10^k < 2^32 * 10^9 < 2^63
    neg = is_negative(h, l)
    ah, al = abs128(h, l)
    qh, ql, r = _divmod_u64_runtime(ah, al, count)
    fits = fits_precision(qh, ql, 38 - k)
    # the long-division invariant needs count < 2^31; a group larger than
    # that nulls rather than silently mis-dividing (Spark would compute it
    # — an accepted engine bound, >2.1e9 rows in ONE group)
    fits = fits & (count < (1 << 31))
    qh, ql = mul_pow10(qh, ql, k)
    # r < count < 2^31 and 10^k <= 10^38's low digits… keep k small enough
    # for int64: the avg shift is at most 4 digits (s+4 result scale), so
    # 2*r*10^k < 2^32 * 2e4 < 2^63
    frac = (2 * r * (10 ** k) + count) // (2 * count)
    qh, ql = add128(qh, ql, jnp.zeros_like(h), frac)
    nh, nl = neg128(qh, ql)
    return jnp.where(neg, nh, qh), jnp.where(neg, nl, ql), fits


def div_pow10_half_up(h, l, k: int):
    """value / 10^k with HALF_UP rounding (Spark decimal rescale-down)."""
    if k == 0:
        return h, l
    if k >= 39:
        # |value| < 10^38 < 0.5 * 10^k: always rounds to zero
        return jnp.zeros_like(h), jnp.zeros_like(l)
    neg = is_negative(h, l)
    ah, al = abs128(h, l)
    # q, r = divmod(value, 10^k) in <=9-digit chunks. Dividing by d1 then
    # d2: value = q2*d1*d2 + r2*d1 + r1, so the full remainder rebuilds as
    # r = r1 + r2*d1 + r3*d1*d2 + ... (rem_exp tracks the 10^j factor).
    rem_h = jnp.zeros_like(h)
    rem_l = jnp.zeros_like(l)
    rem_exp = 0
    kk = k
    while kk > 0:
        step = min(kk, 9)
        d = 10 ** step
        ah, al, r = _divmod_small(ah, al, d)
        sh, sl = _pow10_limbs(rem_exp)
        rh_, rl_ = mul128(jnp.zeros_like(r), r, jnp.int64(sh),
                          jnp.int64(sl))
        rem_h, rem_l = add128(rem_h, rem_l, rh_, rl_)
        rem_exp += step
        kk -= step
    # HALF_UP: round away from zero when remainder >= 5 * 10^(k-1).
    # (Comparing 2*remainder against 10^k would signed-wrap for k=38
    # remainders >= 2^126.)
    half = 5 * 10 ** (k - 1)
    mask = (1 << 64) - 1
    t_lo = half & mask
    t_hi = (half >> 64) & mask
    t_lo = t_lo - (1 << 64) if t_lo >= 1 << 63 else t_lo
    lt, _eq = cmp128(rem_h, rem_l, jnp.int64(t_hi), jnp.int64(t_lo))
    bump = (~lt).astype(I64)
    ah, al = add128(ah, al, jnp.zeros_like(h), bump)
    nh, nl = neg128(ah, al)
    return jnp.where(neg, nh, ah), jnp.where(neg, nl, al)


def div_pow10_trunc(h, l, k: int):
    """value / 10^k truncated toward zero (decimal → integer casts)."""
    if k == 0:
        return h, l
    neg = is_negative(h, l)
    ah, al = abs128(h, l)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        ah, al, _r = _divmod_small(ah, al, 10 ** step)
        kk -= step
    nh, nl = neg128(ah, al)
    return jnp.where(neg, nh, ah), jnp.where(neg, nl, al)


def fits_precision(h, l, precision: int):
    """|value| < 10^precision (the declared-precision overflow check,
    reference: spark_check_overflow.rs)."""
    ah, al = abs128(h, l)
    bh, bl = _pow10_limbs(min(precision, MAX_PRECISION))
    lt, _ = cmp128(ah, al, jnp.int64(bh), jnp.int64(bl))
    return lt


# ---------------------------------------------------------------------------
# host conversion
# ---------------------------------------------------------------------------

def limbs_from_ints(values: list, cap: int) -> tuple[np.ndarray, np.ndarray,
                                                     np.ndarray]:
    """Python ints (scaled unscaled values; None = null) → limb arrays."""
    hi = np.zeros(cap, np.int64)
    lo = np.zeros(cap, np.int64)
    valid = np.zeros(cap, bool)
    mask = (1 << 64) - 1
    for i, v in enumerate(values):
        if v is None:
            continue
        u = v & ((1 << 128) - 1)           # two's complement 128
        l = u & mask
        h = (u >> 64) & mask
        lo[i] = l - (1 << 64) if l >= 1 << 63 else l
        hi[i] = h - (1 << 64) if h >= 1 << 63 else h
        valid[i] = True
    return hi, lo, valid


def ints_from_limbs(hi: np.ndarray, lo: np.ndarray,
                    valid: np.ndarray) -> list:
    """Limb arrays → python ints (None for nulls)."""
    out = []
    for h, l, ok in zip(hi.tolist(), lo.tolist(), valid.tolist()):
        if not ok:
            out.append(None)
            continue
        u = ((h & ((1 << 64) - 1)) << 64) | (l & ((1 << 64) - 1))
        if u >= 1 << 127:
            u -= 1 << 128
        out.append(u)
    return out


def to_float64(h, l):
    """Approximate float64 value of the 128-bit integer (for float-context
    arithmetic and casts)."""
    neg = is_negative(h, l)
    ah, al = abs128(h, l)
    lo_u = jnp.where(al < 0, al.astype(jnp.float64) + 2.0 ** 64,
                     al.astype(jnp.float64))
    mag = ah.astype(jnp.float64) * (2.0 ** 64) + lo_u
    return jnp.where(neg, -mag, mag)


# ---------------------------------------------------------------------------
# decimal / decimal (Spark's Divide): the exact quotient, HALF_UP
# ---------------------------------------------------------------------------

def _uge128(ah, al, bh, bl):
    """Unsigned a >= b over (hi, lo) bit patterns."""
    return _ult(bh, ah) | ((ah == bh) & ~_ult(al, bl))


def _shl_limbs(limbs, s: int):
    """Limbs (most significant first, int64 bit patterns) shifted left by
    the STATIC ``s`` bits within their own width."""
    n = len(limbs)
    words, bits = divmod(s, 64)
    zero = jnp.zeros_like(limbs[0])
    out = []
    for i in range(n):
        hi = limbs[i + words] if i + words < n else zero
        if bits == 0:
            out.append(hi)
            continue
        lo = limbs[i + words + 1] if i + words + 1 < n else zero
        out.append((hi << bits)
                   | ((lo >> (64 - bits)) & jnp.int64((1 << bits) - 1)))
    return out


def mul_pow10_u256(h, l, k: int):
    """Unsigned (h, l) * 10^k as four int64 limbs, most significant
    first: the dividend of a decimal division scaled to the quotient's
    scale, where 128 bits do not hold it."""
    ph, pl = _pow10_limbs(k)
    ph, pl = jnp.int64(ph), jnp.int64(pl)
    c0h, c0l = _mul_u64(l, pl)
    c1h, c1l = _mul_u64(l, ph)
    c2h, c2l = _mul_u64(h, pl)
    c3h, c3l = _mul_u64(h, ph)

    def add3(a, b, c, carry_in):
        s1 = a + b
        k1 = _ult(s1, a).astype(I64)
        s2 = s1 + c
        k2 = _ult(s2, s1).astype(I64)
        s3 = s2 + carry_in
        k3 = _ult(s3, s2).astype(I64)
        return s3, k1 + k2 + k3

    zero = jnp.zeros_like(l)
    n1, carry = add3(c0h, c1l, c2l, zero)
    n2, carry = add3(c1h, c2h, c3l, carry)
    n3 = c3h + carry
    return [n3, n2, n1, c0l]


def div_scaled_half_up(ah, al, k: int, bh, bl, num_digits: int):
    """round_half_up(a * 10^k / b) for UNSIGNED magnitudes with
    a * 10^k < 10^``num_digits`` and b != 0: the exact quotient, one
    rounding.

    A restoring shift-and-subtract division, one quotient bit an
    iteration: the chip has no 64-bit integer unit and no wide divide, a
    compare, a subtract and two shifts on limb pairs it has, and every
    row runs the same ``bits(10^num_digits)`` iterations (a static count
    from the operand TYPES, 123 for decimal(17,2) / decimal(17,2)). The
    dividend sits left-aligned in a register of two limbs, or four where
    a * 10^k passes 128 bits; the quotient's bits enter at its low end
    as the dividend's leave at the top, and the remainder stays under
    b <= 10^38 < 2^127, so doubling it never leaves 128 unsigned bits.

    Returns (qh, ql, fits): ``fits`` False where the rounded quotient
    passes 2^127 - 1 and so cannot be a decimal(38)."""
    nbits = (10 ** num_digits).bit_length()
    if nbits <= 128:
        reg = list(mul_pow10(ah, al, k))
    else:
        reg = mul_pow10_u256(ah, al, k)
    width = 64 * len(reg)
    assert nbits <= width, (num_digits, k)
    reg = _shl_limbs(reg, width - nbits)
    one = jnp.int64(1)

    def step(_i, state):
        *n, rh, rl = state
        top = (n[0] < 0).astype(I64)
        rh = (rh << 1) | ((rl >> 63) & one)
        rl = (rl << 1) | top
        ge = _uge128(rh, rl, bh, bl)
        sh, sl = sub128(rh, rl, bh, bl)
        rh = jnp.where(ge, sh, rh)
        rl = jnp.where(ge, sl, rl)
        n = _shl_limbs(n, 1)
        n[-1] = n[-1] | ge.astype(I64)
        return (*n, rh, rl)

    zero = jnp.zeros_like(al)
    *q, rh, rl = jax.lax.fori_loop(0, nbits, step, (*reg, zero, zero))
    qh, ql = q[-2], q[-1]
    fits = qh >= 0
    for limb in q[:-2]:
        fits = fits & (limb == 0)
    # HALF_UP on magnitudes: 2r >= b, written r >= b - r (r < b)
    dh, dl = sub128(bh, bl, rh, rl)
    bump = _uge128(rh, rl, dh, dl).astype(I64)
    qh, ql = add128(qh, ql, zero, bump)
    return qh, ql, fits & (qh >= 0)
