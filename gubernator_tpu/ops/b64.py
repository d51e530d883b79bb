"""IEEE binary64 arithmetic on the bit pattern, in pure int32 ops.

Upstream's leaky bucket is float64 arithmetic, rounded after every
operation (algorithms.go ``leakyBucket``: ``float64(elapsed) / rate``,
``b.Remaining += leak``, ``int64(b.Remaining)``).  The TPU has no f64
and Mosaic compiles no 64-bit type, so the served transition
(ops/transition32.py) computes on the float64's bits: a :class:`B64` is
the (lo, hi) int32 pair of the IEEE pattern, and every function here
returns the binary64 round-to-nearest-even result, bit for bit what
numpy float64 gives:

* ``from_pair``     int64 -> float64 (Go's ``float64(i)``)
* ``div``           the 54 quotient bits come from a restoring division
                    in ``lax.fori_loop`` (not unrolled: trace-and-lower
                    time stays flat), round and sticky from its exact
                    remainder
* ``add`` / ``sub`` aligned in a 64-bit window with ten guard bits and a
                    sticky bit
* ``trunc_to_pair`` Go's ``int64(f)``: toward zero, saturating
* ``from_triple`` / ``to_triple``  the stored float32 triple
                    (ops/tfloat.py T3), exactly

Only shifts, adds, compares, selects and ``clz`` on int32: the same code
serves (B,) XLA columns and (1, C) Pallas blocks, CPU and chip alike,
with no float unit's rounding, denormal flushing or division accuracy
in it.

Domain: finite values.  An exponent field of 0 is read and written as
zero (no subnormals) and there is no Inf or NaN: the transition guards
its divisors, and quotients and sums of int64 values stay far inside
the exponent range.
"""

from __future__ import annotations

from typing import NamedTuple

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax.numpy as jnp
import numpy as np
from jax import lax

from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.ops.i64pair import I64
from gubernator_tpu.ops.tfloat import T3

I32 = jnp.int32
F32 = jnp.float32

# numpy scalars so kernels using these ops stay closed (see i64pair.py)
_SIGN = np.int32(-0x80000000)
_ABS = np.int32(0x7FFFFFFF)
_FRAC_HI = np.int32(0xFFFFF)      # the 20 fraction bits of the high word
_IMPLICIT = np.int32(0x100000)    # bit 52 of the significand, in the high word
_BIAS = 1023


class B64(NamedTuple):
    """(lo, hi) int32 pair holding one float64's IEEE bits per element."""

    lo: jnp.ndarray
    hi: jnp.ndarray


def const(v: float, like) -> B64:
    """Broadcast a Python float constant to the shape of ``like``."""
    bits = int(np.float64(v).view(np.int64))
    w = p64.const(bits, like)
    return B64(w.lo, w.hi)


def zeros_like(x) -> B64:
    z = jnp.zeros(jnp.shape(x), I32)
    return B64(z, z)


def select(c, a: B64, b: B64) -> B64:
    return B64(jnp.where(c, a.lo, b.lo), jnp.where(c, a.hi, b.hi))


def is_zero(a: B64):
    """±0 (and anything with exponent field 0: flushed)."""
    return _exp(a) == 0


def neg(a: B64) -> B64:
    return B64(a.lo, a.hi ^ _SIGN)


# ----------------------------------------------------------------------
# 64-bit words in two int32: shifts by a per-element amount.  Every
# 32-bit shift amount stays in [0, 31] (beyond that MLIR's shifts are
# undefined, whatever XLA does).
# ----------------------------------------------------------------------
def _srl(x, n):
    """Logical x >> n (jnp's >> on int32 is arithmetic)."""
    if isinstance(n, int):
        n = np.int32(n)
    return lax.shift_right_logical(x, n)


def _shl64(lo, hi, n):
    """(lo, hi) << n for n in [0, 63]."""
    m = n & 31
    carry = _srl(_srl(lo, 1), 31 - m)          # lo >> (32 - m), 0 at m == 0
    l = lo << m
    h = (hi << m) | carry
    big = n >= 32
    return jnp.where(big, 0, l), jnp.where(big, l, h)


def _shr64(lo, hi, n):
    """Logical (lo, hi) >> n for n in [0, 63], and whether a set bit
    fell off (the sticky bit), as int32 0/1."""
    m = n & 31
    carry_h = (hi << 1) << (31 - m)            # hi << (32 - m), 0 at m == 0
    carry_l = (lo << 1) << (31 - m)            # the bits of lo that fall off
    l = _srl(lo, m) | carry_h
    h = _srl(hi, m)
    big = n >= 32
    # (a select of int32, then the compare: Mosaic lowers no select
    # between bool vectors)
    lost = jnp.where(big, carry_h | lo, carry_l) != 0
    return jnp.where(big, h, l), jnp.where(big, 0, h), lost.astype(I32)


def _clz64(lo, hi):
    return jnp.where(hi != 0, lax.clz(hi), 32 + lax.clz(lo))


def _exp(a: B64):
    """Biased exponent field."""
    return (a.hi >> 20) & 0x7FF


def _sig(a: B64) -> I64:
    """53-bit significand with its implicit bit; 0 for a zero."""
    nz = _exp(a) != 0
    return I64(jnp.where(nz, a.lo, 0),
               jnp.where(nz, (a.hi & _FRAC_HI) | _IMPLICIT, 0))


def _round_pack(sign, lo, hi, e63, sticky=0) -> B64:
    """The float64 nearest to ``(-1)^sign * (lo, hi) * 2^(e63 - 63)``
    (ties to even), where (lo, hi) is an unsigned 64-bit word of any
    alignment, ``sign`` the sign bit in place (0 or 0x80000000), and
    ``sticky`` (0/1) says that set bits lie below the word."""
    nz = _clz64(lo, hi)
    zero = nz == 64
    lo, hi = _shl64(lo, hi, nz & 63)           # leading 1 at bit 63
    e = e63 - nz                               # its unbiased exponent
    # significand: the top 53 bits; guard: bit 10; sticky: bits 9..0
    mlo = _srl(lo, 11) | (hi << 21)
    mhi = _srl(hi, 11)                         # implicit bit at bit 20
    guard = _srl(lo, 10) & 1
    rest = ((lo & 0x3FF) != 0).astype(I32) | sticky
    inc = guard & (rest | (mlo & 1))
    # The implicit bit adds one to the exponent field, and a significand
    # that rounds up to 2^53 carries into it: both come out right.
    packed = p64.add(I64(mlo, ((e + (_BIAS - 1)) << 20) + mhi),
                     I64(inc, jnp.zeros_like(inc)))
    flush = zero | (e < 1 - _BIAS)
    return B64(jnp.where(flush, 0, packed.lo),
               jnp.where(flush, sign, packed.hi | sign))


# ----------------------------------------------------------------------
# Conversions
# ----------------------------------------------------------------------
def from_pair(v: I64) -> B64:
    """int64 -> float64, rounded to nearest even above 2^53 (Go's
    ``float64(i)``)."""
    neg_ = v.hi < 0
    mag = p64.select(neg_, p64.neg(v), v)      # -2^63 keeps its pattern: 2^63
    return _round_pack(jnp.where(neg_, _SIGN, 0), mag.lo, mag.hi, 63)


def trunc_to_pair(a: B64) -> I64:
    """Go's ``int64(f)``: truncation toward zero.  Beyond int64 it
    saturates, as XLA's convert does (Go leaves that case to the
    hardware)."""
    e = _exp(a) - _BIAS
    m = _sig(a)
    rlo, rhi, _ = _shr64(m.lo, m.hi, jnp.clip(52 - e, 0, 63))
    llo, lhi = _shl64(m.lo, m.hi, jnp.clip(e - 52, 0, 63))
    up = e > 52
    mag = I64(jnp.where(up, llo, rlo), jnp.where(up, lhi, rhi))
    mag = p64.select(e < 0, p64.const(0, a.lo), mag)
    neg_ = a.hi < 0
    val = p64.select(neg_, p64.neg(mag), mag)
    sat = p64.select(neg_, p64.const(-(1 << 63), a.lo),
                     p64.const((1 << 63) - 1, a.lo))
    return p64.select(e >= 63, sat, val)


def _f32_fields(x):
    """float32 -> (negative?, biased exponent, 24-bit significand with
    the implicit bit; 0 where the exponent field is 0)."""
    b = lax.bitcast_convert_type(x, I32)
    e = (b >> 23) & 0xFF
    m = jnp.where(e == 0, 0, (b & 0x7FFFFF) | 0x800000)
    return b < 0, e, m


def from_triple(t: T3) -> B64:
    """The stored triple's value hi + mid + lo as a float64: exact when
    the triple holds one (everything ``to_triple`` and the host's
    Dekker split write), rounded to nearest otherwise.  The parts are
    summed as integers in units of 2^-30 of hi's last place, where a
    float64's bits all lie."""
    sh, eh, mh = _f32_fields(t.hi)
    zero = jnp.zeros_like(mh)

    def part(x):
        s, e, m = _f32_fields(x)
        up = 30 - (eh - e)                     # left shift into the window
        llo, lhi = _shl64(m, zero, jnp.clip(up, 0, 63))
        low = _srl(m, jnp.clip(-up, 0, 31))    # below the window: drop
        v = I64(jnp.where(up >= 0, llo, low), jnp.where(up >= 0, lhi, 0))
        return p64.select(s, p64.neg(v), v)

    top = I64(mh << 30, _srl(mh, 2))
    total = p64.add(p64.add(p64.select(sh, p64.neg(top), top), part(t.mid)),
                    part(t.lo))
    neg_ = total.hi < 0
    mag = p64.select(neg_, p64.neg(total), total)
    # bit 0 of the window weighs 2^(eh - 150 - 30)
    out = _round_pack(jnp.where(neg_, _SIGN, 0), mag.lo, mag.hi,
                      eh - (150 + 30 - 63))
    return select(mh == 0, B64(zero, jnp.where(sh, _SIGN, zero)), out)


def _f32_bits(sign, chunk, ef):
    """float32 bits of ``(-1)^sign * chunk * 2^(ef - 150)``: ``chunk``
    in [0, 2^24] whose bit 23 carries the exponent field ``ef``.  A zero
    chunk gives +0 (what a float difference of equals is)."""
    over = chunk >> 24                         # 2^24 itself: a rounding carry
    chunk = jnp.where(over != 0, chunk >> 1, chunk)
    n = lax.clz(chunk) - 8
    field = ef + over - n
    bits = sign | (field << 23) | ((chunk << (n & 31)) & 0x7FFFFF)
    return jnp.where((chunk == 0) | (field < 1), 0, bits)


def to_triple(a: B64) -> T3:
    """float64 -> the stored triple, exactly, and word for word the
    split every other writer makes (buckets._split_f64, tfloat.from_np):
    hi = f32(v), mid = f32(v - hi), lo = v - hi - mid, each rounded to
    nearest even, here on the significand's integer bits."""
    e = _exp(a)
    m = _sig(a)
    sign = a.hi & _SIGN

    # hi: the significand's top 24 bits, rounded on the 29 below them
    c2 = (m.hi << 3) | _srl(m.lo, 29)
    rem = m.lo & 0x1FFFFFFF
    up = (rem > 0x10000000) | ((rem == 0x10000000) & ((c2 & 1) != 0))
    hi = _f32_bits(sign, c2 + up.astype(I32), e - 896)
    # v - hi: d1 units of v's last place, of the other sign if hi
    # rounded up
    d1 = jnp.where(up, 0x20000000 - rem, rem)          # <= 2^28
    s1 = jnp.where(up, sign ^ _SIGN, sign)
    # mid: d1's top 24 bits, rounded on the (at most 5) below them
    shift = jnp.maximum(8 - lax.clz(d1), 0)
    q = d1 >> shift
    rem = d1 - (q << shift)
    half = (1 << shift) >> 1
    up = (shift > 0) & ((rem > half) | ((rem == half) & ((q & 1) != 0)))
    mid = _f32_bits(s1, q + up.astype(I32), e - 925 + shift)
    # lo: what is left, at most 16 units
    d2 = jnp.where(up, (1 << shift) - rem, rem)
    lo = _f32_bits(jnp.where(up, s1 ^ _SIGN, s1), d2, e - 925)
    hi = jnp.where(e == 0, sign, hi)                    # a zero keeps its sign
    return T3(*(lax.bitcast_convert_type(w, F32) for w in (hi, mid, lo)))


# ----------------------------------------------------------------------
# Arithmetic
# ----------------------------------------------------------------------
def add(a: B64, b: B64) -> B64:
    # x: the operand of larger magnitude
    swap = p64.lt(I64(a.lo, a.hi & _ABS), I64(b.lo, b.hi & _ABS))
    x, y = select(swap, b, a), select(swap, a, b)
    ex, ey = _exp(x), _exp(y)
    mx, my = _sig(x), _sig(y)
    # significands to bits 62..10: ten guard bits below, one above for
    # the carry of a sum
    xl, xh = mx.lo << 10, (mx.hi << 10) | _srl(mx.lo, 22)
    yl, yh = my.lo << 10, (my.hi << 10) | _srl(my.lo, 22)
    d = ex - ey                                # >= 0 where y is not zero
    yl, yh, lost = _shr64(yl, yh, jnp.clip(d, 0, 63))
    yl = yl | lost                             # sticky, below every guard bit
    same = ((x.hi ^ y.hi) & _SIGN) == 0
    X, Y = I64(xl, xh), I64(yl, yh)
    s = p64.select(same, p64.add(X, Y), p64.sub(X, Y))
    # Cancellation by more than one bit only happens at d <= 1, where
    # nothing fell off: the sticky bit never moves above the guard.
    sign = jnp.where(same | ~p64.is_zero(s), x.hi & _SIGN, 0)  # x - x = +0
    return _round_pack(sign, s.lo, s.hi, ex - (_BIAS - 1))


def sub(a: B64, b: B64) -> B64:
    return add(a, neg(b))


def _quotient_bits(rl, rh, bl, bh, n: int):
    """``n`` (<= 32) steps of restoring division of the remainder
    (rl, rh) in [0, 2b) by b = (bl, bh) < 2^53: the remainder after them
    (doubled, ready for the next step) and the n quotient bits."""
    blx = bl ^ _SIGN

    def step(_, c):
        rl, rh, q = c
        dl = rl - bl
        dh = rh - bh - ((rl ^ _SIGN) < blx).astype(I32)
        ge = dh >= 0
        rl = jnp.where(ge, dl, rl)
        rh = jnp.where(ge, dh, rh)
        return rl << 1, (rh << 1) | _srl(rl, 31), (q << 1) | ge.astype(I32)

    return lax.fori_loop(0, n, step, (rl, rh, jnp.zeros_like(rl)))


def div(a: B64, b: B64) -> B64:
    """a / b for b != 0."""
    ma, mb = _sig(a), _sig(b)
    # a's significand into [mb, 2 mb): the first quotient bit is 1
    low = p64.lt(ma, mb)
    rl = jnp.where(low, ma.lo << 1, ma.lo)
    rh = jnp.where(low, (ma.hi << 1) | _srl(ma.lo, 31), ma.hi)
    e = _exp(a) - _exp(b) - low.astype(I32)
    # 54 bits: 53 of significand and the guard; the remainder is the rest
    rl, rh, qh = _quotient_bits(rl, rh, mb.lo, mb.hi, 22)
    rl, rh, ql = _quotient_bits(rl, rh, mb.lo, mb.hi, 32)
    sticky = ((rl | rh) != 0).astype(I32)
    sign = (a.hi ^ b.hi) & _SIGN
    out = _round_pack(sign, ql << 10, (qh << 10) | _srl(ql, 22), e, sticky)
    return select(is_zero(a), B64(jnp.zeros_like(a.lo), sign), out)


# ----------------------------------------------------------------------
# Host side (tests)
# ----------------------------------------------------------------------
def to_np(a: B64):
    """B64 -> numpy float64."""
    return p64.to_np(I64(a.lo, a.hi)).view(np.float64)


def from_np(v) -> B64:
    """numpy float64 -> B64."""
    w = p64.from_np(np.ascontiguousarray(v, np.float64).view(np.int64))
    return B64(w.lo, w.hi)
