"""The stored form of the leaky bucket's float64 ``remaining``: an exact
three-way float32 split, and ~70-bit Dekker arithmetic on it.

The leaky bucket's ``remaining`` is a float64 in the reference
(store.go:29-35) and is stored on device as a (hi, mid, lo) float32
triple (ops/buckets.py STATE_DTYPES), which holds any float64 of the
envelope exactly.  On TPU there is no native f64: XLA's X64 rewriter
emulates it with a float32 pair (~49 bits) and Mosaic cannot compile
under ``jax_enable_x64`` at all.

What is guaranteed, and where: the served transition's leaky arithmetic
is **IEEE binary64, every operation rounded to nearest even**, computed
in :mod:`gubernator_tpu.ops.b64` on the float64's bit pattern; this
module's triple is only how the value is *stored* (``b64.from_triple`` /
``b64.to_triple`` convert exactly at that boundary).  The triple
arithmetic below (add / mul_f / div / floor, error-free transforms via
Dekker splitting, ~70 bits, never rounded to 53) no longer computes any
answer: it is the quotient *estimate* of ``i64pair.div_floor_pos``,
which corrects it with an exact integer remainder.

All functions are shape-polymorphic and elementwise.

Domain: finite values, |x| < 2^63 for integer interop (the rate
limiter's envelope — the reference itself stores token counts in f64,
so anything beyond 2^53 is already approximate upstream).
"""

from __future__ import annotations

from typing import NamedTuple

import gubernator_tpu.jaxinit  # noqa: F401  (x64 + compile cache before jax use)
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops import i64pair as p64

F32 = jnp.float32
I32 = jnp.int32

# numpy scalars so kernels using these ops stay closed (see i64pair.py)
_P24 = np.float32(1 << 24)
_P32 = np.float32(2.0**32)
_PM32 = np.float32(2.0**-32)
_P48 = np.float32(2.0**48)
_P16 = np.float32(1 << 16)
_SPLIT = np.float32((1 << 12) + 1)  # Dekker split constant for f32


class T3(NamedTuple):
    """Non-overlapping (hi, mid, lo) float32 triple."""

    hi: jnp.ndarray
    mid: jnp.ndarray
    lo: jnp.ndarray


def _two_sum(a, b):
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _two_prod(a, b):
    """Exact product: p + e == a*b (Dekker split, no FMA)."""
    p = a * b
    ah = (a * _SPLIT) - ((a * _SPLIT) - a)
    al = a - ah
    bh = (b * _SPLIT) - ((b * _SPLIT) - b)
    bl = b - bh
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def renorm(x0, x1, x2) -> T3:
    """Two bubble passes of two_sum: parts come out ordered and
    (to within an ulp) non-overlapping — enough headroom at 72 bits."""
    x0, x1 = _two_sum(x0, x1)
    x1, x2 = _two_sum(x1, x2)
    x0, x1 = _two_sum(x0, x1)
    x1, x2 = _two_sum(x1, x2)
    return T3(x0, x1, x2)


def zeros_like(x) -> T3:
    z = jnp.zeros(jnp.shape(x), F32)
    return T3(z, z, z)


def from_f32(x) -> T3:
    x = jnp.asarray(x, F32)
    z = jnp.zeros_like(x)
    return T3(x, z, z)


def select(c, a: T3, b: T3) -> T3:
    return T3(jnp.where(c, a.hi, b.hi), jnp.where(c, a.mid, b.mid),
              jnp.where(c, a.lo, b.lo))


def neg(a: T3) -> T3:
    return T3(-a.hi, -a.mid, -a.lo)


def add(a: T3, b: T3) -> T3:
    s0, e0 = _two_sum(a.hi, b.hi)
    s1, e1 = _two_sum(a.mid, b.mid)
    s1b, e0b = _two_sum(s1, e0)
    s2 = a.lo + b.lo + e1 + e0b
    return renorm(s0, s1b, s2)


def sub(a: T3, b: T3) -> T3:
    return add(a, neg(b))


def mul_f(a: T3, f) -> T3:
    """Triple times plain f32."""
    p0, e0 = _two_prod(a.hi, f)
    p1, e1 = _two_prod(a.mid, f)
    m, em = _two_sum(e0, p1)
    return renorm(p0, m, em + e1 + a.lo * f)


def div(a: T3, b: T3) -> T3:
    """a / b to ~70 bits: leading-part quotient + two residual
    corrections.  Exact when the quotient is exactly representable
    (integral rates like 30000/10) because the final residual is zero."""
    q0 = a.hi / b.hi
    r1 = sub(a, mul_f(b, q0))
    q1 = r1.hi / b.hi
    r2 = sub(r1, mul_f(b, q1))
    q2 = r2.hi / b.hi
    return renorm(q0, q1, q2)


def from_pair(v: p64.I64) -> T3:
    """Exact i64 pair -> triple (24-bit chunk decomposition)."""
    c2 = p64.shr(v, 48).lo                       # signed top chunk
    c1 = p64.shr(v, 24).lo & jnp.int32(0xFFFFFF)  # unsigned middle
    c0 = v.lo & jnp.int32(0xFFFFFF)               # unsigned low
    return renorm(
        c2.astype(F32) * _P48,
        c1.astype(F32) * _P24,
        c0.astype(F32),
    )


def _part_int_frac(x):
    """Per-part (floor as exact f32 integer, fraction in [0,1))."""
    big = jnp.abs(x) >= _P24          # f32 >= 2^24 is already an integer
    fl = jnp.where(big, x, jnp.floor(x))
    fr = jnp.where(big, jnp.float32(0), x - jnp.floor(x))
    return fl, fr


def _f32int_to_pair(fx) -> p64.I64:
    """Exact-integer f32 (|fx| < 2^63) -> i64 pair.  Decomposes the
    magnitude (whose sub-2^32 suffix is always representable) and negates
    in pair arithmetic — decomposing a negative directly would need
    2^32-|fx| low words that don't fit a 24-bit mantissa."""
    s = fx < 0
    a = jnp.abs(fx)
    h = jnp.floor(a * _PM32)           # high word as f32 integer, >= 0
    l = a - h * _P32                   # in [0, 2^32), <= 24 sig bits, exact
    lh = jnp.floor(l / _P16)           # [0, 2^16)
    ll = l - lh * _P16                 # [0, 2^16)
    lo = ll.astype(I32) | (lh.astype(I32) << 16)
    mag = p64.I64(lo, h.astype(I32))
    return p64.select(s, p64.neg(mag), mag)


def floor_to_pair(t: T3) -> p64.I64:
    """floor(t) as an i64 pair.  floor == trunc for the engine's
    non-negative uses (remaining, rates); negative inputs floor.

    The per-part fraction sum can misround by one when a part sits
    within half an f32 ulp of an integer (e.g. mid = -1e-8 gives a
    1 - 1e-8 fraction that rounds to 1.0), so the candidate is
    re-verified against ``t`` with the ~70-bit triple compares and
    nudged — floor and the compare ops then agree by construction."""
    f0, r0 = _part_int_frac(t.hi)
    f1, r1 = _part_int_frac(t.mid)
    f2, r2 = _part_int_frac(t.lo)
    total = p64.add(p64.add(_f32int_to_pair(f0), _f32int_to_pair(f1)),
                    _f32int_to_pair(f2))
    fr = r0 + r1 + r2                  # [0, 3)
    cand = p64.add(total, p64.from_i32(jnp.floor(fr).astype(I32)))
    # Correct a +-1 error: want cand <= t < cand + 1.
    d = sub(t, from_pair(cand))
    one = p64.const(1, t.hi)
    cand = p64.select(ge_zero(d), cand, p64.sub(cand, one))
    too_low = ge_zero(sub(d, from_f32(jnp.float32(1.0))))
    return p64.select(too_low, p64.add(cand, one), cand)


def ge_zero(t: T3):
    """t >= 0 for a renormalized triple (sign of leading nonzero part)."""
    return (t.hi > 0) | (
        (t.hi == 0) & ((t.mid > 0) | ((t.mid == 0) & (t.lo >= 0)))
    )


def to_np(t: T3):
    """Host-side: triple -> numpy float64 (tests / exports)."""
    import numpy as np

    return (np.asarray(t.hi).astype(np.float64)
            + np.asarray(t.mid).astype(np.float64)
            + np.asarray(t.lo).astype(np.float64))


def from_np(v):
    """Host-side: numpy float64 -> exact Dekker-split triple (tests)."""
    import numpy as np

    v = np.asarray(v, np.float64)
    hi = v.astype(np.float32)
    r1 = v - hi.astype(np.float64)
    mid = r1.astype(np.float32)
    lo = (r1 - mid.astype(np.float64)).astype(np.float32)
    return T3(jnp.asarray(hi), jnp.asarray(mid), jnp.asarray(lo))
