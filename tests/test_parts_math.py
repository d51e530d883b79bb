"""Property tests: i64-pair, triple-f32 and binary64-on-bits arithmetic
vs numpy oracles.

These are the primitives the parts-native bucket transition is built
from (ops/i64pair.py, ops/tfloat.py, ops/b64.py); pair ops must be
bit-exact i64, b64 ops bit-exact IEEE float64, and the triple ops (the
quotient estimate of ``div_floor_pos``) >= f64-class precise on the
engine's envelope.
"""

import numpy as np
import pytest

from gubernator_tpu.ops import i64pair as p64
from gubernator_tpu.ops import tfloat as tf

RNG = np.random.default_rng(7)


def rand_i64(n, lo=-(2**62), hi=2**62):
    specials = np.array(
        [0, 1, -1, 2**31 - 1, 2**31, -(2**31), 2**32 - 1, 2**32,
         -(2**32), 2**52, -(2**52), 2**62 - 1, -(2**62),
         1_700_000_000_000, 3_600_000],
        np.int64,
    )
    vals = RNG.integers(lo, hi, n - len(specials), dtype=np.int64)
    return np.concatenate([specials, vals])


class TestPair:
    def setup_method(self, _):
        self.a = rand_i64(512)
        self.b = rand_i64(512)[::-1].copy()
        self.pa = p64.from_np(self.a)
        self.pb = p64.from_np(self.b)

    def test_roundtrip(self):
        np.testing.assert_array_equal(p64.to_np(self.pa), self.a)

    def test_add_sub_neg(self):
        np.testing.assert_array_equal(
            p64.to_np(p64.add(self.pa, self.pb)), self.a + self.b)
        np.testing.assert_array_equal(
            p64.to_np(p64.sub(self.pa, self.pb)), self.a - self.b)
        np.testing.assert_array_equal(p64.to_np(p64.neg(self.pa)), -self.a)

    def test_mul_wraps(self):
        np.testing.assert_array_equal(
            p64.to_np(p64.mul(self.pa, self.pb)),
            (self.a * self.b))  # numpy int64 mul wraps two's-complement

    def test_compares(self):
        for name, op in [("lt", np.less), ("le", np.less_equal),
                         ("gt", np.greater), ("ge", np.greater_equal),
                         ("eq", np.equal), ("ne", np.not_equal)]:
            got = np.asarray(getattr(p64, name)(self.pa, self.pb))
            np.testing.assert_array_equal(got, op(self.a, self.b), err_msg=name)

    def test_minmax_select(self):
        np.testing.assert_array_equal(
            p64.to_np(p64.max_(self.pa, self.pb)), np.maximum(self.a, self.b))
        np.testing.assert_array_equal(
            p64.to_np(p64.min_(self.pa, self.pb)), np.minimum(self.a, self.b))
        c = self.a > 0
        np.testing.assert_array_equal(
            p64.to_np(p64.select(c, self.pa, self.pb)),
            np.where(c, self.a, self.b))

    def test_shr(self):
        for n in (0, 1, 24, 31, 32, 48, 63):
            np.testing.assert_array_equal(
                p64.to_np(p64.shr(self.pa, n)), self.a >> n, err_msg=str(n))

    def test_from_i32_const(self):
        x = RNG.integers(-(2**31), 2**31, 64, dtype=np.int64)
        np.testing.assert_array_equal(
            p64.to_np(p64.from_i32(x.astype(np.int32))), x)
        np.testing.assert_array_equal(
            p64.to_np(p64.const(-(5 << 40), np.zeros(4, np.int32))),
            np.full(4, -(5 << 40)))


class TestTriple:
    def test_pair_roundtrip_exact(self):
        v = rand_i64(512, -(2**62), 2**62)
        t = tf.from_pair(p64.from_np(v))
        np.testing.assert_array_equal(tf.to_np(t), v.astype(np.float64))
        back = p64.to_np(tf.floor_to_pair(t))
        np.testing.assert_array_equal(back, v)

    def test_add_precision(self):
        # drip accumulation shape: integer counts + small fractions
        a = RNG.uniform(-1e12, 1e12, 512)
        b = RNG.uniform(-1e3, 1e3, 512)
        got = tf.to_np(tf.add(tf.from_np(a), tf.from_np(b)))
        want = a + b
        # ~60-bit precision: within a couple of f64 ulps (XLA's own TPU
        # f64 emulation is a float32 pair, ~49 bits — far looser).
        np.testing.assert_allclose(got, want, rtol=5e-16)

    def test_div_exact_when_representable(self):
        # golden-suite rates: duration / limit with exact quotients
        dur = np.array([30_000, 60_000, 1_000, 5_000, 3_600_000] * 8,
                       np.float64)
        lim = np.array([10, 10, 4, 5, 1000] * 8, np.float64)
        got = tf.to_np(tf.div(tf.from_np(dur), tf.from_np(lim)))
        np.testing.assert_array_equal(got, dur / lim)

    def test_div_precision_random(self):
        a = RNG.uniform(1, 1e15, 512)
        b = RNG.uniform(1, 1e9, 512)
        got = tf.to_np(tf.div(tf.from_np(a), tf.from_np(b)))
        np.testing.assert_allclose(got, a / b, rtol=5e-16)

    def test_floor(self):
        x = np.concatenate([
            RNG.uniform(-1e9, 1e9, 500),
            np.array([0.0, -0.0, 0.5, -0.5, 1.0, -1.0, 2**40 + 0.5,
                      -(2**40) - 0.5, 3.9999999, -3.0000001, 1e-300, 7.0,
                      # within half an f32 ulp of an integer: the raw
                      # per-part fraction sum misrounds without the
                      # compare-verified correction step
                      4.0 - 1e-9, -4.0 + 1e-9, 4.0 + 1e-9, -4.0 - 1e-9,
                      1e6 - 1e-7, -(1e6 - 1e-7)]),
        ])
        got = p64.to_np(tf.floor_to_pair(tf.from_np(x)))
        np.testing.assert_array_equal(got, np.floor(x).astype(np.int64))

    def test_sign_of_a_triple(self):
        # what floor_to_pair's correction step leans on
        a = np.concatenate([RNG.uniform(-100, 100, 512),
                            [0.0, -0.0, 1e-9, -1e-9, 4.0 - 1e-9]])
        np.testing.assert_array_equal(
            np.asarray(tf.ge_zero(tf.from_np(a))), a >= 0)

    def test_mul_f(self):
        a = RNG.uniform(-1e9, 1e9, 512)
        f = RNG.uniform(-1e3, 1e3, 512).astype(np.float32)
        got = tf.to_np(tf.mul_f(tf.from_np(a), f))
        want = a * f.astype(np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-15)

    def test_leaky_drip_scenario(self):
        # 10 tokens / 30s -> rate 3000 ms/token; drip accumulation must
        # stay integer-exact over many steps (the golden sequences).
        rate = tf.div(tf.from_np(np.full(8, 30_000.0)),
                      tf.from_np(np.full(8, 10.0)))
        rem = tf.from_np(np.full(8, 7.0))
        for elapsed in (3000.0, 6000.0, 1500.0, 4500.0):
            leak = tf.div(tf.from_np(np.full(8, elapsed)), rate)
            rem = tf.add(rem, leak)
        np.testing.assert_array_equal(
            tf.to_np(rem), np.full(8, 7 + (3000 + 6000 + 1500 + 4500) / 3000))


# ----------------------------------------------------------------------
# IEEE binary64 on the bit pattern (ops/b64.py): every operation of the
# served leaky path against numpy float64, bit for bit.
# ----------------------------------------------------------------------
import jax  # noqa: E402

from gubernator_tpu.ops import b64  # noqa: E402


def bits(x):
    return np.ascontiguousarray(x, np.float64).view(np.int64)


def assert_same_bits(got: b64.B64, want, what):
    g, w = bits(b64.to_np(got)), bits(want)
    bad = np.flatnonzero(g != w)
    assert bad.size == 0, (what, bad.size, bad[:3], b64.to_np(got)[bad[:3]],
                           np.asarray(want)[bad[:3]])


class TestB64:
    """One jitted function an operation, shared by every case."""

    from_pair = staticmethod(jax.jit(b64.from_pair))
    div = staticmethod(jax.jit(b64.div))
    add = staticmethod(jax.jit(b64.add))
    sub = staticmethod(jax.jit(b64.sub))
    trunc = staticmethod(jax.jit(b64.trunc_to_pair))
    from_triple = staticmethod(jax.jit(b64.from_triple))
    to_triple = staticmethod(jax.jit(b64.to_triple))

    @staticmethod
    def envelope(rng, n):
        """The leaky path's operands: (duration, limit, elapsed,
        remaining_f with a 53-bit fraction)."""
        duration = np.where(
            rng.random(n) < 0.5,
            rng.choice([1_000, 30_000, 3_600_000, 7_200_000, 86_400_000], n),
            rng.integers(1, 2**62, n))
        limit = np.where(
            rng.random(n) < 0.5,
            rng.choice([1, 5, 20, 100, 1000, 2**33], n),
            rng.integers(1, 2**33 + 1, n))
        elapsed = rng.integers(0, 2**40 + 1, n)
        remaining = (rng.integers(0, 2**33, n) * (rng.random(n) < 0.9)
                     + rng.random(n))
        return duration, limit, elapsed, remaining

    def test_from_pair_rounds_to_nearest_even(self):
        v = np.concatenate([
            rand_i64(4096, -(2**63), 2**63 - 1),
            np.array([2**53 + 1, 2**53 + 3, -(2**53) - 1, 2**54 + 2,
                      2**54 + 6, 2**62 + 2**8, 2**62 + 2**9 + 2**8,
                      -(2**63), 2**63 - 1, 2**63 - 513], np.int64)])
        assert_same_bits(self.from_pair(p64.from_np(v)),
                         v.astype(np.float64), "from_pair")

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_leaky_steps_bit_for_bit(self, seed):
        """(a) seeded operands over the envelope: int64 -> float64,
        duration / limit, elapsed / rate, remaining + leak, remaining -
        hits, and int64() of rate, leak and remaining."""
        rng = np.random.default_rng(seed)
        n = 50_000
        duration, limit, elapsed, remaining = self.envelope(rng, n)
        fd, fl, fe = (self.from_pair(p64.from_np(x))
                      for x in (duration, limit, elapsed))
        rate = self.div(fd, fl)
        want_rate = duration.astype(np.float64) / limit.astype(np.float64)
        assert_same_bits(rate, want_rate, "duration / limit")
        leak = self.div(fe, rate)
        want_leak = elapsed.astype(np.float64) / want_rate
        assert_same_bits(leak, want_leak, "elapsed / rate")
        rem = self.add(b64.from_np(remaining), leak)
        want_rem = remaining + want_leak
        assert_same_bits(rem, want_rem, "remaining + leak")
        hits = rng.integers(0, 1000, n)
        left = self.sub(rem, self.from_pair(p64.from_np(hits)))
        want_left = want_rem - hits.astype(np.float64)
        assert_same_bits(left, want_left, "remaining - hits")
        for got, want in ((rate, want_rate), (leak, want_leak),
                          (rem, want_rem), (left, want_left)):
            np.testing.assert_array_equal(
                p64.to_np(self.trunc(got)),
                [max(-(2**63), min(2**63 - 1, int(x))) for x in want])

    def test_add_sub_signs_cancellation_and_far_exponents(self):
        rng = np.random.default_rng(5)
        x = np.concatenate([rng.uniform(-1e12, 1e12, 20_000),
                            [0.0, -0.0, 0.0, 1.0, -1.0, 2.0**62]])
        for y in (rng.uniform(-1e3, 1e3, len(x)),
                  -x * (1 + rng.choice(
                      [0, 2.0**-52, -(2.0**-52), 2.0**-30, 0.5], len(x))),
                  x * 2.0 ** rng.integers(-80, 80, len(x)),
                  np.where(rng.random(len(x)) < 0.5, 0.0, -0.0)):
            assert_same_bits(self.add(b64.from_np(x), b64.from_np(y)),
                             x + y, "add")
            assert_same_bits(self.sub(b64.from_np(x), b64.from_np(y)),
                             x - y, "sub")

    def test_adversarial_sums_that_round_up_to_a_whole_number(self):
        """(b) remaining_f = (k + 1) - frac(leak): the float64 sum is a
        whole number in every draw; in about half of them it is so by
        rounding up, and there the ~70-bit triple stayed just short of it
        and int64() came out one lower."""
        rng = np.random.default_rng(29)
        n = 100_000
        elapsed = rng.integers(3_600, 7_201, n)
        rate = np.float64(3_600_000) / np.float64(1000)
        leak = elapsed.astype(np.float64) / rate
        k = rng.integers(0, 1000, n).astype(np.float64)
        remaining = (k + 1.0) - (leak - np.floor(leak))
        want = remaining + leak
        assert (want == np.floor(want)).all()               # the family
        got_leak = self.div(
            self.from_pair(p64.from_np(elapsed)),
            self.div(b64.from_np(np.full(n, 3_600_000.0)),
                     b64.from_np(np.full(n, 1000.0))))
        assert_same_bits(got_leak, leak, "elapsed / rate")
        # through the stored triple, as the transition reads and writes it
        got = self.add(self.from_triple(tf.from_np(remaining)), got_leak)
        assert_same_bits(got, want, "remaining + leak")
        np.testing.assert_array_equal(
            p64.to_np(self.trunc(got)), want.astype(np.int64))
        np.testing.assert_array_equal(
            bits(tf.to_np(self.to_triple(got))), bits(want))

    def test_one_step_witness(self):
        """(c) limit 1000, 3,600,000 ms, elapsed 5,303 ms, remaining_f
        803.5269444444444: float64 sums to 805.0, the triple read 804."""
        one = lambda v: b64.from_np(np.array([v], np.float64))  # noqa: E731
        rate = self.div(one(3_600_000.0), one(1000.0))
        leak = self.div(one(5_303.0), rate)
        rem = self.add(
            self.from_triple(tf.from_np(np.array([803.5269444444444]))), leak)
        assert b64.to_np(rate)[0] == 3600.0
        assert b64.to_np(leak)[0] == 1.4730555555555556
        assert b64.to_np(rem)[0] == 805.0
        assert p64.to_np(self.trunc(rem))[0] == 805

    def test_triple_boundary_is_exact(self):
        rng = np.random.default_rng(11)
        _, _, _, remaining = self.envelope(rng, 20_000)
        w = np.concatenate([
            remaining, -remaining, rand_i64(2048).astype(np.float64),
            [0.0, -0.0, 1.0, 0.5, 2.0**-52, 1 + 2.0**-52, 2.0**63,
             # hi or mid rounds up, to a power of two or past a tie
             16777215.75, 16777215.5, 1.9999999999, 0.99999999999,
             3.0000000001, 2.0**40 - 2.0**-10, -(2.0**33) + 2.0**-19]])
        assert_same_bits(self.from_triple(tf.from_np(w)), w, "from_triple")
        t3 = self.to_triple(b64.from_np(w))
        # word for word the split every other writer of a row makes
        for got, want in zip(t3, tf.from_np(w)):
            np.testing.assert_array_equal(
                np.asarray(got).view(np.int32),
                np.asarray(want).view(np.int32))
        assert_same_bits(self.from_triple(t3), w, "round trip")
