"""Exact IEEE-754 binary64 arithmetic as integer kernels ("softfloat").

Why this exists: real TPUs have no float64 ALU.  XLA emulates ``f64`` with
a pair of ``f32``s, which means ~48-bit precision, an f32 exponent range
(doubles beyond ~1e38 become inf/NaN, below ~1e-38 flush to zero) and
non-IEEE rounding — a 1e300 SQL DOUBLE literally cannot round-trip device
memory.  SQL DOUBLE semantics (Spark/cuDF, reference: GpuCast.scala,
arithmetic.scala) require the full binary64 domain.

The TPU-native answer: a DOUBLE column's device buffer holds the IEEE-754
**bit pattern in int64**, and arithmetic is exact integer IEEE-754
implemented here.  64-bit *integer* ops ARE exact on TPU (XLA lowers them
to 32-bit pair arithmetic losslessly — verified by probe), so every kernel
below is bit-exact with the host's float64, including subnormals,
signed zeros, infinities and round-to-nearest-even.

This is also a win for the rest of the engine: ordering, grouping, joins
and hash partitioning already operate on integer key words
(kernels/canon.py), so doubles-as-bits removes the only non-integer data
path from the device entirely.

Every public function takes/returns **int64 arrays of bit patterns**
(referred to as "bits").  Scalars enter via :func:`bits_of`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# -- constants (python ints; jnp scalars are created lazily inside kernels) --
SIGN = 0x8000000000000000
EXP_MASK = 0x7FF0000000000000
MANT_MASK = 0x000FFFFFFFFFFFFF
MAG_MASK = 0x7FFFFFFFFFFFFFFF
IMPLICIT = 1 << 52
QNAN = 0x7FF8000000000000
INF = 0x7FF0000000000000
ONE = 0x3FF0000000000000
MAX_FINITE = 0x7FEFFFFFFFFFFFFF


def bits_of(value: float) -> int:
    """Host-side: python float -> bit-pattern int (for literals/fills)."""
    return int(np.float64(value).view(np.int64))


def float_of(bits: int) -> float:
    """Host-side: bit-pattern int -> python float."""
    return float(np.int64(bits).view(np.float64))


def _u(x):
    return x.astype(jnp.uint64) if x.dtype != jnp.uint64 else x


def _i(x):
    return x.astype(jnp.int64) if x.dtype != jnp.int64 else x


def _c(v):
    return jnp.uint64(v)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def is_nan(bits) -> jnp.ndarray:
    u = _u(bits)
    return (u & _c(MAG_MASK)) > _c(INF)


def is_inf(bits) -> jnp.ndarray:
    u = _u(bits)
    return (u & _c(MAG_MASK)) == _c(INF)


def is_zero(bits) -> jnp.ndarray:
    u = _u(bits)
    return (u & _c(MAG_MASK)) == _c(0)


def is_finite(bits) -> jnp.ndarray:
    u = _u(bits)
    return (u & _c(EXP_MASK)) != _c(EXP_MASK)


def is_negative(bits) -> jnp.ndarray:
    """Sign bit set (true for -0.0; NaN sign is ignored by callers)."""
    return (_u(bits) & _c(SIGN)) != _c(0)


def sign_column(bits) -> jnp.ndarray:
    """Spark Signum: -1.0 / 0.0 / 1.0 (NaN -> NaN), as bits."""
    neg = bits_const(-1.0)
    pos = bits_const(1.0)
    zero = jnp.int64(0)
    out = jnp.where(is_zero(bits), zero,
                    jnp.where(is_negative(bits), neg, pos))
    return jnp.where(is_nan(bits), jnp.int64(QNAN), out)


def bits_const(value: float):
    return jnp.int64(bits_of(value))


# ---------------------------------------------------------------------------
# ordering (Spark total order: -NaN conflated, NaN greatest, -0.0 == 0.0)
# ---------------------------------------------------------------------------

def order_word(bits) -> jnp.ndarray:
    """uint64 whose unsigned order equals Spark's total order on doubles.

    All NaNs are canonicalized to +QNaN, and -0.0 to +0.0, *before* the
    IEEE flip trick (reference: NormalizeFloatingNumbers.scala), so
    NaN == NaN and -0.0 == 0.0 hold under plain integer equality.
    """
    u = _u(bits)
    u = jnp.where(is_nan(u), _c(QNAN), u)
    u = jnp.where((u & _c(MAG_MASK)) == _c(0), _c(0), u)
    neg = (u & _c(SIGN)) != _c(0)
    return jnp.where(neg, ~u, u | _c(SIGN))


def word_to_bits(word) -> jnp.ndarray:
    """Inverse of order_word (canonicalized values only)."""
    w = _u(word)
    neg = (w & _c(SIGN)) == _c(0)
    return _i(jnp.where(neg, ~w, w & _c(MAG_MASK)))


def lt(a_bits, b_bits):
    return order_word(a_bits) < order_word(b_bits)


def le(a_bits, b_bits):
    return order_word(a_bits) <= order_word(b_bits)


def eq(a_bits, b_bits):
    return order_word(a_bits) == order_word(b_bits)


def min2(a_bits, b_bits):
    return jnp.where(lt(b_bits, a_bits), b_bits, a_bits)


def max2(a_bits, b_bits):
    return jnp.where(lt(a_bits, b_bits), b_bits, a_bits)


# ---------------------------------------------------------------------------
# bit utilities
# ---------------------------------------------------------------------------

def _clz64(x):
    """Count leading zeros of uint64 (64 for zero) via binary reduction."""
    x = _u(x)
    n = jnp.zeros(x.shape, jnp.int64)
    for shift in (32, 16, 8, 4, 2, 1):
        mask = x < (_c(1) << _c(64 - shift))
        n = jnp.where(mask, n + shift, n)
        x = jnp.where(mask, x << _c(shift), x)
    return n


def _unpack(bits):
    """-> (neg bool, exp int64 raw 0..2047, mant uint64 52-bit)."""
    u = _u(bits)
    neg = (u & _c(SIGN)) != _c(0)
    exp = ((u & _c(EXP_MASK)) >> _c(52)).astype(jnp.int64)
    mant = u & _c(MANT_MASK)
    return neg, exp, mant


def _significand(exp, mant):
    """Effective (significand, exponent) treating subnormals as exp=1."""
    sig = jnp.where(exp > 0, mant | _c(IMPLICIT), mant)
    e = jnp.where(exp > 0, exp, jnp.int64(1))
    return sig, e


def _pack(neg, exp, mant):
    """exp: biased int64 (1..2046 normal); mant 52-bit; no rounding."""
    u = (_u(exp) << _c(52)) | (_u(mant) & _c(MANT_MASK))
    return _i(jnp.where(neg, u | _c(SIGN), u))


def _shift_right_sticky(sig, n):
    """sig >> n with sticky-OR of shifted-out bits; n >= 0 (clamped 63)."""
    n = jnp.minimum(n.astype(jnp.int64), jnp.int64(63))
    nn = _u(n)
    dropped = sig & ((_c(1) << nn) - _c(1))
    return (sig >> nn) | jnp.where(dropped != _c(0), _c(1), _c(0))


def _round_pack(neg, e, sig57):
    """Round-to-nearest-even a 57-bit significand (54 value bits + guard,
    round, sticky in the low 3 bits is NOT the layout here).

    Layout contract: ``sig57`` holds the significand aligned so the
    implicit-1 position is bit 55 (i.e. value bits 55..3) with bits 2..0 =
    guard/round/sticky.  ``e`` is the biased exponent for bit 55 == 2^52.
    Handles subnormal squeeze (e <= 0), overflow to inf, exact-zero.
    """
    # subnormal squeeze: shift right so e becomes 1
    squeeze = jnp.maximum(jnp.int64(1) - e, jnp.int64(0))
    sig57 = jnp.where(squeeze > 0, _shift_right_sticky(sig57, squeeze), sig57)
    e = jnp.where(squeeze > 0, jnp.int64(1), e)

    lsb = (sig57 >> _c(3)) & _c(1)
    guard = (sig57 >> _c(2)) & _c(1)
    rest = sig57 & _c(3)
    round_up = (guard == _c(1)) & ((rest != _c(0)) | (lsb == _c(1)))
    sig = (sig57 >> _c(3)) + jnp.where(round_up, _c(1), _c(0))

    # carry out of rounding: significand reached 2^53 -> renormalize
    carried = sig >= _c(1 << 53)
    sig = jnp.where(carried, sig >> _c(1), sig)
    e = jnp.where(carried, e + 1, e)

    # result subnormal if significand lost its implicit bit
    subn = sig < _c(IMPLICIT)
    exp_field = jnp.where(subn, jnp.int64(0), e)
    exp_field = jnp.where(sig == _c(0), jnp.int64(0), exp_field)

    overflow = e > 2046
    out = _pack(neg, exp_field, sig)
    out = jnp.where(overflow, _pack(neg, jnp.int64(2047), _c(0)), out)
    return out


# ---------------------------------------------------------------------------
# add / sub
# ---------------------------------------------------------------------------

def add(a_bits, b_bits):
    """IEEE-754 binary64 addition, round-to-nearest-even."""
    an, ae, am = _unpack(a_bits)
    bn, be, bm = _unpack(b_bits)
    asig, aexp = _significand(ae, am)
    bsig, bexp = _significand(be, bm)

    # order by magnitude (exp, mant): big, small
    a_mag = _u(a_bits) & _c(MAG_MASK)
    b_mag = _u(b_bits) & _c(MAG_MASK)
    swap = b_mag > a_mag
    big_sig = jnp.where(swap, bsig, asig)
    big_e = jnp.where(swap, bexp, aexp)
    big_n = jnp.where(swap, bn, an)
    sml_sig = jnp.where(swap, asig, bsig)
    sml_e = jnp.where(swap, aexp, bexp)
    sml_n = jnp.where(swap, an, bn)

    # align with 3 extra bits (guard/round/sticky); implicit at bit 55
    big55 = big_sig << _c(3)
    sml55 = _shift_right_sticky(sml_sig << _c(3), big_e - sml_e)

    same_sign = big_n == sml_n
    ssum = big55 + sml55                       # <= 2^57
    sdiff = big55 - sml55                      # >= 0 by magnitude order
    sig = jnp.where(same_sign, ssum, sdiff)

    # normalize: same-sign may carry to bit 56; diff may cancel low
    carry = sig >= _c(1 << 56)
    sig = jnp.where(carry, _shift_right_sticky(sig, jnp.int64(1)), sig)
    e = jnp.where(carry, big_e + 1, big_e)
    # left-normalize after cancellation (keep exponent >= 1 for subnormals)
    lz = _clz64(sig) - 8                       # bits above position 55
    shift_l = jnp.clip(lz, 0, jnp.maximum(e - 1, 0))
    sig = sig << _u(shift_l)
    e = e - shift_l

    out = _round_pack(big_n, e, sig)
    # exact cancellation -> +0.0 (RNE rule)
    out = jnp.where(sig == _c(0), jnp.int64(0), out)

    # specials
    a_nan, b_nan = is_nan(a_bits), is_nan(b_bits)
    a_inf, b_inf = is_inf(a_bits), is_inf(b_bits)
    an_s = is_negative(a_bits)
    bn_s = is_negative(b_bits)
    out = jnp.where(a_inf & b_inf & (an_s != bn_s), jnp.int64(QNAN),
                    jnp.where(a_inf, _i(_u(a_bits)),
                              jnp.where(b_inf, _i(_u(b_bits)), out)))
    # x + (-x) handled above; zero operands: 0 + y = y exactly, but
    # -0 + -0 = -0
    both_zero = is_zero(a_bits) & is_zero(b_bits)
    neg_zero = both_zero & an_s & bn_s
    neg_zero_bits = jnp.int64(SIGN - 2 ** 64)          # -0.0 as signed i64
    out = jnp.where(both_zero, jnp.where(neg_zero, neg_zero_bits,
                                         jnp.int64(0)), out)
    only_a = is_zero(b_bits) & ~is_zero(a_bits)
    only_b = is_zero(a_bits) & ~is_zero(b_bits)
    out = jnp.where(only_a, _i(_u(a_bits)), out)
    out = jnp.where(only_b, _i(_u(b_bits)), out)
    out = jnp.where(a_nan | b_nan, jnp.int64(QNAN), out)
    return out


def neg(bits):
    return _i(_u(bits) ^ _c(SIGN))


def sub(a_bits, b_bits):
    return add(a_bits, neg(b_bits))


def abs_(bits):
    return _i(_u(bits) & _c(MAG_MASK))


# ---------------------------------------------------------------------------
# mul
# ---------------------------------------------------------------------------

def _mul_64x64(a, b):
    """Full 128-bit product of two uint64 -> (hi, lo) uint64."""
    mask32 = _c(0xFFFFFFFF)
    a0 = a & mask32
    a1 = a >> _c(32)
    b0 = b & mask32
    b1 = b >> _c(32)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> _c(32)) + (p01 & mask32) + (p10 & mask32)
    lo = (p00 & mask32) | (mid << _c(32))
    hi = p11 + (p01 >> _c(32)) + (p10 >> _c(32)) + (mid >> _c(32))
    return hi, lo


def _normalize_sig(sig, e):
    """Shift significand up so the implicit bit is at position 52
    (subnormal inputs), adjusting the exponent."""
    lz = _clz64(sig) - 11           # leading zeros above bit 52
    lz = jnp.maximum(lz, jnp.int64(0))
    return sig << _u(lz), e - lz


def mul(a_bits, b_bits):
    """IEEE-754 binary64 multiplication, round-to-nearest-even."""
    an, ae, am = _unpack(a_bits)
    bn, be, bm = _unpack(b_bits)
    rn = an != bn
    asig, aexp = _significand(ae, am)
    bsig, bexp = _significand(be, bm)
    asig, aexp = _normalize_sig(asig, aexp)
    bsig, bexp = _normalize_sig(bsig, bexp)

    hi, lo = _mul_64x64(asig, bsig)           # product in [2^104, 2^106)
    # significand target: implicit at bit 55 (56-bit value + grs in round)
    # product bit 105 set => top = bit 105; else bit 104.
    top105 = (hi & _c(1 << 41)) != _c(0)
    # take bits [105..50] or [104..49] into a 56-bit sig with sticky
    shift = jnp.where(top105, jnp.int64(50), jnp.int64(49))
    # sig = (hi:lo) >> shift, sticky from dropped lo bits
    sh = _u(shift)
    sig = (hi << (_c(64) - sh)) | (lo >> sh)
    dropped = lo & ((_c(1) << sh) - _c(1))
    sig = sig | jnp.where(dropped != _c(0), _c(1), _c(0))
    e = aexp + bexp - 1023 + jnp.where(top105, jnp.int64(1), jnp.int64(0))

    out = _round_pack(rn, e, sig)

    # specials
    a_nan, b_nan = is_nan(a_bits), is_nan(b_bits)
    a_inf, b_inf = is_inf(a_bits), is_inf(b_bits)
    a_zero, b_zero = is_zero(a_bits), is_zero(b_bits)
    inf_times_zero = (a_inf & b_zero) | (b_inf & a_zero)
    signed_zero = _i(jnp.where(rn, _c(SIGN), _c(0)))
    signed_inf = _i(jnp.where(rn, _c(SIGN | INF), _c(INF)))
    out = jnp.where(a_zero | b_zero, signed_zero, out)
    out = jnp.where(a_inf | b_inf, signed_inf, out)
    out = jnp.where(inf_times_zero | a_nan | b_nan, jnp.int64(QNAN), out)
    return out


# ---------------------------------------------------------------------------
# div
# ---------------------------------------------------------------------------

def div(a_bits, b_bits):
    """IEEE-754 binary64 division, round-to-nearest-even.

    Mantissa quotient by vectorized shift-subtract long division (55 bits +
    sticky) under ``lax.fori_loop`` — pure u64 compare/sub/shift per step,
    which XLA maps well onto the VPU's integer lanes.
    """
    an, ae, am = _unpack(a_bits)
    bn, be, bm = _unpack(b_bits)
    rn = an != bn
    asig, aexp = _significand(ae, am)
    bsig, bexp = _significand(be, bm)
    asig, aexp = _normalize_sig(asig, aexp)
    bsig, bexp = _normalize_sig(bsig, bexp)

    def step(_, state):
        rem, q = state
        ge = rem >= bsig
        rem = jnp.where(ge, rem - bsig, rem)
        q = (q << _c(1)) | jnp.where(ge, _c(1), _c(0))
        rem = rem << _c(1)
        return rem, q

    rem0 = asig
    q0 = jnp.zeros_like(asig)
    rem, q = jax.lax.fori_loop(0, 57, step, (rem0, q0))
    # q = floor(asig * 2^56 / bsig) in [2^55, 2^57); invariant rem < 2*bsig
    sticky = jnp.where(rem != _c(0), _c(1), _c(0))
    top57 = (q & _c(1 << 56)) != _c(0)
    # align implicit to bit 55: if quotient >= 2^56 shift down one
    sig = jnp.where(top57, _shift_right_sticky(q, jnp.int64(1)), q) | sticky
    e = aexp - bexp + 1023 + jnp.where(top57, jnp.int64(0), jnp.int64(-1))

    out = _round_pack(rn, e, sig)

    # specials
    a_nan, b_nan = is_nan(a_bits), is_nan(b_bits)
    a_inf, b_inf = is_inf(a_bits), is_inf(b_bits)
    a_zero, b_zero = is_zero(a_bits), is_zero(b_bits)
    signed_zero = _i(jnp.where(rn, _c(SIGN), _c(0)))
    signed_inf = _i(jnp.where(rn, _c(SIGN | INF), _c(INF)))
    out = jnp.where(b_inf, signed_zero, out)
    out = jnp.where(b_zero, signed_inf, out)
    out = jnp.where(a_zero, signed_zero, out)
    out = jnp.where(a_inf, signed_inf, out)
    nan_out = (a_nan | b_nan | (a_zero & b_zero) | (a_inf & b_inf))
    out = jnp.where(nan_out, jnp.int64(QNAN), out)
    return out


# ---------------------------------------------------------------------------
# sqrt
# ---------------------------------------------------------------------------

def sqrt(a_bits):
    """IEEE-754 binary64 square root (restoring digit recurrence, RNE)."""
    an, ae, am = _unpack(a_bits)
    sig, e = _significand(ae, am)
    sig, e = _normalize_sig(sig, e)
    # make unbiased exponent even: value = sig * 2^(e-1075+52)... work with
    # m in [2^52, 2^54): if exponent odd, shift sig left 1
    eu = e - 1023                      # unbiased
    odd = (eu & 1) != 0
    m = jnp.where(odd, sig << _c(1), sig)
    half_e = jnp.where(odd, (eu - 1) // 2, eu // 2)

    # digit recurrence on radicand R = m << 54 (108 bits): root of 54 bits
    # (53 value bits + 1 guard).  rem stays < 4*root + 4 => fits u64.
    def step(i, state):
        rem, root = state
        # bring down bit pair i of R (m occupies bits 107..54 of R)
        shift = jnp.maximum(jnp.int64(52) - 2 * i, jnp.int64(0))
        bits2 = jnp.where(jnp.int64(52) - 2 * i >= 0,
                          (m >> _u(shift)) & _c(3), _c(0))
        rem = (rem << _c(2)) | bits2
        trial = (root << _c(2)) | _c(1)
        ge = rem >= trial
        rem = jnp.where(ge, rem - trial, rem)
        root = (root << _c(1)) | jnp.where(ge, _c(1), _c(0))
        return rem, root

    rem0 = jnp.zeros_like(m)
    root0 = jnp.zeros_like(m)
    rem, root = jax.lax.fori_loop(0, 54, step, (rem0, root0))
    # root = floor(sqrt(m << 54)) in [2^53, 2^54): 53 value bits + guard.
    # sqrt never lands exactly between representables unless exact, so
    # guard + (rem != 0) sticky suffices for RNE.
    sticky = jnp.where(rem != _c(0), _c(1), _c(0))
    guard = root & _c(1)
    val53 = root >> _c(1)
    sig = (val53 << _c(3)) | (guard << _c(2)) | sticky
    out = _round_pack(jnp.zeros_like(an), half_e + 1023, sig)

    out = jnp.where(is_zero(a_bits), _i(_u(a_bits)), out)     # sqrt(±0)=±0
    neg_in = is_negative(a_bits) & ~is_zero(a_bits)
    out = jnp.where(is_inf(a_bits) & ~neg_in, jnp.int64(INF), out)
    out = jnp.where(neg_in | is_nan(a_bits), jnp.int64(QNAN), out)
    return out


# ---------------------------------------------------------------------------
# conversions
# ---------------------------------------------------------------------------

def from_i64(x):
    """int64 -> binary64 bits (RNE for |x| > 2^53)."""
    x = _i(x)
    neg_in = x < 0
    # |int64 min| overflows; handle via uint64 magnitude
    mag = jnp.where(neg_in, (~_u(x)) + _c(1), _u(x))
    lz = _clz64(mag)
    # place MSB at bit 55 (implicit position for _round_pack), grs below
    shift_l = lz - 8
    sig = jnp.where(shift_l >= 0, mag << _u(jnp.maximum(shift_l, 0)),
                    _shift_right_sticky(mag, -shift_l))
    e = jnp.int64(1086) - lz
    out = _round_pack(neg_in, e, sig)
    return jnp.where(mag == _c(0), jnp.int64(0), out)


def from_i32(x):
    return from_i64(x.astype(jnp.int64))


def to_i64(bits):
    """Truncate toward zero with Java/Spark long-cast semantics:
    NaN -> 0, saturate at Long.MIN/MAX."""
    n, exp, mant = _unpack(bits)
    sig, e = _significand(exp, mant)
    # value = sig * 2^(e - 1075); sig < 2^53
    right = jnp.clip(jnp.int64(1075) - e, 0, 63)
    left = jnp.clip(e - jnp.int64(1075), 0, 63)
    mag = jnp.where(e <= 1075, sig >> _u(right), sig << _u(left))
    out = jnp.where(n, -_i(mag), _i(mag))
    imax = jnp.int64(2 ** 63 - 1)
    imin = jnp.int64(-(2 ** 63))
    # e - 1075 >= 11 => |value| >= 2^63: saturate (covers exact -2^63 too)
    too_big = (e - jnp.int64(1075)) >= jnp.int64(11)
    out = jnp.where(too_big | is_inf(bits), jnp.where(n, imin, imax), out)
    out = jnp.where(is_nan(bits), jnp.int64(0), out)
    return out


def to_int(bits, np_dtype):
    """double -> integral cast with Spark non-ANSI semantics: NaN -> 0,
    saturate to the target bounds, truncate toward zero."""
    long = to_i64(bits)
    info = np.iinfo(np_dtype)
    clamped = jnp.clip(long, int(info.min), int(info.max))
    return clamped.astype(np_dtype)


def from_f32(f):
    """float32 array -> binary64 bits (exact widening; native u32 bitcast
    is supported on TPU)."""
    u32 = jax.lax.bitcast_convert_type(f, jnp.uint32).astype(jnp.uint64)
    sign = (u32 >> _c(31)) & _c(1)
    exp = ((u32 >> _c(23)) & _c(0xFF)).astype(jnp.int64)
    mant = u32 & _c(0x7FFFFF)
    # normal: rebias 127 -> 1023, mant << 29
    nexp = exp + (1023 - 127)
    out = _pack(sign != _c(0), nexp, mant << _c(29))
    # subnormal f32: value = mant * 2^-149 — normalize into f64 normal
    lz = _clz64(mant) - 41            # leading zeros above bit 22
    sub_mant = (mant << _u(lz + 1)) & _c(0x7FFFFF)     # drop implicit
    sub_exp = (1023 - 126) - (lz + 1)
    sub = _pack(sign != _c(0), sub_exp, sub_mant << _c(29))
    out = jnp.where(exp == 0, sub, out)
    out = jnp.where((exp == 0) & (mant == _c(0)),
                    _i((_u(sign) << _c(63))), out)
    inf_bits = _i((_u(sign) << _c(63)) | _c(INF))
    out = jnp.where(exp == 255,
                    jnp.where(mant == _c(0), inf_bits, jnp.int64(QNAN)), out)
    return out


def to_f32(bits):
    """binary64 bits -> float32 array (RNE narrowing)."""
    n, exp, mant = _unpack(bits)
    sig, e = _significand(exp, mant)
    sig, e = _normalize_sig(sig, e)
    # f32: 24-bit significand; rebias: e32 = e - 1023 + 127
    e32 = e - (1023 - 127)
    # shift 53-bit sig down to 24-bit value + grs: implicit from 52 to 26
    sig27 = _shift_right_sticky(sig, jnp.int64(52 - 26))
    # subnormal squeeze for f32
    squeeze = jnp.maximum(jnp.int64(1) - e32, jnp.int64(0))
    sig27 = jnp.where(squeeze > 0, _shift_right_sticky(sig27, squeeze),
                      sig27)
    e32 = jnp.where(squeeze > 0, jnp.int64(1), e32)
    lsb = (sig27 >> _c(3)) & _c(1)
    guard = (sig27 >> _c(2)) & _c(1)
    rest = sig27 & _c(3)
    round_up = (guard == _c(1)) & ((rest != _c(0)) | (lsb == _c(1)))
    sig24 = (sig27 >> _c(3)) + jnp.where(round_up, _c(1), _c(0))
    carried = sig24 >= _c(1 << 24)
    sig24 = jnp.where(carried, sig24 >> _c(1), sig24)
    e32 = jnp.where(carried, e32 + 1, e32)
    subn = sig24 < _c(1 << 23)
    exp_field = jnp.where(subn | (sig24 == _c(0)), jnp.int64(0), e32)
    overflow = e32 > 254
    u32 = ((_u(exp_field) & _c(0xFF)) << _c(23)) | (sig24 & _c(0x7FFFFF))
    u32 = jnp.where(overflow, _c(0x7F800000), u32)
    u32 = jnp.where(is_zero(bits), _c(0), u32)
    u32 = jnp.where(is_inf(bits), _c(0x7F800000), u32)
    u32 = jnp.where(is_nan(bits), _c(0x7FC00000), u32)
    u32 = u32 | jnp.where(n & ~is_nan(bits), _c(0x80000000), _c(0))
    return jax.lax.bitcast_convert_type(u32.astype(jnp.uint32), jnp.float32)


# ---------------------------------------------------------------------------
# integer-valued rounding
# ---------------------------------------------------------------------------

def trunc(bits):
    """Round toward zero to an integer-valued double."""
    n, exp, mant = _unpack(bits)
    e = exp - 1023                      # unbiased
    frac_bits = jnp.clip(jnp.int64(52) - e, 0, 63)
    mask = (_c(1) << _u(frac_bits)) - _c(1)
    new_mant = mant & ~mask
    out = _pack(n, exp, new_mant)
    out = jnp.where(e < 0, _i(jnp.where(n, _c(SIGN), _c(0))), out)
    out = jnp.where(e >= 52, _i(_u(bits)), out)
    out = jnp.where(~is_finite(bits), _i(_u(bits)), out)
    return out


def floor(bits):
    t = trunc(bits)
    went_up = is_negative(bits) & (order_word(t) != order_word(bits)) \
        & is_finite(bits)
    return jnp.where(went_up, sub(t, bits_const(1.0)), t)


def ceil(bits):
    t = trunc(bits)
    went_down = ~is_negative(bits) & (order_word(t) != order_word(bits)) \
        & is_finite(bits)
    return jnp.where(went_down, add(t, bits_const(1.0)), t)


def rint(bits):
    """Round half to even to an integer-valued double (Java Math.rint).

    Symmetric: computed on |x|, sign re-applied (preserves -0.0 results).
    """
    n, exp, mant = _unpack(bits)
    e = exp - 1023
    m = abs_(bits)
    down = trunc(m)                       # == floor for non-negative
    up = add(down, bits_const(1.0))
    # fractional part comparison against one half, in integer form
    sig, _ = _significand(exp, mant)
    frac_bits = jnp.clip(jnp.int64(52) - e, 0, 63)
    mask = (_c(1) << _u(frac_bits)) - _c(1)
    frac = sig & mask
    half = _c(1) << _u(jnp.maximum(frac_bits - 1, jnp.int64(0)))
    below = frac < half
    above = frac > half
    down_even = (to_i64(down) & jnp.int64(1)) == 0
    pick_down = below | (~above & down_even)
    out = jnp.where(pick_down, down, up)
    # e in [0, 52): general path above. e >= 52: already integer.
    out = jnp.where(e >= 52, m, out)
    # e == -1: |x| in [0.5, 1): tie at exactly 0.5 -> 0, else 1
    out = jnp.where(e == -1,
                    jnp.where(mant != _c(0), bits_const(1.0), jnp.int64(0)),
                    out)
    out = jnp.where(e < -1, jnp.int64(0), out)          # |x| < 0.5 -> 0
    out = jnp.where(is_zero(bits) | ~is_finite(bits), m, out)
    signed = jnp.where(n, neg(out), out)
    return jnp.where(is_nan(bits), jnp.int64(QNAN), signed)


# ---------------------------------------------------------------------------
# host-callback escape hatch for the transcendental tail
# ---------------------------------------------------------------------------

def host_unary(np_fn, bits):
    """Evaluate a numpy double fn exactly on the host (eager transfer).

    Used for the transcendental tail (exp/log/sin/...): numpy's libm IS the
    CPU oracle's implementation, so results are bit-identical to the CPU
    engine while the hot arithmetic path stays on-device.  The reference
    similarly gates incompatible float ops (docs/compatibility.md).
    Expression evaluation in this engine is eager (only kernels are jitted),
    so this is a plain device->host->device round-trip rather than a
    host callback inside a program.
    """
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="binary64_host_libm"):
        arr = np.asarray(_i(bits)).view(np.float64)
    with np.errstate(all="ignore"):
        out = np.asarray(np_fn(arr), dtype=np.float64)
    return jnp.asarray(out.view(np.int64))


def host_binary(np_fn, a_bits, b_bits):
    from ..analysis import residency  # lazy: avoids import cycle
    with residency.declared_transfer(site="binary64_host_libm"):
        a = np.asarray(_i(a_bits)).view(np.float64)
        b = np.asarray(_i(b_bits)).view(np.float64)
    with np.errstate(all="ignore"):
        out = np.asarray(np_fn(a, b), dtype=np.float64)
    return jnp.asarray(out.view(np.int64))


# ---------------------------------------------------------------------------
# segmented / scan reductions
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# segmented sum: windowed integer superaccumulator
# ---------------------------------------------------------------------------
# Summing doubles exactly does NOT need a per-element softfloat adder: a
# double is sig * 2^(e-1075) with a 53-bit integer sig, so a segment's sum
# is an INTEGER sum in fixed point.  Each segment anchors a 256-bit window
# at its max exponent; every element decomposes into <=3 signed 32-bit limb
# contributions (pure shifts/masks), limbs accumulate with per-limb integer
# prefix sums over the sorted segment order (cumsum is native on the VPU;
# no 64-bit scatters, no associative_scan with a custom combiner — both
# are catastrophically slow/slow-to-compile on this backend), and ONE
# softfloat round-to-nearest-even runs per GROUP at the end.
#
# Accuracy: terms more than W0 bits below the segment max exponent fold
# into the sticky bit.  With NL=8 limbs W0 >= 256-53-log2(n)-2 (capped
# 191), so the result is the correctly-rounded exact sum unless the
# segment both spans >W0 bits of exponent range AND cancels its top ~100
# bits — far beyond f64 summation error in any order, which is the
# reference's own contract (integration tests compare with ulp tolerance).

_SUM_NL = 8          # 256-bit window


def _sum_w0(n: int) -> int:
    # max left-shift position: leave headroom for log2(n) carries above
    # the top term bit and keep limb index j = W0>>5 <= 5 (c2 lands at 7)
    return min(191, _SUM_NL * 32 - 53 - max(n, 2).bit_length() - 2)


def _derive_bounds(seg_id, contrib_mask):
    """Group boundary positions from sorted segment ids (fallback when no
    GroupPlan is available: tests / standalone use)."""
    n = seg_id.shape[0]
    if n > 1:
        head = jnp.concatenate([jnp.ones(1, bool),
                                seg_id[1:] != seg_id[:-1]])
    else:
        head = jnp.ones(1, bool)
    from .basic import filter_compact_indices
    head_pos, num_groups = filter_compact_indices(head, n)
    gi = jnp.arange(n, dtype=jnp.int32)
    nxt = jnp.concatenate([head_pos[1:].astype(jnp.int32),
                           jnp.zeros(1, jnp.int32)])
    last_pos = jnp.where(gi + 1 < num_groups, nxt - 1, jnp.int32(n - 1))
    return head_pos.astype(jnp.int32), last_pos, num_groups


def segmented_sum(sorted_bits, contrib_mask, seg_id, num_segments: int,
                  head_pos=None, last_pos=None, num_groups=None):
    """Exact binary64 sum per segment over sorted segment ids.

    ``head_pos``/``last_pos``/``num_groups`` are the GroupPlan boundary
    arrays (kernels/aggregate.groupby_plan); when omitted they are
    derived from ``seg_id`` (one extra argsort).
    """
    n = sorted_bits.shape[0]
    if head_pos is None:
        head_pos, last_pos, num_groups = _derive_bounds(seg_id,
                                                        contrib_mask)
    W0 = _sum_w0(n)
    u = _u(sorted_bits)
    exp_raw = ((u >> _c(52)) & _c(0x7FF)).astype(jnp.int32)
    mant = u & _c(MANT_MASK)
    sig = jnp.where(exp_raw > 0, mant | _c(IMPLICIT), mant)
    e = jnp.maximum(exp_raw, 1)
    negs = (u & _c(SIGN)) != _c(0)
    mag = u & _c(MAG_MASK)
    ok = contrib_mask
    nan_f = ok & (mag > _c(INF))
    pinf_f = ok & (u == _c(INF))
    ninf_f = ok & (u == _c(SIGN | INF))
    fin_ok = ok & (exp_raw != jnp.int32(2047))

    hp = jnp.clip(head_pos, 0, n - 1)
    lp = jnp.clip(last_pos, 0, n - 1)
    gi = jnp.arange(n, dtype=jnp.int32)
    glive = gi < num_groups

    def group_total(contrib):
        cum = jnp.cumsum(contrib)
        ex = cum - contrib
        total = jnp.take(cum, lp) - jnp.take(ex, hp)
        return jnp.where(glive, total, jnp.zeros_like(total))

    # group max exponent (i32 scatter-max: 32-bit scatters are native)
    emax_g = jax.ops.segment_max(jnp.where(fin_ok, e, jnp.int32(0)),
                                 seg_id, num_segments=n)
    d = jnp.take(emax_g, seg_id) - e
    p = jnp.int32(W0) - d
    # contributions entirely below the window fold into sticky
    keep = fin_ok & (p > jnp.int32(-53))
    rs = jnp.clip(-p, 0, 63).astype(jnp.uint64)
    sig2 = sig >> rs
    lost_low = fin_ok & ((sig2 << rs) != sig)
    dropped = fin_ok & (p <= jnp.int32(-53)) & (sig != _c(0))
    pc = jnp.clip(p, 0, W0)
    r = (pc & jnp.int32(31)).astype(jnp.uint64)
    j = pc >> jnp.int32(5)
    lo = sig2 << r
    hi = (sig2 >> (_c(63) - r)) >> _c(1)
    sgn = jnp.where(negs, jnp.int64(-1), jnp.int64(1))
    zero64 = jnp.int64(0)
    c0 = jnp.where(keep, (lo & _c(0xFFFFFFFF)).astype(jnp.int64) * sgn,
                   zero64)
    c1 = jnp.where(keep, (lo >> _c(32)).astype(jnp.int64) * sgn, zero64)
    c2 = jnp.where(keep, hi.astype(jnp.int64) * sgn, zero64)

    # per-limb group totals (each limb sum |.| <= n * 2^32 < 2^62: exact)
    limbs = []
    for L in range(_SUM_NL):
        lc = jnp.where(j == L, c0, zero64)
        if L >= 1:
            lc = lc + jnp.where(j == L - 1, c1, zero64)
        if L >= 2:
            lc = lc + jnp.where(j == L - 2, c2, zero64)
        limbs.append(group_total(lc))
    sticky_grp = group_total((lost_low | dropped).astype(jnp.int32)) > 0
    nan_cnt = group_total(nan_f.astype(jnp.int32))
    pinf_cnt = group_total(pinf_f.astype(jnp.int32))
    ninf_cnt = group_total(ninf_f.astype(jnp.int32))

    # ---- per-group finalize (all arrays are group-indexed, length n) ----
    m32 = jnp.int64(0xFFFFFFFF)
    carry = jnp.int64(0)
    lo32s = []
    for L in range(_SUM_NL):
        s = limbs[L] + carry
        lo32 = s & m32
        carry = (s - lo32) >> jnp.int64(32)
        lo32s.append(lo32)
    total_neg = carry < 0
    # magnitude limbs: conditional two's complement
    mags = []
    c = jnp.where(total_neg, jnp.int64(1), jnp.int64(0))
    for L in range(_SUM_NL):
        t = jnp.where(total_neg, (~lo32s[L]) & m32, lo32s[L]) + c
        mags.append((t & m32).astype(jnp.uint64))
        c = jnp.where(total_neg, t >> jnp.int64(32), jnp.int64(0))
    # combine to 4 u64 words, find top nonzero word
    words = [(mags[2 * i + 1] << _c(32)) | mags[2 * i] for i in range(4)]
    nzs = [w != _c(0) for w in words]
    top = jnp.zeros(n, jnp.int32)
    any_nz = jnp.zeros(n, bool)
    for i in range(4):
        top = jnp.where(nzs[i], jnp.int32(i), top)
        any_nz = any_nz | nzs[i]

    def pick(idx):
        out = jnp.zeros(n, jnp.uint64)
        for i in range(4):
            out = jnp.where(idx == i, words[i], out)
        return out
    hiw = pick(top)
    loww = pick(top - 1)                      # top == 0 -> stays zero
    lz = _clz64(hiw)                          # 0..63 when any_nz
    lzu = _u(jnp.clip(lz, 0, 63))
    combined = (hiw << lzu) | ((loww >> (_c(63) - lzu)) >> _c(1))
    dropped_low = (loww << lzu) != _c(0)
    lower_nz = jnp.zeros(n, bool)
    for i in range(4):
        lower_nz = lower_nz | (nzs[i] & (jnp.int32(i) < top - 1))
    sticky = dropped_low | lower_nz | sticky_grp | \
        ((combined & _c(0xFF)) != _c(0))
    sig57 = (combined >> _c(8)) | jnp.where(sticky, _c(1), _c(0))
    b_msb = jnp.int64(64) * top.astype(jnp.int64) + 63 - lz
    e_out = b_msb + emax_g.astype(jnp.int64) - jnp.int64(W0 + 52)
    out = _round_pack(total_neg, e_out, sig57)
    out = jnp.where(any_nz, out, jnp.int64(0))
    # specials: any NaN, or +inf and -inf together -> NaN; else inf wins
    out = jnp.where(pinf_cnt > 0, jnp.int64(INF), out)
    out = jnp.where(ninf_cnt > 0, jnp.int64((SIGN | INF) - 2 ** 64), out)
    out = jnp.where(
        (nan_cnt > 0) | ((pinf_cnt > 0) & (ninf_cnt > 0)),
        jnp.int64(QNAN), out)
    out = jnp.where(glive, out, jnp.int64(0))
    if n >= num_segments:
        return out[:num_segments]
    return jnp.pad(out, (0, num_segments - n))


def running_sum(bits, contrib_mask, seg_head):
    """Inclusive segmented running sum (window frames): bits per row."""
    zero = jnp.zeros_like(bits)
    vals = jnp.where(contrib_mask, bits, zero)

    def combine(left, right):
        lv, lf = left
        rv, rf = right
        v = jnp.where(rf, rv, add(lv, rv))
        return v, lf | rf

    scanned, _ = jax.lax.associative_scan(combine, (vals, seg_head))
    return scanned
