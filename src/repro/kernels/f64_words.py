"""Exact float64 predicates over uint32 word pairs.

A TPU has no IEEE float64: XLA emulates it with a pair of float32s, which
keeps about 48 of the 53 mantissa bits and float32's exponent range.  The
device path therefore never does float64 arithmetic.  Timestamps travel
as (hi, lo) uint32 words of their bits or of their order-preserving sort
key (``exec.refine.f64_sort_key``), and the two float64 predicates the
query path needs are computed here with integer word arithmetic that
gives the same answer as numpy's float64 on every input:

* :func:`key_from_bits` / :func:`bits_from_key` — the sort-key map and
  its inverse, so ``a <= b`` becomes a lexicographic word compare;
* :func:`span_at_least` — ``fl(b − a) >= d`` for the dwell predicate,
  a round-to-nearest-even subtraction in the manner of a soft-float
  library (three guard bits and a sticky bit).

Every function is plain ``jnp`` on uint32 arrays; none needs x64.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["key_from_bits", "bits_from_key", "is_nan", "less_equal",
           "span_at_least"]

_U = jnp.uint32
_SIGN = 0x80000000
_MANT_HI = 0x000FFFFF          # mantissa bits in the high word
_EXP_INF = 0x7FF


def _shl(hi, lo, s):
    """(hi, lo) << s for per-element shifts 0 <= s < 64."""
    big = s >= 32
    t = jnp.where(big, s - 32, s)
    spill = jnp.where(t == 0, _U(0), lo >> ((32 - t) & 31))
    return (jnp.where(big, lo << t, (hi << t) | spill),
            jnp.where(big, _U(0), lo << t))


def _shr(hi, lo, s):
    """(hi, lo) >> s (logical) for per-element shifts 0 <= s < 64."""
    big = s >= 32
    t = jnp.where(big, s - 32, s)
    spill = jnp.where(t == 0, _U(0), hi << ((32 - t) & 31))
    return (jnp.where(big, _U(0), hi >> t),
            jnp.where(big, hi >> t, (lo >> t) | spill))


def _add(ahi, alo, bhi, blo):
    lo = alo + blo
    return ahi + bhi + (lo < alo).astype(_U), lo


def _sub(ahi, alo, bhi, blo):
    return ahi - bhi - (alo < blo).astype(_U), alo - blo


def _ge(ahi, alo, bhi, blo):
    return (ahi > bhi) | ((ahi == bhi) & (alo >= blo))


def less_equal(ahi, alo, bhi, blo):
    """a <= b over (hi, lo) word pairs (64-bit lexicographic)."""
    return (ahi < bhi) | ((ahi == bhi) & (alo <= blo))


def key_from_bits(hi, lo):
    """float64 bits → sort-key words, −0.0 first normalized to +0.0 (the
    map ``exec.refine.f64_sort_key`` applies on the host)."""
    neg_zero = (hi == _U(_SIGN)) & (lo == 0)
    hi = jnp.where(neg_zero, _U(0), hi)
    neg = (hi >> 31) != 0
    return (jnp.where(neg, ~hi, hi | _U(_SIGN)), jnp.where(neg, ~lo, lo))


def is_nan(hi, lo):
    """NaN test on float64 bits: exponent all ones, mantissa non-zero."""
    mag = hi & _U(~_SIGN & 0xFFFFFFFF)
    return (mag > _U(_EXP_INF << 20)) | ((mag == _U(_EXP_INF << 20)) & (lo != 0))


def bits_from_key(hi, lo):
    """Inverse of :func:`key_from_bits` (up to the zero's sign)."""
    pos = (hi >> 31) != 0
    return (jnp.where(pos, hi & _U(~_SIGN & 0xFFFFFFFF), ~hi),
            jnp.where(pos, lo, ~lo))


def _unpack(hi, lo):
    """Non-negative finite float64 bits → (mantissa words, exponent) with
    the implicit bit made explicit; subnormals get exponent 1."""
    e = hi >> 20
    m_hi = (hi & _U(_MANT_HI)) | jnp.where(e > 0, _U(1 << 20), _U(0))
    return m_hi, lo, jnp.maximum(e, _U(1))


def _add_or_sub(xhi, xlo, yhi, ylo, subtract):
    """fl(x ± y) for non-negative finite float64 bits with x >= y,
    rounded to nearest even; returns the result's bits (+inf on
    overflow)."""
    mxh, mxl, ex = _unpack(xhi, xlo)
    myh, myl, ey = _unpack(yhi, ylo)
    # three guard bits below the 53-bit mantissa
    mxh, mxl = _shl(mxh, mxl, _U(3))
    myh, myl = _shl(myh, myl, _U(3))
    shift = jnp.minimum(ex - ey, _U(63))
    ah, al = _shr(myh, myl, shift)
    bh, bl = _shl(ah, al, shift)
    sticky = ((bh != myh) | (bl != myl)).astype(_U)
    al = al | sticky
    sh, sl = _sub(mxh, mxl, ah, al)
    th, tl = _add(mxh, mxl, ah, al)
    zh = jnp.where(subtract, sh, th)
    zl = jnp.where(subtract, sl, tl)
    e = ex
    # a carry out of the addition: one right shift, keeping the sticky bit
    carry = zh >= _U(1 << 24)
    ch, cl = _shr(zh, zl, _U(1))
    zh = jnp.where(carry, ch, zh)
    zl = jnp.where(carry, cl | (zl & 1), zl)
    e = e + carry.astype(_U)
    # cancellation: shift left until the leading bit sits at bit 55, but
    # never below exponent 1 (the result is then subnormal)
    lz = jnp.where(zh != 0, jax.lax.clz(zh), 32 + jax.lax.clz(zl))
    norm = jnp.minimum(lz - jnp.minimum(lz, _U(8)), e - 1)
    zh, zl = _shl(zh, zl, norm)
    e = e - norm
    # round to nearest, ties to even, on the three guard bits
    g = zl & 7
    zh, zl = _shr(zh, zl, _U(3))
    up = (g > 4) | ((g == 4) & ((zl & 1) != 0))
    zh, zl = _add(zh, zl, _U(0), up.astype(_U))
    over = zh >= _U(1 << 21)
    oh, ol = _shr(zh, zl, _U(1))
    zh = jnp.where(over, oh, zh)
    zl = jnp.where(over, ol, zl)
    e = e + over.astype(_U)
    e_field = jnp.where(zh >= _U(1 << 20), e, _U(0))
    zero = (zh == 0) & (zl == 0)
    rh = jnp.where(zero, _U(0), (e_field << 20) | (zh & _U(_MANT_HI)))
    rl = jnp.where(zero, _U(0), zl)
    inf = e >= _U(_EXP_INF)
    return jnp.where(inf, _U(_EXP_INF << 20), rh), jnp.where(inf, _U(0), rl)


def span_at_least(a_hi, a_lo, b_hi, b_lo, d: float):
    """``fl(b − a) >= d`` for float64 ``a <= b`` given as sort-key words
    (neither NaN), exactly as numpy's float64 computes it."""
    d = float(d)
    ah, al = bits_from_key(a_hi, a_lo)
    bh, bl = bits_from_key(b_hi, b_lo)
    a_neg = (ah >> 31) != 0
    b_neg = (bh >> 31) != 0
    mag = _U(~_SIGN & 0xFFFFFFFF)
    ah, bh = ah & mag, bh & mag
    a_inf = ah >= _U(_EXP_INF << 20)
    b_inf = bh >= _U(_EXP_INF << 20)
    # inf − inf (both the same infinity) is NaN: never >= d
    nan = a_inf & b_inf & (a_neg == b_neg)
    if np.isnan(d):
        return jnp.zeros(a_hi.shape, bool)
    if d <= 0.0:
        return ~nan
    # same signs: the magnitudes subtract, else they add; either way the
    # larger magnitude leads
    subtract = a_neg == b_neg
    swap = ~_ge(bh, bl, ah, al)
    xh = jnp.where(swap, ah, bh)
    xl = jnp.where(swap, al, bl)
    yh = jnp.where(swap, bh, ah)
    yl = jnp.where(swap, bl, al)
    rh, rl = _add_or_sub(xh, xl, yh, yl, subtract)
    inf = (a_inf | b_inf) & ~nan
    d_bits = int(np.float64(d).view(np.uint64))
    ok = _ge(rh, rl, _U(d_bits >> 32), _U(d_bits & 0xFFFFFFFF))
    return ~nan & (inf | ok)
