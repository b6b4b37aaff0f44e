"""Emulated 128-bit integer arithmetic on int64 lanes.

Reference parity: spi/type/Int128Math.java — the reference's decimal engine
computes rescales, multiplications and divisions in 128-bit two-limb
arithmetic so decimal(38) intermediates never overflow.  TPUs have no
native int128, so the limbs are uint64 jax arrays: products split into
32-bit halves (four partial products), and 128/64 division runs the
classic shift-subtract loop (128 fixed iterations — a static-shape
`lax.fori_loop` the compiler unrolls onto the VPU; ~128 cheap ops/lane).

Values stay *stored* as scaled int64 (decimal ≤ 18 digits); these kernels
protect the transient wide intermediates (e.g. Q14's
`100.00 * sum(..) / sum(..)`, whose numerator rescale exceeds 2^63).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# python ints, NOT jnp scalars: a module-level jnp constant is a device
# array created at import (it initializes the backend before the caller
# has chosen one) and is captured as a hidden const ARGUMENT of every
# jitted program using it; plain ints fold into HLO literals
_MASK32 = 0xFFFFFFFF
_U1 = 1


def umul128(a: jnp.ndarray, b: jnp.ndarray):
    """Unsigned 64x64 -> 128-bit product as (hi, lo) uint64 limbs."""
    a = a.astype(jnp.uint64)
    b = b.astype(jnp.uint64)
    a0, a1 = a & _MASK32, a >> jnp.uint64(32)
    b0, b1 = b & _MASK32, b >> jnp.uint64(32)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> jnp.uint64(32)) + (p01 & _MASK32) + (p10 & _MASK32)
    lo = (p00 & _MASK32) | ((mid & _MASK32) << jnp.uint64(32))
    hi = (
        p11
        + (p01 >> jnp.uint64(32))
        + (p10 >> jnp.uint64(32))
        + (mid >> jnp.uint64(32))
    )
    return hi, lo


def udiv128_64(hi: jnp.ndarray, lo: jnp.ndarray, d: jnp.ndarray):
    """(hi:lo) / d -> (quotient low 64 bits, remainder).

    Requires d >= 1 and d < 2^63 (scaled-decimal divisors always are).
    Quotients that exceed 64 bits return their low limb — callers bound
    result precision so exact results always fit."""
    d = d.astype(jnp.uint64)

    def body(i, st):
        rem, q = st
        bit_index = jnp.uint64(127) - jnp.uint64(i)
        word = jnp.where(bit_index >= jnp.uint64(64), hi, lo)
        sh = jnp.where(
            bit_index >= jnp.uint64(64),
            bit_index - jnp.uint64(64),
            bit_index,
        )
        bit = (word >> sh) & _U1
        rem = (rem << _U1) | bit
        ge = rem >= d
        rem = jnp.where(ge, rem - d, rem)
        q = (q << _U1) | ge.astype(jnp.uint64)
        return rem, q

    rem0 = jnp.zeros_like(d)
    q0 = jnp.zeros_like(d)
    rem, q = jax.lax.fori_loop(0, 128, body, (rem0, q0))
    return q, rem


def udiv128_128(hi, lo, dhi_c: int, dlo_c: int):
    """(hi:lo) / compile-time-constant 128-bit divisor -> 64-bit quotient
    + 128-bit remainder.  Used for /10^k with k up to 38 (10^38 < 2^127).
    Restoring division over two limbs; quotients are bounded by callers'
    precision rules to fit one limb."""
    dhi = jnp.uint64(dhi_c)
    dlo = jnp.uint64(dlo_c)

    def body(i, st):
        rhi, rlo, q = st
        bit_index = jnp.uint64(127) - jnp.uint64(i)
        word = jnp.where(bit_index >= jnp.uint64(64), hi, lo)
        sh = jnp.where(
            bit_index >= jnp.uint64(64),
            bit_index - jnp.uint64(64),
            bit_index,
        )
        bit = (word >> sh) & _U1
        # rem = rem << 1 | bit  (128-bit)
        rhi = (rhi << _U1) | (rlo >> jnp.uint64(63))
        rlo = (rlo << _U1) | bit
        ge = (rhi > dhi) | ((rhi == dhi) & (rlo >= dlo))
        borrow = (rlo < dlo).astype(jnp.uint64)
        rhi = jnp.where(ge, rhi - dhi - borrow, rhi)
        rlo = jnp.where(ge, rlo - dlo, rlo)
        q = (q << _U1) | ge.astype(jnp.uint64)
        return rhi, rlo, q

    z = jnp.zeros_like(lo)
    rhi, rlo, q = jax.lax.fori_loop(0, 128, body, (z, z, z))
    return q, rhi, rlo


def _div_const_round(hi, lo, const: int):
    """(hi:lo) / const with round-half-away, const any positive int
    < 2^127 known at trace time; returns uint64 quotient."""
    if const < (1 << 62):
        d = jnp.full_like(lo, const)
        q, rem = udiv128_64(hi, lo, d)
        return q + (jnp.uint64(2) * rem >= d).astype(jnp.uint64)
    q, rhi, rlo = udiv128_128(lo=lo, hi=hi, dhi_c=const >> 64,
                              dlo_c=const & ((1 << 64) - 1))
    # round half away: 2*rem >= const, in 128-bit
    r2hi = (rhi << _U1) | (rlo >> jnp.uint64(63))
    r2lo = rlo << _U1
    dhi = jnp.uint64(const >> 64)
    dlo = jnp.uint64(const & ((1 << 64) - 1))
    up = (r2hi > dhi) | ((r2hi == dhi) & (r2lo >= dlo))
    return q + up.astype(jnp.uint64)


def mul_shift_div_round(
    l: jnp.ndarray, mul: int, den: jnp.ndarray
) -> jnp.ndarray:
    """round_half_away((l * mul) / den) for signed int64 lanes with a
    128-bit intermediate product (DecimalOperators.divide* analog).
    `mul` is a trace-time power of ten; `den` a scaled int64 lane."""
    sign = jnp.sign(l) * jnp.sign(den)
    al = jnp.abs(l).astype(jnp.uint64)
    ad = jnp.abs(jnp.where(den == 0, 1, den)).astype(jnp.uint64)
    if mul < (1 << 64):
        hi, lo = umul128(al, jnp.uint64(mul))
    else:
        # l * 10^k with 10^k >= 2^64: split the constant into
        # c = c1 * 2^64 + c0; hi limb gains al*c1 (low limb of it)
        c1, c0 = mul >> 64, mul & ((1 << 64) - 1)
        hi, lo = umul128(al, jnp.uint64(c0))
        hi = hi + al * jnp.uint64(c1)
    q, rem = udiv128_64(hi, lo, ad)
    q = q + (jnp.uint64(2) * rem >= ad).astype(jnp.uint64)
    return sign * q.astype(jnp.int64)


def mul_rescale_round(
    l: jnp.ndarray, r: jnp.ndarray, down: int
) -> jnp.ndarray:
    """round_half_away((l * r) / 10^down) with a 128-bit product
    (DecimalOperators.multiply + Decimals.rescale fused)."""
    sign = jnp.sign(l) * jnp.sign(r)
    hi, lo = umul128(jnp.abs(l).astype(jnp.uint64), jnp.abs(r).astype(jnp.uint64))
    if down <= 0:
        return sign * lo.astype(jnp.int64)
    q = _div_const_round(hi, lo, 10**down)
    return sign * q.astype(jnp.int64)
