"""Wide (two-limb) decimal storage and aggregation: decimal(19..38).

Reference parity: spi/type/Int128.java, Int128Math.java and
block/Int128ArrayBlock.java:28 — the reference stores long decimals as
two-limb 128-bit values and aggregates them with Int128Math add/divide.

TPU-first redesign:
  - A wide decimal *lane* is one int64 array of shape (n, 2):
    [:, 0] the low limb (bit pattern, unsigned semantics) and [:, 1] the
    high limb (signed).  A single array (not a companion symbol) rides
    through every generic gather/permute untouched, keeps plan symbol
    lists one-to-one with lanes, and stays a legal single jax value in
    jitted fragment signatures.
  - SUM accumulator state is four *32-bit chunk sums* stored in int64
    lanes (`$c0..$c3`, little-endian chunks, top chunk signed).  A
    segment-sum of 32-bit chunks cannot overflow int64 below 2^31 rows,
    so accumulation is two (narrow input) or four (wide input) ordinary
    segment_sums — no carry logic inside the hot loop.  Carries are
    propagated once per *capacity* (`normalize_chunks`), and chunk sums
    are mergeable by plain addition, which makes the cross-device merge
    a psum per chunk lane (ICI-friendly) instead of a custom collective.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import int128

# python ints, NOT jnp scalars: module-level jnp constants become hidden
# const ARGUMENTS of every jitted program that touches them (visible as
# %arg0 tensor<i64> in the lowered HLO); plain ints fold into literals
_M32 = 0xFFFFFFFF
_SIGN64 = -0x8000000000000000  # 1 << 63 as the int64 bit pattern

WIDE_DIGITS = 18  # precision above this needs two limbs


def is_wide_type(t) -> bool:
    return (
        t is not None
        and getattr(t, "is_decimal", False)
        and t.precision > WIDE_DIGITS
    )


def is_wide(v: jnp.ndarray) -> bool:
    """Is this lane value array a wide (two-limb) decimal?"""
    return v.ndim == 2


def widen(v: jnp.ndarray) -> jnp.ndarray:
    """Promote a narrow int64 lane to wide: hi = sign extension."""
    v = v.astype(jnp.int64)
    return jnp.stack([v, v >> jnp.int64(63)], axis=-1)


def make_wide(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([lo.astype(jnp.int64), hi.astype(jnp.int64)], axis=-1)


def limbs(w: jnp.ndarray):
    """(lo, hi) int64 views of a wide lane."""
    return w[..., 0], w[..., 1]


def narrow(w: jnp.ndarray) -> jnp.ndarray:
    """Low limb (callers must know the value fits 64 bits)."""
    return w[..., 0]


def fits_narrow(w: jnp.ndarray) -> jnp.ndarray:
    """Per-row: does the 128-bit value fit a signed int64?"""
    lo, hi = limbs(w)
    return hi == (lo >> jnp.int64(63))


# -- ordering ----------------------------------------------------------
def order_operands(w: jnp.ndarray, descending: bool = False):
    """Two int64 sort operands (major, minor) whose joint lexicographic
    order equals signed 128-bit order.  The low limb is unsigned, so its
    sign bit is flipped into signed order; DESC complements both."""
    lo, hi = limbs(w)
    lo_s = lo ^ _SIGN64
    if descending:
        return ~hi, ~lo_s
    return hi, lo_s


def order_approx64(w: jnp.ndarray) -> jnp.ndarray:
    """Monotone int64 approximation of 128-bit order: EXACT (= the low
    limb) for values that fit int64, sign-saturated for genuinely wide
    values.  Distinct wide values may collapse to the saturation ties,
    never reorder; TopN phase 1 counts encoded ties, so collapses are
    exactness-safe.  (The previous floor(v/2^32) form collapsed every
    ordinary-magnitude decimal sum — e.g. all of TPC-H Q3's revenues —
    into one tie, forcing the TopN ladder through 3 recompiles into a
    full sort.)"""
    lo, hi = limbs(w)
    sat = jnp.where(
        hi < 0, jnp.int64(-(2**63)), jnp.int64(2**63 - 1)
    )
    return jnp.where(fits_narrow(w), lo, sat)


def compare(a: jnp.ndarray, b: jnp.ndarray, op: str) -> jnp.ndarray:
    """Elementwise signed 128-bit comparison of two wide lanes."""
    alo, ahi = limbs(a)
    blo, bhi = limbs(b)
    alo_u = alo ^ _SIGN64  # unsigned order in the signed domain
    blo_u = blo ^ _SIGN64
    lt = (ahi < bhi) | ((ahi == bhi) & (alo_u < blo_u))
    eq = (ahi == bhi) & (alo == blo)
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return ~(lt | eq)
    if op == ">=":
        return ~lt
    if op == "==":
        return eq
    if op == "!=":
        return ~eq
    raise ValueError(op)


# -- arithmetic --------------------------------------------------------
def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """128-bit wraparound addition of two wide lanes."""
    alo, ahi = limbs(a)
    blo, bhi = limbs(b)
    lo = (alo.astype(jnp.uint64) + blo.astype(jnp.uint64))
    carry = (lo < alo.astype(jnp.uint64)).astype(jnp.int64)
    return make_wide(lo.astype(jnp.int64), ahi + bhi + carry)


def negate(a: jnp.ndarray) -> jnp.ndarray:
    lo, hi = limbs(a)
    nlo = (~lo).astype(jnp.uint64) + jnp.uint64(1)
    carry = (nlo == 0).astype(jnp.int64)
    return make_wide(nlo.astype(jnp.int64), ~hi + carry)


def subtract(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return add(a, negate(b))


def abs128(a: jnp.ndarray):
    """(|a| as wide, sign) — sign is -1/+1 int64."""
    lo, hi = limbs(a)
    neg = hi < 0
    mag = jnp.where(neg[..., None], negate(a), a)
    return mag, jnp.where(neg, jnp.int64(-1), jnp.int64(1))


def rescale(w: jnp.ndarray, up: int) -> jnp.ndarray:
    """w * 10^up (up >= 0) in 128-bit wraparound arithmetic; callers
    bound the result to < 2^127 via precision rules."""
    if up == 0:
        return w
    mag, sign = abs128(w)
    lo, hi = limbs(mag)
    c = 10**up
    if c >= 1 << 63:
        raise NotImplementedError("rescale beyond 10^18 in one step")
    hi_p, lo_p = int128.umul128(lo.astype(jnp.uint64), jnp.uint64(c))
    hi_p = hi_p + hi.astype(jnp.uint64) * jnp.uint64(c)
    out = make_wide(lo_p.astype(jnp.int64), hi_p.astype(jnp.int64))
    return jnp.where((sign < 0)[..., None], negate(out), out)


def div_round(w: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """round_half_away(w / d) for a wide lane over positive int64
    divisors d (per-element); returns a wide lane with a FULL 128-bit
    quotient (restoring division, 128 fixed iterations)."""
    mag, sign = abs128(w)
    lo64, hi64 = limbs(mag)
    hi = hi64.astype(jnp.uint64)
    lo = lo64.astype(jnp.uint64)
    dd = jnp.maximum(d, 1).astype(jnp.uint64)
    one = jnp.uint64(1)

    def body(i, st):
        rem, qhi, qlo = st
        bit_index = jnp.uint64(127) - jnp.uint64(i)
        word = jnp.where(bit_index >= jnp.uint64(64), hi, lo)
        sh = jnp.where(
            bit_index >= jnp.uint64(64),
            bit_index - jnp.uint64(64),
            bit_index,
        )
        bit = (word >> sh) & one
        rem = (rem << one) | bit
        ge = rem >= dd
        rem = jnp.where(ge, rem - dd, rem)
        qhi = (qhi << one) | (qlo >> jnp.uint64(63))
        qlo = (qlo << one) | ge.astype(jnp.uint64)
        return rem, qhi, qlo

    z = jnp.zeros_like(dd)
    rem, qhi, qlo = jax.lax.fori_loop(0, 128, body, (z, z, z))
    up = (jnp.uint64(2) * rem >= dd).astype(jnp.uint64)
    qlo2 = qlo + up
    qhi = qhi + (qlo2 < qlo).astype(jnp.uint64)
    out = make_wide(qlo2.astype(jnp.int64), qhi.astype(jnp.int64))
    return jnp.where((sign < 0)[..., None], negate(out), out)


def _udiv128_const_wide(hi: jnp.ndarray, lo: jnp.ndarray, const: int):
    """Unsigned (hi:lo) / trace-time const -> 128-bit quotient (qhi, qlo)
    + 64-bit remainder-ish (rem fits one limb for const < 2^63).
    Restoring division, 128 fixed iterations (Int128Math.divide role)."""
    dhi = jnp.uint64(const >> 64)
    dlo = jnp.uint64(const & ((1 << 64) - 1))
    one = jnp.uint64(1)

    def body(i, st):
        rhi, rlo, qhi, qlo = st
        bit_index = jnp.uint64(127) - jnp.uint64(i)
        word = jnp.where(bit_index >= jnp.uint64(64), hi, lo)
        sh = jnp.where(
            bit_index >= jnp.uint64(64),
            bit_index - jnp.uint64(64),
            bit_index,
        )
        bit = (word >> sh) & one
        rhi = (rhi << one) | (rlo >> jnp.uint64(63))
        rlo = (rlo << one) | bit
        ge = (rhi > dhi) | ((rhi == dhi) & (rlo >= dlo))
        borrow = (rlo < dlo).astype(jnp.uint64)
        rhi = jnp.where(ge, rhi - dhi - borrow, rhi)
        rlo = jnp.where(ge, rlo - dlo, rlo)
        qhi = (qhi << one) | (qlo >> jnp.uint64(63))
        qlo = (qlo << one) | ge.astype(jnp.uint64)
        return rhi, rlo, qhi, qlo

    z = jnp.zeros_like(lo)
    rhi, rlo, qhi, qlo = jax.lax.fori_loop(0, 128, body, (z, z, z, z))
    return qhi, qlo, rhi, rlo


def mul_wide(l: jnp.ndarray, r: jnp.ndarray, down: int) -> jnp.ndarray:
    """Exact signed product of two lanes (narrow or wide) rescaled down
    by 10^down with round-half-away, as a wide lane.  Exact while the
    unscaled |product| < 2^127 (guaranteed when operand precisions sum
    to <= 38, the DecimalType cap)."""
    lm, ls = abs128(promote(l))
    rm, rs = abs128(promote(r))
    llo, lhi = limbs(lm)
    rlo, rhi = limbs(rm)
    llo_u = llo.astype(jnp.uint64)
    rlo_u = rlo.astype(jnp.uint64)
    hi, lo = int128.umul128(llo_u, rlo_u)
    # cross terms wrap into the high limb (product bounded < 2^127)
    hi = (
        hi
        + llo_u * rhi.astype(jnp.uint64)
        + lhi.astype(jnp.uint64) * rlo_u
    )
    if down > 0:
        const = 10**down
        qhi, qlo, rhi_r, rlo_r = _udiv128_const_wide(hi, lo, const)
        # round half away: 2*rem >= const (rem < const <= 10^38 < 2^127)
        r2hi = (rhi_r << jnp.uint64(1)) | (rlo_r >> jnp.uint64(63))
        r2lo = rlo_r << jnp.uint64(1)
        chi = jnp.uint64(const >> 64)
        clo = jnp.uint64(const & ((1 << 64) - 1))
        up = ((r2hi > chi) | ((r2hi == chi) & (r2lo >= clo))).astype(
            jnp.uint64
        )
        qlo2 = qlo + up
        qhi = qhi + (qlo2 < qlo).astype(jnp.uint64)
        hi, lo = qhi, qlo2
    mag = make_wide(lo.astype(jnp.int64), hi.astype(jnp.int64))
    neg = (ls * rs) < 0
    return jnp.where(neg[..., None], negate(mag), mag)


# -- chunked accumulator form ------------------------------------------
def narrow_row_chunks(v: jnp.ndarray, live: jnp.ndarray):
    """Per-row 32-bit chunks of a narrow int64 lane: [c0 (unsigned),
    c1 (signed high)] — v == c1*2^32 + c0 exactly."""
    vv = jnp.where(live, v.astype(jnp.int64), 0)
    return [vv & _M32, vv >> jnp.int64(32)]


def wide_row_chunks(w: jnp.ndarray, live: jnp.ndarray):
    """Per-row 32-bit chunks of a wide lane: [c0..c3], c3 signed."""
    lo, hi = limbs(w)
    lo = jnp.where(live, lo, 0)
    hi = jnp.where(live, hi, 0)
    return [
        lo & _M32,
        (lo >> jnp.int64(32)) & _M32,  # logical: lo is a bit pattern
        hi & _M32,
        hi >> jnp.int64(32),
    ]


def normalize_chunks(chunks):
    """Propagate carries so every chunk is back in 32-bit range (top
    chunk keeps the sign).  Exact while chunk magnitudes stay < 2^63,
    i.e. < 2^31 accumulated rows — far beyond one device's tile."""
    out = []
    carry = jnp.zeros_like(chunks[0])
    for i, c in enumerate(chunks):
        c = c + carry
        if i == len(chunks) - 1:
            out.append(c)  # top chunk: signed, absorbs remaining carry
        else:
            out.append(c & _M32)
            carry = c >> jnp.int64(32)  # arithmetic: signed carries work
    return out


def shifted_sum_chunks(sums, shifts):
    """Canonical chunks of ``sum(s << sh)`` over int64 lanes ``sums``
    and python-int ``shifts`` (each < 96), without ever forming the
    total in an int64.  Each s is cut into its unsigned low and signed
    high 32 bits BEFORE its shift, so one term adds under 2^32 to chunk
    sh//32 and under 2^31 + (|s| >> 32 << sh%32) to the next: the
    caller proves that lane bound; the total only has to fit 128 bits."""
    lanes = [jnp.zeros_like(sums[0]) for _ in range(4)]
    for s, sh in zip(sums, shifts):
        q, r = divmod(sh, 32)
        low = (s & _M32) << jnp.int64(r)  # < 2^63: no wrap
        lanes[q] = lanes[q] + (low & _M32)
        lanes[q + 1] = (
            lanes[q + 1] + (low >> jnp.int64(32))
            + ((s >> jnp.int64(32)) << jnp.int64(r))
        )
    return normalize_chunks(lanes)


def chunks_to_wide(chunks) -> jnp.ndarray:
    """Canonical (normalized) chunks -> wide (…, 2) lane."""
    c0, c1, c2, c3 = chunks
    lo = (c1 << jnp.int64(32)) | c0
    hi = (c3 << jnp.int64(32)) | c2
    return make_wide(lo, hi)


# XLA:TPU lays the stacked (n, 4) operand of `seg_sum_chunks`' scatter out
# with its minor dimension padded to 128 lanes: 1 KiB a row over its two
# 32-bit planes, 2 GiB at 2^21 rows, and at the 16,777,216 slots of a mesh
# shard (which is never compacted) two buffers of 8 GB that no chip holds
# (PR 35).  Past this many rows a sorted caller sums the lanes one by one.
_STACKED_CHUNK_ROWS = 1 << 21


@jax.named_scope("seg_sum_chunks")
def seg_sum_chunks(row_chunks, gid: jnp.ndarray, cap: int, seg=None):
    """Segment-sum per-row chunk lanes and normalize: the wide SUM
    kernel.  Two-chunk inputs (narrow rows) pad with zero chunks —
    `normalize_chunks`' arithmetic carries sign-extend negatives
    correctly through the zero chunks.

    Small capacities use the masked-matrix reduction per chunk lane
    (XLA:TPU scatter measured ~16M updates/s vs ~100x that for the
    masked form at cap<=32 — round-3 micro-benchmark, record deleted in
    PR 22); large capacities one stacked (n, k) scatter, while its
    operand fits (`_STACKED_CHUNK_ROWS`).  Past that, sorted group ids
    (`seg`, an `aggregation.SortedSegments` over `gid`) sum each chunk
    lane by a prefix sum and range differences, no scatter: a chunk is
    under 2^32, so its running sum is exact in int64 below 2^31 rows."""
    from .aggregation import _use_masked

    if _use_masked(cap):
        from .aggregation import _seg_sum

        sums = [_seg_sum(c, gid, cap) for c in row_chunks]
    elif seg is not None and gid.shape[0] > _STACKED_CHUNK_ROWS:
        sums = [seg.sum(c) for c in row_chunks]
    else:
        mat = jnp.stack(row_chunks, axis=1)  # (n, k)
        sums2 = jax.ops.segment_sum(mat, gid, num_segments=cap)
        sums = [sums2[:, i] for i in range(len(row_chunks))]
    while len(sums) < 4:
        sums.append(jnp.zeros_like(sums[0]))
    return normalize_chunks(sums)


def merge_chunk_lanes(chunk_lanes, w, gid, cap):
    """FINAL-step merge of shipped (canonical) chunk columns: plain
    segment sums + one carry pass.  Exact while the merged partial
    count stays < 2^31 (chunks < 2^32 each)."""
    sums = [
        jax.ops.segment_sum(jnp.where(w, c, 0), gid, num_segments=cap)
        for c in chunk_lanes
    ]
    return normalize_chunks(sums)


def promote(v: jnp.ndarray) -> jnp.ndarray:
    """Lane value -> wide form (no-op if already two-limb)."""
    return v if is_wide(v) else widen(v)


def decimal_rescale_wide(w: jnp.ndarray, fs: int, ts: int) -> jnp.ndarray:
    """Scale change on wide lanes with round-half-away (Int128Math
    rescale analog).  Down-rescales keep a FULL 128-bit quotient, so
    e.g. decimal(38,6) -> decimal(38,2) stays exact."""
    if ts >= fs:
        return rescale(w, ts - fs)
    down = fs - ts
    mag, sign = abs128(w)
    lo, hi = limbs(mag)
    const = 10**down
    qhi, qlo, rhi, rlo = _udiv128_const_wide(
        hi.astype(jnp.uint64), lo.astype(jnp.uint64), const
    )
    # round half away: 2*rem >= const (both < 2^127)
    r2hi = (rhi << jnp.uint64(1)) | (rlo >> jnp.uint64(63))
    r2lo = rlo << jnp.uint64(1)
    chi = jnp.uint64(const >> 64)
    clo = jnp.uint64(const & ((1 << 64) - 1))
    up = ((r2hi > chi) | ((r2hi == chi) & (r2lo >= clo))).astype(jnp.uint64)
    qlo2 = qlo + up
    qhi = qhi + (qlo2 < qlo).astype(jnp.uint64)
    out = make_wide(qlo2.astype(jnp.int64), qhi.astype(jnp.int64))
    return jnp.where((sign < 0)[..., None], negate(out), out)


def to_double(w: jnp.ndarray) -> jnp.ndarray:
    """Wide -> float64 (rounds beyond 2^53 like any int64 cast)."""
    lo, hi = limbs(w)
    lo_f = lo.astype(jnp.float64) + jnp.where(
        lo < 0, jnp.float64(2.0**64), jnp.float64(0.0)
    )
    return hi.astype(jnp.float64) * jnp.float64(2.0**64) + lo_f


def pad_rows(v: jnp.ndarray, extra: int) -> jnp.ndarray:
    """Pad axis 0 by `extra` rows, preserving limb dims (narrow- and
    wide-lane safe replacement for jnp.pad(v, (0, extra)))."""
    return jnp.pad(v, ((0, extra),) + ((0, 0),) * (v.ndim - 1))


# -- device <-> host ----------------------------------------------------
def to_python_ints(lo_arr, hi_arr, valid):
    """Host conversion: limb arrays -> python ints (exact)."""
    import numpy as np

    lo = np.asarray(lo_arr).astype(np.uint64)
    hi = np.asarray(hi_arr).astype(np.int64)
    out = []
    for i in range(lo.shape[0]):
        if valid is not None and not valid[i]:
            out.append(None)
        else:
            out.append((int(hi[i]) << 64) | int(lo[i]))
    return out


def from_python_int(x: int):
    """Python int -> (lo, hi) int64 bit patterns."""
    lo = x & ((1 << 64) - 1)
    hi = (x >> 64) & ((1 << 64) - 1)
    if lo >= 1 << 63:
        lo -= 1 << 64
    if hi >= 1 << 63:
        hi -= 1 << 64
    return lo, hi
