"""Sort / TopN / Limit kernels.

Reference parity: operator/OrderByOperator.java (+ PagesIndexOrdering
bytecode comparators via OrderingCompiler), operator/TopNOperator.java.

TPU-first: one multi-operand jax.lax.sort call replaces the codegen'd
comparator chain — sort keys are transformed (descending -> negate,
NULLS FIRST/LAST -> sentinel bit as a leading key) and the row permutation
is carried as the last operand; payload columns are gathered afterwards.
TopN is sort + static-length slice (XLA's top-k path applies when keys
reduce to one operand).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..expr.lower import Lane


@dataclasses.dataclass(frozen=True)
class SortKey:
    column: str
    ascending: bool = True
    nulls_first: bool = False  # Trino default: NULLS LAST for ASC


def sort_perm(
    keys: Sequence[SortKey],
    lanes: Dict[str, Lane],
    sel: jnp.ndarray,
) -> jnp.ndarray:
    """Permutation ordering selected rows by keys; unselected rows last."""
    n = sel.shape[0]
    operands: List[jnp.ndarray] = [jnp.logical_not(sel)]
    for k in keys:
        v, ok = lanes[k.column]
        # null ordering as a leading bit per key
        nullbit = jnp.logical_not(ok) if not k.nulls_first else ok
        operands.append(nullbit)
        if v.ndim == 2:
            # wide (two-limb) decimal: two operands, 128-bit signed order
            from . import wide_decimal as wd

            operands.extend(wd.order_operands(v, not k.ascending))
            continue
        vv = v.astype(jnp.int8) if v.dtype.kind == "b" else v
        # the nullbit key dominates, so null rows' values need no neutralizing
        operands.append(vv if k.ascending else _negate_for_desc(vv))
    operands.append(jnp.arange(n, dtype=jnp.int64))
    res = jax.lax.sort(tuple(operands), num_keys=len(operands) - 1)
    return res[-1]


def _negate_for_desc(v: jnp.ndarray) -> jnp.ndarray:
    if v.dtype.kind == "f":
        return -v
    if v.dtype.kind == "b":
        return jnp.logical_not(v)
    # bitwise complement, not negation: -INT64_MIN wraps to itself and
    # would sort first under DESC; ~v is an exact order reversal
    return ~v.astype(jnp.int64)


def apply_perm(
    lanes: Dict[str, Lane], perm: jnp.ndarray, sel: jnp.ndarray
) -> Tuple[Dict[str, Lane], jnp.ndarray]:
    from .filter_project import permute_lanes

    return permute_lanes(lanes, perm), sel[perm]


# python int, not a jnp scalar: module-level jnp constants become
# hidden const args of jitted programs (see ops/int128.py note)
_SIGN_BITS = 1 << 63  # applied via jnp.uint64(_SIGN_BITS) at trace time


def _order_encode(v, ok, sel, key: SortKey) -> jnp.ndarray:
    """Rank-preserving uint64 for one sort key where LARGER = earlier in
    the output; unselected rows are strictly worst.  The low bit is
    sacrificed for the selection flag, so distinct values may tie — safe,
    because phase 2 re-sorts candidates on the exact keys and the
    completeness check counts encoded ties."""
    if v.ndim == 2:
        # wide decimal: monotone 64-bit approximation; collapsed values
        # surface as counted ties, phase 2 re-sorts on the exact limbs
        from . import wide_decimal as wd

        enc = wd.order_approx64(v).astype(jnp.uint64) ^ jnp.uint64(_SIGN_BITS)
    elif jnp.issubdtype(v.dtype, jnp.floating):
        from .aggregation import f64_order_bits

        # arithmetic IEEE reconstruction — bitcast f64<->u64 is
        # unimplemented in XLA:TPU's x64 rewrite
        enc = f64_order_bits(v)
    elif v.dtype.kind == "b":
        enc = v.astype(jnp.uint64)
    else:
        enc = v.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(_SIGN_BITS)
    if key.ascending:
        enc = ~enc  # top_k picks largest; ascending wants smallest first
    enc = jnp.where(ok, enc, jnp.uint64(0) if not key.nulls_first else ~jnp.uint64(0))
    enc = (enc >> jnp.uint64(1)) | (sel.astype(jnp.uint64) << jnp.uint64(63))
    # top_k wants a signed operand; u64->i64 after flipping the sign bit is
    # the monotone modular wrap (no 64-bit bitcast on TPU)
    return (enc ^ jnp.uint64(_SIGN_BITS)).astype(jnp.int64)


@jax.named_scope("topn")
def topn(
    keys: Sequence[SortKey],
    lanes: Dict[str, Lane],
    sel: jnp.ndarray,
    n: int,
    factor: int = 1,
) -> Tuple[Dict[str, Lane], jnp.ndarray, Tuple[jnp.ndarray, int] | None]:
    """Sorted first-n rows (static slice; result capacity = n).

    TPU-first: for small n over large inputs, a full multi-operand
    lexicographic sort compiles slowly on XLA:TPU, so phase 1 runs
    `lax.top_k` on a rank-preserving encoding of the FIRST key only,
    keeping 4n candidates, and phase 2 sorts just those candidates on all
    keys.  Exactness: any row excluded by phase 1 is strictly worse on the
    first key than the n-th candidate, so it cannot reach the top n; ties
    on the encoded key are counted and returned as a (count, capacity)
    check — the executor's retry ladder re-runs with a larger candidate
    set if ties ever exceed it (TopNOperator semantics, never heuristic).
    """
    total = sel.shape[0]
    kprime = max(64, 1 << (max(n, 1) * 4 * factor - 1).bit_length())
    if not keys or kprime >= total:
        perm = sort_perm(keys, lanes, sel)
        out, s = apply_perm(lanes, perm, sel)
        out = {name: (v[:n], ok[:n]) for name, (v, ok) in out.items()}
        return out, s[:n], None
    v, ok = lanes[keys[0].column]
    enc = _order_encode(v, ok, sel, keys[0])
    top_enc, idx = jax.lax.top_k(enc, kprime)
    kth = top_enc[n - 1]
    ties = jnp.sum((enc >= kth) & sel)
    cand = {name: (vv[idx], oo[idx]) for name, (vv, oo) in lanes.items()}
    cand_sel = sel[idx]
    perm = sort_perm(keys, cand, cand_sel)
    out, s = apply_perm(cand, perm, cand_sel)
    out = {name: (v2[:n], ok2[:n]) for name, (v2, ok2) in out.items()}
    return out, s[:n], (ties, kprime)


def limit(
    lanes: Dict[str, Lane], sel: jnp.ndarray, n: int, offset: int = 0
) -> Tuple[Dict[str, Lane], jnp.ndarray]:
    """Keep selected rows (offset, offset+n] by running count
    (order-preserving LimitOperator with OFFSET).

    Static-shape: selection mask is trimmed outside the window; array
    capacity is unchanged.
    """
    running = jnp.cumsum(sel.astype(jnp.int64))
    keep = sel & (running <= offset + n)
    if offset:
        keep = keep & (running > offset)
    return lanes, keep
