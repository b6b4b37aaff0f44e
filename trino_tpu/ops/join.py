"""Hash-join equivalent: sorted-build lookup join.

Reference parity: operator/join/ — HashBuilderOperator.java:57 builds a
PagesIndex + generated PagesHashStrategy hash table (JoinCompiler.java:104);
LookupJoinOperator.java:36 probes it per row.

TPU-first redesign: random-access hash tables don't vectorize on TPU, so the
build side becomes a *sorted key array + row permutation* (the bucketed-
sorted table of SURVEY §7), and the probe is a SORT-MERGE rank: build and
probe keys are sorted together once and each probe key's position among
the build keys falls out of a cumulative count (XLA's per-lane
binary-search loop — what jnp.searchsorted lowers to — measured ~17x
slower than one extra sort on TPU at millions of rows).  The reference's
64-bit synthetic row address (SyntheticAddress.java:22) maps to the
permutation index.

Exactness: multi-column keys are packed into a 64-bit mix only to *locate*
candidate build rows; every candidate is then verified against the real key
columns (`verify_rows`), the analog of the generated PagesHashStrategy
positionEqualsRow (JoinCompiler.java:104) running after the hash-bucket
probe.  A hash collision therefore costs an extra candidate, never a wrong
row.  Duplicate build keys (or colliding ones) route to the expansion
kernel (`expand_join_slots`), the vectorized LookupJoinOperator
page-building loop with two-pass counting.

Join types: inner, left (probe-outer), semi, anti — all mask-based with
static shapes.  Right/full-outer are planned to left + union of the
null-extended anti side at analysis time (sql/analyzer.py _build_join).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..expr.lower import Lane

# dead (unselected/NULL-key) build rows sort to the very end: their key is
# pinned to int64 max AND a live-before-dead flag breaks the tie, so the
# first `nvalid` sorted slots are exactly the live rows even when a real
# key equals int64 max — no value is stolen from the key domain
_SENTINEL = 2**63 - 1  # python int (see ops/int128.py const-arg note)


def row_ids(n: int) -> jnp.ndarray:
    """Row numbers 0..n-1 as a sort operand.  XLA:TPU's sort compiles (and
    runs) by the 32-bit words it carries, whatever the row count: an int64
    key with an int64 payload took 185 s to compile (8M rows, for a
    described v5e on the sandbox's host), with an int32 one as the last KEY
    of an unstable sort 50 s (same order: the ids are unique).  So ids ride
    as int32 wherever the static row count allows."""
    return jnp.arange(n, dtype=jnp.int32 if n < 2**31 else jnp.int64)


def _sort_live_first(kv, live, n):
    dead = (~live).astype(jnp.int32)
    sorted_keys, _, perm = jax.lax.sort(
        (kv, dead, row_ids(n)), num_keys=3, is_stable=False
    )
    return sorted_keys, perm.astype(jnp.int64)


def merge_rank(sorted_build: jnp.ndarray, probe: jnp.ndarray, side: str):
    """For each probe key: the number of build keys strictly below it
    (side='left') or at-or-below it (side='right') — searchsorted by
    sort-merge.  One sort of [build ++ probe] by (key, position), so the
    concatenation order breaks ties (build-first = right, probe-first =
    left), then a cumulative count of build elements."""
    nb = sorted_build.shape[0]
    m = probe.shape[0]
    ids = row_ids(nb + m)
    if side == "left":
        keys = jnp.concatenate([probe, sorted_build])
        _, perm = jax.lax.sort((keys, ids), num_keys=2, is_stable=False)
        is_build = perm >= m
        probe_idx = jnp.where(is_build, m, perm)
    else:
        keys = jnp.concatenate([sorted_build, probe])
        _, perm = jax.lax.sort((keys, ids), num_keys=2, is_stable=False)
        is_build = perm < nb
        probe_idx = jnp.where(is_build, m, perm - nb)
    cb = jnp.cumsum(is_build.astype(ids.dtype))
    # route each cb back to its probe row by SORTING on probe_idx
    # (probes get 0..m-1, build rows sink at m): a scatter here cost
    # ~0.6s at 10M (XLA:TPU ~16M updates/s) vs ~0.15s for the sort.
    # Unstable: the probes' ids are unique, and nothing past them is read
    _, back = jax.lax.sort((probe_idx, cb), num_keys=1, is_stable=False)
    return back[:m].astype(jnp.int64)


class LookupSource(NamedTuple):
    """The lent lookup source (PartitionedLookupSourceFactory analog)."""

    sorted_keys: jnp.ndarray  # [n] int64, dead rows pushed to the end
    perm: jnp.ndarray  # [n] original row index per sorted slot
    nvalid: jnp.ndarray  # scalar: number of valid build rows
    dup_count: jnp.ndarray  # scalar: number of duplicate keys (0 required)


@jax.named_scope("build_unique")
def build_unique(key: Lane, sel: jnp.ndarray) -> LookupSource:
    """Sort build rows by key; unselected/null rows sort to the end."""
    v, ok = key
    n = v.shape[0]
    live = sel & ok
    kv = jnp.where(live, v.astype(jnp.int64), _SENTINEL)
    sorted_keys, perm = _sort_live_first(kv, live, n)
    nvalid = live.sum()
    dup = jnp.sum(
        (sorted_keys[1:] == sorted_keys[:-1])
        & (jnp.arange(1, n) < nvalid)
    )
    return LookupSource(sorted_keys, perm, nvalid, dup)


def probe(
    source: LookupSource, key: Lane, sel: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized lookup: returns (build_row_index, matched mask)."""
    v, ok = key
    pk = v.astype(jnp.int64)
    idx = merge_rank(source.sorted_keys, pk, side="left")
    safe = jnp.clip(idx, 0, source.sorted_keys.shape[0] - 1)
    hit = (source.sorted_keys[safe] == pk) & (safe < source.nvalid)
    matched = sel & ok & hit
    build_row = source.perm[safe]
    return build_row, matched


def gather_build(
    build_cols: Dict[str, Lane], build_row: jnp.ndarray, matched: jnp.ndarray
) -> Dict[str, Lane]:
    """Materialize build-side payload lanes for each probe row (one
    stacked row-gather per dtype — see filter_project.permute_lanes)."""
    from .filter_project import permute_lanes

    return permute_lanes(build_cols, build_row, extra_ok=matched)


class DirectLookupSource(NamedTuple):
    """Dense-domain build table: rowid+1 scattered at (key - lo), 0 =
    empty slot.  Collision-FREE addressing (no hash, no verification);
    usable only when the planner PROVED the build key unique (strict
    stats walker) and bounded its domain — the runtime still counts
    out-of-domain build keys and reroutes the join to the sorted kernels
    when the proof was wrong (stale stats), so results stay exact.

    Reference analog: the array-based lookup source the generated
    JoinCompiler emits for dense integer keys
    (operator/join/ArrayPositionLinks / PagesHash fast path); TPU-first
    shape: one scatter to build, ONE random gather per probe row —
    measured 0.09s vs the sort-merge rank's 0.21s at 4M probes
    (round-3 micro-benchmark, record deleted in PR 22)."""

    table: jnp.ndarray  # [domain] int32: build row + 1, 0 = empty
    lo: int
    violations: jnp.ndarray  # scalar: live build keys outside the domain


@jax.named_scope("build_direct")
def build_direct(key: Lane, sel: jnp.ndarray, lo: int, domain: int
                 ) -> DirectLookupSource:
    v, ok = key
    live = sel & ok
    kv = v.astype(jnp.int64) - lo
    in_dom = (kv >= 0) & (kv < domain)
    viol = jnp.sum(live & ~in_dom).astype(jnp.int64)
    idx = jnp.where(live & in_dom, kv, domain)  # dropped writes
    n = v.shape[0]
    rowid1 = jnp.arange(1, n + 1, dtype=jnp.int32)
    table = (
        jnp.zeros(domain, dtype=jnp.int32)
        .at[idx]
        .max(rowid1, mode="drop")
    )
    # duplicate detector: each live row gathers its slot back — with a
    # truly unique key every row reads its own write; an overwritten row
    # reads a different rowid.  One cheap gather over the BUILD side, so
    # exactness never rests on the planner's stats being right.
    readback = table[jnp.clip(kv, 0, domain - 1)]
    dups = jnp.sum(
        live & in_dom & (readback != rowid1)
    ).astype(jnp.int64)
    return DirectLookupSource(table, lo, viol + dups)


@jax.named_scope("probe_direct")
def probe_direct(
    source: DirectLookupSource, key: Lane, sel: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One gather: build row index + matched mask per probe row.
    Out-of-domain probe keys match nothing — exact, because the build
    violation counter guarantees every live build key IS in-domain."""
    v, ok = key
    kv = v.astype(jnp.int64) - source.lo
    domain = source.table.shape[0]
    in_dom = (kv >= 0) & (kv < domain)
    slot = source.table[jnp.clip(kv, 0, domain - 1)]
    matched = sel & ok & in_dom & (slot > 0)
    return (slot - 1).astype(jnp.int64), matched


class MultiLookupSource(NamedTuple):
    """Build side with duplicate keys allowed (the general PagesHash)."""

    sorted_keys: jnp.ndarray
    perm: jnp.ndarray
    nvalid: jnp.ndarray


def build_multi(key: Lane, sel: jnp.ndarray) -> MultiLookupSource:
    v, ok = key
    n = v.shape[0]
    live = sel & ok
    kv = jnp.where(live, v.astype(jnp.int64), _SENTINEL)
    sorted_keys, perm = _sort_live_first(kv, live, n)
    return MultiLookupSource(sorted_keys, perm, live.sum())


def probe_counts(
    source: MultiLookupSource, key: Lane, sel: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-probe-row match count and first-match slot ([lo,hi) range);
    dead build slots (beyond nvalid) and dead probe rows count zero."""
    v, ok = key
    pk = v.astype(source.sorted_keys.dtype)
    lo = merge_rank(source.sorted_keys, pk, side="left")
    # hi = lo + the run length of the matching key.  Run lengths come
    # from two prefix scans over the SORTED build keys — a segment_sum
    # at build-capacity here measured ~0.5s at 8M rows (XLA:TPU scatter
    # ~16M updates/s), while the scan form is bandwidth-bound:
    #   run_start[i] = index of i's run head   (cummax of boundary idx)
    #   run_len[i]   = run_end[i] - run_start[i] + 1 (reverse cummin)
    nb = source.sorted_keys.shape[0]
    boundary = jnp.concatenate(
        [jnp.ones(1, bool),
         source.sorted_keys[1:] != source.sorted_keys[:-1]]
    )
    idx = row_ids(nb)   # an int64 cummax compiles for 80 s, an int32 one for 5
    run_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
    nxt = jnp.concatenate([boundary[1:], jnp.ones(1, bool)])
    run_end = jax.lax.cummin(
        jnp.where(nxt, idx, nb - 1), reverse=True
    )
    run_len = run_end - run_start + 1
    safe = jnp.clip(lo, 0, nb - 1)
    eq = source.sorted_keys[safe] == pk
    hi = jnp.where(eq, lo + run_len[safe], lo)
    lo = jnp.minimum(lo, source.nvalid)
    hi = jnp.minimum(hi, source.nvalid)
    counts = jnp.where(sel & ok, hi - lo, 0).astype(jnp.int64)
    return counts, lo


def build_extremes(key: Lane, val: Lane, sel: jnp.ndarray, dtype):
    """(build, sorted values, outside): the build rows whose key and `val`
    are both non-NULL, sorted by (key, val) as `dtype` words, so each
    key's run holds its values in ascending order — its least value at the
    run's first slot and its greatest at the last.  `build` is what
    `probe_counts` reads (no row permutation: no build row is gathered).
    Dead rows sort last as (max, max): a live row equal to that pair is
    the same pair of values, so the first `nvalid` slots hold exactly the
    live rows' pairs.  `outside` counts live rows whose key or value does
    not fit `dtype` (int32 sort words compile and run far cheaper than
    int64 ones; the caller retraces at int64 where any does not fit)."""
    v, ok = key
    c, cok = val
    v, c = v.astype(jnp.int64), c.astype(jnp.int64)
    live = sel & ok & cok
    info = jnp.iinfo(dtype)
    outside = jnp.sum(live & (
        (v < info.min) | (v > info.max) | (c < info.min) | (c > info.max)))
    kv = jnp.where(live, v, info.max).astype(dtype)
    cv = jnp.where(live, c, info.max).astype(dtype)
    sorted_keys, sorted_vals = jax.lax.sort(
        (kv, cv), num_keys=2, is_stable=False
    )
    return (MultiLookupSource(sorted_keys, None, live.sum()), sorted_vals,
            outside)


def probe_extremes(source: MultiLookupSource, sorted_vals: jnp.ndarray,
                   key: Lane, sel: jnp.ndarray):
    """Per probe row: (any build row, least value, greatest value) over the
    build rows of its key (`build_extremes`), compared in the build's
    words: a probe key outside them matches nothing.  The values are
    garbage where there is no row."""
    v, ok = key
    v = v.astype(jnp.int64)
    info = jnp.iinfo(source.sorted_keys.dtype)
    fits = (v >= info.min) & (v <= info.max)
    counts, lo = probe_counts(
        source, (v.astype(source.sorted_keys.dtype), ok), sel & fits)
    last = sorted_vals.shape[0] - 1
    least = sorted_vals[jnp.clip(lo, 0, last)]
    greatest = sorted_vals[jnp.clip(lo + counts - 1, 0, last)]
    return counts > 0, least, greatest


@jax.named_scope("expand_join_slots")
def expand_join_slots(
    source: MultiLookupSource,
    counts: jnp.ndarray,
    lo: jnp.ndarray,
    capacity: int,
    outer: bool = False,
):
    """Expand probe rows by their match multiplicity into a static-capacity
    output (the LookupJoinOperator page-building loop, vectorized).

    Returns (probe_row, build_row, matched, total, k):
      probe_row[j] : index of the probe row producing output j
      build_row[j] : build-side row index (garbage where not matched)
      matched[j]   : output j is a real (candidate) joined row
      total        : true output size (host checks vs capacity and retries)
      k            : slot offset within the probe row's candidate range;
                     k==0 identifies the one row per probe row that carries
                     the null-extended output when an outer probe row has
                     no surviving match
    """
    eff = jnp.maximum(counts, 1) if outer else counts
    offsets = jnp.cumsum(eff)
    total = offsets[-1]
    j = jnp.arange(capacity, dtype=jnp.int64)
    # output slot -> probe row: scatter each row's id at its start offset,
    # then a running max fills the row's whole range (offsets are
    # monotone; rows with eff=0 own no slots and are dropped)
    starts = offsets - eff
    nrows = counts.shape[0]
    seed = (
        jnp.zeros(capacity, dtype=jnp.int64)
        .at[jnp.where(eff > 0, starts, capacity)]
        .max(jnp.arange(nrows, dtype=jnp.int64), mode="drop")
    )
    probe_row = jax.lax.cummax(seed)
    probe_row = jnp.clip(probe_row, 0, counts.shape[0] - 1)
    start = offsets[probe_row] - eff[probe_row]
    k = j - start
    slot = jnp.clip(lo[probe_row] + k, 0, source.sorted_keys.shape[0] - 1)
    build_row = source.perm[slot]
    within = j < total
    matched = within & (k < counts[probe_row])
    return probe_row, build_row, matched, total, k


def needs_verification(key_lanes) -> bool:
    """True when the locator is a lossy hash that candidates must be
    re-checked against: multi-column keys, or any wide (two-limb)
    decimal key (whose 128 bits cannot pass through one locator)."""
    return len(key_lanes) > 1 or any(
        v.ndim == 2 for v, _ in key_lanes
    )


def verify_rows(
    build_keys, probe_keys, build_row: jnp.ndarray,
    probe_row: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Exact key equality of candidate pairs — the PagesHashStrategy
    positionEqualsRow analog (JoinCompiler.java:104).  Compares every real
    key column; NULL keys never match (SQL equi-join semantics)."""
    def at(lane, rows):
        v, ok = lane
        return lane if rows is None else (v[rows], ok[rows])

    return keys_equal([at(b, build_row) for b in build_keys],
                      [at(p, probe_row) for p in probe_keys])


def keys_equal(build_keys, probe_keys) -> jnp.ndarray:
    """Row by row, every build key lane equals its probe key lane (lanes
    already aligned); NULL keys never match."""
    eq = None
    for (b, bo), (p, po) in zip(build_keys, probe_keys):
        if b.ndim == 2 or p.ndim == 2:
            # wide decimal (either side may be a lane-narrow wide value)
            from . import wide_decimal as wd

            veq = wd.compare(wd.promote(b), wd.promote(p), "==")
        else:
            veq = b == p
        e = veq & bo & po
        eq = e if eq is None else (eq & e)
    return eq


def _canonical_bits(v: jnp.ndarray) -> jnp.ndarray:
    """Lane value -> one uint64 of hash material, IDENTICAL for a
    narrow lane and a two-limb lane holding the same value.  Wide
    decimal arithmetic keeps fast-path lanes narrow even when typed
    wide, so a join/bucket hash must not depend on the lane FORM: a
    wide lane whose value fits one limb hashes as that limb; genuinely
    128-bit values (never equal to any narrow-lane value) fold in the
    high limb.  Callers verify candidates on the real columns."""
    if v.ndim == 2:
        from . import wide_decimal as wd

        lo = v[:, 0].astype(jnp.uint64)
        hi = v[:, 1].astype(jnp.uint64)
        folded = lo ^ (hi * jnp.uint64(0x9E3779B97F4A7C15))
        return jnp.where(wd.fits_narrow(v), lo, folded)
    return v.astype(jnp.uint64)


def _mix(h: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """One splitmix-style mixing round.  Module-level so adversarial tests
    can patch in a deliberately weak hash and prove the exact-verification
    path (verify_rows) absorbs collisions."""
    h = h * jnp.uint64(0x9E3779B97F4A7C15) + x + jnp.uint64(0x632BE59BD9B4E019)
    return h ^ (h >> jnp.uint64(31))


def composite_key(key_lanes, sel, force_hash: bool = False) -> Lane:
    """Combine a multi-column equi-join key into one int64 *locator* lane.

    Single-column NARROW keys pass through (value == locator,
    collision-free).  Multi-column keys — and wide (two-limb) decimal
    keys, whose 128 bits cannot ride one locator — get a 64-bit mix used
    only to find candidate rows; callers MUST filter candidates with
    `verify_rows` on the real columns whenever `needs_verification` says
    so — a collision then only costs an extra (rejected) candidate.

    `force_hash` lets callers impose the JOINT decision across both join
    sides: lane forms may differ per side (a wide-typed product keeps a
    narrow fast-path lane), and build/probe locators must come from the
    same function either way.
    """
    if not force_hash and not needs_verification(key_lanes):
        return key_lanes[0]
    n = key_lanes[0][0].shape[0]
    h = jnp.zeros(n, dtype=jnp.uint64)
    allok = None
    for v, ok in key_lanes:
        h = _mix(h, _canonical_bits(v))
        allok = ok if allok is None else (allok & ok)
    # fold into the non-negative int64 range (dead rows are handled by the
    # live-first sort, not by a reserved value region)
    h = (h % jnp.uint64(2**62)).astype(jnp.int64)
    return (h, allok)
