"""Columnar data model: Column (Block analog) and Page.

Reference parity: core/trino-spi/src/main/java/io/trino/spi/Page.java:31 and
spi/block/Block.java:22 (sealed hierarchy ValueBlock / DictionaryBlock /
RunLengthEncodedBlock / LazyBlock).

TPU-first redesign: a Column is a fixed-dtype device array plus an optional
validity mask (SQL NULLs) and an optional host-side string dictionary.  All
device arrays are padded to static tile sizes before entering jit; the
``count`` field carries the true row count (rows beyond it are padding and
masked out of every kernel).  This replaces the reference's
position-count/SelectedPositions machinery with masks, which XLA fuses for
free, and replaces LazyBlock's deferred IO with deferred host->HBM upload
(numpy arrays stay on host until a kernel needs them; jnp.asarray is the
upload point).

Dictionary columns mirror DictionaryBlock.java:33: device array of int32
codes + a host-side numpy array of the distinct values.  Run-length columns
mirror RunLengthEncodedBlock.java:31 as (value, count) broadcast on demand.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import numpy as np

from . import types as T


class FormattedKeys:
    """Dictionary of a key-formatted varchar column (TPC-H `c_name`,
    `s_name`, `o_clerk`, the addresses): entry `i` is `prefix` followed by
    the integer `first + i`, zero-padded to `width` digits when `width` is
    set.  It answers what a dictionary array is asked (`len`, `d[i]`,
    slices, index arrays, iteration, `np.asarray`) and formats an entry
    only when it is read, so a 1.5 M-customer scan carries four fields and
    a 100-row result formats 100 strings.  `formatted` counts them."""

    __slots__ = ("prefix", "width", "first", "n", "formatted")

    def __init__(self, prefix: str, width: int, first: int, n: int):
        self.prefix, self.width = prefix, int(width)
        self.first, self.n = int(first), int(n)
        self.formatted = 0

    def __len__(self) -> int:
        return self.n

    @property
    def sorted_by_code(self) -> bool:
        """Zero-padded keys sort as their integers: a code is its own
        lexicographic rank, and ORDER BY / min / max need no rank table."""
        return self.width > 0 and self.first >= 0 and (
            self.first + self.n <= 10 ** self.width
        )

    def fingerprint(self) -> str:
        """What `dict_fingerprint` hashes in place of the entries."""
        return "%s\x1e%d\x1e%d\x1e%d" % (
            self.prefix, self.width, self.first, self.n)

    def _entry(self, i: int) -> str:
        self.formatted += 1
        return self._peek(i)

    def __getitem__(self, i):
        if isinstance(i, slice):
            lo, hi, step = i.indices(self.n)
            if step == 1:
                return FormattedKeys(
                    self.prefix, self.width, self.first + lo, max(hi - lo, 0)
                )
            i = np.arange(lo, hi, step)
        if isinstance(i, (int, np.integer)):
            i = int(i)
            if not -self.n <= i < self.n:
                raise IndexError(i)
            return self._entry(i % self.n)
        idx = np.asarray(i)
        if idx.dtype == bool:
            idx = np.nonzero(idx)[0]
        out = np.empty(idx.shape, dtype=object)
        flat = out.reshape(-1)
        for j, k in enumerate(idx.reshape(-1).tolist()):
            if not -self.n <= k < self.n:
                raise IndexError(k)
            flat[j] = self._entry(k % self.n)
        return out

    def __iter__(self):
        return (self._entry(i) for i in range(self.n))

    def __array__(self, dtype=None, copy=None):
        out = self[np.arange(self.n)]
        return out if dtype is None else out.astype(dtype)

    def index_of(self, s: str) -> int:
        """Code of the entry that reads `s`, or -1: parsed, nothing is
        formatted (what an equality predicate on the column asks)."""
        digits = s[len(self.prefix):] if s.startswith(self.prefix) else ""
        if not (digits.isascii() and digits.isdigit()):
            return -1
        i = int(digits) - self.first
        return i if 0 <= i < self.n and self._peek(i) == s else -1

    def _peek(self, i: int) -> str:
        return "%s%0*d" % (self.prefix, self.width, self.first + i)

    def __repr__(self):
        return "FormattedKeys(%r, %d, %d, %d)" % (
            self.prefix, self.width, self.first, self.n)


def same_dictionary(a, b) -> bool:
    """Do two dictionaries hold the same entries under the same codes?
    Two FormattedKeys compare by their four fields, nothing is formatted."""
    if a is b:
        return True
    if isinstance(a, FormattedKeys) and isinstance(b, FormattedKeys):
        return a.fingerprint() == b.fingerprint()
    return np.array_equal(a, b)


@dataclasses.dataclass
class Column:
    """One column of a Page: values + optional validity + optional dictionary.

    values   : np.ndarray or jax.Array, shape (n,), dtype = type.np_dtype
    validity : None (all valid) or bool array shape (n,); True = non-null
    dictionary: for VarcharType columns, np.ndarray of distinct python strings
               (dtype=object or <U*); values are int32 indices into it.
               Code -1 is reserved for "not in dictionary" (never matches).
    """

    type: T.Type
    values: Any
    validity: Optional[Any] = None
    dictionary: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return int(self.values.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.values)

    def to_python(self, count: Optional[int] = None) -> list:
        """Decode to python objects (strings/Decimals) for tests & client."""
        n = len(self) if count is None else count
        vals = np.asarray(self.values)[:n]
        valid = (
            np.ones(n, dtype=bool)
            if self.validity is None
            else np.asarray(self.validity)[:n]
        )
        out: list = []
        if getattr(self.type, "is_array", False):
            d = self.dictionary
            decode = _element_decoder(self.type.element)
            for v, ok in zip(vals, valid):
                if ok and int(v) >= 0:
                    out.append([decode(x) for x in d[int(v)]])
                else:
                    out.append(None)
        elif getattr(self.type, "is_map", False):
            d = self.dictionary
            dk = _element_decoder(self.type.key)
            dv = _element_decoder(self.type.value)
            for v, ok in zip(vals, valid):
                if ok and int(v) >= 0:
                    out.append({dk(k): dv(x) for k, x in d[int(v)]})
                else:
                    out.append(None)
        elif self.type.is_dictionary:
            d = self.dictionary
            for v, ok in zip(vals, valid):
                out.append(str(d[int(v)]) if (ok and int(v) >= 0) else None)
        elif self.type.is_decimal:
            scale = self.type.scale
            div = 10**scale
            if vals.ndim == 2:
                # wide (two-limb) decimal: exact python-int combine
                from decimal import Decimal

                lo = vals[:, 0].astype(np.uint64)
                hi = vals[:, 1].astype(np.int64)
                for l, h, ok in zip(lo, hi, valid):
                    if not ok:
                        out.append(None)
                        continue
                    u = (int(h) << 64) | int(l)
                    out.append(
                        Decimal(u).scaleb(-scale) if scale else u
                    )
                return out
            for v, ok in zip(vals, valid):
                if not ok:
                    out.append(None)
                else:
                    out.append(int(v) / div if scale else int(v))
        elif self.type.name == "date":
            epoch = np.datetime64("1970-01-01")
            for v, ok in zip(vals, valid):
                out.append(str(epoch + np.timedelta64(int(v), "D")) if ok else None)
        elif self.type.name == "boolean":
            for v, ok in zip(vals, valid):
                out.append(bool(v) if ok else None)
        elif self.type.name in ("double", "real"):
            for v, ok in zip(vals, valid):
                out.append(float(v) if ok else None)
        else:
            for v, ok in zip(vals, valid):
                out.append(int(v) if ok else None)
        return out


@dataclasses.dataclass
class Page:
    """A batch of rows as parallel columns (Page.java:31).

    count is the logical row count; column arrays may be longer (padding).
    ``names`` gives the output name of each column (symbol names in plans).
    """

    columns: list
    count: int
    names: Optional[list] = None

    def __post_init__(self):
        for c in self.columns:
            assert len(c) >= self.count, "column shorter than page count"

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return int(self.columns[0].values.shape[0]) if self.columns else self.count

    def column(self, i: int) -> Column:
        return self.columns[i]

    def by_name(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    def to_pylist(self) -> list:
        """Rows as python tuples (decoded), for tests and the client."""
        cols = [c.to_python(self.count) for c in self.columns]
        return [tuple(vals) for vals in zip(*cols)] if cols else []


def _element_decoder(et: T.Type):
    """Array dictionary entries keep IR-constant conventions; decode to
    client python values (matching Column.to_python per-type rules)."""
    import numpy as _np

    if et.is_decimal and et.scale:
        div = 10 ** et.scale

        return lambda x: None if x is None else x / div
    if et.name == "date":
        epoch = _np.datetime64("1970-01-01")

        return lambda x: (
            None if x is None else str(epoch + _np.timedelta64(int(x), "D"))
        )
    return lambda x: x


def _element_encoder(et: T.Type):
    """Inverse of _element_decoder: client python values -> IR conventions."""
    import numpy as _np

    if et.is_decimal and et.scale:
        mul = 10 ** et.scale

        return lambda x: None if x is None else int(round(float(x) * mul))
    if et.name == "date":
        epoch = _np.datetime64("1970-01-01")

        return lambda x: (
            None
            if x is None
            else int((_np.datetime64(x, "D") - epoch).astype(int))
            if isinstance(x, str)
            else int(x)
        )
    return lambda x: x


def column_from_pylist(typ: T.Type, data: Sequence, dictionary=None) -> Column:
    """Build a Column from python values (None = NULL). Test helper."""
    if getattr(typ, "is_array", False):
        enc = _element_encoder(typ.element)
        data = [
            None if v is None else tuple(enc(x) for x in v) for v in data
        ]
    n = len(data)
    validity = None
    if any(v is None for v in data):
        validity = np.array([v is not None for v in data], dtype=bool)
    if typ.is_dictionary:
        if dictionary is None:
            seen: dict = {}
            for v in data:
                if v is not None and v not in seen:
                    seen[v] = len(seen)
            entries = list(seen.keys())
            # element-wise object array: np.array() would make equal-length
            # tuple entries (arrays) into a 2-D array
            dictionary = np.empty(len(entries), dtype=object)
            for _i, _v in enumerate(entries):
                dictionary[_i] = _v
        lookup = {v: i for i, v in enumerate(dictionary)}
        codes = np.array(
            [lookup.get(v, -1) if v is not None else -1 for v in data],
            dtype=np.int32,
        )
        return Column(typ, codes, validity, dictionary)
    if typ.is_decimal:
        scale = 10**typ.scale
        if getattr(typ, "wide", False):
            from decimal import ROUND_HALF_UP, Decimal

            from .ops.wide_decimal import from_python_int

            limbs = np.zeros((n, 2), dtype=np.int64)
            for i, v in enumerate(data):
                if v is None:
                    continue
                u = int(
                    (Decimal(str(v)) * scale).to_integral_value(
                        ROUND_HALF_UP
                    )
                )
                limbs[i, 0], limbs[i, 1] = from_python_int(u)
            return Column(typ, limbs, validity)
        from decimal import ROUND_HALF_UP, Decimal

        def enc(v):
            # ints stay exact (float64 would round >2^53, e.g. 18-digit
            # unscaled decimals); non-ints go through Decimal-of-str
            if isinstance(v, int):
                return v * scale
            return int(
                (Decimal(str(v)) * scale).to_integral_value(ROUND_HALF_UP)
            )

        vals = np.array(
            [0 if v is None else enc(v) for v in data], dtype=np.int64
        )
        return Column(typ, vals, validity)
    if typ.name == "date":
        epoch = np.datetime64("1970-01-01")
        vals = np.array(
            [
                0 if v is None else (np.datetime64(v, "D") - epoch).astype(int)
                for v in data
            ],
            dtype=np.int32,
        )
        return Column(typ, vals, validity)
    dt = typ.np_dtype
    vals = np.array([(0 if v is None else v) for v in data], dtype=dt)
    return Column(typ, vals, validity)


def page_from_pydict(schema: Sequence, data: dict) -> Page:
    """schema: list of (name, Type). data: name -> list of python values."""
    names = [n for n, _ in schema]
    cols = [column_from_pylist(t, data[n]) for n, t in schema]
    counts = {len(c) for c in cols}
    assert len(counts) == 1, "ragged columns"
    return Page(cols, counts.pop(), names)


def pad_to(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    """Pad an array to a static capacity along axis 0 (the tile-shape
    trick); trailing dims (wide-decimal limbs) are preserved."""
    n = arr.shape[0]
    if n == capacity:
        return arr
    assert n < capacity, (n, capacity)
    pad = np.full((capacity - n,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])
