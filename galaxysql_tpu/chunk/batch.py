"""Device-resident column batches — the TPU-native Chunk/Block engine.

Reference analog: `polardbx-executor/.../executor/chunk` (SURVEY.md §2.6, Appendix A):
`Chunk` = positionCount + Block[] + optional selection vector.  Here:

- `Column`  ~= Block: one fixed-dtype lane array + optional validity (null) mask.
- `ColumnBatch` ~= Chunk: dict of named Columns + a `live` row mask standing in for the
  reference's `int[] selection` indirection.  A filter doesn't compact rows (dynamic shapes
  would defeat XLA); it ANDs into `live`, and compaction is an explicit operator applied when
  the plan profits from it — exactly the role selection vectors play in the reference
  (`Chunk.java:79`).

Both are registered JAX pytrees, so whole operator pipelines jit/shard_map over them.
Strings are dictionary-encoded (int32 code lanes); the Dictionary itself is host-side static
metadata and travels in the pytree aux data.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.types import temporal


class Dictionary:
    """Host-side string dictionary: code lane (int32) <-> Python strings.

    Identity-hashed: a Dictionary instance is static jit metadata; rebuilding a dictionary
    creates a new compile key (same trade the reference makes by caching plans per schema
    version).
    """

    __slots__ = ("values", "index", "sorted_codes", "_is_sorted", "uid")

    _next_uid = itertools.count(1)

    def __init__(self, values: Sequence[str] = ()):  # code i -> values[i]
        self.values: List[str] = list(values)
        self.index: Dict[str, int] = {v: i for i, v in enumerate(self.values)}
        self.sorted_codes: Optional[np.ndarray] = None
        self._is_sorted: Optional[bool] = None
        # process-unique, never-reused identity (id() can be recycled after GC, which
        # would alias compiled-kernel cache keys)
        self.uid = next(Dictionary._next_uid)

    def __len__(self) -> int:
        return len(self.values)

    def encode_one(self, s: str, add: bool = True) -> int:
        code = self.index.get(s)
        if code is None:
            if not add:
                return -1
            code = len(self.values)
            self.values.append(s)
            self.index[s] = code
            self._is_sorted = None
        return code

    def encode(self, strings: Sequence[str], add: bool = True) -> np.ndarray:
        return np.fromiter((self.encode_one(s, add) for s in strings), dtype=np.int32,
                           count=len(strings))

    def decode(self, codes: np.ndarray) -> List[Optional[str]]:
        out: List[Optional[str]] = []
        for c in np.asarray(codes).tolist():
            out.append(self.values[c] if 0 <= c < len(self.values) else None)
        return out

    @property
    def is_sorted(self) -> bool:
        if self._is_sorted is None:
            self._is_sorted = all(self.values[i] <= self.values[i + 1]
                                  for i in range(len(self.values) - 1))
        return self._is_sorted

    def rank_array(self) -> np.ndarray:
        """rank[code] = position of code's string in sorted order (for <,> on dict lanes)."""
        order = np.argsort(np.array(self.values, dtype=object), kind="stable")
        rank = np.empty(len(self.values), dtype=np.int32)
        rank[order] = np.arange(len(self.values), dtype=np.int32)
        return rank

    def codes_matching(self, pred) -> np.ndarray:
        """All codes whose string satisfies `pred` — LIKE/regex evaluate host-side once per
        dictionary, then become device-side set membership (SURVEY.md §7 'strings' stance)."""
        return np.array([i for i, v in enumerate(self.values) if pred(v)], dtype=np.int32)

    def sorted_order(self) -> np.ndarray:
        """order[rank] = code whose string sorts at position `rank` (inverse of
        rank_array)."""
        return np.argsort(np.array(self.values, dtype=object), kind="stable").astype(np.int32)


class EncodedStrings:
    """A string column handed over already encoded: `values[codes[i]]` is row
    i's string, a negative code a NULL.  The bulk load's input where a column's
    domain is small (`TableStore.insert_arrays` maps the codes through one
    table of `len(values)` entries); `len()`, iteration, `tolist()` and
    `np.asarray()` answer with the strings themselves."""

    __slots__ = ("codes", "values")

    def __init__(self, codes, values):
        self.codes = np.asarray(codes)
        self.values = np.asarray(values, dtype=str)

    def __len__(self) -> int:
        return int(self.codes.shape[0])

    def __array__(self, dtype=None, copy=None):
        out = self.values.take(self.codes, mode="clip")
        return out if dtype is None else out.astype(dtype)

    def tolist(self) -> List[Optional[str]]:
        out = self.values.astype(object)[self.codes]
        out[self.codes < 0] = None
        return out.tolist()

    def __iter__(self):
        return iter(self.tolist())


def dictionary_translation(target: Dictionary, source: Dictionary) -> np.ndarray:
    """trans[source_code] = target_code (or -1 when the string is absent from target).

    Single home for cross-dictionary alignment, used by both the expression compiler
    (column-vs-column string compare) and the hash join (key domain normalization)."""
    return np.array([target.encode_one(v, add=False) for v in source.values] or [-1],
                    dtype=np.int32)


_UNION_TRANS_CACHE: Dict[Tuple[int, int, int], np.ndarray] = {}


def dictionary_union_translation(target: Dictionary,
                                 source: Dictionary) -> np.ndarray:
    """trans[source_code] = target_code, EXTENDING target with values it lacks
    (UNION semantics: every source string must exist in the output dictionary).

    Cached by (target uid, source uid, len(source)): codes never change once
    assigned, so a cached table stays valid as either dictionary grows."""
    key = (target.uid, source.uid, len(source))
    t = _UNION_TRANS_CACHE.get(key)
    if t is None:
        t = np.array([target.encode_one(v) for v in source.values] or [0],
                     dtype=np.int32)
        if len(_UNION_TRANS_CACHE) > 4096:
            _UNION_TRANS_CACHE.clear()
        _UNION_TRANS_CACHE[key] = t
    return t


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One column lane: `data` + optional validity mask (True = non-null)."""

    data: Any  # jnp/np array, shape [n]
    valid: Optional[Any]  # bool array [n] or None (all valid)
    dtype: dt.DataType = dataclasses.field(default=dt.BIGINT)
    dictionary: Optional[Dictionary] = None

    def tree_flatten(self):
        return (self.data, self.valid), (self.dtype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, valid = children
        return cls(data, valid, aux[0], aux[1])

    def __len__(self) -> int:
        return int(self.data.shape[0])

    def valid_mask(self) -> Any:
        if self.valid is None:
            return jnp.ones(self.data.shape[0], dtype=jnp.bool_)
        return self.valid

    def np_data(self) -> np.ndarray:
        return np.asarray(self.data)

    def np_valid(self) -> np.ndarray:
        if self.valid is None:
            return np.ones(self.data.shape[0], dtype=np.bool_)
        return np.asarray(self.valid)

    # -- host conversions --------------------------------------------------

    def to_pylist(self) -> List[Any]:
        data = self.np_data()
        valid = self.np_valid()
        t = self.dtype
        out: List[Any] = []
        if t.is_string and self.dictionary is not None:
            decoded = self.dictionary.decode(data)
            return [decoded[i] if valid[i] else None for i in range(len(decoded))]
        for i in range(data.shape[0]):
            if not valid[i]:
                out.append(None)
            elif t.clazz == dt.TypeClass.DECIMAL:
                out.append(int(data[i]) / (10 ** t.scale))
            elif t.clazz == dt.TypeClass.DATE:
                out.append(temporal.format_date(int(data[i])))
            elif t.clazz == dt.TypeClass.DATETIME:
                out.append(temporal.format_datetime(int(data[i])))
            elif t.clazz == dt.TypeClass.FLOAT:
                out.append(float(data[i]))
            elif t.clazz == dt.TypeClass.BOOL:
                out.append(bool(data[i]))
            else:
                out.append(int(data[i]))
        return out


def column_from_pylist(values: Sequence[Any], typ: dt.DataType,
                       dictionary: Optional[Dictionary] = None) -> Column:
    """Build a Column from Python values (None = NULL), encoding per type."""
    n = len(values)
    valid = np.array([v is not None for v in values], dtype=np.bool_)
    lane = np.zeros(n, dtype=typ.lane)
    if typ.is_string:
        dictionary = dictionary if dictionary is not None else Dictionary()
        codes = [dictionary.encode_one(v) if v is not None else 0 for v in values]
        lane = np.array(codes, dtype=np.int32)
    else:
        for i, v in enumerate(values):
            if v is None:
                continue
            if typ.clazz == dt.TypeClass.DECIMAL:
                lane[i] = round(float(v) * (10 ** typ.scale))
            elif typ.clazz == dt.TypeClass.DATE:
                lane[i] = temporal.parse_date(v) if isinstance(v, str) else int(v)
            elif typ.clazz == dt.TypeClass.DATETIME:
                lane[i] = temporal.parse_datetime(v) if isinstance(v, str) else int(v)
            else:
                lane[i] = v
    return Column(lane, None if bool(valid.all()) else valid, typ, dictionary)


@jax.tree_util.register_pytree_node_class
class ColumnBatch:
    """A batch of rows: named Columns of equal length + a `live` row mask.

    `live` plays the selection-vector role: rows with live=False exist physically (fixed
    shapes for XLA) but are logically deleted.  `None` means all rows live.
    """

    def __init__(self, columns: Dict[str, Column], live: Optional[Any] = None):
        self.columns = columns
        self.live = live

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        return (tuple(self.columns[n] for n in names), self.live), names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols, live = children
        return cls(dict(zip(names, cols)), live)

    # -- shape -------------------------------------------------------------

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return int(next(iter(self.columns.values())).data.shape[0])

    def live_mask(self) -> Any:
        if self.live is None:
            return jnp.ones(self.capacity, dtype=jnp.bool_)
        return self.live

    def np_live(self) -> np.ndarray:
        if self.live is None:
            return np.ones(self.capacity, dtype=np.bool_)
        return np.asarray(self.live)

    def num_live(self) -> int:
        if self.live is None:
            return self.capacity
        return int(np.asarray(self.live).sum())

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    def names(self) -> List[str]:
        return list(self.columns.keys())

    # -- host-side utilities (not for use under jit) ------------------------

    def compact(self) -> "ColumnBatch":
        """Drop dead rows (host-side gather)."""
        if self.live is None:
            return self
        idx = np.nonzero(np.asarray(self.live))[0]
        cols = {}
        for name, c in self.columns.items():
            valid = c.np_valid()[idx]
            cols[name] = Column(c.np_data()[idx], None if bool(valid.all()) else valid,
                                c.dtype, c.dictionary)
        return ColumnBatch(cols, None)

    def pad_to(self, capacity: int) -> "ColumnBatch":
        """Pad with dead rows up to `capacity` (bucketing to avoid recompiles)."""
        n = self.capacity
        if n == capacity:
            if self.live is None:
                return ColumnBatch(dict(self.columns),
                                   np.ones(n, dtype=np.bool_))
            return self
        if n > capacity:
            raise ValueError(f"cannot pad batch of {n} down to {capacity}")
        pad = capacity - n
        live = np.zeros(capacity, dtype=np.bool_)
        live[:n] = self.np_live()
        cols = {}
        for name, c in self.columns.items():
            data = np.concatenate([c.np_data(), np.zeros(pad, dtype=c.dtype.lane)])
            valid = np.concatenate([c.np_valid(), np.zeros(pad, dtype=np.bool_)])
            cols[name] = Column(data, valid, c.dtype, c.dictionary)
        return ColumnBatch(cols, live)

    def to_pylist(self) -> List[Tuple]:
        """Live rows as tuples of Python values (row-at-a-time boundary, like ChunkRow)."""
        cb = self.compact()
        cols = [cb.columns[n].to_pylist() for n in cb.names()]
        return list(zip(*cols)) if cols else []

    def to_pydict(self) -> Dict[str, List[Any]]:
        cb = self.compact()
        return {n: cb.columns[n].to_pylist() for n in cb.names()}

    def select(self, names: Sequence[str]) -> "ColumnBatch":
        return ColumnBatch({n: self.columns[n] for n in names}, self.live)

    def rename(self, mapping: Dict[str, str]) -> "ColumnBatch":
        return ColumnBatch({mapping.get(n, n): c for n, c in self.columns.items()}, self.live)


def batch_from_pydict(data: Dict[str, Sequence[Any]], schema: Dict[str, dt.DataType],
                      dictionaries: Optional[Dict[str, Dictionary]] = None) -> ColumnBatch:
    cols = {}
    for name, values in data.items():
        d = (dictionaries or {}).get(name)
        cols[name] = column_from_pylist(values, schema[name], d)
    return ColumnBatch(cols, None)


def concat_batches(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """Host-side concatenation of compacted batches (dictionaries must be shared)."""
    batches = [b.compact() for b in batches if b.capacity]
    if not batches:
        return ColumnBatch({}, None)
    names = batches[0].names()
    cols = {}
    for n in names:
        ref = batches[0].columns[n]
        data = np.concatenate([b.columns[n].np_data() for b in batches])
        valid = np.concatenate([b.columns[n].np_valid() for b in batches])
        cols[n] = Column(data, None if bool(valid.all()) else valid, ref.dtype, ref.dictionary)
    return ColumnBatch(cols, None)
