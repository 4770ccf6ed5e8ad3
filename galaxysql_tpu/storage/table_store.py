"""Partitioned columnar table store — the DN (data node) storage analog.

Reference analog: the `galaxyengine` DN holds sharded row storage; the CN ships physical
operations per shard (SURVEY.md §2.9, §3.2).  Here each partition is a host-resident
struct-of-arrays column set (numpy lanes + null masks) with:

- append path used by INSERT/LOAD (routes rows via PartitionRouter),
- scan path yielding ColumnBatches (bucketed/padded for stable jit shapes),
- persistence as one .npz per partition + dictionaries, for restart.

MVCC: each partition keeps per-row `begin_ts`/`end_ts` lanes; a snapshot scan at ts sees
rows with begin_ts <= ts < end_ts.  DML writes go through `txn/` which stamps these lanes
(TSO ordering, SURVEY.md §3.4).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from galaxysql_tpu.chunk.batch import (Column, ColumnBatch, Dictionary, EncodedStrings,
                                       column_from_pylist)
from galaxysql_tpu.meta.catalog import PartitionRouter, TableMeta
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import errors, tracing
from galaxysql_tpu.utils.failpoint import FAIL_POINTS, FP_LOCK_INVERT
from galaxysql_tpu.utils.lockdep import named_lock

INFINITY_TS = (1 << 63) - 1  # int64 max; must exceed any TSO value (phys_ms << 22 ~ 7.5e18)

# the bulk path's accounting (`insert_arrays`), plain adds: rows and lane bytes
# appended (data, validity and the two timestamp lanes), and where the seconds
# went: columns into lanes, rows to partitions, lanes into the partitions
LOAD_STATS = {"calls": 0, "rows": 0, "bytes": 0,
              "encode_s": 0.0, "route_s": 0.0, "append_s": 0.0}


class Partition:
    """One shard of a table: numpy lanes + validity + MVCC timestamps."""

    def __init__(self, table: TableMeta, pid: int):
        self.table = table
        self.pid = pid
        self.lanes: Dict[str, np.ndarray] = {
            c.name: np.zeros(0, dtype=c.dtype.lane) for c in table.columns}
        self.valid: Dict[str, np.ndarray] = {
            c.name: np.zeros(0, dtype=np.bool_) for c in table.columns}
        self.begin_ts = np.zeros(0, dtype=np.int64)
        self.end_ts = np.zeros(0, dtype=np.int64)
        # lockdep class splits base tables from GSI stores ($-named): the
        # write path legitimately nests base-partition -> gsi-partition
        # (e.g. UPDATE holds the base row lock while maintaining the index),
        # which is a cross-class ORDER, not a same-class hazard
        self.lock = named_lock(
            "partition.gsi" if "$" in table.name else "partition")
        # append-aware sorted key indexes: col -> (lane_gen, n0, perm, sorted_keys)
        # where perm sorts rows [0, n0).  Appends don't invalidate (MVCC rows are
        # immutable; the [n0, n) tail is probed linearly until it outgrows
        # _INDEX_TAIL); wholesale lane replacement (column DDL, load) bumps
        # lane_gen and forces a rebuild.
        self._key_indexes: Dict[str, Tuple[int, int, np.ndarray, np.ndarray]] = {}
        self.lane_gen = 0

    _INDEX_TAIL = 8192

    def invalidate_indexes(self):
        """Call after replacing lane arrays in place (column DDL, reload)."""
        self.lane_gen += 1
        self._key_indexes.clear()

    def key_index(self, col: str):
        """(n0, perm, sorted_keys) of the append-aware sorted index over
        `col` (building it if stale).  `perm` stable-sorts rows [0, n0), so
        perm[lo:hi] enumerates equal-key rows in ascending row-id order; rows
        [n0, num_rows) are the unsorted appended tail the caller must probe
        separately.  Caller must hold `self.lock`."""
        n = self.num_rows
        lane = self.lanes[col]
        entry = self._key_indexes.get(col)
        if entry is None or entry[0] != self.lane_gen or \
                n - entry[1] > self._INDEX_TAIL:
            perm = np.argsort(lane[:n], kind="stable")
            entry = (self.lane_gen, n, perm, lane[:n][perm])
            self._key_indexes[col] = entry
        return entry[1], entry[2], entry[3]

    def key_candidates(self, col: str, lane_value) -> np.ndarray:
        """Row ids whose `col` lane equals the (lane-encoded) value.

        MVCC-unaware: returns every physical row version with that key; the
        caller applies visibility.  O(log n) over the indexed prefix plus a
        linear probe of the unsorted appended tail (XPlan key-Get analog,
        RelToXPlanConverter.java:41)."""
        with self.lock:
            n = self.num_rows
            lane = self.lanes[col]
            n0, perm, skeys = self.key_index(col)
            lo = np.searchsorted(skeys, lane_value, side="left")
            hi = np.searchsorted(skeys, lane_value, side="right")
            ids = perm[lo:hi]
            if n > n0:
                tail = np.nonzero(lane[n0:n] == lane_value)[0] + n0
                ids = np.concatenate([ids, tail]) if tail.size else ids
            return ids

    @property
    def num_rows(self) -> int:
        return int(self.begin_ts.shape[0])

    def append(self, lanes: Dict[str, np.ndarray], valid: Dict[str, np.ndarray],
               begin_ts: int, owned: bool = False):
        """`owned`: the arrays are the caller's to give away (the bulk load's
        freshly routed slices), so an empty partition keeps them as they are."""
        n = next(iter(lanes.values())).shape[0] if lanes else 0
        with self.lock:
            adopt = owned and self.num_rows == 0
            for c in self.table.columns:
                if adopt:
                    self.lanes[c.name], self.valid[c.name] = lanes[c.name], valid[c.name]
                    continue
                self.lanes[c.name] = np.concatenate([self.lanes[c.name], lanes[c.name]])
                self.valid[c.name] = np.concatenate([self.valid[c.name], valid[c.name]])
            self.begin_ts = np.concatenate(
                [self.begin_ts, np.full(n, begin_ts, dtype=np.int64)])
            self.end_ts = np.concatenate(
                [self.end_ts, np.full(n, INFINITY_TS, dtype=np.int64)])

    def visible_mask(self, snapshot_ts: Optional[int], txn_id: int = 0) -> np.ndarray:
        """MVCC visibility.  Uncommitted changes carry NEGATIVE timestamps (-txn_id):
        visible only to the owning transaction; finalized to real TSO values at commit
        (the in-process analog of the reference's innodb snapshot_seq/commit_seq
        dance, SURVEY.md §3.4).  Computed by the native runtime when available."""
        from galaxysql_tpu import native
        return native.visible_mask(self.begin_ts, self.end_ts, snapshot_ts, txn_id)

    def delete_rows(self, row_ids: np.ndarray, commit_ts: int):
        with self.lock:
            self.end_ts[row_ids] = commit_ts

    def update_rows(self, row_ids: np.ndarray, new_lanes: Dict[str, np.ndarray],
                    new_valid: Dict[str, np.ndarray], commit_ts: int):
        """MVCC update = end old versions + append new versions."""
        with self.lock:
            full_lanes = {}
            full_valid = {}
            for c in self.table.columns:
                if c.name in new_lanes:
                    full_lanes[c.name] = new_lanes[c.name]
                    full_valid[c.name] = new_valid[c.name]
                else:
                    full_lanes[c.name] = self.lanes[c.name][row_ids]
                    full_valid[c.name] = self.valid[c.name][row_ids]
            self.end_ts[row_ids] = commit_ts
            self.append(full_lanes, full_valid, commit_ts)


def _encode_strings(values, d: Dictionary):
    """(codes, valid or None) of a column of strings, None a NULL: the distinct
    values enter `d` in sorted order."""
    arr = np.asarray(values)
    ok = None
    if arr.dtype.kind != "U":
        arr = arr.astype(object)
        null = np.equal(arr, None).astype(np.bool_)
        if null.any():
            ok = ~null
            arr = np.where(null, "", arr)
        arr = arr.astype(str)
    uniq, inverse = np.unique(arr if ok is None else arr[ok], return_inverse=True)
    trans = d.encode(uniq.tolist())
    if ok is None:
        return trans[inverse].astype(np.int32), None
    lane = np.zeros(arr.shape[0], dtype=np.int32)
    lane[ok] = trans[inverse]
    return lane, ok


def _translate_codes(col: EncodedStrings, d: Dictionary):
    """(codes, valid or None) of a pre-encoded column: what `_encode_strings`
    gives for the same strings, from one table of `len(col.values)` entries."""
    codes = col.codes
    ok = None
    if codes.size and codes.min() < 0:
        ok = codes >= 0
    live = codes if ok is None else codes[ok]
    present = np.flatnonzero(np.bincount(live, minlength=len(col.values)))
    uniq, inverse = np.unique(col.values[present], return_inverse=True)
    trans = np.zeros(len(col.values), dtype=np.int32)
    trans[present] = d.encode(uniq.tolist())[inverse]
    if ok is None:
        return trans[codes], None
    lane = trans.take(codes, mode="clip")
    lane[~ok] = 0
    return lane, ok


class TableStore:
    _next_uid = itertools.count(1)

    def __init__(self, table: TableMeta):
        self.table = table
        self.router = PartitionRouter(table)
        n = table.partition.num_partitions
        self.partitions = [Partition(table, i) for i in range(n)]
        # process-unique identity for caches (id() can be recycled after GC)
        self.uid = next(TableStore._next_uid)
        # serializes the (before-count -> append -> derive appended ranges)
        # critical section DML writers run: two concurrent inserts reading
        # num_rows, appending, and re-reading would each attribute the
        # OTHER's rows to their own [start, n) range — double-captured CDC,
        # double-propagated GSI rows, mis-ranged txn undo entries.  Partition
        # locks only make each append atomic, not the count arithmetic.
        self.append_lock = named_lock(
            "append_lock.gsi" if "$" in table.name else "append_lock")

    # -- write path ----------------------------------------------------------

    def _lockdep_probe(self):
        """FP_LOCK_INVERT: deliberately acquire a partition lock and THEN the
        append_lock — the reverse of the canonical order — on the real insert
        ramp, so the lockdep witness test proves the runtime cycle check trips
        where it matters.  Disarmed (always, outside that test), this is one
        bool read.  Called BEFORE the ramp takes append_lock: a nested
        re-entrant acquisition would not create a graph edge."""
        if FAIL_POINTS.active and FAIL_POINTS.value(FP_LOCK_INVERT) \
                and self.partitions:
            p = self.partitions[0]
            with p.lock:
                with self.append_lock:  # galaxylint: disable=lock-order -- deliberate seeded inversion proving the lockdep witness trips (tests/test_lint.py)
                    pass

    def insert_pylists(self, data: Dict[str, List[Any]], begin_ts: int) -> int:
        """Encode python values and route rows to partitions.  Returns rows inserted."""
        lanes, valid, n = self.encode_pylists(data)
        return self.append_encoded(lanes, valid, n, begin_ts)

    def encode_pylists(self, data: Dict[str, List[Any]]):
        """Phase 1 of insert_pylists: python values -> (lanes, valid, n),
        mutating NOTHING except auto-increment allocation.  Split out so the
        batched write path can fail a bad value strictly pre-mutation."""
        table = self.table
        n = len(next(iter(data.values()))) if data else 0
        lanes: Dict[str, np.ndarray] = {}
        valid: Dict[str, np.ndarray] = {}
        for c in table.columns:
            values = data.get(c.name)
            if values is None:
                if c.auto_increment:
                    start = table.auto_increment_next
                    table.auto_increment_next += n
                    lanes[c.name] = np.arange(start, start + n, dtype=c.dtype.lane)
                    valid[c.name] = np.ones(n, dtype=np.bool_)
                    continue
                dv = c.default
                values = [dv] * n
            col = column_from_pylist(values, c.dtype,
                                     table.dictionaries.get(c.name.lower()))
            lanes[c.name] = col.np_data()
            valid[c.name] = col.np_valid()
            if not c.nullable and not valid[c.name].all() and c.default is None:
                raise errors.TddlError(f"Column '{c.name}' cannot be null")
        return lanes, valid, n

    def append_encoded(self, lanes, valid, n: int, begin_ts: int) -> int:
        """Phase 2 of insert_pylists: route + append pre-encoded lanes."""
        pids = self._route(lanes)
        for pid in np.unique(pids):
            sel = np.nonzero(pids == pid)[0]
            self.partitions[int(pid)].append(
                {k: v[sel] for k, v in lanes.items()},
                {k: v[sel] for k, v in valid.items()}, begin_ts)
        self.table.stats.row_count += n
        return n

    def insert_arrays(self, data: Dict[str, Any], begin_ts: int) -> int:
        """Bulk ingestion fast path (LOAD DATA analog): numeric columns as numpy
        arrays pass through; a string column comes as strings (dictionary codes
        in the order of its sorted distinct values, by `np.unique`) or already
        encoded (`EncodedStrings`: the same codes through one translation table
        of the column's own dictionary, no pass over the rows' strings)."""
        table = self.table
        n = len(next(iter(data.values()))) if data else 0
        strings = [c.name for c in table.columns
                   if c.dtype.is_string and data.get(c.name) is not None]
        encoded = sum(isinstance(data[c], EncodedStrings) for c in strings)
        tc = tracing.current()
        sp = tc.begin(f"load:{table.name}", "load", rows=n,
                      encoded=f"{encoded}/{len(strings)}") if tc is not None else None
        t0 = time.perf_counter()
        lanes, valid = self._encode_arrays(data, n)
        t1 = time.perf_counter()
        pids = self._route(lanes)
        t2 = time.perf_counter()
        nbytes = self._append_routed(lanes, valid, pids, n, begin_ts)
        t3 = time.perf_counter()
        table.stats.row_count += n
        LOAD_STATS["calls"] += 1
        LOAD_STATS["rows"] += n
        LOAD_STATS["bytes"] += nbytes
        LOAD_STATS["encode_s"] += t1 - t0
        LOAD_STATS["route_s"] += t2 - t1
        LOAD_STATS["append_s"] += t3 - t2
        if sp is not None:
            sp.attrs["bytes"] = nbytes
            tc.end(sp)
        return n

    def _encode_arrays(self, data: Dict[str, Any], n: int):
        """Columns -> (lanes, valid); `valid[name]` is None where every row is."""
        table = self.table
        lanes: Dict[str, np.ndarray] = {}
        valid: Dict[str, Optional[np.ndarray]] = {}
        for c in table.columns:
            values = data.get(c.name)
            ok = None
            if values is None:
                if c.auto_increment:
                    start = table.auto_increment_next
                    table.auto_increment_next += n
                    lane = np.arange(start, start + n, dtype=c.dtype.lane)
                else:
                    lane = np.zeros(n, dtype=c.dtype.lane)
                    ok = np.zeros(n, dtype=np.bool_)
            elif c.dtype.is_string:
                d = table.dictionaries[c.name.lower()]
                if isinstance(values, EncodedStrings):
                    lane, ok = _translate_codes(values, d)
                else:
                    lane, ok = _encode_strings(values, d)
            elif c.dtype.clazz == dt.TypeClass.DECIMAL:
                a = np.asarray(values, dtype=np.float64)
                scaled = a * 10 ** c.dtype.scale
                lane = np.round(scaled, out=scaled).astype(np.int64)
                null = np.isnan(a)
                ok = ~null if null.any() else None
            else:
                lane = np.asarray(values).astype(c.dtype.lane, copy=False)
            lanes[c.name] = lane
            valid[c.name] = None if ok is None or bool(ok.all()) else ok
        return lanes, valid

    def _append_routed(self, lanes, valid, pids: np.ndarray, n: int,
                       begin_ts: int) -> int:
        """Every partition's rows, in their order of arrival, in ONE gather a
        lane: the rows are ordered by partition once (a stable radix sort of
        the ids) and a partition takes its slice of each gathered lane as it
        is.  Returns the bytes appended."""
        if n == 0:
            return 0
        parts = self.partitions
        counts = np.bincount(pids, minlength=len(parts))
        order = None
        if counts.max() < n:
            small = np.uint8 if len(parts) <= 256 else np.uint16
            order = np.argsort(pids.astype(small), kind="stable")
        ends = np.cumsum(counts)
        pieces: List[Tuple[dict, dict]] = [({}, {}) for _ in parts]
        for name in list(lanes):
            lane, ok = lanes.pop(name), valid.pop(name)
            if order is not None:
                lane = lane[order]
                ok = ok if ok is None else ok[order]
            elif not lane.flags.owndata:
                lane = lane.copy()  # a view of the caller's column
            for pid, (pl, pv) in enumerate(pieces):
                lo, hi = int(ends[pid] - counts[pid]), int(ends[pid])
                pl[name] = lane[lo:hi]
                pv[name] = np.ones(hi - lo, dtype=np.bool_) if ok is None \
                    else ok[lo:hi]
        nbytes = 0
        for pid, (pl, pv) in enumerate(pieces):
            if counts[pid]:
                nbytes += sum(a.nbytes for a in pl.values()) \
                    + sum(a.nbytes for a in pv.values()) + 16 * int(counts[pid])
                parts[pid].append(pl, pv, begin_ts, owned=True)
        return nbytes

    def _route(self, lanes: Dict[str, np.ndarray]) -> np.ndarray:
        info = self.table.partition
        n = next(iter(lanes.values())).shape[0] if lanes else 0
        if info.method in ("single", "broadcast"):
            return np.zeros(n, dtype=np.int32)
        keys = [lanes[c] if c in lanes else lanes[self.table.column(c).name]
                for c in info.columns]
        return self.router.route_rows(keys)

    # -- read path -------------------------------------------------------------

    def scan_partition(self, pid: int, columns: Sequence[str],
                       snapshot_ts: Optional[int] = None,
                       batch_rows: int = 1 << 20,
                       txn_id: int = 0) -> Iterator[ColumnBatch]:
        """Yield ColumnBatches of up to batch_rows visible rows."""
        p = self.partitions[pid]
        with p.lock:
            vis = p.visible_mask(snapshot_ts, txn_id)
            idx = np.nonzero(vis)[0]
            data = {c: p.lanes[c][idx] for c in columns}
            valid = {c: p.valid[c][idx] for c in columns}
        n = idx.shape[0]
        table = self.table
        for off in range(0, max(n, 1), batch_rows):
            hi = min(off + batch_rows, n)
            if n == 0 and off > 0:
                break
            cols = {}
            for c in columns:
                cm = table.column(c)
                v = valid[c][off:hi]
                cols[c] = Column(data[c][off:hi], None if v.all() else v, cm.dtype,
                                 table.dictionaries.get(c.lower()))
            yield ColumnBatch(cols, None)
            if hi >= n:
                break

    def scan(self, columns: Sequence[str], partitions: Optional[Sequence[int]] = None,
             snapshot_ts: Optional[int] = None, txn_id: int = 0
             ) -> Iterator[ColumnBatch]:
        pids = range(len(self.partitions)) if partitions is None else partitions
        for pid in pids:
            yield from self.scan_partition(pid, columns, snapshot_ts, txn_id=txn_id)

    def row_count(self, snapshot_ts: Optional[int] = None, txn_id: int = 0) -> int:
        return sum(int(p.visible_mask(snapshot_ts, txn_id).sum())
                   for p in self.partitions)

    def truncate(self):
        n = self.table.partition.num_partitions
        self.partitions = [Partition(self.table, i) for i in range(n)]
        self.table.stats.row_count = 0

    # -- persistence -------------------------------------------------------------

    def save(self, directory: str):
        os.makedirs(directory, exist_ok=True)
        for p in self.partitions:
            arrays = {f"lane__{k}": v for k, v in p.lanes.items()}
            arrays.update({f"valid__{k}": v for k, v in p.valid.items()})
            arrays["begin_ts"] = p.begin_ts
            arrays["end_ts"] = p.end_ts
            np.savez_compressed(os.path.join(directory, f"p{p.pid}.npz"), **arrays)
        dicts = {k: d.values for k, d in self.table.dictionaries.items()}
        with open(os.path.join(directory, "dictionaries.json"), "w") as f:
            json.dump(dicts, f)

    def load(self, directory: str):
        dpath = os.path.join(directory, "dictionaries.json")
        if os.path.exists(dpath):
            with open(dpath) as f:
                dicts = json.load(f)
            for k, values in dicts.items():
                d = self.table.dictionaries.get(k)
                if d is not None:
                    for v in values:
                        d.encode_one(v)
        for p in self.partitions:
            path = os.path.join(directory, f"p{p.pid}.npz")
            if not os.path.exists(path):
                continue
            z = np.load(path, allow_pickle=False)
            p.begin_ts = z["begin_ts"]
            p.end_ts = z["end_ts"]
            for c in self.table.columns:
                p.lanes[c.name] = z[f"lane__{c.name}"]
                p.valid[c.name] = z[f"valid__{c.name}"]
            p.invalidate_indexes()
        self.table.stats.row_count = self.row_count()
