"""Device mesh + sharded batch plumbing.

Reference analog: the MPP cluster topology (`InternalNodeManager`/worker set, SURVEY.md
§2.7) — except a "worker" here is a mesh device and "the cluster" is a
`jax.sharding.Mesh`.  Tables shard over the `shard` axis on the row dimension (the
§2.10/§5.7 mapping: DB scan-splits ≈ sequence-parallel row sharding).

A ShardedTable is 1-D column lanes of length S*R (S = mesh size, R = padded rows per
shard; shard s owns slice [s*R, (s+1)*R)), device-put with NamedSharding(P("shard")),
plus a live mask.  1-D lanes keep every stage's outputs in the same layout: a shard_map
stage with out_specs P("shard") concatenates per-shard blocks back into the same form.  Loading is cached
per (store, table-version, mesh) the same way the single-chip device cache pins lanes
in HBM.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec.operators import MIN_BUCKET


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    arr = np.array(devices)
    return Mesh(arr.reshape(len(devices)), ("shard",))


def shard_bucket(n: int) -> int:
    c = max(MIN_BUCKET // 8, 128)
    while c < n:
        c *= 2
    return c


class ShardedTable:
    """1-D column lanes [S*R] sharded over the mesh row-wise + live mask [S*R]."""

    def __init__(self, columns: Dict[str, Column], live: Any, mesh: Mesh):
        self.columns = columns          # Column.data shape [S*R]
        self.live = live                # [S*R] bool
        self.mesh = mesh


class MeshDataCache:
    """(store id, table version, mesh shape, columns) -> ShardedTable."""

    def __init__(self):
        self._map: Dict[Tuple, ShardedTable] = {}
        self._lock = threading.Lock()

    def get(self, store, mesh: Mesh, columns: Sequence[str],
            snapshot_ts: Optional[int], txn_id: int = 0) -> ShardedTable:
        table = store.table
        has_pending = any(((p.begin_ts < 0).any() or
                           (p.end_ts != np.iinfo(np.int64).max).any())
                          for p in store.partitions)
        key = (store.uid, table.version, mesh.shape["shard"],
               tuple(sorted(columns)),
               None if not has_pending else (snapshot_ts, txn_id))
        with self._lock:
            got = self._map.get(key)
            if got is not None:
                return got
        from galaxysql_tpu.utils import tracing
        with tracing.annotation(f"transfer:{table.name}"):
            st = _load_sharded(store, mesh, columns, snapshot_ts, txn_id)
        with self._lock:
            if len(self._map) > 64:
                self._map.clear()
            self._map[key] = st
        return st


def _load_sharded(store, mesh: Mesh, columns: Sequence[str],
                  snapshot_ts: Optional[int], txn_id: int) -> ShardedTable:
    """Distribute storage partitions across mesh shards (round-robin), pad, stack."""
    S = mesh.shape["shard"]
    table = store.table
    per_shard: List[List[int]] = [[] for _ in range(S)]
    for pid in range(len(store.partitions)):
        per_shard[pid % S].append(pid)

    # gather visible rows per shard (host-side)
    shard_lanes: Dict[str, List[np.ndarray]] = {c: [] for c in columns}
    shard_valid: Dict[str, List[np.ndarray]] = {c: [] for c in columns}
    counts = []
    for s in range(S):
        datas = {c: [] for c in columns}
        valids = {c: [] for c in columns}
        n = 0
        for pid in per_shard[s]:
            p = store.partitions[pid]
            vis = p.visible_mask(snapshot_ts, txn_id)
            idx = np.nonzero(vis)[0]
            n += idx.shape[0]
            for c in columns:
                datas[c].append(p.lanes[c][idx])
                valids[c].append(p.valid[c][idx])
        counts.append(n)
        for c in columns:
            shard_lanes[c].append(
                np.concatenate(datas[c]) if datas[c] else
                np.zeros(0, dtype=table.column(c).dtype.lane))
            shard_valid[c].append(
                np.concatenate(valids[c]) if valids[c] else np.zeros(0, np.bool_))

    R = shard_bucket(max(max(counts), 1))
    live_np = np.zeros((S, R), dtype=np.bool_)
    for s in range(S):
        live_np[s, :counts[s]] = True

    sharding = NamedSharding(mesh, P("shard"))
    cols: Dict[str, Column] = {}
    for c in columns:
        cm = table.column(c)
        lane = np.zeros((S, R), dtype=cm.dtype.lane)
        vmask = np.zeros((S, R), dtype=np.bool_)
        for s in range(S):
            k = counts[s]
            lane[s, :k] = shard_lanes[c][s]
            vmask[s, :k] = shard_valid[c][s]
        data = jax.device_put(lane.reshape(-1), sharding)
        valid = None if bool(vmask[live_np].all()) else \
            jax.device_put(vmask.reshape(-1), sharding)
        cols[c] = Column(data, valid, cm.dtype, table.dictionaries.get(c.lower()))
    live = jax.device_put(live_np.reshape(-1), sharding)
    return ShardedTable(cols, live, mesh)


GLOBAL_MESH_CACHE = MeshDataCache()
