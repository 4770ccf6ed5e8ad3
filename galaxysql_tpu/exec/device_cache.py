"""Device residency cache: hot table columns pinned in HBM.

The TPU-first answer to the reference's buffer/scan caching: instead of pumping rows
over JDBC per query (`TableScanClient`, SURVEY.md §2.6), whole column lanes live in
device memory keyed by (table, partition, column, table-version, device).  A version
bump (DML, DDL) invalidates; eviction is LRU by byte budget.  Scans hit HBM, so
steady-state AP queries read at HBM bandwidth instead of PCIe/host bandwidth.

Every upload names its device (`runtime.exec_device()`: the accelerator, or the CPU
device inside the TP path's pin) and the key carries it, so a lane first touched by a
CPU-pinned statement is never what a later accelerator scan of the same version reads.

Concurrent misses on one key are single-flighted: the first thread runs the
(possibly O(table)) builder + device transfer, the rest wait on a per-key event
and adopt its entry — two threads must never both pay the host materialization
or double-count `_bytes`.  Hits/misses/bytes surface through the typed metrics
registry (`bind_metrics`) as `device_cache_*` gauges, next to `frag_cache_*`.
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Any, Dict, Tuple

import jax
import numpy as np

from galaxysql_tpu.runtime import exec_device

# (store.uid, pid, column, version, row_count, device)
Key = Tuple[int, int, str, int, int, Any]

# host->device transfer accounting: every cache MISS materializes + ships a
# lane to the device; bytes/counts accumulate here (plain adds, host-side) and
# traced queries additionally get one `transfer` span per shipped lane.
TRANSFER_STATS = {"bytes": 0, "transfers": 0}


def reset_transfer_stats():
    TRANSFER_STATS["bytes"] = 0
    TRANSFER_STATS["transfers"] = 0


# devices that actually expose memory_stats(), resolved on first call: with
# always-on tracing this runs per query, and on backends without the stats
# (CPU) the jax.devices() + per-device probe loop is pure waste
_HBM_DEVICES: "list | None" = None


def hbm_high_water() -> Dict[str, int]:
    """Per-device peak memory (bytes) where the backend exposes it (TPU/GPU
    runtimes do; CPU may not).  Called from traced/profiled paths — the
    stats query is host-side, and backends without it short-circuit to an
    empty dict after the first probe."""
    global _HBM_DEVICES
    if _HBM_DEVICES is None:
        probed = []
        try:
            for d in jax.devices():
                try:
                    if d.memory_stats():
                        probed.append(d)
                except Exception:
                    pass
            _HBM_DEVICES = probed
        except RuntimeError:
            return {}  # backend not initialized yet: re-probe next call
    out: Dict[str, int] = {}
    for d in _HBM_DEVICES:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            out[str(d)] = int(ms.get("peak_bytes_in_use",
                                     ms.get("bytes_in_use", 0)))
    return out


class DeviceCache:
    def __init__(self, budget_bytes: int = 8 << 30):
        self.budget = budget_bytes
        self._map: "collections.OrderedDict[Key, Any]" = collections.OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self._building: Dict[Key, threading.Event] = {}
        # weakly-held registries: the cache is process-global while registries
        # are per-Instance — every live Instance's SHOW METRICS must see the
        # shared cache, and a dead Instance's registry must not be pinned
        self._metrics_refs: list = []
        self.hits = 0
        self.misses = 0
        # lanes dropped to stay inside the budget (a later scan uploads them
        # again), and their bytes
        self.evictions = 0
        self.evicted_bytes = 0

    def bind_metrics(self, registry):
        """Surface hits/misses/bytes through a typed MetricsRegistry
        (utils/metrics.py): SHOW METRICS, information_schema.metrics and the
        web /metrics endpoint all list the device_cache_* family."""
        if not any(r() is registry for r in self._metrics_refs):
            self._metrics_refs.append(weakref.ref(registry))
        self._push_metrics()

    def _push_metrics(self):
        if not self._metrics_refs:
            return
        live = []
        for r in self._metrics_refs:
            m = r()
            if m is None:
                continue
            live.append(r)
            m.gauge("device_cache_hits",
                    "device lane cache hits").set(self.hits)
            m.gauge("device_cache_misses",
                    "device lane cache misses").set(self.misses)
            m.gauge("device_cache_bytes",
                    "device lane cache resident bytes").set(self._bytes)
            m.gauge("device_cache_entries",
                    "device lane cache entries").set(len(self._map))
            m.gauge("device_cache_evictions",
                    "device lane cache lanes evicted over budget"
                    ).set(self.evictions)
        self._metrics_refs = live

    def _lookup_or_claim(self, key: Key):
        """(value, None) on hit, (None, event) when this thread owns the
        build.  Waiters block on the owner's event and re-check: either the
        entry landed (hit) or the owner failed (the waiter claims the build)."""
        while True:
            with self._lock:
                got = self._map.get(key)
                if got is not None:
                    self._map.move_to_end(key)
                    self.hits += 1
                    return got, None
                ev = self._building.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._building[key] = ev
                    return None, ev
            ev.wait()

    def get_lane_built(self, store, pid: int, column: str, version: int,
                       length: int, builder) -> Any:
        """Like get_lane, but the host array is built lazily: cache hits skip the
        (possibly O(table)) host-side materialization entirely, and concurrent
        misses on one key run the builder exactly once."""
        device = exec_device()
        key = (store.uid, pid, column, version, length, device)
        got, ev = self._lookup_or_claim(key)
        if ev is None:
            # hit path is the per-lane scan hot path: refresh the gauges only
            # every 64th hit (builds/clears always push) — the counters are
            # observability, not accounting, and may lag a scan by a few hits
            if self.hits % 64 == 1:
                self._push_metrics()
            return got
        try:
            from galaxysql_tpu.utils import tracing as _tr
            tc = _tr.current()
            # (a miss: off the hot path) the upload itself is a span in the
            # profiler's trace while a session records this statement
            table = getattr(getattr(store, "table", None), "name", "lanes")
            with _tr.annotation(f"transfer:{table}", column=column):
                dev = jax.device_put(builder(), device)
            nbytes = int(dev.nbytes)
            TRANSFER_STATS["bytes"] += nbytes
            TRANSFER_STATS["transfers"] += 1
            if tc is not None:
                tc.event(f"h2d:{column}", kind="transfer", bytes=nbytes)
            with self._lock:
                self.misses += 1
                self._map[key] = dev
                self._bytes += nbytes
                while self._bytes > self.budget and len(self._map) > 1:
                    _, old = self._map.popitem(last=False)
                    old_bytes = old.nbytes if hasattr(old, "nbytes") else 0
                    self._bytes -= old_bytes
                    self.evictions += 1
                    self.evicted_bytes += old_bytes
        finally:
            with self._lock:
                self._building.pop(key, None)
            ev.set()
        self._push_metrics()
        return dev

    def get_lane(self, store, pid: int, column: str, version: int,
                 host_data: np.ndarray) -> Any:
        return self.get_lane_built(store, pid, column, version,
                                   int(host_data.shape[0]), lambda: host_data)

    def clear(self):
        with self._lock:
            self._map.clear()
            self._bytes = 0
        self._push_metrics()


GLOBAL_DEVICE_CACHE = DeviceCache()
