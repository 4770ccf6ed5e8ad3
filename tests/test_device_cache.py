"""DeviceCache: single-flight builds, byte accounting, LRU, typed metrics.

The double-build race this guards: two threads missing the same key must not
BOTH run the (possibly O(table)) builder and insert — one builds, the rest
wait on the per-key event and adopt its entry, keeping `_bytes` exact.
"""

import threading
import time

import jax
import numpy as np
import pytest

from galaxysql_tpu.exec.device_cache import DeviceCache


class _Store:
    def __init__(self, uid=1):
        self.uid = uid


class TestSingleFlight:
    def test_concurrent_misses_build_once(self):
        cache = DeviceCache()
        store = _Store()
        builds = []
        barrier = threading.Barrier(8)

        def builder():
            builds.append(1)
            time.sleep(0.02)  # widen the race window
            return np.arange(1024, dtype=np.int64)

        out = [None] * 8

        def worker(i):
            barrier.wait()
            out[i] = cache.get_lane_built(store, 0, "c", 1, 1024, builder)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(builds) == 1          # the builder ran exactly once
        assert cache.misses == 1
        assert cache.hits == 7
        first = out[0]
        assert all(o is first for o in out)  # everyone adopted ONE entry
        assert cache._bytes == int(first.nbytes)  # no double count

    def test_stress_many_keys_exact_bytes(self):
        cache = DeviceCache()
        store = _Store()
        n_threads, n_keys = 8, 16
        lane = np.arange(256, dtype=np.int64)

        def worker():
            for k in range(n_keys):
                cache.get_lane(store, k, "c", 1, lane)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert cache.misses == n_keys
        assert cache.hits == n_threads * n_keys - n_keys
        assert len(cache._map) == n_keys
        assert cache._bytes == sum(v.nbytes for v in cache._map.values())

    def test_failed_build_releases_waiters(self):
        cache = DeviceCache()
        store = _Store()
        calls = []

        def failing():
            calls.append(1)
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError):
            cache.get_lane_built(store, 0, "c", 1, 8, failing)
        # the key is not poisoned: the next caller becomes the builder
        got = cache.get_lane_built(store, 0, "c", 1, 8,
                                   lambda: np.arange(8, dtype=np.int64))
        assert int(np.asarray(got).sum()) == 28
        assert len(calls) == 1


class TestEvictionAndVersioning:
    def test_lru_eviction_keeps_bytes_under_budget(self):
        lane = np.arange(1024, dtype=np.int64)
        cache = DeviceCache(budget_bytes=3 * lane.nbytes)
        store = _Store()
        for k in range(6):
            cache.get_lane(store, k, "c", 1, lane)
        assert cache._bytes <= cache.budget
        assert len(cache._map) <= 3
        # the most recent key survived
        assert (store.uid, 5, "c", 1, 1024, jax.devices()[0]) in cache._map

    def test_version_bump_is_a_miss(self):
        cache = DeviceCache()
        store = _Store()
        lane = np.arange(16, dtype=np.int64)
        cache.get_lane(store, 0, "c", 1, lane)
        cache.get_lane(store, 0, "c", 2, lane)
        assert cache.misses == 2 and cache.hits == 0


class TestDevicePlacement:
    def test_pinned_lane_is_not_what_an_unpinned_scan_reads(self):
        """A lane first loaded under a `jax.default_device` pin (the TP host
        path pins the CPU device) is keyed by that device: the next unpinned
        (AP) read of the same table version uploads its own copy to the
        default device instead of adopting the pinned one."""
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs two devices to tell the placements apart")
        cache = DeviceCache()
        store = _Store()
        builds = []

        def builder():
            builds.append(1)
            return np.arange(64, dtype=np.int64)

        with jax.default_device(devs[1]):
            pinned = cache.get_lane_built(store, 0, "c", 1, 64, builder)
            again = cache.get_lane_built(store, 0, "c", 1, 64, builder)
        assert pinned.devices() == {devs[1]} and again is pinned
        ap = cache.get_lane_built(store, 0, "c", 1, 64, builder)
        assert ap.devices() == {devs[0]}
        assert ap is not pinned and len(builds) == 2
        assert cache.misses == 2 and cache.hits == 1
        np.testing.assert_array_equal(np.asarray(ap), np.asarray(pinned))


class TestMetrics:
    def test_typed_registry_gauges(self):
        from galaxysql_tpu.utils.metrics import MetricsRegistry
        reg = MetricsRegistry()
        cache = DeviceCache()
        cache.bind_metrics(reg)
        store = _Store()
        lane = np.arange(32, dtype=np.int64)
        cache.get_lane(store, 0, "c", 1, lane)
        cache.get_lane(store, 0, "c", 1, lane)
        rows = {n: v for n, _k, v, _h in reg.rows()}
        assert rows["device_cache_hits"] == 1
        assert rows["device_cache_misses"] == 1
        assert rows["device_cache_bytes"] == cache._bytes
        assert rows["device_cache_entries"] == 1

    def test_instance_binds_global_cache(self):
        from galaxysql_tpu.server.instance import Instance
        inst = Instance()
        names = {n for n, *_ in inst.metrics.rows()}
        assert {"device_cache_hits", "device_cache_misses",
                "device_cache_bytes"} <= names
