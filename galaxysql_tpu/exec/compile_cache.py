"""Persistent AOT compile cache: restart without the XLA recompile storm.

Every coordinator restart — routine since heal-resume (PR 11) and crash-
resumable rebalance (PR 15) — re-pays the full trace+compile cost of the
steady-state program set that `COMPILE_STATS` measures.  This module
serializes compiled XLA executables (`jax.experimental.serialize_executable`)
keyed by the SAME lifted keys `global_jit` already retraces on, so a restarted
process replays its programs from `data_dir` instead of recompiling them.

Lifecycle (all hooks are no-ops while detached, so the cache costs nothing in
library use and cannot leak across tests):

- `Instance.boot` attaches `<data_dir>/compile_cache` when
  ENABLE_COMPILE_CACHE is set (and detaches when booting memory-only).
- `global_jit` consults `load()` on an in-memory miss BEFORE running the
  builder: a disk hit deserializes the executable, counts a `cache_hits` (NOT
  a retrace — the zero-steady-retrace discipline is the entire point), and
  returns a thin calling wrapper.  Any failure — wrong fingerprint, truncated
  pickle, shape mismatch at call time — falls back to the builder and deletes
  the bad entry: a corrupt cache recompiles, it never errors.
- `_timed_first_call` calls `observe()` after a fresh program's first
  invocation, recording the key + input treedef/specs (the executable itself
  stays only in `_JIT_CACHE`, this module holds no strong program refs).
- `Instance.save` calls `flush()`: observed programs still resident in
  `_JIT_CACHE` are AOT-lowered from the recorded specs, serialized, and
  written atomically; then the on-disk set is LRU-trimmed (by mtime) to
  COMPILE_CACHE_BYTES.

Entries are versioned and fingerprinted (jax version, backend, device kind +
count, host CPU ISA) — an upgrade or topology change invalidates by miss, not
by error.  Calling convention is FLAT: specs describe the flattened leaves
and the wrapper re-flattens call args, because operator pytrees (Column /
ColumnBatch) carry aux data (dtype tags, dictionary refs) whose identity
cannot round-trip through serialization.
"""

from __future__ import annotations

import hashlib
import io
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax

from galaxysql_tpu.runtime import exec_platform, host_isa_id

_LOG = logging.getLogger(__name__)

_FORMAT_VERSION = 2  # 2: entries record their execution devices


def _assigned_device_ids(compiled) -> list:
    """Device ids a compiled program executes on, in assignment order, read
    off its shardings (every sharding of one program spans the same set)."""
    for s in jax.tree_util.tree_leaves(
            (compiled.output_shardings, compiled.input_shardings)):
        mesh = getattr(s, "mesh", None)
        if mesh is not None:
            return [int(d.id) for d in mesh.devices.flat]
        return sorted(int(d.id) for d in s.device_set)
    raise ValueError("compiled program has no sharding to read devices from")


class CompileCache:
    """Disk-backed AOT executable cache (singleton: GLOBAL_COMPILE_CACHE)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dir: Optional[str] = None
        self._budget = 256 << 20
        # key -> (treedef, leaf_specs): what flush() needs to AOT-lower the
        # program again.  NO strong refs to programs — _JIT_CACHE owns those.
        self._observed: Dict[Tuple, Tuple[Any, tuple]] = {}
        self._fp: Optional[str] = None
        self.hits = 0
        self.misses = 0
        self.stores = 0
        # restored programs whose first call raised and fell back to a live
        # build (each also counts a retrace): 0 in a healthy round trip
        self.call_fallbacks = 0
        self._metrics_refs: list = []

    # -- lifecycle ----------------------------------------------------------

    def attach(self, path: str, budget: Optional[int] = None):
        os.makedirs(path, exist_ok=True)
        with self._lock:
            self._dir = path
            if budget is not None:
                self._budget = int(budget)
        self._push_metrics()

    def detach(self):
        with self._lock:
            self._dir = None
            self._observed.clear()

    @property
    def attached(self) -> bool:
        return self._dir is not None

    # -- identity -----------------------------------------------------------

    def _fingerprint(self) -> str:
        if self._fp is None:
            devs = jax.devices()
            kind = devs[0].device_kind if devs else "none"
            self._fp = "|".join([
                f"v{_FORMAT_VERSION}", jax.__version__, jax.default_backend(),
                f"{len(devs)}x{kind}", host_isa_id(),
            ])
        return self._fp

    def _path_for(self, key: Tuple) -> str:
        assert self._dir is not None
        name = hashlib.sha256(
            (repr(key) + "|" + self._fingerprint()).encode()).hexdigest()[:32]
        return os.path.join(self._dir, name + ".aot")

    # -- capture ------------------------------------------------------------

    def observe(self, key: Tuple, f, signature: tuple):
        """Record a freshly compiled program's input signature (`(treedef,
        specs)` as `exec/programs.py:abstract_signature` makes it for the
        registry: shapes, dtypes and the leaves' SHARDINGS, since a program
        whose steady-state args are mesh-sharded must be AOT-lowered for that
        sharding or the restored executable rejects every call) for a later
        flush().  Called from the first-invocation path: cheap."""
        if self._dir is None or not hasattr(f, "lower"):
            return  # detached; host-np programs / plain closures
        if exec_platform() != jax.default_backend():
            # ran under the TP path's CPU pin on an accelerator host:
            # flush() would AOT-lower it for the accelerator instead
            return
        with self._lock:
            if self._dir is not None:
                self._observed[key] = signature

    # -- restore ------------------------------------------------------------

    def load(self, key: Tuple, builder):
        """Disk lookup for `global_jit`: a hit returns a calling wrapper, any
        miss/failure returns None (the caller runs the builder).  The wrapper
        itself falls back to the builder on call-time mismatch — a disk entry
        can never make a query error."""
        with self._lock:
            d = self._dir
        if d is None:
            return None
        path = self._path_for(key)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as fh:
                rec = pickle.load(fh)
            if (rec.get("v") != _FORMAT_VERSION
                    or rec.get("fp") != self._fingerprint()
                    or rec.get("key") != repr(key)):
                raise ValueError("stale compile-cache entry")
            from jax.experimental import serialize_executable as se
            # load for the devices the program was compiled for, in their
            # assignment order (jax otherwise assumes ALL devices and every
            # single-device program fails its first call on a multi-device
            # host); a recorded id this process lacks is a stale entry
            by_id = {d.id: d for d in jax.devices()}
            loaded = se.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in rec["devices"]])
        except FileNotFoundError:
            self.misses += 1
            self._push_metrics()
            return None
        except Exception:
            # corruption tolerance: drop the entry, recompile, never error
            try:
                os.remove(path)
            except OSError:
                pass
            self.misses += 1
            self._push_metrics()
            return None

        dt_ms = (time.perf_counter() - t0) * 1000.0
        from galaxysql_tpu.exec import operators as ops
        self.hits += 1
        ops.COMPILE_STATS["cache_hits"] += 1
        ops.COMPILE_STATS["compile_ms"] += dt_ms
        try:
            os.utime(path)  # LRU recency for the disk trim
        except OSError:
            pass
        self._push_metrics()

        cell = {"fb": None}

        def cached_program(*args, **kw):
            fb = cell["fb"]
            if fb is not None:
                return fb(*args, **kw)
            if not kw:
                try:
                    return loaded(*jax.tree_util.tree_leaves(args))
                except Exception:
                    self.call_fallbacks += 1
                    _LOG.warning("compile cache: restored program %r "
                                 "rejected its call; rebuilding live",
                                 key[0] if key else key, exc_info=True)
            # call-time mismatch (e.g. a shape-polymorphic key whose arrays
            # changed): build live and stay on the built program thereafter
            f2 = builder()
            ops.COMPILE_STATS["retraces"] += 1
            cell["fb"] = f2
            return f2(*args, **kw)

        return cached_program

    # -- persist ------------------------------------------------------------

    def flush(self):
        """Serialize observed programs still resident in `_JIT_CACHE` to disk
        (called from Instance.save).  Per-entry failures are skipped — a
        checkpoint never fails because an executable would not serialize."""
        with self._lock:
            d = self._dir
            todo = list(self._observed.items())
        if d is None or not todo:
            return
        from galaxysql_tpu.exec import operators as ops
        from jax.experimental import serialize_executable as se
        for key, (treedef, specs) in todo:
            path = self._path_for(key)
            if os.path.exists(path):
                continue
            with ops._JIT_CACHE_LOCK:
                f = ops._JIT_CACHE.get(key)
            if f is None or not hasattr(f, "lower"):
                continue  # evicted, or still a first-call wrapper
            try:
                def flat(*lv, _f=f, _td=treedef):
                    return _f(*jax.tree_util.tree_unflatten(_td, lv))
                # a restored executable keeps its family's module name
                flat.__name__ = flat.__qualname__ = ops.program_family(key)

                # AOT path: lower the flat adapter against the recorded
                # specs; the executable identity/caching stays in global_jit
                compiled = jax.jit(flat).lower(*specs).compile()  # galaxylint: disable=jit-raw -- serialization adapter, exists only to .lower(); never dispatched
                payload, in_tree, out_tree = se.serialize(compiled)
                rec = {"v": _FORMAT_VERSION, "fp": self._fingerprint(),
                       "key": repr(key), "payload": payload,
                       "in_tree": in_tree, "out_tree": out_tree,
                       "devices": _assigned_device_ids(compiled)}
                buf = io.BytesIO()
                pickle.dump(rec, buf)
                tmp = path + ".tmp"
                with open(tmp, "wb") as fh:
                    fh.write(buf.getvalue())
                os.replace(tmp, path)  # atomic: readers never see a torn file
                self.stores += 1
            except Exception:
                continue
        self._trim()
        self._push_metrics()

    def _trim(self):
        """Byte-budgeted LRU on disk: evict oldest-mtime entries over budget."""
        d = self._dir
        if d is None:
            return
        try:
            ents = [(e.stat().st_mtime, e.stat().st_size, e.path)
                    for e in os.scandir(d) if e.name.endswith(".aot")]
        except OSError:
            return
        ents.sort(reverse=True)  # newest first
        used = 0
        for mtime, size, path in ents:
            used += size
            if used > self._budget:
                try:
                    os.remove(path)
                except OSError:
                    pass

    def disk_bytes(self) -> int:
        d = self._dir
        if d is None:
            return 0
        try:
            return sum(e.stat().st_size for e in os.scandir(d)
                       if e.name.endswith(".aot"))
        except OSError:
            return 0

    def disk_entries(self) -> int:
        d = self._dir
        if d is None:
            return 0
        try:
            return sum(1 for e in os.scandir(d) if e.name.endswith(".aot"))
        except OSError:
            return 0

    # -- observability ------------------------------------------------------

    def bind_metrics(self, registry):
        """Mirror counters into a metrics registry (SHOW METRICS/Prometheus).
        Weakrefs: a dropped Instance must not pin its registry."""
        import weakref
        self._metrics_refs.append(weakref.ref(registry))
        self._push_metrics()

    def _push_metrics(self):
        if not self._metrics_refs:
            return
        alive = []
        for ref in self._metrics_refs:
            m = ref()
            if m is None:
                continue
            alive.append(ref)
            try:
                m.gauge("compile_cache_hits",
                        "persistent AOT cache: programs restored from disk"
                        ).set(self.hits)
                m.gauge("compile_cache_misses",
                        "persistent AOT cache: disk lookups that recompiled"
                        ).set(self.misses)
                m.gauge("compile_cache_bytes",
                        "persistent AOT cache: bytes on disk").set(
                            self.disk_bytes())
                m.gauge("compile_cache_entries",
                        "persistent AOT cache: entries on disk").set(
                            self.disk_entries())
            except Exception:
                continue
        self._metrics_refs = alive


GLOBAL_COMPILE_CACHE = CompileCache()
