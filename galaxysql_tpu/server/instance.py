"""Engine instance: the in-process root object (CobarServer/TDataSource analog).

Owns the catalog, table stores, planner, TSO, metadb (GMS), DDL engine, and config
(SURVEY.md §2.2/§3.1 boot path).  Sessions (`server/session.py`) hang off an Instance
the way ServerConnections hang off CobarServer.  `boot()` mirrors
`MatrixConfigHolder.doInit`: load catalog from the metadb, attach stores, reload
persisted partitions, then resume interrupted DDL jobs (§3.5 crash recovery).
"""

from __future__ import annotations

import json
import os
import threading
import time as _time
import uuid
from typing import Dict, Optional

from galaxysql_tpu.config.params import ConfigParams
from galaxysql_tpu.meta.catalog import Catalog, TableMeta
from galaxysql_tpu.meta.gms import ConfigListener, MetaDb
from galaxysql_tpu.meta.tso import TimestampOracle
from galaxysql_tpu.plan.planner import Planner
from galaxysql_tpu.storage.table_store import TableStore
from galaxysql_tpu.utils import errors


class Instance:
    def __init__(self, data_dir: Optional[str] = None, boot: bool = True):
        import jax
        if not jax.config.jax_enable_x64:
            # the package import enables it; someone switched it back off.
            # int64 decimal/key lanes would silently truncate to 32 bits.
            raise errors.TddlError(
                "galaxysql_tpu needs jax_enable_x64 (64-bit decimal and key "
                "lanes); it was disabled after `import galaxysql_tpu`")
        self.catalog = Catalog()
        self.stores: Dict[str, TableStore] = {}
        self.planner = Planner(self.catalog)
        self.tso = TimestampOracle()
        self.config = ConfigParams()
        self.data_dir = data_dir
        self.metadb = MetaDb(os.path.join(data_dir, "metadb.sqlite")
                             if data_dir else None)
        self.config_listener = ConfigListener(self.metadb)
        from galaxysql_tpu.ddl.jobs import DdlEngine
        self.ddl_engine = DdlEngine(self)
        from galaxysql_tpu.meta.sequence import SequenceManager
        self.sequences = SequenceManager(self.metadb)
        from galaxysql_tpu.meta.privileges import PrivilegeManager
        self.privileges = PrivilegeManager(self.metadb)
        from galaxysql_tpu.txn.xa import TwoPhaseCoordinator
        self.xa_coordinator = TwoPhaseCoordinator(self)
        from galaxysql_tpu.utils.locks import LockingFunctionManager
        self.locks = LockingFunctionManager()
        from galaxysql_tpu.txn.cdc import CdcManager
        # ordered change log keyed by commit TSO (CdcManager.java:135)
        self.cdc = CdcManager(self)
        from galaxysql_tpu.meta.mdl import MdlManager
        # per-table metadata locks: statements hold SHARED for their duration,
        # DDL cutover (repartition swap) takes EXCLUSIVE (MdlManager.java:35)
        self.mdl = MdlManager()
        from galaxysql_tpu.server.scheduler import ScheduledJobManager
        self.scheduler = ScheduledJobManager(self)
        from galaxysql_tpu.storage.archive import ArchiveManager
        self.archive = ArchiveManager(
            os.path.join(data_dir, "archive") if data_dir else None)
        self.node_id = f"cn-{uuid.uuid4().hex[:8]}"
        self.started_at = _time.time()  # /health + cluster-view uptime
        from galaxysql_tpu.net.dn import SyncBus
        self.workers: Dict[tuple, object] = {}  # (host, port) -> WorkerClient
        # origin rides every RPC with the bus epoch: workers key their
        # last-applied sync epoch per coordinator (net/worker sync healing)
        self.sync_bus = SyncBus(origin=self.node_id)
        from galaxysql_tpu.meta.ha import HaManager
        self.ha = HaManager(self)
        from galaxysql_tpu.utils.metrics import (BATCH_GROUP_SIZE,
                                                 BATCH_WAIT_MS, BREAKER_OPENS,
                                                 DML_GROUP_SIZE, DML_WAIT_MS,
                                                 MetricsRegistry, QUERY_TIMEOUTS,
                                                 RETRY_BUDGET_EXHAUSTED,
                                                 RPC_FAILURES, RPC_RETRIES,
                                                 RPC_RTT_MS, SEGMENT_WALL_MS,
                                                 SPILL_BYTES, SPILL_FILES,
                                                 SYNC_FAILURES, SYNC_HEALS,
                                                 WORKER_FAILOVERS)
        from galaxysql_tpu.utils.tracing import ProfileRing, TraceIdAllocator
        # typed counter/gauge registry: SQL (information_schema.metrics,
        # SHOW METRICS), web (/metrics Prometheus text) and the legacy
        # engine-counter surface all render from here
        self.metrics = MetricsRegistry()
        # process-shared latency histograms (segment dispatch wall, worker RPC
        # round-trip) surface through this instance's registry; query latency
        # is per-instance and observed in Session._finish_query
        self.metrics.adopt(SEGMENT_WALL_MS)
        self.metrics.adopt(RPC_RTT_MS)
        self.metrics.adopt(BATCH_GROUP_SIZE)
        self.metrics.adopt(BATCH_WAIT_MS)
        self.metrics.adopt(DML_GROUP_SIZE)
        self.metrics.adopt(DML_WAIT_MS)
        # fault-tolerance plane counters (net/dn.py retry/breaker, SyncBus
        # healing, deadline kills) — process-shared, surfaced per instance
        for m in (RPC_RETRIES, RPC_FAILURES, BREAKER_OPENS, WORKER_FAILOVERS,
                  SYNC_FAILURES, SYNC_HEALS, QUERY_TIMEOUTS,
                  RETRY_BUDGET_EXHAUSTED, SPILL_BYTES, SPILL_FILES):
            self.metrics.adopt(m)
        self.metrics.histogram("query_latency_ms",
                               "end-to-end query latency (ms)")
        # node-prefixed trace-id mint: peer coordinators (sync_peer setups)
        # must never stamp two queries with one id
        self.trace_ids = TraceIdAllocator(self.node_id)
        # dict-like view over typed counters (engine_counters virtual table);
        # `counters["x"] += 1` call sites keep working unchanged
        self.counters = self.metrics.counter_map("engine")
        # cross-query fragment cache (exec/fragment_cache.py): versioned
        # hash-join build artifacts, deterministic subplan results, cached
        # runtime-filter publications.  Per-instance so multi-coordinator
        # tests stay isolated; frag_cache_* metrics ride this registry.
        from galaxysql_tpu.exec.fragment_cache import FragmentCache
        self.frag_cache = FragmentCache(metrics=self.metrics)
        # device lane cache observability: device_cache_* gauges alongside
        # the frag_cache_* family in SHOW METRICS / /metrics
        from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE
        GLOBAL_DEVICE_CACHE.bind_metrics(self.metrics)
        # last-N per-query runtime profiles (information_schema.query_stats,
        # SHOW FULL STATS, web /query/<trace_id>)
        self.profiles = ProfileRing()
        # tail-sampled trace retention (utils/tracing.TraceStore): every
        # query's finish ramp offers its span tree — per-digest head sample
        # for healthy traces, always-keep for slow/shed/errored — into this
        # byte-budgeted per-node ring; the flight recorder and SHOW TRACE
        # cluster pulls read it
        from galaxysql_tpu.utils.tracing import TraceStore
        self.trace_store = TraceStore(
            budget_bytes=int(self.config.get("TRACE_STORE_BUDGET_BYTES")
                             or (4 << 20)),
            rate=float(self.config.get("TRACE_SAMPLE_RATE") or 0.0),
            node=self.node_id)
        # statement-digest workload-insight store (meta/statement_summary.py):
        # per digest x plan fingerprint time-windowed aggregates + the
        # plan-regression sentinel; fed by Session._finish_query
        from galaxysql_tpu.meta.statement_summary import StatementSummaryStore
        self.stmt_summary = StatementSummaryStore(self)
        # (schema, parameterized-sql) -> PointPlan: binder-free execution of
        # archetypal point SELECTs (DirectShardingKeyTableOperation analog)
        self.point_plans: Dict[tuple, object] = {}
        # (workload, engine) -> bound metric handles for Session._finish_query
        # (registry name-sanitize + lookup x4 per query is measurable at TP
        # serving rates; the handle tuple is immutable so plain dict is safe)
        self.finish_metrics: Dict[tuple, tuple] = {}
        # cross-session point-query batching (server/batch_scheduler.py):
        # plan-cache-identical point reads arriving within the collection
        # window coalesce into one vectorized dispatch per partition
        from galaxysql_tpu.server.batch_scheduler import BatchScheduler
        self.batch_scheduler = BatchScheduler(self)
        # cross-session DML batching (server/dml_batch.py): plan-identical
        # autocommit point writes coalesce into one vectorized flush with a
        # shared flush-time TSO, coalesced CDC/version bumps, and async
        # GSI/replica apply — the write-side mirror of the read batcher
        from galaxysql_tpu.server.dml_batch import DmlBatchScheduler
        self.dml_batch_scheduler = DmlBatchScheduler(self)
        # (schema, parameterized-sql) -> DML batch plan (write-side
        # PointPlans; server/dml_batch.try_register)
        self.dml_plans: Dict[tuple, dict] = {}
        # background applier for GSI maintenance + replica DML legs with
        # read-your-writes watermark fencing (txn/async_apply.py)
        from galaxysql_tpu.txn.async_apply import AsyncApplier
        self.applier = AsyncApplier(self)
        # columnar HTAP replica (storage/columnar.py): CDC-fed delta+base
        # tier serving large AP scans at a TSO watermark while TP stays on
        # the row store; sessions route through it in _run_query_admitted
        from galaxysql_tpu.storage.columnar import ColumnarReplicaManager
        self.columnar = ColumnarReplicaManager(self)
        # overload plane (server/admission.py): workload-class admission gate
        # (AIMD limits, deadline-aware shedding) + the memory-pressure
        # governor (tiered fragment-cache/spill/AP-refusal responses)
        from galaxysql_tpu.server.admission import AdmissionController
        self.admission = AdmissionController(self)
        # SLO plane (utils/metric_history.py + server/slo.py): bounded
        # delta-encoded history of every scalar this node exposes, and the
        # burn-rate / anomaly engine judging it.  Sampled by the maintain
        # loop via slo_tick(); workers run the same sampler over their own
        # registries and the `health` sync action pulls their snapshots.
        from galaxysql_tpu.utils.metric_history import MetricHistory
        self.metric_history = MetricHistory(self)
        from galaxysql_tpu.server.slo import SloEngine
        self.slo = SloEngine(self)
        # incident flight recorder (server/flight_recorder.py): watches the
        # event journal for trigger kinds on every slo_tick and snapshots
        # correlated evidence bundles into data_dir/incidents/
        from galaxysql_tpu.server.flight_recorder import FlightRecorder
        self.recorder = FlightRecorder(self)
        from galaxysql_tpu.server.maintain import RecycleBin
        self.recycle = RecycleBin(self)
        # elastic rebalancing (ddl/rebalance.py + server/balancer.py): the
        # in-memory half of live jobs' shadow partitions, and the heat-driven
        # proposal/execution policy the maintain loop ticks
        self.rebalance_shadows: Dict[str, object] = {}
        from galaxysql_tpu.server.balancer import Balancer
        self.balancer = Balancer(self)
        # physical placement bindings (server/placement.py): group label ->
        # worker endpoint / coordinator / device, persisted in the shared
        # metadb so MOVE PARTITION changes real locality cluster-wide
        from galaxysql_tpu.server.placement import PlacementBinding
        self.placement = PlacementBinding(self)
        # serving tier peer registry: node_id -> sync endpoint (sync_peer()
        # object or a dn-wire client to a remote coordinator's sync listener).
        # Maintained by attach_coordinator/detach_coordinator; the front
        # router (server/router.py) and the SHOW CLUSTER merges read it.
        self.coordinators: Dict[str, object] = {}
        # named for the lockdep witness (unranked class "instance"); a plain
        # RLock when lockdep is disarmed — the default
        from galaxysql_tpu.utils.lockdep import named_lock
        self.lock = named_lock("instance")
        self.next_conn_id = 1
        self.sessions: Dict[int, object] = {}
        self.catalog.create_schema("information_schema", if_not_exists=True)
        if boot:
            self.boot()

    def finish_handles(self, workload: str, engine: str) -> tuple:
        """(latency histogram, total/workload/engine counters) bound once per
        (workload, engine) — shared by Session._finish_query and the batch
        scheduler's bulk group finish."""
        handles = self.finish_metrics.get((workload, engine))
        if handles is None:
            m = self.metrics
            handles = (m.histogram("query_latency_ms",
                                   "end-to-end query latency (ms)"),
                       m.counter("queries_total", "queries executed"),
                       m.counter(f"queries_{workload.lower()}",
                                 f"{workload} workload queries"),
                       m.counter(f"engine_exec_{engine}",
                                 f"queries served by the {engine} engine"))
            self.finish_metrics[(workload, engine)] = handles
        return handles

    # -- boot ------------------------------------------------------------------

    def _reload_global_config(self, *_):
        """Pull persisted SET GLOBAL values from the shared metadb (fired by
        the config listener when a peer coordinator changes one)."""
        for k, v in self.metadb.kv_scan("config.param."):
            try:
                self.config.set_instance(k[len("config.param."):], json.loads(v))
            except Exception:
                continue  # an unknown/stale param must not poison boot

    def boot(self):
        """Load persisted metadata + data, then recover interrupted DDL jobs."""
        # persistent AOT compile cache: attach FIRST so every program traced
        # during/after boot can be replayed from disk on the next restart.
        # Booting without a data_dir DETACHES — the cache is process-global
        # and a later memory-only instance must not inherit another's dir.
        from galaxysql_tpu.exec.compile_cache import GLOBAL_COMPILE_CACHE
        if self.data_dir and self.config.get("ENABLE_COMPILE_CACHE"):
            GLOBAL_COMPILE_CACHE.attach(
                os.path.join(self.data_dir, "compile_cache"),
                budget=int(self.config.get("COMPILE_CACHE_BYTES")))
            GLOBAL_COMPILE_CACHE.bind_metrics(self.metrics)
        else:
            GLOBAL_COMPILE_CACHE.detach()
        self.planner.spm.attach(self.metadb)
        self.config_listener.bind("config.params", self._reload_global_config)
        self._reload_global_config()
        loaded = self.metadb.load_catalog(self.catalog)
        for tm in loaded:
            store = self.register_table(tm, persist=False)
            if self.data_dir:
                d = os.path.join(self.data_dir, tm.schema.lower(), tm.name.lower())
                if os.path.isdir(d):
                    store.load(d)
        # restore the checkpointed catalog counters: replaying schema loads
        # re-derives schema_version differently than the live history did,
        # which would silently invalidate every persisted SPM baseline (and
        # with them the self-heal quarantine state) on restart.  max() so the
        # counters never run backwards past the replayed DDL.
        v = self.metadb.kv_get("catalog.versions")
        if v:
            try:
                parts = json.loads(v)
                self.catalog.version = max(self.catalog.version,
                                           int(parts[0]))
                self.catalog.schema_version = max(self.catalog.schema_version,
                                                  int(parts[1]))
                if len(parts) > 2:  # added with the self-heal stats epoch
                    self.catalog.stats_version = max(
                        self.catalog.stats_version, int(parts[2]))
            except Exception:
                pass  # a corrupt counter record must not poison boot
        self.archive.attach(self.metadb)
        # columnar replicas restore AFTER stores/dictionaries load (persisted
        # stripe lanes hold dictionary codes) and resume tailing from the
        # checkpointed binlog seq
        self.columnar.load()
        # resolve provisional ±txn_id MVCC stamps left by a crash against the
        # durable tx log BEFORE anything reads the loaded partitions
        from galaxysql_tpu.txn.xa import recover_persisted
        recover_persisted(self)
        self.metadb.heartbeat(self.node_id, "coordinator", "127.0.0.1", 0)
        self.ddl_engine.recover()

    # -- store management ------------------------------------------------------

    def store_key(self, schema: str, table: str) -> str:
        return f"{schema.lower()}.{table.lower()}"

    def register_table(self, tm: TableMeta, persist: bool = True) -> TableStore:
        store = TableStore(tm)
        self.stores[self.store_key(tm.schema, tm.name)] = store
        if persist:
            self.metadb.save_table(tm)
        return store

    def drop_store(self, schema: str, table: str):
        self.stores.pop(self.store_key(schema, table), None)
        self.metadb.drop_table(schema, table)

    def store(self, schema: str, table: str) -> TableStore:
        return self.stores[self.store_key(schema, table)]

    # -- persistence -----------------------------------------------------------

    def save(self):
        """Flush all table data + metadata to disk (checkpoint)."""
        if not self.data_dir:
            return
        # pending async GSI/replica applies must land before the snapshot:
        # a checkpoint taken mid-apply would persist a base table whose GSI
        # rows exist only in the in-memory queue — and that queue has no
        # redo source, so saving anyway would freeze the divergence forever.
        # A wedged applier therefore fails the checkpoint LOUDLY.
        applier = getattr(self, "applier", None)
        if applier is not None and not applier.drain():
            raise errors.TddlError(
                "checkpoint aborted: async GSI/replica applies did not "
                "drain (backlog wedged); retry after the applier recovers")
        # marker time is captured BEFORE the store snapshots: a txn committing
        # while save() runs may have provisional stamps in an already-written
        # npz, so tx-log purge may only drop entries resolved before this point
        import time
        t0 = time.time()
        for key, store in self.stores.items():
            store.save(os.path.join(self.data_dir, key.replace(".", os.sep)))
            self.metadb.save_table(store.table)
        self.metadb.kv_put("last_checkpoint_at", repr(t0))
        # columnar replica checkpoint rides the same save: stripe lanes hold
        # dictionary codes, so persisting them beside the stores' own
        # dictionaries.json keeps the code spaces consistent on reload
        self.columnar.save()
        # catalog counters ride the checkpoint so a restarted coordinator
        # keeps its persisted SPM baselines + heal state valid (see boot())
        self.metadb.kv_put("catalog.versions", json.dumps(
            [self.catalog.version, self.catalog.schema_version,
             self.catalog.stats_version]))
        # AOT-serialize this process's steady-state programs alongside the
        # checkpoint; best-effort — a program that won't serialize must never
        # fail a data checkpoint
        try:
            from galaxysql_tpu.exec.compile_cache import GLOBAL_COMPILE_CACHE
            GLOBAL_COMPILE_CACHE.flush()
        except Exception:  # galaxylint: disable=swallow -- best-effort AOT flush: a serialization failure must never fail the data checkpoint (per-entry errors are already handled inside flush)
            pass

    def allocate_conn_id(self) -> int:
        with self.lock:
            cid = self.next_conn_id
            self.next_conn_id += 1
            return cid

    def worker_client(self, host: str, port: int):
        """Get-or-create the WorkerClient for an endpoint, configured from
        instance params (retry budget, breaker thresholds) and wired into the
        sync bus — the ONE constructor for coordinator->worker connections."""
        from galaxysql_tpu.net.dn import WorkerClient
        key = (host, port)
        client = self.workers.get(key)
        if client is None:
            # bind the live config: SET GLOBAL RPC_*/BREAKER_* hatches apply
            # to already-attached workers, not just future attachments
            client = WorkerClient(host, port, config=self.config)
            self.workers[key] = client
            self.sync_bus.attach(client)
        return client

    def worker_rows(self):
        """SHOW WORKERS / information_schema.workers row source: one row per
        attached worker with fence + circuit-breaker state and lifetime
        retry/failure counters."""
        rows = []
        for (host, port), client in sorted(self.workers.items()):
            bk = client.breaker_snapshot() if hasattr(client, "breaker_snapshot") \
                else {"state": "closed", "consec_failures": 0, "opens": 0,
                      "retries": 0, "failures": 0, "last_error": ""}
            budget = getattr(client, "retry_budget", None)
            rows.append((host, port, bk["state"],
                         1 if self.ha.worker_fenced((host, port)) else 0,
                         bk["consec_failures"], bk["retries"], bk["failures"],
                         bk["opens"], bk["last_error"],
                         int(budget.remaining()) if budget is not None else 0))
        return rows

    # -- SLO plane ------------------------------------------------------------

    def slo_tick(self, now: Optional[float] = None,
                 force: bool = False) -> bool:
        """One SLO-plane tick: take a history sample (interval-gated
        unless `force`) and, when one lands, burn-rate every objective
        and rate-anomaly every counter.  Driven by the maintain loop on
        every poll (per-node — NOT leader-gated like scheduled jobs) and
        by tests with synthetic `now` stamps.  Advisory: never raises."""
        try:
            mh = self.metric_history
            sampled = mh.sample(now=now) if force else mh.maybe_sample(now=now)
            if sampled is None:
                return False
            self.slo.evaluate(now=now)
            rec = getattr(self, "recorder", None)
            if rec is not None:
                rec.tick(now=now)
            return True
        except Exception:  # galaxylint: disable=swallow -- advisory plane: a sampler fault must never affect serving (pragma: no cover)
            return False

    def cluster_health(self, pull: bool = True):
        """Cluster-wide health rows: this coordinator first, then one row
        per attached worker.  `pull=True` issues the `health` sync action
        (fresh per-worker sampler snapshots; an unreachable worker gets an
        UNREACHABLE row, never an exception); `pull=False` renders from
        piggybacked reply telemetry only — info_schema refresh uses that
        so a wedged worker cannot stall a catalog query."""
        mh = self.metric_history
        burning = self.slo.burning_names()
        rows = [(self.node_id, "coordinator", "local",
                 "BURNING" if burning else "OK",
                 1 if self.ha.is_leader() else 0,
                 round(_time.time() - self.started_at, 3),
                 float(len(getattr(self, "sessions", []) or [])),
                 round(mh.rate("queries_total"), 3),
                 round(mh.rate("query_errors"), 6),
                 int(self.admission.governor.tier()),
                 ",".join(burning), int(mh.summary()["samples"]))]
        for (host, port), client in sorted(self.workers.items()):
            addr = f"{host}:{port}"
            fenced = self.ha.worker_fenced((host, port))
            if pull:
                try:
                    resp = client.sync_action("health", {})
                except Exception:  # galaxylint: disable=swallow -- the UNREACHABLE row below IS the failure report; the sync client journals breaker state
                    resp = None
                if not (isinstance(resp, dict) and resp.get("ok")):
                    rows.append((addr, "worker", addr, "UNREACHABLE",
                                 0, 0.0, 0.0, 0.0, 0.0, 0,
                                 "", 0))
                    continue
                rows.append((resp.get("node", addr), "worker", addr,
                             "FENCED" if fenced else "OK", 0,
                             round(float(resp.get("uptime_s", 0.0)), 3),
                             float(resp.get("active", 0)),
                             round(float(resp.get("qps", 0.0)), 3),
                             round(float(resp.get("error_rate", 0.0)), 6),
                             int(resp.get("mem_tier", 0)), "",
                             int(resp.get("samples", 0))))
            else:
                rows.append((addr, "worker", addr,
                             "FENCED" if fenced else "OK", 0,
                             round(float(getattr(client, "load_up", 0.0)), 3),
                             float(getattr(client, "load_q", 0) or 0),
                             0.0, 0.0,
                             int(getattr(client, "load_tier", 0) or 0), "",
                             int(getattr(client, "load_samples", 0) or 0)))
        return rows

    def attach_remote_table(self, schema: str, name: str, host: str,
                            port: int):
        """Register a worker-process table: scans compile to shipped SQL
        (MyJdbcHandler.java:691 plan-shipping seam).  The worker is also wired
        into the sync-action bus and the HA prober."""
        from galaxysql_tpu.types import datatype as dt
        from galaxysql_tpu.meta.catalog import ColumnMeta, TableMeta, SINGLE
        client = self.worker_client(host, port)
        resp = client.sync_action("table_meta", {"schema": schema,
                                                 "table": name})
        # (re)attachment is the reconnect point: resolve any XA branches this
        # worker holds in doubt against our commit-point log (XARecoverTask)
        try:
            self.xa_coordinator.recover_remote()
        except Exception:
            pass
        cols = [ColumnMeta(n, dt.from_sql_name(t, p or 0, s or 0), nullable)
                for n, t, p, s, nullable in resp["columns"]]
        tm = TableMeta(schema, name, cols, resp.get("primary_key") or [],
                       SINGLE)
        tm.remote = {"host": host, "port": port}
        self.catalog.create_schema(schema, if_not_exists=True)
        if not self.catalog.add_table(tm, if_not_exists=True):
            # re-attach (worker restarted on a new port): repoint the existing
            # meta so in-flight plans route to the live endpoint
            tm = self.catalog.table(schema, name)
            tm.remote = {"host": host, "port": port}
        return tm

    def attach_replica(self, schema: str, name: str, host: str, port: int,
                       weight: int = 1, backfill: Optional[bool] = None):
        """Register a read replica for a remote table (read-write splitting,
        `TGroupDataSource` weighted-random analog).  Writes go to every live
        endpoint as branches of the same distributed txn (synchronous
        replication); reads pick a weighted-random unfenced endpoint.

        A replica must hold the table's data BEFORE it serves reads:
        `backfill=None` (default) copies from the primary when the replica's
        table is missing or empty and trusts a pre-seeded identical copy
        otherwise; True forces the copy (rebuilding a STALE replica requires
        it); False trusts the caller unconditionally."""
        key = (host, port)
        client = self.worker_client(host, port)
        tm = self.catalog.table(schema, name)
        if getattr(tm, "remote", None) is None:
            raise errors.NotSupportedError(
                f"{schema}.{name} is not a remote table")
        entry = next((r for r in tm.replicas
                      if (r["host"], r["port"]) == key), None)
        if entry is not None and entry.get("stale") and backfill is not True:
            raise errors.TddlError(
                f"replica {key} is stale (missed writes); re-attach with "
                f"backfill=True to rebuild it")
        if backfill is None:
            backfill = self._replica_needs_backfill(client, schema, name)
        # the copy AND the routing registration sit under one EXCLUSIVE MDL:
        # a write committing between the snapshot read and registration would
        # otherwise reach only the primary — a replica registered one row
        # short serves wrong reads forever (writes replicate per-statement to
        # replicas registered at statement time, session._remote_dml)
        with self.mdl.exclusive(f"{schema.lower()}.{name.lower()}"):
            if backfill:
                self._backfill_replica(client, schema, name)
            if entry is not None:
                entry["weight"] = weight
                entry["stale"] = False
                return tm
            tm.replicas.append({"host": host, "port": port, "weight": weight,
                                "stale": False})
        return tm

    def _replica_needs_backfill(self, client, schema: str, name: str) -> bool:
        try:
            _cols, _types, data, _valid = client.execute(
                f"SELECT count(*) FROM {name}", schema)
            lane = next(iter(data.values())) if data else None
            return lane is None or lane.size == 0 or int(lane[0]) == 0
        except Exception:
            return True  # table (or schema) missing on the replica

    def _backfill_replica(self, client, schema: str, name: str):
        """Snapshot copy primary -> replica under shared MDL (writes keep
        flowing; they also ship to the replica's branch once registered, and
        registration happens only after this copy completes)."""
        tm = self.catalog.table(schema, name)
        src = self.workers[(tm.remote["host"], tm.remote["port"])]
        cols_sql = ", ".join(
            f"{c.name} {c.dtype.sql_name()}" + ("" if c.nullable else " NOT NULL")
            for c in tm.columns)
        pk_sql = (f", PRIMARY KEY ({', '.join(tm.primary_key)})"
                  if tm.primary_key else "")
        # IF NOT EXISTS makes these textually idempotent -> retry-safe
        client.execute(f"CREATE DATABASE IF NOT EXISTS {schema}", "",
                       idem=True)
        client.execute(
            f"CREATE TABLE IF NOT EXISTS {name} ({cols_sql}{pk_sql})", schema,
            idem=True)
        cols = tm.column_names()
        # caller (attach_replica) holds the exclusive MDL: no concurrent DML
        names, types, data, valid = src.exec_plan(
            {"schema": schema, "table": name, "columns": cols})
        self._bulk_insert_remote(client, schema, name, names, types,
                                 data, valid)

    @staticmethod
    def _sql_literal(typ: str, v, valid: bool) -> str:
        if not valid:
            return "NULL"
        if typ.endswith("#scaled"):
            import re as _re
            m = _re.search(r"DECIMAL\(\d+,\s*(\d+)\)", typ)
            scale = int(m.group(1)) if m else 0
            s = str(int(v))
            neg = s.startswith("-")
            s = s.lstrip("-").rjust(scale + 1, "0")
            val = (s[:-scale] + "." + s[-scale:]) if scale else s
            return ("-" if neg else "") + val
        if isinstance(v, (int, float)):
            return repr(v)
        return "'" + str(v).replace("\\", "\\\\").replace("'", "''") + "'"

    def _bulk_insert_remote(self, client, schema, table, names, types,
                            data, valid, batch: int = 1000):
        n = len(next(iter(data.values()))) if data else 0
        for off in range(0, n, batch):
            hi = min(off + batch, n)
            rows = []
            for i in range(off, hi):
                vals = []
                for c, ty in zip(names, types):
                    ok_ = bool(valid[c][i]) if c in valid else True
                    vals.append(self._sql_literal(ty, data[c][i], ok_))
                rows.append("(" + ", ".join(vals) + ")")
            # uid-stamped: a reconnect retry of a backfill batch replays the
            # recorded result (worker dedupe window) instead of double-
            # inserting rows into the replica
            client.execute(f"INSERT INTO {table} ({', '.join(names)}) "
                           f"VALUES {', '.join(rows)}", schema,
                           uid=f"{self.node_id}:{self.trace_ids.next()}")

    def move_remote_table(self, schema: str, name: str, host: str, port: int):
        """Relocate a worker-resident table to another worker online.

        Reference analog: `executor/balancer/Balancer.java` data movement +
        the repartition backfill/catchup/cutover shape (ddl/repartition.py):

        1. snapshot backfill under SHARED MDL (writes keep flowing to the
           source),
        2. delta catchup + cutover under EXCLUSIVE MDL: rows inserted/deleted
           since the snapshot are replayed onto the target, then the table's
           primary endpoint swaps."""
        tm = self.catalog.table(schema, name)
        if getattr(tm, "remote", None) is None:
            raise errors.NotSupportedError(
                f"{schema}.{name} is not a remote table")
        src = self.workers[(tm.remote["host"], tm.remote["port"])]
        dst = self.worker_client(host, port)
        # target bootstrap: schema + table shape from this CN's meta
        cols_sql = ", ".join(
            f"{c.name} {c.dtype.sql_name()}" + ("" if c.nullable else " NOT NULL")
            for c in tm.columns)
        pk_sql = (f", PRIMARY KEY ({', '.join(tm.primary_key)})"
                  if tm.primary_key else "")
        dst.execute(f"CREATE DATABASE IF NOT EXISTS {schema}", "", idem=True)
        dst.execute(f"CREATE TABLE IF NOT EXISTS {name} ({cols_sql}{pk_sql})",
                    schema, idem=True)
        cols = tm.column_names()
        mdl_key = f"{schema.lower()}.{name.lower()}"
        pk = tm.primary_key[0] if tm.primary_key else cols[0]
        # phase 1: snapshot backfill (shared MDL: concurrent writes continue)
        with self.mdl.shared({mdl_key}):
            s0 = self.tso.next_timestamp()
            names, types, data, valid = src.exec_plan(
                {"schema": schema, "table": name, "columns": cols})
            self._bulk_insert_remote(dst, schema, name, names, types, data,
                                     valid)
        # phase 2: delta catchup + cutover (exclusive MDL: writes drained)
        with self.mdl.exclusive(mdl_key):
            # drain OPEN txns holding branches on the source worker: their
            # commits bypass MDL (statement-scoped) and would land on the old
            # primary after cutover — a silently lost write.  New DML is
            # blocked on our exclusive MDL, so waiting converges.
            import time as _time
            src_addr = (src.addr[0], src.addr[1])
            deadline = _time.time() + 30.0
            def _pinned():
                for sess in list(self.sessions.values()):
                    txn = getattr(sess, "txn", None)
                    if txn is not None and src_addr in getattr(txn, "remote", {}):
                        return True
                with self.xa_coordinator._lock:
                    for parts in self.xa_coordinator._in_doubt.values():
                        for sp in parts:
                            if getattr(sp, "addr", None) == src_addr:
                                return True
                return False
            while _pinned():
                if _time.time() > deadline:
                    raise errors.TddlError(
                        f"move {schema}.{name}: open transactions pin the "
                        f"source worker {src_addr}; retry later")
                _time.sleep(0.05)
            # delta window widened by a margin: a txn may DRAW its commit_ts
            # before s0 yet stamp the worker's lanes after the phase-1 read
            # (commit_ts issue and stamp application are not atomic).  The
            # delta apply is idempotent (delete-by-PK before insert), so
            # re-copying recent rows is safe; the margin only costs re-copy
            # volume.  10 minutes of physical TSO covers any realistic
            # prepare->stamp descheduling.
            from galaxysql_tpu.meta.tso import LOGICAL_BITS
            margin = 600_000 << LOGICAL_BITS  # 10 min of wall clock
            resp, arrs = src.request(
                {"op": "exec_plan",
                 "fragment": {"schema": schema, "table": name,
                              "columns": cols, "since": max(s0 - margin, 0),
                              "deleted_since_of": pk}})
            ddata = {c: arrs[f"d::{c}"] for c in cols}
            dvalid = {c: arrs[f"v::{c}"] for c in cols if f"v::{c}" in arrs}
            gone = arrs.get("deleted::keys")
            new_keys = list(ddata[pk].tolist()) if cols else []
            drop = set(new_keys) | set(gone.tolist() if gone is not None else [])
            if drop:
                # literal rendering follows the PK's wire type (scaled
                # decimals, quoted strings/dates) — the same encoding the
                # backfill INSERTs used, so the DELETE actually matches
                pk_type = dict(zip(resp["columns"], resp["types"]))[pk]
                in_list = ", ".join(self._sql_literal(pk_type, k, True)
                                    for k in drop)
                # the delta apply is idempotent by construction (delete-by-PK
                # before re-insert), so the DELETE is retry-safe
                dst.execute(f"DELETE FROM {name} WHERE {pk} IN ({in_list})",
                            schema, idem=True)
            self._bulk_insert_remote(dst, schema, name, resp["columns"],
                                     resp["types"], ddata, dvalid)
            tm.remote = {"host": host, "port": port}
            self.catalog.bump_schema()
        self.counters.inc("table_moves")
        return tm

    def try_revive_worker(self, addr) -> bool:
        """Lazy fence revival: ONE ping decides whether a fenced endpoint
        recovered (no background prober exists in production — fencing must
        not be forever).  Returns True when the endpoint is now unfenced.
        Shared by read routing and the remote-DML primary gate so the HA
        policy lives in one place."""
        client = self.workers.get(addr)
        if client is None or not self.ha.worker_fenced(addr):
            return False
        if client.ping(timeout=2.0):
            self.ha.fence_worker(addr, False)
            return True
        return False

    def read_endpoint(self, tm):
        """Pick the endpoint to serve a read of `tm`: weighted random over the
        primary + non-stale replicas, skipping fenced workers.  Returns
        (addr, client) or raises if every endpoint is down."""
        import random
        from galaxysql_tpu.utils import errors as _errors
        cands = [((tm.remote["host"], tm.remote["port"]),
                  tm.remote.get("weight", 1))]
        for r in tm.replicas:
            if not r.get("stale"):
                cands.append(((r["host"], r["port"]), r.get("weight", 1)))
        # breaker-blocked endpoints (open + cooling down) are as good as
        # fenced for routing: picking one would only fast-fail and burn a
        # failover attempt.  A cooled-down breaker stays routable — the next
        # request half-opens it with a ping probe.
        live = [(a, w) for a, w in cands
                if a in self.workers and not self.ha.worker_fenced(a) and
                not getattr(self.workers[a], "breaker_blocked",
                            lambda: False)()]
        if not live:
            # lazy fence revival: fencing has no background prober in
            # production, so before refusing, ping each fenced candidate
            # once and unfence responders (a recovered worker serves again
            # at the first read that needs it)
            for a, w in cands:
                if self.try_revive_worker(a):
                    live.append((a, w))
        if not live:
            raise _errors.WorkerUnavailableError(
                f"remote table {tm.name}: every endpoint is fenced/unattached")
        # backpressure-aware weighting: endpoints that piggybacked a deep
        # queue or an elevated memory tier in recent replies are
        # deprioritized (never excluded — a uniformly-pressured fleet must
        # still serve).  Stale load reports (>5s) decay to neutral.
        import time as _t
        now = _t.time()
        # physical-placement locality: the endpoint bound to this table's
        # dominant group (server/placement.py) gets a 4x boost — MOVE
        # PARTITION into a bound group shifts real read traffic, but a
        # mis-bound group can never black-hole reads (boost, not filter)
        preferred = None
        placement = getattr(self, "placement", None)
        if placement is not None and len(live) > 1:
            try:
                preferred = placement.preferred_endpoint(tm)
            except Exception:  # galaxylint: disable=swallow -- locality is advisory: a placement fault must never fail a read
                preferred = None

        def _load_weight(a, w):
            c = self.workers.get(a)
            if a == preferred:
                w = w * 4.0
            if c is None or now - getattr(c, "load_at", 0.0) > 5.0:
                return float(w)
            penalty = 1.0 + getattr(c, "load_q", 0) \
                + 4.0 * getattr(c, "load_tier", 0)
            return float(w) / penalty

        live = [(a, _load_weight(a, w)) for a, w in live]
        total = sum(w for _, w in live)
        pick = random.random() * total
        for a, w in live:
            pick -= w
            if pick <= 0:
                return a, self.workers[a]
        return live[-1][0], self.workers[live[-1][0]]

    def apply_sync_action(self, action: str, payload: dict) -> dict:
        """Coordinator-side receiver of sync-bus actions (the CN twin of
        net/worker.Worker._sync): peer coordinators attached to each other's
        SyncBus via `sync_peer()` invalidate caches without sharing memory."""
        payload = payload or {}
        if action == "invalidate_fragment_cache":
            key = payload.get("table_key") or \
                f"{payload.get('schema', '').lower()}.{payload.get('table', '').lower()}"
            self.frag_cache.bump_epoch(key)
            return {"ok": True, "action": action, "node": self.node_id}
        if action == "invalidate_plan_cache":
            self.planner.cache.invalidate_all()
            return {"ok": True, "action": action, "node": self.node_id}
        if action == "invalidate_privilege_cache":
            self.privileges.invalidate_cache()
            return {"ok": True, "action": action, "node": self.node_id}
        if action == "health":
            # peer coordinators answer the same health pull workers do.
            # The serving tier rides extra freight on this one action:
            # - inbound `peer_admission` {node: snapshot} gossip is ingested
            #   (the router acts as gossip hub, relaying every peer's
            #   admission state to every other peer), and
            # - the reply carries this node's own admission snapshot, sync
            #   epoch, served placement groups, steady-state retrace count,
            #   and — on request via `want` — bounded statement-summary /
            #   metrics rollups for the SHOW CLUSTER merges.
            mh = self.metric_history
            mh.maybe_sample()
            for node, snap in (payload.get("peer_admission") or {}).items():
                self.admission.note_peer(node, snap)
            reply = {"ok": True, "action": action, "node": self.node_id,
                     "uptime_s": round(_time.time() - self.started_at, 3),
                     "active": float(len(self.sessions)),
                     "qps": round(mh.rate("queries_total"), 3),
                     "error_rate": round(mh.rate("query_errors"), 6),
                     "mem_tier": int(self.admission.governor.tier()),
                     "samples": int(mh.summary()["samples"]),
                     "burning": self.slo.burning_names(),
                     "epoch": int(self.sync_bus.epoch),
                     "admission": self.admission.cluster_snapshot(),
                     "groups": [g.strip().lower() for g in
                                str(self.config.get("COORDINATOR_GROUPS")
                                    or "").split(",") if g.strip()],
                     "retraces": self._retrace_count()}
            want = payload.get("want") or []
            if "statement_summary" in want:
                reply["statement_summary"] = \
                    [list(r) for r in self.stmt_summary.rows()[:256]]
            if "metrics" in want:
                reply["metrics"] = [[n, k, float(v), h] for n, k, v, h
                                    in self.metrics.rows()[:512]]
            if "traces" in want:
                reply["traces"] = [rt.to_dict() for rt in
                                   self.trace_store.entries(limit=64)]
            # exact-id trace pull: the router grafts a routed statement's
            # peer-side span tree back into its own context (ISSUE 20
            # cluster propagation), same want-freight pattern as above
            tid = payload.get("trace_id")
            if tid is not None:
                rt = self.trace_store.get(tid)
                reply["trace"] = rt.to_dict() if rt is not None else None
            return reply
        return {"ok": False, "error": f"unknown sync action {action!r}"}

    @staticmethod
    def _retrace_count() -> int:
        """Process-lifetime XLA retrace count (exec compile stats) — the
        scale-out bench asserts this stays flat per peer at steady state."""
        try:
            from galaxysql_tpu.exec.operators import COMPILE_STATS
            return int(COMPILE_STATS.get("retraces", 0))
        except Exception:  # galaxylint: disable=swallow -- a health reply must not fail because compile stats moved; 0 reads as "unknown"
            return 0

    # -- serving tier (peer coordinators) --------------------------------------

    def attach_coordinator(self, node_id: str, peer) -> None:
        """Register a peer coordinator: `peer` is any sync endpoint
        (`sync_peer()` object in-process, or a dn-wire client pointed at the
        peer's sync listener).  The peer joins this instance's SyncBus so
        cache-invalidation broadcasts reach it, and the admission/gossip and
        SHOW CLUSTER planes start seeing it."""
        from galaxysql_tpu.utils import events
        self.coordinators[node_id] = peer
        self.sync_bus.attach(peer)
        events.publish("coordinator_joined",
                       f"peer coordinator {node_id} joined the serving tier",
                       node=self.node_id, peer=node_id)

    def detach_coordinator(self, node_id: str, reason: str = "detach") -> None:
        peer = self.coordinators.pop(node_id, None)
        if peer is None:
            return
        with self.sync_bus._lock:
            if peer in self.sync_bus.workers:
                self.sync_bus.workers.remove(peer)
        self.admission.forget_peer(node_id)
        from galaxysql_tpu.utils import events
        events.publish("coordinator_left",
                       f"peer coordinator {node_id} left the serving tier "
                       f"({reason})", node=self.node_id, peer=node_id,
                       reason=reason)

    def coordinator_rows(self, pull: bool = True):
        """SHOW COORDINATORS / information_schema.coordinators row source:
        this node first, then every registered peer.  `pull=True` issues a
        fresh health sync per peer (UNREACHABLE rows, never errors);
        `pull=False` renders from the last gossip snapshots only."""
        router = getattr(self, "router", None)
        adm = self.admission
        gossip_age = {n: age for n, _s, age in adm.peer_gossip_rows()}

        def _aff(node):
            if router is None:
                return 0, 0, 0.0
            return router.affinity_of(node)

        routed, hits, ratio = _aff(self.node_id)
        rows = [(self.node_id, "local", "OK", int(self.sync_bus.epoch),
                 round(adm.effective_limit("TP"), 1),
                 round(adm.effective_limit("AP"), 1),
                 float(len(adm._tokens["TP"])), float(len(adm._tokens["AP"])),
                 routed, round(ratio, 4), -1.0)]
        for node_id, peer in sorted(self.coordinators.items()):
            routed, hits, ratio = _aff(node_id)
            age = round(gossip_age.get(node_id, -1.0), 3)
            resp = None
            if pull:
                try:
                    resp = peer.sync_action("health", {})
                except Exception:  # galaxylint: disable=swallow -- the UNREACHABLE row below IS the failure report
                    resp = None
            else:
                snap = next((s for n, s, _a in adm.peer_gossip_rows()
                             if n == node_id), None)
                if snap is not None:
                    resp = {"ok": True, "admission": snap, "epoch": -1}
            if not (isinstance(resp, dict) and resp.get("ok")):
                rows.append((node_id, "peer", "UNREACHABLE", -1,
                             0.0, 0.0, 0.0, 0.0, routed, round(ratio, 4),
                             age))
                continue
            snap = resp.get("admission") or {}
            tp, ap = snap.get("tp") or {}, snap.get("ap") or {}
            rows.append((resp.get("node", node_id), "peer", "OK",
                         int(resp.get("epoch", -1)),
                         float(tp.get("limit", 0.0)),
                         float(ap.get("limit", 0.0)),
                         float(tp.get("inflight", 0)),
                         float(ap.get("inflight", 0)),
                         routed, round(ratio, 4), age))
        return rows

    def sync_peer(self):
        """In-process SyncBus endpoint for this instance: attach the returned
        object to a PEER coordinator's `sync_bus` and that peer's broadcasts
        (fragment/plan-cache invalidation) apply here — the multi-coordinator
        invalidation plane without a socket in between."""
        inst = self

        class _Peer:
            def sync_action(self, action: str, payload: dict) -> dict:
                return inst.apply_sync_action(action, payload)

            def ping(self, timeout: float = 5.0) -> bool:
                return True

        return _Peer()

    def mesh(self):
        """The instance's device mesh for MPP execution (None on a single
        device).  A backend that cannot start is the backend's error, not
        "no mesh"."""
        if not hasattr(self, "_mesh"):
            import jax
            devs = jax.devices()
            if len(devs) > 1:
                from galaxysql_tpu.parallel.mesh import make_mesh
                self._mesh = make_mesh(devices=devs)
            else:
                self._mesh = None
        return self._mesh
