"""Physical planning: logical plan -> operator tree.

Reference analog: the physical convention step (`DrdsConvention`, SURVEY.md §2.5) +
`LocalExecutionPlanner` building operator pipelines (§2.7).  Decisions made here:

- hash join sides: build = smaller estimated input (the probe side streams);
  left/semi/anti joins fix the probe side to the preserved/output side.
- aggregates use estimated group counts to size the fixed-shape kernel output.
- scans rename storage columns to plan field ids and carry pruned partition lists.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from galaxysql_tpu.chunk.batch import Column, ColumnBatch
from galaxysql_tpu.exec import fusion
from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.expr import ir
from galaxysql_tpu.expr.compiler import _find_dictionary
from galaxysql_tpu.plan import logical as L
from galaxysql_tpu.plan.rules import estimate_rows
from galaxysql_tpu.storage.table_store import TableStore
from galaxysql_tpu.types import datatype as dt
from galaxysql_tpu.utils import errors


class ExecContext:
    """Per-execution context (ExecutionContext analog, SURVEY.md §2.5 misc)."""

    def __init__(self, stores: Dict[str, TableStore], snapshot_ts: Optional[int] = None,
                 params: Optional[list] = None, batch_rows: int = 1 << 20,
                 device_cache=None, txn_id: int = 0, archive=None,
                 archive_instance=None, hints=None):
        self.stores = stores          # "schema.table" -> TableStore
        self.snapshot_ts = snapshot_ts
        self.params = params or []
        self.batch_rows = batch_rows
        self.device_cache = device_cache  # DeviceCache or None (host-batch scans)
        self.txn_id = txn_id          # owning txn for MVCC visibility (0 = none)
        self.archive = archive        # ArchiveManager (cold parquet scans)
        self.archive_instance = archive_instance
        self.hints = hints or {}  # statement hints (sql/hints.py)
        # open worker branches of the session's txn: addr -> xid.  Remote scans
        # ship the xid so the worker reads through the branch (read-your-own-
        # writes across the seam, like the reference's txn-bound DN connection)
        self.remote_xids: Dict = {}
        self.sort_spill_bytes = 256 << 20   # SORT_SPILL_BYTES (session override)
        self.join_spill_bytes = 256 << 20   # JOIN_SPILL_BYTES
        self.agg_spill_bytes = 256 << 20    # partial-agg spill threshold
        # per-query memory pool (exec/memory.py child of GLOBAL_POOL): join
        # build / agg partial / sort slab reservations charge it; None keeps
        # every operator charge a no-op (admission disabled, bare contexts)
        self.mem_pool = None
        self.collect_stats = False       # EXPLAIN ANALYZE / profiling stats
        self.op_stats: List[dict] = []   # filled by StatsOp when collecting
        self.profile = None              # owning QueryProfile (utils/tracing)
        self.trace: List[str] = []
        # pipeline segment fusion (exec/fusion.py): module switch + NO_FUSE hint
        self.enable_fusion = fusion.default_enabled(self.hints)
        # per-execution runtime-filter hub (exec/runtime_filter.py): joins
        # publish build-side filters here, probe-side scans consume them;
        # NO_BLOOM / RUNTIME_FILTER(OFF) hints turn it off
        from galaxysql_tpu.exec.runtime_filter import RuntimeFilterManager
        self.rf = RuntimeFilterManager(
            hints=self.hints,
            metrics=getattr(archive_instance, "metrics", None))
        # cross-query fragment cache (exec/fragment_cache.py): join build
        # artifacts, deterministic subplan results, cached filter publications.
        # None when disabled (env/config/hint) or outside an Instance context.
        from galaxysql_tpu.exec import fragment_cache as _fc
        self.frag = _fc.for_context(archive_instance, self.hints)
        # store uids this execution's txn has written (session fills it in);
        # None with a live txn means "unknown write set" — the cache bypasses
        self.txn_write_uids = frozenset() if txn_id == 0 else None
        # skew-aware execution (exec/skew.py): which planted plans this
        # execution may activate (env + SKEW hint + ENABLE_SKEW_EXECUTION),
        # and the per-node decisions EXPLAIN ANALYZE / stage spans surface
        from galaxysql_tpu.exec import skew as _skew
        self.skew_modes = _skew.exec_modes(self.hints, archive_instance)
        self.skew_stats: Dict[int, dict] = {}
        # MAX_EXECUTION_TIME deadline (absolute time.time() seconds, or None):
        # checked at operator drain / fused-segment / MPP-stage boundaries and
        # propagated to workers as the remaining budget in RPC headers
        self.deadline: Optional[float] = None
        # self-heal pin (plan/spm.py heal_pin): non-empty while the plan's
        # digest has a live quarantine episode; salts fragment-cache
        # fingerprints so probation and regressed artifacts never cross
        self.plan_pin = ""
        # columnar HTAP routing (storage/columnar.py): table key ->
        # ReplicaView snapshot taken at routing; scans of those tables read
        # the replica at the routed watermark instead of the row store.
        # The fragment cache fingerprints them as ("cscan", seed_ts, events)
        # so replica-fed and row-fed artifacts never cross.
        self.columnar: Dict[str, object] = {}

    def check_deadline(self):
        """Raise a typed QueryTimeoutError once the deadline passes.  Called
        at pipeline boundaries — a None deadline costs one attribute read."""
        if self.deadline is not None:
            import time as _t
            if _t.time() > self.deadline:
                raise errors.QueryTimeoutError(
                    "query exceeded MAX_EXECUTION_TIME deadline")


# per-(store, version) scan metadata: O(table) host reductions must run once per
# version, not per query (the lanes themselves are cached the same way)
_SCAN_META: Dict = {}


def _scan_meta(store, version: int) -> Dict:
    key = (store.uid, version)
    meta = _SCAN_META.get(key)
    if meta is None:
        all_current = True
        max_begin = 0
        for p in store.partitions:
            if p.num_rows == 0:
                continue
            if not (bool((p.end_ts == np.iinfo(np.int64).max).all()) and
                    bool((p.begin_ts >= 0).all())):
                all_current = False
            else:
                max_begin = max(max_begin, int(p.begin_ts.max()))
        meta = {"all_current": all_current, "max_begin": max_begin,
                "valid_all": {}}
        if len(_SCAN_META) > 512:
            _SCAN_META.clear()
        _SCAN_META[key] = meta
    return meta


def _device_visibility(begin, end, ts, txn_id):
    """Device-side MVCC visibility — the jnp twin of native.visible_mask (one
    semantic change must touch exactly these two implementations)."""
    ins_ok = (begin >= 0) & (begin <= ts)
    dele = (end >= 0) & (end <= ts)
    if txn_id:
        ins_ok = ins_ok | (begin == -txn_id)
        dele = dele | (end == -txn_id)
    return ins_ok & ~dele


class ScanSource(ops.Operator):
    """Storage scan renamed into plan field-id space."""

    def __init__(self, node: L.Scan, ctx: ExecContext):
        self.node = node
        self.ctx = ctx

    def batches(self) -> Iterator[ColumnBatch]:
        t = self.node.table
        self.ctx.check_deadline()  # drain boundary: scans feed every pipeline
        if getattr(t, "remote", None) is not None:
            yield from self._remote_batches(t)
            return
        store = self.ctx.stores[f"{t.schema.lower()}.{t.name.lower()}"]
        storage_cols = [c for _, c in self.node.columns]
        rename = {c: oid for oid, c in self.node.columns}
        # flashback (AS OF TSO n): the scan reads at the requested snapshot —
        # own-txn provisional rows excluded (a historical read, not a txn read)
        as_of = self.node.as_of
        snap = as_of if as_of is not None else self.ctx.snapshot_ts
        txn_id = 0 if as_of is not None else self.ctx.txn_id
        self.ctx.trace.append(
            f"scan {t.name} partitions={self.node.partitions or 'all'}" +
            (f" as_of={as_of}" if as_of is not None else ""))
        yield from self._archive_batches(t, storage_cols, rename, snap)
        # columnar-replica route: the session snapshotted a ReplicaView at
        # the routed watermark (== ctx.snapshot_ts).  The archive batches
        # above still run — TTL-archived rows never reached the replica's
        # seed scan.  Flashback reads (as_of) always stay on the row store.
        if self.ctx.columnar and as_of is None:
            view = self.ctx.columnar.get(f"{t.schema.lower()}.{t.name.lower()}")
            if view is not None:
                yield from self._columnar_batches(t, view, storage_cols,
                                                  rename)
                return
        from galaxysql_tpu.exec.operators import bucket_capacity
        if self.node.point_eq is not None:
            yield from self._point_batches(t, store, snap, txn_id)
            return
        cache = self.ctx.device_cache
        if cache is None:
            for b in store.scan(storage_cols, self.node.partitions,
                                snap, txn_id=txn_id):
                self.ctx.check_deadline()  # per-partition drain boundary
                # pad to power-of-two buckets: partitions of different sizes must not
                # each compile their own kernel shapes
                yield b.pad_to(bucket_capacity(b.capacity)).rename(rename)
            return
        # device-resident path: whole column lanes pinned in HBM keyed by table
        # version; MVCC visibility computed on device from cached ts lanes
        import jax.numpy as jnp
        if self.node.partitions is None:
            # full-table scans fuse all partitions into ONE cached device batch:
            # one kernel dispatch per operator instead of one per partition
            b = self._fused_table_batch(t, store, cache, jnp, snap, txn_id)
            if b is not None:
                yield b.rename(rename)  # fused cols are storage-name keyed
                return
        pids = (range(len(store.partitions)) if self.node.partitions is None
                else self.node.partitions)
        ts = snap
        for pid in pids:
            p = store.partitions[pid]
            if p.num_rows == 0:
                continue
            cap = bucket_capacity(p.num_rows)

            def padded(arr, fill=0):
                if arr.shape[0] == cap:
                    return arr
                return np.concatenate(
                    [arr, np.full(cap - arr.shape[0], fill, dtype=arr.dtype)])

            cols = {}
            for oid, cname in self.node.columns:
                cm = t.column(cname)
                data = cache.get_lane(store, pid, cname, t.version,
                                      padded(p.lanes[cname]))
                valid = None
                if not bool(p.valid[cname].all()):
                    valid = cache.get_lane(store, pid, f"valid::{cname}", t.version,
                                           padded(p.valid[cname], False))
                cols[oid] = Column(data, valid, cm.dtype,
                                   t.dictionaries.get(cname.lower()))
            pad_live = jnp.arange(cap) < p.num_rows if cap != p.num_rows else None
            all_current = bool((p.end_ts == np.iinfo(np.int64).max).all()) and \
                bool((p.begin_ts >= 0).all())
            max_begin = int(p.begin_ts.max()) if p.num_rows else 0
            if all_current and (ts is None or max_begin <= ts):
                live = pad_live
            else:
                begin = cache.get_lane(store, pid, "::begin_ts", t.version,
                                       padded(p.begin_ts))
                end = cache.get_lane(store, pid, "::end_ts", t.version,
                                     padded(p.end_ts, -1))
                live = _device_visibility(begin, end, ts, txn_id)
                if pad_live is not None:
                    live = live & pad_live
            yield ColumnBatch(cols, live)


    def _point_batches(self, t, store, snap, txn_id) -> Iterator[ColumnBatch]:
        """Index access path: candidate rows from the partition's sorted key
        index instead of full lanes (XPlan key-Get / DirectShardingKey plan,
        Planner.java:914).  The Filter above the scan re-verifies the whole
        predicate, so candidates only need to be a superset of the matches
        for the indexed column; MVCC visibility is applied here."""
        from galaxysql_tpu import native
        from galaxysql_tpu.exec.operators import bucket_capacity
        col, val = self.node.point_eq
        pids = (range(len(store.partitions)) if self.node.partitions is None
                else self.node.partitions)
        for pid in pids:
            p = store.partitions[pid]
            if p.num_rows == 0:
                continue
            with p.lock:
                ids = p.key_candidates(col, val)
                if ids.size == 0:
                    continue
                vis = native.visible_mask(p.begin_ts[ids], p.end_ts[ids],
                                          snap, txn_id)
                ids = ids[vis]
                if ids.size == 0:
                    continue
                cols = {}
                for oid, cname in self.node.columns:
                    cm = t.column(cname)
                    d = p.lanes[cname][ids]
                    v = p.valid[cname][ids]
                    cols[oid] = Column(d, None if bool(v.all()) else v,
                                       cm.dtype,
                                       t.dictionaries.get(cname.lower()))
            self.ctx.trace.append(f"point-get {t.name} p{pid} rows={ids.size}")
            yield ColumnBatch(cols, None).pad_to(
                bucket_capacity(max(int(ids.size), 1)))

    def _remote_batches(self, t) -> Iterator[ColumnBatch]:
        """Plan shipping: the scan compiles to SQL executed by the worker
        process that owns the table (MyJdbcHandler.java:691 analog) — column
        pruning rides the SELECT list; results re-encode into this CN's lanes
        and dictionaries."""
        from galaxysql_tpu.chunk.batch import column_from_pylist
        from galaxysql_tpu.exec.operators import bucket_capacity
        inst = self.ctx.archive_instance
        if inst is None:
            raise errors.TddlError(
                f"remote table {t.name} needs an owning instance context")
        # weighted read routing over primary + replicas with fence-triggered
        # failover (TGroupDataSource analog): a request failure fences the
        # endpoint and retries another until none remain — WITHIN the same
        # statement, so a dead replica costs a re-route, not an error
        last_err = None
        for _attempt in range(1 + len(getattr(t, "replicas", []))):
            addr, client = inst.read_endpoint(t)
            try:
                # materialize BEFORE yielding: a mid-stream failover retry must
                # not re-emit rows already handed downstream
                got = list(self._remote_batches_from(t, inst, addr, client))
                yield from got
                return
            except errors.QueryTimeoutError:
                raise  # the deadline kills the STATEMENT, not the endpoint
            except (errors.TddlError, ConnectionError, OSError) as e:
                last_err = e
                transport = isinstance(
                    e, (errors.WorkerUnavailableError, ConnectionError,
                        OSError))
                if transport and not client.ping():
                    # ping-verified dead: fence and re-route — a transient
                    # blip (worker restarting, half-open probe race) must
                    # not fence an endpoint the next ping proves alive
                    from galaxysql_tpu.utils.metrics import WORKER_FAILOVERS
                    from galaxysql_tpu.utils import events
                    inst.ha.fence_worker(addr, True)
                    WORKER_FAILOVERS.inc()
                    events.publish("worker_failover",
                                   f"scan {t.name}: fenced dead endpoint "
                                   f"{addr[0]}:{addr[1]}, re-routing",
                                   node=inst.node_id, table=t.name,
                                   worker=f"{addr[0]}:{addr[1]}",
                                   fenced=True)
                    self.ctx.trace.append(
                        f"failover {t.name}: fenced {addr[0]}:{addr[1]}")
                    continue  # endpoint dead: re-route within the statement
                if transport:
                    # alive but erroring (breaker mid-recovery): re-route
                    # this statement without fencing
                    from galaxysql_tpu.utils.metrics import WORKER_FAILOVERS
                    from galaxysql_tpu.utils import events
                    WORKER_FAILOVERS.inc()
                    events.publish("worker_failover",
                                   f"scan {t.name}: rerouted off live "
                                   f"endpoint {addr[0]}:{addr[1]}",
                                   node=inst.node_id, table=t.name,
                                   worker=f"{addr[0]}:{addr[1]}",
                                   fenced=False)
                    self.ctx.trace.append(
                        f"failover {t.name}: rerouted off "
                        f"{addr[0]}:{addr[1]} (alive)")
                    continue
                raise
        raise errors.WorkerUnavailableError(
            f"remote table {t.name}: no serving endpoint ({last_err})")

    def _remote_batches_from(self, t, inst, addr, client
                             ) -> Iterator[ColumnBatch]:
        from galaxysql_tpu.chunk.batch import column_from_pylist
        from galaxysql_tpu.exec.operators import bucket_capacity
        storage_cols = [c for _, c in self.node.columns]
        # ship the BOUND FRAGMENT first (XPlan analog): table + pruned columns
        # + lane-domain SARGs + numeric point key; the worker executes it with
        # no parse/plan work.  Any error degrades to SQL text, exactly the
        # XPlanTemplate.java:86,132 fallback ladder.
        def lane_safe(v):
            return int(v) if float(v).is_integer() else float(v)
        # planned runtime filters ride the fragment: the build side's min/max
        # range as extra SARGs, small builds additionally as an IN-list — the
        # DN-side scan prunes before rows cross the process seam (the
        # reference's runtime-filter-into-DN-scan pushdown, SURVEY.md §5.1)
        rf_sargs, rf_in = self._rf_pushdown()
        frag = {"schema": t.schema, "table": t.name, "columns": storage_cols,
                "sargs": [[c, op, lane_safe(v)] for c, op, v in
                          list(getattr(self.node, "sargs", [])) + rf_sargs]}
        if rf_in:
            frag["rf_in"] = [[c, vals] for c, vals in rf_in]
        xid = self.ctx.remote_xids.get(addr)
        if xid is not None:
            frag["xid"] = xid  # read through the session's open worker branch
        pe = self.node.point_eq
        if pe is not None and not t.column(pe[0]).dtype.is_string and \
                isinstance(pe[1], (int, np.integer)):
            frag["point"] = [pe[0], int(pe[1])]
        dl = self.ctx.deadline
        try:
            names, rtypes, data, valid = client.exec_plan(frag, deadline=dl)
            self.ctx.trace.append(
                f"remote-plan {t.name} -> {addr[0]}:{addr[1]}")
        except (errors.QueryTimeoutError, errors.WorkerUnavailableError):
            # degrade ladder stops typed: a dead endpoint fails over (the
            # caller re-routes), a blown deadline kills the statement —
            # re-shipping as SQL text would help neither
            raise
        except errors.TddlError:
            sql = (f"SELECT {', '.join(storage_cols)} FROM "
                   f"{t.schema}.{t.name}")
            self.ctx.trace.append(
                f"remote-scan {t.name} -> {addr[0]}:{addr[1]}")
            # the degrade path keeps the branch xid: txn visibility must not
            # depend on which wire form served the scan
            names, rtypes, data, valid = client.execute(sql, t.schema, xid=xid,
                                                        deadline=dl)
        scaled = {nm for nm, ty in zip(names, rtypes)
                  if isinstance(ty, str) and ty.endswith("#scaled")}
        n = len(next(iter(data.values()))) if data else 0
        cols = {}
        for oid, cname in self.node.columns:
            cm = t.column(cname)
            arr = data[cname]
            v = valid.get(cname)
            if cname in scaled:
                # worker shipped the DECIMAL lane as scaled int64 — adopt it
                # directly (no float re-round; exact to the lane's 18 digits)
                cols[oid] = Column(arr.astype(cm.dtype.lane),
                                   None if v is None else v.astype(np.bool_),
                                   cm.dtype, None)
                continue
            vals = arr.tolist()
            if v is not None:
                vals = [x if ok else None for x, ok in zip(vals, v.tolist())]
            cols[oid] = column_from_pylist(vals, cm.dtype,
                                           t.dictionaries.get(cname.lower()))
        if not cols:
            return
        import jax.numpy as jnp
        b = ColumnBatch(cols, jnp.ones(n, dtype=jnp.bool_) if n else
                        jnp.zeros(0, dtype=jnp.bool_))
        yield b.pad_to(bucket_capacity(max(n, 1)))

    def _rf_pushdown(self):
        """(min/max sargs, in-lists) from published runtime filters — the
        lane-domain pushdown shared by remote fragments and archive SARGs."""
        rf = getattr(self.ctx, "rf", None)
        if rf is None or not getattr(self.node, "rf_targets", None):
            return [], []
        sargs, inlists = rf.scan_pushdown(self.node)
        return [[c, op, v] for c, op, v in sargs], inlists

    def _columnar_batches(self, t, view, storage_cols, rename):
        """Vectorized columnar-replica scan: pre-padded immutable stripes +
        one concatenated delta batch, zone-map-pruned by the same SARGs the
        parquet archive refutes with, MVCC-visible at the routed watermark."""
        from galaxysql_tpu.storage import columnar as _col
        mgr = getattr(self.ctx.archive_instance, "columnar", None)
        sargs = [tuple(s) for s in (getattr(self.node, "sargs", None) or [])]
        rf_sargs, _ = self._rf_pushdown()
        sargs += [tuple(s) for s in rf_sargs]
        pruned0 = view.replica.pruned_stripes
        self.ctx.trace.append(
            f"scan-columnar {t.name} watermark={view.watermark} "
            f"stripes={len(view.stripes)} delta={len(view.delta)}")
        for b in _col.scan_view(view, t, storage_cols, sargs, mgr):
            self.ctx.check_deadline()  # per-stripe drain boundary
            yield b.rename(rename)
        pruned = view.replica.pruned_stripes - pruned0
        if pruned:
            self.ctx.trace.append(
                f"scan-columnar {t.name} pruned_stripes={pruned}")

    def _archive_batches(self, t, storage_cols, rename, snap=None):
        """Cold rows from parquet archives (OSSTableScanExec analog)."""
        am = self.ctx.archive
        if am is None:
            return
        snap = self.ctx.snapshot_ts if snap is None else snap
        from galaxysql_tpu.exec.operators import bucket_capacity
        inst_key = f"{t.schema.lower()}.{t.name.lower()}"
        if not am.files_for(inst_key, snap):
            return
        # runtime-filter min/max ranges feed the same parquet SARG refutation
        # as WHERE-derived sargs, skipping whole files the build side refutes
        rf_sargs, _ = self._rf_pushdown()
        rf = getattr(self.ctx, "rf", None)
        cb = rf.note_file_pruned if rf is not None else None
        for b in am.scan_archive(self.ctx.archive_instance, t.schema, t.name,
                                 storage_cols, snap,
                                 sargs=getattr(self.node, "sargs", None),
                                 rf_sargs=[tuple(s) for s in rf_sargs],
                                 rf_pruned_cb=cb):
            self.ctx.trace.append(f"scan-archive {t.name} rows={b.capacity}")
            yield b.pad_to(bucket_capacity(max(b.capacity, 1))).rename(rename)


    def _fused_table_batch(self, t, store, cache, jnp, snap=None, txn_id=None):
        from galaxysql_tpu.exec.operators import bucket_capacity
        ts = self.ctx.snapshot_ts if snap is None else snap
        txn_id = self.ctx.txn_id if txn_id is None else txn_id
        total = sum(p.num_rows for p in store.partitions)
        if total == 0 or total > (1 << 27):
            return None  # empty: old per-partition loop yields nothing
        cap = bucket_capacity(total)

        def fused(name, parts, fill=0):
            def build():
                lane = np.full(cap, fill, dtype=parts[0].dtype)
                off = 0
                for arr in parts:
                    lane[off:off + arr.shape[0]] = arr
                    off += arr.shape[0]
                return lane
            # lazy: a cache hit must not pay the O(table) host concatenation
            return cache.get_lane_built(store, -1, name, t.version, cap, build)

        meta = _scan_meta(store, t.version)
        cols = {}
        for oid, cname in self.node.columns:
            cm = t.column(cname)
            data = fused(cname, [p.lanes[cname] for p in store.partitions])
            valid = None
            v_all = meta["valid_all"].get(cname)
            if v_all is None:
                v_all = all(bool(p.valid[cname].all())
                            for p in store.partitions)
                meta["valid_all"][cname] = v_all
            if not v_all:
                valid = fused(f"valid::{cname}",
                              [p.valid[cname] for p in store.partitions], False)
            cols[oid] = Column(data, valid, cm.dtype,
                               t.dictionaries.get(cname.lower()))
        # O(table) host reductions cached per (store, version) — a warm scan
        # must not re-reduce every timestamp lane per query
        all_current = meta["all_current"] and \
            (ts is None or meta["max_begin"] <= ts)
        pad_live = None
        if cap != total:
            # the pad mask is version-static: cache it beside the lanes
            pad_live = cache.get_lane_built(
                store, -1, "::padlive", t.version, cap,
                lambda: np.arange(cap) < total)
        if all_current:
            live = pad_live
        else:
            begin = fused("::begin_ts", [p.begin_ts for p in store.partitions])
            end = fused("::end_ts", [p.end_ts for p in store.partitions], -1)
            live = _device_visibility(begin, end, ts, txn_id)
            if pad_live is not None:
                live = live & pad_live
        return ColumnBatch(cols, live)


class ValuesSource(ops.Operator):
    def __init__(self, node: L.Values):
        self.node = node

    def batches(self) -> Iterator[ColumnBatch]:
        from galaxysql_tpu.chunk.batch import batch_from_pydict
        rows = self.node.rows
        if not self.node.schema:
            # SELECT without FROM: one anonymous row
            yield batch_from_pydict({"__one": [1] * max(len(rows), 1)},
                                    {"__one": dt.BIGINT})
            return
        data = {fid: [r[i] for r in rows] for i, (fid, _, _) in
                enumerate(self.node.schema)}
        schema = {fid: typ for fid, typ, _ in self.node.schema}
        dicts = {fid: d for fid, typ, d in self.node.schema if d is not None}
        yield batch_from_pydict(data, schema, dicts)


class StatsOp(ops.Operator):
    """EXPLAIN ANALYZE / profiling instrumentation: per-operator batches/rows/
    wall time (RuntimeStatistics analog).  Only wrapped when ctx.collect_stats
    is set — num_live() forces a device sync per batch, so the normal path
    never pays."""

    def __init__(self, inner: ops.Operator, node: L.RelNode, ctx: ExecContext):
        self.inner = inner
        self.node = node
        self.ctx = ctx

    def batches(self):
        import time as _t
        t0 = _t.perf_counter()
        rows = 0
        nb = 0
        for b in self.inner.batches():
            nb += 1
            rows += b.num_live()
            yield b
        self.ctx.op_stats.append(
            {"node_id": id(self.node), "operator": type(self.node).__name__,
             "batches": nb, "rows_out": rows,
             "wall_ms": round((_t.perf_counter() - t0) * 1000, 3)})


class SegmentStatsOp(ops.Operator):
    """Per-operator stats INSIDE a fused segment: drains the segment's stats
    sink (per-stage live counts per dispatch, from the stats program variant)
    and attributes stage i's rows back to chain node i.  Wall time is the
    whole segment's — stages share one program, so per-stage wall does not
    exist; each chain row carries the shared value, flagged `fused`.

    The sink's leading count is the segment INPUT; runtime-filter prelude
    stages (`rf_node` = the scan they mask) report rows pruned per filter to
    the execution's RuntimeFilterManager — the EXPLAIN ANALYZE
    `RuntimeFilter(col, kinds, pruned=…)` lines and the `rf_rows_pruned`
    counter."""

    def __init__(self, inner: ops.Operator, segment, nodes: List[L.RelNode],
                 ctx: ExecContext, rf_node: Optional[L.RelNode] = None):
        self.inner = inner
        self.segment = segment
        self.nodes = nodes
        self.ctx = ctx
        self.rf_node = rf_node
        segment.stats_sink = []

    def batches(self):
        yield from self.inner.batches()
        sink = self.segment.stats_sink
        if not sink:
            return
        totals = np.sum([c for c, _ in sink], axis=0)
        wall = round(sum(w for _, w in sink), 3)
        record_rf_stats(self.ctx, self.segment, self.rf_node, totals)
        off = 1 + self.segment.rf_stage_count  # input count + rf preludes
        for i, n in enumerate(self.nodes):
            self.ctx.op_stats.append(
                {"node_id": id(n), "operator": type(n).__name__,
                 "batches": len(sink), "rows_out": int(totals[off + i]),
                 "wall_ms": wall, "fused": True,
                 "segment": self.segment.chain})


def record_rf_stats(ctx, segment, rf_node, totals):
    """Attribute per-rf-stage pruned rows (stats-sink deltas) to the manager.
    totals[0] is the segment input count; rf stages are a prefix."""
    refs = getattr(segment, "rf_refs", None)
    if not refs:
        return
    mgr = getattr(ctx, "rf", None)
    if mgr is None:
        return
    for j, ref in enumerate(refs):
        pruned = int(totals[j]) - int(totals[j + 1])
        mgr.note_pruned(ref.target, pruned,
                        node_id=id(rf_node) if rf_node is not None else None)


class TraceOp(ops.Operator):
    """Span-tracing wrapper: one `operator` span per plan node, parented at
    BUILD time (the plan tree is the span tree), timed at DRAIN time.  While a
    batch is being pulled from the wrapped operator the context's cursor
    points at this span, so leaf recorders that fire inside the pull — fused
    segment dispatches, compile events, device-cache transfers, worker RPCs —
    attach under the operator doing the work.  Row counts are deliberately
    NOT collected here (that is profiling's job and costs a device sync);
    tracing measures only where wall time went."""

    def __init__(self, inner: ops.Operator, span, tc):
        self.inner = inner
        self.span = span
        self.tc = tc

    def batches(self):
        import time as _t
        from galaxysql_tpu.utils import tracing as _tr
        tc, sp = self.tc, self.span
        sp.start_us = _tr.now_us()
        t0 = _t.perf_counter()
        batches = 0
        it = self.inner.batches()
        # a profiler session records: each pull is also an `op:` annotation
        # (nested like the operators), so a device idle gap names its operator
        op_name = "op:" + sp.name if tc.annotate else None
        while True:
            prev = tc.cursor
            tc.cursor = sp.span_id
            try:
                with tc.annotation(op_name) if op_name else _tr.NO_ANNOTATION:
                    b = next(it)
            except StopIteration:
                break
            finally:
                tc.cursor = prev
            batches += 1
            # finalize-per-pull: a downstream LIMIT may drop the generator
            # without exhausting it, and the span must still carry real time
            sp.dur_us = round((_t.perf_counter() - t0) * 1e6, 1)
            sp.attrs["batches"] = batches
            yield b
        sp.dur_us = round((_t.perf_counter() - t0) * 1e6, 1)
        sp.attrs["batches"] = batches


def build_operator(node: L.RelNode, ctx: ExecContext) -> ops.Operator:
    from galaxysql_tpu.utils import tracing
    tc = tracing.current()
    if tc is None:
        op = _build_operator(node, ctx)
        if getattr(ctx, "collect_stats", False) and \
                not isinstance(op, SegmentStatsOp):
            return StatsOp(op, node, ctx)
        return op
    # traced build: mint this node's span under the parent operator's (the
    # recursion below threads the cursor through ctx), then wrap the drain
    parent = getattr(ctx, "_trace_parent", None)
    sp = tc.add(type(node).__name__, kind="operator",
                parent=tc.cursor if parent is None else parent)
    ctx._trace_parent = sp.span_id
    try:
        op = _build_operator(node, ctx)
    finally:
        ctx._trace_parent = parent
    if getattr(ctx, "collect_stats", False) and \
            not isinstance(op, SegmentStatsOp):
        op = StatsOp(op, node, ctx)
    return TraceOp(op, sp, tc)


def _fusing(ctx: ExecContext) -> bool:
    # kernel-prelude fusion (chains folded INTO the HashAgg partial / join
    # probe programs) has no per-stage observation point, so profiling keeps
    # those chains as standalone operators; standalone SEGMENT fusion stays on
    # under collect_stats — the stats program variant reports per-stage rows,
    # so EXPLAIN ANALYZE describes the fused shape users actually run
    return ctx.enable_fusion and not getattr(ctx, "collect_stats", False)


def _wrap_scan_rf(src: ops.Operator, node: L.Scan,
                  ctx: ExecContext) -> ops.Operator:
    """Scan-level runtime-filter fallback: when no downstream fused segment
    consumed the scan's planned filters (bare join-probe scans, fusion off,
    profiling), apply them here as an rf-only FusedSegment — still one
    on-device program per batch, value-independent cache keys."""
    rf = getattr(ctx, "rf", None)
    seg = rf.segment_for_scan(node) if rf is not None else None
    if seg is None:
        return src
    ctx.trace.append(f"rf-scan {node.table.name} filters={len(seg.stages)}")
    if getattr(ctx, "collect_stats", False):
        # inner StatsOp keeps the scan's own (pre-filter) actual rows; the
        # SegmentStatsOp wrapper reports per-filter pruned counts
        return SegmentStatsOp(
            fusion.FusedPipelineOp(StatsOp(src, node, ctx), seg, ctx),
            seg, [],
            ctx, rf_node=node)
    return fusion.FusedPipelineOp(src, seg, ctx)


def _build_operator(node: L.RelNode, ctx: ExecContext) -> ops.Operator:
    if isinstance(node, L.Scan):
        return _wrap_scan_rf(ScanSource(node, ctx), node, ctx)
    if isinstance(node, L.Values):
        return ValuesSource(node)
    if isinstance(node, (L.Filter, L.Project)):
        if ctx.enable_fusion:
            # profiling fuses even single-stage chains: in production those
            # fold INTO the downstream kernel (agg prelude / join probe), so
            # running them as an instrumented one-stage segment keeps the
            # ANALYZE shape honest (fused tag + per-stage rows) while the
            # kernel-prelude path is held off (no observation point there)
            collecting = getattr(ctx, "collect_stats", False)
            base, seg = fusion.segment_for(node,
                                           min_stages=1 if collecting else 2,
                                           rf=getattr(ctx, "rf", None))
            if seg is not None:
                ctx.trace.append(f"fuse-segment {seg.chain}")
                inner = fusion.FusedPipelineOp(build_operator(base, ctx), seg,
                                               ctx)
                if collecting:
                    return SegmentStatsOp(
                        inner, seg, fusion.chain_nodes(node), ctx,
                        rf_node=base if isinstance(base, L.Scan) else None)
                return inner
        if isinstance(node, L.Filter):
            return ops.FilterOp(build_operator(node.child, ctx), node.cond)
        return ops.ProjectOp(build_operator(node.child, ctx), node.exprs)
    if isinstance(node, L.Aggregate):
        est = estimate_rows(node)
        max_groups = 1 << max(int(est * 2).bit_length(), 10)
        max_groups = min(max_groups, 1 << 22)
        calls = [ops.AggCall(a.kind, a.arg, a.out_id) for a in node.aggs]
        child_node, prelude = node.child, None
        if _fusing(ctx):
            # the agg is itself a pipeline breaker: its feeding chain fuses
            # INTO the partial kernel (scan→filter→project→partial-agg, one
            # program), not into a separate segment in front of it — the
            # base scan's runtime filters ride along as rf prelude stages
            base, prelude = fusion.segment_for(node.child,
                                               rf=getattr(ctx, "rf", None))
            if prelude is not None:
                child_node = base
                ctx.trace.append(f"fuse-agg-prelude {prelude.chain}")
        agg = ops.HashAggOp(build_operator(child_node, ctx),
                            node.groups, calls, max_groups=max_groups,
                            spill_threshold=ctx.agg_spill_bytes,
                            prelude=prelude, mem_pool=ctx.mem_pool)
        # the aggregate is a pipeline breaker with a DETERMINISTIC, usually
        # tiny output: fragment-cache it (version-keyed, same rules as join
        # builds), so a warm repeated query replays grouped rows instead of
        # re-streaming the fact side.  Profiling runs bypass — EXPLAIN
        # ANALYZE must measure the real pipeline, not a cache replay.
        if not getattr(ctx, "collect_stats", False):
            from galaxysql_tpu.exec import fragment_cache as fc
            fkey = fc.fingerprint(node, ctx)
            if fkey is not None:
                return fc.CachedSubplanOp(agg, ctx.frag, fkey,
                                          trace=ctx.trace)
        return agg
    if isinstance(node, L.Window):
        return ops.WindowOp(build_operator(node.child, ctx), node.partitions,
                            node.orders, node.calls, out_schema=node.fields())
    if isinstance(node, L.Join):
        return _build_join(node, ctx)
    if isinstance(node, L.Sort):
        return ops.SortOp(build_operator(node.child, ctx), node.keys,
                          node.limit, node.offset,
                          spill_threshold=ctx.sort_spill_bytes,
                          mem_pool=ctx.mem_pool)
    if isinstance(node, L.Limit):
        return ops.LimitOp(build_operator(node.child, ctx), node.limit, node.offset)
    if isinstance(node, L.Union):
        children = [build_operator(c, ctx) for c in node.children]
        # align column ids across inputs: rename every child to the first child's ids
        first_ids = node.children[0].field_ids()
        target_dicts = {fid: d for fid, _t, d in node.children[0].fields()}

        class UnionOp(ops.Operator):
            def __init__(self, children, id_lists):
                self.children_ops = children
                self.id_lists = id_lists

            def batches(self):
                for op, ids in zip(self.children_ops, self.id_lists):
                    rename = dict(zip(ids, first_ids))
                    for b in op.batches():
                        yield self._align(b.rename(rename))

            def _align(self, b):
                """Translate string codes into the first child's dictionary —
                children from different tables encode against different dicts,
                and concatenating raw codes would silently decode wrong values."""
                from galaxysql_tpu.chunk.batch import dictionary_union_translation
                cols = {}
                for fid, c in b.columns.items():
                    tgt = target_dicts.get(fid)
                    if c.dictionary is None or tgt is None or c.dictionary is tgt:
                        cols[fid] = c
                        continue
                    trans = dictionary_union_translation(tgt, c.dictionary)
                    cols[fid] = Column(trans[np.asarray(c.data)], c.valid,
                                       c.dtype, tgt)
                return ColumnBatch(cols, b.live)

        u = UnionOp(children, [c.field_ids() for c in node.children])
        if node.all:
            return u
        return ops.DistinctOp(u, [(fid, ir.ColRef(fid, typ, d))
                                  for fid, typ, d in node.fields()])
    raise errors.NotSupportedError(f"no physical operator for {type(node).__name__}")


def annotate_explain(rel: L.RelNode, op_stats: List[dict],
                     rf=None, skew_stats=None) -> List[str]:
    """EXPLAIN ANALYZE tree rendering: the logical plan's explain lines with
    each node annotated with its measured rows/batches/wall time (matched by
    node identity).  Operators that executed inside a fused segment carry a
    `fused(<chain>)` tag — their wall time is the whole segment's program.

    `rf` (the execution's RuntimeFilterManager) adds one indented
    `RuntimeFilter(column, kinds, pruned=…)` line under each scan a planned
    runtime filter masked.  `skew_stats` (ExecContext.skew_stats) adds one
    `HotKeys(n, broadcast)` / `Salted(f)` line under each join/aggregate the
    skew-aware executor split.

    Rendering rides the existing `explain_lines` (plain EXPLAIN and ANALYZE
    must draw the same tree): `explain_lines` emits one line per node in
    pre-order, which is exactly `L.walk`'s order, so lines and nodes zip."""
    by_id: Dict[int, dict] = {}
    for st in op_stats:
        nid = st.get("node_id")
        if nid is None:
            continue
        # fused/cached entries win: they mark chain membership (or a fragment
        # cache hit) the plain StatsOp wrapper covering the same node can't see
        if nid not in by_id or st.get("fused") or st.get("cached"):
            by_id[nid] = st
    rf_by_node: Dict[int, List[dict]] = {}
    if rf is not None:
        for st in rf.stats.values():
            rf_by_node.setdefault(st.get("node_id"), []).append(st)
    lines: List[str] = []
    for line, n in zip(rel.explain_lines(), L.walk(rel)):
        st = by_id.get(id(n))
        if st is not None:
            tag = f" fused({st['segment']})" if st.get("fused") else ""
            if st.get("cached"):
                tag += " [cached build]"
            line += (f"  (actual rows={st['rows_out']} "
                     f"batches={st['batches']} wall={st['wall_ms']}ms{tag})")
        lines.append(line)
        indent = " " * (len(line) - len(line.lstrip()) + 2)
        for rst in rf_by_node.get(id(n), []):
            lines.append(f"{indent}RuntimeFilter({rst['column']}, "
                         f"{rst['kinds']}, pruned={rst['pruned']})")
        info = (skew_stats or {}).get(id(n))
        if info is not None:
            from galaxysql_tpu.exec import skew as _skew
            lines.append(f"{indent}{_skew.explain_line(info)}")
    return lines


def _probe_prelude(ctx: ExecContext, probe_node: L.RelNode):
    """(base node, filter-only FusedSegment | None) for an inner join's probe
    side: the WHERE chain above the probe scan fuses INTO the probe kernels
    (one program per batch instead of filter + probe).  Project stages change
    the column namespace the join gathers from, so only all-filter chains
    collapse here; anything else stays a segment in front of the join."""
    if not _fusing(ctx):
        return probe_node, None
    base, seg = fusion.segment_for(probe_node, filters_only=True)
    if seg is not None:
        ctx.trace.append(f"fuse-join-probe {seg.chain}")
    return base, seg


def _rf_publish_specs(node: L.Join, ctx: ExecContext, probe_side: str):
    """Planned runtime-filter producer specs ACTIVE for this execution
    (side-flip/deactivation logic shared with MPP: runtime_filter.specs_for)."""
    from galaxysql_tpu.exec.runtime_filter import specs_for
    rf = getattr(ctx, "rf", None)
    specs = specs_for(node, probe_side, rf)
    if not specs:
        return None, []
    ctx.trace.append(f"rf-publish join filters={len(specs)}")
    return rf, specs


def _frag_build_wiring(build_node: L.RelNode, ctx: ExecContext):
    """Fragment-cache wiring for a join build side: (fingerprint, cache,
    subplan-wrapper, hit-note callback).  The note lands the hit in the trace
    and — under EXPLAIN ANALYZE / profiling — as a `[cached build]` op stat
    on the build node, whose subtree never executed."""
    from galaxysql_tpu.exec import fragment_cache as fc
    fkey = fc.fingerprint(build_node, ctx)
    if fkey is None:
        return None, None, None

    def note(art, _node=build_node):
        ctx.trace.append(
            f"frag-cache build hit [{','.join(sorted(fkey.tables))}] "
            f"rows={art.rows}")
        if getattr(ctx, "collect_stats", False):
            ctx.op_stats.append(
                {"node_id": id(_node), "operator": type(_node).__name__,
                 "batches": 0, "rows_out": art.rows, "wall_ms": 0.0,
                 "cached": True})
    return fkey, ctx.frag, note


def _build_side_op(build_node: L.RelNode, ctx: ExecContext, fkey, cache):
    op = build_operator(build_node, ctx)
    # the subplan lane deliberately duplicates rows the join_build artifact
    # also holds (caps bound it): it is keyed by the subtree ALONE, so other
    # joins with different key/filter shapes — and executions after an
    # artifact eviction — still skip the subtree.  Profiling bypasses, same
    # stance as the aggregate replay: a subplan hit under EXPLAIN ANALYZE
    # would hide the build operators without any [cached build] mark.
    if fkey is not None and not getattr(ctx, "collect_stats", False):
        from galaxysql_tpu.exec import fragment_cache as fc
        op = fc.CachedSubplanOp(op, cache, fkey, trace=ctx.trace)
    return op


def _skew_watch(build_node: L.RelNode, build_keys, ctx: ExecContext):
    """Heavy-hitter runtime-refresh targets for a join build side: one
    (TableMeta, column, field id) per build key that is a bare scan column —
    the materialized build pass folds the key lane into the column's runtime
    sketch (meta/statistics.observe_build_keys), keeping skew detection fresh
    between ANALYZE runs at zero extra device syncs."""
    if not getattr(ctx, "skew_modes", None):
        return []
    from galaxysql_tpu.plan.rules import _rf_resolve_scan
    out = []
    for e in build_keys:
        if not isinstance(e, ir.ColRef):
            continue
        got = _rf_resolve_scan(build_node, e.name)
        if got is None:
            continue
        scan, out_id = got
        if getattr(scan.table, "remote", None) is not None:
            continue
        colname = dict(scan.columns).get(out_id)
        if colname is not None:
            out.append((scan.table, scan.table.column(colname).name, e.name))
    return out


def _build_join(node: L.Join, ctx: ExecContext) -> ops.Operator:
    if node.kind == "cross":
        left = build_operator(node.left, ctx)
        right = build_operator(node.right, ctx)
        bschema = {fid: (typ, d) for fid, typ, d in node.right.fields()}
        return ops.CrossJoinOp(right, left, scalar=getattr(node, "scalar", False),
                               build_schema=bschema)
    lkeys = [a for a, _ in node.equi]
    rkeys = [b for _, b in node.equi]
    bloom = not ctx.hints.get("no_bloom", False)
    if node.kind in ("left", "semi", "anti"):
        # probe side MUST be the preserved/output (left) side
        rf_mgr, rf_specs = _rf_publish_specs(node, ctx, "left") \
            if node.kind == "semi" else (None, [])
        right_schema = {fid: (typ, d) for fid, typ, d in node.right.fields()}
        fkey, cache, note = _frag_build_wiring(node.right, ctx)
        return ops.HashJoinOp(_build_side_op(node.right, ctx, fkey, cache),
                              build_operator(node.left, ctx),
                              rkeys, lkeys, node.kind,
                              residual=node.residual, build_schema=right_schema,
                              enable_bloom=bloom,
                              spill_threshold=ctx.join_spill_bytes,
                              rf_publish=rf_specs, rf_manager=rf_mgr,
                              frag_cache=cache, frag_key=fkey, frag_note=note,
                              skew_watch=_skew_watch(node.right, rkeys, ctx),
                              mem_pool=ctx.mem_pool,
                              output=node.required)
    # inner: build the smaller estimated side
    l_est = estimate_rows(node.left)
    r_est = estimate_rows(node.right)
    if r_est <= l_est:
        build_node, probe_node = node.right, node.left
        build_keys, probe_keys = rkeys, lkeys
        probe_side = "left"
    else:
        build_node, probe_node = node.left, node.right
        build_keys, probe_keys = lkeys, rkeys
        probe_side = "right"
    rf_mgr, rf_specs = _rf_publish_specs(node, ctx, probe_side)
    build_schema = {fid: (typ, d) for fid, typ, d in build_node.fields()}
    probe_node, prelude = _probe_prelude(ctx, probe_node)
    fkey, cache, note = _frag_build_wiring(build_node, ctx)
    return ops.HashJoinOp(_build_side_op(build_node, ctx, fkey, cache),
                          build_operator(probe_node, ctx),
                          build_keys, probe_keys, "inner",
                          residual=node.residual, build_schema=build_schema,
                          enable_bloom=bloom,
                          spill_threshold=ctx.join_spill_bytes,
                          probe_prelude=prelude,
                          rf_publish=rf_specs, rf_manager=rf_mgr,
                          frag_cache=cache, frag_key=fkey, frag_note=note,
                          skew_watch=_skew_watch(build_node, build_keys, ctx),
                          mem_pool=ctx.mem_pool,
                          output=node.required)
